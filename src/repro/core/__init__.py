# Pangolin core: the paper's extend-reduce-filter mining engine in JAX.
from repro.core.api import GraphCtx, MiningApp, make_ctx
from repro.core.engine import (Miner, MineResult, bounded_mine_edge,
                               bounded_mine_vertex, mine_sharded,
                               run_level_loop, sharded_inputs,
                               sharded_program, sharded_results)
from repro.core.plan import (HostCapPolicy, MiningExecutor, MiningPlan,
                             PlanCache, PlanCapPolicy, estimate_plan,
                             plan_program, plan_signature, profile_distance,
                             transfer_caps)
from repro.core.phases import (PhaseBackend, available_backends, get_backend,
                               register_backend)
from repro.core.apps import (make_tc_app, make_cf_app, make_cf_app_compiled,
                             make_mc_app, make_mc_set_app, make_fsm_app,
                             pattern_app, pattern_set_app,
                             triangle_count_fused)
from repro.core.patterns import (GraphStats, Pattern, compile_pattern,
                                 compile_pattern_set, graph_stats,
                                 motif_patterns, n_connected_patterns,
                                 named_pattern_set, pattern_names,
                                 pattern_set_names)
