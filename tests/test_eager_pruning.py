"""Eager in-kernel pruning: fused extend_pruned vs the composed
extend -> filter -> compact trio (property-based), PackedGraph bitmap
semantics, survivor-scale planning, and plan-cache versioning/eviction."""
import os
import time

import jax.numpy as jnp
import numpy as np
import pytest

from _hyp import given, settings, strategies as st
from repro.core import Miner, MiningPlan, PlanCache, make_cf_app, \
    make_mc_app, make_tc_app
from repro.core.api import make_ctx, resolve_kernel_predicate
from repro.core.embedding_list import init_level0_vertex, materialize
from repro.core.phases import available_backends, get_backend
from repro.core.phases.reference import (_vertex_candidates,
                                         finish_extend_vertex)
from repro.core.plan import PLAN_SCHEMA, bucket_cap
from repro.graph import generators as G
from repro.graph.csr import pack_adjacency, packed_contains
from repro.sparse.intersect import adj_contains

APPS = [("tc", make_tc_app),
        ("3-cf-nodag", lambda: make_cf_app(3, use_dag=False)),
        ("4-cf", lambda: make_cf_app(4)),
        ("3-mc", lambda: make_mc_app(3)),
        ("4-mc", lambda: make_mc_app(4))]


# -- PackedGraph -------------------------------------------------------------

def test_packed_contains_matches_binary_search():
    g = G.erdos_renyi(60, 0.15, seed=3)
    pg = pack_adjacency(g)
    assert pg.full and pg.n_packed == g.n_vertices
    rng = np.random.default_rng(0)
    # in-contract inputs: valid vertex ids plus negative padding (-1)
    u = jnp.asarray(rng.integers(-2, 60, 4000), jnp.int32)
    v = jnp.asarray(rng.integers(-2, 60, 4000), jnp.int32)
    ctx = make_ctx(g, pack_bits=False)
    ref = adj_contains(g.row_ptr, g.col_idx, u, v, ctx.n_steps)
    np.testing.assert_array_equal(np.asarray(ref),
                                  np.asarray(packed_contains(pg, u, v)))


def test_partial_pack_falls_back_to_csr():
    g = G.erdos_renyi(80, 0.1, seed=4)
    n_words = -(-g.n_vertices // 32)
    pg = pack_adjacency(g, max_bytes=10 * n_words * 4)  # 10 rows only
    assert not pg.full and pg.n_packed == 10
    # packed rows are the highest-degree vertices
    deg = np.asarray(g.degrees())
    packed_rows = np.flatnonzero(np.asarray(pg.row_slot) >= 0)
    assert deg[packed_rows].min() >= np.sort(deg)[-10:].min()
    ctx = make_ctx(g, pack_max_bytes=10 * n_words * 4, pack_partial=True)
    rng = np.random.default_rng(1)
    u = jnp.asarray(rng.integers(0, 80, 2000), jnp.int32)
    v = jnp.asarray(rng.integers(0, 80, 2000), jnp.int32)
    ref = adj_contains(g.row_ptr, g.col_idx, u, v, ctx.n_steps)
    np.testing.assert_array_equal(np.asarray(ref),
                                  np.asarray(ctx.is_connected(u, v)))


def test_mixed_partial_pack_in_pruned_kernel():
    """Power-law graph whose pack budget only covers the high-degree
    rows: the pruned pallas kernel must take the mixed path (bitmap for
    packed rows, CSR binary search for the tail) and still count exactly
    what the reference backend counts."""
    from repro.core import Pattern, pattern_app

    g = G.rmat(7, edge_factor=6, seed=3)           # 128 vertices, power-law
    n_words = -(-g.n_vertices // 32)
    budget = 20 * n_words * 4                      # ~20 hub rows only
    for make in (lambda: make_cf_app(4, use_dag=False),
                 lambda: pattern_app(Pattern.named("diamond"))):
        ref = Miner(g, make()).run().count
        m = Miner(g, make(), backend="pallas", pack_max_bytes=budget,
                  pack_partial=True)
        assert m.ctx.packed is not None and not m.ctx.packed.full
        assert m.ctx.packed.n_packed < g.n_vertices
        assert m.run().count == ref


def test_linear_search_ablation_skips_packing():
    g = G.erdos_renyi(20, 0.3, seed=1)
    assert make_ctx(g, search="linear").packed is None
    assert make_ctx(g, pack_bits=False).packed is None
    assert make_ctx(g).packed is not None
    # partial packs are opt-in: probing both bitmap and CSR fallback per
    # element is a pessimization without a packed-row-aware consumer
    n_words = -(-g.n_vertices // 32)
    assert make_ctx(g, pack_max_bytes=4 * n_words * 4).packed is None
    partial = make_ctx(g, pack_max_bytes=4 * n_words * 4,
                       pack_partial=True).packed
    assert partial is not None and not partial.full


# -- fused extend_pruned == extend -> filter -> compact (property-based) -----

def _level1_inputs(g, app, backend):
    m = Miner(g, app, backend=backend)
    src, dst = m.init_edges()
    n = int(src.shape[0])
    levels = init_level0_vertex(src, dst, n)
    emb = materialize(levels)
    state = (app.init_state(m.ctx, emb, jnp.int32(n))
             if app.init_state is not None
             else jnp.zeros(emb.shape[:1], jnp.int32))
    return m, emb, jnp.int32(n), state


def _composed_trio(ctx, app, emb, n, state, cand_cap, out_cap):
    """The pre-fusion pipeline: materialize all candidates, then filter,
    then compact — composed from the reference ops."""
    row, u, _, add, total = _vertex_candidates(ctx, app, emb, n, state,
                                               cand_cap)
    level, new_emb = finish_extend_vertex(emb, row, u, add, out_cap,
                                          fuse_filter=False)
    return level, new_emb, total


@given(seed=st.integers(0, 1000), n=st.integers(10, 36),
       p=st.sampled_from([0.15, 0.25, 0.4]),
       app_idx=st.integers(0, len(APPS) - 1),
       backend=st.sampled_from(["reference", "pallas"]))
@settings(max_examples=12, deadline=None)
def test_extend_pruned_equals_composed_trio(seed, n, p, app_idx, backend):
    g = G.erdos_renyi(n, p, seed=seed)
    if g.n_edges == 0:
        return
    app = APPS[app_idx][1]()
    m, emb, nv, state = _level1_inputs(g, app, backend)
    be = m.backend
    cand_cap, out_cap = 2048, 512
    level, new_emb, n_cand, _ = be.extend_pruned(m.ctx, app, emb, nv,
                                                 state, cand_cap, out_cap)
    ref_level, ref_emb, ref_cand = _composed_trio(m.ctx, app, emb, nv,
                                                  state, cand_cap, out_cap)
    assert int(n_cand) == int(ref_cand)
    assert int(level.n) == int(ref_level.n)
    np.testing.assert_array_equal(np.asarray(level.vid),
                                  np.asarray(ref_level.vid))
    np.testing.assert_array_equal(np.asarray(level.idx),
                                  np.asarray(ref_level.idx))
    live = np.asarray(level.vid) >= 0
    np.testing.assert_array_equal(np.asarray(new_emb)[live],
                                  np.asarray(ref_emb)[live])


def test_every_registered_backend_serves_extend_pruned(er_graph):
    for name in available_backends():
        be = get_backend(name)
        app = make_tc_app()
        m, emb, nv, state = _level1_inputs(er_graph, app, name)
        level, _, n_cand, _ = be.extend_pruned(m.ctx, app, emb, nv, state,
                                               1024, 256)
        assert int(n_cand) > 0 and int(level.n) > 0


def test_to_add_kernel_only_app_mines_consistently(er_graph):
    """An app supplying ONLY to_add_kernel (the documented fast path)
    must plan and mine with that predicate on both backends — inspection
    and extension resolve the same predicate, so survivor-scale caps
    never trip the hook-drift guard."""
    import dataclasses
    app = dataclasses.replace(make_cf_app(3, use_dag=False),
                              to_add=None, to_add_bits=None)
    assert app.to_add_kernel is not None
    r = Miner(er_graph, app).run().count
    p = Miner(er_graph, app, backend="pallas").run().count
    assert r == p


def test_kernel_predicate_resolution():
    assert resolve_kernel_predicate(make_cf_app(4)) is not None
    # hook-less apps get the default canonical test as a plain callable
    assert resolve_kernel_predicate(make_mc_app(3, mode="memo")) is not None
    # the multi-pattern trie emits per-level predicates: level required
    assert resolve_kernel_predicate(make_mc_app(3), 2) is not None
    with pytest.raises(ValueError, match="per-level"):
        resolve_kernel_predicate(make_mc_app(3))
    import dataclasses
    dag_no_hooks = dataclasses.replace(make_cf_app(3), to_add=None,
                                       to_add_bits=None, to_add_kernel=None)
    assert resolve_kernel_predicate(dag_no_hooks) is None


# -- survivor-scale planning -------------------------------------------------

def test_bucket_cap_is_tighter_than_pow2():
    from repro.core.plan import bucket_pow2
    assert bucket_cap(1500) == 1536 < bucket_pow2(1500) == 2048
    assert bucket_cap(5) == 128                       # floor
    assert bucket_cap(128) == 128 and bucket_cap(129) == 256


def test_planned_out_caps_are_survivor_scale(er_graph):
    """Recorded plans size outputs by exact survivor counts (tight
    128-quantum), not pow2 candidate-scale buckets."""
    m = Miner(er_graph, make_mc_app(3))
    r = m.run()
    (rep,) = m.plan_reports()
    (cand_cap, out_cap), = rep["caps"]
    n_emb = r.count
    assert out_cap == bucket_cap(n_emb)               # tight survivor scale
    assert out_cap <= cand_cap


# -- plan cache: versioning + LRU eviction ------------------------------------

def test_stale_schema_plan_ignored_and_removed(tmp_path):
    cache = PlanCache(str(tmp_path))
    plan = MiningPlan(kind="vertex", caps=((256, 128),), cap0=128,
                      signature="sig0", source="inspect")
    path = cache.put(plan)
    stale = plan.to_json().replace(f'"schema": {PLAN_SCHEMA}',
                                   '"schema": 1')
    with open(path, "w") as f:
        f.write(stale)
    assert cache.get("sig0") is None
    assert not os.path.exists(path)                   # stale entry dropped


def test_corrupt_plan_ignored(tmp_path):
    cache = PlanCache(str(tmp_path))
    with open(os.path.join(str(tmp_path), "bad.json"), "w") as f:
        f.write("{not json")
    assert cache.get("bad") is None


def test_plan_cache_lru_eviction(tmp_path):
    cache = PlanCache(str(tmp_path), max_entries=2)
    plans = [MiningPlan(kind="vertex", caps=((256, 128),), cap0=128,
                        signature=f"sig{i}", source="inspect")
             for i in range(3)]
    now = time.time()
    for i, p in enumerate(plans[:2]):
        path = cache.put(p)
        os.utime(path, (now - 100 + i, now - 100 + i))  # deterministic age
    cache.put(plans[2])                                  # evicts oldest
    assert cache.get("sig0") is None
    assert cache.get("sig1") is not None
    assert cache.get("sig2") is not None
    assert len([f for f in os.listdir(str(tmp_path))
                if f.endswith(".json")]) == 2


def test_plan_roundtrip_carries_current_schema():
    p = MiningPlan(kind="edge", caps=((256, 128),), filter_caps=(128,),
                   cap0=256, signature="s", source="inspect")
    import json
    assert json.loads(p.to_json())["schema"] == PLAN_SCHEMA
    assert MiningPlan.from_json(p.to_json()) == p


# -- packed sharded FSM bitmap ------------------------------------------------

def test_reduce_domain_sharded_packed_matches_dense():
    from repro.core import make_fsm_app
    from repro.core.engine import _EdgePipeline, _PhaseOps, run_level_loop
    from repro.core.phases.reference import (reduce_domain,
                                             reduce_domain_sharded)
    from repro.core.plan import HostCapPolicy

    g = G.erdos_renyi(14, 0.3, seed=5, labels=3)
    app = make_fsm_app(3, min_support=2, max_patterns=64)
    m = Miner(g, app)
    ops = _PhaseOps(m.ctx, app, get_backend("reference"))
    pipe = _EdgePipeline(ops)
    run_level_loop(pipe, HostCapPolicy())
    ref = reduce_domain(m.ctx, app, pipe.levels)
    packed = reduce_domain_sharded(m.ctx, app, pipe.levels, (), packed=True)
    dense = reduce_domain_sharded(m.ctx, app, pipe.levels, (), packed=False)
    for a, b, c in zip(ref, packed, dense):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c))


# -- launch CLI knobs ----------------------------------------------------------

def test_mine_cli_plan_cache_max(tmp_path, capsys):
    from repro.launch.mine import main
    main(["--app", "tc", "--graph", "er:30,0.2", "--plan-cache",
          str(tmp_path), "--plan-cache-max", "4", "--repeat", "2"])
    out = capsys.readouterr().out
    assert "out_cap_total=" in out
    assert any(f.endswith(".json") for f in os.listdir(str(tmp_path)))
