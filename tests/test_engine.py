"""Engine mechanics: SoA lists, inspection-execution, blocking, bounded
mode, materialization ablation, stats."""
import jax.numpy as jnp
import numpy as np

from oracles import motif_counts, triangle_count
from repro.core import (Miner, bounded_mine_vertex, make_cf_app, make_mc_app,
                        make_tc_app)
from repro.core.embedding_list import (init_level0_vertex, materialize,
                                       total_bytes)


def test_materialize_backtracks():
    src = jnp.asarray([0, 0, 1], jnp.int32)
    dst = jnp.asarray([1, 2, 2], jnp.int32)
    levels = init_level0_vertex(src, dst, 3)
    emb = materialize(levels)
    assert np.asarray(emb).tolist() == [[0, 1], [0, 2], [1, 2]]


def test_soa_levels_and_bytes(er_graph):
    m = Miner(er_graph, make_cf_app(4))
    r = m.run(collect_stats=True)
    assert r.levels is not None and len(r.levels) == 3
    assert total_bytes(r.levels) > 0
    # level stats are monotone in level index
    assert [s.level for s in r.stats] == [2, 3]
    # prefix-tree integrity: every idx points into the previous level
    for prev, cur in zip(r.levels, r.levels[1:]):
        n = int(cur.n)
        idx = np.asarray(cur.idx)[:n]
        assert (idx >= 0).all() and (idx < prev.capacity).all()


def test_edge_blocking_equivalence(er_graph, er_nx):
    ref = triangle_count(er_nx)
    for bs in (16, 37, 64):
        assert Miner(er_graph, make_tc_app()).run(block_size=bs).count == ref


def test_edge_blocking_motifs(er_graph, er_nx):
    ref = motif_counts(er_nx, 3)
    r = Miner(er_graph, make_mc_app(3)).run(block_size=50)
    assert r.p_map[0] == ref[0] and r.p_map[1] == ref[1]


def test_materialization_ablation(er_graph, er_nx):
    """fuse_filter=False (Arabesque-style materialize-then-filter) must be
    numerically identical, only slower (Fig. 12d)."""
    ref = triangle_count(er_nx)
    m = Miner(er_graph, make_tc_app(), fuse_filter=False)
    assert m.run().count == ref


def test_linear_search_mode(er_graph, er_nx):
    m = Miner(er_graph, make_tc_app(), search="linear")
    assert m.run().count == triangle_count(er_nx)


def test_bounded_mode_overflow_flag(er_graph):
    app = make_tc_app()
    m = Miner(er_graph, app)
    src, dst = m.init_edges()
    n = int(src.shape[0])
    # generous caps: no overflow, count matches
    cnt, _, ovf = bounded_mine_vertex(m.ctx, app, src, dst, n,
                                      ((4096, 2048),))
    ref = Miner(er_graph, app).run().count
    assert int(cnt) == ref and not bool(ovf)
    # tiny caps: overflow reported
    cnt2, _, ovf2 = bounded_mine_vertex(m.ctx, app, src, dst, n, ((8, 4),))
    assert bool(ovf2)


def test_checkpoint_callback(er_graph):
    seen = []
    Miner(er_graph, make_cf_app(4)).run(
        checkpoint_cb=lambda level, levels, p_map: seen.append(level))
    assert seen == [2, 3]


def test_miner_reuse_no_retrace(er_graph, er_nx):
    """Second run reuses jitted closures (same counts, much faster)."""
    m = Miner(er_graph, make_tc_app())
    ref = triangle_count(er_nx)
    assert m.run().count == ref
    assert m.run().count == ref


def test_graph_ctx_is_a_pytree_passed_to_compiled_runs(er_graph):
    """GraphCtx round-trips through jax.tree_util (arrays are leaves, the
    ints static), and the compiled executor — which takes it as an
    argument — counts exactly what the host run counted."""
    import jax

    from repro.core.api import GraphCtx

    m = Miner(er_graph, make_mc_app(3))
    leaves, treedef = jax.tree_util.tree_flatten(m.ctx)
    assert all(isinstance(x, jax.Array) for x in leaves)
    back = jax.tree_util.tree_unflatten(treedef, leaves)
    assert isinstance(back, GraphCtx)
    assert (back.n_vertices, back.max_degree, back.n_steps) == \
        (m.ctx.n_vertices, m.ctx.max_degree, m.ctx.n_steps)
    host = m.run()                           # host inspection pass
    m.ctx = back                             # same graph, rebuilt pytree
    warm = m.run()                           # jitted executor, ctx an arg
    assert m.plan_reports()[0]["executions"] == 1
    assert warm.count == host.count
    np.testing.assert_array_equal(warm.p_map, host.p_map)
