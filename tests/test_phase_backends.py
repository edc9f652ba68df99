"""Phase-backend layer: registry semantics, reference/pallas parity on the
mining apps, fused-kernel unit checks, and ragged-primitive edge cases."""
import math

import jax.numpy as jnp
import numpy as np
import pytest

from _hyp import given, settings, strategies as st
from oracles import motif_counts, triangle_count
from repro.core import (Miner, available_backends, bounded_mine_vertex,
                        get_backend, make_cf_app, make_fsm_app, make_mc_app,
                        make_mc_set_app, make_tc_app)
from repro.core.phases import PhaseBackend, register_backend
from repro.core.phases.pallas import PallasExtendBackend
from repro.core.phases.pallas_mp import PallasMPBackend
from repro.core.phases.reference import ReferenceBackend
from repro.graph import generators as G
from repro.graph.csr import to_networkx
from repro.kernels.extend_fused import (fused_extend, fused_extend_pruned,
                                        fused_extend_pruned_mp,
                                        fused_extend_pruned_mp_ref,
                                        fused_extend_pruned_ref,
                                        fused_extend_ref)
from repro.sparse.ops import compact_mask, expand_ragged

KERNEL_BACKENDS = pytest.mark.parametrize(
    "kbackend", ["pallas", "pallas-mp"], ids=["pallas", "pallas_mp"])


# -- registry ----------------------------------------------------------------

def test_registry_contents():
    names = available_backends()
    assert "reference" in names and "pallas" in names
    assert "pallas-mp" in names
    assert isinstance(get_backend("reference"), ReferenceBackend)
    assert isinstance(get_backend("pallas"), PallasExtendBackend)
    assert isinstance(get_backend("pallas-mp"), PallasMPBackend)
    # pallas-mp shares the whole pallas pipeline except the compaction seam
    assert issubclass(PallasMPBackend, PallasExtendBackend)
    assert get_backend(None).name == "reference"


def test_registry_unknown_backend_raises():
    with pytest.raises(KeyError, match="unknown phase backend"):
        get_backend("cuda-someday")


def test_registry_instance_passthrough_and_custom():
    inst = PallasExtendBackend(interpret=True)
    assert get_backend(inst) is inst

    class NullBackend(PhaseBackend):
        name = "null"

    register_backend("null", NullBackend)
    try:
        assert isinstance(get_backend("null"), NullBackend)
    finally:
        from repro.core.phases import _INSTANCES, _REGISTRY
        _REGISTRY.pop("null", None)
        _INSTANCES.pop("null", None)


def test_app_level_backend_preference(er_graph):
    app = make_tc_app()
    import dataclasses
    app_p = dataclasses.replace(app, backend="pallas")
    m = Miner(er_graph, app_p)
    assert m.backend.name == "pallas"
    # Miner override wins over the app preference
    assert Miner(er_graph, app_p, backend="reference").backend.name == \
        "reference"


# -- backend parity on the mining apps --------------------------------------

@pytest.mark.parametrize("seed,n,p", [(0, 12, 0.4), (3, 20, 0.3),
                                      (7, 30, 0.2), (11, 25, 0.35)])
def test_parity_tc_random_graphs(seed, n, p):
    g = G.erdos_renyi(n, p, seed=seed)
    ref = triangle_count(to_networkx(g))
    assert Miner(g, make_tc_app()).run().count == ref
    assert Miner(g, make_tc_app(), backend="pallas").run().count == ref


@pytest.mark.parametrize("k", [3, 4, 5])
def test_parity_clique(er_graph, k):
    r = Miner(er_graph, make_cf_app(k)).run().count
    p = Miner(er_graph, make_cf_app(k), backend="pallas").run().count
    assert r == p


@pytest.mark.parametrize("use_dag,eager", [(True, True), (True, False),
                                           (False, True), (False, False)])
def test_parity_clique_ablation_modes(er_graph, use_dag, eager):
    app = make_cf_app(3, use_dag=use_dag, eager_prune=eager)
    r = Miner(er_graph, app).run().count
    p = Miner(er_graph, app, backend="pallas").run().count
    assert r == p


def test_parity_dag_app_without_add_hooks(er_graph):
    """use_dag app with neither to_add nor to_add_bits: the pallas backend
    must fall back to the CSR-probing canonical test (conn bits have the
    wrong isConnected direction on an oriented DAG)."""
    import dataclasses
    app = dataclasses.replace(make_cf_app(3), to_add=None, to_add_bits=None)
    assert app.use_dag
    r = Miner(er_graph, app).run().count
    p = Miner(er_graph, app, backend="pallas").run().count
    assert r == p


@pytest.mark.parametrize("k", [3, 4])
def test_parity_motifs(er_graph, er_nx, k):
    rm = np.asarray(Miner(er_graph, make_mc_app(k)).run().p_map)
    pm = np.asarray(
        Miner(er_graph, make_mc_app(k), backend="pallas").run().p_map)
    assert (rm == pm).all()
    ref = motif_counts(er_nx, k)
    assert all(int(pm[i]) == ref.get(i, 0) for i in ref)


@pytest.mark.parametrize("seed", [1, 5, 9])
def test_parity_motifs_random_graphs(seed):
    g = G.erdos_renyi(16, 0.3, seed=seed)
    rm = np.asarray(Miner(g, make_mc_app(4)).run().p_map)
    pm = np.asarray(Miner(g, make_mc_app(4), backend="pallas").run().p_map)
    assert (rm == pm).all()


def test_parity_bounded_mode(er_graph):
    app = make_tc_app()
    m = Miner(er_graph, app)
    src, dst = m.init_edges()
    n = int(src.shape[0])
    cnt_r, pm_r, ovf_r = bounded_mine_vertex(m.ctx, app, src, dst, n,
                                             ((4096, 2048),))
    cnt_p, pm_p, ovf_p = bounded_mine_vertex(m.ctx, app, src, dst, n,
                                             ((4096, 2048),),
                                             backend="pallas")
    assert int(cnt_r) == int(cnt_p) and not bool(ovf_p)
    assert (np.asarray(pm_r) == np.asarray(pm_p)).all()


def test_parity_edge_blocking(er_graph):
    ref = Miner(er_graph, make_tc_app()).run().count
    got = Miner(er_graph, make_tc_app(),
                backend="pallas").run(block_size=37).count
    assert got == ref


# -- fused extend_pruned: bitwise reference/pallas parity ---------------------

PRUNED_APPS = [("tc", make_tc_app), ("4-cf", lambda: make_cf_app(4)),
               ("3-cf-nodag", lambda: make_cf_app(3, use_dag=False)),
               ("3-mc", lambda: make_mc_app(3)),
               ("4-mc", lambda: make_mc_app(4))]


@KERNEL_BACKENDS
@pytest.mark.parametrize("aname,make_app", PRUNED_APPS)
@pytest.mark.parametrize("seed", [0, 7])
def test_extend_pruned_bitwise_parity(aname, make_app, seed, kbackend):
    """The fused op must return bit-identical levels, embeddings, and
    counts on every backend (the pallas kernels prune+compact in-kernel —
    sequential-SMEM or two-pass-scan; the reference backend composes the
    same predicate in XLA)."""
    import jax.numpy as jnp
    from repro.core.embedding_list import init_level0_vertex, materialize

    g = G.erdos_renyi(24, 0.3, seed=seed)
    app = make_app()
    results = []
    for backend in ("reference", kbackend):
        m = Miner(g, app, backend=backend)
        src, dst = m.init_edges()
        n = int(src.shape[0])
        emb = materialize(init_level0_vertex(src, dst, n))
        state = (app.init_state(m.ctx, emb, jnp.int32(n))
                 if app.init_state is not None
                 else jnp.zeros(emb.shape[:1], jnp.int32))
        level, new_emb, n_cand = m.backend.extend_pruned(
            m.ctx, app, emb, jnp.int32(n), state, 1024, 512)
        st = (None if level.state is None else np.asarray(level.state))
        results.append((np.asarray(level.vid), np.asarray(level.idx),
                        int(level.n), np.asarray(new_emb), int(n_cand),
                        st))
    (vid_r, idx_r, n_r, emb_r, c_r, st_r), \
        (vid_p, idx_p, n_p, emb_p, c_p, st_p) = results
    assert (n_r, c_r) == (n_p, c_p)
    np.testing.assert_array_equal(vid_r, vid_p)
    np.testing.assert_array_equal(idx_r, idx_p)
    live = vid_r >= 0
    np.testing.assert_array_equal(emb_r[live], emb_p[live])
    # the compacted state column (update_state_kernel apps) is part of
    # the bitwise contract too
    assert (st_r is None) == (st_p is None)
    if st_r is not None:
        np.testing.assert_array_equal(st_r, st_p)


def _diamond_labeled_app():
    from repro.core.apps.psm import pattern_app
    from repro.core.patterns import Pattern
    return pattern_app(Pattern.from_edges(
        [(0, 1), (1, 2), (2, 0), (2, 3)], labels=[0, 1, 0, 1]))


def _dag_app_without_add_hooks():
    import dataclasses
    return dataclasses.replace(make_cf_app(3), to_add=None, to_add_bits=None)


CHUNKED_APPS = PRUNED_APPS + [
    ("4-mc-set", lambda: make_mc_set_app(4)),
    ("diamond-labeled", _diamond_labeled_app),
    ("3-cf-no-add-hooks", _dag_app_without_add_hooks)]


@pytest.mark.parametrize("aname,make_app", CHUNKED_APPS)
@pytest.mark.parametrize("pack_bytes", [0, 4 << 20], ids=["csr", "bitmap"])
@pytest.mark.parametrize("caps", [(1024, 512), (40, 512), (1024, 7)],
                         ids=["roomy", "cand-overflow", "out-overflow"])
@pytest.mark.parametrize("chunk", [16, 1 << 16],
                         ids=["many-chunks", "one-chunk"])
def test_chunked_extend_matches_one_shot(aname, make_app, pack_bytes, caps,
                                         chunk, monkeypatch):
    """The reference backend's chunked extend (per-parent values forward-
    filled over each chunk of slots) returns the bit-identical level,
    embeddings and counts of the one-shot enumerate-filter-compact, on
    every level of the run, with chunks much smaller than the level or
    one chunk for all of it, and under candidate or survivor overflow."""
    import jax.numpy as jnp
    from repro.core.api import resolve_state_kernel
    from repro.core.embedding_list import init_level0_vertex, materialize
    from repro.core.phases import reference as R

    monkeypatch.setattr(R, "CHUNK_SLOTS", chunk)
    cand_cap, out_cap = caps
    g = G.erdos_renyi(24, 0.3, seed=3, labels=2)
    app = make_app()
    m = Miner(g, app, pack_max_bytes=pack_bytes)
    assert (m.ctx.packed is None) == (pack_bytes == 0)
    src, dst = m.init_edges()
    n = jnp.int32(src.shape[0])
    emb = materialize(init_level0_vertex(src, dst, int(n)))
    state = (app.init_state(m.ctx, emb, n) if app.init_state is not None
             else None)
    for _ in range(app.max_size - 2):
        k = emb.shape[1]
        row, u, slot, add, total = R._vertex_candidates(
            m.ctx, app, emb, n, state, cand_cap)
        upd = resolve_state_kernel(app, k)
        new_st = (None if upd is None else
                  R.apply_state_kernel(m.ctx, upd, emb, row, u, slot, state))
        want, want_emb = R.finish_extend_vertex(emb, row, u, add, out_cap,
                                                new_state=new_st)
        got, got_emb, got_total = R.extend_vertex_chunked(
            m.ctx, app, emb, n, state, cand_cap, out_cap)
        assert int(got_total) == int(total)
        assert int(got.n) == int(want.n)
        np.testing.assert_array_equal(got.vid, want.vid)
        np.testing.assert_array_equal(got.idx, want.idx)
        np.testing.assert_array_equal(got_emb, want_emb)
        assert (got.state is None) == (want.state is None)
        if want.state is not None:
            np.testing.assert_array_equal(got.state, want.state)
        if int(total) > cand_cap or int(want.n) > out_cap:
            break
        emb, state, n = want_emb, want.state, want.n


def test_pruned_kernel_matches_oracle():
    """fused_extend_pruned (pallas, interpret) == fused_extend_pruned_ref
    (pure jnp) in every connectivity mode: full bitmap, mixed
    partial-pack (bitmap rows + CSR fallback), and pure CSR search."""
    import jax.numpy as jnp
    from repro.core.api import is_auto_canonical_kernel
    from repro.graph.csr import pack_adjacency
    from repro.kernels.extend_fused import (fused_extend_pruned,
                                            fused_extend_pruned_ref)

    g = G.erdos_renyi(40, 0.25, seed=6)
    rng = np.random.default_rng(0)
    emb = jnp.asarray(rng.integers(0, 40, size=(50, 3)), jnp.int32)
    offsets, starts, emb_flat, vlo, vhi, n_steps = _kernel_inputs(g, emb)
    state = jnp.zeros((50,), jnp.int32)
    full_pg = pack_adjacency(g)
    n_words = full_pg.n_words
    partial_pg = pack_adjacency(g, max_bytes=12 * n_words * 4)  # 12 rows
    assert full_pg.full and not partial_pg.full
    modes = {
        "bitmap": (full_pg.words.reshape(-1), jnp.zeros((1,), jnp.int32),
                   full_pg.n_packed),
        "mixed": (partial_pg.words.reshape(-1), partial_pg.row_slot,
                  partial_pg.n_packed),
        "search": (jnp.zeros((1,), jnp.uint32), jnp.zeros((1,), jnp.int32),
                   1),
    }
    args = (g.col_idx, offsets, starts, emb_flat, vlo, vhi, state)
    for cand_cap, out_cap in [(int(offsets[-1]) + 17, 256),
                              (max(int(offsets[-1]) // 2, 8), 32)]:
        kw = dict(k=3, cand_cap=cand_cap, out_cap=out_cap, n_steps=n_steps)
        ref = fused_extend_pruned_ref(*args, pred=is_auto_canonical_kernel,
                                      **kw)
        for conn_mode, (bits, row_slot, n_rows) in modes.items():
            got = fused_extend_pruned(
                *args, bits, row_slot, n_vertices=g.n_vertices,
                n_words=n_words, n_rows=n_rows,
                pred=is_auto_canonical_kernel, conn_mode=conn_mode,
                interpret=True, block_c=128, **kw)
            for r, o in zip(ref, got):
                np.testing.assert_array_equal(np.asarray(r), np.asarray(o))


def test_pruned_kernel_state_output_matches_oracle():
    """With a state_upd hook the kernel grows a compacted state output
    (the multi-pattern branch bitmap path); without one the output —
    and its gather/write — must not exist at all (3-tuple contract)."""
    import jax.numpy as jnp
    from repro.core.api import is_auto_canonical_kernel
    from repro.graph.csr import pack_adjacency
    from repro.kernels.extend_fused import (fused_extend_pruned,
                                            fused_extend_pruned_ref)

    g = G.erdos_renyi(40, 0.25, seed=6)
    rng = np.random.default_rng(2)
    emb = jnp.asarray(rng.integers(0, 40, size=(50, 3)), jnp.int32)
    offsets, starts, emb_flat, vlo, vhi, n_steps = _kernel_inputs(g, emb)
    state = jnp.asarray(rng.integers(0, 8, size=(50,)), jnp.int32)
    pg = pack_adjacency(g)

    def upd(emb_cols, u, src_slot, st, conn):
        return (st * 2) | conn[0].astype(jnp.int32)

    args = (g.col_idx, offsets, starts, emb_flat, vlo, vhi, state)
    kw = dict(k=3, cand_cap=int(offsets[-1]) + 5, out_cap=128,
              n_steps=n_steps)
    ref = fused_extend_pruned_ref(*args, pred=is_auto_canonical_kernel,
                                  state_upd=upd, **kw)
    got = fused_extend_pruned(
        *args, pg.words.reshape(-1), jnp.zeros((1,), jnp.int32),
        n_vertices=g.n_vertices, n_words=pg.n_words, n_rows=pg.n_packed,
        pred=is_auto_canonical_kernel, state_upd=upd, conn_mode="bitmap",
        interpret=True, block_c=128, **kw)
    assert len(ref) == len(got) == 4
    for r, o in zip(ref, got):
        np.testing.assert_array_equal(np.asarray(r), np.asarray(o))
    # stateless specialization: the original 3-tuple, no state buffer
    ref3 = fused_extend_pruned_ref(*args, pred=is_auto_canonical_kernel,
                                   **kw)
    got3 = fused_extend_pruned(
        *args, pg.words.reshape(-1), jnp.zeros((1,), jnp.int32),
        n_vertices=g.n_vertices, n_words=pg.n_words, n_rows=pg.n_packed,
        pred=is_auto_canonical_kernel, conn_mode="bitmap",
        interpret=True, block_c=128, **kw)
    assert len(ref3) == len(got3) == 3
    for r, o in zip(ref3, got3):
        np.testing.assert_array_equal(np.asarray(r), np.asarray(o))


# -- two-pass scan compaction (pallas-mp): tile-boundary properties ----------
#
# The concurrent-grid contract forbids any tile-to-tile carry, so the
# dangerous inputs are exactly the tile-boundary shapes: tiles where every
# lane survives (dest windows must abut exactly), tiles where none does
# (bases must not advance), straddling tiles, and totals past out_cap
# (overflow must clamp without corrupting in-range slots).  The predicates
# below engineer each shape deterministically.

def _pred_alive(emb_cols, u, src_slot, st, conn):
    return u >= 0                                     # all-alive tiles


def _pred_dead(emb_cols, u, src_slot, st, conn):
    return u < -1                                     # all-dead tiles


def _pred_straddle(emb_cols, u, src_slot, st, conn):
    return (u % 3) == 0                               # straddling tiles


_MP_PREDS = {"alive": _pred_alive, "dead": _pred_dead,
             "straddle": _pred_straddle}


@given(seed=st.integers(0, 12), n_emb=st.sampled_from([8, 24, 47]),
       pred_name=st.sampled_from(sorted(_MP_PREDS)),
       tight_cap=st.booleans())
@settings(max_examples=10, deadline=None)
def test_mp_compaction_tile_boundary_property(seed, n_emb, pred_name,
                                              tight_cap):
    """Property: the two-pass concurrent-tile compaction is bitwise equal
    to the sequential kernel AND both jnp oracles across all-alive,
    all-dead, and straddling tiles — including out_cap overflow, where
    the true survivor count (the overflow flag's input) must agree on
    every path."""
    g = G.erdos_renyi(32, 0.3, seed=seed % 5)
    rng = np.random.default_rng(seed)
    emb = jnp.asarray(rng.integers(0, 32, size=(n_emb, 3)), jnp.int32)
    offsets, starts, emb_flat, vlo, vhi, n_steps = _kernel_inputs(g, emb)
    state = jnp.zeros((n_emb,), jnp.int32)
    total = int(offsets[-1])
    # round the capacity so each (shape, pred) combo traces once but the
    # live region still straddles several 128-lane tiles
    cand_cap = (total // 256 + 1) * 256
    out_cap = 16 if tight_cap else cand_cap
    pred = _MP_PREDS[pred_name]
    args = (g.col_idx, offsets, starts, emb_flat, vlo, vhi, state)
    kw = dict(k=3, cand_cap=cand_cap, out_cap=out_cap, n_steps=n_steps)
    ref = fused_extend_pruned_ref(*args, pred=pred, **kw)
    mp_ref = fused_extend_pruned_mp_ref(*args, pred=pred, block_c=128, **kw)
    bits = jnp.zeros((1,), jnp.uint32)
    rs = jnp.zeros((1,), jnp.int32)
    kkw = dict(n_vertices=g.n_vertices, n_words=1, n_rows=1, pred=pred,
               conn_mode="search", interpret=True, block_c=128, **kw)
    seq = fused_extend_pruned(*args, bits, rs, **kkw)
    mp = fused_extend_pruned_mp(*args, bits, rs, **kkw)
    assert len(seq) == len(mp) == len(ref) == 3
    for a, b in zip(seq, mp):                         # kernel vs kernel
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(ref, mp):                         # oracle vs kernel
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    row2, u2, n_surv2, tile_counts = mp_ref           # two-pass oracle
    np.testing.assert_array_equal(np.asarray(row2), np.asarray(mp[0]))
    np.testing.assert_array_equal(np.asarray(u2), np.asarray(mp[1]))
    assert int(n_surv2) == int(mp[2]) == int(jnp.sum(tile_counts))
    if pred_name == "dead":
        assert int(mp[2]) == 0
    if pred_name == "alive" and tight_cap and total > out_cap:
        assert int(mp[2]) > out_cap                   # overflow flag parity


def test_mp_kernels_carry_no_cross_tile_state():
    """Static guard on the concurrent-grid contract: the two-pass kernels
    (and the fused edge kernel, legal on both grids) must not allocate
    SMEM scratch or reference the grid-carried offset at all — the only
    cross-tile information is the bases vector computed OUTSIDE the
    kernel by the exclusive scan."""
    import inspect

    from repro.kernels.extend_fused import extend as E

    for fn in (E._mp_count_kernel, E._mp_scatter_kernel,
               E._edge_extend_kernel, E._tile_enumerate, E._tile_compact):
        src = inspect.getsource(fn)
        assert "SMEM" not in src, fn.__name__
        assert "base_ref" not in src, fn.__name__   # the sequential carry
    # the sequential kernel is the one that carries — keep the contrast
    assert "base_ref" in inspect.getsource(E._pruned_extend_kernel)


def test_mp_compaction_with_state_column():
    """The compacted state column rides through the same two-pass scatter
    (pass 2 recomputes state_upd and places it at the scanned offsets)."""
    from repro.core.api import is_auto_canonical_kernel
    from repro.graph.csr import pack_adjacency

    g = G.erdos_renyi(40, 0.25, seed=6)
    rng = np.random.default_rng(2)
    emb = jnp.asarray(rng.integers(0, 40, size=(50, 3)), jnp.int32)
    offsets, starts, emb_flat, vlo, vhi, n_steps = _kernel_inputs(g, emb)
    state = jnp.asarray(rng.integers(0, 8, size=(50,)), jnp.int32)
    pg = pack_adjacency(g)

    def upd(emb_cols, u, src_slot, st, conn):
        return (st * 2) | conn[0].astype(jnp.int32)

    args = (g.col_idx, offsets, starts, emb_flat, vlo, vhi, state)
    kw = dict(k=3, cand_cap=int(offsets[-1]) + 5, out_cap=128,
              n_steps=n_steps)
    ref = fused_extend_pruned_ref(*args, pred=is_auto_canonical_kernel,
                                  state_upd=upd, **kw)
    got = fused_extend_pruned_mp(
        *args, pg.words.reshape(-1), jnp.zeros((1,), jnp.int32),
        n_vertices=g.n_vertices, n_words=pg.n_words, n_rows=pg.n_packed,
        pred=is_auto_canonical_kernel, state_upd=upd, conn_mode="bitmap",
        interpret=True, block_c=128, **kw)
    assert len(ref) == len(got) == 4
    for r, o in zip(ref, got):
        np.testing.assert_array_equal(np.asarray(r), np.asarray(o))


# -- capabilities surface ----------------------------------------------------

def test_backend_capabilities_compaction_contract():
    ref = get_backend("reference").capabilities()
    assert ref["compaction"] == "xla-scan"
    assert ref["compaction_passes"] == 0
    assert ref["grid_contract"] == "any"
    pal = get_backend("pallas").capabilities()
    assert pal["compaction"] == "sequential-smem"
    assert pal["compaction_passes"] == 1
    assert pal["grid_contract"] == "sequential"
    mp = get_backend("pallas-mp").capabilities()
    assert mp["backend"] == "pallas-mp"
    assert mp["compaction"] == "two-pass-scan"
    assert mp["compaction_passes"] == 2
    assert mp["grid_contract"] == "concurrent"


def test_backend_capabilities_per_app():
    tc = make_tc_app()
    for name in ("pallas", "pallas-mp"):
        caps = get_backend(name).capabilities(tc)
        assert caps["extend_pruned"] == "fused-kernel"
        assert caps["extend_edge"] == "n/a"
    assert get_backend("reference").capabilities(tc)["extend_pruned"] == "xla"
    # edge apps: the vertex-mask eager hook keeps enumeration fusible;
    # a batch to_add hook would force the xla fallback
    fsm = make_fsm_app(3, min_support=2)
    for name in ("pallas", "pallas-mp"):
        caps = get_backend(name).capabilities(fsm)
        assert caps["extend_edge"] == "fused-kernel"
        assert caps["extend_pruned"] == "n/a"
    import dataclasses
    batch = dataclasses.replace(fsm, to_add_vertex_mask=None,
                                to_add=lambda ctx, slots, u, eid: u >= 0)
    caps = get_backend("pallas").capabilities(batch)
    assert caps["extend_edge"] == "xla-fallback:batch-to-add"


def test_plan_reports_surface_capabilities(er_graph):
    m = Miner(er_graph, make_tc_app(), backend="pallas-mp")
    m.run()
    reports = m.plan_reports()
    assert reports
    for rep in reports:
        caps = rep["capabilities"]
        assert caps["backend"] == "pallas-mp"
        assert caps["compaction"] == "two-pass-scan"
        assert caps["compaction_passes"] == 2
        assert caps["extend_pruned"] == "fused-kernel"


# -- interpret-mode env override ---------------------------------------------

def test_interpret_env_override(monkeypatch):
    from repro.kernels.runtime import ENV_VAR, env_interpret, resolve_interpret

    monkeypatch.delenv(ENV_VAR, raising=False)
    assert env_interpret() is None
    default = resolve_interpret(None)
    assert resolve_interpret(True) is True
    assert resolve_interpret(False) is False
    for raw, want in [("1", True), ("true", True), ("0", False),
                      ("false", False)]:
        monkeypatch.setenv(ENV_VAR, raw)
        assert env_interpret() is want
        # the env wins over both the explicit arg and the autodetect
        assert resolve_interpret(None) is want
        assert resolve_interpret(not want) is want
    monkeypatch.setenv(ENV_VAR, "sometimes")
    with pytest.raises(ValueError, match=ENV_VAR):
        env_interpret()
    monkeypatch.delenv(ENV_VAR)
    assert resolve_interpret(None) is default


def test_interpret_env_reaches_kernels(monkeypatch, er_graph):
    """The override is resolved per call (outside jit), so flipping the
    env between calls must not be frozen into a stale trace."""
    from repro.kernels.runtime import ENV_VAR

    monkeypatch.setenv(ENV_VAR, "1")
    ref = Miner(er_graph, make_tc_app()).run().count
    assert Miner(er_graph, make_tc_app(),
                 backend="pallas-mp").run().count == ref


# -- fused edge enumeration through the backends ------------------------------

@KERNEL_BACKENDS
@pytest.mark.parametrize("minsup", [0, 2])
def test_parity_fsm_edge_kernel(labeled_graph, minsup, kbackend):
    """FSM rides the fused edge-enumeration kernel (its eager prune is a
    per-vertex mask, gathered in-kernel): supports and codes must match
    the reference pipeline exactly."""
    app = make_fsm_app(3, min_support=minsup, max_patterns=64)
    r = Miner(labeled_graph, app).run()
    p = Miner(labeled_graph, app, backend=kbackend).run()
    np.testing.assert_array_equal(np.asarray(r.codes), np.asarray(p.codes))
    np.testing.assert_array_equal(np.asarray(r.supports),
                                  np.asarray(p.supports))


# -- fused kernel vs jnp oracle ----------------------------------------------

def _kernel_inputs(g, emb):
    rp = jnp.asarray(g.row_ptr)
    embc = jnp.clip(emb, 0, g.n_vertices - 1).reshape(-1)
    vlo = rp[embc]
    vhi = rp[embc + 1]
    deg = jnp.where((emb >= 0).reshape(-1), vhi - vlo, 0).astype(jnp.int32)
    offsets = jnp.cumsum(deg)
    starts = offsets - deg
    n_steps = max(1, math.ceil(math.log2(g.max_degree + 1)))
    return offsets, starts, emb.reshape(-1), vlo, vhi, n_steps


@pytest.mark.parametrize("block_c", [128, 512])
def test_fused_extend_kernel_matches_ref(block_c):
    g = G.erdos_renyi(40, 0.25, seed=6)
    rng = np.random.default_rng(0)
    emb = jnp.asarray(rng.integers(0, 40, size=(50, 3)), jnp.int32)
    offsets, starts, emb_flat, vlo, vhi, n_steps = _kernel_inputs(g, emb)
    cand_cap = int(offsets[-1]) + 17        # capacity past the total
    args = (g.col_idx, offsets, starts, emb_flat, vlo, vhi)
    kw = dict(k=3, cand_cap=cand_cap, n_steps=n_steps)
    ref = fused_extend_ref(*args, **kw)
    got = fused_extend(*args, **kw, block_c=block_c, interpret=True)
    for r, o in zip(ref, got):
        np.testing.assert_array_equal(np.asarray(r), np.asarray(o))


def test_fused_extend_kernel_truncation():
    """cand_cap below the true total truncates but stays slot-exact."""
    g = G.erdos_renyi(30, 0.4, seed=2)
    rng = np.random.default_rng(1)
    emb = jnp.asarray(rng.integers(0, 30, size=(20, 2)), jnp.int32)
    offsets, starts, emb_flat, vlo, vhi, n_steps = _kernel_inputs(g, emb)
    cand_cap = max(int(offsets[-1]) // 2, 8)
    args = (g.col_idx, offsets, starts, emb_flat, vlo, vhi)
    kw = dict(k=2, cand_cap=cand_cap, n_steps=n_steps)
    ref = fused_extend_ref(*args, **kw)
    got = fused_extend(*args, **kw, interpret=True)
    for r, o in zip(ref, got):
        np.testing.assert_array_equal(np.asarray(r), np.asarray(o))


# -- ragged primitive edge cases ---------------------------------------------

def test_expand_ragged_all_zero_counts():
    parent, rank, total = expand_ragged(jnp.zeros((6,), jnp.int32), 8)
    assert int(total) == 0
    assert (np.asarray(parent) == -1).all()
    assert (np.asarray(rank) == 0).all()


def test_expand_ragged_capacity_overflow_truncates():
    counts = jnp.asarray([3, 2, 4], jnp.int32)       # total 9, capacity 5
    parent, rank, total = expand_ragged(counts, 5)
    assert int(total) == 9                            # true total reported
    assert np.asarray(parent).tolist() == [0, 0, 0, 1, 1]
    assert np.asarray(rank).tolist() == [0, 1, 2, 0, 1]


def test_compact_mask_all_false():
    gather, n = compact_mask(jnp.zeros((5,), bool), 4)
    assert int(n) == 0
    assert (np.asarray(gather) == 0).all()            # padding points at 0


def test_compact_mask_capacity_overflow_truncates():
    mask = jnp.asarray([1, 0, 1, 1, 0, 1, 1], bool)   # 5 survivors, cap 3
    gather, n = compact_mask(mask, 3)
    assert int(n) == 5                                # true count reported
    assert np.asarray(gather).tolist() == [0, 2, 3]   # first 3 survivors
