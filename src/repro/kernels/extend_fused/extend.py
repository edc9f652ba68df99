"""Pallas TPU kernel: fused EXTEND candidate enumeration (paper §5.3).

One kernel fuses the three gather stages of inspection-execution candidate
generation that the reference backend runs as separate XLA ops:

  1. *offset search*: each output slot binary-searches the per-parent
     prefix-sum offsets to find its (parent, rank) — the ragged expansion
     of ``expand_ragged``, done branchlessly in VMEM instead of a
     ``searchsorted`` over HBM;
  2. *candidate gather*: the slot gathers its candidate vertex ``u`` from
     the CSR adjacency chunk at ``row_ptr[v] + rank``;
  3. *toAdd probing*: for every parent-embedding slot j, the kernel binary
     searches ``u`` in N(emb[row, j]) (generalizing the pairwise
     ``intersect`` kernel to k-way membership), emitting a connectivity
     bitmask that the filter hooks (``to_add_bits`` / the bits-based
     canonical test) consume without touching the CSR again.

All arrays are VMEM-resident per the edge-blocking contract of §5.2 (the
adjacency chunk and the [cap*k] parent tables must fit in ~16 MB); the
grid tiles the candidate slots.  Every probe step is one vectorized
gather + compare + select over a (1, block_c) lane tile — the same
VPU-bound shape as ``kernels/intersect``.  Runs under ``interpret=True``
on CPU.

Two compaction contracts exist for the eager-pruning variant:

  * :func:`fused_extend_pruned_pallas` — **sequential-grid** compaction:
    the running survivor offset lives in SMEM scratch and is carried
    tile-to-tile, which is only legal when grid tiles execute in order
    (TPU / interpret mode).
  * :func:`fused_extend_pruned_mp_pallas` — **concurrent-grid** two-pass
    compaction: pass 1 writes only a per-tile survivor count, the host
    exclusive-scans tile counts into per-tile bases, pass 2 re-runs the
    (deterministic) predicate and masked-scatters survivors at their
    final offsets.  Zero cross-tile communication; every tile touches
    disjoint output lanes, so the kernels are legal on architectures
    that launch grid tiles concurrently (GPU-style).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _take(arr, idx2d):
    """Gather a 1-D VMEM array at a [1, block] index tile."""
    return jnp.take(arr, idx2d.reshape(-1), axis=0).reshape(idx2d.shape)


def _take_tile(tile, idx2d):
    """Gather a computed [1, block] tile at a [1, block] index tile."""
    return jnp.take(tile.reshape(-1), idx2d.reshape(-1),
                    axis=0).reshape(idx2d.shape)


def _rup(x, q):
    return -(-x // q) * q


def _fused_extend_kernel(offsets_ref, starts_ref, emb_ref, vlo_ref, vhi_ref,
                         col_ref, row_ref, u_ref, slot_ref, conn_ref, *,
                         k: int, m: int, n_parents: int, n_steps: int,
                         n_steps_p: int, block_c: int):
    offsets = offsets_ref[...]
    starts = starts_ref[...]
    emb_flat = emb_ref[...]
    vlo = vlo_ref[...]
    vhi = vhi_ref[...]
    col = col_ref[...]

    i = pl.program_id(0)
    slot = (i * block_c
            + jax.lax.broadcasted_iota(jnp.int32, (1, block_c), 1))

    # stage 1 — searchsorted-right on the inclusive prefix sum:
    # parent p = first index with offsets[p] > slot (branchless)
    low = jnp.zeros_like(slot)
    high = jnp.full_like(slot, n_parents - 1)
    for _ in range(n_steps_p):
        mid = (low + high) >> 1
        val = _take(offsets, jnp.clip(mid, 0, n_parents - 1))
        go_right = val <= slot
        low = jnp.where(go_right, mid + 1, low)
        high = jnp.where(go_right, high, mid - 1)
    p = jnp.clip(low, 0, n_parents - 1)
    row = p // k
    src_slot = p % k

    # stage 2 — candidate gather from the CSR chunk
    rank = slot - _take(starts, p)
    ptr = _take(vlo, p) + rank
    u = _take(col, jnp.clip(ptr, 0, m - 1))

    # stage 3 — k-way adjacency probe: conn bit j = u in N(emb[row, j])
    # (bitwise-identical to sparse.intersect.binary_contains)
    conn = jnp.zeros_like(slot)
    base = row * k
    for j in range(k):
        pj = jnp.clip(base + j, 0, n_parents - 1)
        lo_b = _take(vlo, pj)
        hi_b = _take(vhi, pj)
        ev = _take(emb_flat, pj)
        lo_s, hi_s = lo_b, hi_b - 1
        for _ in range(max(n_steps, 1)):
            mid = (lo_s + hi_s) >> 1
            val = _take(col, jnp.clip(mid, 0, m - 1))
            go_right = val < u
            lo_s = jnp.where(go_right, mid + 1, lo_s)
            hi_s = jnp.where(go_right, hi_s, mid - 1)
        probe = jnp.clip(lo_s, 0, m - 1)
        found = (_take(col, probe) == u) & (lo_s < hi_b) & (lo_b < hi_b)
        found = found & (ev >= 0) & (u >= 0)
        conn = conn | (found.astype(jnp.int32) << j)

    row_ref[...] = row.reshape(block_c)
    u_ref[...] = u.reshape(block_c)
    slot_ref[...] = src_slot.reshape(block_c)
    conn_ref[...] = conn.reshape(block_c)


def fused_extend_pallas(col_idx: jnp.ndarray, offsets: jnp.ndarray,
                        starts: jnp.ndarray, emb_flat: jnp.ndarray,
                        vlo: jnp.ndarray, vhi: jnp.ndarray, *,
                        k: int, cand_cap: int, n_steps: int,
                        block_c: int = 512, interpret: bool = False):
    """Raw fused-extend call.  All parent tables are [cap*k] flattened.

    Returns (row, u, src_slot, conn) each i32[cand_cap]; slots past the
    true candidate total carry well-defined garbage (clipped last parent)
    that the caller masks with ``slot < total`` — same contract as
    ``expand_ragged``.
    """
    n_parents = offsets.shape[0]
    m = col_idx.shape[0]
    p_pad = _rup(n_parents, 128)

    def pad_p(x):
        return jnp.pad(x, (0, p_pad - n_parents))

    offsets, starts, emb_flat, vlo, vhi = map(
        pad_p, (offsets.astype(jnp.int32), starts.astype(jnp.int32),
                emb_flat.astype(jnp.int32), vlo.astype(jnp.int32),
                vhi.astype(jnp.int32)))
    m_pad = _rup(m, 128)
    col = jnp.pad(col_idx, (0, m_pad - m), constant_values=2**31 - 1)
    c_pad = _rup(cand_cap, block_c)
    n_steps_p = max(1, math.ceil(math.log2(n_parents + 1)))

    full = lambda size: pl.BlockSpec((size,), lambda i: (0,))
    tile = pl.BlockSpec((block_c,), lambda i: (i,))
    out = jax.ShapeDtypeStruct((c_pad,), jnp.int32)
    row, u, src_slot, conn = pl.pallas_call(
        functools.partial(_fused_extend_kernel, k=k, m=m,
                          n_parents=n_parents, n_steps=n_steps,
                          n_steps_p=n_steps_p, block_c=block_c),
        grid=(c_pad // block_c,),
        in_specs=[full(p_pad)] * 5 + [full(m_pad)],
        out_specs=[tile] * 4,
        out_shape=[out] * 4,
        interpret=interpret,
    )(offsets, starts, emb_flat, vlo, vhi, col)
    return row[:cand_cap], u[:cand_cap], src_slot[:cand_cap], conn[:cand_cap]


# ---------------------------------------------------------------------------
# Eager in-kernel pruning: predicate + stream compaction fused into EXTEND


def _tile_enumerate(i, offsets, starts, emb_flat, vlo, vhi, col, state,
                    bits, row_slot, labels, *, k: int, m: int,
                    n_parents: int, n_steps: int, n_steps_p: int,
                    block_c: int, cand_cap: int, n_vertices: int,
                    n_words: int, n_rows: int, n_cols: int,
                    conn_mode: str, pred, state_upd, needs_labels: bool):
    """Stages 1-4 of the pruned extend, for grid tile ``i``.

    Enumerate one (1, block_c) candidate tile (parent search + CSR
    gather), probe k-way connectivity, evaluate the app's elementwise
    predicate (and optional state update).  Entirely tile-local — no
    refs, no scratch, no cross-tile state — so the sequential kernel and
    both passes of the concurrent-grid two-pass kernel share it
    verbatim, which is what makes pass 2's predicate replay bitwise
    equal to pass 1's counts.

    Returns ``(row, u, mask, new_st)`` as (1, block_c) tiles (``new_st``
    is None for stateless apps).
    """
    slot = (i * block_c
            + jax.lax.broadcasted_iota(jnp.int32, (1, block_c), 1))

    # stage 1 — parent search on the inclusive prefix sum (as fused_extend)
    low = jnp.zeros_like(slot)
    high = jnp.full_like(slot, n_parents - 1)
    for _ in range(n_steps_p):
        mid = (low + high) >> 1
        val = _take(offsets, jnp.clip(mid, 0, n_parents - 1))
        go_right = val <= slot
        low = jnp.where(go_right, mid + 1, low)
        high = jnp.where(go_right, high, mid - 1)
    p = jnp.clip(low, 0, n_parents - 1)
    row = p // k
    src_slot = p % k

    # stage 2 — candidate gather from the CSR chunk
    rank = slot - _take(starts, p)
    ptr = _take(vlo, p) + rank
    u = _take(col, jnp.clip(ptr, 0, m - 1))
    total = offsets[n_parents - 1]
    live = (slot < total) & (slot < cand_cap)

    # stage 3 — k-way connectivity.  Three modes (static):
    #   "bitmap" — every row is bit-packed: one word gather + bit test,
    #              rows indexed by vertex id (row_slot is the identity);
    #   "mixed"  — partial pack: packed rows (row_slot[v] >= 0) answer
    #              from the bitmap, the long tail falls back to the CSR
    #              binary search (both evaluated branchlessly per lane,
    #              select on the slot sign — the VPU has no divergence).
    #              Core packs additionally cover only columns < n_cols:
    #              candidates outside the covered prefix take the CSR
    #              branch of the same select;
    #   "search" — no pack: CSR binary search only.
    base_p = row * k
    u_c = jnp.clip(u, 0, n_vertices - 1)
    emb_cols, conn_cols, lab_cols = [], [], []

    def csr_probe(pj):
        lo_b = _take(vlo, pj)
        hi_b = _take(vhi, pj)
        lo_s, hi_s = lo_b, hi_b - 1
        for _ in range(max(n_steps, 1)):
            mid = (lo_s + hi_s) >> 1
            val = _take(col, jnp.clip(mid, 0, m - 1))
            go_right = val < u
            lo_s = jnp.where(go_right, mid + 1, lo_s)
            hi_s = jnp.where(go_right, hi_s, mid - 1)
        probe = jnp.clip(lo_s, 0, m - 1)
        return (_take(col, probe) == u) & (lo_s < hi_b) & (lo_b < hi_b)

    u_b = jnp.clip(u, 0, max(n_cols - 1, 0))

    def bitmap_probe(rows):
        widx = jnp.clip(rows, 0, n_rows - 1) * n_words + (u_b >> 5)
        w = _take(bits, widx)
        return ((w >> (u_b & 31).astype(jnp.uint32))
                & jnp.uint32(1)) == 1

    for j in range(k):
        pj = jnp.clip(base_p + j, 0, n_parents - 1)
        ev = _take(emb_flat, pj)
        ev_c = jnp.clip(ev, 0, n_vertices - 1)
        if conn_mode == "bitmap":
            found = bitmap_probe(ev_c)
        elif conn_mode == "mixed":
            pack_row = _take(row_slot, ev_c)    # don't shadow `slot` above
            in_pack = pack_row >= 0
            if n_cols < n_vertices:             # core pack column guard
                in_pack = in_pack & (u < n_cols)
            found = jnp.where(in_pack, bitmap_probe(pack_row),
                              csr_probe(pj))
        else:
            found = csr_probe(pj)
        found = found & (ev >= 0) & (u >= 0)
        emb_cols.append(ev)
        conn_cols.append(found)
        if needs_labels:
            lab_cols.append(_take(labels, ev_c))

    # stage 4 — the app's eager toAdd / symmetry-break predicate (and the
    # optional state update — e.g. the multi-pattern branch bitmap),
    # traced directly into the kernel on the (1, block_c) lane tiles.
    # Shared subexpressions between pred and state_upd (the typical case:
    # the bitmap IS the predicate) are CSE'd by the compiler.  Labeled
    # predicates (``pred.needs_labels``) get one extra gather stage —
    # candidate/parent labels, the same word-gather shape as the
    # adjacency bitmap probe.
    st = _take(state, jnp.clip(row, 0, n_parents // k - 1))
    if needs_labels:
        lab_u = _take(labels, u_c)
        mask = pred(tuple(emb_cols), u, src_slot, st, tuple(conn_cols),
                    tuple(lab_cols), lab_u) & live
    else:
        mask = pred(tuple(emb_cols), u, src_slot, st,
                    tuple(conn_cols)) & live
    new_st = None
    if state_upd is not None:
        new_st = state_upd(tuple(emb_cols), u, src_slot, st,
                           tuple(conn_cols)).astype(jnp.int32)
    return row, u, mask, new_st


def _tile_compact(mask, block_c: int):
    """Stage 5 — in-tile exclusive-scan stream compaction (tile-local).

    ``incl[j]`` is the 1-based output rank of slot j among this tile's
    survivors; the stable compaction gather sel[t] = "first j with
    incl[j] >= t+1" is the same branchless binary search as stage 1,
    over the tile.  Returns ``(cnt, sel, t)``: survivor count, stable
    gather indices, and the 1-based lane rank (``t <= cnt`` is the
    live-lane mask).
    """
    mi = mask.astype(jnp.int32)
    incl = jnp.cumsum(mi, axis=1)
    cnt = incl[0, block_c - 1]
    t = 1 + jax.lax.broadcasted_iota(jnp.int32, (1, block_c), 1)
    lo_t = jnp.zeros_like(t)
    hi_t = jnp.full_like(t, block_c - 1)
    for _ in range(max(1, math.ceil(math.log2(block_c)))):
        mid = (lo_t + hi_t) >> 1
        val = _take_tile(incl, jnp.clip(mid, 0, block_c - 1))
        go_right = val < t
        lo_t = jnp.where(go_right, mid + 1, lo_t)
        hi_t = jnp.where(go_right, hi_t, mid - 1)
    sel = jnp.clip(lo_t, 0, block_c - 1)
    return cnt, sel, t


def _pruned_extend_kernel(offsets_ref, starts_ref, emb_ref, vlo_ref, vhi_ref,
                          col_ref, state_ref, bits_ref, slot_ref, lab_ref,
                          *refs, out_len: int, block_c: int,
                          state_upd, **statics):
    # the compacted-state output exists only for state-updating apps —
    # stateless ones (state_upd None, the common case) skip the extra
    # buffer, gather, and write entirely (static specialization)
    if state_upd is not None:
        row_ref, u_ref, st_ref, cnt_ref, base_ref = refs
    else:
        row_ref, u_ref, cnt_ref, base_ref = refs
        st_ref = None
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        base_ref[0] = 0

    row, u, mask, new_st = _tile_enumerate(
        i, offsets_ref[...], starts_ref[...], emb_ref[...], vlo_ref[...],
        vhi_ref[...], col_ref[...], state_ref[...], bits_ref[...],
        slot_ref[...], lab_ref[...], block_c=block_c, state_upd=state_upd,
        **statics)
    cnt, sel, t = _tile_compact(mask, block_c)
    lane_live = t <= cnt
    comp_row = jnp.where(lane_live, _take_tile(row, sel), 0)
    comp_u = jnp.where(lane_live, _take_tile(u, sel), -1)

    # stage 6 — append at the running survivor offset.  The grid is
    # sequential (TPU contract; interpret mode likewise), so the SMEM
    # running count makes the cross-tile exclusive scan free.  Overflowing
    # tiles clamp into the tail headroom — garbage there is fine because
    # the true survivor count is returned and flagged by the planner.
    base = base_ref[0]
    bw = jnp.minimum(base, out_len - block_c)
    row_ref[pl.dslice(bw, block_c)] = comp_row.reshape(block_c)
    u_ref[pl.dslice(bw, block_c)] = comp_u.reshape(block_c)
    if st_ref is not None:
        comp_st = jnp.where(lane_live, _take_tile(new_st, sel), 0)
        st_ref[pl.dslice(bw, block_c)] = comp_st.reshape(block_c)
    base_ref[0] = base + cnt
    cnt_ref[0] = base + cnt


def _prep_pruned_inputs(col_idx, offsets, starts, emb_flat, vlo, vhi, state,
                        bits, row_slot, labels, *, k: int, cand_cap: int,
                        out_cap: int, block_c: int):
    """Shared input padding for the pruned-extend kernel family.

    Returns ``(inputs, specs, dims)``: the padded VMEM-ready operand
    tuple, the matching ``full``-BlockSpec list, and the static shape
    dictionary both the sequential and the two-pass wrappers consume.
    """
    n_parents = offsets.shape[0]
    m = col_idx.shape[0]
    cap = n_parents // k
    p_pad = _rup(n_parents, 128)

    def pad_to(x, size, fill=0):
        return jnp.pad(x, (0, size - x.shape[0]), constant_values=fill)

    offsets_p = pad_to(offsets.astype(jnp.int32), p_pad)
    starts_p = pad_to(starts.astype(jnp.int32), p_pad)
    emb_p = pad_to(emb_flat.astype(jnp.int32), p_pad)
    vlo_p = pad_to(vlo.astype(jnp.int32), p_pad)
    vhi_p = pad_to(vhi.astype(jnp.int32), p_pad)
    m_pad = _rup(m, 128)
    col = pad_to(col_idx, m_pad, fill=2**31 - 1)
    cap_pad = _rup(max(cap, 1), 128)
    state_p = pad_to(state.astype(jnp.int32), cap_pad)
    b_pad = _rup(max(int(bits.shape[0]), 1), 128)
    bits_p = pad_to(bits.astype(jnp.uint32), b_pad)
    s_pad = _rup(max(int(row_slot.shape[0]), 1), 128)
    slot_p = pad_to(row_slot.astype(jnp.int32), s_pad, fill=-1)
    if labels is None:
        labels = jnp.zeros((1,), jnp.int32)
    l_pad = _rup(max(int(labels.shape[0]), 1), 128)
    lab_p = pad_to(labels.astype(jnp.int32), l_pad)
    c_pad = _rup(cand_cap, block_c)
    dims = dict(
        n_parents=n_parents, m=m, c_pad=c_pad, n_tiles=c_pad // block_c,
        out_len=_rup(out_cap, block_c) + block_c,
        n_steps_p=max(1, math.ceil(math.log2(n_parents + 1))))
    full = lambda size: pl.BlockSpec((size,), lambda i: (0,))
    specs = ([full(p_pad)] * 5
             + [full(m_pad), full(cap_pad), full(b_pad), full(s_pad),
                full(l_pad)])
    inputs = (offsets_p, starts_p, emb_p, vlo_p, vhi_p, col, state_p,
              bits_p, slot_p, lab_p)
    return inputs, specs, dims


def fused_extend_pruned_pallas(col_idx: jnp.ndarray, offsets: jnp.ndarray,
                               starts: jnp.ndarray, emb_flat: jnp.ndarray,
                               vlo: jnp.ndarray, vhi: jnp.ndarray,
                               state: jnp.ndarray, bits: jnp.ndarray,
                               row_slot: jnp.ndarray, labels=None, *,
                               k: int, cand_cap: int, out_cap: int,
                               n_steps: int, n_vertices: int, n_words: int,
                               n_rows: int, pred, state_upd=None,
                               conn_mode: str = "search",
                               n_cols: int | None = None,
                               block_c: int = 512,
                               interpret: bool = False):
    """Fused EXTEND with eager in-kernel pruning + stream compaction.

    One kernel enumerates candidates (ragged expand + CSR gather), probes
    k-way connectivity, evaluates the app's elementwise ``to_add_kernel``
    predicate ``pred`` per candidate, and exclusive-scan-compacts the
    survivors into ``out_cap``-scale buffers — dead candidates are never
    materialized in HBM (paper §4 / §5.2 eager pruning).  Returns
    (row i32[out_cap], u i32[out_cap], n_surv i32[]) with ``n_surv`` the
    *true* survivor count (may exceed ``out_cap``; slots past
    ``min(n_surv, out_cap)`` are garbage the caller masks).

    ``state_upd`` (optional, same elementwise contract as ``pred`` but
    returning i32) computes each surviving candidate's new memo state —
    the multi-pattern trie's branch bitmap rides through here.  When
    given, the return becomes (row, u, st i32[out_cap], n_surv): the
    compacted new-state column.  Stateless calls are specialized — no
    extra buffer, gather, or write exists in their kernel.

    ``conn_mode`` picks the connectivity probe: ``"bitmap"`` (full pack —
    ``bits`` holds ``n_vertices`` u32 rows, indexed by vertex id),
    ``"mixed"`` (partial pack — ``bits`` holds ``n_rows`` packed rows,
    ``row_slot[v]`` maps a vertex to its row or -1, unpacked rows fall
    back to the CSR binary search), or ``"search"`` (CSR only; ``bits`` /
    ``row_slot`` may be dummies).  ``n_cols`` (default ``n_vertices``)
    is the pack's column coverage: mixed-mode probes whose candidate id
    is ``>= n_cols`` take the CSR branch (the core-pack contract).

    ``labels`` (i32[n_vertices], optional) feeds labeled predicates:
    when ``pred.needs_labels`` is set, the kernel gathers the candidate's
    and every parent slot's label and passes them as two extra predicate
    arguments ``(lab_cols, lab_u)``.

    The cross-tile output offset lives in SMEM scratch and relies on the
    sequential TPU grid (interpret mode is likewise sequential); this
    kernel is not safe on architectures with concurrent grid tiles — use
    :func:`fused_extend_pruned_mp_pallas` there.
    """
    needs_labels = bool(getattr(pred, "needs_labels", False))
    if n_cols is None:
        n_cols = n_vertices
    inputs, specs, dims = _prep_pruned_inputs(
        col_idx, offsets, starts, emb_flat, vlo, vhi, state, bits,
        row_slot, labels, k=k, cand_cap=cand_cap, out_cap=out_cap,
        block_c=block_c)
    out_len = dims["out_len"]
    full = lambda size: pl.BlockSpec((size,), lambda i: (0,))
    buf = jax.ShapeDtypeStruct((out_len,), jnp.int32)
    n_bufs = 3 if state_upd is not None else 2
    outs = pl.pallas_call(
        functools.partial(_pruned_extend_kernel, k=k, m=dims["m"],
                          n_parents=dims["n_parents"], n_steps=n_steps,
                          n_steps_p=dims["n_steps_p"], block_c=block_c,
                          cand_cap=cand_cap, out_len=out_len,
                          n_vertices=n_vertices,
                          n_words=n_words, n_rows=n_rows, n_cols=n_cols,
                          conn_mode=conn_mode, pred=pred,
                          state_upd=state_upd, needs_labels=needs_labels),
        grid=(dims["n_tiles"],),
        in_specs=specs,
        out_specs=[full(out_len)] * n_bufs + [full(1)],
        out_shape=[buf] * n_bufs + [jax.ShapeDtypeStruct((1,), jnp.int32)],
        scratch_shapes=[pltpu.SMEM((1,), jnp.int32)],
        interpret=interpret,
    )(*inputs)
    *bufs, cnt = outs
    n_surv = cnt[0]
    live = jnp.arange(out_cap, dtype=jnp.int32) < n_surv
    row, u = bufs[0], bufs[1]
    out = (jnp.where(live, row[:out_cap], 0),
           jnp.where(live, u[:out_cap], -1))
    if state_upd is not None:
        out = out + (jnp.where(live, bufs[2][:out_cap], 0),)
    return out + (n_surv,)


# ---------------------------------------------------------------------------
# Concurrent-grid (massively-parallel) two-pass scan compaction


def _mp_count_kernel(offsets_ref, starts_ref, emb_ref, vlo_ref, vhi_ref,
                     col_ref, state_ref, bits_ref, slot_ref, lab_ref,
                     cnt_ref, *, block_c: int, **statics):
    """Pass 1: per-tile survivor count.  No scratch, no cross-tile state —
    every tile writes exactly its own one-element output block, so the
    grid may execute tiles in any order or all at once."""
    i = pl.program_id(0)
    _, _, mask, _ = _tile_enumerate(
        i, offsets_ref[...], starts_ref[...], emb_ref[...], vlo_ref[...],
        vhi_ref[...], col_ref[...], state_ref[...], bits_ref[...],
        slot_ref[...], lab_ref[...], block_c=block_c, **statics)
    cnt_ref[0] = jnp.sum(mask.astype(jnp.int32))


def _mp_scatter_kernel(offsets_ref, starts_ref, emb_ref, vlo_ref, vhi_ref,
                       col_ref, state_ref, bits_ref, slot_ref, lab_ref,
                       bases_ref, *refs, out_len: int, block_c: int,
                       state_upd, **statics):
    """Pass 2: re-run the (deterministic) predicate, compact in-tile, and
    masked-scatter this tile's survivors at their final offsets.

    ``bases_ref[i]`` is the exclusive scan of pass-1 tile counts, so the
    write windows ``[base_i, base_i + cnt_i)`` of distinct tiles are
    disjoint by construction; each lane past ``cnt_i`` is masked out of
    the store entirely (no read-modify-write), which keeps the kernel
    race-free on a concurrent grid.  Tiles whose base lands past the
    output clamp into the tail headroom (indices >= out_cap — discarded
    by the caller, and the true survivor total flags the overflow).
    """
    if state_upd is not None:
        row_ref, u_ref, st_ref = refs
    else:
        row_ref, u_ref = refs
        st_ref = None
    i = pl.program_id(0)
    row, u, mask, new_st = _tile_enumerate(
        i, offsets_ref[...], starts_ref[...], emb_ref[...], vlo_ref[...],
        vhi_ref[...], col_ref[...], state_ref[...], bits_ref[...],
        slot_ref[...], lab_ref[...], block_c=block_c, state_upd=state_upd,
        **statics)
    cnt, sel, t = _tile_compact(mask, block_c)
    lane_live = (t <= cnt).reshape(block_c)
    comp_row = _take_tile(row, sel).reshape(block_c)
    comp_u = _take_tile(u, sel).reshape(block_c)
    base = bases_ref[i]
    bw = jnp.minimum(base, out_len - block_c)
    win = pl.dslice(bw, block_c)
    pltpu.store(row_ref.at[win], comp_row, mask=lane_live)
    pltpu.store(u_ref.at[win], comp_u, mask=lane_live)
    if st_ref is not None:
        comp_st = _take_tile(new_st, sel).reshape(block_c)
        pltpu.store(st_ref.at[win], comp_st, mask=lane_live)


def fused_extend_pruned_mp_pallas(col_idx: jnp.ndarray, offsets: jnp.ndarray,
                                  starts: jnp.ndarray, emb_flat: jnp.ndarray,
                                  vlo: jnp.ndarray, vhi: jnp.ndarray,
                                  state: jnp.ndarray, bits: jnp.ndarray,
                                  row_slot: jnp.ndarray, labels=None, *,
                                  k: int, cand_cap: int, out_cap: int,
                                  n_steps: int, n_vertices: int,
                                  n_words: int, n_rows: int, pred,
                                  state_upd=None,
                                  conn_mode: str = "search",
                                  n_cols: int | None = None,
                                  block_c: int = 512,
                                  interpret: bool = False):
    """Concurrent-grid fused EXTEND: two-pass tile-count scan compaction.

    Same contract (arguments, returns, bitwise results) as
    :func:`fused_extend_pruned_pallas`, but with the cross-tile exclusive
    scan lifted out of the kernel so no tile ever communicates with
    another — the compaction contract of a massively-parallel (GPU-style)
    grid where tiles run concurrently:

      pass 1   every tile independently enumerates + filters its
               candidates and writes ONE number: its survivor count
               (``i32[n_tiles]`` — the tile-count buffer, sized by the
               planner's ``cand_cap``).
      scan     the host/XLA layer exclusive-scans the tile counts into
               per-tile base offsets; the scan total is the true global
               survivor count, from which the caller's overflow flag
               (``n_surv > out_cap``) is computed — grow-and-retry works
               unchanged.
      pass 2   every tile re-runs the (cheap, deterministic) predicate,
               compacts in-tile, and masked-scatters its survivors —
               including the compacted ``state`` column — at final
               offsets ``[base_i, base_i + cnt_i)``.  Windows are
               disjoint by construction of the scan, so there is zero
               cross-tile communication and no store ordering
               requirement.

    The sequential kernel's SMEM running offset (tile-to-tile carry)
    does not exist anywhere in this pair of kernels.
    """
    needs_labels = bool(getattr(pred, "needs_labels", False))
    if n_cols is None:
        n_cols = n_vertices
    inputs, specs, dims = _prep_pruned_inputs(
        col_idx, offsets, starts, emb_flat, vlo, vhi, state, bits,
        row_slot, labels, k=k, cand_cap=cand_cap, out_cap=out_cap,
        block_c=block_c)
    n_tiles, out_len = dims["n_tiles"], dims["out_len"]
    statics = dict(k=k, m=dims["m"], n_parents=dims["n_parents"],
                   n_steps=n_steps, n_steps_p=dims["n_steps_p"],
                   block_c=block_c, cand_cap=cand_cap,
                   n_vertices=n_vertices, n_words=n_words, n_rows=n_rows,
                   n_cols=n_cols, conn_mode=conn_mode, pred=pred,
                   needs_labels=needs_labels)
    full = lambda size: pl.BlockSpec((size,), lambda i: (0,))

    # pass 1 — per-tile survivor counts (each tile owns one output block)
    counts = pl.pallas_call(
        functools.partial(_mp_count_kernel, state_upd=None, **statics),
        grid=(n_tiles,),
        in_specs=specs,
        out_specs=pl.BlockSpec((1,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((n_tiles,), jnp.int32),
        interpret=interpret,
    )(*inputs)

    # host/XLA exclusive scan: per-tile bases + the global survivor total
    incl = jnp.cumsum(counts)
    n_surv = incl[n_tiles - 1]
    bases = incl - counts
    t_pad = _rup(n_tiles, 128)
    bases_p = jnp.pad(bases, (0, t_pad - n_tiles))

    # pass 2 — masked scatter at final offsets (disjoint windows)
    buf = jax.ShapeDtypeStruct((out_len,), jnp.int32)
    n_bufs = 3 if state_upd is not None else 2
    bufs = pl.pallas_call(
        functools.partial(_mp_scatter_kernel, out_len=out_len,
                          state_upd=state_upd, **statics),
        grid=(n_tiles,),
        in_specs=specs + [full(t_pad)],
        out_specs=[full(out_len)] * n_bufs,
        out_shape=[buf] * n_bufs,
        interpret=interpret,
    )(*inputs, bases_p)

    live = jnp.arange(out_cap, dtype=jnp.int32) < n_surv
    out = (jnp.where(live, bufs[0][:out_cap], 0),
           jnp.where(live, bufs[1][:out_cap], -1))
    if state_upd is not None:
        out = out + (jnp.where(live, bufs[2][:out_cap], 0),)
    return out + (n_surv,)


# ---------------------------------------------------------------------------
# Edge-induced pipeline: fused candidate enumeration + canonical test


def _edge_extend_kernel(offsets_ref, starts_ref, slots_ref, vlo_ref,
                        col_ref, uid_ref, eids_ref, esrc_ref, edst_ref,
                        vmask_ref, row_ref, s_ref, u_ref, eid_ref, add_ref,
                        *, n_slots: int, m: int, n_parents: int,
                        n_uedges: int, n_steps_p: int, block_c: int,
                        cand_cap: int, n_vertices: int, has_vmask: bool):
    offsets = offsets_ref[...]
    starts = starts_ref[...]
    slots_flat = slots_ref[...]
    vlo = vlo_ref[...]
    col = col_ref[...]
    uid = uid_ref[...]
    eids = eids_ref[...]
    esrc = esrc_ref[...]
    edst = edst_ref[...]

    i = pl.program_id(0)
    slot = (i * block_c
            + jax.lax.broadcasted_iota(jnp.int32, (1, block_c), 1))

    # stage 1 — parent search over the [cap * (E+1)] slot-parent table
    low = jnp.zeros_like(slot)
    high = jnp.full_like(slot, n_parents - 1)
    for _ in range(n_steps_p):
        mid = (low + high) >> 1
        val = _take(offsets, jnp.clip(mid, 0, n_parents - 1))
        go_right = val <= slot
        low = jnp.where(go_right, mid + 1, low)
        high = jnp.where(go_right, high, mid - 1)
    p = jnp.clip(low, 0, n_parents - 1)
    row = p // n_slots
    s = p % n_slots

    # stage 2 — candidate + new-edge-uid gather from the CSR chunk
    rank = slot - _take(starts, p)
    ptr = jnp.clip(_take(vlo, p) + rank, 0, m - 1)
    total = offsets[n_parents - 1]
    live = (slot < total) & (slot < cand_cap)
    u = jnp.where(live, _take(col, ptr), -1)
    new_eid = jnp.where(live, _take(uid, ptr), -1)
    w = _take(slots_flat, p)                    # source vertex

    # stage 3 — edge-canonical test against the row's E existing edges:
    # gather each edge's uid and endpoints, "neighbour" = shares an
    # endpoint with the candidate edge (w, u).  Same total-order rule as
    # is_auto_canonical_edge, evaluated branchlessly per lane.
    E = n_slots - 1
    e_rows = n_parents // n_slots * E
    eid0 = _take(eids, jnp.clip(row * E, 0, e_rows - 1))
    ok = new_eid > eid0
    found = jnp.zeros(ok.shape, bool)
    for j in range(E):
        eidj = _take(eids, jnp.clip(row * E + j, 0, e_rows - 1))
        ec = jnp.clip(eidj, 0, max(n_uedges - 1, 0))
        es = _take(esrc, ec)
        ed = _take(edst, ec)
        shares = ((w == es) | (w == ed) | (u == es) | (u == ed))
        ok = ok & ~(found & (new_eid < eidj))
        found = found | shares
        ok = ok & (new_eid != eidj)
    add = ok & found

    # stage 4 — the app's eager per-vertex toAdd mask (e.g. FSM's
    # label-frequency pruning), one gather — same shape as the bitmap
    # word gather of the vertex kernel
    if has_vmask:
        vm = vmask_ref[...]
        add = add & (_take(vm, jnp.clip(u, 0, n_vertices - 1)) != 0)
    add = add & live

    row_ref[...] = row.reshape(block_c)
    s_ref[...] = s.reshape(block_c)
    u_ref[...] = u.reshape(block_c)
    eid_ref[...] = new_eid.reshape(block_c)
    add_ref[...] = add.astype(jnp.int32).reshape(block_c)


def fused_extend_edge_pallas(col_idx: jnp.ndarray, edge_uid: jnp.ndarray,
                             offsets: jnp.ndarray, starts: jnp.ndarray,
                             slots_flat: jnp.ndarray, vlo: jnp.ndarray,
                             eids_flat: jnp.ndarray, usrc: jnp.ndarray,
                             udst: jnp.ndarray, vmask=None, *,
                             n_slots: int, cand_cap: int, n_uedges: int,
                             n_vertices: int, block_c: int = 512,
                             interpret: bool = False):
    """Fused edge-induced candidate enumeration (one kernel).

    Replaces the reference pipeline's XLA enumeration chain
    (``expand_ragged`` + CSR/uid/endpoint gathers + canonical-edge test)
    with one VMEM-tiled kernel.  Parent tables are per *slot-parent*
    (``[cap * n_slots]`` flattened, ``n_slots = E + 1`` vertex slots per
    embedding): ``offsets``/``starts`` the inclusive prefix sum of
    per-slot candidate degrees, ``slots_flat`` the slot's vertex,
    ``vlo`` its CSR row start.  ``eids_flat`` is the ``[cap * E]`` table
    of existing edge uids; ``usrc``/``udst`` the per-uid endpoints.

    ``vmask`` (i32[n_vertices], optional) is the app's eager per-vertex
    ``to_add`` mask (``MiningApp.to_add_vertex_mask``), applied in-kernel
    so pruned candidates never survive to the XLA compaction.

    Returns (row, s, u, new_eid, add) each i32[cand_cap]; lanes past the
    true candidate total are dead (``add`` 0, ``u``/``new_eid`` -1; the
    parent coordinates of dead lanes are unspecified, as with
    ``expand_ragged``).  Tiles are independent — no scratch, no carry —
    so the kernel is legal on sequential and concurrent grids alike.
    """
    n_parents = offsets.shape[0]
    m = col_idx.shape[0]
    p_pad = _rup(n_parents, 128)

    def pad_to(x, size, fill=0):
        return jnp.pad(x, (0, size - x.shape[0]), constant_values=fill)

    offsets_p = pad_to(offsets.astype(jnp.int32), p_pad)
    starts_p = pad_to(starts.astype(jnp.int32), p_pad)
    slots_p = pad_to(slots_flat.astype(jnp.int32), p_pad)
    vlo_p = pad_to(vlo.astype(jnp.int32), p_pad)
    m_pad = _rup(m, 128)
    col = pad_to(col_idx, m_pad, fill=2**31 - 1)
    uid = pad_to(edge_uid.astype(jnp.int32), m_pad, fill=-1)
    e_pad = _rup(max(int(eids_flat.shape[0]), 1), 128)
    eids_p = pad_to(eids_flat.astype(jnp.int32), e_pad, fill=-1)
    ue_pad = _rup(max(n_uedges, 1), 128)
    usrc_p = pad_to(usrc.astype(jnp.int32), ue_pad, fill=-1)
    udst_p = pad_to(udst.astype(jnp.int32), ue_pad, fill=-1)
    has_vmask = vmask is not None
    if vmask is None:
        vmask = jnp.zeros((1,), jnp.int32)
    v_pad = _rup(max(int(vmask.shape[0]), 1), 128)
    vmask_p = pad_to(vmask.astype(jnp.int32), v_pad)
    c_pad = _rup(cand_cap, block_c)
    n_steps_p = max(1, math.ceil(math.log2(n_parents + 1)))

    full = lambda size: pl.BlockSpec((size,), lambda i: (0,))
    tile = pl.BlockSpec((block_c,), lambda i: (i,))
    out = jax.ShapeDtypeStruct((c_pad,), jnp.int32)
    row, s, u, new_eid, add = pl.pallas_call(
        functools.partial(_edge_extend_kernel, n_slots=n_slots, m=m,
                          n_parents=n_parents, n_uedges=n_uedges,
                          n_steps_p=n_steps_p, block_c=block_c,
                          cand_cap=cand_cap, n_vertices=n_vertices,
                          has_vmask=has_vmask),
        grid=(c_pad // block_c,),
        in_specs=[full(p_pad)] * 4 + [full(m_pad)] * 2
                 + [full(e_pad), full(ue_pad), full(ue_pad), full(v_pad)],
        out_specs=[tile] * 5,
        out_shape=[out] * 5,
        interpret=interpret,
    )(offsets_p, starts_p, slots_p, vlo_p, col, uid, eids_p, usrc_p,
      udst_p, vmask_p)
    return (row[:cand_cap], s[:cand_cap], u[:cand_cap],
            new_eid[:cand_cap], add[:cand_cap])
