"""Reference (pure-XLA) phase backend — the paper's algorithms in jnp.

EXTEND is the inspection-execution candidate generation of paper §5.3:

  1. *inspection*: per parent embedding, count candidate extensions
     (degree gather, masked by ``toExtend``) and prefix-sum to obtain each
     parent's output offset;
  2. *expansion*: each output slot finds its (parent, rank) — by a
     prefix sum over marks at the parents' first slots in the chunked
     vertex extend (:func:`extend_vertex_chunked`, which scatters only
     the rows a chunk spans, ``FILL_ROWS`` a pass, since the TPU charges
     a scatter per entry, dropped ones too), by binary search on the
     offsets (``expand_ragged``) elsewhere — and gathers its candidate
     vertex from CSR;
  3. *write*: ``toAdd`` is evaluated on candidates *before* they are
     written (the paper's loop fusion / materialization avoidance, §5.2),
     and survivors are compacted into the next SoA level by a prefix-sum
     scatter — conflict-free parallel writes.

``inspect_*`` returns the exact candidate and survivor counts so the host
driver can allocate exact static capacities (the recomputation-for-layout
trade-off the paper makes for GPUs, §5.3).

REDUCE implements the two support modes of §2.1 (count and domain/MNI) and
FILTER the support-based compaction of Alg. 2.  The module-level functions
are the single source of truth; :class:`ReferenceBackend` packages them
behind the :class:`~repro.core.phases.base.PhaseBackend` interface, and the
fused-kernel backends override only the enumeration step
(:meth:`ReferenceBackend._vertex_candidates`).
"""
from __future__ import annotations

import itertools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.api import (GraphCtx, MiningApp, is_auto_canonical_edge,
                            is_auto_canonical_vertex,
                            is_auto_canonical_vertex_bits,
                            resolve_kernel_predicate, resolve_state_kernel)
from repro.core.embedding_list import EmbeddingLevel, materialize_edges
from repro.core.phases.base import PhaseBackend
from repro.core import pattern as P
from repro.sparse.intersect import binary_contains
from repro.sparse.ops import compact_mask, expand_ragged, scatter_sorted

_INT_MAX = np.int32(np.iinfo(np.int32).max)


# ---------------------------------------------------------------------------
# EXTEND: vertex-induced


def vertex_ext_degrees(ctx: GraphCtx, app: MiningApp, emb: jnp.ndarray,
                       n_valid: jnp.ndarray,
                       state: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Step 1: per-(parent, slot) candidate counts, masked by ``toExtend``.

    With a ``to_extend_state`` hook (and a state column) the mask is
    per-embedding: rows enumerate only the slots their memo state still
    needs — the multi-pattern trie's dead branches never generate
    candidates at all.
    """
    ext = vertex_ext_mask(ctx, app, emb, n_valid, state)
    return jnp.where(ext, ctx.degree(emb), 0)          # [cap, k]


def vertex_ext_mask(ctx: GraphCtx, app: MiningApp, emb: jnp.ndarray,
                    n_valid: jnp.ndarray,
                    state: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """bool[cap, k]: which (parent, slot) pairs extend (``toExtend``)."""
    cap, k = emb.shape
    valid = jnp.arange(cap, dtype=jnp.int32) < n_valid
    if app.to_extend_state is not None and state is not None:
        ext = app.to_extend_state(ctx, emb, state)
    elif app.to_extend is not None:
        ext = app.to_extend(ctx, emb)
    else:
        ext = jnp.ones((cap, k), bool)
    return ext & valid[:, None]


def vertex_add_mask(ctx: GraphCtx, app: MiningApp, emb: jnp.ndarray,
                    row_c: jnp.ndarray, u: jnp.ndarray,
                    src_slot: jnp.ndarray, state: Optional[jnp.ndarray],
                    live: jnp.ndarray,
                    conn: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Step 3's filter: evaluate ``toAdd`` (or the default canonical test).

    When ``conn`` is given (bool[N, k], bit j = candidate u adjacent to the
    parent's j-th vertex, as precomputed by a fused kernel) the bits-based
    hook path is taken: ``app.to_add_bits`` if provided, else the
    connectivity-bit variant of the automorphism-canonical test.
    """
    parent_state = None if state is None else state[row_c]
    return _add_mask(ctx, app, emb[row_c], u, src_slot, parent_state, live,
                     conn)


def _add_mask(ctx: GraphCtx, app: MiningApp, parent_emb: jnp.ndarray,
              u: jnp.ndarray, src_slot: jnp.ndarray,
              parent_state: Optional[jnp.ndarray], live: jnp.ndarray,
              conn: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """:func:`vertex_add_mask` on the candidates' parent rows."""
    if conn is not None and app.to_add_bits is not None:
        add = app.to_add_bits(ctx, parent_emb, u, src_slot, parent_state,
                              conn)
    elif app.to_add is not None:
        add = app.to_add(ctx, parent_emb, u, src_slot, parent_state)
    elif conn is not None and not app.use_dag:
        add = is_auto_canonical_vertex_bits(parent_emb, u, conn, src_slot)
    else:
        # On an oriented DAG the two isConnected directions differ, so the
        # precomputed conn bits (u in N(emb_j)) cannot stand in for the
        # canonical test's emb_j-in-N(u) probes — re-probe the CSR instead.
        add = is_auto_canonical_vertex(ctx, parent_emb, u, src_slot)
    return add & live


def parent_columns(emb: jnp.ndarray, row_c: jnp.ndarray
                   ) -> tuple[jnp.ndarray, ...]:
    """The parent embedding's k columns at candidate scale, as k 1-D
    gathers.  A row gather ``emb[row_c]`` would make an ``[N, k]`` array,
    which the TPU tiles to 128 lanes: 512 bytes per candidate."""
    return tuple(emb[:, j][row_c] for j in range(emb.shape[1]))


def apply_kernel_predicate(ctx: GraphCtx, pred, emb: jnp.ndarray,
                           row_c: jnp.ndarray, u: jnp.ndarray,
                           src_slot: jnp.ndarray,
                           state: Optional[jnp.ndarray],
                           live: jnp.ndarray) -> jnp.ndarray:
    """Evaluate an elementwise ``to_add_kernel`` predicate on flat batches.

    Connectivity bits are probed here (O(1) against the packed bitmap);
    the Pallas backend traces the *same* ``pred`` inside the extend kernel
    on its in-VMEM bits, so the two backends stay bitwise equal.  Labeled
    predicates (``pred.needs_labels``) additionally receive the parent
    and candidate labels, gathered with the same clipping as the kernel's
    label stage (zeros when the graph is unlabeled) — again bitwise
    equal by construction.
    """
    emb_cols = parent_columns(emb, row_c)
    conn = tuple(ctx.is_connected(c, u) for c in emb_cols)
    st = (jnp.zeros(u.shape, jnp.int32) if state is None
          else state[row_c])
    if getattr(pred, "needs_labels", False):
        labels = (ctx.labels if ctx.labels is not None
                  else jnp.zeros((1,), jnp.int32))
        nv = labels.shape[0]
        lab_cols = tuple(labels[jnp.clip(c, 0, nv - 1)] for c in emb_cols)
        lab_u = labels[jnp.clip(u, 0, nv - 1)]
        return pred(emb_cols, u, src_slot, st, conn, lab_cols, lab_u) & live
    return pred(emb_cols, u, src_slot, st, conn) & live


def apply_state_kernel(ctx: GraphCtx, upd, emb: jnp.ndarray,
                       row_c: jnp.ndarray, u: jnp.ndarray,
                       src_slot: jnp.ndarray,
                       state: Optional[jnp.ndarray]) -> jnp.ndarray:
    """Evaluate an elementwise ``update_state_kernel`` on flat batches.

    Same plumbing (and therefore the same connectivity bits) as
    :func:`apply_kernel_predicate`; the Pallas backend traces the same
    ``upd`` inside the extend kernel, keeping the two backends bitwise
    equal.  Non-surviving candidates' outputs are dropped by the
    compaction gather, so no masking is needed here.
    """
    emb_cols = parent_columns(emb, row_c)
    conn = tuple(ctx.is_connected(c, u) for c in emb_cols)
    st = (jnp.zeros(u.shape, jnp.int32) if state is None
          else state[row_c])
    return upd(emb_cols, u, src_slot, st, conn).astype(jnp.int32)


def _pad_empty_frontier(emb: jnp.ndarray, state: Optional[jnp.ndarray]):
    """Zero-row frontier (zero-edge graph): pad to one dead row.

    Gathers from zero-length arrays are invalid in XLA; ``n_valid`` is 0
    for such frontiers, so every downstream live mask drops the pad row.
    """
    if emb.shape[0]:
        return emb, state
    emb = jnp.full((1, emb.shape[1]), -1, emb.dtype)
    state = None if state is None else jnp.zeros((1,), state.dtype)
    return emb, state


def _vertex_candidates(ctx: GraphCtx, app: MiningApp, emb: jnp.ndarray,
                       n_valid: jnp.ndarray, state: Optional[jnp.ndarray],
                       cand_cap: int):
    """Steps 1+2+filter: enumerate candidate (parent, u) pairs.

    Returns (parent_row i32[cand_cap], u i32[cand_cap],
             src_slot i32[cand_cap], add_mask bool[cand_cap],
             n_candidates i32[]).
    """
    emb, state = _pad_empty_frontier(emb, state)
    cap, k = emb.shape
    with jax.named_scope("rows"):
        deg = vertex_ext_degrees(ctx, app, emb, n_valid, state)
    with jax.named_scope("fill"):
        slot_parent, rank, total = expand_ragged(deg.reshape(-1), cand_cap)
    with jax.named_scope("draw"):
        row = slot_parent // k
        col = slot_parent % k
        live = slot_parent >= 0
        row_c = jnp.clip(row, 0, cap - 1)
        v = emb.reshape(-1)[row_c * k + jnp.clip(col, 0, k - 1)]
        ptr = ctx.row_ptr[jnp.clip(v, 0, ctx.n_vertices - 1)] + rank
        # zero-edge graphs: col_idx is empty and a gather from it is
        # invalid
        col_idx = (ctx.col_idx if ctx.n_edges
                   else jnp.zeros(1, ctx.col_idx.dtype))
        u = col_idx[jnp.clip(ptr, 0, max(ctx.n_edges - 1, 0))]
        u = jnp.where(live, u, -1)
        src_slot = jnp.clip(col, 0, k - 1).astype(jnp.int32)
    pred = resolve_kernel_predicate(app, k)
    with jax.named_scope("probe"):
        if pred is not None:
            add = apply_kernel_predicate(ctx, pred, emb, row_c, u, src_slot,
                                         state, live)
        else:
            add = vertex_add_mask(ctx, app, emb, row_c, u, src_slot, state,
                                  live)
    return row_c, u, src_slot, add, total


def inspect_vertex(ctx: GraphCtx, app: MiningApp, emb: jnp.ndarray,
                   n_valid: jnp.ndarray, state: Optional[jnp.ndarray],
                   cand_cap: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Exact (n_candidates, n_survivors) for capacity planning."""
    _, _, _, add, total = _vertex_candidates(ctx, app, emb, n_valid, state,
                                             cand_cap)
    return total, jnp.sum(add.astype(jnp.int32))


def candidate_bound_vertex(ctx: GraphCtx, app: MiningApp, emb: jnp.ndarray,
                           n_valid: jnp.ndarray,
                           state: Optional[jnp.ndarray] = None
                           ) -> jnp.ndarray:
    """Cheap upper bound on candidate count (degree sum) — step 1 only."""
    return jnp.sum(vertex_ext_degrees(ctx, app, emb, n_valid, state))


def finish_extend_vertex(emb: jnp.ndarray, row: jnp.ndarray, u: jnp.ndarray,
                         add: jnp.ndarray, out_cap: int,
                         fuse_filter: bool = True,
                         new_state: Optional[jnp.ndarray] = None):
    """Step 3's write: compact survivors into the next SoA level.

    ``new_state`` (i32[cand_cap], from ``update_state_kernel``) is
    compacted with the same gather into the level's ``state`` column.
    """
    if not fuse_filter:
        # Materialize the full candidate list (extra HBM traffic), then
        # filter — deliberately wasteful, for the ablation benchmark
        # (paper Fig. 12d; what Arabesque/RStream do).
        cand_vid = jnp.stack([row, u], axis=1)
        cand_vid = jax.lax.optimization_barrier(cand_vid)
        row, u = cand_vid[:, 0], cand_vid[:, 1]
    with jax.named_scope("compact"):
        gather, n_new = compact_mask(add, out_cap)
        live = jnp.arange(out_cap) < n_new
        vid = jnp.where(live, u[gather], -1)
        idx = jnp.where(live, row[gather], 0)
        st = (None if new_state is None
              else jnp.where(live, new_state[gather], 0).astype(jnp.int32))
    with jax.named_scope("emit"):
        level = EmbeddingLevel(vid=vid.astype(jnp.int32),
                               idx=idx.astype(jnp.int32), n=n_new, state=st)
        new_emb = jnp.concatenate(
            [emb[idx], vid[:, None].astype(jnp.int32)], axis=1)
    return level, new_emb


def extend_vertex(ctx: GraphCtx, app: MiningApp, emb: jnp.ndarray,
                  n_valid: jnp.ndarray, state: Optional[jnp.ndarray],
                  cand_cap: int, out_cap: int,
                  fuse_filter: bool = True):
    """Produce the next SoA level (and next emb matrix)."""
    row, u, _, add, _ = _vertex_candidates(ctx, app, emb, n_valid, state,
                                           cand_cap)
    return finish_extend_vertex(emb, row, u, add, out_cap, fuse_filter)


# Candidate slots one step of :func:`extend_vertex_chunked` enumerates: a
# level takes ceil(n_candidates / CHUNK_SLOTS) steps whatever its planned
# capacity, so capacity padding costs no gathers.
CHUNK_SLOTS = 1 << 16
# Rows one pass of :func:`_forward_fill` scatters: a chunk takes as many
# passes as the rows it spans need, one where they average 16 slots or
# more.
FILL_ROWS = CHUNK_SLOTS // 16


def _forward_fill(table: jnp.ndarray, q0: jnp.ndarray, q1: jnp.ndarray,
                  base: jnp.ndarray, size: int, fill: int):
    """``out[r][i] = table[r][j]`` for the last row ``j`` whose start
    ``table[0][j] <= base + i``, for ``i < size``; and the entries it
    scattered.

    ``table[0]`` must increase; ``q0`` is the last row to start at or
    before ``base``, ``q1`` the last before ``base + size``, and the
    table must hold ``fill + 1`` rows past ``q1``.  Each value is
    scattered as its difference to the previous row's and prefix-summed,
    which is exact in int32 (wraparound cancels) and gathers nothing.
    Only rows ``q0..q1`` are scattered, in passes of ``fill + 1`` (a
    pass's first row is the previous pass's last, there for the
    difference): on the TPU a scatter costs about as much per entry as
    a gather, the entries it drops too, so the fill's cost follows the
    rows the chunk spans, not its size.  The passes run in a ``while``
    loop unless ``size <= fill``, where one always does.
    """
    n_pass = jnp.maximum((q1 - q0 + fill - 1) // fill, 1)

    def fill_pass(p, marks):
        win = jax.lax.dynamic_slice_in_dim(table, q0 + p * fill, fill + 1,
                                           axis=1)
        pos = jnp.maximum(win[0] - base, 0)
        return tuple(
            scatter_sorted(m, pos,
                           jnp.diff(v, prepend=jnp.where(p == 0, 0, v[:1])))
            for m, v in zip(marks, win))

    marks = (jnp.zeros((size,), jnp.int32),) * table.shape[0]
    if size <= fill:          # q1 - q0 < size: one pass spans the chunk
        marks = fill_pass(0, marks)
    else:
        _, marks = jax.lax.while_loop(
            lambda c: c[0] < n_pass, lambda c: (c[0] + 1, fill_pass(*c)),
            (jnp.int32(0), marks))
    return [jnp.cumsum(m) for m in marks], n_pass * (fill + 1)


def _pick(col: jnp.ndarray, options) -> jnp.ndarray:
    """``options[col]`` elementwise, for a short static list of arrays."""
    out = options[0]
    for j in range(1, len(options)):
        out = jnp.where(col == j, options[j], out)
    return out


def extend_vertex_chunked(ctx: GraphCtx, app: MiningApp, emb: jnp.ndarray,
                          n_valid: jnp.ndarray,
                          state: Optional[jnp.ndarray], cand_cap: int,
                          out_cap: int):
    """:func:`extend_vertex` (fused filter, plus the state update) in a
    loop over chunks of candidate slots; bitwise the same level.

    The loop runs over the level's real candidates only (at most
    ``cand_cap`` of them), so the padding of an estimated capacity costs
    nothing.  Within a chunk every per-parent quantity (the row's slot
    offsets per column, its embedding columns and their CSR ranges, its
    state) reaches the slots by :func:`_forward_fill` instead of a gather
    per slot.  The fill scatters only the rows that start in the chunk,
    in passes of ``FILL_ROWS``: on the TPU a scatter is charged per
    entry, the entries it drops too, and a chunk of ``CHUNK_SLOTS``
    slots spans a few hundred to a few thousand rows on power-law
    graphs.  What is left per candidate is the CSR gather of the
    candidate itself and the connectivity probes.  Survivors are written
    at their running offset, in slot order, exactly as the one-shot
    compaction writes them.  Everything is kept in 1-D columns: on the
    TPU, flattening an ``[N, k]`` array costs tens of seconds of compile
    time.  Returns ``(level, new_emb, n_candidates, n_fill)``, the last
    the entries the fill scattered over the level.
    """
    emb, state = _pad_empty_frontier(emb, state)
    cap, k = emb.shape
    chunk = min(cand_cap, CHUNK_SLOTS)
    pred = resolve_kernel_predicate(app, k)
    upd = resolve_state_kernel(app, k)
    csr_conn = ctx.packed is None and ctx.search == "binary"
    with_labels = pred is not None and getattr(pred, "needs_labels", False)
    labels = (ctx.labels if ctx.labels is not None
              else jnp.zeros((1,), jnp.int32))
    with jax.named_scope("rows"):
        ext = vertex_ext_mask(ctx, app, emb, n_valid, state)
        raw = [emb[:, j] for j in range(k)]
        vtx = [jnp.clip(e, 0, ctx.n_vertices - 1) for e in raw]
        lo = [ctx.row_ptr[v] for v in vtx]
        hi = [ctx.row_ptr[v + 1] for v in vtx]
        cum, acc = [], jnp.zeros((cap,), jnp.int32)
        for j in range(k):            # slots of the row's columns <= j
            acc = acc + jnp.where(ext[:, j], hi[j] - lo[j], 0)
            cum.append(acc)
        row_offsets = jnp.cumsum(acc)
        total = row_offsets[-1].astype(jnp.int32)
        limit = jnp.minimum(total, jnp.int32(cand_cap))

        # per-row table over the rows with candidates, in slot order
        rid, n_rows = compact_mask(acc > 0, cap)
        columns = [row_offsets - acc, jnp.arange(cap, dtype=jnp.int32)]
        columns += cum[:-1] + lo + raw
        if csr_conn:
            columns += hi
        if state is not None:
            columns.append(state)
        if with_labels:
            columns += [labels[jnp.clip(e, 0, labels.shape[0] - 1)]
                        for e in raw]
        live_r = jnp.arange(cap, dtype=jnp.int32) < n_rows
        table = [c.astype(jnp.int32)[rid] for c in columns]
        table[0] = jnp.where(live_r, table[0], _INT_MAX)
        table = jnp.stack(table)
        # a fill pass reads fill + 1 rows from a chunk's rows on: pad
        # past the end, so that no window's start is clamped
        fill = min(FILL_ROWS, chunk)
        pad = jnp.zeros((table.shape[0], fill + 1), jnp.int32)
        table = jnp.concatenate([table, pad.at[0].set(_INT_MAX)], axis=1)

    col_idx = ctx.col_idx if ctx.n_edges else jnp.zeros(1, ctx.col_idx.dtype)
    last_edge = max(ctx.n_edges - 1, 0)
    lane = jnp.arange(chunk, dtype=jnp.int32)

    def step(carry):
        c, n_surv, n_fill, out_vid, out_idx, out_st = carry
        base = c * chunk
        with jax.named_scope("fill"):
            # the chunk's first and last rows (0 and 0 with no rows)
            ends = jnp.stack([base, base + chunk - 1])
            q0, q1 = jnp.maximum(
                jnp.searchsorted(table[0], ends, side="right") - 1, 0)
            f, entries = _forward_fill(table, q0, q1, base, chunk, fill)
            n_fill = n_fill + entries
        with jax.named_scope("draw"):
            slot = base + lane
            live = slot < limit
            off = slot - f[0]                   # slot within its row
            row_c = jnp.clip(f[1], 0, cap - 1)
            cum_f, f = f[2:1 + k], f[1 + k:]
            lo_f, emb_cols, f = f[:k], tuple(f[k:2 * k]), f[2 * k:]
            col = sum((off >= b).astype(jnp.int32) for b in cum_f)
            col = jnp.asarray(col, jnp.int32)
            first = _pick(col, [jnp.zeros_like(off)] + cum_f)
            u = col_idx[jnp.clip(_pick(col, lo_f) + off - first, 0,
                                 last_edge)]
            u = jnp.where(live, u, -1)
        with jax.named_scope("probe"):
            if csr_conn:
                hi_f, f = f[:k], f[k:]
                conn = tuple(binary_contains(col_idx, lo_f[j], hi_f[j], u,
                                             ctx.n_steps)
                             & (emb_cols[j] >= 0) & (u >= 0)
                             for j in range(k))
            else:
                conn = tuple(ctx.is_connected(e, u) for e in emb_cols)
            st = None
            if state is not None:
                st, f = f[0], f[1:]
            st0 = jnp.zeros(u.shape, jnp.int32) if st is None else st
            if pred is None:
                add = _add_mask(ctx, app, jnp.stack(emb_cols, axis=1), u,
                                col, st, live)
            elif with_labels:
                lab_u = labels[jnp.clip(u, 0, labels.shape[0] - 1)]
                add = pred(emb_cols, u, col, st0, conn, tuple(f[:k]),
                           lab_u) & live
            else:
                add = pred(emb_cols, u, col, st0, conn) & live
            new_st = (None if upd is None else
                      upd(emb_cols, u, col, st0, conn).astype(jnp.int32))
        # survivors land at their running offset; every other lane adds
        # 0 at the next survivor's position (sorted, collision-safe)
        with jax.named_scope("compact"):
            keep = add.astype(jnp.int32)
            at = n_surv + jnp.cumsum(keep) - keep
            out_vid = scatter_sorted(out_vid, at, (u + 1) * keep)
            out_idx = scatter_sorted(out_idx, at, row_c * keep)
            if new_st is not None:
                out_st = scatter_sorted(out_st, at, new_st * keep)
            n_surv = n_surv + jnp.sum(keep)
        return c + 1, n_surv, n_fill, out_vid, out_idx, out_st

    zeros = jnp.zeros((out_cap,), jnp.int32)
    carry = (jnp.int32(0), jnp.int32(0), jnp.int32(0), zeros, zeros, zeros)
    if chunk == cand_cap:       # one chunk covers the level; an empty
        carry = step(carry)     # one writes nothing
    else:
        carry = jax.lax.while_loop(lambda c: c[0] * chunk < limit, step,
                                   carry)
    _, n_new, n_fill, vid, idx, st = carry
    with jax.named_scope("emit"):
        vid = vid - 1                         # vid is stored plus one
        level = EmbeddingLevel(vid=vid, idx=idx, n=n_new,
                               state=None if upd is None else st)
        new_emb = jnp.concatenate([emb[idx], vid[:, None]], axis=1)
    return level, new_emb, total, n_fill


# ---------------------------------------------------------------------------
# EXTEND: edge-induced

MAX_EDGE_SLOTS = 8   # static bound on vertex slots (E+1 for E <= 7)


def edge_vertex_slots(v0: jnp.ndarray, vid: jnp.ndarray, his: jnp.ndarray
                      ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Vertex slots [cap, E+1] and first-appearance mask.

    Slot 0 = v0; slot s>=1 = destination vertex of edge s-1.  A slot is
    "fresh" iff its vertex did not appear in an earlier slot (edges closing
    cycles repeat vertices).
    """
    slots = jnp.concatenate([v0[:, None], vid], axis=1)
    n_slots = slots.shape[1]
    fresh = jnp.ones(slots.shape, bool)
    for s in range(1, n_slots):
        seen = jnp.zeros(slots.shape[:1], bool)
        for t in range(s):
            seen = seen | (slots[:, t] == slots[:, s])
        fresh = fresh.at[:, s].set(~seen)
    return slots, fresh


def _edge_candidates(ctx: GraphCtx, app: MiningApp,
                     v0, vid, his, eid, n_valid: jnp.ndarray,
                     cand_cap: int):
    cap, E = vid.shape
    n_slots = E + 1
    with jax.named_scope("rows"):
        slots, fresh = edge_vertex_slots(v0, vid, his)
        valid = jnp.arange(cap, dtype=jnp.int32) < n_valid
        ext = fresh & valid[:, None]
        if app.to_extend is not None:
            ext = ext & app.to_extend(ctx, slots)
        deg = jnp.where(ext, ctx.degree(slots), 0)    # [cap, E+1]
    with jax.named_scope("fill"):
        slot_parent, rank, total = expand_ragged(deg.reshape(-1), cand_cap)
    with jax.named_scope("draw"):
        row = jnp.clip(slot_parent // n_slots, 0, cap - 1)
        s = jnp.clip(slot_parent % n_slots, 0, n_slots - 1)
        live = slot_parent >= 0
        w = slots[row, s]                              # source vertex
        ptr = ctx.row_ptr[jnp.clip(w, 0, ctx.n_vertices - 1)] + rank
        ptr = jnp.clip(ptr, 0, ctx.n_edges - 1)
        u = jnp.where(live, ctx.col_idx[ptr], -1)      # destination vertex
        new_eid = jnp.where(live, ctx.edge_uid[ptr], -1)

    with jax.named_scope("probe"):
        # endpoints of existing edges (for the shares-endpoint test)
        eids_row = eid[row]                            # [cand, E]
        e_uid = jnp.clip(eids_row, 0, max(ctx.n_uedges - 1, 0))
        e_src = ctx.usrc[e_uid]
        e_dst = ctx.udst[e_uid]
        add = is_auto_canonical_edge(ctx, eids_row, new_eid, w, u, e_src,
                                     e_dst)
        if app.to_add_vertex_mask is not None:
            # per-candidate-vertex eager mask (e.g. FSM's label-frequency
            # prune) — the form the fused edge kernel applies in-VMEM
            vm = app.to_add_vertex_mask(ctx)
            add = add & vm[jnp.clip(u, 0, ctx.n_vertices - 1)]
        elif app.to_add is not None:
            add = add & app.to_add(ctx, slots[row], u, None)
        add = add & live
    return row, s, u, new_eid, add, total


def inspect_edge(ctx, app, v0, vid, his, eid, n_valid, cand_cap):
    _, _, _, _, add, total = _edge_candidates(ctx, app, v0, vid, his, eid,
                                              n_valid, cand_cap)
    return total, jnp.sum(add.astype(jnp.int32))


def candidate_bound_edge(ctx, app, v0, vid, his, n_valid):
    slots, fresh = edge_vertex_slots(v0, vid, his)
    cap = slots.shape[0]
    valid = jnp.arange(cap, dtype=jnp.int32) < n_valid
    deg = jnp.where(fresh & valid[:, None], ctx.degree(slots), 0)
    return jnp.sum(deg)


def finish_extend_edge(row, s, u, new_eid, add, out_cap: int):
    """Compact surviving edge candidates into the next SoA level."""
    with jax.named_scope("compact"):
        gather, n_new = compact_mask(add, out_cap)
        live_out = jnp.arange(out_cap) < n_new
        return EmbeddingLevel(
            vid=jnp.where(live_out, u[gather], -1).astype(jnp.int32),
            idx=jnp.where(live_out, row[gather], 0).astype(jnp.int32),
            n=n_new,
            his=jnp.where(live_out, s[gather], 0).astype(jnp.int32),
            eid=jnp.where(live_out, new_eid[gather], -1).astype(jnp.int32),
        )


def extend_edge(ctx, app, v0, vid, his, eid, n_valid, cand_cap, out_cap):
    """Produce the next edge-induced SoA level (vid, his, idx, eid).

    Returns ``(level, n_candidates)`` — the fused-counts contract of
    :func:`extend_pruned`, so plan replay needs no inspection pass.
    """
    row, s, u, new_eid, add, total = _edge_candidates(
        ctx, app, v0, vid, his, eid, n_valid, cand_cap)
    return finish_extend_edge(row, s, u, new_eid, add, out_cap), total


# ---------------------------------------------------------------------------
# REDUCE: vertex-induced (count support)


def build_adjacency(ctx: GraphCtx, emb: jnp.ndarray) -> jnp.ndarray:
    """Pairwise connectivity of embedding vertices: bool[N, k, k]."""
    n, k = emb.shape
    adj = jnp.zeros((n, k, k), bool)
    for i in range(k):
        for j in range(i + 1, k):
            c = ctx.is_connected(emb[:, i], emb[:, j])
            adj = adj.at[:, i, j].set(c).at[:, j, i].set(c)
    return adj


def reduce_count(ctx: GraphCtx, app: MiningApp, emb: jnp.ndarray,
                 n_valid: jnp.ndarray, state: Optional[jnp.ndarray]):
    """Classify + count.  Returns (p_map i32[max_patterns], pat i32[N], state)."""
    cap = emb.shape[0]
    valid = jnp.arange(cap, dtype=jnp.int32) < n_valid
    if app.state_histogram is not None:
        # the state column already carries the per-embedding pattern
        # attribution (e.g. the multi-pattern trie's leaf-branch bitmap);
        # the histogram is a fixed bit-count — no canonical labeling, no
        # jnp.unique, no segment sort
        p_map = app.state_histogram(state, valid).astype(jnp.int32)
        return p_map, jnp.zeros((cap,), jnp.int32), state
    if app.get_pattern is not None:
        pat, new_state = app.get_pattern(ctx, emb, state, valid)
    else:
        adj = build_adjacency(ctx, emb)
        codes = P.canonical_code(adj, None, emb.shape[1])
        codes = jnp.where(valid, codes, _INT_MAX)
        # +1 slot: the INT_MAX padding bucket sorts last and is dropped.
        uniq, pat = jnp.unique(codes, size=app.max_patterns + 1,
                               fill_value=_INT_MAX, return_inverse=True)
        new_state = pat
    pat = jnp.clip(pat, 0, app.max_patterns)
    p_map = jax.ops.segment_sum(valid.astype(jnp.int32), pat,
                                num_segments=app.max_patterns + 1)
    return p_map[:app.max_patterns], pat.astype(jnp.int32), new_state


# ---------------------------------------------------------------------------
# REDUCE: edge-induced — embedding -> labeled local graph


def edge_embedding_graph(ctx: GraphCtx, levels: list[EmbeddingLevel]):
    """Build per-embedding labeled local graphs from the SoA prefix tree.

    Returns (vert_vid i32[cap, V], labels i32[cap, V], adj bool[cap, V, V],
             n_verts i32[cap], eids i32[cap, E]) with V = E + 1 slots;
    vertices are in first-appearance order; pad vertices carry label
    ``ctx.n_labels`` (one past the real alphabet).
    """
    v0, vid, his, eid = materialize_edges(levels)
    cap, E = vid.shape
    V = E + 1
    slots, fresh = edge_vertex_slots(v0, vid, his)        # [cap, V]
    lid_fresh = jnp.cumsum(fresh.astype(jnp.int32), axis=1) - 1
    # local id per slot: fresh slots take their rank; stale slots copy the
    # local id of the first earlier slot holding the same vertex.
    lid = lid_fresh
    for s in range(1, V):
        match = jnp.zeros((cap,), jnp.int32) - 1
        for t in range(s):
            hit = (slots[:, t] == slots[:, s]) & (match < 0)
            match = jnp.where(hit, lid[:, t], match)
        lid = lid.at[:, s].set(jnp.where(fresh[:, s], lid[:, s], match))
    n_verts = jnp.sum(fresh.astype(jnp.int32), axis=1)
    # vertex ids per local slot
    vert_vid = jnp.full((cap, V), -1, jnp.int32)
    for s in range(V):
        tgt = jnp.where(fresh[:, s], lid[:, s], V)  # V = scratch (dropped)
        vert_vid = vert_vid.at[jnp.arange(cap), jnp.clip(tgt, 0, V - 1)].set(
            jnp.where(fresh[:, s] & (tgt < V), slots[:, s],
                      vert_vid[jnp.arange(cap), jnp.clip(tgt, 0, V - 1)]))
    # labels (pad = n_labels)
    if ctx.labels is not None:
        lab = ctx.labels[jnp.clip(vert_vid, 0, ctx.n_vertices - 1)]
    else:
        lab = jnp.zeros((cap, V), jnp.int32)
    arangeV = jnp.arange(V, dtype=jnp.int32)
    is_real = arangeV[None, :] < n_verts[:, None]
    lab = jnp.where(is_real, lab, jnp.int32(ctx.n_labels))
    # adjacency: edge j connects lid[his_j] -- lid[j+1]
    adj = jnp.zeros((cap, V, V), bool)
    rows = jnp.arange(cap)
    for j in range(E):
        a = lid[rows, jnp.clip(his[:, j], 0, V - 1)]
        b = lid[:, j + 1]
        a = jnp.clip(a, 0, V - 1)
        b = jnp.clip(b, 0, V - 1)
        adj = adj.at[rows, a, b].set(True).at[rows, b, a].set(True)
    return vert_vid, lab, adj, n_verts, eid


def _decode_n_verts(codes: jnp.ndarray, k: int, n_eff: int) -> jnp.ndarray:
    """Recover #real vertices from a packed code (pad label = n_eff - 1)."""
    n_pairs = k * (k - 1) // 2
    lab_part = codes >> n_pairs
    n_real = jnp.zeros(codes.shape, jnp.int32)
    for i in range(k - 1, -1, -1):
        li = lab_part % n_eff
        lab_part = lab_part // n_eff
        n_real = n_real + (li != (n_eff - 1)).astype(jnp.int32)
    return n_real


def _canonical_edge_codes(ctx: GraphCtx, app: MiningApp,
                          levels: list[EmbeddingLevel]):
    """Shared FSM-reduce front half: per-embedding canonical codes.

    Returns (vert_vid i32[cap, V], n_verts i32[cap], valid bool[cap],
    perms, codes_all i32[cap, n_perms], canon i32[cap]) with invalid rows'
    canon parked at INT_MAX.
    """
    vert_vid, lab, adj, n_verts, _ = edge_embedding_graph(ctx, levels)
    cap, V = lab.shape
    n_eff = ctx.n_labels + 1
    n_valid = levels[-1].n
    valid = jnp.arange(cap, dtype=jnp.int32) < n_valid
    perms = list(itertools.permutations(range(V)))
    codes_all = []
    for p in perms:
        pl = list(p)
        codes_all.append(P.pack_code(adj[:, pl][:, :, pl], lab[:, pl], V,
                                     n_eff))
    codes_all = jnp.stack(codes_all, axis=1)            # [cap, n_perms]
    canon = jnp.min(codes_all, axis=1)
    canon = jnp.where(valid, canon, _INT_MAX)
    return vert_vid, n_verts, valid, perms, codes_all, canon


def _domain_contributions(vert_vid, n_verts, valid, perms, codes_all,
                          canon, pat, park: int):
    """Flattened (domain, vertex, bucket) triples for MNI counting.

    Every minimizing permutation contributes its slot->domain assignment
    (exact MNI); ``bucket = pat * V + domain`` with dead contributions
    parked at ``park``.
    """
    cap, V = vert_vid.shape
    # ``perms`` is the pattern's static automorphism list (plain Python,
    # never traced) — this is trace-time constant arithmetic, not a sync.
    inv_perms = np.argsort(np.asarray(perms), axis=1)  # repro: ignore[host-sync]
    is_min = codes_all == canon[:, None]                 # [cap, n_perms]
    doms, vids, oks = [], [], []
    for pi, p in enumerate(perms):
        inv = inv_perms[pi]  # static [n_perms, V] host array (see above)
        for l in range(V):
            doms.append(jnp.full((cap,), int(inv[l]), jnp.int32))  # repro: ignore[host-sync]
            vids.append(vert_vid[:, l])
            oks.append(is_min[:, pi] & valid & (l < n_verts))
    dom = jnp.stack(doms, axis=1).reshape(-1)
    vid = jnp.stack(vids, axis=1).reshape(-1)
    ok = jnp.stack(oks, axis=1).reshape(-1)
    pidf = jnp.repeat(pat, len(perms) * V)
    bucket = jnp.where(ok, pidf * V + dom, park)
    return dom, vid, ok, bucket


def reduce_domain(ctx: GraphCtx, app: MiningApp,
                  levels: list[EmbeddingLevel]):
    """FSM reduce: canonical codes + MNI (domain) support.

    Returns (codes i32[P], support i32[P], pat i32[cap], pat_valid bool[P])
    with P = app.max_patterns.
    """
    vert_vid, n_verts, valid, perms, codes_all, canon = \
        _canonical_edge_codes(ctx, app, levels)
    cap, V = vert_vid.shape
    n_eff = ctx.n_labels + 1
    uniq, pat = jnp.unique(canon, size=app.max_patterns,
                           fill_value=_INT_MAX, return_inverse=True)
    pat_valid = uniq != _INT_MAX

    # Domain contributions from every minimizing permutation (exact MNI);
    # distinct-count per (pattern, domain) bucket: lexsort + adjacent-unique.
    park = app.max_patterns * V
    dom, vid, ok, bucket = _domain_contributions(
        vert_vid, n_verts, valid, perms, codes_all, canon, pat, park)
    order = jnp.lexsort((vid, bucket))
    bucket_s, vid_s = bucket[order], vid[order]
    first = jnp.ones(bucket_s.shape, bool)
    first = first.at[1:].set((bucket_s[1:] != bucket_s[:-1])
                             | (vid_s[1:] != vid_s[:-1]))
    live = bucket_s < park
    distinct = jax.ops.segment_sum((first & live).astype(jnp.int32),
                                   jnp.minimum(bucket_s, park),
                                   num_segments=park + 1)
    distinct = distinct[:park].reshape(app.max_patterns, V)
    return _domain_support(ctx, app, uniq, pat_valid, distinct, pat, valid,
                           V, n_eff)


def _domain_support(ctx, app, uniq, pat_valid, distinct, pat, valid, V,
                    n_eff):
    """Back half of the FSM reduce: MNI support = min over real domains."""
    n_real = _decode_n_verts(uniq, V, n_eff)
    dom_ok = jnp.arange(V)[None, :] < n_real[:, None]
    support = jnp.min(jnp.where(dom_ok, distinct, _INT_MAX), axis=1)
    support = jnp.where(pat_valid, support, 0)
    pat = jnp.where(valid, pat, app.max_patterns - 1).astype(jnp.int32)
    return uniq, support.astype(jnp.int32), pat, pat_valid


def reduce_domain_sharded(ctx: GraphCtx, app: MiningApp,
                          levels: list[EmbeddingLevel],
                          axis_names: tuple[str, ...],
                          packed: bool = True):
    """FSM reduce over ``shard_map``-distributed embeddings (exact MNI).

    The paper disables simple blocking for FSM because MNI support needs a
    *global* view: domain supports count distinct vertices, so per-device
    supports cannot just be summed.  This variant keeps the level-0 edge
    sharding and makes the reduce collective instead:

      1. every device canonicalizes its local embeddings and the pattern
         tables are aligned by all-gather + global unique (deterministic,
         so every device holds the same code table);
      2. domain membership is materialized as a (pattern, domain, vertex)
         bitmap, merged across devices as a set union, and distinct
         counts are read off the merged bitmap — exactly the global MNI
         domain;
      3. support = min over real domains of the merged distinct counts.

    Because every device then filters with the same global supports, the
    per-level support filter (Alg. 2) stays sound under distribution —
    the paper's "global support sync".  With ``axis_names=()`` this is a
    collective-free local reduce, numerically identical to
    :func:`reduce_domain` (used by tests as the bitmap-path oracle).

    ``packed=True`` (default) bit-packs the vertex axis into u32 words —
    32x smaller than the dense u8 bitmap, the difference between "fine at
    test scale" and "fits at web scale".  Bits are set exactly once via a
    lexsort dedupe + scatter-add (add of once-only power-of-two values ==
    bitwise OR), and the cross-device union is an all-gather + local OR:
    integer ``pmax`` on packed words is *not* a bitwise OR, and psum would
    carry between bits, so the packed path trades the dense psum for
    moving ``n_devices`` copies of a 32x smaller tensor — less wire bytes
    up to 32 devices, identical (exact) results at any device count.
    ``packed=False`` keeps the dense u8 psum/pmax merge as the oracle
    path for parity tests.
    """
    vert_vid, n_verts, valid, perms, codes_all, canon = \
        _canonical_edge_codes(ctx, app, levels)
    cap, V = vert_vid.shape
    n_eff = ctx.n_labels + 1
    Pn = app.max_patterns

    local_uniq = jnp.unique(canon, size=Pn, fill_value=_INT_MAX)
    gathered = local_uniq
    for ax in axis_names:
        gathered = jax.lax.all_gather(gathered, ax).reshape(-1)
    uniq = jnp.unique(gathered, size=Pn, fill_value=_INT_MAX)
    pat_valid = uniq != _INT_MAX
    # local embeddings -> global pattern slots (uniq is sorted).  A code
    # beyond a truncated table must contribute nowhere (not be clamped
    # into slot Pn-1 and inflate its support): require an exact hit.
    pat = jnp.minimum(jnp.searchsorted(uniq, canon), Pn - 1).astype(
        jnp.int32)
    hit = uniq[pat] == canon

    park = Pn * V
    dom, vid, ok, bucket = _domain_contributions(
        vert_vid, n_verts, valid & hit, perms, codes_all, canon, pat, park)
    if packed:
        n_words = -(-ctx.n_vertices // 32)
        vid_c = jnp.clip(vid, 0, ctx.n_vertices - 1)
        # set each (bucket, vertex) bit exactly once: lexsort + adjacent-
        # unique dedupe, then one scatter-add of the per-vertex bit value
        # (a once-only sum of distinct powers of two is a bitwise OR)
        order = jnp.lexsort((vid_c, bucket))
        bucket_s, vid_s = bucket[order], vid_c[order]
        first = jnp.ones(bucket_s.shape, bool)
        first = first.at[1:].set((bucket_s[1:] != bucket_s[:-1])
                                 | (vid_s[1:] != vid_s[:-1]))
        sel = first & (bucket_s < park)
        bit = jnp.where(sel,
                        jnp.uint32(1) << (vid_s & 31).astype(jnp.uint32),
                        jnp.uint32(0))
        member = jnp.zeros((park + 1, n_words), jnp.uint32)
        member = member.at[jnp.minimum(bucket_s, park), vid_s >> 5].add(bit)
        member = member[:park]
        for ax in axis_names:    # set union = all-gather + bitwise OR
            devs = jax.lax.all_gather(member, ax)
            member = devs[0]
            for d in range(1, devs.shape[0]):
                member = member | devs[d]
        distinct = jnp.sum(jax.lax.population_count(member).astype(
            jnp.int32), axis=1)
    else:
        member = jnp.zeros((park + 1, ctx.n_vertices), jnp.uint8)
        member = member.at[bucket,
                           jnp.clip(vid, 0, ctx.n_vertices - 1)].max(
            ok.astype(jnp.uint8))
        member = member[:park]
        for ax in axis_names:    # pmax == set union, device-count-proof
            member = jax.lax.pmax(member, ax)
        distinct = jnp.sum((member > 0).astype(jnp.int32), axis=1)
    distinct = distinct.reshape(Pn, V)
    return _domain_support(ctx, app, uniq, pat_valid, distinct, pat, valid,
                           V, n_eff)


# ---------------------------------------------------------------------------
# FILTER phase (paper Alg. 2 lines 14-17)


def filter_levels(levels: list[EmbeddingLevel], keep: jnp.ndarray,
                  out_cap: int) -> list[EmbeddingLevel]:
    """Compact the last level by ``keep`` (support-based pruning)."""
    last = levels[-1]
    cap = last.vid.shape[0]
    keep = keep & (jnp.arange(cap, dtype=jnp.int32) < last.n)
    gather, n_new = compact_mask(keep, out_cap)
    live = jnp.arange(out_cap) < n_new
    new_last = EmbeddingLevel(
        vid=jnp.where(live, last.vid[gather], -1).astype(jnp.int32),
        idx=jnp.where(live, last.idx[gather], 0).astype(jnp.int32),
        n=n_new,
        his=None if last.his is None else
            jnp.where(live, last.his[gather], 0).astype(jnp.int32),
        eid=None if last.eid is None else
            jnp.where(live, last.eid[gather], -1).astype(jnp.int32),
    )
    return levels[:-1] + [new_last]


# ---------------------------------------------------------------------------
# Backend assembly


class ReferenceBackend(PhaseBackend):
    """All phases in plain jnp — correct on any XLA target, CPU included."""

    name = "reference"

    # -- primitives
    def expand_ragged(self, counts, capacity):
        return expand_ragged(counts, capacity)

    def compact_mask(self, mask, capacity):
        return compact_mask(mask, capacity)

    # -- vertex EXTEND (enumeration is the backend-swappable step)
    def _vertex_candidates(self, ctx, app, emb, n_valid, state, cand_cap):
        return _vertex_candidates(ctx, app, emb, n_valid, state, cand_cap)

    def candidate_bound_vertex(self, ctx, app, emb, n_valid, state=None):
        return candidate_bound_vertex(ctx, app, emb, n_valid, state)

    def _enumerates_in_xla(self) -> bool:
        """True unless a subclass swapped the enumeration step: the
        chunked extend is this class's own enumeration."""
        return (type(self)._vertex_candidates
                is ReferenceBackend._vertex_candidates)

    def inspect_vertex(self, ctx, app, emb, n_valid, state, cand_cap):
        if self._enumerates_in_xla():
            level, _, total, _ = extend_vertex_chunked(
                ctx, app, emb, n_valid, state, cand_cap, 1)
            return total, level.n
        _, _, _, add, total = self._vertex_candidates(ctx, app, emb,
                                                      n_valid, state,
                                                      cand_cap)
        return total, jnp.sum(add.astype(jnp.int32))

    def extend_vertex(self, ctx, app, emb, n_valid, state, cand_cap,
                      out_cap, fuse_filter=True):
        emb, state = _pad_empty_frontier(emb, state)
        row, u, _, add, _ = self._vertex_candidates(ctx, app, emb, n_valid,
                                                    state, cand_cap)
        return finish_extend_vertex(emb, row, u, add, out_cap, fuse_filter)

    def extend_pruned(self, ctx, app, emb, n_valid, state, cand_cap,
                      out_cap, fuse_filter=True):
        if fuse_filter and self._enumerates_in_xla():
            return extend_vertex_chunked(ctx, app, emb, n_valid, state,
                                         cand_cap, out_cap)
        emb, state = _pad_empty_frontier(emb, state)
        row, u, src_slot, add, total = self._vertex_candidates(
            ctx, app, emb, n_valid, state, cand_cap)
        upd = resolve_state_kernel(app, emb.shape[1])
        new_st = (None if upd is None
                  else apply_state_kernel(ctx, upd, emb, row, u, src_slot,
                                          state))
        level, new_emb = finish_extend_vertex(emb, row, u, add, out_cap,
                                              fuse_filter,
                                              new_state=new_st)
        return level, new_emb, total, jnp.int32(0)

    # -- edge EXTEND (enumeration is the backend-swappable step, like
    #    _vertex_candidates)
    def _edge_candidates(self, ctx, app, v0, vid, his, eid, n_valid,
                         cand_cap):
        return _edge_candidates(ctx, app, v0, vid, his, eid, n_valid,
                                cand_cap)

    def candidate_bound_edge(self, ctx, app, v0, vid, his, n_valid):
        return candidate_bound_edge(ctx, app, v0, vid, his, n_valid)

    def inspect_edge(self, ctx, app, v0, vid, his, eid, n_valid, cand_cap):
        _, _, _, _, add, total = self._edge_candidates(
            ctx, app, v0, vid, his, eid, n_valid, cand_cap)
        return total, jnp.sum(add.astype(jnp.int32))

    def extend_edge(self, ctx, app, v0, vid, his, eid, n_valid, cand_cap,
                    out_cap):
        row, s, u, new_eid, add, total = self._edge_candidates(
            ctx, app, v0, vid, his, eid, n_valid, cand_cap)
        return finish_extend_edge(row, s, u, new_eid, add, out_cap), total

    # -- REDUCE / FILTER
    def reduce_count(self, ctx, app, emb, n_valid, state):
        return reduce_count(ctx, app, emb, n_valid, state)

    def reduce_domain(self, ctx, app, levels):
        return reduce_domain(ctx, app, levels)

    def reduce_domain_sharded(self, ctx, app, levels, axis_names):
        return reduce_domain_sharded(ctx, app, levels, axis_names)

    def filter_levels(self, levels, keep, out_cap):
        return filter_levels(levels, keep, out_cap)
