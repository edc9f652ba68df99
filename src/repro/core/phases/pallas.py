"""Fused-Pallas phase backend: kernel-accelerated vertex EXTEND.

Swaps the reference backend's candidate enumeration (``expand_ragged`` +
three separate CSR gathers + per-hook ``isConnected`` searches) for one
fused VMEM-tiled kernel (:mod:`repro.kernels.extend_fused`) that emits
(parent row, candidate u, source slot, k-way connectivity bitmask) per
candidate slot.  The ``toAdd`` filter is then evaluated from the bitmask:
``app.to_add_bits`` when the app provides it, else the bits-based
automorphism-canonical test — no second pass over the adjacency.

When the app's predicate is expressible in the elementwise
``to_add_kernel`` form (:func:`repro.core.api.resolve_kernel_predicate`),
:meth:`extend_pruned` goes further: the predicate *and* the exclusive-scan
stream compaction run inside the kernel, connectivity is answered from
the u32 bit-packed adjacency bitmap (``ctx.packed``, one word gather per
probe instead of a log-depth binary search), and only the compacted
survivor buffer — ``out_cap``-scale, not ``cand_cap``-scale — ever
reaches HBM.  This is the paper's eager pruning (§4) fused end to end.

Everything downstream (reduce, filter, the whole edge-induced pipeline)
is inherited from the reference backend; per-op fallback is the intended
composition model — a backend overrides exactly the ops it accelerates.

Notes:
  * ``interpret=None`` auto-selects interpreter mode off-TPU, so the same
    backend name works on the CPU CI box and on real hardware.
  * Connectivity inside the pruned kernel is three-mode: full bitmap
    when every row is packed, *mixed* when only a partial (high-degree)
    pack fits the byte budget — packed rows answer from the bitmap, the
    tail binary-searches the CSR (the power-law case) — and pure binary
    search with no pack (the paper's §5.4 choice).  The
    ``search="linear"`` ablation knob only affects the reference backend.
  * The bits-based default canonical test assumes symmetric adjacency
    (undirected input graph).  For ``use_dag`` apps without a
    ``to_add_bits``/``to_add`` hook, ``vertex_add_mask`` falls back to
    re-probing the CSR with the reference canonical test (the two
    ``isConnected`` directions differ on an oriented DAG).
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core.api import (GraphCtx, MiningApp, resolve_kernel_predicate,
                            resolve_state_kernel)
from repro.core.embedding_list import EmbeddingLevel
from repro.core.phases.reference import (ReferenceBackend, edge_vertex_slots,
                                         vertex_add_mask,
                                         vertex_ext_degrees)
from repro.kernels.extend_fused import (fused_extend, fused_extend_edge,
                                        fused_extend_pruned)
from repro.kernels.runtime import require_lowerable, resolve_interpret


class PallasExtendBackend(ReferenceBackend):
    """Reference pipeline with the vertex EXTEND enumeration fused."""

    name = "pallas"
    compaction = "sequential-smem"
    compaction_passes = 1
    grid_contract = "sequential"

    # the extend_pruned entry point (bound so the MP subclass swaps only
    # this, keeping every line of input prep shared)
    _pruned_kernel = staticmethod(fused_extend_pruned)

    def __init__(self, interpret: bool | None = None, block_c: int = 512):
        self.interpret = interpret
        self.block_c = block_c

    # the Pallas kernels this backend launches (keys of TPU_REFUSALS)
    kernels = ("fused_extend", "fused_extend_pruned", "fused_extend_edge")

    def _use_interpret(self) -> bool:
        return resolve_interpret(self.interpret)

    def check_device(self) -> None:
        require_lowerable(self.kernels, f"backend={self.name!r}",
                          self._use_interpret())

    # -- capability report -------------------------------------------------

    @staticmethod
    def _edge_fusible(ctx: GraphCtx | None, app: MiningApp) -> bool:
        """The fused edge kernel handles canonical test + per-vertex eager
        mask; a general batch ``to_add`` hook forces the XLA fallback."""
        app_ok = app.to_add is None or app.to_add_vertex_mask is not None
        if ctx is None:
            return app_ok
        return app_ok and ctx.edge_uid is not None and ctx.usrc is not None

    def capabilities(self, app: MiningApp | None = None) -> dict:
        caps = super().capabilities(app)
        caps["extend_vertex"] = "fused-kernel"
        if app is None:
            caps["extend_pruned"] = "fused-kernel"
            caps["extend_edge"] = "fused-kernel"
            return caps
        if app.kind == "vertex":
            caps["extend_edge"] = "n/a"
            ks = range(2, max(app.max_size, 3))
            if all(resolve_kernel_predicate(app, k) is not None for k in ks):
                caps["extend_pruned"] = "fused-kernel"
            else:
                caps["extend_pruned"] = "xla-fallback:no-kernel-predicate"
        else:
            caps["extend_pruned"] = "n/a"
            caps["extend_vertex"] = "n/a"
            if self._edge_fusible(None, app):
                caps["extend_edge"] = "fused-kernel"
            else:
                caps["extend_edge"] = "xla-fallback:batch-to-add"
        return caps

    @staticmethod
    def _kernel_inputs(ctx: GraphCtx, app: MiningApp, emb: jnp.ndarray,
                       n_valid: jnp.ndarray, state=None):
        deg = vertex_ext_degrees(ctx, app, emb, n_valid, state)
        counts = deg.reshape(-1).astype(jnp.int32)
        offsets = jnp.cumsum(counts)                  # inclusive prefix sum
        starts = offsets - counts
        embc = jnp.clip(emb, 0, ctx.n_vertices - 1).reshape(-1)
        vlo = ctx.row_ptr[embc]
        vhi = ctx.row_ptr[embc + 1]
        return offsets, starts, vlo, vhi

    def _vertex_candidates(self, ctx: GraphCtx, app: MiningApp,
                           emb: jnp.ndarray, n_valid: jnp.ndarray,
                           state, cand_cap: int):
        cap, k = emb.shape
        offsets, starts, vlo, vhi = self._kernel_inputs(ctx, app, emb,
                                                        n_valid, state)
        total = offsets[-1].astype(jnp.int32)
        row, u, src_slot, conn = fused_extend(
            ctx.col_idx, offsets, starts, emb.reshape(-1), vlo, vhi,
            k=k, cand_cap=cand_cap, n_steps=ctx.n_steps,
            block_c=self.block_c, interpret=self._use_interpret())
        live = jnp.arange(cand_cap, dtype=jnp.int32) < total
        row_c = jnp.clip(row, 0, cap - 1)
        u = jnp.where(live, u, -1)
        conn_b = (((conn[:, None] >> jnp.arange(k, dtype=jnp.int32)[None, :])
                   & 1).astype(bool) & live[:, None])
        pred = resolve_kernel_predicate(app, k)
        if pred is not None:
            # same predicate resolution as extend_pruned (and as the
            # reference backend), so inspection counts and extension
            # survivors can never drift apart
            parent = emb[row_c]
            st = (jnp.zeros(u.shape, jnp.int32) if state is None
                  else state[row_c])
            emb_cols = tuple(parent[:, j] for j in range(k))
            conn_cols = tuple(conn_b[:, j] for j in range(k))
            if getattr(pred, "needs_labels", False):
                labels = (ctx.labels if ctx.labels is not None
                          else jnp.zeros((1,), jnp.int32))
                nv = labels.shape[0]
                lab_cols = tuple(labels[jnp.clip(c, 0, nv - 1)]
                                 for c in emb_cols)
                lab_u = labels[jnp.clip(u, 0, nv - 1)]
                add = pred(emb_cols, u, src_slot, st, conn_cols, lab_cols,
                           lab_u) & live
            else:
                add = pred(emb_cols, u, src_slot, st, conn_cols) & live
        else:
            add = vertex_add_mask(ctx, app, emb, row_c, u, src_slot, state,
                                  live, conn=conn_b)
        return row_c, u, src_slot, add, total

    def extend_pruned(self, ctx: GraphCtx, app: MiningApp, emb: jnp.ndarray,
                      n_valid: jnp.ndarray, state, cand_cap: int,
                      out_cap: int, fuse_filter: bool = True):
        pred = resolve_kernel_predicate(app, emb.shape[1])
        if pred is None or not fuse_filter:
            # hooks not expressible in-kernel (or the materialize-then-
            # filter ablation): full-buffer enumeration + host-side hook
            return super().extend_pruned(ctx, app, emb, n_valid, state,
                                         cand_cap, out_cap,
                                         fuse_filter=fuse_filter)
        cap, k = emb.shape
        offsets, starts, vlo, vhi = self._kernel_inputs(ctx, app, emb,
                                                        n_valid, state)
        total = offsets[-1].astype(jnp.int32)
        st = (jnp.zeros((cap,), jnp.int32) if state is None
              else state.astype(jnp.int32))
        # connectivity-probe mode: full pack -> pure bitmap; partial pack
        # -> mixed (bitmap for packed rows, CSR binary search for the
        # tail — the power-law case where only high-degree rows fit the
        # pack budget); no pack -> CSR search only
        pg = ctx.packed
        if pg is not None and pg.full:
            conn_mode, n_rows = "bitmap", pg.n_packed
            bits = pg.words.reshape(-1)
            row_slot = jnp.zeros((1,), jnp.int32)
        elif pg is not None:
            conn_mode, n_rows = "mixed", pg.n_packed
            bits = pg.words.reshape(-1)
            row_slot = pg.row_slot
        else:
            conn_mode, n_rows = "search", 1
            bits = jnp.zeros((1,), jnp.uint32)
            row_slot = jnp.zeros((1,), jnp.int32)
        n_words = pg.n_words if pg is not None else 1
        n_cols = pg.n_cols if pg is not None else ctx.n_vertices
        upd = resolve_state_kernel(app, k)
        *out, n_surv = self._pruned_kernel(
            ctx.col_idx, offsets, starts, emb.reshape(-1), vlo, vhi, st,
            bits, row_slot, ctx.labels, k=k, cand_cap=cand_cap,
            out_cap=out_cap, n_steps=ctx.n_steps, n_vertices=ctx.n_vertices,
            n_words=n_words, n_rows=n_rows, n_cols=n_cols, pred=pred,
            state_upd=upd, conn_mode=conn_mode, block_c=self.block_c,
            interpret=self._use_interpret())
        row, u = out[0], out[1]
        st_out = out[2] if upd is not None else None
        live_out = jnp.arange(out_cap, dtype=jnp.int32) < n_surv
        vid = jnp.where(live_out, u, -1).astype(jnp.int32)
        idx = jnp.where(live_out, jnp.clip(row, 0, cap - 1),
                        0).astype(jnp.int32)
        level = EmbeddingLevel(vid=vid, idx=idx, n=n_surv, state=st_out)
        new_emb = jnp.concatenate([emb[idx], vid[:, None]], axis=1)
        return level, new_emb, total, jnp.int32(0)

    def _edge_candidates(self, ctx: GraphCtx, app: MiningApp, v0, vid, his,
                         eid, n_valid: jnp.ndarray, cand_cap: int):
        """Edge-induced enumeration, fused (paper §5.2 for the FSM path).

        The inspection-scale work (slot freshness, toExtend mask, degree
        prefix sum — all [cap, E+1]) stays in XLA; the candidate-scale
        work (ragged expand, CSR/uid gathers, canonical-edge test, eager
        per-vertex toAdd mask) runs in one tile-independent kernel, so
        dead candidates cost one VMEM lane instead of five HBM columns.
        Apps with a general batch ``to_add`` (not expressible as a
        per-vertex mask) fall back to the reference enumeration.
        """
        if not self._edge_fusible(ctx, app) or ctx.n_edges == 0 \
                or vid.shape[0] == 0:
            return super()._edge_candidates(ctx, app, v0, vid, his, eid,
                                            n_valid, cand_cap)
        cap, E = vid.shape
        n_slots = E + 1
        slots, fresh = edge_vertex_slots(v0, vid, his)
        valid = jnp.arange(cap, dtype=jnp.int32) < n_valid
        ext = fresh & valid[:, None]
        if app.to_extend is not None:
            ext = ext & app.to_extend(ctx, slots)
        deg = jnp.where(ext, ctx.degree(slots), 0)
        counts = deg.reshape(-1).astype(jnp.int32)
        offsets = jnp.cumsum(counts)                  # inclusive prefix sum
        starts = offsets - counts
        total = offsets[-1].astype(jnp.int32)
        slots_c = jnp.clip(slots, 0, ctx.n_vertices - 1).reshape(-1)
        vlo = ctx.row_ptr[slots_c]
        vmask = None
        if app.to_add_vertex_mask is not None:
            vmask = app.to_add_vertex_mask(ctx).astype(jnp.int32)
        row, s, u, new_eid, add = fused_extend_edge(
            ctx.col_idx, ctx.edge_uid, offsets, starts, slots_c, vlo,
            eid.reshape(-1), ctx.usrc, ctx.udst, vmask,
            n_slots=n_slots, cand_cap=cand_cap, n_uedges=ctx.n_uedges,
            n_vertices=ctx.n_vertices, block_c=self.block_c,
            interpret=self._use_interpret())
        return row, s, u, new_eid, add.astype(bool), total
