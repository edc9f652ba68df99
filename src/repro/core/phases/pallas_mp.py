"""Massively-parallel Pallas backend: concurrent-grid two-pass compaction.

``PallasMPBackend`` is :class:`PallasExtendBackend` with exactly one
substitution: ``extend_pruned`` calls the two-pass kernel pair
(:func:`repro.kernels.extend_fused.fused_extend_pruned_mp`) instead of the
sequential-grid kernel.  Everything else — input prep, connectivity-mode
selection, label plumbing, the fused edge enumeration, the plain
``fused_extend`` enumeration — is shared with the sequential backend,
because those kernels are already tile-independent.

Why a separate backend instead of a flag: the compaction strategy is part
of the *plan identity* (``repro.core.plan.plan_app_key`` folds the
backend's ``compaction`` attribute), and the sequential kernel's SMEM
running offset is a grid-ordering assumption that concurrent-tile
architectures (the GPU side of the paper's §6 claims) do not satisfy.
The two-pass split pays one predicate replay per tile to delete that
assumption:

  pass 1  every tile enumerates + filters independently and emits one
          survivor count — no scratch, no carry;
  scan    XLA exclusive-scans the ``i32[n_tiles]`` count buffer (sized by
          the planner's ``cand_cap``) into per-tile base offsets; the
          scan total is the true survivor count that drives the planner's
          overflow flag exactly as in the sequential path;
  pass 2  every tile re-runs the (deterministic, VMEM-cheap) predicate,
          compacts in-tile, and masked-scatters its survivors — and the
          compacted ``state`` column — into its disjoint output window.

Results are bitwise-identical to the sequential backend and the
reference backend (asserted across the backend-parity matrix and the
benchmark suite).
"""
from __future__ import annotations

from repro.core.phases.pallas import PallasExtendBackend
from repro.kernels.extend_fused import fused_extend_pruned_mp


class PallasMPBackend(PallasExtendBackend):
    """Concurrent-grid (GPU-style) variant of the fused Pallas backend."""

    name = "pallas-mp"
    compaction = "two-pass-scan"
    compaction_passes = 2
    grid_contract = "concurrent"

    _pruned_kernel = staticmethod(fused_extend_pruned_mp)
    kernels = ("fused_extend", "fused_extend_pruned_mp", "fused_extend_edge")

    def extend_pruned(self, ctx, app, emb, n_valid, state, cand_cap,
                      out_cap, fuse_filter=True):
        # note_op in the parent records mode/compaction under self.name
        # ("pallas-mp"), so the metrics dump distinguishes two-pass-scan
        # tracings from the sequential backend's.
        return super().extend_pruned(ctx, app, emb, n_valid, state,
                                     cand_cap, out_cap,
                                     fuse_filter=fuse_filter)
