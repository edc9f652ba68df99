"""Locality-aware streaming edge-block scheduler (paper §5.2, out-of-core).

The paper's edge blocking bounds peak memory by mining the level-0
worklist in chunks; PR 2 implemented it as arbitrary id-range slices of
device-resident arrays.  This module makes blocking a first-class layer:

* **Block construction** — contiguous worklist ranges (post-relabel,
  contiguity == locality: :func:`repro.graph.csr.relabel` puts the hot
  high-degree core in the id prefix, so early blocks share the packed
  adjacency core and late blocks the sparse tail).  Block size comes
  either from the caller or from a *byte budget* via the analytic
  live-bytes model below (:func:`auto_block_size`).
* **Live-bytes model** — :func:`estimate_live_bytes` prices one block's
  device residency from its capacity plan: the SoA embedding-list
  columns of every level, the widest materialized frontier, and the
  transient candidate buffers of the largest extend.  Deterministic and
  monotone in every capacity, so blocked runs are bounded below
  unblocked ones by construction; it is also the bench's
  ``peak_live_bytes`` field.
* **Streaming queue** — :class:`BlockQueue` keeps the full worklist
  host-side (numpy) and stages one block at a time to the device,
  double-buffered: the ``device_put`` of block i+1 is issued *before*
  block i is consumed, so the host->device copy of the next block
  overlaps the current block's mining (JAX async dispatch).  Only the
  active block's padded level-0 arrays — plus one in flight — are ever
  device-resident.

The sharded path reuses the same block construction:
:func:`repro.core.engine.mine_sharded` distributes one contiguous block
per device (:func:`stack_blocks`) instead of ad-hoc pad-and-reshape
ranges.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Iterable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.plan import bucket_pow2
from repro.obs import metrics as _M
from repro.obs import trace as _T

# Bytes per i32 column element; every embedding-list column is i32.
_W = 4


@dataclasses.dataclass(frozen=True)
class EdgeBlock:
    """One contiguous level-0 worklist range ``[lo, lo + n)``."""

    index: int
    lo: int
    n: int


def make_blocks(m: int, block_size: int,
                count: Optional[int] = None) -> list[EdgeBlock]:
    """Split an ``m``-entry worklist into contiguous blocks.

    ``count`` forces exactly that many blocks (trailing ones possibly
    empty) — the sharded path needs one block per device.
    """
    block_size = max(int(block_size), 1)
    blocks = [EdgeBlock(index=i, lo=lo, n=min(block_size, m - lo))
              for i, lo in enumerate(range(0, max(m, 0), block_size))]
    if not blocks:
        blocks = [EdgeBlock(index=0, lo=0, n=0)]
    if count is not None:
        if len(blocks) > count:
            raise ValueError(f"{len(blocks)} blocks of {block_size} "
                             f"exceed requested count {count}")
        blocks += [EdgeBlock(index=i, lo=m, n=0)
                   for i in range(len(blocks), count)]
    return blocks


def estimate_live_bytes(kind: str,
                        caps: Sequence[tuple[int, int]],
                        filter_caps: Sequence[int] = (),
                        cap0: int = 0) -> int:
    """Analytic peak of device-resident mining bytes for one (blocked) run.

    Prices what the pipelines actually keep live at the deepest level:

    * every level's SoA columns — level 0 holds 2 columns (vertex: vid +
      idx; edge: the four (vid, idx, his, eid) columns) plus the memo
      state, each extension level its ``out_cap``-sized columns;
    * the widest materialized frontier (vertex: the ``[cap, k]``
      embedding matrix; edge: the per-slot expansion of all levels);
    * the transient candidate buffers of the largest extend (row / u /
      src_slot / conn at ``cand_cap`` scale).

    Exact constants matter less than the contract: deterministic, and
    monotone in ``cap0`` and every planned capacity — so a blocked run
    (every cap scaled down by the block ratio) always prices below the
    unblocked run, which is the bound the bench's ``peak_live_bytes``
    column reports.
    """
    cap0 = int(cap0)
    caps = [(int(c), int(o)) for c, o in caps]
    if kind == "vertex":
        total = 3 * _W * cap0                      # vid + idx + state
        width = 2
        frontier = _W * cap0 * width               # materialized emb matrix
        cand_peak = 0
        for cand_cap, out_cap in caps:
            width += 1
            total += 3 * _W * out_cap              # vid + idx + state
            frontier = max(frontier, _W * out_cap * width)
            cand_peak = max(cand_peak, 4 * _W * cand_cap)
        return total + frontier + cand_peak
    # edge-induced: all levels stay live (the domain reduce walks them),
    # each level 4 columns; the frontier expands every level to E+1 slots
    total = 4 * _W * cap0
    level_caps = [cap0] + [o for _, o in caps]
    for fc in filter_caps:                         # post-filter compactions
        level_caps.append(int(fc))
    for c in level_caps[1:]:
        total += 4 * _W * c
    n_slots = len(caps) + 2
    deepest = max(level_caps) if level_caps else 0
    frontier = _W * deepest * (2 * n_slots + 2)    # v0, vid/his[E], eid[E]
    cand_peak = max((5 * _W * c for c, _ in caps), default=0)
    return total + frontier + cand_peak


def scale_caps(caps: Sequence[tuple[int, int]],
               filter_caps: Sequence[int], ratio: float
               ) -> tuple[tuple[tuple[int, int], ...], tuple[int, ...]]:
    """Scale a capacity schedule by a worklist ratio (floor 128, pow2/raw).

    Blocked runs reuse the full-worklist plan with every capacity scaled
    by ``block / worklist`` — per-level frontier sizes are roughly
    proportional to the level-0 size for contiguous blocks of a
    degree-relabeled worklist.  The executor's grow-on-overflow backstop
    covers skewed blocks (the hot-core block extends far more than the
    tail block).
    """
    ratio = float(ratio)
    sc = tuple((bucket_pow2(int(np.ceil(c * ratio))),
                max(-(-int(np.ceil(o * ratio)) // 128) * 128, 128))
               for c, o in caps)
    fc = tuple(max(-(-int(np.ceil(f * ratio)) // 128) * 128, 128)
               for f in filter_caps)
    return sc, fc


def auto_block_size(m: int, caps: Sequence[tuple[int, int]],
                    filter_caps: Sequence[int], budget_bytes: int,
                    kind: str = "vertex", min_block: int = 128) -> int:
    """Pick the largest block size whose estimated live bytes fit a budget.

    ``caps``/``filter_caps`` describe the *full-worklist* plan (from the
    sampled estimator or a finished inspection pass); candidate block
    sizes walk down the power-of-two grid, pricing each with the plan
    scaled by the block ratio.  Returns ``m`` when even the unblocked
    run fits (no blocking needed); floors at ``min_block`` when not even
    the smallest block fits (the budget is then advisory — mining still
    needs one block's buffers).
    """
    m = max(int(m), 1)
    if estimate_live_bytes(kind, caps, filter_caps, bucket_pow2(m)) \
            <= budget_bytes:
        return m
    b = bucket_pow2(m) // 2
    while b > min_block:
        sc, fc = scale_caps(caps, filter_caps, b / m)
        if estimate_live_bytes(kind, sc, fc, b) <= budget_bytes:
            return b
        b //= 2
    return min_block


class BlockQueue:
    """Double-buffered host->device staging of level-0 worklist blocks.

    ``arrays`` are the full worklist columns (host numpy); iteration
    yields ``(block, device_columns)`` with each column zero-padded to
    ``cap0``.  The next block's ``device_put`` is dispatched before the
    current block is handed to the consumer, so its H2D copy overlaps
    the current block's mining (JAX's async dispatch); at most two
    blocks' level-0 arrays exist on device at once.
    """

    def __init__(self, arrays: Iterable[np.ndarray],
                 blocks: Sequence[EdgeBlock], cap0: int):
        self.arrays = [np.asarray(a) for a in arrays]
        self.blocks = list(blocks)
        self.cap0 = int(cap0)

    def _stage(self, blk: EdgeBlock):
        with _T.span("block.stage", cat="blocks", index=blk.index,
                     n=blk.n):
            out = []
            for a in self.arrays:
                buf = np.zeros((self.cap0,), dtype=a.dtype)
                if blk.n:
                    buf[: blk.n] = a[blk.lo: blk.lo + blk.n]
                out.append(jax.device_put(buf))
            return tuple(out)

    def __len__(self) -> int:
        return len(self.blocks)

    def __iter__(self):
        """Yield ``(block, staged_columns)``; records overlap metrics.

        Host time between a yield and the generator's re-entry is the
        consumer *mining* the block; time inside :meth:`_stage` is the
        host-side staging work that double-buffering is meant to hide.
        ``blocks.stage_overlap`` = mine / (mine + stage): 1.0 means
        staging cost no extra wall time (fully overlapped / negligible);
        recorded in a ``finally`` so early exits still report.
        """
        stage_s = mine_s = 0.0
        try:
            t0 = time.perf_counter()
            nxt = self._stage(self.blocks[0]) if self.blocks else None
            stage_s += time.perf_counter() - t0
            for i, blk in enumerate(self.blocks):
                t0 = time.perf_counter()
                cur, nxt = nxt, (self._stage(self.blocks[i + 1])
                                 if i + 1 < len(self.blocks) else None)
                dt = time.perf_counter() - t0
                stage_s += dt
                _M.observe("blocks.stage_ms", dt * 1e3)
                t0 = time.perf_counter()
                yield blk, cur
                dt = time.perf_counter() - t0
                mine_s += dt
                _M.observe("blocks.mine_ms", dt * 1e3)
        finally:
            total = stage_s + mine_s
            if total > 0:
                _M.set_gauge("blocks.stage_overlap", mine_s / total)


def stack_blocks(arrays: Iterable[np.ndarray], blocks: Sequence[EdgeBlock],
                 cap0: int) -> tuple[jnp.ndarray, ...]:
    """Stage every block at once into stacked ``[n_blocks, cap0]`` arrays.

    The sharded path's form: one contiguous block per device, stacked so
    ``shard_map`` scatters row i to device i.  Same padding contract as
    :class:`BlockQueue` (zero-fill past ``block.n``).
    """
    arrays = [np.asarray(a) for a in arrays]
    out = []
    for a in arrays:
        buf = np.zeros((len(blocks), int(cap0)), dtype=a.dtype)
        for i, blk in enumerate(blocks):
            if blk.n:
                buf[i, : blk.n] = a[blk.lo: blk.lo + blk.n]
        out.append(jnp.asarray(buf))
    return tuple(out)
