"""Shared sparse/ragged primitives.

JAX has no native EmbeddingBag or CSR SpMM — message passing and embedding
lookups are built from ``jnp.take`` + ``jax.ops.segment_*`` here, exactly as
the assignment requires.  These primitives are the common substrate for

  * the Pangolin mining engine (ragged neighbor expansion + compaction —
    the paper's inspection-execution, §5.3),
  * GNN message passing (GraphSAGE/GAT/NequIP/Equiformer),
  * recsys embedding bags (DIEN).

All functions are jit-/vmap-/pjit-safe: static output sizes, no host sync.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def segment_sum(data: jnp.ndarray, segment_ids: jnp.ndarray,
                num_segments: int) -> jnp.ndarray:
    return jax.ops.segment_sum(data, segment_ids, num_segments=num_segments)


def segment_max(data: jnp.ndarray, segment_ids: jnp.ndarray,
                num_segments: int) -> jnp.ndarray:
    return jax.ops.segment_max(data, segment_ids, num_segments=num_segments)


def segment_mean(data: jnp.ndarray, segment_ids: jnp.ndarray,
                 num_segments: int) -> jnp.ndarray:
    tot = segment_sum(data, segment_ids, num_segments)
    cnt = segment_sum(jnp.ones(data.shape[:1], data.dtype), segment_ids,
                      num_segments)
    return tot / jnp.maximum(cnt, 1.0)[(...,) + (None,) * (tot.ndim - 1)]


def edge_softmax(scores: jnp.ndarray, dst: jnp.ndarray,
                 num_nodes: int) -> jnp.ndarray:
    """Softmax over incoming edges per destination node (GAT).

    scores: f[E, ...heads]; dst: i32[E]. Returns normalized scores.
    """
    smax = jax.ops.segment_max(scores, dst, num_segments=num_nodes)
    # gather max back to edges; subtract for stability
    shift = scores - smax[dst]
    ex = jnp.exp(shift)
    denom = jax.ops.segment_sum(ex, dst, num_segments=num_nodes)
    return ex / jnp.maximum(denom[dst], 1e-30)


def embedding_bag(table: jnp.ndarray, indices: jnp.ndarray,
                  bag_ids: jnp.ndarray, num_bags: int,
                  mode: str = "sum",
                  weights: jnp.ndarray | None = None) -> jnp.ndarray:
    """EmbeddingBag built from take + segment ops (no torch analogue in JAX).

    table: f[V, D]; indices: i32[N] (flattened multi-hot ids);
    bag_ids: i32[N] mapping each index to its bag; returns f[num_bags, D].
    """
    rows = jnp.take(table, indices, axis=0)
    if weights is not None:
        rows = rows * weights[:, None]
    if mode == "sum":
        return segment_sum(rows, bag_ids, num_bags)
    if mode == "mean":
        return segment_mean(rows, bag_ids, num_bags)
    if mode == "max":
        return segment_max(rows, bag_ids, num_bags)
    raise ValueError(mode)


def expand_ragged(counts: jnp.ndarray, capacity: int
                  ) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Inspection-execution ragged expansion (paper §5.3, vectorized).

    Given per-parent candidate counts, produce for each output slot
    ``j < capacity`` the parent it belongs to and its rank within that
    parent.  This is step 2 of the paper's three-step generation: step 1
    (count) is the caller's gather of degrees; step 3 (write) is the
    caller's gather at (parent, rank).

    Each slot binary-searches the offsets, a chain of gathers; the vertex
    path's chunked extend (``reference.extend_vertex_chunked``) carries
    parents across slots with prefix sums instead.  Here the search
    stays: on the TPU the prefix-sum form tripled the compile time of the
    edge-induced (FSM) executor.

    Returns (parent: i32[capacity], rank: i32[capacity], total: i32[]).
    Slots >= total are padded with parent == -1.
    """
    counts = counts.astype(jnp.int32)
    offsets = jnp.cumsum(counts)                      # inclusive prefix sum
    total = offsets[-1] if counts.shape[0] else jnp.int32(0)
    starts = offsets - counts                         # exclusive prefix sum
    slots = jnp.arange(capacity, dtype=jnp.int32)
    # parent[j] = index of first offset > j  (searchsorted right on inclusive)
    parent = jnp.searchsorted(offsets, slots, side="right").astype(jnp.int32)
    valid = slots < total
    parent = jnp.where(valid, parent, -1)
    # empty-frontier guard: gathering from a zero-length starts is invalid
    starts = starts if counts.shape[0] else jnp.zeros(1, jnp.int32)
    rank = jnp.where(valid, slots - starts[jnp.clip(parent, 0, None)], 0)
    return parent, rank.astype(jnp.int32), total.astype(jnp.int32)


def compact_mask(mask: jnp.ndarray, capacity: int
                 ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Stable stream compaction by prefix sum (conflict-free scatter).

    Returns (gather_idx: i32[capacity], n_valid: i32[]) such that
    ``x[gather_idx]`` packs the masked elements of x to the front (slots
    >= n_valid point at 0 and must be treated as padding by the caller).
    Every element is added at its exclusive prefix, a non-decreasing
    position; unmasked elements add 0 there, so collisions are harmless.
    """
    mask = mask.astype(jnp.int32)
    pos = jnp.cumsum(mask) - mask                      # exclusive prefix sum
    n_valid = jnp.sum(mask).astype(jnp.int32)
    src = jnp.arange(mask.shape[0], dtype=jnp.int32)
    gather_idx = scatter_sorted(jnp.zeros((capacity,), jnp.int32), pos,
                                src * mask)
    return gather_idx, n_valid


def scatter_sorted(into: jnp.ndarray, idx: jnp.ndarray,
                   vals: jnp.ndarray) -> jnp.ndarray:
    """``into.at[idx].add(vals)`` for non-decreasing ``idx``, out-of-range
    entries dropped.  Saying that the indices are sorted spares the TPU
    compiler tens of seconds on a large scatter."""
    return into.at[idx].add(vals, mode="drop", indices_are_sorted=True)
