"""Triangle counting (paper §3.3).

TC is 3-clique finding; the engine path reuses the CF app.  The fused path
(`triangle_count_fused`) is the hand-optimized-equivalent: orient to a DAG
and sum |N+(u) ∩ N+(v)| over directed edges with the binary-search
intersection — the computation the Pallas ``intersect`` kernel implements
on TPU (Table 4a comparison point).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.api import MiningApp
from repro.core.apps.cf import make_cf_app
from repro.graph.csr import CSRGraph
from repro.graph.dag import orient_dag
from repro.sparse.intersect import intersect_count_sorted


def make_tc_app(use_dag: bool = True, eager_prune: bool = True) -> MiningApp:
    app = make_cf_app(3, use_dag=use_dag, eager_prune=eager_prune)
    return MiningApp(**{**app.__dict__, "name": "tc"})


# bound on rows * width of one intersection call (int32 lanes), so the
# device footprint of a call does not grow with the graph
_CHUNK_LANES = 1 << 24


def triangle_count_fused(g: CSRGraph, use_kernel: bool = False,
                         interpret: bool | None = None) -> int:
    """DAG + per-edge sorted-intersection count (no embedding lists).

    Directed DAG edges are bucketed by the shorter of their endpoints'
    out-degrees (powers of two), so each ``[rows, width]`` intersection
    tile is padded to its own bucket's width rather than the graph's
    maximum degree; ``_CHUNK_LANES`` bounds ``rows * width`` per call, which keeps
    the device footprint fixed however large the graph.  The CSR is an
    argument of every compiled call (one compile per bucket width).
    """
    dag = orient_dag(g)
    rp = np.asarray(dag.row_ptr)
    deg = np.diff(rp)
    src = np.repeat(np.arange(dag.n_vertices, dtype=np.int32), deg)
    dst = np.asarray(dag.col_idx)
    n_steps = max(1, math.ceil(math.log2(max(dag.max_degree, 1) + 1)))
    if use_kernel:
        from repro.kernels.intersect.ops import intersect_count
        from repro.kernels.runtime import (require_lowerable,
                                           resolve_interpret)
        require_lowerable(("intersect_count",),
                          "triangle_count_fused(use_kernel=True)",
                          resolve_interpret(interpret))
        count = functools.partial(intersect_count, interpret=interpret)
    else:
        count = _intersect_count_jit
    # |N+(a) ∩ N+(b)| is symmetric: walk the shorter list, search the other
    swap = deg[dst] < deg[src]
    src, dst = np.where(swap, dst, src), np.where(swap, src, dst)
    width_of = np.maximum(1, deg[src]).astype(np.int64)
    width_of = 1 << np.ceil(np.log2(width_of)).astype(np.int64)
    total = 0
    for width in np.unique(width_of):
        sel = np.nonzero(width_of == width)[0]
        rows = min(max(1, _CHUNK_LANES // int(width)),
                   1 << (int(sel.size) - 1).bit_length())
        for lo in range(0, sel.size, rows):
            e = np.zeros(rows, np.int64)
            part = sel[lo:lo + rows]
            e[:part.size] = part
            live = np.arange(rows) < part.size
            a, b = src[e], dst[e]
            # padding rows get empty A segments (lo == hi) and count 0
            cnt = count(dag.col_idx, jnp.asarray(rp[a]),
                        jnp.asarray(np.where(live, rp[a + 1], rp[a])),
                        jnp.asarray(rp[b]), jnp.asarray(rp[b + 1]),
                        max_deg=int(width), n_steps=n_steps)
            total += int(jnp.sum(cnt))
    return total


_intersect_count_jit = jax.jit(intersect_count_sorted,
                               static_argnames=("max_deg", "n_steps"))
