"""4-clique counting: the host reference, its control and its work.

With ``S[e, w] = 1`` iff ``w`` is a common out-neighbour of DAG edge
``e = (a, b)``, the 4-cliques a < b < c < d (by rank) are counted once,
at ``e = (a, b)``, as the DAG edges inside row ``e``: the sum of
``(S @ U) * S``.  The edges are taken in blocks so that ``S`` fits in
host memory.  Each function takes the generator's graph (``edges``,
``n``, ``labels`` where it has them); labels do not enter a clique count.
"""
from __future__ import annotations

import numpy as np

from bench.references.common import (common_out, dag_edges, edge_blocks,
                                     oriented, thinned_sum)


def _per_edge(edges: np.ndarray, n: int, want_pairs: bool) -> np.ndarray:
    u = oriented(edges, n)
    src, dst = dag_edges(u)
    out = np.zeros(src.size, np.int64)
    for blk in edge_blocks(u, src):
        s = common_out(u, src[blk], dst[blk])
        if want_pairs:
            t = np.diff(s.indptr).astype(np.int64)
            out[blk] = t * (t - 1) // 2
        else:
            out[blk] = np.asarray((s @ u).multiply(s).sum(axis=1)).ravel()
    return out


def count(edges: np.ndarray, n: int, labels=None) -> int:
    return int(_per_edge(edges, n, want_pairs=False).sum())


def approximate(edges: np.ndarray, n: int, keep: float, seed: int,
                labels=None) -> int:
    """The count with each DAG edge's 4-cliques kept with probability
    ``keep`` and rescaled: the exactness guarantee broken."""
    return thinned_sum(_per_edge(edges, n, want_pairs=False), keep, seed)


def work(edges: np.ndarray, n: int, labels=None) -> int:
    """Candidates the last extension tests: over DAG edges e, C(t(e), 2)
    with t(e) the triangles on e, i.e. the pairs of common
    out-neighbours.  Each pair needs one probe."""
    return int(_per_edge(edges, n, want_pairs=True).sum())
