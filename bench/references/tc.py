"""Triangle counting: the host reference, its control and its work.

The count is the degree-ordered DAG's ``sum((U @ U) * U)``: every
triangle a < b < c (by rank) once, at its edge (a, c).  Each function
takes the generator's graph (``edges``, ``n``, ``labels`` where it has
them); labels do not enter a triangle count.
"""
from __future__ import annotations

import numpy as np

from bench.references.common import oriented, thinned_sum


def _per_edge(edges: np.ndarray, n: int) -> np.ndarray:
    u = oriented(edges, n)
    return np.asarray((u @ u).multiply(u).tocsr().data, np.int64)


def count(edges: np.ndarray, n: int, labels=None) -> int:
    return int(_per_edge(edges, n).sum())


def approximate(edges: np.ndarray, n: int, keep: float, seed: int,
                labels=None) -> int:
    """The count with each DAG edge's triangles kept with probability
    ``keep`` and rescaled: the exactness guarantee broken."""
    return thinned_sum(_per_edge(edges, n), keep, seed)


def work(edges: np.ndarray, n: int, labels=None) -> int:
    """Candidates the extension tests: the DAG wedges, the sum over
    vertices of C(out-degree, 2).  Each needs one probe."""
    d = np.diff(oriented(edges, n).indptr).astype(np.int64)
    return int((d * (d - 1) // 2).sum())
