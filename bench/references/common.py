"""The plain graph the references count on, built from the raw edge list.

Independent of the program: scipy alone turns the generator's edges
into a simple undirected graph (symmetric, no self-loops, no duplicate
edges), then orients it into a DAG by the rank degree * n + id, so
every edge points from the lower to the higher (degree, id) vertex.
"""
from __future__ import annotations

import numpy as np
import scipy.sparse as sp


def simple_graph(edges: np.ndarray, n: int) -> sp.csr_matrix:
    """Symmetric 0/1 adjacency with an empty diagonal."""
    u = np.asarray(edges[:, 0], np.int64)
    v = np.asarray(edges[:, 1], np.int64)
    keep = u != v
    u, v = u[keep], v[keep]
    a = sp.csr_matrix((np.ones(2 * u.size, np.int8),
                       (np.concatenate([u, v]), np.concatenate([v, u]))),
                      shape=(n, n))
    a.sum_duplicates()
    a.data[:] = 1
    return a


def oriented(edges: np.ndarray, n: int) -> sp.csr_matrix:
    """The DAG ``U``: ``U[u, v] = 1`` iff ``u - v`` is an edge and
    ``rank[u] < rank[v]`` with ``rank = degree * n + id``."""
    a = simple_graph(edges, n)
    deg = np.diff(a.indptr).astype(np.int64)
    rank = deg * n + np.arange(n, dtype=np.int64)
    src = np.repeat(np.arange(n, dtype=np.int64), deg)
    col = a.indices.astype(np.int64)
    up = rank[src] < rank[col]
    u = sp.csr_matrix((np.ones(int(up.sum()), np.int64),
                       (src[up], col[up])), shape=(n, n))
    u.sort_indices()
    return u


def dag_edges(u: sp.csr_matrix) -> tuple[np.ndarray, np.ndarray]:
    """(src, dst) of every DAG edge, in CSR order."""
    src = np.repeat(np.arange(u.shape[0], dtype=np.int64), np.diff(u.indptr))
    return src, u.indices.astype(np.int64)


def common_out(u: sp.csr_matrix, src: np.ndarray, dst: np.ndarray
               ) -> sp.csr_matrix:
    """Row e holds the common out-neighbours of DAG edge (src[e], dst[e])."""
    return u[src].multiply(u[dst]).tocsr()


def edge_blocks(u: sp.csr_matrix, src: np.ndarray,
                budget: int = 1 << 24):
    """Slices of the DAG edges whose rows of ``U[src]`` hold about
    ``budget`` entries, so that a block fits in host memory."""
    cost = np.cumsum(np.diff(u.indptr)[src])
    start = 0
    while start < src.size:
        stop = int(np.searchsorted(cost, cost[start] + budget, "right"))
        stop = max(stop, start + 1)
        yield slice(start, stop)
        start = stop


def thinned_sum(per_edge: np.ndarray, keep: float, seed: int) -> int:
    """An approximate total: each DAG edge's share kept with probability
    ``keep`` and the sum scaled by ``1 / keep``."""
    rng = np.random.default_rng(seed)
    kept = rng.random(per_edge.size) < keep
    return int(round(float(per_edge[kept].sum()) / keep))
