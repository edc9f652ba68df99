"""Compressed-sparse-row graph storage (sorted adjacency, symmetric input).

The paper keeps the input graph in CSR with neighbor lists sorted by
ascending vertex ID (§6.1); sorted adjacency is what makes the binary-search
connectivity check (§5.4) possible.  We mirror that exactly: ``row_ptr`` /
``col_idx`` int32 arrays, optional per-vertex labels for FSM.

Everything here is host-side preprocessing (numpy) producing device arrays;
mining/jit code only ever sees the dense arrays.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class CSRGraph:
    """Immutable CSR graph. Neighbor lists sorted ascending.

    Attributes:
      row_ptr: int32[n_vertices + 1]
      col_idx: int32[n_edges]           (directed edge count; symmetric graphs
                                         store both directions)
      labels:  int32[n_vertices] or None (vertex labels, FSM)
      n_vertices / n_edges: python ints (static for jit tracing)
    """

    row_ptr: jnp.ndarray
    col_idx: jnp.ndarray
    n_vertices: int
    n_edges: int
    labels: Optional[jnp.ndarray] = None

    def degrees(self) -> jnp.ndarray:
        return self.row_ptr[1:] - self.row_ptr[:-1]

    @property
    def max_degree(self) -> int:
        return int(np.max(np.asarray(self.degrees()))) if self.n_vertices else 0

    def edge_list(self) -> tuple[jnp.ndarray, jnp.ndarray]:
        """Return (src, dst) arrays for all directed edges in CSR order."""
        src = np.repeat(np.arange(self.n_vertices, dtype=np.int32),
                        np.asarray(self.degrees()))
        return jnp.asarray(src), self.col_idx

    def undirected_edge_list(self) -> tuple[jnp.ndarray, jnp.ndarray]:
        """(src, dst) with src < dst — each undirected edge once."""
        src, dst = self.edge_list()
        src_np, dst_np = np.asarray(src), np.asarray(dst)
        keep = src_np < dst_np
        return jnp.asarray(src_np[keep]), jnp.asarray(dst_np[keep])


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=["words", "row_slot"],
                   meta_fields=["n_words", "full", "n_cols"])
@dataclasses.dataclass(frozen=True)
class PackedGraph:
    """Bit-packed adjacency bitmap rows (u32 words) for O(1) connectivity.

    The paper's hot loops are dominated by ``isConnected`` probes; a
    binary search over sorted CSR adjacency costs ``ceil(log2 max_degree)``
    dependent gathers per probe.  Packing a vertex's neighborhood as an
    ``n_vertices``-bit bitmap turns the probe into one word gather + one
    bit test — the G2Miner/Sandslash "bit-packed connectivity set" trick.

    ``row_slot[v]`` maps vertex v to its bitmap row in ``words`` (or -1
    when v's row is not packed and callers must fall back to CSR binary
    search).  ``full`` means every vertex is packed, which lets fused
    kernels skip the fallback path entirely.  Packing is budgeted: under
    ``max_bytes`` every row is packed; above it only the highest-degree
    rows are (they answer the most probes per byte), the long tail staying
    on binary search.

    ``n_cols`` is the *column* coverage: each bitmap row answers
    membership only for neighbor ids ``< n_cols`` (callers must fall back
    to CSR for ``v >= n_cols``).  A plain pack covers every column
    (``n_cols == n_vertices``); the square *core pack* built for
    degree-relabeled graphs truncates both rows and columns to the
    top-degree prefix ``[0, c)``, shrinking each row from
    ``ceil(n/32)*4`` to ``ceil(c/32)*4`` bytes so ~sqrt-factor more hot
    rows fit under the same byte budget.

    Attributes:
      words:    u32[n_packed, n_words]  bitmap rows (bit u of row r set
                iff u in N(vertex owning row r) and u < n_cols)
      row_slot: i32[n_vertices]         vertex -> row index, -1 = unpacked
      n_words:  ceil(n_cols / 32)
      full:     row_slot is the identity AND every column is covered
      n_cols:   column ids covered by each row (bit j = neighbor j)
    """

    words: jnp.ndarray
    row_slot: jnp.ndarray
    n_words: int
    full: bool
    n_cols: int

    @property
    def n_packed(self) -> int:
        return int(self.words.shape[0])

    def nbytes(self) -> int:
        return self.words.nbytes + self.row_slot.nbytes


def core_size(n_vertices: int, max_bytes: int) -> int:
    """Largest c <= n_vertices with ``c * ceil(c/32) * 4 <= max_bytes``.

    The square core-pack dimension: rows and columns both truncate to
    ``[0, c)``, so the pack cost is quadratic in c instead of linear in
    ``n_vertices`` per row — c grows like ``sqrt(8 * max_bytes)``.
    """
    if n_vertices <= 0 or max_bytes <= 0:
        return 0
    c = min(int((max(max_bytes, 1) * 8) ** 0.5) + 32, n_vertices)
    while c > 0 and c * (-(-c // 32)) * 4 > max_bytes:
        c -= 1
    return c


def pack_adjacency(g: CSRGraph, max_bytes: int = 4 << 20,
                   core: bool = False) -> Optional[PackedGraph]:
    """Build the bit-packed adjacency bitmap for ``g`` (host-side numpy).

    Full pack when ``n_vertices**2 / 8`` fits in ``max_bytes``; otherwise
    a partial pack of the highest-degree rows that fit (ties broken by
    vertex id so the selection is deterministic).  Returns None when not
    even one row fits (degenerate budget) or the graph is empty.

    ``core=True`` switches the over-budget case to the square *core
    pack*: rows AND columns truncate to the prefix ``[0, c)`` with c the
    largest size whose ``c x c`` bitmap fits ``max_bytes``
    (:func:`core_size`).  Meant for degree-relabeled graphs
    (:func:`relabel`), where ``[0, c)`` is exactly the high-degree core
    answering most connectivity probes; on arbitrary labelings the
    truncated columns make the bitmap nearly useless (correctness is
    unaffected — probes outside the core fall back to CSR).
    """
    n = g.n_vertices
    if n == 0:
        return None
    n_words = -(-n // 32)
    row_bytes = n_words * 4
    budget_rows = max_bytes // max(row_bytes, 1)
    rp = np.asarray(g.row_ptr)
    ci = np.asarray(g.col_idx)
    n_cols = n
    if budget_rows >= n:
        rows = np.arange(n, dtype=np.int64)
        full = True
    elif core:
        c = core_size(n, max_bytes)
        if c < 1:
            return None
        rows = np.arange(c, dtype=np.int64)
        n_cols = int(c)
        n_words = -(-n_cols // 32)
        full = False
    elif budget_rows < 1:
        return None
    else:
        deg = rp[1:] - rp[:-1]
        # degree-major, id-minor: highest-degree rows answer the most
        # probes per packed byte
        order = np.lexsort((np.arange(n), -deg))
        rows = np.sort(order[: int(budget_rows)]).astype(np.int64)
        full = False
    words = np.zeros((rows.shape[0], n_words), dtype=np.uint32)
    for slot, v in enumerate(rows):
        nbrs = ci[rp[v]:rp[v + 1]].astype(np.int64)
        if n_cols < n:
            nbrs = nbrs[nbrs < n_cols]
        np.bitwise_or.at(words[slot], nbrs >> 5,
                         np.uint32(1) << (nbrs & 31).astype(np.uint32))
    row_slot = np.full((n,), -1, dtype=np.int32)
    row_slot[rows] = np.arange(rows.shape[0], dtype=np.int32)
    return PackedGraph(words=jnp.asarray(words),
                       row_slot=jnp.asarray(row_slot),
                       n_words=int(n_words), full=full, n_cols=int(n_cols))


def packed_contains(pg: PackedGraph, u: jnp.ndarray,
                    v: jnp.ndarray) -> jnp.ndarray:
    """Bitmap membership: is v in N(u)?  Only valid for packed rows of u
    with v inside the column coverage (callers guard with
    ``pg.row_slot[u] >= 0`` and ``v < pg.n_cols``); out-of-range u/v
    (padding, e.g. -1, or columns past a core pack's coverage) -> False."""
    n_vertices = pg.row_slot.shape[0]
    slot = pg.row_slot[jnp.clip(u, 0, n_vertices - 1)]
    v_c = jnp.clip(v, 0, pg.n_cols - 1)
    word = pg.words[jnp.clip(slot, 0, pg.words.shape[0] - 1), v_c >> 5]
    bit = (word >> (v_c & 31).astype(jnp.uint32)) & jnp.uint32(1)
    return ((bit == 1) & (slot >= 0) & (u >= 0) & (v >= 0)
            & (u < n_vertices) & (v < pg.n_cols))


def pack_hit_rate(g: CSRGraph, pg: Optional[PackedGraph]) -> float:
    """Degree-weighted probability a connectivity probe hits the bitmap.

    Static proxy for the kernel's mixed-mode bitmap hit rate: under
    degree-biased sampling (both probe endpoints land on a vertex with
    probability proportional to its degree — the distribution mining
    frontiers actually induce), the probe answers from the bitmap iff the
    probed row is packed AND the candidate column is covered.  Returns
    P(row packed) * P(column covered); 1.0 for a full pack, 0.0 with no
    pack.  This is the bench's ``pack_hit_rate`` field — the quantity
    degree relabeling + core packing is meant to move.
    """
    if pg is None or g.n_vertices == 0 or g.n_edges == 0:
        return 0.0
    deg = np.asarray(g.degrees(), dtype=np.float64)
    tot = float(deg.sum())
    if tot <= 0:
        return 0.0
    slot = np.asarray(pg.row_slot)
    p_row = float(deg[slot >= 0].sum()) / tot
    p_col = float(deg[: pg.n_cols].sum()) / tot
    return p_row * p_col


@dataclasses.dataclass(frozen=True)
class Relabeling:
    """A vertex-relabeled copy of a graph plus both id maps.

    ``perm[old_id] = new_id`` and ``inv[new_id] = old_id``; ``graph`` is
    the relabeled CSR (labels permuted along).  Mining results that are
    pure counts/codes/supports are permutation-invariant; anything
    exposing vertex ids (embedding levels, domains) maps back through
    ``inv``.
    """

    graph: CSRGraph
    perm: np.ndarray
    inv: np.ndarray


def relabel(g: CSRGraph, order: str = "degree") -> Relabeling:
    """Relabel vertices into a locality-aware id order (host-side numpy).

    ``order="degree"`` assigns ids by descending degree (ties broken by
    old id, so the permutation is deterministic): the hot high-degree
    core becomes the contiguous prefix ``[0, c)``.  That is what makes
    (a) the partial/core adjacency pack cover the rows answering most
    connectivity probes *by construction* and (b) contiguous level-0
    blocks (``core/blocks.py``) locality-coherent.  ``order="identity"``
    is the no-op permutation (useful for parity tests).

    Counts, pattern maps, and FSM codes/supports are bitwise invariant
    under relabeling: canonical pattern codes derive from structure +
    labels only, automorphism-canonical tests keep exactly one embedding
    per class, and MNI support counts distinct vertices.
    """
    n = g.n_vertices
    if order == "degree":
        deg = np.asarray(g.degrees())
        inv = np.lexsort((np.arange(n), -deg)).astype(np.int64)
    elif order == "identity":
        inv = np.arange(n, dtype=np.int64)
    else:
        raise ValueError(f"relabel order {order!r} not in "
                         "('degree', 'identity')")
    perm = np.empty(n, dtype=np.int64)
    perm[inv] = np.arange(n, dtype=np.int64)
    rp = np.asarray(g.row_ptr)
    src = np.repeat(np.arange(n, dtype=np.int64), rp[1:] - rp[:-1])
    dst = np.asarray(g.col_idx, dtype=np.int64)
    labels = None
    if g.labels is not None:
        labels = np.asarray(g.labels)[inv]
    new_g = build_csr(n, perm[src], perm[dst], labels=labels)
    return Relabeling(graph=new_g, perm=perm, inv=inv)


def build_csr(n_vertices: int, src: np.ndarray, dst: np.ndarray,
              labels: Optional[np.ndarray] = None) -> CSRGraph:
    """Build a CSR graph from directed edge arrays (already deduplicated)."""
    src = np.asarray(src, dtype=np.int64)
    dst = np.asarray(dst, dtype=np.int64)
    order = np.lexsort((dst, src))
    src, dst = src[order], dst[order]
    counts = np.bincount(src, minlength=n_vertices)
    row_ptr = np.zeros(n_vertices + 1, dtype=np.int64)
    np.cumsum(counts, out=row_ptr[1:])
    return CSRGraph(
        row_ptr=jnp.asarray(row_ptr, dtype=jnp.int32),
        col_idx=jnp.asarray(dst, dtype=jnp.int32),
        n_vertices=int(n_vertices),
        n_edges=int(dst.shape[0]),
        labels=None if labels is None else jnp.asarray(labels, dtype=jnp.int32),
    )


def from_edge_list(edges, n_vertices: Optional[int] = None,
                   labels: Optional[np.ndarray] = None,
                   symmetrize: bool = True) -> CSRGraph:
    """Build a symmetric, loop-free, deduplicated CSR graph from (u, v) pairs.

    Matches the paper's input contract: symmetric, no self loops, no
    duplicate edges (§6.1, Table 1).
    """
    edges = np.asarray(edges, dtype=np.int64).reshape(-1, 2)
    u, v = edges[:, 0], edges[:, 1]
    keep = u != v  # drop self loops
    u, v = u[keep], v[keep]
    if symmetrize:
        uu = np.concatenate([u, v])
        vv = np.concatenate([v, u])
    else:
        uu, vv = u, v
    if n_vertices is None:
        n_vertices = int(max(uu.max(initial=-1), vv.max(initial=-1)) + 1) if uu.size else 0
    # dedup via flat keys
    key = uu * np.int64(n_vertices) + vv
    _, uniq = np.unique(key, return_index=True)
    uu, vv = uu[uniq], vv[uniq]
    return build_csr(n_vertices, uu, vv, labels=labels)


# Quantile grid of the degree-profile sketch carried by MiningPlan for
# plan transfer: coarse enough to be a few floats per plan, fine enough
# that an ER graph and a power-law graph of equal edge count land far
# apart (the tail quantiles separate them).
DEGREE_QUANTILES = (0.1, 0.25, 0.5, 0.75, 0.9, 0.99, 1.0)


def degree_profile(g: CSRGraph) -> tuple[float, ...]:
    """Compact degree-distribution sketch: quantiles of the degree vector.

    Together with the edge count this is the plan-transfer identity —
    two graphs with close profiles produce close per-level frontier
    sizes for the same app, so a cached plan from the nearest profile is
    a good capacity seed (scaled by worklist size; exactness comes from
    the executor's grow-and-retry backstop, not from the match).
    """
    if g.n_vertices == 0:
        return (0.0,) * len(DEGREE_QUANTILES)
    deg = np.asarray(g.degrees(), dtype=np.float64)
    return tuple(float(x) for x in np.quantile(deg, DEGREE_QUANTILES))


def neighbors_np(g: CSRGraph, v: int) -> np.ndarray:
    rp = np.asarray(g.row_ptr)
    ci = np.asarray(g.col_idx)
    return ci[rp[v]:rp[v + 1]]


def to_networkx(g: CSRGraph):
    """Convert to networkx for oracle checks (tests only)."""
    import networkx as nx

    G = nx.Graph()
    G.add_nodes_from(range(g.n_vertices))
    src, dst = g.edge_list()
    for s, d in zip(np.asarray(src), np.asarray(dst)):
        if s < d:
            G.add_edge(int(s), int(d))
    if g.labels is not None:
        lab = np.asarray(g.labels)
        for i in range(g.n_vertices):
            G.nodes[i]["label"] = int(lab[i])
    return G
