"""Pangolin programming interface (paper §3.2, Listing 1/2).

A mining application is a :class:`MiningApp` providing the paper's six
hooks — ``toExtend``, ``toAdd``, ``getPattern``, ``getSupport``,
``Aggregate``, ``toPrune`` — as *vectorized* callables over embedding
batches (the TPU analogue of the paper's per-embedding C++/CUDA functions).
Every hook is optional and has the paper's default semantics: extend all
vertices, default automorphism-canonical test, generic canonical pattern,
count support, sum aggregation, no pruning.

:class:`GraphCtx` packages the device-resident graph arrays plus the static
search parameters; it is what the helper routines of Listing 2
(``isConnected``, ``isAutoCanonical``, ...) consume.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.graph.csr import (CSRGraph, PackedGraph, pack_adjacency,
                             packed_contains)
from repro.sparse.intersect import adj_contains


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=["row_ptr", "col_idx", "labels", "edge_uid", "usrc", "udst",
                 "packed"],
    meta_fields=["n_vertices", "n_edges", "max_degree", "n_steps", "search",
                 "n_labels", "n_uedges"])
@dataclasses.dataclass(frozen=True)
class GraphCtx:
    """Device-side graph context threaded through all hooks.

    A pytree: the arrays (and the ``packed`` bitmap) are leaves, the ints
    and ``search`` are static aux data.  Compiled programs take it as an
    argument, so the graph is an input buffer of the executable and never
    a constant folded into its HLO.
    """

    row_ptr: jnp.ndarray          # i32[n+1]
    col_idx: jnp.ndarray          # i32[m]
    labels: Optional[jnp.ndarray]  # i32[n] or None
    n_vertices: int
    n_edges: int
    max_degree: int               # static bound for ragged expansion
    n_steps: int                  # binary search depth (ceil log2 max_degree)
    search: str = "binary"        # "binary" | "linear" (Fig. 13b ablation)
    n_labels: int = 1
    # edge-induced support: undirected edge ids
    edge_uid: Optional[jnp.ndarray] = None   # i32[m] uid per directed edge
    usrc: Optional[jnp.ndarray] = None       # i32[m/2] endpoints per uid
    udst: Optional[jnp.ndarray] = None
    n_uedges: int = 0
    # bit-packed adjacency bitmap (u32 rows); None = CSR search only
    packed: Optional[PackedGraph] = None

    def is_connected(self, u: jnp.ndarray, v: jnp.ndarray) -> jnp.ndarray:
        """Listing 2 ``isConnected``.

        With a packed adjacency bitmap the probe is one word gather + bit
        test (O(1) instead of O(log max_degree)); unpacked rows — and the
        ``search="linear"`` ablation — fall back to the CSR scan.
        """
        if self.packed is not None and self.search == "binary":
            hit = packed_contains(self.packed, u, v)
            if self.packed.full:
                return hit
            slot = self.packed.row_slot[jnp.clip(u, 0,
                                                 self.n_vertices - 1)]
            use_bitmap = slot >= 0
            if self.packed.n_cols < self.n_vertices:
                # core pack: rows answer only columns < n_cols; probes
                # outside the covered column prefix fall back to CSR
                use_bitmap = use_bitmap & (v < self.packed.n_cols)
            fallback = adj_contains(self.row_ptr, self.col_idx, u, v,
                                    self.n_steps, method=self.search)
            return jnp.where(use_bitmap, hit, fallback)
        return adj_contains(self.row_ptr, self.col_idx, u, v, self.n_steps,
                            method=self.search)

    def degree(self, v: jnp.ndarray) -> jnp.ndarray:
        v = jnp.clip(v, 0, self.n_vertices - 1)
        return self.row_ptr[v + 1] - self.row_ptr[v]


def make_ctx(g: CSRGraph, search: str = "binary",
             n_labels: Optional[int] = None,
             with_edge_uids: bool = False,
             pack_bits: bool = True,
             pack_max_bytes: int = 4 << 20,
             pack_partial: bool = False,
             pack_core: bool = False) -> GraphCtx:
    """Build a GraphCtx from a CSR graph (host-side preprocessing).

    ``pack_bits`` builds the bit-packed adjacency bitmap (u32 rows) that
    turns ``isConnected`` into an O(1) bit test; disabled automatically
    for the ``search="linear"`` ablation so the knob keeps measuring the
    CSR scan.  By default only a *full* pack (every row fits under
    ``pack_max_bytes``) is attached: a partial pack of high-degree rows
    makes every ``is_connected`` evaluate both the bitmap probe and the
    CSR fallback (vectorized select), which is a pessimization unless a
    consumer exploits the packed rows — opt in with ``pack_partial``.
    The pruned Pallas kernel is such a consumer: its mixed connectivity
    mode answers packed rows from the bitmap and binary-searches only
    the tail (``Miner(pack_partial=True, pack_max_bytes=...)``).

    ``pack_core`` builds the square *core pack* instead when the full
    pack is over budget (rows AND columns truncated to the top-id prefix
    — see :func:`repro.graph.csr.pack_adjacency`); meant for
    degree-relabeled graphs where the prefix is the high-degree core
    (``Miner(relabel=...)`` enables it by default).
    """
    max_deg = max(g.max_degree, 1)
    n_steps = max(1, math.ceil(math.log2(max_deg + 1)))
    if n_labels is None:
        n_labels = (int(np.asarray(g.labels).max()) + 1
                    if g.labels is not None else 1)
    edge_uid = usrc = udst = None
    n_uedges = 0
    if with_edge_uids:
        src, dst = map(np.asarray, g.edge_list())
        lo = np.minimum(src, dst).astype(np.int64)
        hi = np.maximum(src, dst).astype(np.int64)
        key = lo * np.int64(g.n_vertices) + hi
        uniq, inv = np.unique(key, return_inverse=True)
        edge_uid = jnp.asarray(inv.astype(np.int32))
        usrc = jnp.asarray((uniq // g.n_vertices).astype(np.int32))
        udst = jnp.asarray((uniq % g.n_vertices).astype(np.int32))
        n_uedges = int(uniq.shape[0])
    packed = None
    if pack_bits and search == "binary":
        n_words = -(-max(g.n_vertices, 1) // 32)
        would_be_full = g.n_vertices * n_words * 4 <= pack_max_bytes
        if would_be_full or pack_partial or pack_core:
            # never build a pack we'd drop
            packed = pack_adjacency(g, max_bytes=pack_max_bytes,
                                    core=pack_core and not would_be_full)
    return GraphCtx(
        row_ptr=g.row_ptr, col_idx=g.col_idx, labels=g.labels,
        n_vertices=g.n_vertices, n_edges=g.n_edges, max_degree=max_deg,
        n_steps=n_steps, search=search, n_labels=n_labels,
        edge_uid=edge_uid, usrc=usrc, udst=udst, n_uedges=n_uedges,
        packed=packed)


# ---------------------------------------------------------------------------
# Default canonicality tests (Listing 2 ``isAutoCanonical``)


def is_auto_canonical_vertex(ctx: GraphCtx, emb: jnp.ndarray,
                             u: jnp.ndarray,
                             src_slot: Optional[jnp.ndarray] = None
                             ) -> jnp.ndarray:
    """Vertex-induced automorphism-canonical extension test.

    emb: i32[N, k] parent vertices (extension order); u: i32[N] candidates;
    src_slot: i32[N] — which embedding position generated the candidate.
    Accept iff (Arabesque/Pangolin rule): u > v_0; u not in emb; u was
    extended from the *first* embedding vertex it is adjacent to (kills
    within-parent duplicates when u neighbors several members); and for
    every position after that first neighbor, u > that vertex.
    """
    k = emb.shape[1]
    ok = u > emb[:, 0]
    found = jnp.zeros(u.shape, bool)
    for j in range(k):
        adj = ctx.is_connected(u, emb[:, j])
        # "else if (found && u < emb_j) reject" — strict else-branch
        ok = ok & ~(found & (u < emb[:, j]))
        found = found | adj
        ok = ok & (u != emb[:, j])
        if src_slot is not None:
            # u adjacent to an earlier slot => this (slot, u) pair is the
            # duplicate; the canonical one extends from the first neighbor.
            ok = ok & ~(adj & (jnp.int32(j) < src_slot))
    return ok & found


def is_auto_canonical_vertex_bits(emb: jnp.ndarray, u: jnp.ndarray,
                                  conn: jnp.ndarray,
                                  src_slot: Optional[jnp.ndarray] = None
                                  ) -> jnp.ndarray:
    """Connectivity-bit variant of :func:`is_auto_canonical_vertex`.

    ``conn[:, j]`` must hold the precomputed adjacency of candidate u to
    embedding vertex j (as emitted by a fused extend kernel); the rule is
    otherwise identical.  Assumes symmetric adjacency — on an oriented DAG
    the two ``isConnected`` directions differ, so DAG apps must supply
    ``to_add_bits`` instead of relying on this default.
    """
    k = emb.shape[1]
    ok = u > emb[:, 0]
    found = jnp.zeros(u.shape, bool)
    for j in range(k):
        adj = conn[:, j]
        ok = ok & ~(found & (u < emb[:, j]))
        found = found | adj
        ok = ok & (u != emb[:, j])
        if src_slot is not None:
            ok = ok & ~(adj & (jnp.int32(j) < src_slot))
    return ok & found


def is_auto_canonical_kernel(emb_cols, u, src_slot, state, conn):
    """Elementwise (kernel-traceable) automorphism-canonical test.

    The ``to_add_kernel`` form of :func:`is_auto_canonical_vertex_bits`:
    ``emb_cols``/``conn`` are length-k tuples of arrays (one per parent
    slot) instead of [N, k] matrices, and every operation is elementwise —
    the contract that lets the same function be traced both on flat jnp
    batches (reference backend) and on VMEM lane tiles inside the fused
    Pallas extend kernel.  Assumes symmetric adjacency (undirected graph).
    """
    k = len(emb_cols)
    ok = u > emb_cols[0]
    found = jnp.zeros(u.shape, bool)
    for j in range(k):
        adj = conn[j]
        ok = ok & ~(found & (u < emb_cols[j]))
        found = found | adj
        ok = ok & (u != emb_cols[j])
        ok = ok & ~(adj & (jnp.int32(j) < src_slot))
    return ok & found


def resolve_kernel_predicate(app: "MiningApp", k: Optional[int] = None):
    """The eager in-kernel ``toAdd`` predicate for ``app``, or None.

    Fused backends prune candidates *inside* the extend kernel (filter +
    stream compaction fused into enumeration) whenever the app's predicate
    is expressible in the elementwise kernel form: either the app supplies
    ``to_add_kernel`` explicitly, or it relies entirely on the default
    automorphism-canonical test on an undirected graph (the bits-based
    variant is exact there).  Apps with only host-side hooks — or
    ``use_dag`` apps without hooks, where the precomputed connectivity
    bits have the wrong ``isConnected`` direction for the default test —
    return None and take the unfused enumerate-then-filter path.

    ``to_add_kernel`` may be *per-level*: a sequence indexed by extension
    level, entry ``k - 2`` deciding the extension from ``k`` parent
    vertices to ``k + 1`` (the pattern compiler emits one predicate per
    matching-order position).  Backends pass ``k`` — the parent embedding
    width — to select the level's predicate; a plain callable ignores it.
    """
    if app.kind != "vertex":
        return None
    if app.to_add_kernel is not None:
        tak = app.to_add_kernel
        if callable(tak):
            return tak
        if k is None:
            raise ValueError(
                f"app {app.name!r} has a per-level to_add_kernel; callers "
                "must pass the level (parent embedding width k)")
        idx = k - 2
        if not 0 <= idx < len(tak):
            raise ValueError(
                f"app {app.name!r}: no to_add_kernel entry for level k={k} "
                f"({len(tak)} per-level predicates)")
        return tak[idx]
    if app.to_add is None and app.to_add_bits is None and not app.use_dag:
        return is_auto_canonical_kernel
    return None


def resolve_state_kernel(app: "MiningApp", k: Optional[int] = None):
    """The eager in-kernel state-update hook for ``app``, or None.

    ``update_state_kernel`` has the same elementwise contract as
    ``to_add_kernel`` — ``fn(emb_cols, u, src_slot, state, conn) ->
    i32`` — but returns the *new* per-embedding memo state for each
    candidate instead of a keep mask.  Backends evaluate it alongside the
    ``to_add_kernel`` predicate (inside the fused Pallas kernel, or on
    flat batches in the reference backend) and stream-compact the result
    into the next level's ``state`` column, so path-dependent per-branch
    information (the multi-pattern trie's branch bitmap) survives the
    extension without a second pass.  Like ``to_add_kernel`` it may be a
    per-level sequence indexed by ``k - 2``.
    """
    usk = app.update_state_kernel
    if usk is None or app.kind != "vertex":
        return None
    if callable(usk):
        return usk
    if k is None:
        raise ValueError(
            f"app {app.name!r} has a per-level update_state_kernel; "
            "callers must pass the level (parent embedding width k)")
    idx = k - 2
    if not 0 <= idx < len(usk):
        raise ValueError(
            f"app {app.name!r}: no update_state_kernel entry for level "
            f"k={k} ({len(usk)} per-level updates)")
    return usk[idx]


def is_auto_canonical_edge(ctx: GraphCtx, eids: jnp.ndarray,
                           new_eid: jnp.ndarray, new_src: jnp.ndarray,
                           new_dst: jnp.ndarray, e_src: jnp.ndarray,
                           e_dst: jnp.ndarray) -> jnp.ndarray:
    """Edge-induced canonical extension test over undirected edge ids.

    eids: i32[N, E] existing edge uids (extension order); new_eid: i32[N];
    (new_src, new_dst): endpoints of the candidate; (e_src, e_dst):
    i32[N, E] endpoints of existing edges.  Same total-order rule as the
    vertex case, with "neighbour" = shares an endpoint.
    """
    E = eids.shape[1]
    ok = new_eid > eids[:, 0]
    found = jnp.zeros(new_eid.shape, bool)
    for j in range(E):
        shares = ((new_src == e_src[:, j]) | (new_src == e_dst[:, j])
                  | (new_dst == e_src[:, j]) | (new_dst == e_dst[:, j]))
        ok = ok & ~(found & (new_eid < eids[:, j]))
        found = found | shares
        ok = ok & (new_eid != eids[:, j])
    return ok & found


# ---------------------------------------------------------------------------
# Application definition


@dataclasses.dataclass(frozen=True)
class MiningApp:
    """One graph-mining application (paper Listing 1).

    Hook signatures (all vectorized; N = candidate/embedding batch):
      to_extend(ctx, emb[N,k])                           -> bool[N,k]
      to_add(ctx, emb[N,k], u[N], src_slot[N], state[N]) -> bool[N]
      to_add_bits(ctx, emb, u, src_slot, state, conn[N,k]) -> bool[N]
      get_pattern(ctx, emb[N,k], state[N]|None)     -> (pat[N], new_state)
      to_prune(support[P], pat_id[N])               -> bool[N] (True = drop)
    ``state`` is the per-embedding memo slot (paper §4.2 memoization) —
    e.g. the previous level's motif id; it flows level to level.

    ``to_add_bits`` is the fused-backend variant of ``to_add``: instead of
    probing ``ctx.is_connected`` itself, it receives ``conn[:, j]`` =
    "candidate u is adjacent to embedding vertex j", precomputed inside
    the extend kernel.  Backends that don't precompute connectivity ignore
    it and call ``to_add``.  ``backend`` names the app's preferred phase
    backend (see repro.core.phases); ``Miner(backend=...)`` overrides it.

    ``to_add_kernel`` is the strictest — and fastest — form:
    ``fn(emb_cols, u, src_slot, state, conn) -> bool`` where ``emb_cols``
    and ``conn`` are length-k tuples of arrays and every operation must be
    elementwise (no ``ctx``, no gathers).  Predicates in this form are
    evaluated *inside* the fused Pallas extend kernel, so dead candidates
    are pruned and stream-compacted before they are ever materialized
    (the paper's eager pruning, §4); the reference backend traces the
    same function on flat batches, keeping the two backends bitwise
    equal.  Supply it whenever the app's ``toAdd`` only needs the parent
    vertices, the candidate, and the k connectivity bits.  It may also be
    a *sequence* of such predicates, indexed by extension level (entry
    ``k - 2`` extends ``k``-vertex embeddings) — the form the pattern
    compiler emits, one symmetry-breaking/connectivity predicate per
    matching-order position (see :func:`resolve_kernel_predicate`).

    ``directed_worklist`` makes the level-0 worklist the *directed* edge
    list (both orientations of every undirected edge) instead of the
    ``src < dst`` half.  Compiled pattern apps need it when matching
    positions 0 and 1 are not automorphism-exchangeable (no ``v0 < v1``
    symmetry-breaking constraint exists, so both orientations are
    distinct partial matches).  Ignored by ``use_dag`` apps (the DAG
    already directs the worklist).

    ``plan_key`` is extra app identity folded into the capacity-plan
    signature — pattern apps put the pattern's isomorphism hash here so
    two different patterns of the same size never share a cached plan.

    ``update_state_kernel`` is the state-update twin of ``to_add_kernel``
    (same elementwise contract, returns the i32 memo state of the *new*
    embedding); backends compact its output into the next level's state
    column, so state can carry path-dependent facts the next level's
    predicate needs — the multi-pattern trie threads its per-embedding
    branch bitmap this way.  ``state_histogram(state[N], valid[N]) ->
    p_map[max_patterns]`` turns the final state column directly into the
    per-pattern histogram (a fixed bit-count, no canonical labeling and
    no ``jnp.unique``); when present it replaces the ``get_pattern``
    reduce entirely.
    """

    name: str
    kind: str = "vertex"            # "vertex" | "edge"
    max_size: int = 3               # target #vertices (vertex) / #edges+1
    use_dag: bool = False           # §4.1 orientation
    needs_reduce: bool = False
    needs_filter: bool = False
    support_mode: str = "count"     # "count" | "domain" (MNI)
    max_patterns: int = 8           # static bound on distinct patterns
    min_support: int = 0
    to_extend: Optional[Callable] = None
    # state-aware toExtend: (ctx, emb[N,k], state[N]) -> bool[N,k].  Takes
    # precedence over to_extend when the memo state is available — the
    # multi-pattern trie uses it to enumerate only the anchor slots of
    # branches the embedding still carries (dead branches cost nothing)
    to_extend_state: Optional[Callable] = None
    to_add: Optional[Callable] = None
    to_add_bits: Optional[Callable] = None  # fused-backend toAdd variant
    # per-candidate-vertex eager toAdd: (ctx) -> bool[n_vertices].  The
    # strongest edge-pipeline form: when the app's toAdd depends only on
    # the candidate vertex u (e.g. FSM's label-frequency prune), backends
    # gather this mask per candidate — the reference pipeline in XLA, the
    # fused edge kernel in-VMEM, so pruned candidates are never
    # materialized.  Takes precedence over ``to_add`` in the edge pipeline.
    to_add_vertex_mask: Optional[Callable] = None
    # in-kernel elementwise toAdd: one callable, or a per-level sequence.
    # A predicate with attribute ``needs_labels = True`` receives two
    # extra arguments ``(lab_cols, lab_u)`` — the parent-slot and
    # candidate vertex labels, gathered by the backend (in-kernel for the
    # fused backends) — the labeled-pattern form.
    to_add_kernel: Optional[Callable | tuple] = None
    # in-kernel elementwise state update (same form as to_add_kernel)
    update_state_kernel: Optional[Callable | tuple] = None
    # final-state -> pattern histogram (replaces the get_pattern reduce)
    state_histogram: Optional[Callable] = None
    get_pattern: Optional[Callable] = None
    to_prune: Optional[Callable] = None
    init_state: Optional[Callable] = None  # (ctx, emb[N,2], n) -> state[N]
    backend: Optional[str] = None           # preferred phase backend
    directed_worklist: bool = False         # level-0: both edge orientations
    plan_key: str = ""                      # extra plan-signature identity
