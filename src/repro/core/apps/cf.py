"""k-clique finding (paper Listing 3).

Eager pruning: only the *last* vertex of each embedding is extended
(``toExtend``), and a candidate survives ``toAdd`` iff it is connected to
every embedding vertex.  With DAG orientation (§4.1) every clique is
generated exactly once (vertices appear in total order), so no canonical
test is needed at all; without DAG the same uniqueness is enforced with
``u > last`` (ablation mode for Fig. 12a).
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core.api import (GraphCtx, MiningApp, is_auto_canonical_kernel,
                            is_auto_canonical_vertex,
                            is_auto_canonical_vertex_bits)


def make_cf_app(k: int, use_dag: bool = True,
                eager_prune: bool = True) -> MiningApp:
    def to_extend(ctx: GraphCtx, emb: jnp.ndarray) -> jnp.ndarray:
        mask = jnp.zeros(emb.shape, bool)
        if eager_prune:
            return mask.at[:, emb.shape[1] - 1].set(True)
        return jnp.ones(emb.shape, bool)

    def _decide(emb, u, src_slot, connected, canonical):
        """One clique rule for both hook variants; ``connected(j)`` answers
        isConnected(emb_j, u), ``canonical()`` the automorphism test."""
        kk = emb.shape[1]
        ok = u >= 0
        # connected to all current vertices (clique property). The extension
        # edge (last, u) is already a graph edge; checking it again is
        # harmless and keeps the code uniform (paper Listing 3 does same).
        for j in range(kk):
            ok = ok & connected(j)
        if use_dag:
            # DAG: out-neighbors always rank higher; uniqueness is free —
            # but with all slots extendable the same clique arrives from
            # every member, so keep only the last-slot extension.
            for j in range(kk):
                ok = ok & (u != emb[:, j])
            if not eager_prune:
                ok = ok & (src_slot == kk - 1)
        elif eager_prune:
            # undirected with last-vertex extension: enforce sorted order
            ok = ok & (u > emb[:, kk - 1])
        else:
            ok = ok & canonical()
        return ok

    def to_add(ctx: GraphCtx, emb: jnp.ndarray, u: jnp.ndarray,
               src_slot: jnp.ndarray, state):
        return _decide(emb, u, src_slot,
                       lambda j: ctx.is_connected(emb[:, j], u),
                       lambda: is_auto_canonical_vertex(ctx, emb, u,
                                                        src_slot))

    def to_add_bits(ctx: GraphCtx, emb: jnp.ndarray, u: jnp.ndarray,
                    src_slot: jnp.ndarray, state, conn: jnp.ndarray):
        # isConnected answered from the fused kernel's connectivity
        # bitmask (conn[:, j] = u in N(emb_j))
        return _decide(emb, u, src_slot,
                       lambda j: conn[:, j],
                       lambda: is_auto_canonical_vertex_bits(emb, u, conn,
                                                             src_slot))

    def to_add_kernel(emb_cols, u, src_slot, state, conn):
        # elementwise form: evaluated *inside* the fused extend kernel, so
        # non-clique candidates are pruned and compacted before they are
        # ever materialized (the paper's eager pruning, Listing 3)
        kk = len(emb_cols)
        ok = u >= 0
        # with eager pruning every candidate is a neighbour of the last
        # vertex, which it was drawn from: that probe is known true, and
        # leaving it out lets the compiler drop it
        for j in range(kk - 1 if eager_prune else kk):
            ok = ok & conn[j]
        if use_dag:
            for j in range(kk):
                ok = ok & (u != emb_cols[j])
            if not eager_prune:
                ok = ok & (src_slot == kk - 1)
        elif eager_prune:
            ok = ok & (u > emb_cols[kk - 1])
        else:
            ok = ok & is_auto_canonical_kernel(emb_cols, u, src_slot,
                                               state, conn)
        return ok

    return MiningApp(name=f"{k}-clique", kind="vertex", max_size=k,
                     use_dag=use_dag, to_extend=to_extend, to_add=to_add,
                     to_add_bits=to_add_bits, to_add_kernel=to_add_kernel)


def make_cf_app_compiled(k: int) -> MiningApp:
    """k-clique via the pattern compiler instead of the hand-written rules.

    ``pattern_app(Pattern.clique(k))`` derives the same eager pruning
    automatically: the compiled symmetry-breaking chain for K_k is the
    total order ``v0 < v1 < ... < v_{k-1}`` — the role DAG orientation
    plays in the hand-written app.  Kept alongside :func:`make_cf_app`
    as the compiler's parity check (both must count every clique once).
    """
    from repro.core.apps.psm import pattern_app
    from repro.core.patterns import Pattern
    return pattern_app(Pattern.clique(k))
