"""Frequent subgraph mining (paper Listing 5).

Edge-induced exploration over a labeled graph; MNI (domain) support
(Fig. 2); FILTER drops embeddings whose pattern's support is below the
threshold — the anti-monotonic property of MNI makes this sound (§2.1
footnote 2).  k-FSM mines frequent patterns with k-1 edges (§6.1).

Eager pruning (``to_add_vertex_mask``): a candidate vertex whose *label*
occurs fewer than ``min_support`` times in the whole graph can never
appear in a frequent embedding — MNI domains are label-homogeneous, so
the domain holding that vertex is capped by the label's global
frequency.  The prune depends only on the candidate vertex, so it is
expressed as a per-vertex mask that the fused edge kernel gathers
in-VMEM (and the reference pipeline gathers in XLA): such candidates are
dropped inside the extend phase, before materialization, exactly for
every frequent pattern (it only sheds embeddings of provably-infrequent
ones) — the FSM analogue of the paper's §4 eager search-space pruning.

The engine wires the edge-induced default canonical test
(:func:`repro.core.api.is_auto_canonical_edge`) and the domain-support
reduce (:func:`repro.core.reduce.reduce_domain`); this module only adds
the pruning hook and sets the knobs, mirroring how short the paper's
Listing 5 is.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core.api import GraphCtx, MiningApp


def make_fsm_app(k: int, min_support: int,
                 max_patterns: int = 64) -> MiningApp:
    def to_add_vertex_mask(ctx: GraphCtx) -> jnp.ndarray:
        if ctx.labels is None or min_support <= 0:
            return jnp.ones((ctx.n_vertices,), bool)
        # the label histogram is computed in the program from the labels
        # it is given (a traced argument of compiled runs); the backend
        # then gathers the mask per candidate — in XLA on the reference
        # path, inside the fused edge kernel on the Pallas paths
        lab = jnp.clip(ctx.labels, 0, ctx.n_labels)
        label_freq = jnp.bincount(lab, length=ctx.n_labels + 1)
        return label_freq[lab] >= min_support

    return MiningApp(name=f"{k}-fsm", kind="edge", max_size=k,
                     needs_reduce=True, needs_filter=True,
                     support_mode="domain", min_support=min_support,
                     to_add_vertex_mask=to_add_vertex_mask,
                     max_patterns=max_patterns)
