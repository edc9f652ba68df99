"""Graph500 Kronecker generator, with its random vertex permutation.

A line-by-line numpy transcription of the specification's reference
generator (Graph500 benchmark specification, "Kronecker generator",
``kronecker_generator.m``)::

    N = 2^SCALE; M = edgefactor * N
    ab = A + B; c_norm = C / (1 - ab); a_norm = A / ab
    for each of SCALE bits:
        ii_bit = rand(M) > ab
        jj_bit = rand(M) > (c_norm * ii_bit + a_norm * not ii_bit)
        ij += 2^bit * [ii_bit; jj_bit]
    ij = p(ij) for a random permutation p of the N vertex ids

The specification's last step, a random order of the edge list, is left
out: the program's ingestion sorts the list, so the order reaches
nothing.  The edge list keeps the self-loops and duplicates the
generator draws; the ingestion drops them.  ``D`` is implied: 1 - A - B
- C.

The graph is drawn from ``graph_seed``, a number of the configuration,
and not from the run's seed: every run of a configuration mines the
identical graph.  With vertex ids drawn per run, the program's sampled
planner gives each graph its own executor shapes, so every run would
compile in set-up and the work itself would move with the seed.

``labels``, where a configuration gives it (``{"count": L, "zipf": s}``),
draws one label per vertex from ``0 .. L-1`` with probability
proportional to ``1 / (l + 1)^s``, after the edges and from the same
draw.
"""
from __future__ import annotations

import numpy as np


def generate(seed: int, *, scale: int, edgefactor: int, A: float,
             B: float, C: float, graph_seed: int,
             labels: dict | None = None) -> dict:
    """Return ``{"edges": int64 [M, 2], "n": N}``, with ``"labels"``
    (int32 ``[N]``) where the configuration asks for them.  ``seed``,
    the run's, is not used (see the module's docstring)."""
    del seed
    n = 1 << scale
    m = edgefactor * n
    rng = np.random.default_rng(graph_seed)
    ab = A + B
    c_norm = C / (1.0 - ab)
    a_norm = A / ab
    ii = np.zeros(m, np.int64)
    jj = np.zeros(m, np.int64)
    for bit in range(scale):
        ii_bit = rng.random(m) > ab
        jj_bit = rng.random(m) > np.where(ii_bit, c_norm, a_norm)
        ii |= ii_bit.astype(np.int64) << bit
        jj |= jj_bit.astype(np.int64) << bit
    perm = rng.permutation(n)
    graph = {"edges": np.stack([perm[ii], perm[jj]], axis=1), "n": n}
    if labels is not None:
        p = 1.0 / np.arange(1, labels["count"] + 1) ** labels["zipf"]
        graph["labels"] = rng.choice(labels["count"], size=n,
                                     p=p / p.sum()).astype(np.int32)
    return graph
