"""The Graph500 Kronecker generator against its specification."""
from __future__ import annotations

import numpy as np

from bench.graphs import kronecker

ABC = dict(A=0.57, B=0.19, C=0.19)


def gen(graph_seed, scale, edgefactor=16, seed=2**31 + 9, **kw):
    return kronecker.generate(seed, scale=scale, edgefactor=edgefactor,
                              graph_seed=graph_seed, **ABC, **kw)


def test_edge_count_before_dedup():
    g = gen(3, 8)
    assert g["n"] == 256 and g["edges"].shape == (16 * 256, 2)
    assert g["edges"].min() >= 0 and g["edges"].max() < g["n"]
    assert "labels" not in g


def test_seed_determinism():
    a, b, c = gen(2**33 + 1, 8), gen(2**33 + 1, 8), gen(2**33 + 2, 8)
    assert np.array_equal(a["edges"], b["edges"])
    assert not np.array_equal(a["edges"], c["edges"])


def test_quadrant_probabilities():
    # scale 1: i == j with probability A + D whatever the permutation
    edges = gen(0, 1, edgefactor=100_000)["edges"]
    same = np.mean(edges[:, 0] == edges[:, 1])
    assert abs(same - (0.57 + 0.05)) < 0.01


def _degrees(edges, n):
    return np.bincount(edges.ravel(), minlength=n)


def test_permutation_applied():
    # unpermuted, the all-zero-bits vertex 0 is the heaviest; permuted,
    # the heaviest vertex lies elsewhere, at another id for each seed
    heaviest = []
    for graph_seed in (1, 2, 3):
        g = gen(graph_seed, 10)
        heaviest.append(int(np.argmax(_degrees(g["edges"], g["n"]))))
    assert 0 not in heaviest and len(set(heaviest)) == 3


def test_graph_seed_fixes_the_graph():
    # the run's seed changes nothing: every run mines the identical graph
    a, b = gen(0, 10, seed=1), gen(0, 10, seed=2**31 + 1)
    assert np.array_equal(a["edges"], b["edges"])


def test_zipf_labels():
    plain = gen(4, 10)
    g = gen(4, 10, labels={"count": 16, "zipf": 1.0})
    # the labels are drawn after the edges: the graph is unchanged
    assert np.array_equal(plain["edges"], g["edges"])
    lab = g["labels"]
    assert lab.shape == (g["n"],) and lab.dtype == np.int32
    assert lab.min() >= 0 and lab.max() < 16
    freq = np.bincount(lab, minlength=16) / g["n"]
    want = 1.0 / np.arange(1, 17)
    assert np.abs(freq - want / want.sum()).max() < 0.05
    assert np.array_equal(lab, gen(4, 10, labels={"count": 16,
                                                   "zipf": 1.0})["labels"])
