"""The host references against brute force on small graphs."""
from __future__ import annotations

import itertools

import numpy as np
import pytest

from bench.references import cf4, tc


def brute(edges, n, k):
    adj = {(int(a), int(b)) for a, b in edges if a != b}
    adj |= {(b, a) for a, b in adj}
    return sum(all((a, b) in adj for a, b in itertools.combinations(c, 2))
               for c in itertools.combinations(range(n), k))


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("n,m", [(12, 40), (16, 70)])
def test_counts_match_brute_force(seed, n, m):
    rng = np.random.default_rng(seed)
    # self-loops and duplicates included: the references drop them
    edges = rng.integers(0, n, size=(m, 2))
    edges = np.concatenate([edges, edges[:5], [[3, 3]]])
    assert tc.count(edges, n) == brute(edges, n, 3)
    assert cf4.count(edges, n) == brute(edges, n, 4)


def test_blocked_4cf_matches_one_block(monkeypatch):
    from bench.references import common
    rng = np.random.default_rng(5)
    edges = rng.integers(0, 40, size=(400, 2))
    whole = cf4.count(edges, 40)
    real = common.edge_blocks
    monkeypatch.setattr(cf4, "edge_blocks",
                        lambda u, src: real(u, src, budget=7))
    assert cf4.count(edges, 40) == whole == brute(edges, 40, 4)


def test_approximate_breaks_exactness():
    rng = np.random.default_rng(1)
    edges = rng.integers(0, 60, size=(900, 2))
    for ref in (tc, cf4):
        exact = ref.count(edges, 60)
        assert ref.approximate(edges, 60, 1.0, 0) == exact
        assert ref.approximate(edges, 60, 0.9, 0) != exact
