"""The work functions on hand-counted graphs."""
from __future__ import annotations

import numpy as np

from bench.references import cf4, tc


def test_k4():
    # equal degrees: the DAG follows the ids, out-degrees 3, 2, 1, 0
    edges = np.array([[a, b] for a in range(4) for b in range(a + 1, 4)])
    assert tc.work(edges, 4) == 3 + 1          # C(3,2) + C(2,2)
    assert tc.count(edges, 4) == 4
    # only DAG edge (0, 1) has two common out-neighbours, {2, 3}
    assert cf4.work(edges, 4) == 1
    assert cf4.count(edges, 4) == 1


def test_triangle_with_pendant():
    # triangle 0-1-2 and pendant 3 on vertex 2: degrees 2, 2, 3, 1;
    # ranks 3 < 0 < 1 < 2, so DAG 3->2, 0->1, 0->2, 1->2
    edges = np.array([[0, 1], [1, 2], [0, 2], [2, 3]])
    assert tc.work(edges, 4) == 1              # vertex 0: C(2,2)
    assert tc.count(edges, 4) == 1
    assert cf4.work(edges, 4) == 0             # (0,1) shares only {2}
    assert cf4.count(edges, 4) == 0
