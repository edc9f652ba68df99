"""The trace reduction: on hand-made events, and on a small trace
recorded on a v5e by ``data/small_trace.py`` (a jitted ``while_loop``
over a gather, run three times as three ``bench.job`` spans inside one
``bench.window``)."""
from __future__ import annotations

from pathlib import Path

import pytest

from bench import xplane

RECORDED = Path(__file__).parent / "data" / "v5e_small.xplane.pb"


def test_union_and_clip():
    assert xplane.union([(5, 7), (0, 2), (1, 3), (7, 9)]) == [(0, 3), (5, 9)]
    assert xplane.clip([(0, 3), (5, 9)], 2, 6) == [(2, 3), (5, 6)]


def test_self_times_subtract_nested_ops():
    loop = "%while.3 = (s32[], s32[8]{0}) while((s32[], s32[8]{0}) %t)"
    fus = "%fusion.1 = s32[8]{0:T(1024)} fusion(s32[8]{0} %p), kind=kLoop"
    events = [(loop, 0, 100), (fus, 10, 30), (fus, 40, 70),
              ("%copy.2 = s32[8]{0} copy(s32[8]{0} %x)", 120, 125)]
    assert xplane.self_times(events) == {"while.3": 50,
                                         "fusion.1 s32[8]": 50,
                                         "copy.2 s32[8]": 5}


@pytest.fixture(scope="module")
def recorded():
    return xplane.reduce(xplane.load(RECORDED))


def test_recorded_trace_layout():
    pd = xplane.load(RECORDED)
    chips = xplane.device_lines(pd)
    assert len(chips) == 1 and chips[0][xplane.OPS_LINE]
    assert len(xplane.host_spans(pd, xplane.WINDOW)) == 1
    assert len(xplane.host_spans(pd, xplane.JOB)) == 3


def test_recorded_trace_reduction(recorded):
    r = recorded
    assert r.jobs == 3
    assert 0 < r.busy_s < r.window_s
    # every op's self time adds up to the busy time (one chip, no overlap
    # beyond nesting)
    assert sum(r.ops.values()) == pytest.approx(r.busy_s, rel=1e-6)
    assert any(name.startswith("while") for name in r.ops)
    b = r.breakdown()
    assert 0 < len(b["device_ops"]) <= xplane.TOP
    assert 0 < len(b["idle_gaps"]) <= xplane.TOP
    gaps = [s for _, s in b["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True)
    assert sum(gaps) <= r.window_s - r.busy_s + 1e-9
    assert all(label.split(",")[0] in ("job", "between jobs")
               for label, _ in b["idle_gaps"])
