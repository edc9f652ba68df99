"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's numbers.

What a v5e trace holds, as read by hand: one plane per chip named
``/device:TPU:<i>``, whose line ``XLA Ops`` has one event per executed
HLO op (inside loops, one per iteration) and whose line ``XLA Modules``
has one event per executed program; the host plane ``/host:CPU`` holds,
among its threads' events, the benchmark's ``TraceAnnotation`` spans
(``bench.window`` around the measured loop, ``bench.job`` around each
job).  All events are on one clock.

* busy: the union of the ``XLA Ops`` intervals inside the window, per
  chip, averaged over the chips;
* per-op device time: the summed self time of each op (its duration
  less that of the ops nested in it: a ``while`` op's event encloses
  the events of its body's ops), by a short name (``fusion.7
  s32[65536]``);
* idle gaps: the holes in that union inside the window, each labelled by
  the benchmark span that was open on the host (``job`` or ``between
  jobs``) and the program the device ran next.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW, JOB = "bench.window", "bench.job"
TOP = 10


@dataclass
class Reduction:
    busy_s: float                 # device busy time, averaged over chips
    window_s: float
    jobs: int                     # bench.job spans inside the window
    ops: dict[str, float] = field(default_factory=dict)
    gaps: list[tuple[str, float]] = field(default_factory=list)

    def breakdown(self) -> dict:
        top = sorted(self.ops.items(), key=lambda kv: -kv[1])[:TOP]
        return {"device_ops": [[k, v] for k, v in top],
                "idle_gaps": [list(g) for g in
                              sorted(self.gaps, key=lambda g: -g[1])[:TOP]]}


def union(intervals) -> list[tuple[int, int]]:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def short_name(hlo: str) -> str:
    """``%fusion.7 = s32[65536]{0:T(1024)} fusion(...)`` -> ``fusion.7
    s32[65536]``; a tuple-typed op keeps its name alone."""
    name, _, rhs = hlo.partition(" = ")
    name = name.lstrip("%")
    if rhs and not rhs.startswith("("):
        return f"{name} {rhs.split('{')[0].split(' ')[0]}"
    return name


def self_times(events) -> dict[str, int]:
    """Nanoseconds per op name, less the time of the ops nested in it
    (a ``while`` op's event encloses those of its body)."""
    out: dict[str, int] = defaultdict(int)
    stack: list[tuple[str, int]] = []          # (name, end)
    for n, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][1] <= s:
            stack.pop()
        name = short_name(n)
        out[name] += e - s
        if stack:
            out[stack[-1][0]] -= min(e, stack[-1][1]) - s
        stack.append((name, e))
    return dict(out)


def clip(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _events(line):
    return [(ev.name, int(ev.start_ns), int(ev.end_ns)) for ev in line.events]


def host_spans(pd, name: str) -> list[tuple[int, int]]:
    return sorted((s, e) for plane in pd.planes
                  if not DEVICE_PLANE.match(plane.name)
                  for line in plane.lines
                  for n, s, e in _events(line) if n == name)


def device_lines(pd) -> list[dict[str, list]]:
    """Per chip: line name -> events, for the lines read here."""
    out = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {ln.name: _events(ln) for ln in plane.lines
                     if ln.name in (OPS_LINE, MODULES_LINE)}
            if lines.get(OPS_LINE):
                out.append(lines)
    return out


def _label(mid: int, jobs, module_starts, module_names) -> str:
    i = bisect.bisect_right(jobs, (mid, float("inf"))) - 1
    where = "job" if i >= 0 and jobs[i][0] <= mid < jobs[i][1] else \
        "between jobs"
    k = bisect.bisect_left(module_starts, mid)
    return (f"{where}, before {module_names[k]}"
            if k < len(module_names) else where)


def reduce(pd) -> Reduction:
    """Reduce one loaded trace (``jax.profiler.ProfileData``)."""
    windows = host_spans(pd, WINDOW)
    if not windows:
        raise ValueError(f"no {WINDOW!r} span in the trace")
    lo, hi = windows[-1]
    jobs = [(s, e) for s, e in host_spans(pd, JOB) if s >= lo and e <= hi]
    chips = device_lines(pd)
    if not chips:
        raise ValueError(f"no device plane with an {OPS_LINE!r} line")
    busy_ns = 0
    ops: dict[str, float] = defaultdict(float)
    for lines in chips:
        op_events = [(n, max(s, lo), min(e, hi)) for n, s, e in
                     lines[OPS_LINE] if e > lo and s < hi]
        for n, t in self_times(op_events).items():
            ops[n] += t / 1e9 / len(chips)
        busy_ns += sum(e - s for s, e in
                       union([(s, e) for _, s, e in op_events]))
    # the longest idle gaps of the first chip, labelled
    busy = union(clip([(s, e) for _, s, e in chips[0][OPS_LINE]], lo, hi))
    holes, t = [], lo
    for s, e in busy + [(hi, hi)]:
        if s > t:
            holes.append((t, s))
        t = max(t, e)
    holes.sort(key=lambda h: h[0] - h[1])
    modules = sorted(chips[0].get(MODULES_LINE, []), key=lambda m: m[1])
    starts = [s for _, s, _ in modules]
    names = [n for n, _, _ in modules]
    gaps = [(_label((s + e) // 2, jobs, starts, names), (e - s) / 1e9)
            for s, e in holes[:TOP]]
    return Reduction(busy_s=busy_ns / len(chips) / 1e9,
                     window_s=(hi - lo) / 1e9, jobs=len(jobs),
                     ops=dict(ops), gaps=gaps)


def load(path):
    from jax.profiler import ProfileData
    return ProfileData.from_file(str(path))


def find_xplane(directory) -> Path:
    found = sorted(Path(directory).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return found[-1]


def reduce_dir(directory) -> Reduction:
    return reduce(load(find_xplane(directory)))
