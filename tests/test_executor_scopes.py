"""What the compiled executor tells about itself.

* Named scopes: every executor program carries ``level{L}/<phase>``
  scopes in its compiled HLO text (``Compiled.as_text()``), and
  ``Miner.op_scopes()`` maps its instructions to them.
* Replay-path counters: with tracing on, a replay reads its per-level
  (candidates, survivors, fill entries) back and records them under the
  host path's names, with the host path's values; with tracing off
  nothing is read back and the registry gains no level entry.
"""
import numpy as np
import pytest

from repro.core import Miner
from repro.core.plan import hlo_op_scopes, level_phase
from repro.graph import generators as G
from repro.launch.mine import make_app
from repro.obs import metrics, trace

_RUNS: dict = {}

LEVEL_METRICS = ("mine.candidates", "mine.survivors", "mine.fill_entries",
                 "mine.cap_utilization", "mine.cand_cap_utilization")


def _levels(name: str) -> dict:
    return {dict(labels)["level"]: m.value
            for labels, m in metrics.find(name).items()}


def _mined(app: str) -> dict:
    """One Miner per app: a host (inspection) run, then a traced
    executor run; the counters each recorded."""
    if app not in _RUNS:
        g = (G.erdos_renyi(14, 0.3, seed=5, labels=3) if "fsm" in app
             else G.erdos_renyi(40, 0.2, seed=3))
        miner = Miner(g, make_app(app, 2))
        trace.disable()
        metrics.reset()
        host = miner.run()
        out = {"miner": miner, "host_count": host.count,
               "host": {n: _levels(n) for n in LEVEL_METRICS[:3]}}
        metrics.reset()
        trace.enable()
        replay = miner.run()
        events = trace.get().events
        trace.disable()
        out.update(replay_count=replay.count,
                   replay={n: _levels(n) for n in LEVEL_METRICS},
                   span=[e for e in events if e["name"] == "executor.replay"])
        metrics.reset()
        _RUNS[app] = out
    return _RUNS[app]


EXPECTED_SCOPES = {
    "tc": {"level2/" + p for p in ("rows", "fill", "draw", "probe",
                                   "compact")},
    "4-cf": ({"level2/" + p for p in ("rows", "fill", "draw", "probe",
                                      "compact", "emit")}
             | {"level3/" + p for p in ("rows", "fill", "draw", "probe",
                                        "compact")}),
    "3-fsm": ({"level1/reduce", "level1/filter"}
              | {"level2/" + p for p in ("rows", "fill", "draw", "probe",
                                         "compact", "reduce", "filter")}),
}


@pytest.mark.parametrize("app", sorted(EXPECTED_SCOPES))
def test_executor_carries_level_phase_scopes(app):
    miner = _mined(app)["miner"]
    (ex,) = miner._executors.values()
    (exe,) = ex._fns.values()
    text = exe.as_text()
    for scope in EXPECTED_SCOPES[app]:
        assert f"/{scope}/" in text, scope
    scopes = miner.op_scopes()
    assert list(scopes) == [f"jit_mine_{ex.kind}_{ex.cap0}"]
    named = {level_phase(path) for ops in scopes.values()
             for path in ops.values()}
    assert EXPECTED_SCOPES[app] <= named


@pytest.mark.parametrize("app", ["tc", "4-cf", "3-mc"])
def test_replay_counts_equal_host_counts(app):
    run = _mined(app)
    assert run["replay_count"] == run["host_count"]
    for name in LEVEL_METRICS[:3]:
        assert run["replay"][name] == run["host"][name], name
    (ex,) = run["miner"]._executors.values()
    counts = np.asarray(ex.last_level_counts)
    assert counts.shape == (len(ex.plan.caps), 3)
    for li, (nc, ns, nf) in enumerate(counts.tolist()):
        level = li + 2
        assert (nc, ns, nf) == tuple(run["host"][name][level]
                                     for name in LEVEL_METRICS[:3])
        out_cap = ex.plan.caps[li][1]
        assert run["replay"]["mine.cap_utilization"][level] == ns / out_cap
    (span,) = run["span"]
    last = len(counts) + 1
    assert span["args"][f"candidates.level{last}"] == counts[-1][0]
    assert span["args"][f"survivors.level{last}"] == counts[-1][1]
    assert span["args"][f"fill_entries.level{last}"] == counts[-1][2]


def test_traced_replay_counts_fill_entries(monkeypatch):
    """With chunks of 16 slots and fill passes of 2 rows, a traced replay
    records as each level's ``mine.fill_entries`` the passes its chunks
    took, 3 entries a pass: a chunk's rows are those starting in it and
    the one running into it, and a pass reaches 2 rows past its first."""
    from repro.core.embedding_list import materialize
    from repro.core.phases import reference as R

    monkeypatch.setattr(R, "CHUNK_SLOTS", 16)
    monkeypatch.setattr(R, "FILL_ROWS", 2)
    app = make_app("4-cf", 2)
    miner = Miner(G.erdos_renyi(40, 0.2, seed=3), app)
    trace.disable()
    levels = miner.run().levels           # plans the capacities
    metrics.reset()
    trace.enable()
    miner.run()
    trace.disable()
    fill = _levels("mine.fill_entries")
    metrics.reset()
    (ex,) = miner._executors.values()
    assert sorted(fill) == [2, 3]
    for level, (cand_cap, _) in enumerate(ex.plan.caps, start=2):
        front = levels[:level - 1]
        emb = materialize(front)
        deg = R.vertex_ext_degrees(miner.ctx, app, emb, front[-1].n)
        sizes = np.asarray(deg).sum(axis=1)
        starts = np.cumsum(sizes)[sizes > 0] - sizes[sizes > 0]
        chunk = min(cand_cap, 16)
        want = 0
        for base in range(0, min(int(sizes.sum()), cand_cap), chunk):
            q0, q1 = (np.searchsorted(starts, at, side="right") - 1
                      for at in (base, base + chunk - 1))
            want += max(-(-(q1 - q0) // 2), 1) * 3
        assert want > 3 * len(range(0, int(sizes.sum()), chunk))
        assert fill[level] == want, level


def test_untraced_replay_reads_nothing_back():
    miner = _mined("tc")["miner"]
    metrics.reset()
    assert not trace.on
    miner.run()
    for name in LEVEL_METRICS:
        assert not metrics.find(name), name
    assert not metrics.find("executor.replay_candidates")
    assert metrics.value("executor.replays", kind="vertex") == 1.0
    metrics.reset()


def test_hlo_op_scopes_reads_fusion_metadata():
    text = "\n".join([
        "HloModule jit_mine_vertex_256, is_scheduled=true",
        '  %gather_bitcast_fusion = s32[64]{0} fusion(%a), kind=kLoop, '
        'metadata={op_type="gather" op_name="jit(mine_vertex_256)/while/'
        'body/level2/draw/gather" source_file="x.py" source_line=3}',
        '  ROOT %reduce_window_sum.3 = s32[64]{0} reduce-window(%b), '
        'metadata={op_name="reduce_window_sum"}',
        "  %c = s32[] constant(0)"])
    assert hlo_op_scopes(text) == {"jit_mine_vertex_256": {
        "gather_bitcast_fusion": "while/body/level2/draw/gather"}}
    assert level_phase("while/body/level2/draw/gather") == "level2/draw"
    assert level_phase("level3/probe/jit(clip)/min") == "level3/probe"
    assert level_phase("level2/while") is None
    assert level_phase("reduce_sum") is None
