"""Observability layer (PR 9): tracing, metrics, and the wired stack.

Contracts under test:

* the span tracer nests, exports valid Chrome trace-event JSON
  (:mod:`repro.obs.validate` is the schema), and costs < 100ns per
  guarded call site when disabled (the ``if trace.on:`` fast path);
  its ``annotate`` hook opens one annotation per span, in nesting
  order, and garbage collections are spans only while it is enabled;
* the histogram's log2 bucket math and percentile bounds;
* the registry's typed get-or-create, render/snapshot shapes;
* live-bytes drift detection (actual > predicted fires the warning);
* the wired stack: ``mine --trace --metrics`` emits one span per level
  plus plan-provenance events and per-level cap-utilization gauges;
  ``serve --mine`` reports p50/p99 over the query stream; the block
  scheduler records stage/mine overlap; the executor distinguishes
  compiles from replays; ``mine --profile`` puts the program's spans
  into the profiler's trace.
"""
import json
import time

import pytest

from repro.obs import metrics, report, trace
from repro.obs.validate import validate_metrics, validate_trace


@pytest.fixture(autouse=True)
def _clean_obs():
    """Every test starts and ends with tracer off + empty registry."""
    trace.disable()
    metrics.reset()
    yield
    trace.disable()
    metrics.reset()


# -- tracer -------------------------------------------------------------------


def test_span_nesting_and_chrome_schema(tmp_path):
    trace.enable()
    with trace.span("outer", cat="t", level=1):
        time.sleep(0.002)
        with trace.span("inner", cat="t"):
            time.sleep(0.001)
    trace.instant("plan.test", cat="plan", note="hi")
    with trace.span("level", level=2) as sp:
        sp.set(survivors=7)
    path = tmp_path / "t.json"
    trace.save(str(path))
    doc = json.loads(path.read_text())
    info = validate_trace(doc)
    assert info["events"] == 4
    evs = {e["name"]: e for e in doc["traceEvents"]}
    outer, inner = evs["outer"], evs["inner"]
    # same thread; nested by interval containment (how Perfetto stacks)
    assert outer["tid"] == inner["tid"]
    assert outer["ts"] <= inner["ts"]
    assert outer["ts"] + outer["dur"] >= inner["ts"] + inner["dur"]
    assert evs["plan.test"]["ph"] == "i"
    assert evs["level"]["args"]["survivors"] == 7
    assert all("cpu_us" in e["args"] for e in doc["traceEvents"]
               if e["ph"] == "X")


def test_span_args_coerce_to_json(tmp_path):
    np = pytest.importorskip("numpy")
    trace.enable()
    with trace.span("x", n=np.int32(5), f=np.float64(0.5), o=object()):
        pass
    path = tmp_path / "t.json"
    trace.save(str(path))                # must not raise on json.dump
    args = json.loads(path.read_text())["traceEvents"][0]["args"]
    assert args["n"] == 5 and args["f"] == 0.5 and isinstance(args["o"], str)


def test_disabled_tracer_is_noop_and_off():
    assert not trace.on
    with trace.span("x", level=1) as sp:
        sp.set(a=1)                      # no-op, no error
    trace.instant("y")
    assert trace.save("/nonexistent/dir/t.json") is None   # no write attempt
    assert trace.get() is None


def test_disabled_guard_overhead_under_100ns():
    """The hot-path idiom `if trace.on:` must cost < 100ns per call site.

    Best-of-5 batches of 200k iterations: the *minimum* batch mean is
    the machine's actual cost with scheduler noise excluded (any single
    batch can only be slowed down, never sped up).
    """
    assert not trace.on
    n = 200_000
    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter_ns()
        for _ in range(n):
            if trace.on:
                with trace.span("x", level=3):
                    pass
        best = min(best, (time.perf_counter_ns() - t0) / n)
    assert best < 100.0, f"disabled guard costs {best:.0f}ns/span"


class _Recorder:
    """An ``annotate`` hook that logs each annotation's enter and exit."""

    def __init__(self):
        self.log = []

    def __call__(self, name):
        rec = self

        class _Ann:
            def __enter__(self):
                rec.log.append(("open", name))

            def __exit__(self, *exc):
                rec.log.append(("close", name))
        return _Ann()


def test_annotate_hook_opens_and_closes_once_per_span():
    rec = _Recorder()
    trace.enable(annotate=rec)
    with trace.span("outer"):
        sp = trace.span("level", level=2).__enter__()   # the loop's form
        with trace.span("inner"):
            pass
        sp.end()
        sp.end()                                        # idempotent
    trace.disable()
    assert [e for e in rec.log if e[1] != "python.gc"] == [
        ("open", "outer"), ("open", "level"), ("open", "inner"),
        ("close", "inner"), ("close", "level"), ("close", "outer")]


def test_gc_spans_only_while_enabled():
    import gc

    gc.collect()
    assert not trace.active()
    trace.enable()
    gc.collect()
    tracer = trace.get()
    trace.disable()
    gc.collect()
    spans = [e for e in tracer.events if e["name"] == "python.gc"]
    assert any(e["args"]["generation"] == 2 for e in spans)
    assert all(cb.__self__ is not tracer for cb in gc.callbacks
               if hasattr(cb, "__self__"))


# -- metrics ------------------------------------------------------------------


def test_histogram_bucket_math():
    h = metrics.Histogram()
    # bucket i covers (2^(i-1), 2^i]
    assert h.bucket_of(1.0) == 0
    assert h.bucket_of(1.5) == 1
    assert h.bucket_of(2.0) == 1
    assert h.bucket_of(2.001) == 2
    assert h.bucket_of(1024.0) == 10
    assert h.bucket_of(0.25) == -2
    assert h.bucket_of(0.0) is None and h.bucket_of(-3.0) is None
    assert h.bucket_of(1e-30) == -64     # clamp: no unbounded tail


def test_histogram_percentile_upper_bound():
    h = metrics.Histogram()
    for v in [1, 2, 3, 4, 100]:
        h.observe(v)
    s = h.summary()
    assert s["count"] == 5 and s["min"] == 1 and s["max"] == 100
    # percentile returns the upper bucket edge: within 2x above the true
    # quantile, never below it
    assert 3 <= s["p50"] <= 6
    assert 100 <= s["p99"] <= 200
    assert h.percentile(0.0) in (0.0, 1.0)


def test_histogram_zero_bucket():
    h = metrics.Histogram()
    h.observe(0.0)
    h.observe(0.0)
    h.observe(8.0)
    assert h.summary()["zero"] == 2
    assert h.percentile(0.5) == 0.0


def test_registry_identity_and_types():
    metrics.inc("c", 2.0, app="tc")
    metrics.inc("c", 3.0, app="tc")
    metrics.inc("c", 1.0, app="mc")      # different labels = new metric
    assert metrics.value("c", app="tc") == 5.0
    assert metrics.value("c", app="mc") == 1.0
    assert metrics.value("missing") is None
    with pytest.raises(TypeError):
        metrics.gauge("c", app="tc")     # kind mismatch on the same key


def test_registry_render_and_snapshot():
    metrics.inc("mine.candidates", 10, level=2)
    metrics.set_gauge("mine.cap_utilization", 0.9, level=2)
    metrics.observe("lat_ms", 3.0)
    text = metrics.render()
    assert "counter   mine.candidates{level=2} 10" in text
    assert "gauge     mine.cap_utilization{level=2} 0.9" in text
    assert "histogram lat_ms" in text
    snap = metrics.snapshot()
    validate_metrics(snap)
    assert snap["histograms"]["lat_ms"]["count"] == 1
    json.dumps(snap)                     # JSON-serializable end to end


def test_metrics_dump_json_and_text(tmp_path):
    metrics.set_gauge("g", 1.5)
    j = tmp_path / "m.json"
    t = tmp_path / "m.txt"
    assert metrics.dump(str(j)) == str(j)
    assert json.loads(j.read_text())["gauges"]["g"] == 1.5
    metrics.dump(str(t))
    assert "gauge     g 1.5" in t.read_text()
    assert "gauge     g 1.5" in metrics.dump(None)


def test_report_level_table():
    class S:
        def __init__(self, level, nc, ns, cap):
            self.level, self.n_candidates = level, nc
            self.n_embeddings, self.capacity = ns, cap
            self.seconds, self.live_bytes = 0.01, 1 << 20
    table = report.level_table([S(2, 100, 50, 64), S(3, 10, 5, 128)])
    lines = table.splitlines()
    assert lines[0].split() == ["level", "candidates", "survivors", "cap",
                                "util%", "time_ms", "live_MB"]
    assert lines[1].split()[:5] == ["2", "100", "50", "64", "78.1"]


# -- live-bytes drift ---------------------------------------------------------


def test_live_bytes_drift_warning():
    from repro.core.engine import LevelStats, _note_live_bytes
    from repro.core.plan import MiningPlan

    plan = MiningPlan(kind="vertex", caps=((256, 128),))
    stats = [LevelStats(2, 10, 5, 128, 1000, 0.01, live_bytes=10_000)]
    trace.enable()
    _note_live_bytes("vertex", plan, 256, stats)
    # model predicts > 10KB for these caps: no overrun
    assert metrics.value("blocks.live_bytes.actual") == 10_000
    assert metrics.value("blocks.live_bytes.overrun") is None
    # an absurd observed peak must fire the warning + counter
    stats = [LevelStats(2, 10, 5, 128, 1000, 0.01, live_bytes=10**9)]
    _note_live_bytes("vertex", plan, 256, stats, block=3)
    assert metrics.value("blocks.live_bytes.overrun") == 1.0
    warn = [e for e in trace.get().events
            if e["name"] == "live_bytes_overrun"]
    assert len(warn) == 1 and warn[0]["args"]["block"] == 3


# -- the wired stack ----------------------------------------------------------


def test_mine_cli_trace_and_metrics_smoke(tmp_path, capsys):
    from repro.launch.mine import main

    tr = tmp_path / "t.json"
    mt = tmp_path / "m.json"
    main(["--app", "3-mc", "--graph", "er:60,0.1", "--stats",
          "--trace", str(tr), "--metrics", str(mt)])
    out = capsys.readouterr().out
    assert "util%" in out                # structured reporter table
    doc = json.loads(tr.read_text())
    info = validate_trace(doc)           # >=1 level span, >=1 plan event
    assert info["level_spans"] >= 1 and info["plan_events"] >= 1
    names = [e["name"] for e in doc["traceEvents"]]
    assert "miner.run" in names and "op.extend_pruned" in names
    snap = json.loads(mt.read_text())
    validate_metrics(snap)               # cap_utilization gauges in [0,1]


def test_mine_cli_profile_has_program_spans(tmp_path):
    """``--profile DIR``: the program's spans are annotated into the
    profiler session, on the host plane of the written xplane, and the
    executor ops' scopes are written beside it."""
    import glob

    from jax.profiler import ProfileData

    from repro.launch.mine import main

    main(["--app", "tc", "--graph", "er:60,0.1", "--plan", "estimate",
          "--repeat", "2", "--profile", str(tmp_path)])
    found = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    assert found
    pd = ProfileData.from_file(found[0])
    host = {e.name for p in pd.planes if p.name.startswith("/host:")
            for line in p.lines for e in line.events}
    assert {"miner.run", "executor.replay", "executor.wait",
            "executor.fetch", "miner.worklist"} <= host
    scopes = json.loads((tmp_path / "op_scopes.json").read_text())
    assert any("level2/probe" in v for ops in scopes.values()
               for v in ops.values())


def test_blocked_mine_records_overlap_and_blocks(tmp_path):
    from repro.launch.mine import main

    tr = tmp_path / "t.json"
    main(["--app", "tc", "--graph", "er:100,0.08", "--blocks", "3",
          "--stats", "--trace", str(tr)])
    overlap = metrics.value("blocks.stage_overlap")
    assert overlap is not None and 0.0 < overlap <= 1.0
    assert metrics.REGISTRY.histogram("blocks.stage_ms").count >= 1
    assert metrics.REGISTRY.histogram("blocks.mine_ms").count == 3
    # per-block actual-vs-predicted live-bytes gauges (satellite 2)
    assert metrics.value("blocks.live_bytes.actual", block=0) is not None
    assert metrics.value("blocks.live_bytes.predicted", block=0) is not None
    doc = json.loads(tr.read_text())
    names = [e["name"] for e in doc["traceEvents"]]
    assert names.count("block") == 3 and "block.stage" in names


def test_executor_compile_vs_replay_counters():
    from repro.core import Miner, make_tc_app
    from repro.graph import generators as G

    m = Miner(G.erdos_renyi(60, 0.1, seed=1), make_tc_app())
    m.run()                              # plans (host inspection)
    m.run()                              # compile, then the first replay
    m.run()                              # second replay
    assert metrics.value("executor.compiles", kind="vertex") == 1.0
    assert metrics.value("executor.replays", kind="vertex") == 2.0
    assert metrics.value("executor.compile_s", kind="vertex") > \
        metrics.value("executor.replay_s", kind="vertex")
    assert metrics.value("executor.wait_s", kind="vertex") <= \
        metrics.value("executor.replay_s", kind="vertex")
    assert metrics.value("plan.inspect", kind="vertex") == 1.0


def test_serve_mine_latency_summary(capsys):
    from repro.launch.serve import main

    main(["--mine", "--graph", "er:60,0.1", "--queries", "tc,3-mc",
          "--query-repeats", "10", "--metrics"])
    out = capsys.readouterr().out
    assert "p50=" in out and "p99=" in out
    warm = metrics.REGISTRY.histogram("serve.warm_ms")
    assert warm.count == 20              # 2 queries x 10 repeats
    assert metrics.REGISTRY.histogram("serve.first_ms").count == 2


def test_estimate_plan_span(tmp_path):
    from repro.core import Miner, make_tc_app
    from repro.graph import generators as G

    trace.enable()
    m = Miner(G.erdos_renyi(60, 0.1, seed=1), make_tc_app())
    m.run(plan_source="estimate")
    names = [e["name"] for e in trace.get().events]
    assert "plan.estimate" in names and "plan.estimated" in names
