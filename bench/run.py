"""The on-chip benchmark: one cell of ``BENCHMARK.json`` per run.

    python3 bench/run.py --workload g500-s16.tc --seed 7 --seconds 10 \
        --trace 0

Everything that belongs to one configuration, traffic mix or metric is a
file found by its name: ``bench/configs/<config>.json`` (a deployment:
the generator under ``bench/graphs/`` and its parameters; a generator
returns the graph as ``{"edges", "n"}``, with ``"labels"`` where it has
them), ``bench/traffic/<traffic>.json`` (the entry under
``bench/entries/`` that runs a job, the app, the ``Miner.run`` keywords,
the loop and the host reference under ``bench/references/``) and
``bench/metrics/<metric>.py`` (one reader per metric).  This file has no
branch on a cell, a configuration, an app or a metric.

A run: check that JAX sees an accelerator with the cell's chips (else
exit 2, no result); generate the graph; set-up (the entry: the
program's ingestion and ``Miner(graph, app)``; then the first and cold
job); the window (a closed loop of jobs on that entry until
``--seconds`` have passed, every started job finished; with ``--trace
1`` under the JAX profiler); the peak device memory; then, with the
program's state freed, the host reference count, which every job's
count has to equal.  The last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or its per-layer metrics with ``--trace 1``),
``device``, with ``--trace 1`` a ``breakdown``, and last ``checks``,
each number compared beside its limit.  The same checks are the last
lines of standard error.
"""
from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Optional

ROOT = Path(__file__).resolve().parents[1]


def say(*parts) -> None:
    print("[bench]", *parts, flush=True)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    """Import a file by its path (names may hold '.' or '-')."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    """One ``workloads`` entry with the files it names."""
    name: str
    chips: int
    config: dict
    traffic: dict
    metrics: list[dict]          # the BENCHMARK.json metric entries

    @classmethod
    def find(cls, root: Path, workload: str, trace: bool) -> "Cell":
        spec = load_json(root / "BENCHMARK.json")
        cells = {w["name"]: w for w in spec["workloads"]}
        if workload not in cells:
            raise SystemExit(f"no workload {workload!r} in BENCHMARK.json "
                             f"(have {sorted(cells)})")
        w = cells[workload]
        cfg = {c["name"]: c for c in spec["configs"]}[w["config"]]
        kind = "per_layer" if trace else "end_to_end"
        metrics = [m for m in spec[kind]
                   if workload in m.get("workloads", [workload])]
        return cls(name=workload, chips=int(w["chips"]),
                   config=load_json(root / cfg["file"]),
                   traffic=load_json(root / "bench" / "traffic"
                                     / f"{w['traffic']}.json"),
                   metrics=metrics)


@dataclass
class Run:
    """What one run measured; the metric readers read it."""
    jobs: list[tuple[float, float]] = field(default_factory=list)
    setup_s: float = 0.0
    spans: dict[str, float] = field(default_factory=dict)
    before: dict[str, int] = field(default_factory=dict)
    after: dict[str, int] = field(default_factory=dict)
    peak_bytes: Optional[int] = None
    work: Optional[int] = None    # candidates one job must test
    peaks: dict = field(default_factory=dict)
    trace: Optional[Any] = None   # bench.xplane.Reduction


def require_accelerator(chips: int) -> list:
    """The devices the cell runs on; exit 2 without an accelerator or
    with fewer chips than the cell asks for.  Never falls back to the
    CPU."""
    import jax
    devs = jax.devices()
    if devs[0].platform == "cpu":
        print(f"no accelerator: JAX reports platform {devs[0].platform!r}",
              file=sys.stderr)
        raise SystemExit(2)
    if len(devs) < chips:
        print(f"{chips} chips wanted, JAX sees {len(devs)}", file=sys.stderr)
        raise SystemExit(2)
    return devs[:chips]


class CompileLog:
    """XLA compiles (or compile-cache loads) and the cache's hits, from
    JAX's monitoring events."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    TRACE = "/jax/core/compile/jaxpr_trace_duration"

    def __init__(self):
        self.reset()

    def reset(self):
        self.compiles: list[tuple[float, str]] = []
        self.trace_s = 0.0
        self.requests = self.hits = 0

    def on_duration(self, event, duration, fun_name="?", **_):
        if event == self.COMPILE:
            self.compiles.append((duration, fun_name))
        elif event == self.TRACE:
            self.trace_s += duration

    def on_event(self, event, **_):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def summary(self) -> str:
        slow = sorted(self.compiles, reverse=True)[:5]
        return (f"{len(self.compiles)} programs in "
                f"{sum(d for d, _ in self.compiles):.3f} s (cache hits "
                f"{self.hits} of {self.requests}), tracing "
                f"{self.trace_s:.3f} s; slowest "
                + ", ".join(f"{n} {d:.3f} s" for d, n in slow))


def span_summary(events, top: int = 8) -> str:
    """The longest of the program's host spans (``repro.obs.trace``)."""
    spans = sorted((e["dur"] / 1e6, e["name"]) for e in events
                   if e.get("ph") == "X")
    return ", ".join(f"{n} {d:.3f} s" for d, n in spans[::-1][:top])


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             root: Path = ROOT) -> dict:
    t_start = time.perf_counter()
    devices = require_accelerator(cell.chips)
    import jax
    kind = devices[0].device_kind
    peaks = load_json(root / "bench" / "peaks.json")
    if kind not in peaks:
        raise SystemExit(f"device kind {kind!r} not in bench/peaks.json")

    sys.path.insert(0, str(root / "src"))
    from repro.launch.compile_cache import configure_compile_cache
    cache_dir = configure_compile_cache()
    # every program, however quick to compile, goes to the cache, so that
    # a cell's second run in a checkout compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    log = CompileLog()
    jax.monitoring.register_event_duration_secs_listener(log.on_duration)
    jax.monitoring.register_event_listener(log.on_event)

    # the benchmark's own input: not set-up, a user would not pay it
    t0 = time.perf_counter()
    cfg = cell.config
    gen = load_module(root / "bench" / "graphs" / f"{cfg['generator']}.py")
    graph = gen.generate(seed, **cfg["params"])
    gen_s = time.perf_counter() - t0
    say(f"{cell.name}: {cfg['name']} (run seed {seed}): {graph['n']} "
        f"vertices, {len(graph['edges'])} generated edges"
        + (", labelled" if "labels" in graph else "")
        + f" in {gen_s:.3f} s; compile cache {cache_dir}")

    tr = cell.traffic
    if tr.get("loop") != "closed" or int(tr.get("clients", 1)) != 1:
        raise SystemExit("only a closed loop with one client is driven")
    ref = load_module(root / "bench" / "references" / f"{tr['reference']}.py")
    entry_mod = load_module(root / "bench" / "entries" / f"{tr['entry']}.py")
    run = Run(peaks=peaks[kind])

    # set-up: the entry (ingestion, Miner), the first (cold) job
    entry = entry_mod.Entry(graph, tr, devices, run.spans)
    from repro.obs import trace as obs_trace
    tracer = obs_trace.enable()       # the program's own host spans
    t0 = time.perf_counter()
    counts = [entry.job()]
    t1 = time.perf_counter()
    obs_trace.disable()
    run.spans["first_job"] = t1 - t0
    run.setup_s = (t1 - t_start) - gen_s
    say(f"set-up compiles: {log.summary()}")
    say(f"first job's program spans: {span_summary(tracer.events)}")
    log.reset()
    say(f"set-up {run.setup_s:.3f} s: "
        + ", ".join(f"{k} {v:.3f} s" for k, v in run.spans.items())
        + f"; {entry.edges} edges; executor {entry.counters()}")

    # the window
    run.before = entry.counters()
    prof_dir = None
    if trace:
        prof_dir = tempfile.mkdtemp(prefix="bench-trace-")
        jax.profiler.start_trace(prof_dir)
    failed = 0
    with jax.profiler.TraceAnnotation("bench.window"):
        w0 = time.perf_counter()
        while True:
            s = time.perf_counter()
            try:
                with jax.profiler.TraceAnnotation("bench.job"):
                    c = entry.job()
            except Exception as e:        # a job that raises has failed
                print(f"job {len(run.jobs)} raised: {e!r}", file=sys.stderr)
                c, failed = None, failed + 1
            end = time.perf_counter()
            run.jobs.append((s, end))
            counts.append(c)
            if end - w0 >= seconds:
                break
    if trace:
        jax.profiler.stop_trace()
    run.after = entry.counters()
    stats = [d.memory_stats() or {} for d in devices]
    # the executables' temporaries are reserved memory, outside
    # peak_bytes_in_use (buffers) on the v5e: a chip's peak is both
    chip_peaks = [s["peak_bytes_in_use"] + s.get("peak_bytes_reserved", 0)
                  for s in stats if "peak_bytes_in_use" in s]
    run.peak_bytes = max(chip_peaks) if chip_peaks else None
    say(f"window: {len(run.jobs)} jobs in "
        f"{run.jobs[-1][1] - run.jobs[0][0]:.3f} s; executor "
        f"{run.before} -> {run.after}; XLA compiles in the window "
        f"{len(log.compiles)}; memory_stats {stats[0]}")

    # the program's state goes before the reference runs
    del entry
    gc.collect()
    if trace:
        from bench import xplane
        run.trace = xplane.reduce_dir(prof_dir)
        shutil.rmtree(prof_dir, ignore_errors=True)

    t0 = time.perf_counter()
    want = ref.count(**graph)
    ref_s = time.perf_counter() - t0
    if trace:
        run.work = ref.work(**graph)
    gap = max((abs(c - want) for c in counts if c is not None), default=0)
    failed += sum(c is not None and c != want for c in counts)
    say(f"host reference {tr['reference']}: {want} in {ref_s:.3f} s; "
        f"counts {sorted(set(counts), key=str)}")

    metrics = {}
    for m in cell.metrics:
        value = load_module(root / "bench" / "metrics"
                            / f"{m['name']}.py").read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": run.peak_bytes}
    out: dict[str, Any] = {"correct": failed == 0 and len(counts) > 1,
                           "attempted": len(counts), "failed": failed,
                           "metrics": metrics, "device": device}
    if run.trace is not None:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        out["breakdown"] = run.trace.breakdown()
    out["checks"] = {"count_gap": {"value": gap, "limit": 0},
                     "failed_jobs": {"value": failed, "limit": 0}}
    for name, c in out["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr)
    return out


def main(argv=None, root: Path = ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = Cell.find(root, args.workload, bool(args.trace))
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace), root)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    # import the benchmark as the package ``bench``, never its files as
    # top-level modules
    sys.path[0] = str(ROOT)
    sys.exit(main())
