"""The readers of the program's own counters: on a hand-made ``Run`` and
registry, on a registry the program left empty (they read nothing and
raise nothing), and after a harness run of a tiny cell on the CPU."""
from __future__ import annotations

import pytest

from bench import run as R
from bench.tests.conftest import REPO
from bench.xplane import Reduction
from repro.obs import metrics, trace

READERS = ("plan_estimate_s", "executor_compile_s", "cap_utilization",
           "device_ns_per_candidate", "job_host_s")


def read(name: str, run: R.Run):
    return R.load_module(REPO / "bench" / "metrics" / f"{name}.py").read(run)


@pytest.fixture
def registry():
    trace.disable()
    metrics.reset()
    yield metrics
    metrics.reset()


def traced_run(busy_s: float = 2.0, jobs: int = 2) -> R.Run:
    return R.Run(jobs=[(0.0, 1.0)] * jobs,
                 trace=Reduction(busy_s=busy_s, window_s=busy_s + 0.1,
                                 jobs=jobs))


def test_readers_on_a_hand_made_registry(registry):
    registry.inc("plan.estimate_s", 0.25, kind="vertex")
    registry.inc("executor.compile_s", 1.5, kind="vertex")
    registry.inc("executor.wait_s", 9.0, kind="vertex")
    registry.set_gauge("mine.cap_utilization", 0.4, level=2)
    registry.set_gauge("mine.cap_utilization", 0.25, level=3)
    registry.observe("executor.replay_candidates", 4e6)
    registry.set_gauge("miner.host_s", 0.003)
    run = traced_run(busy_s=2.0, jobs=2)
    assert read("plan_estimate_s", run) == 0.25
    assert read("executor_compile_s", run) == 1.5
    assert read("cap_utilization", run) == 0.25
    assert read("device_ns_per_candidate", run) == pytest.approx(250.0)
    assert read("job_host_s", run) == 0.003


def test_compile_reader_needs_the_timed_wait(registry):
    """Without ``executor.wait_s`` the compile counter also holds the
    first execution: nothing is read."""
    registry.inc("executor.compile_s", 12.0, kind="vertex")
    assert read("executor_compile_s", traced_run()) is None


@pytest.mark.parametrize("name", READERS)
def test_reader_reads_nothing_from_an_empty_registry(registry, name):
    assert read(name, traced_run()) is None
    assert read(name, R.Run()) is None


def test_readers_after_a_tiny_harness_run(tiny_root, no_chip_check,
                                          registry):
    cell = R.Cell.find(tiny_root, "tiny.4cf-batch", trace=False)
    out = R.run_cell(cell, 2**31 + 9, 0.3, False, tiny_root)
    assert out["correct"]
    run = traced_run(busy_s=1.0, jobs=1)
    assert read("plan_estimate_s", run) > 0
    assert read("executor_compile_s", run) > 0
    assert 0 < read("cap_utilization", run) <= 1
    assert read("device_ns_per_candidate", run) > 0
    assert read("job_host_s", run) > 0
