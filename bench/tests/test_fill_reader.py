"""The reader of the vertex extend's fill counter: on a hand-made
registry, on a registry without the counter (it reads nothing and
raises nothing, as from a program that keeps no such counter), and
after a harness run of a tiny cell on the CPU."""
from __future__ import annotations

import pytest

from bench import run as R
from bench.tests.conftest import REPO
from bench.xplane import Reduction
from repro.obs import metrics, trace


def read(run: R.Run):
    path = REPO / "bench" / "metrics" / "fill_entries_per_candidate.py"
    return R.load_module(path).read(run)


@pytest.fixture
def registry():
    trace.disable()
    metrics.reset()
    yield metrics
    metrics.reset()


def traced_run() -> R.Run:
    return R.Run(jobs=[(0.0, 1.0)],
                 trace=Reduction(busy_s=1.0, window_s=1.1, jobs=1))


def test_fill_reader_on_a_hand_made_registry(registry):
    registry.inc("mine.candidates", 1000, level=2)
    registry.inc("mine.candidates", 3000, level=3)
    registry.inc("mine.fill_entries", 100, level=2)
    registry.inc("mine.fill_entries", 150, level=3)
    assert read(traced_run()) == 0.0625


def test_fill_reader_needs_the_fill_counter(registry):
    """A program that counts candidates but keeps no fill counter, as
    one from before the counter does, gives nothing."""
    registry.inc("mine.candidates", 1000, level=2)
    assert read(traced_run()) is None


def test_fill_reader_reads_nothing_from_an_empty_registry(registry):
    assert read(traced_run()) is None
    assert read(R.Run()) is None


def test_fill_reader_after_a_tiny_harness_run(tiny_root, no_chip_check,
                                              registry):
    cell = R.Cell.find(tiny_root, "tiny.tc-batch", trace=False)
    out = R.run_cell(cell, 2**31 + 11, 0.3, False, tiny_root)
    assert out["correct"]
    assert 0 < read(traced_run()) <= 1
