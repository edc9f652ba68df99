"""The harness end to end on the CPU, with its look for a chip skipped:
it finds a configuration, a traffic mix and a metric by name from
files, and its comparison fails the control and each fault that a cell
can have."""
from __future__ import annotations

import json
import subprocess
import sys
import types

import pytest

from bench import control
from bench import run as R
from bench.tests.conftest import REPO

SECONDS = 0.3


def run_tiny(root, workload, seed=2**31 + 5, trace=False):
    cell = R.Cell.find(root, workload, trace)
    return R.run_cell(cell, seed, SECONDS, trace, root)


@pytest.mark.parametrize("workload", ["tiny.tc-batch", "tiny.4cf-batch"])
def test_sound_run_is_correct(tiny_root, no_chip_check, workload):
    out = run_tiny(tiny_root, workload)
    assert out["correct"] and out["failed"] == 0 and out["attempted"] >= 2
    assert set(out["metrics"]) == {"job_s", "peak_hbm_mib", "setup_s"} \
        - ({"peak_hbm_mib"} if out["device"]["memory_peak_bytes"] is None
           else set())
    assert list(out)[-1] == "checks"
    assert out["checks"]["count_gap"] == {"value": 0, "limit": 0}


def test_finds_files_by_name(tiny_root, no_chip_check):
    """A new metric is a file and a BENCHMARK.json entry, nothing more."""
    spec = json.loads((tiny_root / "BENCHMARK.json").read_text())
    spec["end_to_end"].append({"name": "jobs.window", "unit": "count",
                               "better": "higher", "bound": 0.01,
                               "source": "host_clock",
                               "workloads": ["tiny.tc-batch"]})
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(spec))
    (tiny_root / "bench/metrics/jobs.window.py").write_text(
        "def read(run):\n    return len(run.jobs)\n")
    try:
        cell = R.Cell.find(tiny_root, "tiny.tc-batch", trace=False)
        assert cell.config["params"]["scale"] == 9
        assert cell.traffic["app"] == "tc"
        out = R.run_cell(cell, 7, SECONDS, False, tiny_root)
        assert out["metrics"]["jobs.window"]["value"] >= 1
        other = R.Cell.find(tiny_root, "tiny.4cf-batch", trace=False)
        assert "jobs.window" not in {m["name"] for m in other.metrics}
    finally:
        spec["end_to_end"].pop()
        (tiny_root / "BENCHMARK.json").write_text(json.dumps(spec))
        (tiny_root / "bench/metrics/jobs.window.py").unlink()


def test_labels_reach_the_program_and_the_reference(tiny_root,
                                                     no_chip_check,
                                                     monkeypatch):
    """A labelled configuration and a reference that reads labels are
    files and BENCHMARK.json entries, nothing more: the generator's
    labels reach the program's ingestion and the reference."""
    import repro.graph.csr as csr
    seen = []
    real = csr.from_edge_list

    def spy(edges, n_vertices=None, labels=None, **kw):
        seen.append(labels)
        return real(edges, n_vertices=n_vertices, labels=labels, **kw)

    monkeypatch.setattr(csr, "from_edge_list", spy)
    cfg = json.loads((tiny_root / "bench/configs/tiny.json").read_text())
    cfg["name"] = "tiny-labelled"
    cfg["params"]["labels"] = {"count": 8, "zipf": 1.0}
    traffic = json.loads((tiny_root / "bench/traffic/tc-batch.json")
                         .read_text())
    traffic["reference"] = "tc-labelled"
    added = {"bench/configs/tiny-labelled.json": json.dumps(cfg),
             "bench/traffic/tc-labelled.json": json.dumps(traffic),
             "bench/references/tc-labelled.py":
                 "from bench.references import tc\n\n\n"
                 "def count(edges, n, labels=None):\n"
                 "    assert labels is not None and labels.shape == (n,)\n"
                 "    return tc.count(edges, n)\n"}
    spec_path = tiny_root / "BENCHMARK.json"
    spec_text = spec_path.read_text()
    spec = json.loads(spec_text)
    spec["configs"].append({"name": "tiny-labelled", "source": "test",
                            "file": "bench/configs/tiny-labelled.json",
                            "reduced": ["scale"], "why": "test"})
    spec["workloads"].append({"name": "tiny-labelled.tc", "chips": 1,
                              "config": "tiny-labelled",
                              "traffic": "tc-labelled", "why": "test"})
    try:
        for path, text in added.items():
            (tiny_root / path).write_text(text)
        spec_path.write_text(json.dumps(spec))
        out = run_tiny(tiny_root, "tiny-labelled.tc")
        assert out["correct"]
        assert seen and all(lab is not None and lab.max() < 8
                            for lab in seen)
    finally:
        spec_path.write_text(spec_text)
        for path in added:
            (tiny_root / path).unlink()


def test_more_chips_than_the_entry_drives_is_refused(tiny_root,
                                                     monkeypatch):
    """A cell on four chips is refused, never run on one chip and
    reported as four."""
    import jax
    spec_path = tiny_root / "BENCHMARK.json"
    spec_text = spec_path.read_text()
    spec = json.loads(spec_text)
    spec["workloads"].append({"name": "tiny.tc-4", "config": "tiny",
                              "traffic": "tc-batch", "chips": 4,
                              "why": "test"})
    monkeypatch.setattr(R, "require_accelerator",
                        lambda chips: [jax.devices()[0]] * chips)
    try:
        spec_path.write_text(json.dumps(spec))
        with pytest.raises(SystemExit, match="one chip"):
            run_tiny(tiny_root, "tiny.tc-4")
    finally:
        spec_path.write_text(spec_text)


def _answer_altered(real):
    def run(self, **kw):
        return types.SimpleNamespace(count=real(self, **kw).count + 1)
    return run


def _half_worklist(real):
    def init_edges(self):
        src, dst = real(self)
        half = src.shape[0] // 2
        return src[:half], dst[:half]
    return init_edges


@pytest.mark.parametrize("fault,attr,wrap", [
    ("answer altered where it is produced", "run", _answer_altered),
    ("half of the worklist left out", "init_edges", _half_worklist),
])
@pytest.mark.parametrize("workload", ["tiny.tc-batch", "tiny.4cf-batch"])
def test_fault_is_not_correct(tiny_root, no_chip_check, monkeypatch,
                              workload, fault, attr, wrap):
    from repro.core import Miner
    monkeypatch.setattr(Miner, attr, wrap(getattr(Miner, attr)))
    out = run_tiny(tiny_root, workload)
    assert not out["correct"], fault
    assert out["checks"]["count_gap"]["value"] > 0


@pytest.mark.parametrize("workload", ["tiny.tc-batch", "tiny.4cf-batch"])
def test_control_is_not_correct(tiny_root, no_chip_check, workload):
    out = control.run_control(workload, 11, 0.99, SECONDS, tiny_root)
    assert not out["correct"]
    assert out["checks"]["count_gap"]["value"] > 0


def test_no_accelerator_exits_without_a_result():
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "g500-s16.tc",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=REPO, capture_output=True, text=True,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"}, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no accelerator" in proc.stderr
