"""A benchmark root at a size the CPU holds: a copy of ``bench/`` beside
the program's ``src``, with a scale-9 configuration and a cell per
traffic mix, and the CPU's entry in the peaks table (so that the
harness runs here with its look for a chip skipped)."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

import jax
import pytest

REPO = Path(__file__).resolve().parents[2]
TINY_SCALE = 9


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory) -> Path:
    root = tmp_path_factory.mktemp("bench_root")
    shutil.copytree(REPO / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    (root / "src").symlink_to(REPO / "src")
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    cfg = json.loads((REPO / "bench/configs/g500-s13.json").read_text())
    cfg["name"] = "tiny"
    cfg["params"]["scale"] = TINY_SCALE
    (root / "bench/configs/tiny.json").write_text(json.dumps(cfg))
    spec["configs"].append({"name": "tiny", "source": "test",
                            "file": "bench/configs/tiny.json",
                            "reduced": ["scale"], "why": "test"})
    for traffic in ("tc-batch", "4cf-batch"):
        spec["workloads"].append({"name": f"tiny.{traffic}",
                                  "config": "tiny", "traffic": traffic,
                                  "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    peaks = json.loads((root / "bench/peaks.json").read_text())
    peaks[jax.devices()[0].device_kind] = peaks["TPU v5 lite"]
    (root / "bench/peaks.json").write_text(json.dumps(peaks))
    return root


@pytest.fixture
def no_chip_check(monkeypatch):
    """Skip the harness's look for a chip: run on the CPU's devices."""
    from bench import run as R
    monkeypatch.setattr(R, "require_accelerator",
                        lambda chips: jax.devices()[:chips])
