"""The control: a count with the exactness guarantee broken, put in the
program's place and judged by the benchmark's own comparison.

    python3 bench/control.py --workload g500-s16.tc --seeds 11 12 13

For each seed, one run of the cell (``bench/run.py``'s ``run_cell``,
with a short window) in which ``Miner.run`` returns the app reference's
``approximate`` count instead of mining: every DAG edge's share kept
with probability ``--keep`` and the total rescaled, the approximate
answer a later change could be tempted to give.  It prints each run's
``count_gap`` beside its limit; the control has to come out not
correct.  The benchmark's own runs never run it.
"""
from __future__ import annotations

import argparse
import json
import sys
import types
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]


def approximate_run(reference, keep: float, seed: int):
    """A stand-in for ``Miner.run``: the approximate count of the graph
    the Miner was given."""
    def run(self, **_):
        g = self.graph_in
        rp = np.asarray(g.row_ptr, np.int64)
        src = np.repeat(np.arange(g.n_vertices, dtype=np.int64), np.diff(rp))
        edges = np.stack([src, np.asarray(g.col_idx, np.int64)], axis=1)
        labels = None if g.labels is None else np.asarray(g.labels)
        return types.SimpleNamespace(count=reference.approximate(
            edges, g.n_vertices, keep, seed, labels=labels))
    return run


def run_control(workload: str, seed: int, keep: float, seconds: float,
                root: Path = ROOT) -> dict:
    from bench import run as R
    cell = R.Cell.find(root, workload, trace=False)
    sys.path.insert(0, str(root / "src"))
    from repro.core import Miner
    reference = R.load_module(root / "bench" / "references"
                              / f"{cell.traffic['reference']}.py")
    real = Miner.run
    Miner.run = approximate_run(reference, keep, seed)
    try:
        return R.run_cell(cell, seed, seconds, False, root)
    finally:
        Miner.run = real


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--keep", type=float, default=0.99)
    ap.add_argument("--seconds", type=float, default=1.0)
    args = ap.parse_args(argv)
    for seed in args.seeds:
        out = run_control(args.workload, seed, args.keep, args.seconds)
        print(json.dumps({"control": args.workload, "seed": seed,
                          "keep": args.keep, "correct": out["correct"],
                          "checks": out["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.path[0] = str(ROOT)
    sys.exit(main())
