"""The entry a job drives: the program's ``Miner``, on one chip.

One job is ``Miner(graph, app).run(**run)``, the path that ``python -m
repro.launch.mine --plan estimate`` takes, with the app, its ``minsup``
and the ``run`` keywords from the traffic file.  The graph is the
program's own ingestion (``from_edge_list``) of the generator's edge
list, with its labels where it has them.  A ``Miner`` drives one device,
so a cell on more chips is refused here, never run on one and reported
as more.
"""
from __future__ import annotations

import time

from repro.core import Miner
from repro.graph.csr import from_edge_list
from repro.launch.mine import make_app


class Entry:
    def __init__(self, graph: dict, traffic: dict, devices: list,
                 spans: dict[str, float]):
        if len(devices) != 1:
            raise SystemExit(f"the miner entry drives one chip; the cell "
                             f"asks for {len(devices)}")
        t0 = time.perf_counter()
        csr = from_edge_list(graph["edges"], n_vertices=graph["n"],
                             labels=graph.get("labels"))
        spans["ingest"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        self.miner = Miner(csr, make_app(traffic["app"],
                                         int(traffic.get("minsup", 0))))
        spans["miner_build"] = time.perf_counter() - t0
        self.run_kwargs = traffic["run"]
        self.edges = csr.n_edges // 2

    def job(self) -> int:
        """One job: its count, read back to the host."""
        return int(self.miner.run(**self.run_kwargs).count)

    def counters(self) -> dict[str, int]:
        """The executor's counters, summed over the Miner's plans."""
        out = {"compiles": 0, "executions": 0, "replans": 0}
        for r in self.miner.plan_reports():
            for k in out:
                out[k] += int(r[k])
        return out
