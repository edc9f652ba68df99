"""Seconds of ``Miner(graph, app)``: the graph layout (DAG orientation,
the device CSR and its packed adjacency), by the benchmark's own span;
nothing where the cell's entry builds no ``Miner``."""


def read(run):
    return run.spans.get("miner_build")
