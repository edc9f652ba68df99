"""Seconds of the executor's compile (trace, lower, XLA compile or
compile-cache load) without its first execution, by the program's own
``executor.compile_s`` counter.  Nothing where the executor does not
time its wait for the device (``executor.wait_s``): there the counter
holds the first execution too."""
from repro.obs import metrics


def read(run):
    found = metrics.find("executor.compile_s")
    if not found or not metrics.find("executor.wait_s"):
        return None
    return sum(c.value for c in found.values())
