"""Least over levels of survivors over the planned out capacity on the
replay path, by the program's ``mine.cap_utilization{level}`` gauges,
which a replay records when the program's tracer is on (the harness has
it on for the first job).  Nothing where the replay records none."""
from repro.obs import metrics


def read(run):
    found = metrics.find("mine.cap_utilization")
    return min(g.value for g in found.values()) if found else None
