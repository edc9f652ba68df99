"""Entries the vertex extend's forward fill scattered per candidate it
drew, summed over levels: the program's ``mine.fill_entries{level}``
over its ``mine.candidates{level}`` counters, which a replay records
when the program's tracer is on (the harness has it on for the first
job).  Nothing where the program keeps no fill counter."""
from repro.obs import metrics


def read(run):
    fill = metrics.find("mine.fill_entries")
    cand = sum(c.value for c in metrics.find("mine.candidates").values())
    if not fill or cand <= 0:
        return None
    return sum(c.value for c in fill.values()) / cand
