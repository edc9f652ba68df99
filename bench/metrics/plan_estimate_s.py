"""Seconds of the sampled planner's estimate (``estimate_plan``), by the
program's own ``plan.estimate_s`` counter: the first job's, the only one
that plans.  Nothing where the program keeps no such counter."""
from repro.obs import metrics


def read(run):
    found = metrics.find("plan.estimate_s")
    return sum(c.value for c in found.values()) if found else None
