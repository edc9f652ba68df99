"""Device busy nanoseconds per candidate: the traced window's busy time
per job over the candidates one replay draws (the sum over levels of the
extension's true candidate count), by the program's
``executor.replay_candidates`` histogram, which a replay feeds when the
program's tracer is on (the harness has it on for the first job).
Nothing where the program keeps no such histogram."""
from repro.obs import metrics


def read(run):
    tr = run.trace
    found = metrics.find("executor.replay_candidates")
    if tr is None or tr.jobs == 0 or not found:
        return None
    (hist,) = found.values()
    if hist.count == 0 or hist.total <= 0:
        return None
    return 1e9 * (tr.busy_s / tr.jobs) / (hist.total / hist.count)
