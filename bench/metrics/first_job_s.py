"""Seconds of the first, cold ``run``: planning (the sampled estimate),
the executor's compile or compile-cache load, and its first execution,
by the benchmark's own span."""


def read(run):
    return run.spans["first_job"]
