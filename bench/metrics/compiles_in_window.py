"""Executor compiles plus replans inside the window, from the Miner's
``plan_reports()`` (expected 0: set-up warms every program)."""


def read(run):
    return sum(run.after[k] - run.before[k] for k in ("compiles", "replans"))
