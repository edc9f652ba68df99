"""Executor calls per job over the window: the change in the Miner's
``plan_reports()`` ``executions``, over the jobs."""


def read(run):
    return (run.after["executions"] - run.before["executions"]) \
        / len(run.jobs)
