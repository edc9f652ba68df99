"""Seconds per exact job: the measured span, from the first job's start
to the last job's end, over the number of jobs (a mean over all of the
window's work, not a median of jobs)."""


def read(run):
    return (run.jobs[-1][1] - run.jobs[0][0]) / len(run.jobs)
