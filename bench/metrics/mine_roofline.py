"""Share of the memory-bandwidth roofline that a job's device time
reaches, in %.

The least time is the job's bytes over the chip's peak HBM bandwidth
(``bench/peaks.json``): bandwidth bounds it, since the extension does no
arithmetic to speak of.  The bytes are 8 per candidate the extension must
test (one id read, one probe word), and the candidates are counted by the
app's work function from the graph alone (``bench/references/<app>.py``),
so the number counts the same work whatever implements it.  The time is
the device busy time of the traced window over its jobs.
"""

BYTES_PER_CANDIDATE = 8


def read(run):
    tr = run.trace
    if tr is None or run.work is None or tr.jobs == 0 or tr.busy_s <= 0:
        return None
    least_s = BYTES_PER_CANDIDATE * run.work / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / (tr.busy_s / tr.jobs)
