"""Host seconds of the window's last job: ``Miner.run``'s wall time
less the executor's wait for the device (worklist, dispatch, fetch), by
the program's ``miner.host_s`` gauge.  Nothing where the program keeps
no such gauge."""
from repro.obs import metrics


def read(run):
    return metrics.value("miner.host_s")
