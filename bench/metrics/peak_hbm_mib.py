"""Peak device memory after the window, in MiB: of the fullest chip's
``memory_stats()``, ``peak_bytes_in_use`` (buffers) plus
``peak_bytes_reserved`` (where a v5e holds the executables'
temporaries, the frontier among them).  Nothing where the backend does
not report it: never an analytic model in its place."""


def read(run):
    return None if run.peak_bytes is None else run.peak_bytes / (1 << 20)
