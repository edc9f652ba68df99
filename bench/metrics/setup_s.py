"""Program set-up: start-up and device init, the program's ingestion of
the edge list, ``Miner(...)`` and the first, cold job (planning, compile
or compile-cache load, first execution).  The benchmark's own graph
generation and reference count are left out."""


def read(run):
    return run.setup_s
