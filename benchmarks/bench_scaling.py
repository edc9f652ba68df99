"""Paper Fig. 9 analogue: strong scaling of sharded mining.

One process sweeps meshes over the first 1, 2, 4, ... of its own
devices (``jax.devices()[:n]``) — a chip belongs to one process, so no
child processes are started.  On a CPU host the devices are virtual
(``XLA_FLAGS=--xla_force_host_platform_device_count=N``) and the numbers
show correct work partitioning, not speedup.  A count that differs from
the one-device run raises, so a failing sweep exits non-zero.
"""
from __future__ import annotations

import time

import jax
import numpy as np
from jax.sharding import Mesh

from benchmarks.common import emit
from repro.core import make_tc_app, mine_sharded
from repro.graph import generators as G


def run(small: bool = True) -> list[str]:
    g = G.erdos_renyi(200, 0.05, seed=3)
    caps = ((8192, 4096),)
    n_avail = len(jax.devices())
    counts = [n for n in ((1, 2, 4) if small else (1, 2, 4, 8))
              if n <= n_avail]
    out, ref = [], None
    for n in counts:
        mesh = Mesh(np.asarray(jax.devices()[:n]), ("data",))
        mine_sharded(g, make_tc_app(), mesh, caps)          # compile
        t0 = time.perf_counter()
        cnt, _, ovf = mine_sharded(g, make_tc_app(), mesh, caps)
        dt = time.perf_counter() - t0
        if ovf:
            raise RuntimeError(f"fig9/tc-scaling/{n}dev: capacity overflow")
        ref = cnt if ref is None else ref
        if cnt != ref:
            raise RuntimeError(f"fig9/tc-scaling/{n}dev: count {cnt} != "
                               f"one-device count {ref}")
        out.append(emit(f"fig9/tc-scaling/{n}dev", dt, f"count={cnt}"))
    return out


if __name__ == "__main__":
    run(small=False)
