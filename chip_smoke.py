"""Smoke run of the mining path on a TPU: one chip by default.

    python chip_smoke.py             # one chip: batch mining of Graph500
                                     # Kronecker graphs, host-reference
                                     # parity, served queries
    python chip_smoke.py --chips 4   # four chips: mine_sharded against a
                                     # single-device Miner, nothing else

Drives the entry points users call (``Miner(...).run`` as
``python -m repro.launch.mine`` does, and ``serve_mine`` as
``python -m repro.launch.serve --mine`` does) and checks every count
against an independent reference.  Times printed here are smoke timings
of one run, not benchmark numbers.  The last line of standard output is
``{"ok": true, "device": {...}}``; any failed check raises, and the
script then exits non-zero without that line.  It refuses to start
without a TPU (e.g. under ``JAX_PLATFORMS=cpu``), with Pallas interpret
mode forced on (``REPRO_PALLAS_INTERPRET=1``), or outside a checkout.
"""
from __future__ import annotations

import argparse
import json
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

# Graph500 Kronecker graphs rmat(scale, edge_factor=16, seed=0).  Scale 20
# is the target.  On one v5e chip XLA gathers about 1.2e8 int32 a second
# whatever the table (PERF.md), and a TC candidate costs about 11 of them,
# so a TC run on scale 18 (6.5e8 candidates) takes about 90 s and one on
# scale 19 about 2.6x that; with the warm repeat, the fused count and the
# other phases, scale 19 would not fit the smoke's 1,200 s.
TC_SCALE = 18
CF_SCALE = 13              # 3.0e7 candidates at 4-CF's last level
EDGE_FACTOR = 16
BLOCK_BYTES = 512 << 20    # streaming-block budget (--block-bytes)
FSM_MINSUP = 100
# served graphs (launch/mine.py specs, edge factor 8): TC on rmat:16; the
# 4-vertex patterns enumerate ~3.5x more candidates per scale step (7.5e7
# for 4-cycle on rmat:12), so they are served on rmat:11
SERVE = (("rmat:16", "tc", ()),
         ("rmat:11", "diamond,4-cycle,3-mc", ()),
         ("rmat:10", "3-fsm", ("--labels", "3", "--minsup", str(FSM_MINSUP))))
REPEATS = 2
# four chips: TC on rmat(16, 16); 4-MC on rmat(9, 16) (5.8e7 candidates;
# its 3-stars grow as degree cubed); 3-FSM on a labelled rmat(10, 16)
SHARDED_SCALE, MC_SCALE, FSM_SCALE = 16, 9, 10


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def say(*parts) -> None:
    print("[smoke]", *parts, flush=True)


# ---------------------------------------------------------------------------
# The plain reference: host-side numpy/scipy, independent of repro.core


def host_triangle_count(row_ptr, col_idx) -> int:
    """Triangles of a symmetric CSR graph: orient every edge from lower
    to higher (degree, id) rank, then sum (U @ U) * U over the oriented
    adjacency U."""
    import numpy as np
    import scipy.sparse as sp

    rp = np.asarray(row_ptr, np.int64)
    col = np.asarray(col_idx, np.int64)
    n = rp.size - 1
    deg = np.diff(rp)
    src = np.repeat(np.arange(n, dtype=np.int64), deg)
    rank = deg * n + np.arange(n, dtype=np.int64)
    up = rank[src] < rank[col]
    u = sp.csr_matrix((np.ones(int(up.sum()), np.int64),
                       (src[up], col[up])), shape=(n, n))
    return int((u @ u).multiply(u).sum())


# ---------------------------------------------------------------------------
# Phases


def device_phase(want_chips: int) -> dict:
    import jax
    from repro.kernels.runtime import env_interpret, resolve_interpret

    check(env_interpret() is not True,
          "Pallas interpret mode is forced on (REPRO_PALLAS_INTERPRET); "
          "the chip run must compile its kernels")
    devs = jax.devices()
    d = devs[0]
    check(d.platform == "tpu", f"no TPU: JAX reports platform {d.platform!r}")
    check(not resolve_interpret(), "kernels would run in interpret mode")
    check(len(devs) >= want_chips,
          f"{want_chips} chips wanted, JAX sees {len(devs)}")
    stats = d.memory_stats() or {}
    say(f"device {d.platform} {d.device_kind} x{len(devs)}; memory_stats "
        f"bytes_limit={stats.get('bytes_limit')} "
        f"bytes_in_use={stats.get('bytes_in_use')}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": want_chips}


def _timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def _plan_line(miner) -> str:
    return "; ".join(
        f"cap0={r['cap0']} source={r['source']} replans={r['replans']} "
        f"compiles={r['compiles']} executions={r['executions']}"
        for r in miner.plan_reports())


def _graph(scale: int):
    from repro.graph import generators as G

    g, dt = _timed(lambda: G.rmat(scale, edge_factor=EDGE_FACTOR, seed=0))
    say(f"graph rmat({scale}, edge_factor={EDGE_FACTOR}): "
        f"{g.n_vertices} vertices, {g.n_edges // 2} undirected edges "
        f"(generated in {dt:.1f}s)")
    return g


def batch_phase(tc_scale: int = TC_SCALE, cf_scale: int = CF_SCALE) -> None:
    from repro.core import (Miner, make_cf_app, make_tc_app,
                            triangle_count_fused)

    g = _graph(tc_scale)
    ref, dt = _timed(lambda: host_triangle_count(g.row_ptr, g.col_idx))
    say(f"host reference TC (scipy): {ref} in {dt:.1f}s")

    with tempfile.TemporaryDirectory(dir=ROOT) as plans:
        tc = Miner(g, make_tc_app())
        for i in range(REPEATS):
            r, dt = _timed(lambda: tc.run(plan_source="estimate",
                                          block_bytes=BLOCK_BYTES,
                                          plan_cache=plans))
            say(f"TC run {i} ({'cold' if i == 0 else 'warm'}): "
                f"count={r.count}, {dt:.2f}s (smoke timing)")
            check(r.count == ref, f"TC {r.count} != host reference {ref}")
        say(f"TC plan: {_plan_line(tc)}; peak live bytes (analytic) "
            f"{tc.peak_live_bytes()}")
    fused, dt = _timed(lambda: triangle_count_fused(g))
    say(f"fused DAG-intersection TC: {fused} in {dt:.2f}s (smoke timing)")
    check(fused == ref, f"fused TC {fused} != host reference {ref}")

    g = _graph(cf_scale)
    cf = Miner(g, make_cf_app(4))
    counts = []
    for i in range(REPEATS):
        r, dt = _timed(lambda: cf.run(plan_source="estimate",
                                      block_bytes=BLOCK_BYTES))
        counts.append(r.count)
        say(f"4-CF estimate run {i} ({'cold' if i == 0 else 'warm'}): "
            f"count={r.count}, {dt:.2f}s (smoke timing)")
    say(f"4-CF plan: {_plan_line(cf)}; peak live bytes (analytic) "
        f"{cf.peak_live_bytes()}")
    block = cf.plan_reports()[0]["cap0"]
    inspected = Miner(g, make_cf_app(4))
    r, dt = _timed(lambda: inspected.run(plan_source="inspect",
                                         block_size=block))
    say(f"4-CF inspect run (block {block}): count={r.count}, {dt:.2f}s "
        f"(smoke timing); plan: {_plan_line(inspected)}")
    counts.append(r.count)
    check(len(set(counts)) == 1, f"4-CF counts disagree: {counts}")


def backend_phase() -> None:
    """The Pallas backends either match the host reference count or
    refuse before tracing, naming the compiler's reason."""
    from repro.core import Miner, make_tc_app
    from repro.graph import generators as G
    from repro.kernels.runtime import KernelNotLowerable

    g = G.rmat(10, edge_factor=EDGE_FACTOR, seed=0)
    ref = host_triangle_count(g.row_ptr, g.col_idx)
    for name in ("pallas", "pallas-mp"):
        try:
            miner = Miner(g, make_tc_app(), backend=name)
        except KernelNotLowerable as e:
            say(f"backend {name}: refused before tracing: {e}")
            continue
        got = miner.run().count
        check(got == ref, f"backend {name}: TC {got} != host reference "
                          f"{ref}")
        say(f"backend {name}: TC {got} matches the host reference")


def serve_phase() -> None:
    from repro.core import Miner, graph_stats
    from repro.launch.mine import load_graph
    from repro.launch.serve import build_parser, query_app, serve_mine

    for graph, queries, extra in SERVE:
        args = build_parser().parse_args(
            ["--mine", "--graph", graph, "--queries", queries,
             "--query-repeats", str(REPEATS), *extra])
        results = serve_mine(args)
        g = load_graph(graph, labels=args.labels)
        for res in results:
            # a new Miner and executor; the same sampled plan, so its
            # programs come from the compile cache
            fresh = Miner(g, query_app(res["query"], args.minsup,
                                       graph_stats(g))).run(
                plan_source=args.plan)
            check(res["result"].count == fresh.count
                  and res["result"].count > 0,
                  f"served {res['query']!r} count {res['result'].count} "
                  f"!= fresh Miner {fresh.count}")
            say(f"served {res['query']!r} on {graph}: count="
                f"{res['result'].count} matches a fresh Miner")


def sharded_phase() -> None:
    """mine_sharded over four chips against a single-device Miner.

    Runs mine_sharded's own steps (``sharded_inputs``, ``sharded_program``,
    ``sharded_results``) so that the placement check reads the very
    arrays whose counts are compared."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from repro.core import (Miner, make_fsm_app, make_mc_app, make_tc_app,
                            sharded_inputs, sharded_program,
                            sharded_results)
    from repro.graph import generators as G

    devs = jax.devices()[:4]
    check(len({d.id for d in devs}) == 4, f"mesh devices {devs}")
    mesh = Mesh(np.asarray(devs), ("data",))
    for name, scale, labels, app in (
            ("tc", SHARDED_SCALE, None, make_tc_app()),
            ("4-mc", MC_SCALE, None, make_mc_app(4)),
            ("3-fsm", FSM_SCALE, 3, make_fsm_app(3, FSM_MINSUP, 256))):
        graph = G.rmat(scale, edge_factor=EDGE_FACTOR, seed=0, labels=labels)
        say(f"sharded {name} on rmat({scale}, {EDGE_FACTOR})"
            f"{' labelled' if labels else ''}: {graph.n_edges // 2} edges")
        single = Miner(graph, app)
        ref, dt1 = _timed(single.run)
        plan = single.plan_reports()[0]
        caps = tuple(tuple(c) for c in plan["caps"])
        fcaps = tuple(plan["filter_caps"]) if app.kind == "edge" else None
        args = sharded_inputs(graph, app, mesh)
        # every worklist array: one row per chip, each chip its own row
        for a in args[1:]:
            rows = sorted((s.device.id, s.index[0].start)
                          for s in a.addressable_shards)
            check([d for d, _ in rows] == sorted(d.id for d in devs)
                  and len({r for _, r in rows}) == 4
                  and all(s.data.shape[0] == 1
                          for s in a.addressable_shards),
                  f"{name}: worklist not one block per chip: {rows}")
        program = sharded_program(app, mesh, caps, filter_caps=fcaps)
        out, dt = _timed(lambda: sharded_results(app, program(*args)))
        check(not out[-1], f"{name}: sharded run overflowed its caps")
        check(out[0] == ref.count,
              f"{name}: sharded count {out[0]} != single-device {ref.count}")
        if app.kind == "edge":
            check(np.array_equal(out[1], ref.codes)
                  and np.array_equal(out[2], ref.supports),
                  f"{name}: sharded codes/supports differ")
        elif ref.p_map is not None:
            check(np.array_equal(out[1], ref.p_map),
                  f"{name}: sharded pattern map differs")
        say(f"sharded {name}: count={out[0]} == single device "
            f"({dt1:.2f}s); four chips {dt:.2f}s (smoke timings); "
            f"worklist rows on devices {[d.id for d in devs]}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the multi-chip path and its "
                         "single-device comparison")
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print("chip_smoke.py: no repro checkout next to this script",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import configure_compile_cache

    device = device_phase(args.chips)
    say(f"compile cache: {configure_compile_cache()}")
    t0 = time.perf_counter()
    if args.chips == 4:
        sharded_phase()
    else:
        batch_phase()
        backend_phase()
        serve_phase()
    say(f"all phases passed in {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
