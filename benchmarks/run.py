"""Benchmark entry point: one section per paper table/figure.

``python -m benchmarks.run [--full]`` — prints ``name,us_per_call,derived``
CSV lines.  Default mode is scaled for the 1-core CI box; --full uses the
larger graphs.
"""
from __future__ import annotations

import argparse
import sys


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--sections", default="apps,handopt,ablations,memory,"
                                          "scaling,backends,roofline")
    args = ap.parse_args()
    from repro.launch.compile_cache import configure_compile_cache
    configure_compile_cache()
    small = not args.full
    sections = args.sections.split(",")
    print("name,us_per_call,derived")
    if "apps" in sections:
        from benchmarks import bench_apps
        bench_apps.run(small=small)
    if "handopt" in sections:
        from benchmarks import bench_handopt
        bench_handopt.run(small=small)
    if "ablations" in sections:
        from benchmarks import bench_ablations
        bench_ablations.run(small=small)
    if "memory" in sections:
        from benchmarks import bench_memory
        bench_memory.run(small=small)
    if "scaling" in sections:
        from benchmarks import bench_scaling
        bench_scaling.run(small=small)
    if "backends" in sections:
        from benchmarks import bench_backends
        bench_backends.run(small=small)
    if "roofline" in sections:
        # summarize dry-run artifacts when present (no compiles here)
        import glob, json, os
        arts = sorted(glob.glob("artifacts/dryrun/*.json"))
        print(f"roofline/artifacts,0.0,count={len(arts)}")
        for p in arts[:200]:
            with open(p) as f:
                r = json.load(f)
            rl = r.get("roofline", {})
            print(f"roofline/{r['arch']}/{r['shape']}/{r['mesh']},0.0,"
                  f"dominant={rl.get('dominant', '?')};"
                  f"bound_s={rl.get('bound_s', 0):.3e}")


if __name__ == "__main__":
    main()
