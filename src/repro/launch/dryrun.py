"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production mesh, prove memory fit, and extract roofline terms.

MUST set the device-count flag before ANY other import (jax locks device
count at first init).  Do NOT import this module from tests/benches — run
as ``python -m repro.launch.dryrun``.
"""
import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))

import argparse      # noqa: E402
import json          # noqa: E402
import re            # noqa: E402
import subprocess    # noqa: E402
import sys           # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402

import jax           # noqa: E402
import numpy as np   # noqa: E402

# v5e-class chip constants (per assignment)
PEAK_FLOPS = 197e12          # bf16 FLOP/s
HBM_BW = 819e9               # bytes/s
ICI_BW = 50e9                # bytes/s/link

_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
                "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
                "f64": 8, "c64": 8, "c128": 16}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")


def parse_collective_bytes(hlo_text: str) -> dict:
    """Sum result-shape bytes of collective ops in post-SPMD HLO.

    Result bytes ~= bytes moved per device per op (ring all-gather moves
    (n-1)/n of the full result; all-reduce ~2x the shard — we report the
    raw result-byte sum and apply no fudge factors, stated in the docs).
    """
    out = {k: {"count": 0, "bytes": 0} for k in _COLLECTIVES}
    # lines like: %name = (bf16[8,128]{1,0}, ...) all-gather(...)
    #         or: %name = bf16[8,128]{1,0} all-reduce(...)
    shape_re = re.compile(r"(\w+)\[([0-9,]*)\]")
    for line in hlo_text.splitlines():
        line = line.strip()
        m = re.match(r"^(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(.+?)\s+"
                     r"(all-reduce|all-gather|reduce-scatter|all-to-all|"
                     r"collective-permute)(-start|-done)?\(", line)
        if not m:
            continue
        if m.group(3) == "-done":     # avoid double counting async pairs
            continue
        shapes_str, kind = m.group(1), m.group(2)
        total = 0
        for dt, dims in shape_re.findall(shapes_str):
            nbytes = _DTYPE_BYTES.get(dt)
            if nbytes is None:
                continue
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            total += n * nbytes
        out[kind]["count"] += 1
        out[kind]["bytes"] += total
    out["total_bytes"] = sum(v["bytes"] for k, v in out.items()
                             if isinstance(v, dict))
    return out


def roofline_terms(flops: float, hbm_bytes: float, coll_bytes: float,
                   n_chips: int) -> dict:
    # cost_analysis is per-program; with SPMD the program is per-device.
    compute_s = flops / PEAK_FLOPS
    memory_s = hbm_bytes / HBM_BW
    collective_s = coll_bytes / ICI_BW
    terms = {"compute_s": compute_s, "memory_s": memory_s,
             "collective_s": collective_s}
    dom = max(terms, key=terms.get)
    terms["dominant"] = dom
    terms["bound_s"] = terms[dom]
    return terms


def run_cell(arch: str, shape: str, multi_pod: bool, out_dir: str,
             mine: bool = False, optimized: bool = True) -> dict:
    from repro.launch.mesh import make_production_mesh
    from repro.launch.steps import build_cell

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = int(np.prod(list(mesh.shape.values())))
    t0 = time.time()
    mesh_tag = ("2x16x16" if multi_pod else "16x16") + \
        ("" if optimized else "-baseline")
    result = {"arch": arch, "shape": shape, "mesh": mesh_tag,
              "n_chips": n_chips, "optimized": optimized}
    cell = build_cell(arch, shape, mesh=mesh, smoke=False,
                      optimized=optimized)
    with mesh:
        jitted = jax.jit(cell.fn, in_shardings=cell.in_shardings,
                         out_shardings=cell.out_shardings)
        lowered = jitted.lower(*cell.args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    coll = parse_collective_bytes(hlo)
    flops = float(cost.get("flops", 0.0)) if cost else 0.0
    btes = float(cost.get("bytes accessed", 0.0)) if cost else 0.0
    result.update({
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "flops": flops, "bytes_accessed": btes,
        "collectives": coll,
        "memory_analysis": {
            k: getattr(mem, k) for k in
            ("argument_size_in_bytes", "output_size_in_bytes",
             "temp_size_in_bytes", "generated_code_size_in_bytes")
            if hasattr(mem, k)} if mem is not None else None,
        "roofline": roofline_terms(flops, btes, coll["total_bytes"],
                                   n_chips),
        "kind": cell.kind,
    })
    os.makedirs(out_dir, exist_ok=True)
    tag = f"{arch}_{shape}_{result['mesh']}".replace("/", "-")
    with open(os.path.join(out_dir, tag + ".json"), "w") as f:
        json.dump(result, f, indent=1)
    print(f"[dryrun] {arch} {shape} {result['mesh']}: "
          f"compile {t_compile:.1f}s, flops {flops:.3e}, "
          f"coll {coll['total_bytes']:.3e}B, "
          f"dominant {result['roofline']['dominant']}")
    if mem is not None and hasattr(mem, "temp_size_in_bytes"):
        per_dev = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
                   + mem.output_size_in_bytes)
        print(f"[dryrun]   memory/device ~ {per_dev/1e9:.2f} GB "
              f"(args {mem.argument_size_in_bytes/1e9:.2f} + temp "
              f"{mem.temp_size_in_bytes/1e9:.2f} + out "
              f"{mem.output_size_in_bytes/1e9:.2f})")
    return result


def run_mining(multi_pod: bool, out_dir: str) -> dict:
    """Dry-run the distributed mining step on the production mesh."""
    from jax.sharding import PartitionSpec as PSpec
    from repro.launch.mesh import make_production_mesh
    from repro.core import make_mc_app, bounded_mine_vertex
    from repro.core.api import GraphCtx
    import jax.numpy as jnp

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = int(np.prod(list(mesh.shape.values())))
    app = make_mc_app(4)
    # abstract graph: RMAT-scale web graph chunk per the paper's Table 1
    n_vertices, n_edges, max_deg = 2_000_000, 64_000_000, 4096
    ctx = GraphCtx(
        row_ptr=jax.ShapeDtypeStruct((n_vertices + 1,), jnp.int32),
        col_idx=jax.ShapeDtypeStruct((n_edges,), jnp.int32),
        labels=None, n_vertices=n_vertices, n_edges=n_edges,
        max_degree=max_deg, n_steps=12)
    edges_per_dev = 65536
    caps = ((edges_per_dev * 8, edges_per_dev * 8),
            (edges_per_dev * 32, edges_per_dev * 32))
    axes = tuple(mesh.axis_names)

    def local(c, src, dst, n_blk):
        cnt, p_map, ovf = bounded_mine_vertex(c, app, src, dst,
                                              n_blk[0], caps)
        for ax in axes:
            cnt = jax.lax.psum(cnt, ax)
            p_map = jax.lax.psum(p_map, ax)
        return cnt, p_map

    espec = PSpec(axes)
    fn = jax.shard_map(local, mesh=mesh,
                       in_specs=(PSpec(), espec, espec, espec),
                       out_specs=(PSpec(), PSpec()), check_vma=False)
    args = (ctx,
            jax.ShapeDtypeStruct((n_chips * edges_per_dev,), jnp.int32),
            jax.ShapeDtypeStruct((n_chips * edges_per_dev,), jnp.int32),
            jax.ShapeDtypeStruct((n_chips,), jnp.int32))
    t0 = time.time()
    compiled = jax.jit(fn).lower(*args).compile()
    cost = compiled.cost_analysis()
    coll = parse_collective_bytes(compiled.as_text())
    mem = compiled.memory_analysis()
    flops = float(cost.get("flops", 0.0)) if cost else 0.0
    btes = float(cost.get("bytes accessed", 0.0)) if cost else 0.0
    result = {"arch": "pangolin-4mc", "shape": "web_64M_edges",
              "mesh": "2x16x16" if multi_pod else "16x16",
              "n_chips": n_chips, "compile_s": round(time.time() - t0, 2),
              "flops": flops, "bytes_accessed": btes, "collectives": coll,
              "memory_analysis": {
                  k: getattr(mem, k) for k in
                  ("argument_size_in_bytes", "output_size_in_bytes",
                   "temp_size_in_bytes") if hasattr(mem, k)}
              if mem is not None else None,
              "roofline": roofline_terms(flops, btes, coll["total_bytes"],
                                         n_chips), "kind": "mine"}
    os.makedirs(out_dir, exist_ok=True)
    tag = f"pangolin-4mc_web_{result['mesh']}"
    with open(os.path.join(out_dir, tag + ".json"), "w") as f:
        json.dump(result, f, indent=1)
    print(f"[dryrun] mining {result['mesh']}: compile "
          f"{result['compile_s']}s dominant "
          f"{result['roofline']['dominant']}")
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--mine", action="store_true")
    ap.add_argument("--baseline", action="store_true",
                    help="paper-faithful/naive variant (no microbatching, "
                         "naive CE) for the before/after table")
    ap.add_argument("--out", default="artifacts/dryrun")
    args = ap.parse_args()
    if args.mine:
        run_mining(args.multi_pod, args.out)
        return
    run_cell(args.arch, args.shape, args.multi_pod, args.out,
             optimized=not args.baseline)


if __name__ == "__main__":
    main()
