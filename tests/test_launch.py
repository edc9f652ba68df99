"""Launch layer: mesh construction, collective-bytes parser, small-mesh
lower+compile of representative cells (the CI-scale version of the
512-device dry-run, in a subprocess with 4 host devices)."""
import os
import subprocess
import sys
import textwrap

import pytest

from repro.launch.dryrun import parse_collective_bytes, roofline_terms

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_parse_collective_bytes():
    hlo = textwrap.dedent("""
      %ar = bf16[256,1024]{1,0} all-reduce(bf16[256,1024]{1,0} %x)
      %ag.1 = f32[64,32]{1,0} all-gather(f32[4,32]{1,0} %y)
      ROOT %t = (f32[2,2]{1,0}) tuple(%z)
      %rs = f32[8,128]{1,0} reduce-scatter(f32[64,128]{1,0} %w)
      %cp-start = bf16[16]{0} collective-permute-start(bf16[16]{0} %v)
      %cp-done = bf16[16]{0} collective-permute-done(%cp-start)
    """)
    out = parse_collective_bytes(hlo)
    assert out["all-reduce"]["count"] == 1
    assert out["all-reduce"]["bytes"] == 256 * 1024 * 2
    assert out["all-gather"]["bytes"] == 64 * 32 * 4
    assert out["reduce-scatter"]["bytes"] == 8 * 128 * 4
    assert out["collective-permute"]["count"] == 1   # start only, not done
    assert out["total_bytes"] == (256 * 1024 * 2 + 64 * 32 * 4
                                  + 8 * 128 * 4 + 16 * 2)


def test_roofline_terms():
    t = roofline_terms(flops=197e12, hbm_bytes=819e9, coll_bytes=0,
                       n_chips=1)
    assert abs(t["compute_s"] - 1.0) < 1e-6
    assert abs(t["memory_s"] - 1.0) < 1e-6
    assert t["dominant"] in ("compute_s", "memory_s")
    t2 = roofline_terms(1e12, 1e9, 1e12, 1)
    assert t2["dominant"] == "collective_s"


def test_make_mesh_shapes():
    code = """
import jax
from repro.launch.mesh import make_test_mesh, dp_axes
m = make_test_mesh(2, 2)
assert m.axis_names == ("data", "model")
assert dp_axes(m) == ("data",)
print("OK")
"""
    _run_subprocess(code, devices=4)


@pytest.mark.parametrize("arch,shape", [
    ("qwen3-0.6b", "train_4k"),
    ("deepseek-moe-16b", "decode_32k"),
    ("gat-cora", "full_graph_sm"),
    ("dien", "retrieval_cand"),
])
def test_cell_compiles_on_small_mesh(arch, shape):
    """Lower+compile the SMOKE config of a cell on a real 2x2 mesh —
    validates the sharding rules end-to-end without 512 fake devices."""
    code = f"""
import jax
from repro.launch.mesh import make_test_mesh
from repro.launch.steps import build_cell
mesh = make_test_mesh(2, 2)
cell = build_cell({arch!r}, {shape!r}, mesh=mesh, smoke=True)
with mesh:
    jitted = jax.jit(cell.fn, in_shardings=cell.in_shardings,
                     out_shardings=cell.out_shardings)
    compiled = jitted.lower(*cell.args).compile()
assert compiled.cost_analysis() is not None
print("OK")
"""
    _run_subprocess(code, devices=4, timeout=900)


def _run_subprocess(code, devices=4, timeout=600):
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", code],
                         capture_output=True, text=True, env=env,
                         timeout=timeout)
    assert out.returncode == 0, out.stderr[-4000:]
    assert "OK" in out.stdout


def test_compile_cache_placed_from_outside_or_in_checkout(monkeypatch,
                                                          tmp_path):
    """JAX_COMPILATION_CACHE_DIR wins and nothing else is set; without it
    the cache goes to the fixed, git-ignored <checkout>/.jax_cache."""
    import jax

    from repro.launch import compile_cache as cc

    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv(cc.ENV_VAR, str(tmp_path))
        assert cc.configure_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == was
        monkeypatch.delenv(cc.ENV_VAR)
        path = cc.configure_compile_cache()
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert path == os.path.join(repo, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        with open(os.path.join(repo, ".gitignore")) as f:
            assert ".jax_cache/" in f.read().split()
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
