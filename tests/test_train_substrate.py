"""Optimizer math, checkpoint/restart (bit-exact + simulated failure),
data determinism, gradient compression."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data import pipeline as data_pipe
from repro.train.checkpoint import (latest_step, restore_checkpoint,
                                    save_checkpoint)
from repro.train.optimizer import (OptConfig, apply_updates, init_opt_state,
                                   schedule_lr)

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _quad_params():
    return {"w": jnp.asarray([1.0, -2.0, 3.0]),
            "layers": {"k": jnp.ones((4, 8, 3))}}


def test_adamw_first_step_matches_reference():
    cfg = OptConfig(lr=0.1, warmup_steps=1, weight_decay=0.0,
                    grad_clip=0.0, schedule="constant")
    p = {"w": jnp.asarray([1.0, 2.0])}
    g = {"w": jnp.asarray([0.5, -0.5])}
    s = init_opt_state(p, cfg)
    p2, s2 = apply_updates(p, g, s, cfg)
    # bias-corrected adam first step: update = g / (|g| + eps) = sign(g)
    np.testing.assert_allclose(np.asarray(p2["w"]),
                               [1.0 - 0.1, 2.0 + 0.1], atol=1e-4)
    assert int(s2["step"]) == 1


def test_optimizer_converges_quadratic():
    cfg = OptConfig(lr=0.05, warmup_steps=1, weight_decay=0.0,
                    schedule="constant", total_steps=200)
    target = jnp.asarray([3.0, -1.0, 0.5])
    p = {"w": jnp.zeros(3)}
    s = init_opt_state(p, cfg)
    loss = lambda pp: jnp.sum((pp["w"] - target) ** 2)  # noqa: E731
    for _ in range(200):
        g = jax.grad(loss)(p)
        p, s = apply_updates(p, g, s, cfg)
    assert float(loss(p)) < 1e-2


@pytest.mark.parametrize("factored,beta1", [(True, 0.9), (True, 0.0),
                                            (False, 0.9)])
def test_factored_variants_step(factored, beta1):
    cfg = OptConfig(factored=factored, beta1=beta1, m_dtype="bfloat16",
                    scan_update=True)
    p = _quad_params()
    g = jax.tree.map(lambda x: jnp.ones_like(x) * 0.1, p)
    s = init_opt_state(p, cfg)
    p2, s2 = apply_updates(p, g, s, cfg)
    for a, b in zip(jax.tree.leaves(p), jax.tree.leaves(p2)):
        assert a.shape == b.shape
        assert not np.array_equal(np.asarray(a), np.asarray(b))
    if factored:
        assert "vr" in s2["v"]["layers"]["k"]
        # factored state is strictly smaller than the parameter
        assert s2["v"]["layers"]["k"]["vr"].size < p["layers"]["k"].size


def test_schedule_warmup_cosine():
    cfg = OptConfig(lr=1.0, warmup_steps=10, total_steps=100)
    assert float(schedule_lr(cfg, jnp.int32(0))) < 0.2
    assert float(schedule_lr(cfg, jnp.int32(10))) > 0.9
    assert float(schedule_lr(cfg, jnp.int32(99))) < 0.1


def test_checkpoint_roundtrip(tmp_path):
    tree = {"a": jnp.arange(6).reshape(2, 3).astype(jnp.bfloat16),
            "b": [jnp.ones(4), {"c": jnp.zeros((), jnp.int32)}]}
    save_checkpoint(str(tmp_path), 7, tree, extra={"foo": 1})
    assert latest_step(str(tmp_path)) == 7
    restored, step, extra = restore_checkpoint(str(tmp_path), tree)
    assert step == 7 and extra == {"foo": 1}
    for x, y in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(x, np.float32),
                                      np.asarray(y, np.float32))
        assert x.dtype == y.dtype


def test_train_restart_bit_exact(tmp_path):
    """Crash at step 6, resume, and match an uninterrupted run exactly —
    the fault-tolerance contract."""
    from repro.launch.train import main as train_main

    d1 = str(tmp_path / "ck_crash")
    try:
        train_main(["--arch", "qwen3-0.6b", "--smoke", "--steps", "10",
                    "--batch", "4", "--seq", "16", "--ckpt-dir", d1,
                    "--ckpt-every", "3", "--fail-at", "6"])
        raise AssertionError("expected simulated failure")
    except SystemExit as e:
        assert e.code == 42
    resumed = train_main(["--arch", "qwen3-0.6b", "--smoke", "--steps",
                          "10", "--batch", "4", "--seq", "16",
                          "--ckpt-dir", d1, "--ckpt-every", "3"])
    clean = train_main(["--arch", "qwen3-0.6b", "--smoke", "--steps", "10",
                        "--batch", "4", "--seq", "16"])
    # resumed run covers steps 6..9; compare the overlap with clean run
    np.testing.assert_allclose(resumed[-2:], clean[-2:], rtol=1e-5)


def test_data_stateless_by_step():
    b1 = data_pipe.lm_batch(0, step=5, batch=4, seq_len=8, vocab=64)
    b2 = data_pipe.lm_batch(0, step=5, batch=4, seq_len=8, vocab=64)
    b3 = data_pipe.lm_batch(0, step=6, batch=4, seq_len=8, vocab=64)
    np.testing.assert_array_equal(np.asarray(b1["tokens"]),
                                  np.asarray(b2["tokens"]))
    assert not np.array_equal(np.asarray(b1["tokens"]),
                              np.asarray(b3["tokens"]))
    r1 = data_pipe.recsys_batch(0, 3, 8, 10, 100, 10)
    r2 = data_pipe.recsys_batch(0, 3, 8, 10, 100, 10)
    np.testing.assert_array_equal(np.asarray(r1["hist_items"]),
                                  np.asarray(r2["hist_items"]))


def test_grad_compression_psum():
    """int8 compressed psum approximates the exact psum (subprocess with
    4 host devices)."""
    code = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.train.compression import psum_grads
from repro.launch.mesh import make_mesh
mesh = make_mesh((4,), ("data",))
x = jnp.linspace(-1, 1, 4 * 32).reshape(4, 32)
def f(xs, comp):
    return psum_grads(xs[0], "data", comp)
for comp in (None, "int8"):
    g = jax.shard_map(lambda xs: f(xs, comp), mesh=mesh,
                      in_specs=(P("data"),), out_specs=P(),
                      check_vma=False)(x)
    ref = np.asarray(x).sum(0)
    err = np.abs(np.asarray(g) - ref).max()
    assert err < (1e-6 if comp is None else 0.05), (comp, err)
print("OK")
"""
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", code],
                         capture_output=True, text=True, env=env,
                         timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "OK" in out.stdout
