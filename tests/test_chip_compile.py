"""Compile rehearsals for a TPU v5e chip that is described, not attached.

The TPU compiler is installed on CPU-only hosts too: these tests compile
the mining programs at Graph500 scale-20 widths (rmat(20, edge_factor=16):
2^20 vertices, 15,701,711 undirected edges, DAG max out-degree 674) from
``jax.ShapeDtypeStruct`` shapes, without running anything.  Each asserts
that the program fits one chip's 16 GB and that the graph reaches it as
an argument — no graph-sized constant in the lowered text.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process may hold the TPU library, and the
test workers all import every test file.  Keep every compile rehearsal
in this one file so that they share that process.
"""
import math
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.core import (GraphCtx, MiningPlan, get_backend, make_fsm_app,
                        make_tc_app, plan_program, sharded_program)
from repro.core.api import is_auto_canonical_kernel
from repro.kernels.runtime import TPU_REFUSALS

HBM_BYTES = 16e9                       # one TPU v5e chip
N_VERTICES = 1 << 20
N_UEDGES = 15_701_711                  # undirected edges of rmat(20, 16)
DAG_MAX_DEGREE = 674

_CONST = re.compile(r'constant dense<(\[|"0x)[^>]*> : tensor<([0-9x]*)x?[a-z]')


def largest_constant(text: str) -> int:
    """Elements of the largest non-splat constant in lowered StableHLO."""
    sizes = [math.prod(int(d) for d in dims.split("x") if d) or 1
             for _, dims in _CONST.findall(text)]
    return max(sizes, default=0)


@pytest.fixture(scope="module")
def topo():
    import os
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:                 # noqa: BLE001
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shape(shape, sharding, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _ctx(sharding, *, n_edges, max_degree, labeled=False):
    """Shapes of a scale-20 graph context (DAG for TC, undirected + edge
    uids + labels for FSM), every array placed with ``sharding``."""
    steps = max(1, math.ceil(math.log2(max_degree + 1)))
    kw = {}
    if labeled:
        kw = dict(labels=_shape((N_VERTICES,), sharding),
                  edge_uid=_shape((n_edges,), sharding),
                  usrc=_shape((N_UEDGES,), sharding),
                  udst=_shape((N_UEDGES,), sharding), n_uedges=N_UEDGES,
                  n_labels=3)
    else:
        kw = dict(labels=None)
    return GraphCtx(row_ptr=_shape((N_VERTICES + 1,), sharding),
                    col_idx=_shape((n_edges,), sharding),
                    n_vertices=N_VERTICES, n_edges=n_edges,
                    max_degree=max_degree, n_steps=steps, **kw)


def _check(compiled, lowered, per_device_budget=HBM_BYTES):
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < per_device_budget, used
    # the smallest graph array (row_ptr, 2^20 + 1 entries) must not be
    # folded into the program as a constant
    assert largest_constant(lowered.as_text()) < N_VERTICES


def test_largest_constant_finds_dense_literals():
    text = ('%c = stablehlo.constant dense<[1, 2, 3]> : tensor<3xi32>\n'
            '%d = stablehlo.constant dense<"0x0000000001000000"> : '
            'tensor<2x1xi32>\n%e = stablehlo.constant dense<0> : '
            'tensor<9999999xi32>')
    assert largest_constant(text) == 3


def test_tc_vertex_executor_compiles_for_one_chip(one_chip):
    """The TC plan executor for one streamed block of the rmat-20 DAG."""
    cap0 = 1 << 17
    plan = MiningPlan(kind="vertex", caps=((1 << 25, 1 << 22),), cap0=cap0)
    program = plan_program(plan, make_tc_app(), get_backend("reference"))
    ctx = _ctx(one_chip, n_edges=N_UEDGES, max_degree=DAG_MAX_DEGREE)
    lowered = program.lower(ctx, _shape((cap0,), one_chip),
                            _shape((cap0,), one_chip), _shape((), one_chip))
    _check(lowered.compile(), lowered)


def test_fsm_edge_executor_compiles_for_one_chip(one_chip):
    """The 3-FSM edge executor over the labelled undirected rmat-20 CSR."""
    cap0 = 1 << 20
    plan = MiningPlan(kind="edge", caps=((1 << 23, 1 << 21),),
                      filter_caps=(1 << 20, 1 << 21), cap0=cap0)
    app = make_fsm_app(3, min_support=100, max_patterns=256)
    program = plan_program(plan, app, get_backend("reference"))
    ctx = _ctx(one_chip, n_edges=2 * N_UEDGES, max_degree=64701,
               labeled=True)
    blk = _shape((cap0,), one_chip)
    lowered = program.lower(ctx, blk, blk, blk, _shape((), one_chip))
    _check(lowered.compile(), lowered)


def test_sharded_tc_compiles_for_four_chips(topo):
    """``mine_sharded``'s TC program on a 4-chip mesh: graph replicated,
    one worklist block per chip."""
    from jax.sharding import Mesh
    import numpy as np

    mesh = Mesh(np.asarray(topo.devices[:4]), ("data",))
    program = sharded_program(make_tc_app(), mesh, ((1 << 25, 1 << 22),))
    rep = NamedSharding(mesh, PartitionSpec())
    row = NamedSharding(mesh, PartitionSpec("data"))
    cap0 = 1 << 17
    ctx = _ctx(rep, n_edges=N_UEDGES, max_degree=DAG_MAX_DEGREE)
    lowered = program.lower(ctx, _shape((4, cap0), row),
                            _shape((4, cap0), row), _shape((4,), row))
    compiled = lowered.compile()
    _check(compiled, lowered)
    assert "all-reduce" in compiled.as_text()


def _pruned_extend(one_chip, m):
    from repro.kernels.extend_fused.extend import fused_extend_pruned_pallas
    cap, k = 1 << 16, 2

    def run(col, offsets, starts, emb, vlo, vhi, state):
        return fused_extend_pruned_pallas(
            col, offsets, starts, emb, vlo, vhi, state,
            jnp.zeros((1,), jnp.uint32), jnp.zeros((1,), jnp.int32), None,
            k=k, cand_cap=cap * 8, out_cap=cap * 4, n_steps=10,
            n_vertices=N_VERTICES, n_words=1, n_rows=1,
            pred=is_auto_canonical_kernel, interpret=False)

    par = _shape((cap * k,), one_chip)
    return jax.jit(run).lower(_shape((m,), one_chip), par, par, par, par,
                              par, _shape((cap,), one_chip))


@pytest.mark.xfail(strict=True, raises=NotImplementedError,
                   reason="ROADMAP R2: Mosaic lowers only 2-D gathers; the "
                          "kernel gathers from 1-D VMEM refs")
def test_pruned_extend_kernel_compiles_for_one_chip(one_chip):
    _pruned_extend(one_chip, N_UEDGES).compile()


def _refused_kernels(one_chip):
    """A small compiled-mode call of each kernel in TPU_REFUSALS."""
    from repro.kernels.extend_fused.extend import (
        fused_extend_edge_pallas, fused_extend_pallas,
        fused_extend_pruned_mp_pallas)
    from repro.kernels.intersect.intersect import intersect_count_pallas
    m, cap, k = 4096, 256, 2
    v = _shape((cap * k,), one_chip)
    col = _shape((m,), one_chip)
    pruned_kw = dict(k=k, cand_cap=cap * 8, out_cap=cap * 4, n_steps=10,
                     n_vertices=1024, n_words=1, n_rows=1,
                     pred=is_auto_canonical_kernel, interpret=False)
    return {
        "fused_extend": (lambda *a: fused_extend_pallas(
            *a, k=k, cand_cap=cap * 8, n_steps=10, interpret=False),
            (col, v, v, v, v, v)),
        "fused_extend_pruned": (lambda *a: _pruned_extend(one_chip, m),
                                None),
        "fused_extend_pruned_mp": (lambda *a: fused_extend_pruned_mp_pallas(
            *a, jnp.zeros((1,), jnp.uint32), jnp.zeros((1,), jnp.int32),
            None, **pruned_kw), (col, v, v, v, v, v, _shape((cap,),
                                                            one_chip))),
        "fused_extend_edge": (lambda c, u, o, s, sl, lo, e, us, ud:
                              fused_extend_edge_pallas(
                                  c, u, o, s, sl, lo, e, us, ud, None,
                                  n_slots=3, cand_cap=cap * 8,
                                  n_uedges=m // 2, n_vertices=1024,
                                  interpret=False),
                              (col, col) + (_shape((cap * 3,), one_chip),)
                              * 4 + (_shape((cap * 2,), one_chip),
                                     _shape((m // 2,), one_chip),
                                     _shape((m // 2,), one_chip))),
        "intersect_count": (lambda *a: intersect_count_pallas(
            *a, max_deg=128, n_steps=10, interpret=False),
            (col,) + (_shape((cap,), one_chip),) * 4),
    }


@pytest.mark.parametrize("kernel", sorted(TPU_REFUSALS))
def test_refusal_table_matches_the_compiler(one_chip, kernel):
    """Every kernel the backends refuse up front is still refused by the
    TPU compiler, with the recorded reason; a kernel that starts to lower
    must leave the table (and its backend's refusal) in the same PR."""
    fn, args = _refused_kernels(one_chip)[kernel]
    with pytest.raises((NotImplementedError, ValueError)) as err:
        lowered = fn() if args is None else jax.jit(fn).lower(*args)
        lowered.compile()
    assert type(err.value).__name__ in TPU_REFUSALS[kernel]
