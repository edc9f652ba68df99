"""Execution engine (paper Alg. 1): extend -> reduce -> filter per level.

The engine is the *high-level* half of the Sandslash-style split: it owns
the per-level loop, blocking, checkpointing, and distribution, and
resolves every low-level set operation through the phase-backend registry
(:mod:`repro.core.phases`) — ``"reference"`` pure XLA, ``"pallas"`` fused
kernels, or any registered custom backend.

Capacity planning is factored out of the loop (plan-once / execute-many,
:mod:`repro.core.plan`): there is exactly **one** level loop
(:func:`run_level_loop`, shared by the vertex- and edge-induced pipeline
adapters), and a *capacity policy* decides how each level's static buffer
capacities are obtained:

* ``HostCapPolicy`` — the paper's inspection-execution at the host/XLA
  boundary: per level, run the inspection jit (exact candidate/survivor
  counts), bucket to powers of two, record the decisions.  This is how a
  cold :meth:`Miner.run` works — and the finished run doubles as a
  *planning pass*.
* ``PlanCapPolicy`` — replay a recorded :class:`~repro.core.plan.MiningPlan`
  with static capacities and **no host sync**.  The whole run becomes one
  jit; overflow is reported as a flag (re-plan-and-retry, owned by
  :class:`~repro.core.plan.MiningExecutor`, is the only host loop left).

:meth:`Miner.run` compiles one :class:`~repro.core.plan.MiningExecutor`
per (signature, cap0) and reuses it across all edge blocks of a run and
across repeated runs; :func:`bounded_mine_vertex` /
:func:`bounded_mine_edge` are the same loop under a ``PlanCapPolicy``,
used directly by the multi-pod dry-run and by ``shard_map`` distribution
(:func:`mine_sharded`), where level-0 edges are sharded over mesh axes
(the paper's edge blocking as the distribution unit).  FSM distribution
keeps the paper's "global support sync" exact: per-level domain bitmaps
are psum-merged and pattern tables aligned by all-gather, so MNI support
is computed on the union of all devices' embeddings.

Fault tolerance: :meth:`Miner.run` optionally checkpoints after every
level (unblocked: ``cb(level, levels, payload)``) or after every edge
block (blocked: ``cb(block_index, None, {"count", "p_map", "block"})``
with the accumulated totals) via a user callback; a killed blocked run
restarts from the last completed block by passing the saved payload back
as ``Miner.run(resume_from=...)`` (see repro.train.checkpoint).
"""
from __future__ import annotations

import dataclasses
import functools
import hashlib
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.api import GraphCtx, MiningApp, make_ctx
from repro.core.blocks import (BlockQueue, auto_block_size,
                               estimate_live_bytes, make_blocks, scale_caps,
                               stack_blocks)
from repro.core.embedding_list import (EmbeddingLevel, init_level0_edge,
                                       init_level0_vertex, materialize,
                                       materialize_edges, total_bytes)
from repro.core.phases import BackendSpec, get_backend
from repro.core.plan import (HostCapPolicy, MiningExecutor, MiningPlan,
                             PlanCache, PlanCapPolicy, bucket_pow2,
                             compatible_caps, estimate_plan, transfer_caps)
from repro.graph.csr import CSRGraph, degree_profile
from repro.graph.csr import pack_hit_rate as _pack_hit_rate
from repro.graph.csr import relabel as relabel_graph
from repro.graph.dag import orient_dag
from repro.obs import metrics as _M
from repro.obs import trace as _T

_bucket = bucket_pow2          # back-compat alias
_INT_MAX = np.iinfo(np.int32).max


@dataclasses.dataclass
class LevelStats:
    level: int
    n_candidates: int
    n_embeddings: int
    capacity: int
    bytes: int
    seconds: float
    live_bytes: int = 0       # embedding list + materialized frontier


@dataclasses.dataclass
class MineResult:
    count: int
    p_map: Optional[np.ndarray] = None          # count support per pattern
    codes: Optional[np.ndarray] = None          # canonical codes (FSM)
    supports: Optional[np.ndarray] = None       # MNI supports (FSM)
    stats: list[LevelStats] = dataclasses.field(default_factory=list)
    levels: Optional[list[EmbeddingLevel]] = None


# ---------------------------------------------------------------------------
# Phase-op binding: one (ctx, app, backend) triple, jitted or traceable


def _obs_op(name: str, backend_name: str, fn):
    """Wrap a host-dispatched phase op in an (optional) trace span.

    The span measures the host-side dispatch of the jitted op (JAX async
    dispatch returns before the device finishes), which is exactly what
    the warm path is allowed to pay — no forced sync; the op's device
    time is in a profile (``--profile``), under its span.  Only applied
    to the host path's jitted closures (``_PhaseOps(jit=True)``) — the
    raw ops traced into the executor's single jit must stay
    uninstrumented.
    """
    def wrapped(*args, **kwargs):
        if not _T.on:
            return fn(*args, **kwargs)
        with _T.span("op." + name, cat="phase", backend=backend_name):
            return fn(*args, **kwargs)
    return wrapped


class _PhaseOps:
    """Backend phase ops bound to one (ctx, app, backend) triple.

    ``jit=True`` wraps each op in its own ``jax.jit`` with static capacity
    arguments — the host driver's mode, where per-level closures are
    compiled once per bucketed capacity and reused across runs and blocks.
    ``jit=False`` leaves the ops raw so a whole mining run composes into a
    single jit (executor / ``shard_map`` / dry-run), whose caller builds
    the ops from the ctx it received as an argument.  Either way every op
    takes the graph as its first (bound) argument: a jit never closes
    over a graph array.  In jitted (host) mode every op is additionally
    bracketed by a trace span (:func:`_obs_op`) — the ``_PhaseOps`` seam
    is where backend op timings come from, uniformly for all registered
    backends.
    """

    def __init__(self, ctx: GraphCtx, app: MiningApp, backend,
                 fuse_filter: bool = True, materialize_fn=None,
                 jit: bool = False):
        self.ctx, self.app, self.backend = ctx, app, backend
        self.fuse_filter = fuse_filter
        self.materialize = materialize_fn or materialize
        be = backend

        def bind(name, fn, static=()):
            if jit:
                fn = _obs_op(name, be.name,
                             jax.jit(fn, static_argnames=static))
            return functools.partial(fn, ctx)

        if app.kind == "vertex":
            def inspect(c, emb, n, st, *, cand_cap):
                return be.inspect_vertex(c, app, emb, n, st, cand_cap)

            def bound(c, emb, n, st):
                return be.candidate_bound_vertex(c, app, emb, n, st)

            def extend(c, emb, n, st, *, cand_cap, out_cap):
                # fused extend+filter+compact with counts: the one
                # enumeration per level (no separate inspection on replay)
                return be.extend_pruned(c, app, emb, n, st, cand_cap,
                                        out_cap, fuse_filter=fuse_filter)

            def reduce(c, emb, n, st):
                return be.reduce_count(c, app, emb, n, st)

            self._inspect = bind("inspect_vertex", inspect, ("cand_cap",))
            self._bound = bind("bound_vertex", bound)
            self._extend = bind("extend_pruned", extend,
                                ("cand_cap", "out_cap"))
            self._reduce = bind("reduce_count", reduce)
        else:
            def bound_e(c, v0, vid, his, n):
                return be.candidate_bound_edge(c, app, v0, vid, his, n)

            def inspect_e(c, v0, vid, his, eid, n, *, cand_cap):
                return be.inspect_edge(c, app, v0, vid, his, eid, n,
                                       cand_cap)

            def extend_e(c, v0, vid, his, eid, n, *, cand_cap, out_cap):
                return be.extend_edge(c, app, v0, vid, his, eid, n,
                                      cand_cap, out_cap)

            def reduce_e(c, lvls):
                return be.reduce_domain(c, app, lvls)

            def filter_e(c, lvls, keep, *, out_cap):
                return be.filter_levels(lvls, keep, out_cap)

            self._bound_e = bind("bound_edge", bound_e)
            self._inspect_e = bind("inspect_edge", inspect_e, ("cand_cap",))
            self._extend_e = bind("extend_edge", extend_e,
                                  ("cand_cap", "out_cap"))
            self._reduce_e = bind("reduce_domain", reduce_e)
            self._filter_e = bind("filter_levels", filter_e, ("out_cap",))

    def reduce_e(self, levels, axis_names: tuple[str, ...] = ()):
        """Domain reduce; with mesh axes, the collective (sharded) variant."""
        if axis_names:
            return self.backend.reduce_domain_sharded(self.ctx, self.app,
                                                      levels, axis_names)
        return self._reduce_e(levels)


# ---------------------------------------------------------------------------
# Pipeline adapters: the kind-specific plumbing around the shared level loop


class _VertexPipeline:
    """Vertex-induced frontier: emb matrix + memo state, count reduce."""

    def __init__(self, ops: _PhaseOps, src, dst, n0):
        self.ops = ops
        self.levels = init_level0_vertex(src, dst, n0)
        self.emb = ops.materialize(self.levels)
        self.n = self.levels[0].n
        app, ctx = ops.app, ops.ctx
        self.state = (app.init_state(ctx, self.emb, self.n)
                      if app.init_state is not None
                      else jnp.zeros(self.emb.shape[:1], jnp.int32))
        self.p_map = None

    def level_range(self):
        return range(2, self.ops.app.max_size)

    def pre_loop(self, policy):
        return None

    def frontier_nbytes(self) -> int:
        """Bytes of the live materialized frontier (the [n, k] emb matrix)."""
        return int(self.emb.size) * self.emb.dtype.itemsize

    def bound(self):
        return self.ops._bound(self.emb, self.n, self.state)

    def inspect(self, cand_cap: int):
        return self.ops._inspect(self.emb, self.n, self.state,
                                 cand_cap=cand_cap)

    def extend(self, cand_cap: int, out_cap: int):
        new_level, self.emb, n_cand, n_fill = self.ops._extend(
            self.emb, self.n, self.state, cand_cap=cand_cap,
            out_cap=out_cap)
        self.levels.append(new_level)
        self.n = new_level.n
        # memo state follows the tree; apps with update_state_kernel get
        # the state column the extend op compacted itself (path-dependent
        # state — e.g. the multi-pattern branch bitmap)
        if new_level.state is not None:
            self.state = new_level.state
        elif self.state.shape[0] == 0:       # empty level-0 worklist
            self.state = jnp.zeros(new_level.idx.shape, jnp.int32)
        else:
            self.state = self.state[new_level.idx]
        return n_cand, new_level.n, n_fill

    def reduce_filter(self, level: int, policy):
        app = self.ops.app
        if app.get_pattern is not None or (app.needs_reduce
                                           and level == app.max_size - 1):
            with jax.named_scope("reduce"):
                pm, pat, self.state = self.ops._reduce(self.emb, self.n,
                                                       self.state)
            self.p_map = pm
        elif app.update_state_kernel is None:
            # apps without a kernel state update get a fresh memo slot per
            # level; kernel-threaded state must survive between levels
            self.state = jnp.zeros(self.emb.shape[:1], jnp.int32)

    def checkpoint_payload(self):
        return self.p_map

    def result(self, stats) -> MineResult:
        return MineResult(
            count=int(self.n),
            p_map=None if self.p_map is None else np.asarray(self.p_map),
            stats=stats, levels=self.levels)

    def bounded_result(self, policy):
        """Traceable (count, p_map, overflowed) for single-jit callers."""
        p_map = (self.p_map if self.p_map is not None
                 else jnp.zeros((self.ops.app.max_patterns,), jnp.int32))
        return self.n, p_map, policy.overflow()


class _EdgePipeline:
    """Edge-induced frontier: (v0, vid, his, eid), domain reduce + filter.

    The level-0 worklist defaults to the full undirected edge list of the
    graph context; explicit ``(src, dst, eid, n)`` arrays select a block
    (executor path) or a per-device shard (``axis_names`` switches the
    domain reduce to its collective variant for exact global MNI support).
    """

    def __init__(self, ops: _PhaseOps, src=None, dst=None, eid=None, n=None,
                 axis_names: tuple[str, ...] = ()):
        self.ops = ops
        ctx = ops.ctx
        if src is None:
            src, dst = ctx.usrc, ctx.udst
            eid = jnp.arange(ctx.n_uedges, dtype=jnp.int32)
            n = ctx.n_uedges
        self.levels = init_level0_edge(src, dst, eid, n)
        self.axis_names = tuple(axis_names)
        self.codes = self.supports = None
        self._front = None        # frontier cache, one materialize per level

    def level_range(self):
        # k-FSM: patterns of max_size - 1 edges; level 1 is pre-loop
        return range(2, self.ops.app.max_size)

    def pre_loop(self, policy):
        with jax.named_scope("level1"):
            self._reduce_filter(policy)
        return 1                  # the initial reduce+filter is "level 1"

    def _frontier(self):
        if self._front is None:
            self._front = materialize_edges(self.levels)
        return self._front

    def frontier_nbytes(self) -> int:
        """Bytes of the cached per-slot frontier expansion (0 if dropped)."""
        if self._front is None:
            return 0
        return sum(int(a.size) * a.dtype.itemsize for a in self._front
                   if hasattr(a, "size"))

    def bound(self):
        v0, vid, his, _ = self._frontier()
        return self.ops._bound_e(v0, vid, his, self.levels[-1].n)

    def inspect(self, cand_cap: int):
        return self.ops._inspect_e(*self._frontier(), self.levels[-1].n,
                                   cand_cap=cand_cap)

    def extend(self, cand_cap: int, out_cap: int):
        new_level, n_cand = self.ops._extend_e(
            *self._frontier(), self.levels[-1].n,
            cand_cap=cand_cap, out_cap=out_cap)
        self.levels.append(new_level)
        self._front = None
        return n_cand, new_level.n, jnp.int32(0)

    def reduce_filter(self, level: int, policy):
        self._reduce_filter(policy)

    def _reduce_filter(self, policy):
        app = self.ops.app
        with jax.named_scope("reduce"):
            codes, supports, pat, _ = self.ops.reduce_e(self.levels,
                                                        self.axis_names)
        self.codes, self.supports = codes, supports
        if not app.needs_filter:
            return
        with jax.named_scope("filter"):
            sup_of = supports[jnp.clip(pat, 0, app.max_patterns - 1)]
            keep = sup_of >= app.min_support
            n_keep = jnp.sum(
                (keep & (jnp.arange(keep.shape[0]) < self.levels[-1].n)
                 ).astype(jnp.int32))
            out_cap = policy.filter_cap(n_keep)
            self.levels = self.ops._filter_e(self.levels, keep,
                                             out_cap=out_cap)
        self._front = None

    def checkpoint_payload(self):
        return None if self.supports is None else np.asarray(self.supports)

    def result(self, stats) -> MineResult:
        app = self.ops.app
        mask = np.asarray(self.supports) >= app.min_support
        mask &= np.asarray(self.codes) != _INT_MAX
        return MineResult(count=int(mask.sum()),
                          codes=np.asarray(self.codes),
                          supports=np.asarray(self.supports),
                          stats=stats, levels=self.levels)

    def bounded_result(self, policy):
        """Traceable (codes, supports, overflowed) for single-jit callers."""
        return self.codes, self.supports, policy.overflow()


# ---------------------------------------------------------------------------
# The one level loop (paper Alg. 1, both embedding kinds, both policies)


def run_level_loop(pipe, policy, collect_stats: bool = False,
                   checkpoint_cb: Optional[Callable] = None
                   ) -> list[LevelStats]:
    """Drive a pipeline through all levels under a capacity policy.

    With a ``HostCapPolicy`` this is the classic host driver (and
    ``collect_stats`` / ``checkpoint_cb`` are honored); with a
    ``PlanCapPolicy`` the whole loop is jit-traceable — stats and
    checkpoints require host sync and must be off.
    """
    stats: list[LevelStats] = []

    def record(level, n_cand, t0):
        last = pipe.levels[-1]
        jax.block_until_ready(last.vid)
        nbytes = total_bytes(pipe.levels)
        stats.append(LevelStats(level, n_cand, int(last.n),
                                last.capacity, nbytes,
                                time.perf_counter() - t0,
                                nbytes + pipe.frontier_nbytes()))

    # Host spans and counters are host-path only: a traceable policy
    # means this loop body is being traced into a jit (executor /
    # shard_map / estimator probe), where a span would time tracing, not
    # running, and any int() would force a device sync the warm path
    # must not pay.  What names the traced path's device time is the
    # ``level{L}`` scope (op metadata, read back by
    # ``Miner.op_scopes()``); its counts come back through the policy
    # (``PlanCapPolicy.level_counts``).
    host = not policy.traceable
    t0 = time.perf_counter()
    pre_level = pipe.pre_loop(policy)
    if collect_stats and pre_level is not None:
        record(pre_level, 0, t0)
    for level in pipe.level_range():
        t0 = time.perf_counter()
        sp = (_T.span("level", level=level).__enter__()
              if (host and _T.on) else None)
        with jax.named_scope(f"level{level}"):
            cand_cap, out_cap = policy.extend_caps(pipe)
            # one fused enumeration per level: extend_pruned applies the
            # app's eager toAdd predicate and stream-compacts in the same
            # pass, returning the true counts — the policy's overflow
            # check (plan replay) consumes them instead of a second
            # inspection run
            n_cand, n_surv, n_fill = pipe.extend(cand_cap, out_cap)
            policy.note_extend(n_cand, n_surv, n_fill, cand_cap, out_cap)
            pipe.reduce_filter(level, policy)
        if host:
            # cap-utilization: true counts over the planned caps — the
            # exact-planner contract (util <= 1) made visible, and the
            # figure every later perf PR reports buffer tightness with
            nc, ns, nf = int(n_cand), int(n_surv), int(n_fill)
            _M.set_gauge("mine.cap_utilization",
                         ns / out_cap if out_cap else 0.0, level=level)
            _M.set_gauge("mine.cand_cap_utilization",
                         nc / cand_cap if cand_cap else 0.0, level=level)
            _M.inc("mine.candidates", nc, level=level)
            _M.inc("mine.survivors", ns, level=level)
            _M.inc("mine.fill_entries", nf, level=level)
            if sp is not None:
                sp.set(candidates=nc, survivors=ns, fill_entries=nf,
                       cand_cap=cand_cap, out_cap=out_cap,
                       utilization=ns / out_cap if out_cap else 0.0)
                sp.end()
        if collect_stats:
            record(level, int(n_cand), t0)
        if checkpoint_cb is not None:
            checkpoint_cb(level, pipe.levels, pipe.checkpoint_payload())
    return stats


def _note_live_bytes(kind: str, plan, cap0: int, stats,
                     block: Optional[int] = None) -> None:
    """Record actual-vs-predicted peak live bytes; warn on model drift.

    The PR-8 blocking story rests on :func:`~repro.core.blocks.
    estimate_live_bytes` upper-bounding what a run actually keeps
    device-resident ("blocked < unblocked by construction").  Whenever a
    host run measured real per-level ``live_bytes`` (``collect_stats``),
    this compares the observed peak against the model's prediction for
    the plan that drove the run: both land in the metrics registry as
    gauges, and an over-run (actual > predicted — the model drifted
    under the claim) emits a ``live_bytes_overrun`` warning event plus a
    counter, making the construction checkable at runtime instead of
    asserted in a docstring.
    """
    if plan is None or not stats:
        return
    actual = max((s.live_bytes for s in stats), default=0)
    if actual <= 0:
        return
    predicted = estimate_live_bytes(kind, plan.caps, plan.filter_caps,
                                    cap0)
    labels = {} if block is None else {"block": block}
    _M.set_gauge("blocks.live_bytes.actual", actual, **labels)
    _M.set_gauge("blocks.live_bytes.predicted", predicted, **labels)
    if actual > predicted:
        _M.inc("blocks.live_bytes.overrun")
        _T.instant("live_bytes_overrun", cat="warning", actual=actual,
                   predicted=predicted, **labels)


class Miner:
    """Host-driver mining engine for one (graph, app, backend) triple.

    Jitted phase closures are built once per Miner and reused across runs
    (and across edge blocks), so benchmark loops pay compilation once.
    ``backend`` picks the phase backend ("reference", "pallas", an
    instance, or None to honor ``app.backend``).

    Plan-once / execute-many: the first :meth:`run` for a given level-0
    capacity is a host-driven inspection pass that *records* a
    :class:`~repro.core.plan.MiningPlan`; subsequent runs (and all edge
    blocks after the first) replay the plan through one compiled
    :class:`~repro.core.plan.MiningExecutor` — a single jit call per
    block, no per-level host sync.  ``collect_stats`` / per-level
    checkpointing force the host path (they need the sync).
    """

    def __init__(self, graph: CSRGraph, app: MiningApp,
                 search: str = "binary", fuse_filter: bool = True,
                 materialize_fn=None, backend: BackendSpec = None,
                 pack_max_bytes: int = 4 << 20, pack_partial: bool = False,
                 relabel: bool | str = False,
                 pack_core: Optional[bool] = None):
        self.app = app
        self.backend = get_backend(backend if backend is not None
                                   else app.backend)
        self.backend.check_device()
        # locality-aware layout: relabel *before* DAG orientation so the
        # oriented CSR, the packed adjacency core, and the level-0
        # worklist all live in the permuted id space; every mined
        # quantity (counts, pattern maps, FSM codes/supports) is
        # permutation-invariant, so results are bitwise unchanged
        self.relabeling = None
        if relabel:
            order = "degree" if relabel is True else str(relabel)
            self.relabeling = relabel_graph(graph, order=order)
            graph = self.relabeling.graph
        self.graph_in = graph
        g = orient_dag(graph) if app.use_dag else graph
        self.graph = g
        if pack_core is None:       # core pack only pays off post-relabel
            pack_core = self.relabeling is not None
        self.ctx = make_ctx(g, search=search,
                            with_edge_uids=(app.kind == "edge"),
                            pack_max_bytes=pack_max_bytes,
                            pack_partial=pack_partial,
                            pack_core=pack_core)
        self.fuse_filter = fuse_filter
        self._materialize = materialize_fn or materialize
        self.ops = _PhaseOps(self.ctx, app, self.backend,
                             fuse_filter=fuse_filter,
                             materialize_fn=materialize_fn, jit=True)
        self._executors: dict[int, MiningExecutor] = {}
        self._digest: Optional[str] = None
        self._profile: Optional[tuple[tuple[float, ...], int]] = None
        self._full_plan: Optional[tuple] = None   # (caps, fcaps, cap0)

    # -- identity / executors ----------------------------------------------

    def graph_digest(self) -> str:
        """Cheap stable fingerprint of the (oriented) CSR arrays."""
        if self._digest is None:
            h = hashlib.sha1()
            h.update(np.asarray(self.graph.row_ptr).tobytes())
            h.update(np.asarray(self.graph.col_idx).tobytes())
            if self.graph.labels is not None:   # FSM survivor counts
                h.update(np.asarray(self.graph.labels).tobytes())
            self._digest = h.hexdigest()[:16]
        return self._digest

    def profile_sketch(self) -> tuple[tuple[float, ...], int]:
        """Degree-profile sketch of the (oriented) graph for plan transfer."""
        if self._profile is None:
            self._profile = (degree_profile(self.graph),
                             int(self.graph.n_edges))
        return self._profile

    def executor(self, cap0: int, plan_cache: Optional[PlanCache] = None
                 ) -> MiningExecutor:
        """The (cached) compiled executor for level-0 capacity ``cap0``."""
        ex = self._executors.get(cap0)
        if ex is None:
            ex = MiningExecutor(self, cap0, cache=plan_cache)
            self._executors[cap0] = ex
        else:
            ex.attach_cache(plan_cache)
        return ex

    def plan_reports(self) -> list[dict]:
        """Public view of the plan/executor state (for CLIs, logging).

        Each report carries the backend's per-app capability dict
        (``PhaseBackend.capabilities``) so users can see which ops
        actually ran fused — and which silently fell back to the
        reference XLA path — instead of inferring it from timings.
        """
        caps_report = self.backend.capabilities(self.app)
        out = []
        for cap0, ex in sorted(self._executors.items()):
            if ex.plan is not None:
                out.append({"cap0": cap0, "source": ex.plan.source,
                            "caps": list(ex.plan.caps),
                            "filter_caps": list(ex.plan.filter_caps),
                            "out_cap_total":
                                sum(o for _, o in ex.plan.caps)
                                + sum(ex.plan.filter_caps),
                            "compiles": ex.n_compiles,
                            "executions": ex.n_executions,
                            "replans": ex.n_replans,
                            "capabilities": dict(caps_report)})
        return out

    def op_scopes(self) -> dict[str, dict[str, str]]:
        """Per compiled executor, ``{module: {HLO instruction: scope
        path}}``: which ``level{L}/<phase>`` scope each op of the
        executor programs came from (``level2/probe/gather``), to name
        the ops of a device profile, which carry instruction names."""
        out: dict[str, dict[str, str]] = {}
        for ex in self._executors.values():
            out.update(ex.op_scopes())
        return out

    def pack_hit_rate(self) -> Optional[float]:
        """Degree-weighted probability a connectivity probe hits the
        packed adjacency bitmap (None when no pack was built)."""
        if self.ctx.packed is None:
            return None
        return _pack_hit_rate(self.graph, self.ctx.packed)

    def peak_live_bytes(self) -> Optional[int]:
        """Analytic peak device-resident bytes over all planned executors
        (:func:`repro.core.blocks.estimate_live_bytes`); the bench's
        ``peak_live_bytes`` column.  Blocked runs plan at block ``cap0``,
        so their peak prices below the same workload unblocked."""
        vals = [estimate_live_bytes(self.app.kind, ex.plan.caps,
                                    ex.plan.filter_caps, ex.cap0)
                for ex in self._executors.values() if ex.plan is not None]
        return max(vals) if vals else None

    def _p_map_meaningful(self) -> bool:
        return self.app.get_pattern is not None or self.app.needs_reduce

    # -- public ------------------------------------------------------------

    def init_edges(self):
        """Level-0 worklist: DAG edges (directed) or undirected src<dst.

        Apps with ``directed_worklist`` (compiled patterns whose first two
        matching positions are not automorphism-exchangeable) get both
        orientations of every undirected edge.
        """
        if self.app.use_dag or self.app.directed_worklist:
            return self.graph.edge_list()
        return self.graph.undirected_edge_list()

    def run(self, block_size: Optional[int] = None, collect_stats=False,
            checkpoint_cb=None, plan_cache: Optional[str | PlanCache] = None,
            plan_source: str = "inspect", safety_factor: float = 2.0,
            sample_size: int = 256, plan_seed: int = 0,
            block_bytes: Optional[int] = None,
            resume_from: Optional[dict] = None) -> MineResult:
        """Mine the graph; ``plan_source`` picks how a cold run plans.

        * ``"inspect"`` — the paper's inspection-execution: exact per-level
          host inspection (also the planning pass).  Default.
        * ``"estimate"`` — sampled estimator: a host-side pass over
          ``sample_size`` sampled level-0 embeddings estimates every
          capacity (times ``safety_factor``); the first real run goes
          straight through the compiled executor, and the overflow
          backstop guarantees exact results.
        * ``"cache"`` — like ``"estimate"``, but first try transferring
          the cached plan with the nearest degree profile (plan transfer
          across graphs and backends); fall back to the estimator.

        ``block_bytes`` (instead of an explicit ``block_size``) derives
        the block size from a device-byte budget: the sampled estimator
        prices the full-worklist plan, :func:`~repro.core.blocks.
        auto_block_size` picks the largest block that fits, and the
        scaled plan seeds the block executor.  ``resume_from`` restarts a
        blocked run from a checkpoint payload (``{"block", "count",
        "p_map"}``): completed blocks are skipped and the saved totals
        carried forward.

        An exact plan-cache hit (same graph/app/backend/cap0 signature)
        always wins regardless of mode; ``collect_stats`` / per-level
        checkpointing force the host inspection path.
        """
        if plan_source not in ("inspect", "estimate", "cache"):
            raise ValueError(f"plan_source {plan_source!r} not in "
                             "('inspect', 'estimate', 'cache')")
        cache = (PlanCache(plan_cache) if isinstance(plan_cache, str)
                 else plan_cache)
        seeding = (None if plan_source == "inspect" or collect_stats
                   or checkpoint_cb is not None
                   else (plan_source, safety_factor, sample_size,
                         plan_seed, cache))
        t0, wait0 = time.perf_counter(), self._wait_s()
        with _T.span("miner.run", app=self.app.name,
                     backend=self.backend.name, kind=self.app.kind,
                     plan_source=plan_source):
            try:
                if self.app.kind == "edge":
                    # paper §5.2: blocking disabled for FSM (global
                    # support sync); bounded/sharded FSM paths:
                    # bounded_mine_edge.
                    return self._run_edge(collect_stats, checkpoint_cb,
                                          cache, seeding)
                with _T.span("miner.worklist"):
                    src, dst = self.init_edges()
                    m = int(src.shape[0])
                if block_bytes and not block_size:
                    block_size = self._auto_block_size(
                        m, block_bytes, sample_size, safety_factor,
                        plan_seed)
                if not block_size or block_size >= m:
                    return self._run_vertex_full(src, dst, m, collect_stats,
                                                 checkpoint_cb, cache,
                                                 seeding)
                return self._run_vertex_blocked(src, dst, m, block_size,
                                                collect_stats, checkpoint_cb,
                                                cache, seeding, resume_from)
            finally:
                # the run's host seconds: all but the executor's wait
                # for the device
                _M.set_gauge("miner.host_s", time.perf_counter() - t0
                             - (self._wait_s() - wait0))

    def _wait_s(self) -> float:
        return sum(ex.wait_s for ex in self._executors.values())

    def _auto_block_size(self, m: int, budget_bytes: int,
                         sample_size: int = 256,
                         safety_factor: float = 2.0,
                         plan_seed: int = 0) -> int:
        """Block size fitting ``budget_bytes``, from an estimated plan.

        Prices the *full-worklist* plan with the sampled estimator, then
        walks block sizes down until the scaled plan's live bytes fit.
        The full plan is stashed so the block executor can be seeded with
        its block-ratio rescale instead of a second sampling pass.
        """
        cap0 = bucket_pow2(m)
        caps, fcaps = estimate_plan(self, cap0, sample_size=sample_size,
                                    safety_factor=safety_factor,
                                    seed=plan_seed)
        self._full_plan = (caps, fcaps, cap0)
        return auto_block_size(m, caps, fcaps, budget_bytes,
                               kind=self.app.kind)

    def _seed_plan(self, ex: MiningExecutor, seeding) -> None:
        """Give a cold executor an estimated or transferred plan."""
        if seeding is None or ex.has_plan:
            return
        plan_source, safety_factor, sample_size, plan_seed, cache = seeding
        if plan_source == "cache" and cache is not None:
            profile, n_edges = self.profile_sketch()
            near = cache.nearest(ex.app_key, self.app.kind, profile,
                                 n_edges, exclude=(ex.signature,),
                                 transfer_key=ex.transfer_key,
                                 cap0=ex.cap0)
            # cross-backend candidates passed the transfer-key match but
            # may still have been recorded under an incompatible cap
            # schedule (different max_size build, truncated plan);
            # shape-validate before rescaling, else fall through to the
            # estimator
            if near is not None and compatible_caps(near, self.app):
                caps, fcaps = transfer_caps(near, ex.cap0, safety_factor)
                ex.adopt_plan(caps, fcaps, source="transfer")
                return
        caps, fcaps = estimate_plan(self, ex.cap0, sample_size=sample_size,
                                    safety_factor=safety_factor,
                                    seed=plan_seed)
        ex.adopt_plan(caps, fcaps, source="estimated")

    # -- vertex-induced paths ----------------------------------------------

    def _host_run(self, pipe, executor: MiningExecutor, collect_stats,
                  checkpoint_cb, block: Optional[int] = None) -> MineResult:
        """Inspection-execution host run; records the executor's plan."""
        policy = HostCapPolicy()
        stats = run_level_loop(pipe, policy, collect_stats, checkpoint_cb)
        executor.adopt_plan(policy.caps, policy.filter_caps)
        if collect_stats:
            _note_live_bytes(self.app.kind, executor.plan, executor.cap0,
                             stats, block=block)
        return pipe.result(stats)

    def _run_vertex_full(self, src, dst, m, collect_stats, checkpoint_cb,
                         cache, seeding=None) -> MineResult:
        cap0 = bucket_pow2(m)
        ex = self.executor(cap0, cache)
        self._seed_plan(ex, seeding)
        if collect_stats or checkpoint_cb is not None or not ex.has_plan:
            return self._host_run(_VertexPipeline(self.ops, src, dst, m),
                                  ex, collect_stats, checkpoint_cb)
        pad = cap0 - m
        with _T.span("miner.worklist", pad=pad):
            src, dst = jnp.pad(src, (0, pad)), jnp.pad(dst, (0, pad))
        cnt, p_map = ex.execute(src, dst, m)
        return MineResult(count=cnt,
                          p_map=p_map if self._p_map_meaningful() else None)

    def _run_vertex_blocked(self, src, dst, m, block_size, collect_stats,
                            checkpoint_cb, cache, seeding=None,
                            resume_from=None) -> MineResult:
        # Edge blocking (§5.2): stream level-0 chunks through one warm
        # executor, bounding peak memory; pattern maps / counts
        # accumulate.  The worklist stays host-side — BlockQueue stages
        # one block (plus one in flight, double-buffered) to the device.
        # Only the first block of a cold miner runs the host inspection
        # pass (doubling as planner) — unless an estimated, transferred,
        # or block-ratio-rescaled plan lets it skip even that.
        cap0 = bucket_pow2(block_size)
        ex = self.executor(cap0, cache)
        if not ex.has_plan and self._full_plan is not None:
            fcaps, ffcaps, fcap0 = self._full_plan
            sc, fc = scale_caps(fcaps, ffcaps, cap0 / fcap0)
            ex.adopt_plan(sc, fc, source="estimated")
        self._seed_plan(ex, seeding)
        total = 0
        p_map = None
        done = -1                 # last completed block index
        if resume_from:
            done = int(resume_from.get("block", -1))
            total = int(resume_from.get("count", 0))
            pm = resume_from.get("p_map")
            p_map = None if pm is None else jnp.asarray(pm)
        stats: list[LevelStats] = []
        blocks = [b for b in make_blocks(m, block_size) if b.index > done]
        queue = BlockQueue((np.asarray(src), np.asarray(dst)), blocks, cap0)
        for blk, (s, d) in queue:
            with _T.span("block", index=blk.index, n=blk.n):
                if collect_stats or not ex.has_plan:
                    r = self._host_run(
                        _VertexPipeline(self.ops, s, d, blk.n), ex,
                        collect_stats, None, block=blk.index)
                    cnt, pm = r.count, r.p_map
                    stats.extend(r.stats)
                else:
                    cnt, pm_arr = ex.execute(s, d, blk.n)
                    pm = pm_arr if self._p_map_meaningful() else None
            total += cnt
            if pm is not None:
                p_map = pm if p_map is None else p_map + pm
            if checkpoint_cb is not None:
                checkpoint_cb(blk.index, None,
                              {"count": total, "p_map": p_map,
                               "block": blk.index})
        return MineResult(count=total, p_map=p_map, stats=stats)

    # -- edge-induced (FSM) path -------------------------------------------

    def _run_edge(self, collect_stats, checkpoint_cb, cache,
                  seeding=None) -> MineResult:
        m = self.ctx.n_uedges
        cap0 = bucket_pow2(m)
        ex = self.executor(cap0, cache)
        self._seed_plan(ex, seeding)
        if collect_stats or checkpoint_cb is not None or not ex.has_plan:
            return self._host_run(_EdgePipeline(self.ops), ex,
                                  collect_stats, checkpoint_cb)
        pad = cap0 - m
        with _T.span("miner.worklist", pad=pad):
            worklist = (jnp.pad(self.ctx.usrc, (0, pad)),
                        jnp.pad(self.ctx.udst, (0, pad)),
                        jnp.pad(jnp.arange(m, dtype=jnp.int32), (0, pad)))
        codes, supports = ex.execute_edge(*worklist, m)
        mask = (supports >= self.app.min_support) & (codes != _INT_MAX)
        return MineResult(count=int(mask.sum()), codes=codes,
                          supports=supports)


# ---------------------------------------------------------------------------
# Bounded single-jit mining (dry-run / shard_map distribution)


def bounded_mine_vertex(ctx: GraphCtx, app: MiningApp,
                        src: jnp.ndarray, dst: jnp.ndarray,
                        n_valid: jnp.ndarray, caps: tuple[int, ...],
                        backend: BackendSpec = None):
    """Whole vertex-induced mining run as one jittable function.

    caps[i] = (cand_cap, out_cap) for extension level i.  Returns
    (count i32[], p_map i32[max_patterns], overflowed bool[]).
    Capacities overflowing truncate the worklist; ``overflowed`` reports it
    (callers re-run with bigger caps — the bounded-mode contract).  This is
    the shared level loop under a :class:`~repro.core.plan.PlanCapPolicy`;
    all phase ops resolve through the backend registry.
    """
    be = get_backend(backend if backend is not None else app.backend)
    be.check_device()
    ops = _PhaseOps(ctx, app, be)
    pipe = _VertexPipeline(ops, src, dst, n_valid)
    policy = PlanCapPolicy(MiningPlan(kind="vertex", caps=tuple(caps)))
    run_level_loop(pipe, policy)
    return pipe.bounded_result(policy)


def bounded_mine_edge(ctx: GraphCtx, app: MiningApp,
                      src: jnp.ndarray, dst: jnp.ndarray,
                      eid: jnp.ndarray, n_valid: jnp.ndarray,
                      caps: tuple[tuple[int, int], ...],
                      filter_caps: tuple[int, ...],
                      backend: BackendSpec = None,
                      axis_names: tuple[str, ...] = ()):
    """Whole edge-induced (FSM) mining run as one jittable function.

    ``(src, dst, eid)`` is the level-0 undirected-edge worklist (a block
    or per-device shard of ``(ctx.usrc, ctx.udst, arange(n_uedges))``);
    ``filter_caps`` are the support-filter output capacities in
    invocation order (pre-loop first, then one per level).  Returns
    (codes i32[max_patterns], supports i32[max_patterns],
    overflowed bool[]).

    Under ``shard_map``, pass the mesh ``axis_names``: the domain reduce
    switches to its collective variant (pattern tables aligned by
    all-gather, domain bitmaps merged by psum), which keeps MNI support —
    and therefore every level's support filter — exact over the union of
    all devices' embeddings (the paper's global support sync).
    """
    be = get_backend(backend if backend is not None else app.backend)
    be.check_device()
    ops = _PhaseOps(ctx, app, be)
    pipe = _EdgePipeline(ops, src=src, dst=dst, eid=eid, n=n_valid,
                         axis_names=axis_names)
    policy = PlanCapPolicy(MiningPlan(kind="edge", caps=tuple(caps),
                                      filter_caps=tuple(filter_caps)))
    run_level_loop(pipe, policy)
    return pipe.bounded_result(policy)


def sharded_program(app: MiningApp, mesh,
                    caps: tuple[tuple[int, int], ...],
                    axis_names: tuple[str, ...] = ("data",),
                    backend: BackendSpec = None,
                    filter_caps: Optional[tuple[int, ...]] = None):
    """The jitted ``shard_map`` program behind :func:`mine_sharded`.

    Vertex apps: ``(ctx, src[n_dev, cap0], dst[n_dev, cap0], n[n_dev]) ->
    (count, p_map, overflowed)``; edge apps take ``eid`` after ``dst`` and
    return ``(codes, supports, overflowed)``.  Row i of each stacked
    worklist array goes to device i; the graph context is an argument
    replicated on every device (``PartitionSpec()``), so the program
    carries no graph-sized constant.
    """
    from jax.sharding import PartitionSpec as PSpec

    be = get_backend(backend if backend is not None else app.backend)
    spec, rep = PSpec(axis_names), PSpec()
    if app.kind == "edge":
        if filter_caps is None:
            raise ValueError("sharded FSM needs filter_caps (support-filter "
                             "output capacities per level)")

        def local_e(c, src_blk, dst_blk, eid_blk, n_blk):
            codes, sup, ovf = bounded_mine_edge(
                c, app, src_blk[0], dst_blk[0], eid_blk[0], n_blk[0],
                caps, tuple(filter_caps), backend=be,
                axis_names=axis_names)
            for ax in axis_names:
                ovf = jax.lax.pmax(ovf.astype(jnp.int32), ax).astype(bool)
            return codes, sup, ovf

        return jax.jit(jax.shard_map(
            local_e, mesh=mesh, in_specs=(rep,) + (spec,) * 4,
            out_specs=(rep, rep, rep), check_vma=False))

    def local(c, src_blk, dst_blk, n_blk):
        cnt, p_map, ovf = bounded_mine_vertex(c, app, src_blk[0],
                                              dst_blk[0], n_blk[0], caps,
                                              backend=be)
        for ax in axis_names:
            cnt = jax.lax.psum(cnt, ax)
            p_map = jax.lax.psum(p_map, ax)
            ovf = jax.lax.pmax(ovf.astype(jnp.int32), ax).astype(bool)
        return cnt, p_map, ovf

    return jax.jit(jax.shard_map(
        local, mesh=mesh, in_specs=(rep, spec, spec, spec),
        out_specs=(rep, rep, rep), check_vma=False))


def mine_sharded(graph: CSRGraph, app: MiningApp, mesh,
                 caps: tuple[tuple[int, int], ...],
                 axis_names: tuple[str, ...] = ("data",),
                 backend: BackendSpec = None,
                 filter_caps: Optional[tuple[int, ...]] = None,
                 relabel: bool | str = False):
    """Distributed mining: level-0 edge *blocks* sharded over mesh axes.

    The graph CSR is replicated (in-memory GPM practice); the worklist is
    cut into one contiguous :class:`~repro.core.blocks.EdgeBlock` per
    device (:func:`~repro.core.blocks.make_blocks` /
    :func:`~repro.core.blocks.stack_blocks` — the same construction the
    single-host streaming scheduler uses, so ``relabel=True`` gives every
    device a locality-coherent range of the degree-ordered worklist).
    Each device mines its block with :func:`bounded_mine_vertex` (vertex
    apps) or :func:`bounded_mine_edge` (FSM, which needs
    ``filter_caps``); counts and pattern maps merge with one psum per
    run, FSM supports via the collective domain reduce — the support
    filter stays exact over the union of all devices' embeddings
    (paper's global support sync), so blocking never changes FSM output.
    Returns global values:
    vertex apps -> (count, p_map, overflowed);
    edge apps   -> (count, codes, supports, overflowed).
    """
    program = sharded_program(app, mesh, caps, axis_names, backend,
                              filter_caps)
    args = sharded_inputs(graph, app, mesh, axis_names, backend, relabel)
    return sharded_results(app, program(*args))


def sharded_inputs(graph: CSRGraph, app: MiningApp, mesh,
                   axis_names: tuple[str, ...] = ("data",),
                   backend: BackendSpec = None,
                   relabel: bool | str = False) -> tuple:
    """The arguments of :func:`sharded_program`, placed on ``mesh``.

    Returns ``(ctx, *worklist, counts)``: the graph context replicated on
    every device, and the level-0 worklist cut into one block per device,
    row i of each stacked array (and ``counts[i]``) on device i.
    """
    from jax.sharding import NamedSharding, PartitionSpec as PSpec

    # reuse ctx preprocessing (DAG orient, packs, uids) + optional relabel
    miner = Miner(graph, app, backend=backend, relabel=relabel)
    ctx = miner.ctx
    n_dev = int(np.prod([mesh.shape[a] for a in axis_names]))
    if app.kind == "edge":
        m = ctx.n_uedges
        columns = (np.asarray(ctx.usrc), np.asarray(ctx.udst),
                   np.arange(m, dtype=np.int32))
    else:
        columns = tuple(np.asarray(a) for a in miner.init_edges())
        m = int(columns[0].shape[0])
    per_dev = -(-m // n_dev)
    blocks = make_blocks(m, per_dev, count=n_dev)
    counts = np.asarray([b.n for b in blocks], dtype=np.int32)
    stacked = stack_blocks(columns, blocks, bucket_pow2(per_dev))
    ctx = jax.device_put(ctx, NamedSharding(mesh, PSpec()))
    worklist = jax.device_put(stacked + (counts,),
                              NamedSharding(mesh, PSpec(axis_names)))
    return (ctx, *worklist)


def sharded_results(app: MiningApp, out) -> tuple:
    """:func:`sharded_program`'s outputs as :func:`mine_sharded` returns
    them (host values)."""
    if app.kind == "edge":
        codes, sup, ovf = out
        codes, sup = np.asarray(codes), np.asarray(sup)
        cnt = int(((sup >= app.min_support)
                   & (codes != _INT_MAX)).sum())
        return cnt, codes, sup, bool(ovf)
    cnt, p_map, ovf = out
    return int(cnt), np.asarray(p_map), bool(ovf)
