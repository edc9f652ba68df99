"""Plan-once / execute-many mining layer (inspection-execution, compiled).

The paper's inspection-execution optimization plans buffer capacities
before running a phase.  The host driver (:class:`repro.core.engine.Miner`)
derives that plan with one ``int()`` sync per level — fine for a single
run, wasteful when the same (graph, app, backend) triple is mined many
times: every edge block, every device, every repeated serving request
re-pays the per-level host round-trips.

This module separates *planning* from *execution*:

* :class:`MiningPlan` — the per-level ``(cand_cap, out_cap)`` schedule
  (plus FSM filter capacities) together with a signature identifying the
  (graph, app, backend, level-0 capacity) it was planned for.  Plans are
  JSON-serializable; :class:`PlanCache` persists them on disk so a later
  process skips the inspection pass entirely (``--plan-cache``).
* Capacity policies — the *one* level loop in :mod:`repro.core.engine`
  asks a policy for each level's capacities.  :class:`HostCapPolicy` is
  the paper's inspection-execution (exact counts, host sync; candidate
  caps bucket to powers of two, output caps to tight survivor-scale
  multiples — see :func:`bucket_cap`) and records the plan as a side
  effect; :class:`PlanCapPolicy` replays a recorded plan with **no host
  sync and no inspection pass** — the fused ``extend_pruned`` op reports
  the true counts with its result, and the policy folds them into a
  jit-traceable overflow flag.
* :class:`MiningExecutor` — compiles the whole mining run once per plan
  (one XLA executable with static capacities) and reuses it across edge
  blocks and repeated runs.  Overflow (a block bigger than the plan
  assumed) triggers the only remaining host loop: grow the plan, refresh
  the cache, retry.

The same compiled artifact serves the ``shard_map`` distribution path:
:func:`repro.core.engine.bounded_mine_vertex` /
:func:`~repro.core.engine.bounded_mine_edge` are thin wrappers running the
shared level loop under a :class:`PlanCapPolicy`.
"""
from __future__ import annotations

import dataclasses
import functools
import hashlib
import json
import os
import tempfile
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import metrics as _M
from repro.obs import trace as _T


def bucket_pow2(n: int, minimum: int = 128) -> int:
    """Round up to the next power of two (bounded retrace count)."""
    n = max(int(n), minimum)
    return 1 << (n - 1).bit_length()


def bucket_cap(n: int, quantum: int = 128, minimum: int = 128) -> int:
    """Survivor-scale capacity: round up to a tight multiple of quantum.

    Post-filter buffers (extend ``out_cap``, FSM filter caps) are planned
    from *exact* survivor counts, so the pow2 slack bucket_pow2 carries —
    up to 2x over-allocation — buys nothing once a plan is recorded: the
    executor compiles per plan anyway.  Tight caps are the memory half of
    eager pruning: warm-run buffers scale with survivors, not candidates.
    Overflow (a later block/run with more survivors) is already handled by
    the executor's grow-and-retry loop.
    """
    n = max(int(n), minimum)
    return -(-n // quantum) * quantum


PLAN_SCHEMA = 4


class StalePlanError(ValueError):
    """A serialized plan from an incompatible (older/newer) schema."""


# ---------------------------------------------------------------------------
# The plan


@dataclasses.dataclass(frozen=True)
class MiningPlan:
    """Static capacity schedule for one compiled mining run.

    ``caps[i]`` is the ``(cand_cap, out_cap)`` pair for extension level
    ``i`` (paper level ``i + 2``); ``filter_caps`` holds the output
    capacities of the FSM support-filter compactions in invocation order
    (the pre-loop filter first, then one per level).  ``cap0`` is the
    level-0 worklist capacity the plan assumes (the padded block size).

    Plan transfer (schema 3): ``app_key`` identifies the app/backend
    semantics *without* the graph, and ``profile``/``n_edges`` are the
    planned graph's degree-profile sketch
    (:func:`repro.graph.csr.degree_profile`), so :meth:`PlanCache.nearest`
    can seed a plan for a *new* graph from the cached plan whose profile
    is closest.  ``source`` records provenance: ``inspect`` (exact host
    inspection pass), ``estimated`` (sampled estimator), ``transfer``
    (profile-nearest cached plan, rescaled), ``cache`` (exact cache hit),
    ``grown`` (overflow backstop), ``manual``.
    """

    kind: str                                  # "vertex" | "edge"
    caps: tuple[tuple[int, int], ...]
    filter_caps: tuple[int, ...] = ()
    cap0: int = 0
    signature: str = ""
    source: str = "manual"
    app_key: str = ""
    profile: tuple[float, ...] = ()
    n_edges: int = 0
    # backend-agnostic app identity (schema 4): capacities are counts of
    # candidates/survivors, which every backend produces bitwise equal —
    # so a plan recorded under "reference" is a valid capacity seed for a
    # "pallas"/"pallas-mp" run of the same app.  transfer_key drops the
    # backend name and compaction contract from app_key; cross-backend
    # lookups (PlanCache.nearest) match on it.
    transfer_key: str = ""

    def grown(self, factor: int = 2) -> "MiningPlan":
        """Overflow response: scale every capacity (stays a power of two)."""
        return dataclasses.replace(
            self,
            caps=tuple((c * factor, o * factor) for c, o in self.caps),
            filter_caps=tuple(f * factor for f in self.filter_caps),
            source="grown")

    def to_json(self) -> str:
        return json.dumps({
            "schema": PLAN_SCHEMA, "kind": self.kind, "cap0": self.cap0,
            "caps": [list(c) for c in self.caps],
            "filter_caps": list(self.filter_caps),
            "signature": self.signature, "source": self.source,
            "app_key": self.app_key, "profile": list(self.profile),
            "n_edges": self.n_edges, "transfer_key": self.transfer_key})

    @classmethod
    def from_json(cls, text: str) -> "MiningPlan":
        d = json.loads(text)
        schema = d.get("schema")
        if schema != PLAN_SCHEMA:
            # capacity semantics changed (e.g. pow2 -> survivor-scale
            # buckets); replaying a stale plan would be silently wasteful
            # or overflow-loop, so callers must ignore it and re-plan
            raise StalePlanError(
                f"plan schema {schema!r} != current {PLAN_SCHEMA}")
        return cls(kind=d["kind"], cap0=int(d["cap0"]),
                   caps=tuple((int(c), int(o)) for c, o in d["caps"]),
                   filter_caps=tuple(int(f) for f in d["filter_caps"]),
                   signature=d.get("signature", ""),
                   source=d.get("source", "cache"),
                   app_key=d.get("app_key", ""),
                   profile=tuple(float(x) for x in d.get("profile", ())),
                   n_edges=int(d.get("n_edges", 0)),
                   transfer_key=d.get("transfer_key", ""))


def plan_app_key(app, backend_name: str, fuse_filter: bool = True,
                 compaction: str = "xla-scan") -> str:
    """App/backend identity *without* the graph — the transfer axis.

    Everything capacity-relevant about the app (including
    ``min_support`` and the compiled ``plan_key``) but no graph digest
    and no cap0: plans recorded under the same ``app_key`` on different
    graphs are capacity schedules for the *same* computation, so their
    per-level shapes are comparable once rescaled by worklist size.

    ``compaction`` is the backend's survivor-offset strategy
    (``PhaseBackend.compaction``): it sizes auxiliary buffers (the
    two-pass backend's tile-count vector scales with ``cand_cap``), so a
    plan captured under one compaction contract must not replay under
    another even when the backend name is reused in a custom registry."""
    fields = (app.name, app.kind, app.max_size, app.use_dag,
              app.needs_reduce, app.needs_filter, app.support_mode,
              app.max_patterns, app.min_support, app.plan_key,
              app.directed_worklist, backend_name, bool(fuse_filter),
              str(compaction))
    return hashlib.sha1(repr(fields).encode()).hexdigest()[:20]


def plan_transfer_key(app, fuse_filter: bool = True) -> str:
    """App identity for *cross-backend* plan transfer: no backend name,
    no compaction contract.

    Capacities in a plan are candidate/survivor counts; the phase
    backends are bitwise equal on those (the parity contract), so the
    same app mined under any backend produces the same per-level shapes.
    Plans whose ``transfer_key`` matches are capacity-comparable even
    when their ``app_key`` (which folds the backend) differs — a plan
    recorded under ``reference`` seeds a ``pallas``/``pallas-mp`` run.
    Backend-specific *auxiliary* buffer sizing (e.g. the two-pass
    tile-count vector) derives from the transferred caps at compile
    time, so it needs no key of its own.
    """
    fields = (app.name, app.kind, app.max_size, app.use_dag,
              app.needs_reduce, app.needs_filter, app.support_mode,
              app.max_patterns, app.min_support, app.plan_key,
              app.directed_worklist, bool(fuse_filter))
    return hashlib.sha1(repr(fields).encode()).hexdigest()[:20]


def compatible_caps(plan: "MiningPlan", app) -> bool:
    """Can ``plan``'s capacity schedule drive a run of ``app``?

    The shape contract a transferred plan must meet: same embedding
    kind, one ``(cand_cap, out_cap)`` pair per extension level, and —
    for support-filtered FSM — one filter capacity per compaction
    (pre-loop + one per level).  Plans recorded under a different
    capability surface (older app revision, different max_size) fail
    here and the caller falls back to the estimator.
    """
    if plan.kind != app.kind or not plan.caps:
        return False
    n_levels = max(app.max_size - 2, 0)
    if len(plan.caps) != n_levels:
        return False
    if app.kind == "edge" and app.needs_filter:
        return len(plan.filter_caps) == n_levels + 1
    return True


def plan_signature(graph_digest: str, app, backend_name: str, cap0: int,
                   fuse_filter: bool = True,
                   compaction: str = "xla-scan") -> str:
    """Stable identity of (graph, app knobs, backend, block capacity)."""
    fields = (graph_digest,
              plan_app_key(app, backend_name, fuse_filter, compaction),
              int(cap0))
    return hashlib.sha1(repr(fields).encode()).hexdigest()[:20]


class PlanCache:
    """Directory of ``<signature>.json`` plans (atomic writes).

    Entries carry a schema version: stale-schema (or corrupt) files are
    ignored on load and deleted, so a capacity-semantics change never
    replays an incompatible plan.  ``max_entries`` caps the directory with
    LRU-by-mtime eviction — reads touch the file's mtime, writes evict the
    oldest entries past the cap (``--plan-cache-max`` on the CLI).
    """

    def __init__(self, directory: str, max_entries: Optional[int] = None):
        self.directory = directory
        self.max_entries = max_entries

    def _path(self, signature: str) -> str:
        return os.path.join(self.directory, f"{signature}.json")

    def get(self, signature: str) -> Optional[MiningPlan]:
        path = self._path(signature)
        if not os.path.exists(path):
            return None
        try:
            with open(path) as f:
                plan = MiningPlan.from_json(f.read())
        except (StalePlanError, ValueError, KeyError):
            try:
                os.remove(path)              # stale schema / corrupt entry
            except OSError:
                pass
            return None
        if plan.signature != signature:
            # A renamed/copied/hand-edited entry whose recorded signature
            # disagrees with its filename.  Replaying it would resurrect
            # capacities planned for a DIFFERENT (graph, app, backend,
            # cap0) identity — for FSM that includes min_support, whose
            # filter_caps would silently truncate the support filter.
            # plan_signature folds every cap-relevant app knob (including
            # min_support and plan_key), so an honest lookup can only hit
            # a plan recorded under the same semantics; anything else is
            # dropped here.
            try:
                os.remove(path)
            except OSError:
                pass
            return None
        try:
            os.utime(path)                   # LRU touch
        except OSError:
            pass
        return dataclasses.replace(plan, source="cache")

    def nearest(self, app_key: str, kind: str, profile: tuple[float, ...],
                n_edges: int, exclude: tuple[str, ...] = (),
                transfer_key: Optional[str] = None,
                cap0: Optional[int] = None) -> Optional[MiningPlan]:
        """The cached plan for this app with the closest degree profile.

        Plan transfer: an exact signature miss (new graph) scans the
        cache for plans of the same app semantics recorded on other
        graphs/backends and returns the one whose degree-profile sketch
        is nearest (log-space quantile distance + edge-count term).  The
        caller rescales its capacities (:func:`transfer_caps`) — the
        match seeds the plan, the overflow backstop guarantees exactness.

        Candidates match on ``app_key`` (same backend) or — when
        ``transfer_key`` is given — on the backend-agnostic transfer key
        (cross-backend transfer); same-backend plans win ties.  With
        ``cap0`` the *worklist-size ratio* is weighted into the distance
        (:data:`CAP0_WEIGHT`): rescaling a tiny graph's plan 1000x
        amplifies its noise 1000x, so a same-scale plan with a slightly
        worse profile beats a tiny plan with a perfect one.
        Stale/corrupt entries are skipped (not deleted: only an exact
        ``get`` proves an entry unusable for its own signature).
        """
        try:
            names = [n for n in os.listdir(self.directory)
                     if n.endswith(".json")]
        except OSError:
            return None
        best, best_d = None, None
        for name in sorted(names):
            try:
                with open(os.path.join(self.directory, name)) as f:
                    plan = MiningPlan.from_json(f.read())
            except (OSError, StalePlanError, ValueError, KeyError):
                continue
            same_backend = plan.app_key == app_key
            transferable = (transfer_key is not None and plan.transfer_key
                            and plan.transfer_key == transfer_key)
            if (not (same_backend or transferable) or plan.kind != kind
                    or plan.signature in exclude or not plan.caps):
                continue
            d = profile_distance(profile, n_edges, plan.profile,
                                 plan.n_edges)
            if d is None:
                continue
            if cap0 is not None and plan.cap0:
                d += CAP0_WEIGHT * float(
                    np.log(int(cap0) / plan.cap0) ** 2)
            if not same_backend:
                d += CROSS_BACKEND_PENALTY
            if best_d is None or d < best_d:
                best, best_d = plan, d
        return best

    def put(self, plan: MiningPlan) -> str:
        os.makedirs(self.directory, exist_ok=True)
        path = self._path(plan.signature)
        fd, tmp = tempfile.mkstemp(dir=self.directory, suffix=".tmp")
        with os.fdopen(fd, "w") as f:
            f.write(plan.to_json())
        os.replace(tmp, path)
        self._evict()
        return path

    def _evict(self) -> None:
        if self.max_entries is None:
            return
        try:
            names = [n for n in os.listdir(self.directory)
                     if n.endswith(".json")]
        except OSError:
            return
        if len(names) <= self.max_entries:
            return
        def mtime(name):
            try:
                return os.path.getmtime(os.path.join(self.directory, name))
            except OSError:
                return 0.0
        for name in sorted(names, key=mtime)[: len(names)
                                             - self.max_entries]:
            try:
                os.remove(os.path.join(self.directory, name))
            except OSError:
                pass


# nearest() distance weights: the cap0-ratio term dominates once the
# worklist sizes are more than ~a decade apart (log^2 10 ~ 5.3 vs the
# O(0.1) profile terms of roughly-similar graphs), which is the point —
# a 1000x rescale of a tiny plan is a worse seed than a same-scale plan
# with a mildly different degree shape.  The cross-backend penalty is a
# *tiebreak* (capacities transfer exactly across backends; prefer the
# same backend only when otherwise equally near).
CAP0_WEIGHT = 1.0
CROSS_BACKEND_PENALTY = 1e-6


def profile_distance(profile_a: tuple[float, ...], m_a: int,
                     profile_b: tuple[float, ...], m_b: int
                     ) -> Optional[float]:
    """Log-space distance between two degree-profile sketches.

    Quantiles compare in ``log1p`` space (a 10 -> 20 median shift matters
    as much at scale 10 as 100 -> 200 does at scale 100) plus a log
    edge-count term, so "similar shape, similar size" wins.  ``None``
    when the sketches are incomparable (different quantile grids)."""
    if not profile_a or len(profile_a) != len(profile_b):
        return None
    a = np.log1p(np.asarray(profile_a, np.float64))
    b = np.log1p(np.asarray(profile_b, np.float64))
    size_term = np.log((m_a + 1.0) / (m_b + 1.0)) ** 2
    return float(np.mean((a - b) ** 2) + size_term)


def transfer_caps(plan: MiningPlan, cap0: int, safety_factor: float = 2.0
                  ) -> tuple[tuple[tuple[int, int], ...],
                             tuple[int, ...]]:
    """Rescale a transferred plan's capacities to a new worklist size.

    Per-level counts scale roughly linearly with the level-0 worklist
    for graphs of similar degree profile, so every capacity is scaled by
    ``cap0_new / cap0_old`` (times the safety factor) and re-bucketed.
    The result is a *seed*, not a guarantee — overflow grows it."""
    ratio = (int(cap0) / max(plan.cap0, 1)) * float(safety_factor)
    caps = tuple((bucket_pow2(int(np.ceil(c * ratio))),
                  bucket_cap(int(np.ceil(o * ratio))))
                 for c, o in plan.caps)
    filter_caps = tuple(bucket_cap(int(np.ceil(f * ratio)))
                        for f in plan.filter_caps)
    return caps, filter_caps


# ---------------------------------------------------------------------------
# Capacity policies — what the shared level loop asks per level


class HostCapPolicy:
    """Inspection-execution with per-level host sync; records the plan.

    ``extend_caps`` runs the cheap degree-sum bound, then the exact
    inspection jit — the paper's inspection-execution at the host/XLA
    boundary.  Candidate capacities bucket to powers of two (the bound is
    loose and varies); output capacities are planned *post-filter* at
    tight survivor scale (:func:`bucket_cap`) from the exact survivor
    count the inspection observed.  Every decision is appended to
    ``caps`` / ``filter_caps`` so a finished run doubles as a planning
    pass.
    """

    traceable = False

    def __init__(self):
        self.caps: list[tuple[int, int]] = []
        self.filter_caps: list[int] = []

    def extend_caps(self, pipe):
        cand_cap = bucket_pow2(int(pipe.bound()))
        _, n_next = pipe.inspect(cand_cap)
        out_cap = bucket_cap(int(n_next))
        self.caps.append((cand_cap, out_cap))
        return cand_cap, out_cap

    def note_extend(self, n_cand, n_surv, cand_cap: int,
                    out_cap: int) -> None:
        # out_cap was planned from the inspection pass's exact survivor
        # count; more survivors coming back from extend_pruned means the
        # inspect and extend predicates disagree (app hook drift between
        # to_add/to_add_bits/to_add_kernel).  With tight survivor-scale
        # caps that would silently truncate results — fail loudly instead.
        if int(n_surv) > out_cap or int(n_cand) > cand_cap:
            raise RuntimeError(
                f"extend produced {int(n_surv)} survivors / "
                f"{int(n_cand)} candidates for planned caps "
                f"({cand_cap}, {out_cap}): the app's toAdd hook variants "
                f"disagree between inspection and extension")

    def filter_cap(self, n_keep) -> int:
        cap = bucket_cap(int(n_keep))
        self.filter_caps.append(cap)
        return cap

    def overflow(self):
        return False                      # exact capacities never overflow


class PlanCapPolicy:
    """Replay a :class:`MiningPlan` with no host sync (jit-traceable).

    The fused ``extend_pruned`` op returns the true candidate/survivor
    counts with its result, so plan replay runs **no** inspection pass at
    all — the loop body is one enumeration per level instead of two.
    Capacities overflowing truncate the worklist; the accumulated
    ``overflow`` flag (fed by :meth:`note_extend`) reports it so the
    executor (or the bounded-mode caller) can re-plan and retry — the
    bounded-mode contract.
    """

    traceable = True

    def __init__(self, plan: MiningPlan):
        self.plan = plan
        self._li = 0
        self._fi = 0
        self._ovf = jnp.zeros((), bool)

    def extend_caps(self, pipe):
        cand_cap, out_cap = self.plan.caps[self._li]
        self._li += 1
        return cand_cap, out_cap

    def note_extend(self, n_cand, n_surv, cand_cap: int,
                    out_cap: int) -> None:
        self._ovf = (self._ovf | (n_cand > cand_cap)
                     | (n_surv > out_cap))

    def filter_cap(self, n_keep) -> int:
        cap = self.plan.filter_caps[self._fi]
        self._fi += 1
        self._ovf = self._ovf | (n_keep > cap)
        return cap

    def overflow(self):
        return self._ovf


# ---------------------------------------------------------------------------
# Sampled capacity estimation — zero-cold-start planning
#
# The inspection pass is exact but pays per-level jit compiles and host
# syncs over the FULL worklist before the executor ever runs.  The
# estimator instead mines a small *sample* of the level-0 worklist in ONE
# jitted probe with fixed sample-scale capacities: the probe runs the
# same pipeline adapters and app hooks (to_extend / to_add[_bits|_kernel]
# / reduce / filter, reference backend) as the real run and reports the
# true per-level candidate/survivor/keep counts the fused ops already
# compute.  The host then scales those counts by the sampling fraction
# (correcting for any probe-capacity truncation, which the true counts
# make observable) times a safety factor and buckets them — an estimated
# plan after one small compile instead of four per level.  Semantics are
# exact by construction; only the *scale* is statistical, and the
# executor's grow-and-retry backstop turns an under-estimate into one
# extra compile instead of a wrong answer.

# Fixed probe capacities: every level of the sampled run gets the same
# static buffers, so the probe is one XLA program regardless of how the
# sample frontier grows.  Overflowing them only *truncates the sample*
# (the reported true counts let the host correct the scale); it never
# affects the real run.
SAMPLE_CAND_CAP = 1 << 15
SAMPLE_OUT_CAP = 4096


class _ProbePolicy(PlanCapPolicy):
    """Replay fixed probe capacities; collect the true traced counts."""

    def __init__(self, plan: MiningPlan):
        super().__init__(plan)
        self.n_cand: list = []
        self.n_surv: list = []
        self.n_keep: list = []

    def note_extend(self, n_cand, n_surv, cand_cap: int,
                    out_cap: int) -> None:
        self.n_cand.append(n_cand)
        self.n_surv.append(n_surv)
        super().note_extend(n_cand, n_surv, cand_cap, out_cap)

    def filter_cap(self, n_keep) -> int:
        self.n_keep.append(n_keep)
        return super().filter_cap(n_keep)


def _minimal_plan(app) -> tuple[tuple[tuple[int, int], ...],
                                tuple[int, ...]]:
    """Floor-capacity plan for degenerate inputs (empty worklist)."""
    n_levels = max(app.max_size - 2, 0)
    caps = ((bucket_pow2(0), bucket_cap(0)),) * n_levels
    filter_caps = ((bucket_cap(0),) * (n_levels + 1)
                   if app.kind == "edge" and app.needs_filter else ())
    return caps, filter_caps


def estimate_plan(miner, cap0: int, sample_size: int = 256,
                  safety_factor: float = 2.0, seed: int = 0
                  ) -> tuple[tuple[tuple[int, int], ...],
                             tuple[int, ...]]:
    """Estimate a capacity plan from a sampled worklist (no inspection).

    Draws ``sample_size`` level-0 embeddings, probes them through the
    app's real pipeline (one jit, fixed sample-scale capacities,
    reference backend) and returns ``(caps, filter_caps)`` — the probe's
    true per-level counts scaled by the sampling fraction times
    ``safety_factor``, bucketed like the exact planner's.

    FSM support filtering runs on the sample with ``min_support``
    rescaled by the sampling fraction — sample MNI supports are roughly
    proportional to the fraction of the worklist seen, so the rescaled
    threshold prunes the sample frontier about as hard as the real
    threshold prunes the real one.

    Exactness is NOT this function's contract: the estimate seeds a
    :class:`MiningPlan` (``source="estimated"``) and the executor's
    overflow-grow-and-retry loop guarantees correct results even when
    every level is under-estimated.
    """
    with _T.span("plan.estimate", cat="plan", sample_size=sample_size):
        return _estimate_plan(miner, cap0, sample_size, safety_factor,
                              seed)


def _estimate_plan(miner, cap0, sample_size, safety_factor, seed
                   ) -> tuple[tuple[tuple[int, int], ...],
                              tuple[int, ...]]:
    from repro.core import engine as E
    from repro.core.phases import get_backend
    from repro.graph.sampler import (sample_worklist,
                                     sample_worklist_stratified)

    app, ctx = miner.app, miner.ctx
    rng = np.random.default_rng(seed)
    if app.kind == "edge":
        m = int(ctx.n_uedges)
    else:
        src, dst = miner.init_edges()
        m = int(src.shape[0])
    if m == 0 or app.max_size <= 2:
        return _minimal_plan(app)

    # sorted sample: FSM's canonical edge-growth test compares edge uids,
    # and a sorted subset preserves every uid comparison the full
    # worklist would make.  Relabeled vertex miners sample stratified
    # over contiguous index bands — post-relabel index order is degree
    # order, so the bands are degree strata and the hub head can't be
    # missed (a uniform draw over a skewed worklist can).
    if app.kind != "edge" and getattr(miner, "relabeling", None) is not None:
        idx = sample_worklist_stratified(m, sample_size, rng)
    else:
        idx = sample_worklist(m, sample_size, rng,
                              sort=(app.kind == "edge"))
    n_sample = len(idx)
    samp_app = app
    if app.kind == "edge" and app.needs_filter and n_sample < m:
        samp_app = dataclasses.replace(
            app, min_support=max(1, int(round(app.min_support
                                              * n_sample / m))))
    n_levels = app.max_size - 2
    needs_filter = app.kind == "edge" and app.needs_filter
    probe_plan = MiningPlan(
        kind=app.kind,
        caps=((SAMPLE_CAND_CAP, SAMPLE_OUT_CAP),) * n_levels,
        filter_caps=((SAMPLE_OUT_CAP,) * (n_levels + 1)
                     if needs_filter else ()))
    reference = get_backend("reference")

    def ops_for(c):
        return E._PhaseOps(c, samp_app, reference,
                           fuse_filter=miner.fuse_filter,
                           materialize_fn=miner._materialize)

    if app.kind == "edge":
        def probe(c, s, d, e, n):
            pipe = E._EdgePipeline(ops_for(c), src=s, dst=d, eid=e, n=n)
            policy = _ProbePolicy(probe_plan)
            E.run_level_loop(pipe, policy)
            return (tuple(policy.n_cand), tuple(policy.n_surv),
                    tuple(policy.n_keep))
        args = (ctx.usrc[jnp.asarray(idx)], ctx.udst[jnp.asarray(idx)],
                jnp.asarray(idx, jnp.int32), jnp.int32(n_sample))
    else:
        def probe(c, s, d, n):
            pipe = E._VertexPipeline(ops_for(c), s, d, n)
            policy = _ProbePolicy(probe_plan)
            E.run_level_loop(pipe, policy)
            return (tuple(policy.n_cand), tuple(policy.n_surv),
                    tuple(policy.n_keep))
        args = (jnp.asarray(np.asarray(src)[idx]),
                jnp.asarray(np.asarray(dst)[idx]), jnp.int32(n_sample))
    n_cand, n_surv, n_keep = jax.jit(probe)(ctx, *args)
    n_cand = [int(x) for x in n_cand]
    n_surv = [int(x) for x in n_surv]
    n_keep = [int(x) for x in n_keep]

    # Host-side scale arithmetic.  scale = (estimated true frontier) /
    # (sample frontier); probe truncation shrinks the sample frontier but
    # the true counts are reported pre-truncation, so every truncation
    # folds into the scale instead of biasing the estimate downward.
    caps: list[tuple[int, int]] = []
    fcaps: list[int] = []
    scale = min(m, int(cap0)) / n_sample

    def est(n: float) -> int:
        return int(np.ceil(n * scale * safety_factor))

    ki = 0
    if needs_filter:                    # pre-loop filter ("level 1")
        k = n_keep[ki]
        ki += 1
        fcaps.append(bucket_cap(est(k)))
        kept = min(k, SAMPLE_OUT_CAP)
        scale = (k * scale) / kept if kept else scale
    for li in range(n_levels):
        c, s = n_cand[li], n_surv[li]
        c_seen = min(c, SAMPLE_CAND_CAP)
        # survivors were counted among the first c_seen candidates only
        s_corr = s * (c / c_seen) if c_seen else 0.0
        caps.append((bucket_pow2(est(c)), bucket_cap(est(s_corr))))
        kept = min(s, SAMPLE_OUT_CAP)
        scale = (s_corr * scale) / kept if kept else scale
        if needs_filter:
            k = n_keep[ki]
            ki += 1
            fcaps.append(bucket_cap(est(k)))
            kkept = min(k, SAMPLE_OUT_CAP)
            scale = (k * scale) / kkept if kkept else scale
    return tuple(caps), tuple(fcaps)


# ---------------------------------------------------------------------------
# The executor


def plan_program(plan: MiningPlan, app, backend, fuse_filter: bool = True,
                 materialize_fn=None):
    """The jitted whole-run program that replays ``plan``.

    Vertex plans: ``(ctx, src, dst, n_valid) -> (count, p_map,
    overflowed)``; edge plans: ``(ctx, src, dst, eid, n_valid) -> (codes,
    supports, overflowed)``.  The graph context is the first argument —
    an input buffer of the executable, never a constant folded into it.
    """
    from repro.core import engine as E

    def ops_for(ctx):
        return E._PhaseOps(ctx, app, backend, fuse_filter=fuse_filter,
                           materialize_fn=materialize_fn)

    if plan.kind == "vertex":
        def fn(ctx, src, dst, n_valid):
            pipe = E._VertexPipeline(ops_for(ctx), src, dst, n_valid)
            policy = PlanCapPolicy(plan)
            E.run_level_loop(pipe, policy)
            return pipe.bounded_result(policy)
    else:
        def fn(ctx, src, dst, eid, n_valid):
            pipe = E._EdgePipeline(ops_for(ctx), src=src, dst=dst, eid=eid,
                                   n=n_valid)
            policy = PlanCapPolicy(plan)
            E.run_level_loop(pipe, policy)
            return pipe.bounded_result(policy)
    return jax.jit(fn)


class MiningExecutor:
    """One compiled mining run, reused across blocks / runs / queries.

    Holds the plan for one (graph, app, backend, cap0) signature and a
    jit cache keyed by the plan's capacities: every edge block of a run —
    and every repeated run — goes through the same XLA executable with a
    single device sync, no per-level host inspection.  ``execute`` /
    ``execute_edge`` retry with a grown plan when the overflow flag comes
    back set; that re-plan loop is the only host-side control flow left.
    """

    def __init__(self, miner, cap0: int, plan: Optional[MiningPlan] = None,
                 cache: Optional[PlanCache] = None, max_retries: int = 6):
        self.miner = miner
        self.cap0 = int(cap0)
        self.cache = cache
        self.max_retries = max_retries
        self.kind = miner.app.kind
        compaction = getattr(miner.backend, "compaction", "xla-scan")
        self.signature = plan_signature(miner.graph_digest(), miner.app,
                                        miner.backend.name, self.cap0,
                                        miner.fuse_filter, compaction)
        self.app_key = plan_app_key(miner.app, miner.backend.name,
                                    miner.fuse_filter, compaction)
        self.transfer_key = plan_transfer_key(miner.app, miner.fuse_filter)
        self._plan = plan
        if self._plan is None and cache is not None:
            self._plan = cache.get(self.signature)
            if self._plan is not None:
                self._note_plan_event("cache_hit")
        self._fns: dict = {}
        self.n_compiles = 0
        self.n_executions = 0
        self.n_replans = 0

    # -- plan management ----------------------------------------------------

    @property
    def plan(self) -> Optional[MiningPlan]:
        return self._plan

    @property
    def has_plan(self) -> bool:
        return self._plan is not None

    def _note_plan_event(self, event: str, **extra) -> None:
        """Record plan provenance: a counter plus a trace instant."""
        _M.inc("plan." + event, kind=self.kind)
        if _T.on:
            args = {"signature": self.signature, "cap0": self.cap0}
            if self._plan is not None:
                args["caps"] = str(self._plan.caps)
                args["source"] = self._plan.source
            args.update(extra)
            _T.instant("plan." + event, cat="plan", **args)

    def attach_cache(self, cache: Optional[PlanCache]) -> None:
        if cache is None or (self.cache is not None
                             and self.cache.directory == cache.directory):
            return                    # same cache: plan already persisted
        self.cache = cache
        if self._plan is None:
            self._plan = cache.get(self.signature)
            if self._plan is not None:
                self._note_plan_event("cache_hit")
        elif self._plan.signature == self.signature:
            cache.put(self._plan)

    def adopt_plan(self, caps, filter_caps=(), source: str = "inspect"
                   ) -> None:
        """Install a freshly recorded plan (inspection pass, sampled
        estimate, or profile transfer — ``source`` records which).

        A plan already in place wins — plan once, execute many.
        """
        if self._plan is not None:
            return
        profile, n_edges = self.miner.profile_sketch()
        self._plan = MiningPlan(kind=self.kind, caps=tuple(caps),
                                filter_caps=tuple(filter_caps),
                                cap0=self.cap0, signature=self.signature,
                                source=source, app_key=self.app_key,
                                profile=profile, n_edges=n_edges,
                                transfer_key=self.transfer_key)
        self._note_plan_event(source)
        if self.cache is not None:
            self.cache.put(self._plan)

    def _grow(self) -> None:
        self.n_replans += 1
        # the superseded capacities never run again: dropping their jit
        # entry releases the compiled executable (otherwise every grow
        # pins another whole-pipeline XLA program for the process
        # lifetime)
        self._fns.pop((self._plan.caps, self._plan.filter_caps), None)
        self._plan = self._plan.grown()
        self._note_plan_event("grown", replans=self.n_replans)
        if self.cache is not None:
            self.cache.put(self._plan)

    # -- compilation --------------------------------------------------------

    def _fn(self):
        key = (self._plan.caps, self._plan.filter_caps)
        fn = self._fns.get(key)
        if fn is None:
            fn = self._build(self._plan)
            self._fns[key] = fn
            self.n_compiles += 1
        return fn

    def _build(self, plan: MiningPlan):
        miner = self.miner
        program = plan_program(plan, miner.app, miner.backend,
                               fuse_filter=miner.fuse_filter,
                               materialize_fn=miner._materialize)
        return functools.partial(program, miner.ctx)

    # -- execution ----------------------------------------------------------

    def _run_with_retry(self, *args):
        """Call the compiled plan; on overflow grow it and recompile.

        Timing here is exact without extra syncs: ``bool(ovf)``
        data-depends on the whole pipeline, so each iteration's wall
        time covers the full device execution.  A call whose
        ``(caps, filter_caps)`` key is not in the jit cache yet pays
        tracing + XLA compilation; that first call is recorded as
        ``executor.compile_s``, later ones as ``executor.replay_s``.
        """
        for attempt in range(self.max_retries + 1):
            fresh = (self._plan.caps,
                     self._plan.filter_caps) not in self._fns
            what = "executor.compile" if fresh else "executor.replay"
            t0 = time.perf_counter()
            with _T.span(what, cat="executor", kind=self.kind,
                         attempt=attempt) as sp:
                *out, ovf = self._fn()(*args)
                self.n_executions += 1
                overflowed = bool(ovf)    # forces the device sync
                sp.set(overflow=overflowed)
            dt = time.perf_counter() - t0
            _M.inc(what + "_s", dt, kind=self.kind)
            _M.inc("executor.compiles" if fresh else "executor.replays",
                   kind=self.kind)
            if not overflowed:
                return out
            if attempt == self.max_retries:
                break                 # don't grow/persist a plan never run
            self._grow()
        raise RuntimeError(
            f"mining plan {self.signature} still overflows after "
            f"{self.max_retries + 1} attempts")

    def execute(self, src, dst, n_valid) -> tuple[int, np.ndarray]:
        """Vertex-induced block: one compiled call -> (count, p_map)."""
        assert self.kind == "vertex"
        cnt, p_map = self._run_with_retry(src, dst, jnp.int32(n_valid))
        return int(cnt), np.asarray(p_map)

    def execute_edge(self, src, dst, eid, n_valid
                     ) -> tuple[np.ndarray, np.ndarray]:
        """Edge-induced (FSM) run: one call -> (codes, supports)."""
        assert self.kind == "edge"
        codes, supports = self._run_with_retry(src, dst, eid,
                                               jnp.int32(n_valid))
        return np.asarray(codes), np.asarray(supports)
