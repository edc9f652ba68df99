"""Phase-backend interface: the paper's extend-reduce-filter as pluggable ops.

Sandslash-style two-level split: the *engine* (repro.core.engine) owns the
high-level per-level loop (inspection, capacity planning, checkpointing,
blocking, sharding); a :class:`PhaseBackend` owns the low-level set
operations that loop composes — candidate enumeration, ragged expansion,
compaction, pattern reduction.  Every architecture target (XLA reference,
fused Pallas kernels, future multi-GPU blocking / TPU tilings) is one
backend; the engine never calls an implementation module directly.

The op surface, grouped by phase:

  EXTEND   candidate_bound_{vertex,edge}  cheap degree-sum upper bound
           inspect_{vertex,edge}          exact (candidate, survivor) counts
           extend_{vertex,edge}           produce the next SoA level
           extend_pruned                  fused extend+filter+compact with
                                          candidate/survivor counts (the
                                          warm-path op: no separate
                                          inspection pass)
  REDUCE   reduce_count                   classify + count support
           reduce_domain                  FSM canonical codes + MNI support
           reduce_domain_sharded          same, collective (shard_map) MNI
  FILTER   filter_levels                  support-based compaction
  PRIMS    expand_ragged, compact_mask    the shared ragged building blocks

A backend may override any subset; the registry (repro.core.phases) hands
the engine a fully-assembled instance.  All ops must be jit-traceable with
static capacities (no host sync) so they compose with ``shard_map`` and the
bounded single-jit mining mode.
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from repro.core.api import GraphCtx, MiningApp
from repro.core.embedding_list import EmbeddingLevel


class PhaseBackend:
    """Abstract extend/reduce/filter op set.  Subclass and register."""

    name: str = "abstract"

    # -- capability metadata ----------------------------------------------
    # How the backend's extend_pruned resolves cross-tile survivor offsets,
    # and what grid-execution order that strategy assumes.  Part of the
    # plan identity (repro.core.plan.plan_app_key): plans captured under
    # one compaction contract must not replay under another.
    #
    #   compaction          "xla-scan"        host-side prefix-sum compact
    #                       "sequential-smem" in-kernel SMEM running offset
    #                                         carried tile-to-tile (legal
    #                                         only on a sequential grid)
    #                       "two-pass-scan"   per-tile counts -> host
    #                                         exclusive scan -> masked
    #                                         scatter at final offsets
    #                                         (zero cross-tile state; legal
    #                                         on concurrent grids)
    #   compaction_passes   kernel passes over the candidate range (0 for
    #                       pure-XLA backends)
    #   grid_contract       "any" | "sequential" | "concurrent" — the
    #                       weakest grid-ordering guarantee the backend's
    #                       kernels still work under
    compaction: str = "xla-scan"
    compaction_passes: int = 0
    grid_contract: str = "any"

    def check_device(self) -> None:
        """Raise, before anything is traced, if this backend cannot run
        on the current JAX backend.  Pure-XLA backends always can."""

    def capabilities(self, app: Optional[MiningApp] = None) -> dict:
        """Which ops actually run fused under this backend.

        With ``app`` given the report is per-app (a backend may fall back
        to XLA for hooks its kernels cannot express); without, it reports
        the backend's mechanisms.  Surfaced to users through
        ``MiningExecutor.plan_reports()``.
        """
        return {
            "backend": self.name,
            "compaction": self.compaction,
            "compaction_passes": self.compaction_passes,
            "grid_contract": self.grid_contract,
            "extend_vertex": "xla",
            "extend_pruned": "xla",
            "extend_edge": "xla",
        }

    # -- shared ragged primitives -----------------------------------------

    def expand_ragged(self, counts: jnp.ndarray, capacity: int):
        raise NotImplementedError

    def compact_mask(self, mask: jnp.ndarray, capacity: int):
        raise NotImplementedError

    # -- EXTEND: vertex-induced -------------------------------------------

    def candidate_bound_vertex(self, ctx: GraphCtx, app: MiningApp,
                               emb: jnp.ndarray, n_valid: jnp.ndarray,
                               state: Optional[jnp.ndarray] = None
                               ) -> jnp.ndarray:
        """Degree-sum bound; ``state`` feeds state-aware toExtend masks."""
        raise NotImplementedError

    def inspect_vertex(self, ctx: GraphCtx, app: MiningApp, emb: jnp.ndarray,
                       n_valid: jnp.ndarray, state: Optional[jnp.ndarray],
                       cand_cap: int):
        raise NotImplementedError

    def extend_vertex(self, ctx: GraphCtx, app: MiningApp, emb: jnp.ndarray,
                      n_valid: jnp.ndarray, state: Optional[jnp.ndarray],
                      cand_cap: int, out_cap: int, fuse_filter: bool = True):
        raise NotImplementedError

    def extend_pruned(self, ctx: GraphCtx, app: MiningApp, emb: jnp.ndarray,
                      n_valid: jnp.ndarray, state: Optional[jnp.ndarray],
                      cand_cap: int, out_cap: int, fuse_filter: bool = True):
        """Fused extend + eager toAdd filter + stream compaction.

        Returns ``(level, new_emb, n_candidates, n_fill)``; the survivor
        count is ``level.n``, and ``n_fill`` the entries the reference
        backend's forward fill scattered (0 where a backend fills none).
        Because the true counts come back with the result, a plan-replay
        caller needs **no** separate inspection pass — the
        overflow check reads them directly (``n_candidates > cand_cap`` or
        ``level.n > out_cap``).  Backends fuse as deeply as they can: the
        reference backend evaluates the resolved elementwise predicate and
        prefix-sum-compacts in one XLA fusion; the Pallas backend prunes
        and compacts inside the extend kernel so dead candidates never
        reach HBM.
        """
        raise NotImplementedError

    # -- EXTEND: edge-induced ---------------------------------------------

    def candidate_bound_edge(self, ctx, app, v0, vid, his, n_valid):
        raise NotImplementedError

    def inspect_edge(self, ctx, app, v0, vid, his, eid, n_valid,
                     cand_cap: int):
        raise NotImplementedError

    def extend_edge(self, ctx, app, v0, vid, his, eid, n_valid,
                    cand_cap: int, out_cap: int):
        """Produce the next edge-induced level.

        Returns ``(level, n_candidates)`` — same fused-counts contract as
        :meth:`extend_pruned` (survivors are ``level.n``).
        """
        raise NotImplementedError

    # -- REDUCE / FILTER ---------------------------------------------------

    def reduce_count(self, ctx: GraphCtx, app: MiningApp, emb: jnp.ndarray,
                     n_valid: jnp.ndarray, state: Optional[jnp.ndarray]):
        raise NotImplementedError

    def reduce_domain(self, ctx: GraphCtx, app: MiningApp,
                      levels: list[EmbeddingLevel]):
        raise NotImplementedError

    def reduce_domain_sharded(self, ctx: GraphCtx, app: MiningApp,
                              levels: list[EmbeddingLevel],
                              axis_names: tuple[str, ...]):
        """FSM reduce under shard_map: exact global MNI via collectives."""
        raise NotImplementedError

    def filter_levels(self, levels: list[EmbeddingLevel], keep: jnp.ndarray,
                      out_cap: int) -> list[EmbeddingLevel]:
        raise NotImplementedError

    def __repr__(self) -> str:
        return f"<PhaseBackend {self.name}>"
