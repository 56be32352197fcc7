"""The traversal engine: one entry point for every BFS in the repo.

    from repro.engine import Engine
    engine = Engine(graph)                      # wraps a GraphSession
    result = engine.bfs([r0, r1, ...])          # batch or single root
    result.validate(graph)

Three backends, auto-selected from partition count and available devices
(explicit `backend=` always wins):

* ``fused``   — single-partition path. A batch of B roots runs the
  batch-native cohort model (`repro.core.bfs.init_batch`/`make_batch_step`
  on the shared `LevelDriver`): per level the batch splits into a top-down
  cohort, a bottom-up cohort, and a finished cohort, and each direction
  pass runs ONCE over its masked cohort — with per-level streaming and
  cancellation. Unbatched (Graph500) mode runs the SAME cohort step at
  batch bucket 1, one root at a time with per-root wall timing — there is
  exactly one step implementation, which is what lets the heterogeneous
  hub/tail split (`BFSConfig.hub_split`) specialize scalar and batched
  traversal at once.
* ``sharded`` — the paper's partitioned BSP search under `shard_map`
  (`repro.core.hybrid_bfs.hybrid_search_program`), pipelined over roots: all
  queries are dispatched asynchronously against one cached executable and
  the host blocks once at the end.
* ``stepper`` — instrumented per-level python loop (single-partition or
  BSP) returning per-level direction/frontier/timing stats; the benchmark
  backend.

Every executable is compiled at most once per (config, backend, batch
shape) on the owning `GraphSession` — repeated queries are pure cache hits
(see `GraphSession.trace_count`).
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Callable, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bfs as B
from repro.core.bfs import BFSConfig
from repro.core.graph import Graph
from repro.core.hybrid_bfs import (HybridConfig, HybridShapes,
                                   finalize_hybrid, hybrid_search_program,
                                   make_hybrid_stepper, make_root_mapper)
from repro.engine.level_loop import (BSPStepBackend, CohortBatchBackend,
                                     LevelDriver, QueryCancelled,
                                     QueryControl, QueryDeadlineExceeded,
                                     SingleStepBackend)
from repro.engine.result import TraversalResult, edges_traversed_from_levels
from repro.engine.session import GraphSession
from repro.runtime.faults import fault_point

BACKENDS = ("fused", "sharded", "stepper")

# Auto-selection: below this many directed edges a single fused program beats
# the BSP machinery even when more devices exist (exchange overhead dominates).
AUTO_SHARD_MIN_EDGES = 1 << 19
# Cap auto-selected partition counts at the chips of one host (a v5e host
# has 4 or 8).
AUTO_MAX_PARTS = 8

RootsLike = Union[int, np.integer, Sequence[int], np.ndarray]

# Batched fused queries pad to the next power of two, floored at this bucket,
# so ragged batch sizes share executables instead of compiling one each
# (batch 1 stays 1: the Graph500 per-root measurement mode).
MIN_BATCH_BUCKET = 8


def _bucket_batch(batch: int) -> int:
    """Executable batch bucket: 1, or the next power of two >= 8."""
    if batch <= 1:
        return 1
    return max(MIN_BATCH_BUCKET, 1 << (batch - 1).bit_length())


@dataclasses.dataclass(frozen=True)
class QueryPlan:
    """Fully resolved query parameters: the coalescing/compatibility key.

    Two queries with equal plans hit the same compiled executables, so a
    server may merge their root batches into one dispatch (`BFSServer` does
    exactly that, grouping queued queries by plan). Hashable because
    `HybridConfig`/`BFSConfig` are frozen dataclasses.
    """
    backend: str              # resolved: "fused" | "sharded" | "stepper"
    n_parts: int
    hcfg: HybridConfig
    strategy: str
    hub_edge_fraction: float


def _tree_depth(level: np.ndarray) -> np.ndarray:
    """Deepest discovered BFS level per root (0 when only the root)."""
    return np.where(level >= 0, level, 0).max(axis=1).astype(np.int32)


class Engine:
    """Facade over a `GraphSession`: compile-once, query-many traversal."""

    def __init__(self, graph_or_session: Union[Graph, GraphSession], **session_kw):
        if isinstance(graph_or_session, GraphSession):
            if session_kw:
                raise ValueError("session kwargs only apply when passing a Graph")
            self.session = graph_or_session
        else:
            self.session = GraphSession(graph_or_session, **session_kw)

    @property
    def graph(self) -> Graph:
        return self.session.graph

    # ----------------------------------------------------------- selection --

    def _auto_parts(self) -> int:
        n_dev = len(jax.devices())
        if n_dev == 1 or self.graph.num_directed_edges < AUTO_SHARD_MIN_EDGES:
            return 1
        return min(n_dev, AUTO_MAX_PARTS)

    def _resolve(self, backend: str, n_parts: Optional[int]):
        if backend not in BACKENDS + ("auto",):
            raise ValueError(f"unknown backend {backend!r}; "
                             f"want one of {BACKENDS + ('auto',)}")
        if n_parts is None:
            n_parts = 1 if backend == "fused" else self._auto_parts()
        if backend == "auto":
            backend = "fused" if n_parts == 1 else "sharded"
        if backend == "fused" and n_parts != 1:
            raise ValueError("fused backend is single-partition; "
                             f"got n_parts={n_parts}")
        if backend == "sharded" and n_parts < 2:
            raise ValueError("sharded backend needs n_parts >= 2 "
                             "(use backend='fused' for one partition)")
        return backend, n_parts

    @staticmethod
    def _normalize_cfg(cfg) -> HybridConfig:
        if cfg is None:
            return HybridConfig()
        if isinstance(cfg, BFSConfig):
            return HybridConfig(bfs=cfg)
        if isinstance(cfg, HybridConfig):
            return cfg
        raise TypeError(f"cfg must be BFSConfig or HybridConfig, got {type(cfg)}")

    def _normalize_roots(self, roots: RootsLike) -> np.ndarray:
        arr = np.atleast_1d(np.asarray(roots, dtype=np.int64))
        if arr.ndim != 1:
            raise ValueError(f"roots must be a scalar or 1-D, got {arr.shape}")
        v = self.graph.num_vertices
        if arr.size:
            if v == 0:
                raise ValueError("cannot run BFS on an empty (0-vertex) graph")
            if arr.min() < 0 or arr.max() >= v:
                raise ValueError(f"roots out of range [0, {v})")
        return arr

    # --------------------------------------------------------------- query --

    def plan(self, cfg=None, *, backend: str = "auto",
             n_parts: Optional[int] = None, strategy: Optional[str] = None,
             hub_edge_fraction: Optional[float] = None) -> QueryPlan:
        """Resolve query knobs into a canonical, hashable `QueryPlan`.

        The plan is the batch-coalescing hook: queries with equal plans
        share every compiled executable, so a server can concatenate their
        roots and run them as one dispatch (see `BFSServer`). Canonicalizes
        session-default partition knobs so "default" and an explicitly
        passed default coincide.
        """
        hcfg = self._normalize_cfg(cfg)
        backend, n_parts = self._resolve(backend, n_parts)
        strategy = strategy or self.session.default_strategy
        if hub_edge_fraction is None:
            hub_edge_fraction = self.session.default_hub_edge_fraction
        return QueryPlan(backend, n_parts, hcfg, strategy, hub_edge_fraction)

    def bfs(self, roots: RootsLike, cfg=None, *, backend: str = "auto",
            n_parts: Optional[int] = None, strategy: Optional[str] = None,
            hub_edge_fraction: Optional[float] = None, batched: bool = True,
            validate: bool = False, on_level: Optional[Callable] = None,
            control: Optional[QueryControl] = None) -> TraversalResult:
        """Run BFS from one root or a batch of roots.

        Args:
          roots: int or 1-D int array of original vertex ids.
          cfg: `BFSConfig` (heuristic/chunk knobs) or a full `HybridConfig`
            (adds exchange/coordinator knobs for the sharded path).
          backend: "auto" | "fused" | "sharded" | "stepper".
          n_parts: partition count; None = auto from devices and graph size.
          strategy / hub_edge_fraction: partitioning knobs (sharded/stepper
            multi-partition paths); session defaults otherwise.
          batched: True executes the batch as one fused program (fused) or
            one pipelined async dispatch train (sharded) — maximum
            throughput, per-root seconds are an even split. False runs and
            times roots one at a time against the same cached executable —
            the Graph500 measurement mode.
          validate: check every parent tree against the python oracle.
          on_level: streaming callback invoked as
            `on_level(batch_index, stats_row)` the moment each level's stats
            land on the host, before the search finishes (the server's
            result-streaming hook). Stepper backend: one row per root per
            level (`batch_index` = root position). Batched fused (cohort)
            backend: one batch-level row per level, `batch_index == -1`.
          control: cooperative `QueryControl` (cancel event + absolute
            deadline). Checked before dispatch on every backend, between
            roots on the per-root paths, and once per level on the
            driver-backed paths — the stepper backend and the batched
            fused (cohort) path (the `LevelDriver` hook); aborts raise the
            typed `QueryCancelled` / `QueryDeadlineExceeded` carrying
            partial per-level stats.

        Returns a `TraversalResult`; compile time is never inside the timed
        region (the first query per (config, backend, batch shape) warms the
        executable cache).
        """
        qp = self.plan(cfg, backend=backend, n_parts=n_parts,
                       strategy=strategy, hub_edge_fraction=hub_edge_fraction)
        return self.bfs_plan(roots, qp, batched=batched, validate=validate,
                             on_level=on_level, control=control)

    def bfs_plan(self, roots: RootsLike, plan: QueryPlan, *,
                 batched: bool = True, validate: bool = False,
                 on_level: Optional[Callable] = None,
                 control: Optional[QueryControl] = None) -> TraversalResult:
        """Run a query whose knobs were already resolved by `plan()`."""
        backend, n_parts = plan.backend, plan.n_parts
        hcfg = plan.hcfg
        if on_level is not None and not (
                backend == "stepper" or (backend == "fused" and batched)):
            raise ValueError(
                "on_level streaming needs backend='stepper' or the batched "
                f"fused path, got {backend!r} (batched={batched})")
        if control is not None:
            control.check()
        # Chaos hook: simulated device/memory pressure at query entry
        # (non-transient `DevicePressure` — the degradation chain, not the
        # retry loop, is the recovery path).
        fault_point("device", backend=backend)
        roots_arr = self._normalize_roots(roots)
        if roots_arr.size == 0:
            v = self.graph.num_vertices
            return TraversalResult(
                roots=roots_arr, parent=np.empty((0, v), np.int32),
                level=np.empty((0, v), np.int32),
                num_levels=np.empty((0,), np.int32), seconds=0.0,
                per_root_seconds=np.empty((0,)), backend=backend,
                n_parts=n_parts,
                edges_undirected=self.graph.num_undirected_edges,
                edges_traversed=np.empty((0,), np.int64))

        if backend == "fused":
            res = self._bfs_fused(roots_arr, hcfg, batched, control, on_level)
        elif backend == "sharded":
            res = self._bfs_sharded(roots_arr, hcfg, n_parts, plan.strategy,
                                    plan.hub_edge_fraction, batched, control)
        else:
            res = self._bfs_stepper(roots_arr, hcfg, n_parts, plan.strategy,
                                    plan.hub_edge_fraction, on_level, control)
        res.edges_traversed = edges_traversed_from_levels(self.graph.degrees,
                                                          res.level)
        if validate:
            res.validate(self.graph)
        return res

    # --------------------------------------------------------- fused path --
    #
    # Batched fused queries run the batch-native cohort model: SoA [B, V]
    # state on a `LevelDriver` over `CohortBatchBackend`, one direction
    # kernel per cohort per level (never both directions per lane — the
    # old vmap-of-whole-search lowered its per-level `lax.cond` to a select
    # that executed both), finished and pad lanes out of every cohort, and
    # the driver's per-level streaming/cancellation hooks for free.
    # Unbatched (Graph500) mode is the SAME machinery at bucket 1: one
    # cohort step implementation serves scalar and batched traversal, so a
    # step specialization (the hub/tail split) lands everywhere at once.

    def _cohort_backend(self, bcfg: BFSConfig,
                        bucket: int) -> CohortBatchBackend:
        """Cohort driver backend for a batch bucket, executables cached.

        Five executables per (config, bucket): init, the three step
        variants (td / bu / mixed — the host dispatches whichever matches
        each level's cohort occupancy), and the sync payload; a forced
        single-direction heuristic only compiles its one reachable
        variant. The key holds the *bucket*: ragged batches round up to
        `_bucket_batch` and pad their roots with inactive lanes, so e.g.
        batches of 3/5/7 all share one size-8 executable set
        (`trace_count` proves it).
        """
        graph = self.session.cohort_graph(bcfg)
        init = self.session.executable(
            ("cohort", bcfg, bucket, "init"),
            lambda: lambda g, roots, active: B.init_batch(g.dg, bcfg, roots,
                                                          active))
        steps = {
            var: functools.partial(self.session.executable(
                ("cohort", bcfg, bucket, var),
                lambda v=var: B.make_batch_step(bcfg, v)), graph)
            for var in B.reachable_variants(bcfg)
        }
        scalars = self.session.executable(("cohort", bcfg, bucket, "scalars"),
                                          lambda: B.batch_scalars)
        return CohortBatchBackend(functools.partial(init, graph), steps,
                                  scalars, graph.dg.num_vertices, bucket)

    def _bfs_fused(self, roots_arr, hcfg, batched, control=None,
                   on_level=None) -> TraversalResult:
        e_und = self.graph.num_undirected_edges
        if batched:
            b = len(roots_arr)
            bucket = _bucket_batch(b)
            backend = self._cohort_backend(hcfg.bfs, bucket)
            # How the driver's chaos hooks describe this dispatch — the
            # handle that lets schedules target e.g. [kernels=pallas] or
            # [mode=batch] and leave the degraded paths clear.
            backend.fault_ctx = dict(
                mode="batch",
                kernels="pallas" if B.kernels_enabled(hcfg.bfs) else "xla")
            # Pad to the bucket with a repeat of the first root; pad lanes
            # start INACTIVE (masked out of every cohort at level 0), so
            # padding costs no traversal work — they are placeholders for
            # the executable's batch shape, not extra queries.
            padded = np.full(bucket, roots_arr[0], dtype=np.int64)
            padded[:b] = roots_arr
            dev_roots = jnp.asarray(padded, jnp.int32)
            active0 = jnp.asarray(np.arange(bucket) < b)
            self.session.warm(("cohort_warm", hcfg.bfs, bucket),
                              lambda: backend.warm((dev_roots, active0)))
            if control is not None:
                control.check()      # the warm-up may outlive a deadline
            driver = LevelDriver(backend)
            cb = (lambda row: on_level(-1, row)) if on_level else None
            t0 = time.perf_counter()
            try:
                parent, level, rows, _timings = driver.run(
                    (dev_roots, active0), cb, control)
            except (QueryCancelled, QueryDeadlineExceeded) as e:
                # Batch-level rows -> the engine's per-root convention (one
                # entry describing the whole merged batch).
                e.per_level_stats = [e.per_level_stats]
                raise
            dt = time.perf_counter() - t0
            parent, level = parent[:b], level[:b]
            per_root = np.full(b, dt / b)
            return TraversalResult(roots_arr, parent, level,
                                   _tree_depth(level), dt, per_root,
                                   "fused", 1, e_und,
                                   batch_level_stats=rows)
        # Graph500 mode: one root at a time through the B=1 cohort — the
        # same five executables as a size-1 batch, timed per root. The
        # driver's host loop replaces the old whole-search `lax.while_loop`
        # program; level dispatch stays one executable call per level.
        kernels = "pallas" if B.kernels_enabled(hcfg.bfs) else "xla"
        backend = self._cohort_backend(hcfg.bfs, 1)
        backend.fault_ctx = dict(mode="scalar", kernels=kernels)
        active1 = jnp.ones(1, dtype=bool)
        self.session.warm(
            ("cohort_warm", hcfg.bfs, 1),
            lambda: backend.warm((jnp.asarray([roots_arr[0]], jnp.int32),
                                  active1)))
        parents, levels, per_root = [], [], []
        for r in roots_arr:
            if control is not None:
                control.check()
            fault_point("dispatch", mode="scalar", kernels=kernels)
            t0 = time.perf_counter()
            # repro-ok: TH001 timed dispatch: driver.run blocks on the final
            # sync, so per_root latency includes device completion.
            parent, level, _rows, _t = LevelDriver(backend).run(
                (jnp.asarray([r], jnp.int32), active1), None, control)
            per_root.append(time.perf_counter() - t0)
            parents.append(parent[0]); levels.append(level[0])
        per_root = np.asarray(per_root)
        level = np.stack(levels)
        return TraversalResult(roots_arr, np.stack(parents), level,
                               _tree_depth(level), float(per_root.sum()),
                               per_root, "fused", 1, e_und)

    # ------------------------------------------------------- sharded path --

    def _sharded_executable(self, hcfg, n_parts, strategy, hub):
        plan, pg = self.session.partitioned(n_parts, strategy, hub)
        pkey = (n_parts, strategy, hub)
        skey = ("sharded", hcfg) + pkey
        graph = self.session.hybrid_graph(n_parts, strategy, hub,
                                          hcfg.axis_name,
                                          B.kernels_enabled(hcfg.bfs))
        mesh = self.session.mesh_for(n_parts, hcfg.axis_name)
        # Sharded searches are compiled for this session's device mesh, so
        # the executable is only valid under its device binding: keep it
        # session-local and off the persistent store.
        fn = self.session.executable(
            skey, lambda: hybrid_search_program(HybridShapes.of(pg), hcfg,
                                                mesh),
            persist=False)
        root_mapper = self.session.cached(("root_mapper",) + pkey,
                                          lambda: make_root_mapper(plan))
        return skey, functools.partial(fn, graph), root_mapper, plan

    def _bfs_sharded(self, roots_arr, hcfg, n_parts, strategy, hub,
                     batched, control=None) -> TraversalResult:
        skey, fn, root_mapper, plan = self._sharded_executable(
            hcfg, n_parts, strategy, hub)
        roots_new = [root_mapper(int(r)) for r in roots_arr]
        self.session.warm(skey, lambda: fn(jnp.int32(roots_new[0]))[0])
        e_und = self.graph.num_undirected_edges
        kernels = "pallas" if B.kernels_enabled(hcfg.bfs) else "xla"
        per_root = []
        if batched:
            # Pipelined: dispatch every query before blocking once.
            fault_point("dispatch", mode="sharded", kernels=kernels)
            t0 = time.perf_counter()
            outs = [fn(jnp.int32(rn)) for rn in roots_new]
            # repro-ok: TH001 one sync for the whole pipelined batch; this is the batching win being measured
            jax.block_until_ready([o[0] for o in outs])
            dt = time.perf_counter() - t0
            per_root = np.full(len(roots_arr), dt / len(roots_arr))
        else:
            outs = []
            for rn in roots_new:
                if control is not None:
                    control.check()
                fault_point("dispatch", mode="sharded", kernels=kernels)
                t0 = time.perf_counter()
                out = fn(jnp.int32(rn))
                # repro-ok: TH001 timed dispatch: per_root latency must include device completion
                jax.block_until_ready(out[0])
                per_root.append(time.perf_counter() - t0)
                outs.append(out)
            per_root = np.asarray(per_root)
            dt = float(per_root.sum())
        parents, levels = [], []
        for parent_new, level_new, _rounds in outs:
            p, l = finalize_hybrid(plan, parent_new, level_new)
            parents.append(p); levels.append(l)
        level = np.stack(levels)
        return TraversalResult(roots_arr, np.stack(parents), level,
                               _tree_depth(level), dt, per_root,
                               "sharded", n_parts, e_und)

    # ------------------------------------------------------- stepper path --
    #
    # Both stepper variants are thin adapters now: they build a backend over
    # session-cached pieces and hand it to the shared `LevelDriver`
    # (repro.engine.level_loop), which owns the per-level loop, the single
    # host sync per level, the stats rows, and the cancellation hook.

    def _bfs_stepper(self, roots_arr, hcfg, n_parts, strategy, hub,
                     on_level=None, control=None) -> TraversalResult:
        backend = (self._stepper_backend_single(hcfg.bfs) if n_parts == 1
                   else self._stepper_backend_sharded(hcfg, n_parts,
                                                      strategy, hub))
        backend.fault_ctx = dict(
            mode="stepper",
            kernels="pallas" if B.kernels_enabled(hcfg.bfs) else "xla")
        driver = LevelDriver(backend)
        wkey = ("stepper_warm", hcfg, n_parts, strategy, hub)
        # The warm-up is a full traversal too: it honours the control so the
        # first (cold) query on a plan can still abort per level. An aborted
        # warm run never marks the key warmed (`GraphSession.warm` only
        # records success), so the next query warms the plan normally.
        try:
            self.session.warm(wkey,
                              lambda: driver.run(int(roots_arr[0]), None,
                                                 control)[0])
        except (QueryCancelled, QueryDeadlineExceeded) as e:
            e.per_level_stats = [e.per_level_stats]     # per-root convention
            raise
        if control is not None:
            control.check()             # the warm-up may outlive a deadline
        parents, levels, stats_all, timings, per_root = [], [], [], [], []
        for b, r in enumerate(roots_arr):
            cb = (lambda row, _b=b: on_level(_b, row)) if on_level else None
            t0 = time.perf_counter()
            try:
                p, l, stats, extra = driver.run(int(r), cb, control)
            except (QueryCancelled, QueryDeadlineExceeded) as e:
                # Promote the driver's flat row list to the engine's
                # per-root convention: completed roots + the aborted one.
                e.per_level_stats = stats_all + [e.per_level_stats]
                raise
            per_root.append(time.perf_counter() - t0)
            parents.append(p); levels.append(l)
            stats_all.append(stats)
            timings.append(extra)
        per_root = np.asarray(per_root)
        level = np.stack(levels)
        return TraversalResult(roots_arr, np.stack(parents), level,
                               _tree_depth(level), float(per_root.sum()),
                               per_root, "stepper", n_parts,
                               self.graph.num_undirected_edges,
                               per_level_stats=stats_all, timings=timings)

    def _stepper_backend_single(self, bcfg: BFSConfig) -> SingleStepBackend:
        dg = self.session.device_graph()
        ell = self.session.ell_tiles() if B.kernels_enabled(bcfg) else None
        step = self.session.cached(("stepper_step", bcfg),
                                   lambda: B.make_level_step(dg, bcfg, ell))
        init = self.session.cached(("stepper_init",), lambda: B.make_init(dg))
        return SingleStepBackend(init, step, dg.num_vertices)

    def _stepper_backend_sharded(self, hcfg, n_parts, strategy,
                                 hub) -> BSPStepBackend:
        plan, pg = self.session.partitioned(n_parts, strategy, hub)
        graph = self.session.hybrid_graph(n_parts, strategy, hub,
                                          hcfg.axis_name,
                                          B.kernels_enabled(hcfg.bfs))
        pieces = self.session.cached(
            ("hybrid_stepper", hcfg, n_parts, strategy, hub),
            lambda: make_hybrid_stepper(
                pg, hcfg, self.session.mesh_for(n_parts, hcfg.axis_name),
                graph=graph))
        return BSPStepBackend(pieces, plan)
