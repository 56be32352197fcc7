"""Graph sessions: preprocessing ownership + compiled-plan caching.

A `GraphSession` is the serving-system unit of state for one graph (the
paper treats a BFS as a query against a preprocessed, partitioned graph —
Totem and Gunrock both amortize that preprocessing across many queries).
The session owns, and builds at most once each:

* the single-device CSR (`DeviceGraph`),
* every `PartitionPlan`/`PartitionedGraph` requested, keyed by
  (n_parts, strategy, hub_edge_fraction),
* the device mesh per partition count,
* the degree-bucketed ELL tiles the Pallas kernel path traverses
  (`ell_tiles` single-partition, `hybrid_ell` per partitioning),
* compiled search executables, keyed by
  (backend, config, n_parts/strategy, batch shape) — the graph itself is
  the session, so graph shape is implicit in the key.

Executables are wrapped so *tracing* (not calling) bumps a per-key counter;
`trace_count` lets tests assert that repeated queries with an identical
config never retrace.

Three cache tiers back `executable()` (each consulted before the next, the
`repro.runtime` layer):

1. **in-process cross-session registry** — plans are keyed by the graph's
   *content hash* (`runtime.fingerprint.graph_fingerprint`), not session
   identity, so two sessions over the same graph — or over a rebuilt,
   byte-identical graph — share one compiled copy (zero traces for the
   second; `RuntimeConfig.share_plans`);
2. **persistent artifact cache** — when `RuntimeConfig.cache_dir` is set,
   a cache miss consults the disk store before tracing, and a fresh trace
   is AOT-compiled and serialized back
   (`jax.experimental.serialize_executable`), so a restarted process
   re-attaches with zero traces (`load_count`/`materialize_count` make
   both tiers observable);
3. **trace + compile** — the cold path, exactly the old behavior.

On attach, a session with a persistent cache **pre-warms** in a background
thread: disk entries whose metadata matches this graph + environment are
deserialized into a preload pool ahead of the first query
(`prewarm_progress` is the observable handle; `prewarm_wait()` blocks).

Sessions are **thread-safe**: every cache (partitions, executables, helper
objects, warm set) is guarded by one per-session `RLock` with
double-checked builds, so concurrent queries — the `BFSServer` case —
build/trace each plan at most once instead of racing check-then-set on
plain dicts. The lock is re-entrant because builders call back into the
session (e.g. a fused executable build reads `device_graph()`); it is held
across `build()`/`warm()` bodies, which serializes *first-time compiles*
per session but never steady-state cache hits (readers check outside the
lock first) and never cross-session work (each session has its own lock).
Counters live under a separate leaf-level `_stats_lock` (a plan resolving
inside another session's `warm()` must be able to bump its builder's
counters without that session's lock).
"""
from __future__ import annotations

import threading
import time
import warnings
from typing import Any, Callable, Optional

import jax
import numpy as np

from repro.analysis.concurrency import ensure_installed as _ensure_sanitizer
from repro.analysis.concurrency import make_lock, make_rlock
from repro.core import ell as ELL
from repro.core import partition as PT
from repro.core.bfs import (BFSConfig, CohortGraph, DeviceGraph, hub_rows,
                            kernels_enabled)
from repro.core.graph import Graph
from repro.core.hybrid_bfs import (HybridGraph, default_mesh,
                                   place_hybrid_graph)
from repro.runtime.artifact_cache import artifact_cache_for
from repro.runtime.config import RuntimeConfig, get_runtime_config
from repro.runtime.faults import ensure_installed as _ensure_faults
from repro.runtime.faults import fault_point
from repro.runtime.fingerprint import (canonical_plan_key,
                                       environment_fingerprint,
                                       graph_fingerprint, plan_fingerprint)
from repro.runtime.plan_registry import registry_get, registry_put


class _PlanExecutable:
    """One plan's executable, resolved lazily on first call.

    Resolution order: the owning session's preload pool (filled by the
    background pre-warm), then the disk artifact cache, then trace +
    AOT-compile (persisting the result). A failed store keeps the compiled
    executable in memory, so persistence can never break a query; a failed
    compile is the query's own error and propagates. The wrapper may be
    shared across sessions via the plan registry; its internal lock makes
    the first resolution process-wide exclusive, and trace/load counters
    always land on the *builder* session.
    """

    __slots__ = ("_key", "_build", "_static", "_session", "_fp", "_lock",
                 "_fn", "source", "resolve_s")

    def __init__(self, key, build: Callable[[], Callable], static_argnums,
                 session: "GraphSession", fingerprint: Optional[str]):
        self._key = key
        self._build = build
        self._static = tuple(static_argnums)
        self._session = session
        self._fp = fingerprint          # None = never persisted to disk
        self._lock = make_lock("plan_exec")
        self._fn: Optional[Callable] = None
        self.source: Optional[str] = None   # traced | disk | prewarmed
        self.resolve_s = 0.0

    def __call__(self, *args):
        fn = self._fn
        if fn is None:
            fn = self._resolve(args)
        return fn(*args)

    def _resolve(self, args) -> Callable:
        with self._lock:
            if self._fn is not None:
                return self._fn
            t0 = time.perf_counter()
            sess = self._session
            fn = source = None
            if self._fp is not None:
                fn = sess._take_preloaded(self._fp)
                if fn is not None:
                    source = "prewarmed"
                elif sess._artifacts is not None:
                    fn = sess._artifacts.load(self._fp)
                    if fn is not None:
                        source = "disk"
            if fn is None:
                fn, source = self._trace(args)
            self._fn = fn
            self.source = source
            self.resolve_s = time.perf_counter() - t0
            sess._note_resolved(self._key, source)
            return fn

    def _trace(self, args):
        """Build + jit; AOT-compile and persist when the store is usable."""
        sess = self._session
        fault_point("compile", key=self._key)
        raw = self._build()
        key = self._key

        def counted(*a, _raw=raw, _key=key, _sess=sess):
            _sess._bump_trace(_key)
            return _raw(*a)

        jitted = jax.jit(counted, static_argnums=self._static)
        cache = sess._artifacts
        if self._fp is None or self._static or cache is None:
            return jitted, "traced"
        # A compile error propagates: it is the query's error, not a reason
        # to retry the same program through plain jit.
        compiled = jitted.lower(*args).compile()
        meta = dict(graph_hash=sess.graph_fingerprint,
                    key=canonical_plan_key(key),
                    **environment_fingerprint())
        cache.store(self._fp, compiled, meta)
        return compiled, "traced"


class PrewarmProgress:
    """Observable progress of one session's background pre-warm pass."""

    def __init__(self):
        self.total = 0              # matching disk entries found
        self.loaded = 0             # deserialized into the preload pool
        self.failed = 0             # corrupt/unloadable (evicted by cache)
        self.skipped = 0            # beyond RuntimeConfig.prewarm_limit
        self.seconds = 0.0
        self.error: Optional[str] = None   # pass died: repr of the exception
        self._done = threading.Event()

    def wait(self, timeout: Optional[float] = None) -> bool:
        """Block until the pass finishes; True when it did."""
        return self._done.wait(timeout)

    @property
    def done(self) -> bool:
        return self._done.is_set()

    def as_dict(self) -> dict:
        return dict(total=self.total, loaded=self.loaded, failed=self.failed,
                    skipped=self.skipped, seconds=self.seconds,
                    done=self.done, error=self.error)


class GraphSession:
    """Owns one graph's preprocessing products and compiled executables."""

    def __init__(self, graph: Graph, *, mesh=None,
                 default_strategy: str = "specialized",
                 default_hub_edge_fraction: float = 0.5,
                 runtime: Optional[RuntimeConfig] = None,
                 prewarm: Optional[bool] = None):
        self.graph = graph
        self.default_strategy = default_strategy
        self.default_hub_edge_fraction = default_hub_edge_fraction
        self._mesh = mesh
        self.runtime = runtime if runtime is not None else get_runtime_config()
        _ensure_sanitizer(self.runtime)  # REPRO_SANITIZE instruments these
        self._lock = make_rlock("session")
        self._stats_lock = make_lock("session.stats")
        self._device_graph: Optional[DeviceGraph] = None
        self._partitions: dict[tuple, tuple] = {}
        self._executables: dict[Any, Callable] = {}
        self._objects: dict[Any, Any] = {}
        self._trace_counts: dict[Any, int] = {}
        self._load_counts: dict[Any, int] = {}
        self._shared_counts: dict[Any, int] = {}
        self._plan_sources: dict[Any, str] = {}
        self._warmed: set = set()
        self._contract_checked: set = set()
        self._graph_shape_cache = None
        self._graph_fp: Optional[str] = None
        self._artifacts = artifact_cache_for(self.runtime)
        self._preloaded: dict[str, Callable] = {}
        self.attached_at = time.time()
        self.prewarm_progress: Optional[PrewarmProgress] = None
        self._prewarm_thread: Optional[threading.Thread] = None
        self._prewarm_stop = threading.Event()
        _ensure_faults(self.runtime)     # REPRO_FAULTS chaos schedule, if any
        do_prewarm = (self.runtime.prewarm if prewarm is None else prewarm)
        if do_prewarm and self._artifacts is not None:
            self._start_prewarm()

    # ------------------------------------------------------- preprocessing --

    def device_graph(self) -> DeviceGraph:
        """Single-device CSR arrays (built once, reused by every query)."""
        if self._device_graph is None:
            with self._lock:
                if self._device_graph is None:
                    self._device_graph = DeviceGraph.from_graph(self.graph)
        return self._device_graph

    def partitioned(self, n_parts: int, strategy: Optional[str] = None,
                    hub_edge_fraction: Optional[float] = None):
        """(plan, partitioned_graph) for a partitioning, built once."""
        strategy = strategy or self.default_strategy
        hub = (self.default_hub_edge_fraction
               if hub_edge_fraction is None else hub_edge_fraction)
        key = (n_parts, strategy, hub)
        got = self._partitions.get(key)
        if got is None:
            with self._lock:
                got = self._partitions.get(key)
                if got is None:
                    plan = PT.make_plan(self.graph, n_parts, strategy,
                                        hub_edge_fraction=hub)
                    got = (plan, PT.apply_plan(self.graph, plan))
                    self._partitions[key] = got
        return got

    def ell_tiles(self, *, base: int = ELL.DEFAULT_BASE,
                  growth: int = ELL.DEFAULT_GROWTH):
        """Degree-bucketed ELL tiles for the single-partition kernel path.

        Built once per (base, growth) and shared by every
        `backend_kernels` query, like plans and meshes.
        """
        return self.cached(("ell", base, growth),
                           lambda: ELL.build_graph_ell(self.graph, base=base,
                                                       growth=growth))

    def hybrid_ell(self, n_parts: int, strategy: Optional[str] = None,
                   hub_edge_fraction: Optional[float] = None, *,
                   base: int = ELL.DEFAULT_BASE,
                   growth: int = ELL.DEFAULT_GROWTH):
        """Stacked per-device ELL tiles for a partitioning (cached)."""
        strategy = strategy or self.default_strategy
        hub = (self.default_hub_edge_fraction
               if hub_edge_fraction is None else hub_edge_fraction)
        key = ("hybrid_ell", n_parts, strategy, hub, base, growth)
        _plan, pg = self.partitioned(n_parts, strategy, hub)
        return self.cached(key, lambda: ELL.build_hybrid_ell(pg, base=base,
                                                             growth=growth))

    def cohort_graph(self, cfg: BFSConfig) -> CohortGraph:
        """The graph-side arguments of `cfg`'s cohort executables: the CSR,
        plus the ELL tiles (kernel path) and hub row list (hub split)."""
        ell = self.ell_tiles() if kernels_enabled(cfg) else None
        hub = (self.cached(("hub_rows", cfg.hub_deg),
                           lambda: hub_rows(self.graph.degrees, cfg.hub_deg))
               if cfg.hub_split else None)
        return CohortGraph(self.device_graph(), ell, hub)

    def hybrid_graph(self, n_parts: int, strategy: Optional[str] = None,
                     hub_edge_fraction: Optional[float] = None,
                     axis_name: str = "part",
                     kernels: bool = False) -> HybridGraph:
        """A partitioning committed to its mesh, placed once: every
        sharded executable and stepper over it takes these arrays as
        arguments."""
        strategy = strategy or self.default_strategy
        hub = (self.default_hub_edge_fraction
               if hub_edge_fraction is None else hub_edge_fraction)
        _plan, pg = self.partitioned(n_parts, strategy, hub)
        return self.cached(
            ("hybrid_graph", n_parts, strategy, hub, axis_name, kernels),
            lambda: place_hybrid_graph(
                pg, self.mesh_for(n_parts, axis_name), axis_name,
                self.hybrid_ell(n_parts, strategy, hub) if kernels else ()))

    def mesh_for(self, n_parts: int, axis_name: str = "part"):
        if self._mesh is not None:
            if self._mesh.devices.size != n_parts:
                raise ValueError(
                    f"session mesh has {self._mesh.devices.size} devices but "
                    f"the query wants {n_parts} partitions")
            # Validate the axis up front: a mismatched axis otherwise dies
            # deep inside shard_map with an opaque unbound-axis error.
            if axis_name not in self._mesh.axis_names:
                raise ValueError(
                    f"session mesh axes {self._mesh.axis_names} do not "
                    f"include the query's axis {axis_name!r}; construct the "
                    f"mesh with Mesh(devices, ({axis_name!r},)) or set "
                    f"HybridConfig(axis_name=...) to a mesh axis")
            return self._mesh
        return default_mesh(n_parts, axis_name)

    # --------------------------------------------------------- fingerprint --

    @property
    def graph_fingerprint(self) -> str:
        """Content hash of this session's CSR (memoized; identity of every
        shared/persisted plan)."""
        if self._graph_fp is None:
            # Double-checked under the session lock: the prewarm thread and
            # the first query can race here, and an unguarded write would
            # let them hash the CSR twice (benign) or tear on exotic
            # interpreters (not benign).
            with self._lock:
                if self._graph_fp is None:
                    self._graph_fp = graph_fingerprint(self.graph)
        return self._graph_fp

    # ------------------------------------------------------ compiled plans --

    def executable(self, key, build: Callable[[], Callable],
                   static_argnums=(), persist: bool = True) -> Callable:
        """Cached callable for `key`; `build` traces at most once
        *process-wide* (registry) and at most once *ever* per artifact-cache
        directory (disk).

        `build()` must return a pure traceable function. The wrapper bumps
        the key's trace counter from inside tracing, so a cache hit that
        silently retraced (e.g. a weak-type or shape mismatch) is visible;
        a disk load bumps `load_count` instead (`materialize_count` is
        their sum — the "this session did first-time work" ledger).

        `persist=False` keeps a plan session-local and off disk — the
        sharded backend's executables are compiled for a device mesh, so
        they are only valid for the session's own device binding.
        """
        fn = self._executables.get(key)
        if fn is not None:
            return fn
        with self._lock:
            fn = self._executables.get(key)
            if fn is None:
                self._contract_gate(key)
                fn = self._make_executable(key, build, static_argnums,
                                           persist)
                self._executables[key] = fn
        return fn

    def _contract_gate(self, key) -> None:
        """Static kernel-contract check on first build of a kernel plan.

        Runs `repro.analysis.kernel_contracts.contract_report` against this
        graph's shape when the plan key carries a BFS/Hybrid config whose
        kernel path is enabled. An infeasible plan emits one structured
        `KernelContractWarning` (or raises `KernelBudgetError` under
        `RuntimeConfig.strict_contracts`) *before* tracing — the static
        analogue of failing at Mosaic lowering time, with the fix in the
        message. Checked once per key; called under the session lock.
        """
        if not isinstance(key, tuple) or key in self._contract_checked:
            return
        cfg = None
        for item in key:
            bfs = getattr(item, "bfs", None)
            if bfs is not None and hasattr(bfs, "td_chunk"):
                cfg = bfs
                break
            if hasattr(item, "td_chunk"):
                cfg = item
                break
        if cfg is None:
            return
        if not kernels_enabled(cfg, self.runtime):
            return
        from repro.analysis.kernel_contracts import (GraphShape,
                                                     contract_report)
        from repro.kernels.contracts import (KernelBudgetError,
                                             KernelContractWarning)
        if self._graph_shape_cache is None:
            # repro-ok: LS001 under self._lock — executable() holds it across the gate
            self._graph_shape_cache = GraphShape.from_graph(self.graph)
        report = contract_report(key, self._graph_shape_cache,
                                 budget_bytes=self.runtime.vmem_budget_bytes)
        if report.feasible:
            self._contract_checked.add(key)
            return
        first = report.errors[0]
        msg = (f"plan {key!r} fails its kernel contract: {report.summary()}; "
               f"first error: [{first.kernel}] {first.rule} {first.message}")
        if self.runtime.strict_contracts:
            # NOT marked checked: a strict retry must refuse again.
            raise KernelBudgetError(msg)
        self._contract_checked.add(key)
        warnings.warn(msg, KernelContractWarning, stacklevel=3)

    def _make_executable(self, key, build, static_argnums, persist):
        shareable = persist and not static_argnums
        if not shareable:
            return _PlanExecutable(key, build, static_argnums, self, None)
        gh = self.graph_fingerprint
        if self.runtime.share_plans:
            shared = registry_get((gh, key))
            if shared is not None:
                with self._stats_lock:
                    self._shared_counts[key] = \
                        self._shared_counts.get(key, 0) + 1
                    self._plan_sources[key] = "shared"
                return shared
        fp = (plan_fingerprint(gh, key)
              if self._artifacts is not None else None)
        wrapper = _PlanExecutable(key, build, static_argnums, self, fp)
        if self.runtime.share_plans:
            # First writer wins: a racing session's wrapper may already be
            # registered — adopt it so the plan still compiles only once.
            wrapper = registry_put((gh, key), wrapper)
        return wrapper

    def cached(self, key, build: Callable[[], Any]) -> Any:
        """Cache for non-executable helper objects (steppers, mappers)."""
        got = self._objects.get(key)
        if got is None:
            with self._lock:
                got = self._objects.get(key)
                if got is None:
                    got = build()
                    self._objects[key] = got
        return got

    def warm(self, key, run: Callable[[], Any]) -> None:
        """Run `run()` (and block) the first time `key` is used: pays
        compilation outside any timed region.

        Holds the session lock across the run, so two concurrent queries on
        one plan compile it once (the second blocks, then cache-hits) —
        without the lock both would trace and the trace-count proof of
        zero per-query recompiles would fail under a concurrent server.
        """
        if key in self._warmed:
            return
        with self._lock:
            if key in self._warmed:
                return
            # repro-ok: TH001 warm() absorbs the compile stall off the query path; blocking is the feature
            jax.block_until_ready(run())
            self._warmed.add(key)

    # ------------------------------------------------------------- prewarm --

    def _start_prewarm(self) -> None:
        # repro-ok: LS001 called only from __init__, before the session is shared with any other thread
        self.prewarm_progress = PrewarmProgress()
        # repro-ok: LS001 attach-time init; published by the same happens-before as the session object itself
        self._prewarm_stop = threading.Event()
        # Non-daemon: a daemon thread killed mid-XLA-deserialize at
        # interpreter shutdown aborts the process from C++. The pass is
        # bounded (prewarm_limit fast loads) and checks a stop flag, so
        # joining at exit is cheap.
        # repro-ok: LS001 attach-time init; Thread.start() below is the publication barrier
        self._prewarm_thread = threading.Thread(
            target=self._prewarm_pass, name="bfs-session-prewarm",
            daemon=False)
        self._prewarm_thread.start()

    def _prewarm_pass(self) -> None:
        """Deserialize this graph's disk entries into the preload pool.

        Runs on a background thread started at attach: by the time the
        first query resolves its executables, matching entries are already
        in memory (`_take_preloaded`), so even the cold *query* path pays
        no disk latency. Every step is observable on `prewarm_progress`.
        """
        progress = self.prewarm_progress
        t0 = time.perf_counter()
        try:
            gh = self.graph_fingerprint
            env = environment_fingerprint()
            matches = [
                fp for fp, meta in self._artifacts.scan()
                if meta.get("graph_hash") == gh
                and meta.get("jax_version") == env["jax_version"]
                and meta.get("backend") == env["backend"]
            ]
            progress.total = len(matches)
            limit = self.runtime.prewarm_limit
            for i, fp in enumerate(matches):
                if i >= limit or self._prewarm_stop.is_set():
                    progress.skipped = len(matches) - i
                    break
                fn = self._artifacts.load(fp)
                if fn is None:
                    progress.failed += 1
                    continue
                with self._stats_lock:
                    self._preloaded.setdefault(fp, fn)
                progress.loaded += 1
        except Exception as e:  # noqa: BLE001 — a dead pre-warm thread must
            # be visible, not silent: the error lands on the progress object
            # and in runtime_stats(); queries still work (they fall through
            # to disk/trace), but operators can see the pass died.
            progress.error = repr(e)
        finally:
            progress.seconds = time.perf_counter() - t0
            progress._done.set()

    def prewarm_wait(self, timeout: Optional[float] = None) -> dict:
        """Block until the attach-time pre-warm finishes; its report."""
        if self.prewarm_progress is None:
            return dict(total=0, loaded=0, failed=0, skipped=0, seconds=0.0,
                        done=True)
        self.prewarm_progress.wait(timeout)
        return self.prewarm_progress.as_dict()

    def _take_preloaded(self, fingerprint: str) -> Optional[Callable]:
        with self._stats_lock:
            return self._preloaded.pop(fingerprint, None)

    def signal_close(self) -> None:
        """Ask the pre-warm pass to stop WITHOUT waiting for it.

        `BFSServer.close()` calls this for every session up front, then
        joins everything on one shared deadline — signaling and joining as
        a single per-session step would let an early session's slow join
        eat the budget while later sessions' pre-warm passes kept running.
        Idempotent; `close()` still signals for standalone sessions.
        """
        self._prewarm_stop.set()

    def close(self, timeout: Optional[float] = None) -> bool:
        """Stop and join the pre-warm thread (it is non-daemon, so leaving
        it running blocks interpreter exit). True when fully joined."""
        self._prewarm_stop.set()
        t = self._prewarm_thread
        if t is not None and t.is_alive():
            t.join(timeout)
            if t.is_alive():
                return False
        # repro-ok: LS001 close() is single-caller teardown; the thread was joined above
        self._prewarm_thread = None
        return True

    # ---------------------------------------------- counter plumbing (leaf) --

    def _bump_trace(self, key) -> None:
        with self._stats_lock:
            self._trace_counts[key] = self._trace_counts.get(key, 0) + 1

    def _note_resolved(self, key, source: str) -> None:
        with self._stats_lock:
            self._plan_sources[key] = source
            if source in ("disk", "prewarmed"):
                self._load_counts[key] = self._load_counts.get(key, 0) + 1

    # ---------------------------------------------------------- inspection --

    def trace_count(self, key) -> int:
        with self._stats_lock:
            return self._trace_counts.get(key, 0)

    def load_count(self, key) -> int:
        """Times this session materialized `key` from disk (incl. pre-warm)."""
        with self._stats_lock:
            return self._load_counts.get(key, 0)

    def materialize_count(self, key) -> int:
        """trace_count + load_count: first-time work this session did for
        `key` (0 = it reused a plan another session already built)."""
        with self._stats_lock:
            return (self._trace_counts.get(key, 0)
                    + self._load_counts.get(key, 0))

    @property
    def total_traces(self) -> int:
        with self._stats_lock:
            return sum(self._trace_counts.values())

    @property
    def total_loads(self) -> int:
        with self._stats_lock:
            return sum(self._load_counts.values())

    @property
    def total_materialized(self) -> int:
        with self._stats_lock:
            return (sum(self._trace_counts.values())
                    + sum(self._load_counts.values()))

    def cache_info(self) -> dict:
        with self._lock, self._stats_lock:
            return {
                "graph": dict(V=self.graph.num_vertices,
                              E_undirected=self.graph.num_undirected_edges),
                "partitions": sorted(self._partitions),
                "executables": sorted(self._executables, key=repr),
                "trace_counts": dict(self._trace_counts),
                "load_counts": dict(self._load_counts),
                "shared_counts": dict(self._shared_counts),
                "plan_sources": dict(self._plan_sources),
            }

    def runtime_stats(self) -> dict:
        """Cold-start accounting: plan sources, cache counters, pre-warm."""
        with self._stats_lock:
            sources: dict = {}
            for src in self._plan_sources.values():
                sources[src] = sources.get(src, 0) + 1
            loads = sum(self._load_counts.values())
            traces = sum(self._trace_counts.values())
            shared = sum(self._shared_counts.values())
        out = dict(
            cache_enabled=self._artifacts is not None,
            traces=traces, loads=loads, shared=shared,
            plan_sources=sources,
            prewarm=(self.prewarm_progress.as_dict()
                     if self.prewarm_progress is not None else None),
        )
        if self._artifacts is not None:
            cache_stats = self._artifacts.stats()
            cache_stats.pop("per_entry", None)   # bulky; fetch via the cache
            out["artifact_cache"] = cache_stats
        return out
