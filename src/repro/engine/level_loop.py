"""The canonical per-level BFS loop: one driver for every host-synced search.

The paper's direction-optimized BFS is a level-synchronous BSP loop —
compute, exchange, decide — and both Buluç & Madduri (arXiv:1104.4518) and
Pan et al. (arXiv:1803.03922) structure their distributed BFS around exactly
one such driver. This repo used to run it in four hand-duplicated copies
(engine `_stepper_single` / `_stepper_sharded`, core `bfs_instrumented` /
`hybrid_bfs_instrumented`), which drifted: PR 2 and PR 3 each patched the
same host-sync bug four times. `LevelDriver` is the single copy; the four
call sites are thin adapters over two backends.

The driver owns everything the four loops duplicated:

* init + the per-level step structure (compute, then exchange when the
  backend splits them — the BSP timing breakdown of Fig. 3);
* **one host sync per level**: the loop condition, the stats row, the
  direction flag, and the termination bound all read from a single
  `jax.device_get` — four scalars, or one dict on the extended protocol
  (this is the only such site in the repo);
* the stats-row schema (level, direction, frontier_size, frontier_edges,
  seconds, compute_s, exchange_s) and the `on_level` streaming hook;
* the spans of a search's phases (`repro.runtime.spans`): `repro.level.init`,
  `.step`, `.exchange` (partitioned backends), `.sync` and `.finalize`; the
  row's times and the `timings` are read off their timestamps;
* the termination bound, checked *before* stepping: no BFS level can exceed
  `depth_bound` = the TOTAL vertex count minus one (levels 0..V-1 all
  non-empty pigeonholes every vertex into the visited set), so a frontier
  sitting at that level is final — every neighbour is provably visited —
  and the loop stops without the old wasted extra step (the two
  pre-refactor guards disagreed — `cur > num_vertices` single vs
  `cur > v_pad` sharded — and only fired *after* stepping);
* cooperative cancellation: a `QueryControl` is checked once per level — the
  single safe point between BSP rounds — and aborts with a typed
  `QueryCancelled` / `QueryDeadlineExceeded` carrying the partial per-level
  stats, so a stuck Scale-29-sized traversal cannot pin a worker forever.

Backends only describe *what* runs per level, never the loop itself:

    class ...Backend:            # duck-typed; see SingleStepBackend
        depth_bound: int         # TOTAL vertex count - 1 (see above; a
                                 # smaller bound breaks the pre-step stop)
        has_exchange: bool       # True -> time compute/exchange separately
        def init(root) -> state
        def compute(state) -> work
        def exchange(state, work) -> state      # identity when fused in
        def scalars(state) -> (nf, mf, cur, bu) # device scalars, ONE get
        def finalize(state) -> (parent, level)  # host numpy

Extended (batched-cohort) protocol, opted into per backend:

* `scalars(state)` may return a DICT of device values instead of the
  4-tuple; it must contain "nf"/"mf"/"cur"/"bu" and may add anything else
  (cohort occupancy, per-lane vectors) — still ONE `jax.device_get`.
* `needs_sync = True` makes the driver call `compute(state, sync)` with the
  host dict from the most recent sync, so the backend can pick which
  compiled step to dispatch from cohort occupancy without a second device
  round-trip (`CohortBatchBackend` selects its td/bu/mixed executable
  this way). Such a backend also has `variant(sync)`, the name of the
  step it will dispatch, which the step's span carries.
* `row_extra(pre, post)` (optional) merges backend-specific fields into
  the level's stats row — `pre` is the sync entering the step (per-lane
  frontier stats, the directions the step used), `post` the one after it
  (realized cohort sizes). It may override "direction" (e.g. "mixed").
"""
from __future__ import annotations

import threading
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.core import bfs as B
from repro.core.hybrid_bfs import finalize_hybrid
from repro.runtime.faults import fault_point
from repro.runtime.spans import span


# ------------------------------------------------------------ cancellation --


class QueryCancelled(RuntimeError):
    """Query aborted by `QueryControl.cancel()` (between two BFS levels).

    `per_level_stats` holds the stats rows completed before the abort —
    a flat row list when raised by the driver, a per-root list of row lists
    once the engine re-raises it for a multi-root query.
    """

    def __init__(self, msg: str = "query cancelled", per_level_stats=None):
        super().__init__(msg)
        self.per_level_stats = per_level_stats if per_level_stats is not None \
            else []


class QueryDeadlineExceeded(RuntimeError):
    """Query aborted because its `QueryControl.deadline` passed.

    Carries `per_level_stats` exactly like `QueryCancelled`.
    """

    def __init__(self, msg: str = "query deadline exceeded",
                 per_level_stats=None):
        super().__init__(msg)
        self.per_level_stats = per_level_stats if per_level_stats is not None \
            else []


class QueryControl:
    """Cancel event + absolute deadline for one query (thread-safe).

    The driver calls `check()` once per level — between BSP rounds, the one
    point where aborting cannot corrupt device state. `deadline` is an
    absolute `time.monotonic()` timestamp (`with_timeout` converts relative
    seconds); `cancel()` may be called from any thread.
    """

    def __init__(self, deadline: Optional[float] = None):
        self.deadline = deadline
        self._cancelled = threading.Event()

    @classmethod
    def with_timeout(cls, seconds: Optional[float]) -> "QueryControl":
        """Control whose deadline is `seconds` from now (None = no deadline)."""
        return cls(None if seconds is None else time.monotonic() + seconds)

    def cancel(self) -> None:
        self._cancelled.set()

    @property
    def cancelled(self) -> bool:
        return self._cancelled.is_set()

    @property
    def expired(self) -> bool:
        return self.deadline is not None and time.monotonic() >= self.deadline

    def poll(self) -> Optional[RuntimeError]:
        """The pending abort, if any (None = keep running). Never raises."""
        if self._cancelled.is_set():
            return QueryCancelled()
        if self.expired:
            return QueryDeadlineExceeded(
                f"deadline passed {time.monotonic() - self.deadline:.3f}s ago")
        return None

    def check(self) -> None:
        """Raise the typed abort error if cancelled or past the deadline."""
        err = self.poll()
        if err is not None:
            raise err


# ----------------------------------------------------------------- backends --


class SingleStepBackend:
    """Single-partition backend: one jitted `state -> state` step per level.

    Wraps `repro.core.bfs`'s `init_state`/`make_level_step` products (or any
    functions with the same shapes). Compute and exchange are fused in the
    one step, so the driver reports `exchange_s == 0.0`.
    """

    has_exchange = False

    def __init__(self, init_fn: Callable, step_fn: Callable,
                 num_vertices: int):
        self._init = init_fn
        self._step = step_fn
        self.depth_bound = max(num_vertices - 1, 0)

    def init(self, root: int):
        return self._init(jnp.int32(root))

    def compute(self, state):
        return self._step(state)

    def exchange(self, state, work):
        return work                     # the step already merged the frontier

    def scalars(self, state):
        return (state.nf, state.mf, state.cur_level, state.bu_mode)

    def finalize(self, state):
        return B.finalize(state)


class BSPStepBackend:
    """Partitioned BSP backend over `make_hybrid_stepper` pieces.

    `compute` runs every partition's local TD/BU work (no communication);
    `exchange` is the per-round push/pull merge + state update — the driver
    times them separately, reproducing the paper's computation-vs-
    communication breakdown. Finalization maps padded new-id results back to
    original ids through the partition plan.
    """

    has_exchange = True

    def __init__(self, pieces, plan):
        init_fn, compute_fn, exchange_fn, finalize_fn, root_mapper = pieces
        self._init = init_fn
        self._compute = compute_fn
        self._exchange = exchange_fn
        self._finalize = finalize_fn
        self._root_mapper = root_mapper
        self._plan = plan
        self.depth_bound = max(plan.v_orig - 1, 0)

    def init(self, root: int):
        return self._init(self._root_mapper(int(root)))

    def compute(self, state):
        return self._compute(state)

    def exchange(self, state, work):
        return self._exchange(state, *work)

    def scalars(self, state):
        return (state["nf"], state["mf"], state["cur"], state["bu"])

    def finalize(self, state):
        parent_new, level_new = self._finalize(state)
        # repro-ok: TH001 traversal is over; finalize_hybrid needs host arrays next anyway
        jax.block_until_ready(parent_new)
        return finalize_hybrid(self._plan, parent_new, level_new)


class CohortBatchBackend:
    """Batched cohort backend: SoA `[B, ...]` state, per-level cohort dispatch.

    Drives `repro.core.bfs`'s batched pieces (`init_batch`,
    `make_batch_step` x td/bu/mixed, `batch_scalars`) as a `LevelDriver`
    backend: each level the host reads the next-step cohort occupancy from
    the (single) sync and dispatches exactly ONE step executable — the
    "td"/"bu" variant when the whole batch agrees (its traced program
    contains no code for the other direction), "mixed" when both cohorts
    are non-empty (one masked pass per direction over its cohort). Never
    both directions per lane, which is the point: under `vmap` the
    per-level `lax.cond` lowered to a select and every lane paid both.

    `dispatched` counts executable dispatches per variant — the host-side
    ledger tests use to prove a direction-mixed batch costs at most one
    top-down plus one bottom-up pass per level regardless of batch size.

    `root` for `init`/`run` is the pair `(roots, active)`: int32[B] device
    roots (pad lanes repeat a valid id) and the bool[B] activity mask that
    keeps pad lanes out of every cohort from level 0.
    """

    has_exchange = False
    needs_sync = True

    def __init__(self, init_fn: Callable, step_fns: dict,
                 scalars_fn: Callable, num_vertices: int, bucket: int):
        self._init = init_fn
        self._steps = dict(step_fns)        # reachable variants only
        self._scalars = scalars_fn
        self.depth_bound = max(num_vertices - 1, 0)
        self.bucket = bucket
        self.dispatched = {v: 0 for v in self._steps}

    @staticmethod
    def variant_for(td_next: int, bu_next: int) -> str:
        if td_next and bu_next:
            return "mixed"
        return "bu" if bu_next else "td"

    def init(self, root):
        roots, active = root
        return self._init(roots, active)

    def variant(self, sync) -> str:
        """The step variant the level after `sync` dispatches."""
        return self.variant_for(int(sync["td_next"]), int(sync["bu_next"]))

    def compute(self, state, sync):
        variant = self.variant(sync)
        self.dispatched[variant] += 1
        return self._steps[variant](state)

    def exchange(self, state, work):
        return work

    def scalars(self, state):
        return self._scalars(state)

    def finalize(self, state):
        return B.finalize(state)

    def warm(self, root):
        """Trace/compile every executable this backend can dispatch.

        Runs init once and each step variant once on the init state (the
        results are discarded); returns the outputs so the caller can block
        on them. Without this, the first level that flips the batch into a
        new variant would pay its compile inside the timed/served region.
        """
        state = self.init(root)
        outs = [state, self._scalars(state)]
        outs += [self._steps[v](state) for v in self._steps]
        return outs

    def row_extra(self, pre, post) -> dict:
        # Side-aware occupancy: td/bu_lanes count lanes with ANY side in
        # that direction (a lane whose sides agree is one lane, not two —
        # `td_next`/`bu_next` of the pre-step sync are exactly the cohort
        # sizes the dispatched step ran). With the heterogeneous split off
        # the hub counters are zero and every row degenerates to the old
        # schema (hub_* = 0, frontier_hub = 0, hub lane direction mirrors
        # tail).
        used_td = int(pre["td_next"])
        used_bu = int(pre["bu_next"])
        nf_hub = int(pre.get("nf_hub", 0))
        return dict(
            direction=("mixed" if used_td and used_bu
                       else ("bu" if used_bu else "td")),
            td_lanes=used_td,
            bu_lanes=used_bu,
            hub_td_lanes=int(post.get("used_td_hub", 0)),
            hub_bu_lanes=int(post.get("used_bu_hub", 0)),
            frontier_hub=nf_hub,
            frontier_tail=int(pre["nf"]) - nf_hub,
            active_lanes=int(pre["active_n"]),
            batch=self.bucket,
            lane_frontier=[int(x) for x in pre["nf_lanes"]],
            lane_edges=[int(x) for x in pre["mf_lanes"]],
            lane_direction=["bu" if x else "td" for x in pre["bu_lanes"]],
            lane_hub_direction=["bu" if x else "td"
                                for x in pre.get("hub_bu_lanes",
                                                 pre["bu_lanes"])],
            lane_hub_frontier=[int(x) for x in pre.get("nf_hub_lanes",
                                                       [0] * self.bucket)],
            lane_active=[bool(x) for x in pre["active_lanes"]],
        )


# ------------------------------------------------------------------- driver --


class LevelDriver:
    """Run a whole search as host-synced per-level steps over a backend."""

    def __init__(self, backend):
        self.backend = backend

    def _sync(self, state, cur: int):
        """THE per-level host sync — the repo's single `device_get` site.

        Loop condition, stats row, direction flag, and the depth bound all
        come from this one read — a four-scalar tuple, or a dict carrying
        the same keys plus backend extras (the batched cohort backend's
        occupancy counts and per-lane vectors); separate `int()`/`bool()`
        reads would each issue their own device round-trip. `cur` is the
        level the synced state has reached (the span's attribute).
        """
        with span("repro.level.sync", level=cur):
            # repro-ok: TH001 THE sanctioned per-level sync: exactly one device_get per BFS level
            host = jax.device_get(self.backend.scalars(state))
            if not isinstance(host, dict):
                nf, mf, cur, bu = host
                host = dict(nf=nf, mf=mf, cur=cur, bu=bu)
            return (int(host["nf"]), int(host["mf"]), int(host["cur"]),
                    bool(host["bu"]), host)

    def run(self, root: int, on_level: Optional[Callable] = None,
            control: Optional[QueryControl] = None):
        """One root -> (parent, level, per_level_stats, timings).

        `on_level(row)` fires the moment each level's stats land on the
        host (the server's streaming hook). `control` is checked once per
        level before stepping; on abort the typed error carries the rows
        completed so far. `timings` holds the out-of-loop phases (init_s,
        agg_s) plus `driver_overhead_s` — wall time the host loop spent
        outside the timed device work, the refactor's cost ledger.
        """
        b = self.backend
        needs_sync = getattr(b, "needs_sync", False)
        row_extra = getattr(b, "row_extra", None)
        with span("repro.level.init") as init:
            state = b.init(root)
            # repro-ok: TH001 timing fence: init_s must not absorb async dispatch of the first level
            jax.block_until_ready(state)
        stats: list = []
        nf, mf, cur, bu, pre = self._sync(state, cur=0)
        while nf > 0 and cur < b.depth_bound:
            if control is not None:
                try:
                    control.check()
                except (QueryCancelled, QueryDeadlineExceeded) as e:
                    e.per_level_stats = stats
                    raise
            # Chaos hooks at the dispatch boundary: a straggler spec sleeps
            # here (the per-level delay the paper's BSP model is most
            # sensitive to), a dispatch spec raises — before the step runs,
            # so device state is never half-advanced. `fault_ctx` is the
            # engine's description of this dispatch (mode/kernels), the
            # handle schedule filters like [kernels=pallas] select on.
            fctx = getattr(b, "fault_ctx", None) or {}
            fault_point("straggler", level=cur, **fctx)
            fault_point("dispatch", level=cur, **fctx)
            # A cohort backend names the step it dispatches before it runs;
            # for the others the direction comes with the sync after it.
            attrs = dict(level=cur + 1, nf=nf, mf=mf)
            if needs_sync:
                attrs["variant"] = b.variant(pre)
            with span("repro.level.step", **attrs) as step:
                work = b.compute(state, pre) if needs_sync else b.compute(state)
                # repro-ok: TH001 timing fence: per-level compute_s is a reported paper metric
                jax.block_until_ready(work)
            t_end = step.t1
            if b.has_exchange:
                with span("repro.level.exchange", level=cur + 1) as ex:
                    state = b.exchange(state, work)
                    # repro-ok: TH001 timing fence: exchange_s isolates the partition-boundary cost
                    jax.block_until_ready(state)
                t_end = ex.t1
            else:
                state = b.exchange(state, work)
            nf2, mf2, cur, bu, post = self._sync(state, cur=cur + 1)
            row = dict(level=cur, seconds=t_end - step.t0,
                       compute_s=step.t1 - step.t0,
                       exchange_s=t_end - step.t1,
                       direction="bu" if bu else "td",
                       frontier_size=nf, frontier_edges=mf)
            if row_extra is not None:
                row.update(row_extra(pre, post))
            step.attrs.setdefault("variant", row["direction"])
            stats.append(row)
            if on_level:
                on_level(row)
            nf, mf, pre = nf2, mf2, post
        with span("repro.level.finalize") as agg:
            parent, level = b.finalize(state)
        init_s = init.t1 - init.t0
        agg_s = agg.t1 - agg.t0
        overhead = (agg.t1 - init.t0) - init_s - agg_s \
            - sum(r["seconds"] for r in stats)
        return parent, level, stats, dict(init_s=init_s, agg_s=agg_s,
                                          driver_overhead_s=max(overhead, 0.0))
