"""Single-partition direction-optimized BFS in JAX (jit-compatible).

Faithful to Beamer et al. / the paper's Algorithm 1, formulated with static
shapes so the whole search (or one level) is a compiled XLA program:

* **Top-down (push)**: the frontier is compacted into a queue; a
  `lax.while_loop` walks its *edge slots* in fixed-size chunks (work
  proportional to frontier edge mass, the direction-optimization invariant).
  Ownership of an edge slot is recovered with a vectorized `searchsorted`
  over the queue's degree prefix sum — the TPU-native replacement for the
  GPU's per-thread edge binning ("virtual warp" has no TPU analogue; see
  API.md §Kernel-backed traversal).
* **Bottom-up (pull)**: unvisited vertices are scanned in row chunks; each
  chunk walks its adjacency in width-`bu_slab` slabs with a while-loop that
  exits as soon as every row in the chunk found a frontier parent —
  block-granularity early exit, enabled by the descending-degree adjacency
  ordering (paper §3.4).
* Direction switching implements both the paper's heuristic (static fraction
  of total edges + fixed number of bottom-up rounds, §3.3) and Beamer's
  alpha/beta heuristic.

Two interchangeable formulations of the per-level steps exist:

* the pure-XLA gather/scatter loops above (the reference path), and
* a Pallas kernel path (`BFSConfig.backend_kernels`) dispatching to
  `repro.kernels.ops` over degree-bucketed ELL tiles (`repro.core.ell`):
  block-early-exit bottom-up, fused visited-gather top-down, and one fused
  pack+count+edge-mass pass for the per-level frontier statistics, which
  thread through `BFSState.nf`/`BFSState.mf` so neither the direction
  heuristic nor the loop condition re-scans the frontier.

Both produce bitwise-identical parent/level arrays (gated by
tests/test_kernel_bfs.py); `backend_kernels=None` runs the XLA path on every
backend. The kernels are opt-in (`backend_kernels=True` / `REPRO_KERNELS=on`):
Mosaic still refuses each of them for TPU (tests/test_tpu_compile.py).

Every compiled program takes the graph (`DeviceGraph`, ELL tiles, hub row
list) as jit *arguments*, never as closed-over constants: a scale-22 CSR is
about 0.5 GB, and a constant copy per executable would bloat compiles,
serialized plans and device memory alike.

All vertex/edge indices are int32 (per-partition E < 2**31; the multi-pod
sharding in `hybrid_bfs.py` keeps per-device edge counts far below this).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import ell as ELL
from repro.core import frontier as fr
from repro.core.graph import Graph
from repro.kernels import ops as K
from repro.runtime.spans import span

INT_MAX = np.iinfo(np.int32).max


@dataclasses.dataclass(frozen=True)
class BFSConfig:
    """Tuning + heuristic knobs (defaults follow the paper / Beamer)."""
    heuristic: str = "paper"      # "paper" | "beamer" | "topdown" | "bottomup"
    alpha: float = 14.0           # beamer: switch down when mf > mu/alpha
    beta: float = 24.0            # beamer: switch up when nf < V/beta
    gamma: float = 0.06           # paper: switch down when mf > gamma * E
    fixed_bu_steps: int = 3       # paper: return to top-down after N BU rounds
    td_chunk: int = 4096          # edge slots per top-down chunk
    bu_chunk: int = 512           # rows per bottom-up chunk
    bu_slab: int = 32             # neighbour slots per bottom-up slab
    max_levels: int = 0           # 0 = num_vertices (safe upper bound)
    # Heterogeneous hub/tail dispatch (API.md §Heterogeneous dispatch).
    # When `hub_split` is on, every cohort level is executed as two sides:
    # the hub side (rows with degree above the `hub_deg` threshold, snapped
    # to the ELL bucket ladder) and the tail side (the low-degree mass,
    # excluding degree-0 rows, which can never pull). Each side carries its
    # own direction decision per level: the paper heuristic's threshold is a
    # static fraction of ALL edges, so its sides always agree (the split is
    # then a pure execution reorganization — bitwise-identical results,
    # bounded tail slab scans, a wide dense pass for the few hub rows);
    # beamer's pull-cost input `mu` is side-local, so the hub side flips
    # bottom-up as soon as its own unvisited edge mass collapses — the
    # paper's asymmetric switch inside one query. Split dispatch lives in
    # the batched cohort path (the engine routes ALL fused traffic through
    # it, single roots as B=1 cohorts); the one-shot `search_state` ignores
    # `hub_split`.
    hub_split: bool = False       # enable hub/tail split per-level dispatch
    hub_deg: int = 256            # hub threshold (snapped to bucket ladder)
    hub_slab: int = 256           # neighbour slots per hub-side pull slab
    # Pallas kernel path over ELL tiles. None defers to REPRO_KERNELS
    # (default off: the XLA step on every backend, since Mosaic refuses the
    # kernels for TPU; see kernels_enabled). Explicit True forces the kernel
    # path anywhere (interpret mode off-TPU — the CI equivalence
    # configuration).
    backend_kernels: Optional[bool] = None


def kernels_enabled(cfg: BFSConfig, runtime=None) -> bool:
    """Resolve `cfg.backend_kernels`: the one place the kernel policy lives.

    An explicit `BFSConfig.backend_kernels` always wins (per-query beats
    process-wide). None defers to `runtime.kernel_backend` (REPRO_KERNELS,
    'on' | 'off', default 'off'), where `runtime` is a session's private
    `RuntimeConfig` or, when omitted, the process config. Off runs the XLA
    step: Mosaic still refuses each Pallas kernel for TPU
    (tests/test_tpu_compile.py marks the refusals as strict xfails).
    """
    if cfg.backend_kernels is not None:
        return cfg.backend_kernels
    if runtime is None:
        from repro.runtime.config import get_runtime_config
        runtime = get_runtime_config()
    return runtime.kernel_backend == "on"


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class DeviceGraph:
    """CSR graph as device arrays (+ one-slot padding for queue-fill gathers).

    `pull_order` lists the rows a bottom-up pass may scan — every row of
    nonzero degree, by descending degree — then fill ids (V). Pulling rows
    in that order groups rows of like degree into one chunk, so a chunk's
    slab loop is not held open by one wide row among narrow ones, and the
    zero-degree rows (which can never find a parent) cost no chunk at all.
    """
    indptr: jax.Array      # int32[V+1]
    indices: jax.Array     # int32[E]
    deg_ext: jax.Array     # int32[V+1]; deg_ext[V] == 0 (fill-vertex degree)
    pull_order: jax.Array  # int32[V]; pullable rows, widest first; fill V
    num_vertices: int
    num_directed_edges: int

    def tree_flatten(self):
        return ((self.indptr, self.indices, self.deg_ext, self.pull_order),
                (self.num_vertices, self.num_directed_edges))

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(*leaves, *aux)

    @classmethod
    def from_graph(cls, g: Graph) -> "DeviceGraph":
        assert g.num_directed_edges < INT_MAX, "per-partition E must be < 2^31"
        deg_ext = np.zeros(g.num_vertices + 1, dtype=np.int32)
        deg_ext[:g.num_vertices] = g.degrees
        # Edgeless graphs keep one dummy slot so gathers stay well-formed
        # (never addressed: every edge-slot predicate is False when E == 0).
        indices = g.indices if g.num_directed_edges else np.zeros(1, np.int32)
        order = np.argsort(-g.degrees.astype(np.int64), kind="stable")
        pull_order = np.full(g.num_vertices, g.num_vertices, np.int32)
        pullable = int(np.count_nonzero(g.degrees))
        pull_order[:pullable] = order[:pullable]
        return cls(
            indptr=jnp.asarray(g.indptr, dtype=jnp.int32),
            indices=jnp.asarray(indices, dtype=jnp.int32),
            deg_ext=jnp.asarray(deg_ext),
            pull_order=jnp.asarray(pull_order),
            num_vertices=g.num_vertices,
            num_directed_edges=g.num_directed_edges,
        )


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class BFSState:
    visited: jax.Array    # uint8[V]
    frontier: jax.Array   # uint8[V]
    parent: jax.Array     # int32[V], INT_MAX = undiscovered
    level: jax.Array      # int32[V], INT_MAX = undiscovered
    cur_level: jax.Array  # int32 scalar
    bu_mode: jax.Array    # bool scalar: currently bottom-up
    bu_steps: jax.Array   # int32: bottom-up rounds taken
    mu: jax.Array         # int32: edge mass of unvisited vertices
    nf: jax.Array         # int32: frontier vertex count (carried stat)
    mf: jax.Array         # int32: frontier edge mass (carried stat)

    def tree_flatten(self):
        return ((self.visited, self.frontier, self.parent, self.level,
                 self.cur_level, self.bu_mode, self.bu_steps, self.mu,
                 self.nf, self.mf), None)

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(*leaves)


def init_state(dg: DeviceGraph, root) -> BFSState:
    v = dg.num_vertices
    visited = jnp.zeros(v, jnp.uint8).at[root].set(1)
    frontier = jnp.zeros(v, jnp.uint8).at[root].set(1)
    parent = jnp.full(v, INT_MAX, jnp.int32).at[root].set(root)
    level = jnp.full(v, INT_MAX, jnp.int32).at[root].set(0)
    total_e = dg.deg_ext.sum(dtype=jnp.int32)
    mu = total_e - dg.deg_ext[root]
    return BFSState(visited, frontier, parent, level,
                    jnp.int32(0), jnp.bool_(False), jnp.int32(0), mu,
                    jnp.int32(1), dg.deg_ext[root])


# ---------------------------------------------------------------- top-down --

def _top_down_step(dg: DeviceGraph, cfg: BFSConfig, frontier, visited, parent,
                   dst_mask=None):
    """One push level: work ~ frontier edge mass, chunked.

    Takes the flat (frontier, visited, parent) triple rather than a
    `BFSState` so the batched cohort path can run it lane by lane.

    `dst_mask` (bool[V] or None) restricts which DESTINATIONS this pass may
    discover — the heterogeneous split's side filter. The scatter-min parent
    merge is commutative, so side-masked passes union to exactly the
    unmasked pass's result whenever both sides push.
    """
    v = dg.num_vertices
    c = cfg.td_chunk
    queue, _n = fr.compact(frontier)             # fill entries == v
    degq = dg.deg_ext[queue]                     # 0 for fill
    cum = jnp.cumsum(degq, dtype=jnp.int32)
    total = cum[-1] if v else jnp.int32(0)

    def body(carry):
        base, next_flags, pcand = carry
        slots = base + jnp.arange(c, dtype=jnp.int32)
        valid = slots < total
        owner = jnp.searchsorted(cum, slots, side="right").astype(jnp.int32)
        owner = jnp.minimum(owner, v - 1)
        src = queue[owner]
        src = jnp.minimum(src, v - 1)            # fill guard (valid==False)
        start = cum[owner] - degq[owner]
        eidx = dg.indptr[src] + (slots - start)
        eidx = jnp.clip(eidx, 0, max(dg.num_directed_edges - 1, 0))
        dst = jnp.where(valid, dg.indices[eidx], 0)
        fresh = valid & (visited[dst] == 0)
        if dst_mask is not None:
            fresh = fresh & dst_mask[dst]
        next_flags = next_flags.at[dst].max(fresh.astype(jnp.uint8))
        pcand = pcand.at[dst].min(jnp.where(fresh, src, INT_MAX))
        return base + c, next_flags, pcand

    def cond(carry):
        return carry[0] < total

    init = (jnp.int32(0), jnp.zeros(v, jnp.uint8), jnp.full(v, INT_MAX, jnp.int32))
    _, next_flags, pcand = jax.lax.while_loop(cond, body, init)
    parent = jnp.where(next_flags > 0, jnp.minimum(parent, pcand), parent)
    return next_flags, parent


# --------------------------------------------------------------- bottom-up --

def _bottom_up_step(dg: DeviceGraph, cfg: BFSConfig, frontier, visited,
                    parent_in, row_mask=None, chunk=None, slab=None):
    """One pull level: row chunks x adjacency slabs with block early exit.

    Rows are pulled in `dg.pull_order` (nonzero degree, widest first), so
    a chunk holds rows of like degree. `row_mask` (bool[V] or None)
    restricts the unvisited scan: the heterogeneous split passes a
    per-vertex side mask here, plus side-tuned `chunk`/`slab` overrides
    (defaults: `cfg.bu_chunk`/`cfg.bu_slab`). The per-row first-hit parent
    is invariant under chunk grouping, row order and slab width (first hit
    == lowest adjacency slot regardless of how slots are grouped), so any
    side partition or order of the rows produces bitwise-identical flags
    and parents to one unsplit pass — they only change how many slab
    iterations a chunk's widest row can force on its neighbours.
    """
    v = dg.num_vertices
    r = min(chunk or cfg.bu_chunk, v)
    w = slab or cfg.bu_slab
    order = dg.pull_order
    order_rows = jnp.minimum(order, v - 1)
    pull = (order < v) & (visited[order_rows] == 0)
    if row_mask is not None:
        pull = pull & row_mask[order_rows]
    at, m = fr.compact(pull.astype(jnp.uint8))   # positions in pull_order
    queue = jnp.where(at < v, order[jnp.minimum(at, v - 1)], v)  # fill == v

    def chunk_body(carry):
        base, next_flags, parent = carry
        rows = jax.lax.dynamic_slice(queue, (base,), (r,))   # may include fill
        rows_safe = jnp.minimum(rows, v)          # deg_ext[v] == 0
        rdeg = dg.deg_ext[rows_safe]
        rptr = jnp.where(rows < v, dg.indptr[jnp.minimum(rows, v - 1)], 0)

        def slab_cond(sc):
            s, found, _ = sc
            return jnp.any(~found & (rdeg > s * w))

        def slab_body(sc):
            s, found, par = sc
            col = s * w + jnp.arange(w, dtype=jnp.int32)
            nidx = rptr[:, None] + col[None, :]
            nvalid = (col[None, :] < rdeg[:, None]) & ~found[:, None]
            nidx = jnp.clip(nidx, 0, max(dg.num_directed_edges - 1, 0))
            nbr = jnp.where(nvalid, dg.indices[nidx], 0)
            hit = nvalid & (frontier[nbr] > 0)
            anyhit = jnp.any(hit, axis=1)
            first = jnp.argmax(hit, axis=1)
            pcand = nbr[jnp.arange(r), first]
            par = jnp.where(~found & anyhit, pcand, par)
            return s + 1, found | anyhit, par

        found0 = jnp.zeros(r, bool)
        par0 = jnp.full(r, INT_MAX, jnp.int32)
        _, found, par = jax.lax.while_loop(
            slab_cond, slab_body, (jnp.int32(0), found0, par0))
        # rows may contain the fill id v -> mode="drop" discards those.
        next_flags = next_flags.at[rows].max(found.astype(jnp.uint8), mode="drop")
        parent = parent.at[rows].min(jnp.where(found, par, INT_MAX), mode="drop")
        return base + r, next_flags, parent

    def chunk_cond(carry):
        return carry[0] < m

    init = (jnp.int32(0), jnp.zeros(v, jnp.uint8), parent_in)
    _, next_flags, parent = jax.lax.while_loop(chunk_cond, chunk_body, init)
    return next_flags, parent


# -------------------------------------------------------- kernel-path steps --
#
# Same level semantics as the XLA steps above, dispatched to the Pallas
# kernels over degree-bucketed ELL tiles (repro.core.ell). Activity masking
# replaces queue compaction: inactive rows get degree 0, so bottom-up blocks
# of settled rows exit after zero slabs (the block-granularity early exit the
# chunked slab while-loop provided). ELL rows preserve CSR slot order, so
# first-hit parents are bitwise-identical to the XLA formulation.

def _top_down_step_kernels(dg: DeviceGraph, cfg: BFSConfig, ell, st: BFSState):
    """Push level via `kernels.ops.topdown`: fused visited-gather + masking
    per tile; the idempotent scatter-max/min stays in XLA."""
    v = dg.num_vertices
    next_flags = jnp.zeros(v, jnp.uint8)
    pcand = jnp.full(v, INT_MAX, jnp.int32)
    for rows, deg, nbrs in ell:
        act_deg = jnp.where(st.frontier[rows] > 0, deg, 0)
        fresh, dst = K.topdown(act_deg, nbrs, st.visited)
        next_flags = next_flags.at[dst].max(fresh)
        src = jnp.broadcast_to(rows[:, None], dst.shape)
        pcand = pcand.at[dst].min(jnp.where(fresh > 0, src, INT_MAX))
    parent = jnp.where(next_flags > 0, jnp.minimum(st.parent, pcand), st.parent)
    return next_flags, parent


def _bottom_up_step_kernels(dg: DeviceGraph, cfg: BFSConfig, ell, st: BFSState):
    """Pull level via `kernels.ops.bottomup`: ELL slab scan with block early
    exit (visited rows are masked to degree 0 and cost no slabs)."""
    v = dg.num_vertices
    next_flags = jnp.zeros(v, jnp.uint8)
    parent = st.parent
    for rows, deg, nbrs in ell:
        act_deg = jnp.where(st.visited[rows] == 0, deg, 0)
        found, par = K.bottomup(act_deg, nbrs, st.frontier,
                                slab=min(cfg.bu_slab, nbrs.shape[1]))
        next_flags = next_flags.at[rows].max(found)
        parent = parent.at[rows].min(jnp.where(found > 0, par, INT_MAX))
    return next_flags, parent


# ------------------------------------------------------------------ levels --

def _decide_direction(dg: DeviceGraph, cfg: BFSConfig, st: BFSState,
                      mf: jax.Array, nf: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Next-level direction (True = bottom-up) + updated bu_steps counter."""
    v = dg.num_vertices
    e = dg.num_directed_edges
    if cfg.heuristic == "topdown":
        return jnp.bool_(False), st.bu_steps
    if cfg.heuristic == "bottomup":
        return jnp.bool_(True), st.bu_steps
    if cfg.heuristic == "beamer":
        go_down = ~st.bu_mode & (mf.astype(jnp.float32) > st.mu.astype(jnp.float32) / cfg.alpha)
        go_up = st.bu_mode & (nf.astype(jnp.float32) < v / cfg.beta)
        bu = (st.bu_mode | go_down) & ~go_up
        return bu, jnp.where(bu, st.bu_steps + 1, 0)
    # Paper §3.3: down when frontier edge mass exceeds a static fraction of
    # all edges; back up after a fixed number of bottom-up rounds.
    go_down = ~st.bu_mode & (mf.astype(jnp.float32) > cfg.gamma * e)
    stay_down = st.bu_mode & (st.bu_steps < cfg.fixed_bu_steps)
    bu = go_down | stay_down
    return bu, jnp.where(bu, st.bu_steps + 1, 0)


def _advance(dg: DeviceGraph, cfg: BFSConfig, ell, st: BFSState) -> BFSState:
    """Advance one BFS level (direction decision + step + state merge).

    The direction decision reads the carried `st.nf`/`st.mf` (computed once
    when the frontier was produced) instead of re-scanning the frontier; the
    next level's statistics come from a single fused pass on the kernel path
    (`kernels.ops.frontier_fused`) or two XLA reductions on the reference
    path — both feed the carry, the loop condition, and the `mu` update.
    """
    use_kernels = kernels_enabled(cfg)
    bu, bu_steps = _decide_direction(dg, cfg, st, st.mf, st.nf)
    if use_kernels:
        next_flags, parent = jax.lax.cond(
            bu,
            lambda s: _bottom_up_step_kernels(dg, cfg, ell, s),
            lambda s: _top_down_step_kernels(dg, cfg, ell, s),
            st)
        _, nf, mf = K.frontier_fused(next_flags, dg.deg_ext[:-1])
    else:
        next_flags, parent = jax.lax.cond(
            bu,
            lambda s: _bottom_up_step(dg, cfg, s.frontier, s.visited, s.parent),
            lambda s: _top_down_step(dg, cfg, s.frontier, s.visited, s.parent),
            st)
        nf = fr.count(next_flags)
        mf = fr.edge_count(next_flags, dg.deg_ext[:-1])
    visited = jnp.maximum(st.visited, next_flags)
    level = jnp.where(next_flags > 0, st.cur_level + 1, st.level)
    mu = st.mu - mf
    return BFSState(visited, next_flags, parent, level,
                    st.cur_level + 1, bu, bu_steps, mu, nf, mf)


def _resolve_ell(dg: DeviceGraph, cfg: BFSConfig, ell):
    """ELL tiles for the kernel path (None when the XLA path runs).

    Building tiles requires *concrete* graph arrays: callers jitting over a
    traced `DeviceGraph` (the one-shot `bfs()` wrapper does) must build tiles
    outside the trace — `GraphSession.ell_tiles` is the cached way. Tiles
    built here are memoized on the `DeviceGraph` instance so repeated
    `bfs()`/`bfs_instrumented()` calls on one graph pay the host-side
    bucketing once.
    """
    if not kernels_enabled(cfg):
        return None
    if ell is None:
        if isinstance(dg.indptr, jax.core.Tracer):
            raise ValueError(
                "backend_kernels traversal needs prebuilt ELL tiles when the "
                "graph arrays are traced; pass ell=GraphSession.ell_tiles() "
                "(see API.md §Kernel-backed traversal)")
        ell = getattr(dg, "_ell_cache", None)
        if ell is None:
            ell = ELL.build_device_graph_ell(dg)
            dg._ell_cache = ell
    return ell


_advance_jit = jax.jit(_advance, static_argnums=(1,))
_init_state_jit = jax.jit(init_state)


def make_level_step(dg: DeviceGraph, cfg: BFSConfig, ell=None):
    """Returns `state -> state` advancing one BFS level (one jitted
    program; `dg` and `ell` are bound as its arguments)."""
    ell = _resolve_ell(dg, cfg, ell)
    return functools.partial(_advance_jit, dg, cfg, ell)


def make_init(dg: DeviceGraph):
    """Returns jitted `root -> BFSState`, the graph bound as an argument."""
    return functools.partial(_init_state_jit, dg)


def search_state(dg: DeviceGraph, root, cfg: BFSConfig, ell=None) -> BFSState:
    """Whole-search body: init + level loop, as a pure traceable function.

    This is the public building block for compiled one-root search plans:
    wrap it in `jax.jit` (cfg static) for a whole-search executable whose
    per-level `lax.cond` is a real branch (`repro.engine`'s unbatched
    Graph500 mode). `jax.vmap` over `root` also works but is the WRONG way
    to batch: under vmap the per-level cond lowers to a select, so every
    lane pays both directions' work every level and the batch runs until
    its slowest member finishes — batched multi-root queries should use the
    cohort model (`init_batch`/`make_batch_step` below), which is what the
    engine's batched fused path does.

    When `kernels_enabled(cfg)`, pass `ell` (degree-bucketed tiles from
    `repro.core.ell` / `GraphSession.ell_tiles`); jit this function with
    `dg`, `root` and `ell` as arguments (cfg static), as `bfs()` does.
    """
    ell = _resolve_ell(dg, cfg, ell)
    st = init_state(dg, root)
    max_levels = cfg.max_levels or dg.num_vertices

    def cond(st: BFSState):
        return (st.nf > 0) & (st.cur_level < max_levels)

    return jax.lax.while_loop(cond, functools.partial(_advance, dg, cfg, ell),
                              st)


_bfs_jit = jax.jit(search_state, static_argnums=(2,))


# ------------------------------------------------- batched cohort traversal --
#
# Batch-native multi-root search: structure-of-arrays `[B, ...]` state, the
# direction decision as per-lane DATA, and one step executable per direction
# *cohort* per level. Under `vmap`-of-whole-search the per-level `lax.cond`
# lowers to a select, so every lane executes BOTH directions every level and
# the batch runs until its slowest member finishes; here each level
# partitions the batch into a top-down cohort, a bottom-up cohort, and a
# finished cohort, and each direction kernel runs ONCE over its masked
# cohort. Lanes outside a cohort (including pow2-bucket pad lanes, which
# start inactive) contribute zero frontier/row mass, so they cost no
# traversal work. The host-side per-level loop lives in
# `repro.engine.level_loop.CohortBatchBackend`; this module provides the
# traceable pieces (`init_batch`, `make_batch_step`, `batch_scalars`).

BATCH_VARIANTS = ("td", "bu", "mixed")


class CohortGraph(NamedTuple):
    """The graph-side arguments of every cohort step executable.

    A pytree, passed to the jitted step as an argument (never closed over):
    the CSR arrays, the ELL tiles when the kernel path runs (else None),
    and the static hub row list when `hub_split` is on (else None).
    """
    dg: DeviceGraph
    ell: Optional[tuple]
    hub_rows: Optional[jax.Array]    # int32[H]


def hub_rows(degrees: np.ndarray, hub_deg: int) -> jax.Array:
    """int32[H]: the rows above the snapped hub floor (`_hub_row_mask`'s
    true set), listed on the host — a property of the graph, not of any
    search, so it is built once per session and passed to the steps."""
    floor = ELL.hub_degree_floor(hub_deg)
    return jnp.asarray(np.flatnonzero(np.asarray(degrees) > floor)
                       .astype(np.int32))


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class BatchState:
    """SoA state for a fused batch of B concurrent single-partition searches.

    `bu_mode` holds the direction each lane will take on the NEXT step
    (decided at the end of the previous step from the same carried nf/mf/mu
    statistics the single-root `_advance` reads at step start — the
    decisions coincide lane-for-lane). `active` gates every cohort mask:
    a finished or pad lane is in no cohort and does no traversal work.
    `used_td`/`used_bu` record the cohort sizes of the step that produced
    this state (the per-level direction-split observability hook).

    Under `hub_split`, every lane carries TWO direction tracks: `bu_mode`/
    `bu_steps`/`mu` describe the TAIL side and `bu_hub`/`bu_steps_hub`/
    `mu_hub` the hub side (per-side frontier stats in `nf_hub`/`mf_hub`);
    `used_*_hub` record the hub-side cohort sizes of the last step. With
    the split off, the hub track mirrors the tail track (`bu_hub ==
    bu_mode`) and the side stats stay zero, so side-aware consumers
    degenerate to the unsplit schema.
    """
    visited: jax.Array    # uint8[B, V]
    frontier: jax.Array   # uint8[B, V]
    parent: jax.Array     # int32[B, V], INT_MAX = undiscovered
    level: jax.Array      # int32[B, V], INT_MAX = undiscovered
    cur_level: jax.Array  # int32 scalar: shared level counter (synchronous)
    active: jax.Array     # bool[B]: lane still traversing
    bu_mode: jax.Array    # bool[B]: NEXT step's tail-side direction per lane
    bu_steps: jax.Array   # int32[B]: tail-side bottom-up rounds per lane
    mu: jax.Array         # int32[B]: unvisited edge mass per lane (all rows)
    nf: jax.Array         # int32[B]: frontier vertex count per lane
    mf: jax.Array         # int32[B]: frontier edge mass per lane
    used_td: jax.Array    # int32 scalar: tail top-down cohort of LAST step
    used_bu: jax.Array    # int32 scalar: tail bottom-up cohort of LAST step
    bu_hub: jax.Array       # bool[B]: NEXT step's hub-side direction
    bu_steps_hub: jax.Array  # int32[B]: hub-side bottom-up rounds
    mu_hub: jax.Array       # int32[B]: unvisited HUB edge mass (0 when off)
    nf_hub: jax.Array       # int32[B]: hub-side frontier count (0 when off)
    mf_hub: jax.Array       # int32[B]: hub-side frontier edge mass (0 = off)
    used_td_hub: jax.Array  # int32 scalar: hub top-down cohort of LAST step
    used_bu_hub: jax.Array  # int32 scalar: hub bottom-up cohort of LAST step

    def tree_flatten(self):
        return ((self.visited, self.frontier, self.parent, self.level,
                 self.cur_level, self.active, self.bu_mode, self.bu_steps,
                 self.mu, self.nf, self.mf, self.used_td, self.used_bu,
                 self.bu_hub, self.bu_steps_hub, self.mu_hub, self.nf_hub,
                 self.mf_hub, self.used_td_hub, self.used_bu_hub), None)

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(*leaves)


def init_batch(dg: DeviceGraph, cfg: BFSConfig, roots, active) -> BatchState:
    """Batched `init_state` with an activity mask.

    `roots` is int32[B] (pad lanes may repeat any valid id); `active` is
    bool[B]. Inactive (pad) lanes get an empty frontier, no visited root,
    and INT_MAX parent/level everywhere: they traverse nothing and report
    zero reached vertices. Active lanes match `init_state` bitwise. The
    first step's per-lane direction is decided here, from the same inputs
    the single-root path's first `_advance` sees.
    """
    v = dg.num_vertices
    b = roots.shape[0]
    roots = roots.astype(jnp.int32)
    active = active.astype(jnp.bool_)
    lanes = jnp.arange(b)
    on = active.astype(jnp.uint8)
    visited = jnp.zeros((b, v), jnp.uint8).at[lanes, roots].max(on)
    parent = jnp.full((b, v), INT_MAX, jnp.int32).at[lanes, roots].min(
        jnp.where(active, roots, INT_MAX))
    level = jnp.full((b, v), INT_MAX, jnp.int32).at[lanes, roots].min(
        jnp.where(active, 0, INT_MAX))
    total_e = dg.deg_ext.sum(dtype=jnp.int32)
    rdeg = dg.deg_ext[roots]
    mu = jnp.where(active, total_e - rdeg, 0)
    nf = jnp.where(active, 1, 0).astype(jnp.int32)
    mf = jnp.where(active, rdeg, 0)
    off, zi = jnp.zeros(b, jnp.bool_), jnp.zeros(b, jnp.int32)
    if cfg.hub_split:
        hub_v = _hub_row_mask(dg, cfg)
        e_hub = jnp.sum(jnp.where(hub_v, dg.deg_ext[:-1], 0), dtype=jnp.int32)
        root_hub = active & hub_v[roots]
        nf_hub = jnp.where(root_hub, 1, 0).astype(jnp.int32)
        mf_hub = jnp.where(root_hub, rdeg, 0)
        mu_hub = jnp.where(active, e_hub - mf_hub, 0)
        bu, bu_steps = _decide_direction_batch(dg, cfg, off, zi,
                                               mu - mu_hub, nf, mf)
        bu_h, steps_h = _decide_direction_batch(dg, cfg, off, zi,
                                                mu_hub, nf, mf)
    else:
        bu, bu_steps = _decide_direction_batch(dg, cfg, off, zi, mu, nf, mf)
        bu_h, steps_h = bu, bu_steps
        nf_hub = mf_hub = mu_hub = zi
    return BatchState(visited, visited, parent, level, jnp.int32(0), active,
                      bu, bu_steps, mu, nf, mf, jnp.int32(0), jnp.int32(0),
                      bu_h, steps_h, mu_hub, nf_hub, mf_hub,
                      jnp.int32(0), jnp.int32(0))


def _hub_row_mask(dg: DeviceGraph, cfg: BFSConfig):
    """bool[V]: row belongs to the hub side (degree above the snapped floor).

    The floor comes from `ell.hub_degree_floor`, so this elementwise
    predicate selects exactly the rows the kernel path's hub ELL buckets
    hold — both executions agree on side membership bitwise.
    """
    floor = ELL.hub_degree_floor(cfg.hub_deg)
    return dg.deg_ext[:-1] > floor


def _decide_direction_batch(dg: DeviceGraph, cfg: BFSConfig, bu_mode,
                            bu_steps, mu, nf, mf):
    """Vectorized `_decide_direction`: per-lane next direction + bu counter.

    Under `hub_split` this runs once per SIDE with that side's unvisited
    edge mass as `mu` (the pull-cost input is the only side-local term):
    the paper heuristic ignores `mu` — its threshold is a static fraction
    of all edges — so its sides always agree, while beamer's hub side
    flips bottom-up as soon as the hub edge mass collapses.
    """
    v = dg.num_vertices
    e = dg.num_directed_edges
    if cfg.heuristic == "topdown":
        return jnp.zeros_like(bu_mode), bu_steps
    if cfg.heuristic == "bottomup":
        return jnp.ones_like(bu_mode), bu_steps
    if cfg.heuristic == "beamer":
        go_down = ~bu_mode & (mf.astype(jnp.float32)
                              > mu.astype(jnp.float32) / cfg.alpha)
        go_up = bu_mode & (nf.astype(jnp.float32) < v / cfg.beta)
        bu = (bu_mode | go_down) & ~go_up
        return bu, jnp.where(bu, bu_steps + 1, 0)
    go_down = ~bu_mode & (mf.astype(jnp.float32) > cfg.gamma * e)
    stay_down = bu_mode & (bu_steps < cfg.fixed_bu_steps)
    bu = go_down | stay_down
    return bu, jnp.where(bu, bu_steps + 1, 0)


def _lane_by_lane(step, frontier, visited, parent, mask):
    """Run the single-root `step(f, vis, par) -> (flags, parent)` for every
    lane in `mask`, one lane after another; lanes outside it produce no
    flags and keep their parents, at no cost.

    Lanes run in sequence because the per-lane loops then keep scalar trip
    counts and plain 1-D gathers and scatters. Under `vmap` a `while_loop`
    predicate becomes batched (JAX then selects each `[B, V]` carry on
    every trip) and the gathers and scatters gain a batch dimension: on a
    v5e chip a 16-lane bottom-up level written that way took 25-40 times as
    long as a whole single-root search.
    """
    def lane(args):
        f, vis, par, on = args
        return jax.lax.cond(on, lambda: step(f, vis, par),
                            lambda: (jnp.zeros_like(f), par))

    return jax.lax.map(lane, (frontier, visited, parent, mask))


def _top_down_step_batch(dg: DeviceGraph, cfg: BFSConfig, frontier, visited,
                         parent, mask, dst_mask=None):
    """XLA push over the top-down cohort, lane by lane. `dst_mask`
    (bool[V], lane-invariant) is the split's side filter."""
    return _lane_by_lane(
        lambda f, vis, par: _top_down_step(dg, cfg, f, vis, par, dst_mask),
        frontier, visited, parent, mask)


def _bottom_up_step_batch(dg: DeviceGraph, cfg: BFSConfig, frontier, visited,
                          parent, mask, side=None, chunk=None, slab=None):
    """XLA pull over the bottom-up cohort, lane by lane. `side` (bool[V],
    lane-invariant) restricts the unvisited scan to one split side, with
    side-tuned `chunk`/`slab` geometry."""
    return _lane_by_lane(
        lambda f, vis, par: _bottom_up_step(dg, cfg, f, vis, par,
                                            row_mask=side, chunk=chunk,
                                            slab=slab),
        frontier, visited, parent, mask)


def _hub_pull_batch(dg: DeviceGraph, cfg: BFSConfig, hub_rows, frontier,
                    visited, parent, mask):
    """Dense pull over the STATIC hub row set, vmapped across lanes.

    Hub membership is a property of the graph (`deg > hub_degree_floor`),
    not of the search, so the row list has a static length (it is built once
    on the host and passed in with the graph, `CohortGraph.hub_rows`): the
    hub pull needs no queue compaction (the tail pays one O(V) compact; the
    hub none) and no chunked while-loop — one slab scan over all H rows,
    H being hundreds even at scale 22 (a row in the hub needs > floor
    edges, so H <= 2E/floor). Settled/masked rows carry degree 0 and the
    data-dependent slab cond skips them; first-hit parents are bitwise
    those of the generic chunked scan (same slot order, same argmax rule).
    """
    v = dg.num_vertices
    h = hub_rows.shape[0]
    w = min(cfg.hub_slab, max(int(dg.num_directed_edges), 1))
    rptr = dg.indptr[hub_rows]
    deg = dg.deg_ext[hub_rows]

    def one_lane(f, vis, par, m):
        rdeg = jnp.where((vis[hub_rows] == 0) & m, deg, 0)

        def slab_cond(sc):
            s, found, _ = sc
            return jnp.any(~found & (rdeg > s * w))

        def slab_body(sc):
            s, found, par_ = sc
            col = s * w + jnp.arange(w, dtype=jnp.int32)
            nidx = rptr[:, None] + col[None, :]
            nvalid = (col[None, :] < rdeg[:, None]) & ~found[:, None]
            nidx = jnp.clip(nidx, 0, max(dg.num_directed_edges - 1, 0))
            nbr = jnp.where(nvalid, dg.indices[nidx], 0)
            hit = nvalid & (f[nbr] > 0)
            anyhit = jnp.any(hit, axis=1)
            first = jnp.argmax(hit, axis=1)
            pcand = nbr[jnp.arange(h), first]
            par_ = jnp.where(~found & anyhit, pcand, par_)
            return s + 1, found | anyhit, par_

        found0 = jnp.zeros(h, bool)
        par0 = jnp.full(h, INT_MAX, jnp.int32)
        _, found, par_h = jax.lax.while_loop(
            slab_cond, slab_body, (jnp.int32(0), found0, par0))
        flags = jnp.zeros(v, jnp.uint8).at[hub_rows].max(
            found.astype(jnp.uint8))
        return flags, par.at[hub_rows].min(jnp.where(found, par_h, INT_MAX))

    return jax.vmap(one_lane)(frontier, visited, parent, mask)


def _top_down_step_kernels_batch(dg: DeviceGraph, cfg: BFSConfig, ell,
                                 frontier, visited, parent, mask,
                                 dst_mask=None):
    """Kernel push over the top-down cohort: one `topdown_batch` invocation
    per ELL bucket serves every lane; masked lanes carry zero degrees and
    their tile blocks skip the visited-gather entirely."""
    b, v = frontier.shape
    next_flags = jnp.zeros((b, v), jnp.uint8)
    pcand = jnp.full((b, v), INT_MAX, jnp.int32)
    for rows, deg, nbrs in ell:
        act = mask[:, None] & (frontier[:, rows] > 0)
        act_deg = jnp.where(act, deg[None, :], 0)
        fresh = K.topdown_batch(act_deg, nbrs, visited)      # uint8[B, R, W]
        dst = jnp.clip(nbrs, 0, v - 1)                       # lane-invariant
        if dst_mask is not None:
            fresh = fresh * dst_mask[dst][None].astype(fresh.dtype)
        next_flags = next_flags.at[:, dst].max(fresh)
        src = jnp.broadcast_to(rows[:, None], nbrs.shape)
        pcand = pcand.at[:, dst].min(
            jnp.where(fresh > 0, src[None], INT_MAX))
    parent = jnp.where(next_flags > 0, jnp.minimum(parent, pcand), parent)
    return next_flags, parent


def _bottom_up_step_kernels_batch(dg: DeviceGraph, cfg: BFSConfig, ell,
                                  frontier, visited, parent, mask,
                                  hub_kernel=False):
    """Kernel pull over the bottom-up cohort: one `bottomup_batch` invocation
    per ELL bucket; masked lanes exit after zero slabs. With `hub_kernel`,
    the side's (wide, few-row) buckets dispatch to the hub-specialized
    single-dense-pass kernel instead of the generic slab scan — same
    first-hit parents (ELL preserves CSR slot order), no slab loop."""
    b, v = frontier.shape
    next_flags = jnp.zeros((b, v), jnp.uint8)
    for rows, deg, nbrs in ell:
        act = mask[:, None] & (visited[:, rows] == 0)
        act_deg = jnp.where(act, deg[None, :], 0)
        if hub_kernel:
            found, par = K.hub_bottomup_batch(act_deg, nbrs, frontier)
        else:
            found, par = K.bottomup_batch(act_deg, nbrs, frontier,
                                          slab=min(cfg.bu_slab,
                                                   nbrs.shape[1]))
        next_flags = next_flags.at[:, rows].max(found)
        parent = parent.at[:, rows].min(jnp.where(found > 0, par, INT_MAX))
    return next_flags, parent


def _advance_batch(cfg: BFSConfig, variant: str, graph: CohortGraph,
                   st: BatchState) -> BatchState:
    """One cohort level: at most one top-down plus one bottom-up pass, each
    over its masked cohort — never both per lane.

    `variant` selects which cohorts this executable contains: the host
    driver dispatches "td" / "bu" when a level's batch is single-direction
    (the traced program then contains NO code for the other direction) and
    "mixed" when both cohorts are non-empty.

    Under `hub_split`, "single-direction" means single over every
    (lane, side) pair. "td" stays ONE unmasked push pass (both sides push:
    bitwise-identical to the unsplit level, zero split overhead); "bu"
    becomes two side-restricted pull passes — the tail's slab loop is
    bounded by the snapped hub floor and its row queue drops the
    zero-degree mass, while the few hub rows get a wide `hub_slab` scan —
    which unions to exactly the unsplit pull's flags/parents (per-row
    first hit is partition-invariant); "mixed" runs up to four side x
    direction passes, each self-annihilating when its cohort is empty.
    """
    i32 = jnp.int32
    dg, ell = graph.dg, graph.ell
    use_kernels = kernels_enabled(cfg)
    b, v = st.frontier.shape
    next_flags = jnp.zeros((b, v), jnp.uint8)
    parent = st.parent
    bu_t, bu_h = st.bu_mode, st.bu_hub
    td_t_mask = st.active & ~bu_t
    bu_t_mask = st.active & bu_t
    td_h_mask = st.active & ~bu_h
    bu_h_mask = st.active & bu_h
    if not cfg.hub_split:
        if variant in ("td", "mixed"):
            if use_kernels:
                flags, parent = _top_down_step_kernels_batch(
                    dg, cfg, ell, st.frontier, st.visited, parent, td_t_mask)
            else:
                flags, parent = _top_down_step_batch(
                    dg, cfg, st.frontier, st.visited, parent, td_t_mask)
            next_flags = jnp.maximum(next_flags, flags)
        if variant in ("bu", "mixed"):
            if use_kernels:
                flags, parent = _bottom_up_step_kernels_batch(
                    dg, cfg, ell, st.frontier, st.visited, parent, bu_t_mask)
            else:
                flags, parent = _bottom_up_step_batch(
                    dg, cfg, st.frontier, st.visited, parent, bu_t_mask)
            next_flags = jnp.maximum(next_flags, flags)
    else:
        hub_v = _hub_row_mask(dg, cfg)
        tail_pull = ~hub_v & (dg.deg_ext[:-1] > 0)   # deg-0 rows never pull
        # The hub row LIST is static (graph property, not search state):
        # built once on the host (`hub_rows`) and passed in with the graph.
        hub_list = graph.hub_rows
        if use_kernels:
            ell_tail, ell_hub = ELL.split_tiles(ell, cfg.hub_deg)

        def push(par, lane_mask, dst_mask):
            if use_kernels:
                return _top_down_step_kernels_batch(
                    dg, cfg, ell, st.frontier, st.visited, par, lane_mask,
                    dst_mask)
            return _top_down_step_batch(
                dg, cfg, st.frontier, st.visited, par, lane_mask, dst_mask)

        def pull(par, lane_mask, hub_side):
            if use_kernels:
                return _bottom_up_step_kernels_batch(
                    dg, cfg, ell_hub if hub_side else ell_tail, st.frontier,
                    st.visited, par, lane_mask, hub_kernel=hub_side)
            if hub_side:
                if hub_list.shape[0] == 0:
                    return jnp.zeros_like(st.frontier), par
                return _hub_pull_batch(dg, cfg, hub_list, st.frontier,
                                       st.visited, par, lane_mask)
            # Tail-tuned chunking is the split's other XLA win: tail rows
            # are degree-bounded by the snapped hub floor, so one wide row
            # can no longer convoy a whole chunk through hundreds of slab
            # iterations — the tail safely takes chunks 4x wider (fewer
            # while-loop trips over the big unvisited queue). Chunk/slab
            # regrouping never changes first-hit parents.
            return _bottom_up_step_batch(
                dg, cfg, st.frontier, st.visited, par, lane_mask,
                side=tail_pull, chunk=4 * cfg.bu_chunk, slab=cfg.bu_slab)

        if variant == "td":
            # Both sides push: one unmasked pass covers hub + tail targets.
            flags, parent = push(parent, td_t_mask, None)
            next_flags = jnp.maximum(next_flags, flags)
        else:
            if variant == "mixed":
                flags, parent = push(parent, td_t_mask, ~hub_v)
                next_flags = jnp.maximum(next_flags, flags)
                flags, parent = push(parent, td_h_mask, hub_v)
                next_flags = jnp.maximum(next_flags, flags)
            flags, parent = pull(parent, bu_t_mask, False)
            next_flags = jnp.maximum(next_flags, flags)
            flags, parent = pull(parent, bu_h_mask, True)
            next_flags = jnp.maximum(next_flags, flags)
    if use_kernels:
        _, nf, mf = K.frontier_fused_batch(next_flags, dg.deg_ext[:-1])
    else:
        nf = jnp.sum(next_flags, axis=1, dtype=i32)
        mf = jnp.sum(jnp.where(next_flags > 0, dg.deg_ext[:-1][None, :], 0),
                     axis=1, dtype=i32)
    cur = st.cur_level + 1
    visited = jnp.maximum(st.visited, next_flags)
    level = jnp.where(next_flags > 0, cur, st.level)
    mu = st.mu - mf
    max_levels = cfg.max_levels or dg.num_vertices
    active = st.active & (nf > 0) & (cur < max_levels)
    if cfg.hub_split:
        hub_row = _hub_row_mask(dg, cfg)[None, :]
        nf_hub = jnp.sum(next_flags * hub_row.astype(jnp.uint8),
                         axis=1, dtype=i32)
        mf_hub = jnp.sum(jnp.where((next_flags > 0) & hub_row,
                                   dg.deg_ext[:-1][None, :], 0),
                         axis=1, dtype=i32)
        mu_hub = st.mu_hub - mf_hub
        bu2, steps2 = _decide_direction_batch(dg, cfg, bu_t, st.bu_steps,
                                              mu - mu_hub, nf, mf)
        bu_h2, steps_h2 = _decide_direction_batch(
            dg, cfg, bu_h, st.bu_steps_hub, mu_hub, nf, mf)
    else:
        bu2, steps2 = _decide_direction_batch(dg, cfg, bu_t, st.bu_steps,
                                              mu, nf, mf)
        bu_h2, steps_h2 = bu2, steps2
        nf_hub = mf_hub = mu_hub = jnp.zeros(b, i32)
    return BatchState(visited, next_flags, parent, level, cur, active,
                      bu2, steps2, mu, nf, mf,
                      jnp.sum(td_t_mask.astype(i32)),
                      jnp.sum(bu_t_mask.astype(i32)),
                      bu_h2, steps_h2, mu_hub, nf_hub, mf_hub,
                      jnp.sum(td_h_mask.astype(i32)) if cfg.hub_split
                      else jnp.int32(0),
                      jnp.sum(bu_h_mask.astype(i32)) if cfg.hub_split
                      else jnp.int32(0))


def reachable_variants(cfg: BFSConfig) -> tuple[str, ...]:
    """Step variants `_decide_direction_batch` can actually produce.

    The forced heuristics pin every lane to one direction, so only that
    variant's executable can ever be dispatched — compiling the others
    would be pure warm-up cost (the adaptive heuristics need all three).
    """
    if cfg.heuristic == "topdown":
        return ("td",)
    if cfg.heuristic == "bottomup":
        return ("bu",)
    return BATCH_VARIANTS


def make_batch_step(cfg: BFSConfig, variant: str):
    """Raw traceable `(CohortGraph, BatchState) -> BatchState` for one cohort
    step variant.

    `variant` is one of `BATCH_VARIANTS` ("td" | "bu" | "mixed"); the engine
    compiles all three per (config, batch bucket) and the driver backend
    dispatches whichever matches the level's cohort occupancy. Jit-wrap the
    result yourself (`repro.engine` caches it on the session) and pass the
    graph (`CohortGraph`; `GraphSession.cohort_graph` builds it) on every
    call: it is an argument of the executable, so one compiled step serves
    any graph of the same shapes.
    """
    if variant not in BATCH_VARIANTS:
        raise ValueError(f"variant must be one of {BATCH_VARIANTS}, "
                         f"got {variant!r}")
    return functools.partial(_advance_batch, cfg, variant)


def batch_scalars(st: BatchState) -> dict:
    """Per-level host-sync payload for the batched driver backend.

    Everything the host needs each level — loop condition, next-step cohort
    occupancy (the executable-variant choice), last-step direction split,
    and the per-lane statistics for streaming/observability — in ONE
    `jax.device_get`-able dict. `nf`/`mf` count ACTIVE lanes only, so the
    driver's `nf > 0` loop condition terminates when every lane finished
    even if finished lanes still hold a non-empty final frontier.

    Direction-occupancy keys are SIDE-AWARE: `td_next`/`bu_next` count
    active lanes with ANY side in that direction (under `hub_split` a lane
    can be in both when its sides disagree; with the split off `bu_hub`
    mirrors `bu_mode` and the counts collapse to the unsplit schema), and
    the `*_hub` keys expose the hub side's cohort sizes and frontier mass
    for the per-level occupancy rows.
    """
    act = st.active
    i32 = jnp.int32
    return dict(
        nf=jnp.sum(jnp.where(act, st.nf, 0), dtype=i32),
        mf=jnp.sum(jnp.where(act, st.mf, 0), dtype=i32),
        cur=st.cur_level,
        bu=jnp.any(act & (st.bu_mode | st.bu_hub)),
        td_next=jnp.sum((act & (~st.bu_mode | ~st.bu_hub)).astype(i32)),
        bu_next=jnp.sum((act & (st.bu_mode | st.bu_hub)).astype(i32)),
        active_n=jnp.sum(act.astype(i32)),
        used_td=st.used_td,
        used_bu=st.used_bu,
        used_td_hub=st.used_td_hub,
        used_bu_hub=st.used_bu_hub,
        nf_hub=jnp.sum(jnp.where(act, st.nf_hub, 0), dtype=i32),
        mf_hub=jnp.sum(jnp.where(act, st.mf_hub, 0), dtype=i32),
        nf_lanes=st.nf,
        mf_lanes=st.mf,
        bu_lanes=st.bu_mode,
        hub_bu_lanes=st.bu_hub,
        nf_hub_lanes=st.nf_hub,
        active_lanes=act,
    )


def finalize(st: BFSState) -> tuple[np.ndarray, np.ndarray]:
    """Sentinels -> Graph500 conventions (-1 for unreached).

    Works on a `BFSState` ([V] arrays) or a `BatchState` ([B, V] arrays)."""
    with span("repro.result.transfer",
              bytes=int(st.parent.nbytes + st.level.nbytes)):
        parent = np.asarray(st.parent)
        level = np.asarray(st.level)
    with span("repro.result.convert"):
        parent = np.where(parent == INT_MAX, -1, parent)
        level = np.where(level == INT_MAX, -1, level)
        return parent.astype(np.int32), level.astype(np.int32)


def bfs(g: Graph | DeviceGraph, root: int,
        cfg: BFSConfig = BFSConfig()) -> tuple[np.ndarray, np.ndarray]:
    """Run a full direction-optimized BFS; returns (parent, level).

    One-shot convenience: pass a `DeviceGraph` (or use `repro.engine`) for
    repeated queries — the ELL tiles the kernel path needs are cached on the
    `DeviceGraph` instance, and a fresh `Graph` conversion rebuilds them.
    """
    dg = g if isinstance(g, DeviceGraph) else DeviceGraph.from_graph(g)
    ell = _resolve_ell(dg, cfg, None)
    st = _bfs_jit(dg, jnp.int32(root), cfg, ell)
    return finalize(st)


def bfs_instrumented(g: Graph | DeviceGraph, root: int,
                     cfg: BFSConfig = BFSConfig()):
    """Level-by-level search over the shared `LevelDriver`.

    Returns (parent, level, per_level_stats) where stats rows follow the
    driver schema (level, direction, frontier_size, frontier_edges,
    seconds, compute_s, exchange_s). Used by the Fig-1/Fig-4 benchmarks.
    The loop itself lives in `repro.engine.level_loop` (imported lazily:
    `repro.engine` imports this module at package init).
    """
    from repro.engine.level_loop import LevelDriver, SingleStepBackend
    dg = g if isinstance(g, DeviceGraph) else DeviceGraph.from_graph(g)
    backend = SingleStepBackend(make_init(dg), make_level_step(dg, cfg),
                                dg.num_vertices)
    parent, level, stats, _timings = LevelDriver(backend).run(int(root))
    return parent, level, stats
