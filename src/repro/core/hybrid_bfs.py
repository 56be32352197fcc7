"""Partitioned direction-optimized BFS under `shard_map` (paper Alg. 1–3).

BSP structure, faithful to §3.1:

* Every device owns a partition's rows (CSR block with *global* columns) and
  keeps replicated `visited`/`frontier` flags over the global (padded) id
  space. The once-per-round **push** (after top-down) and **pull** (before
  bottom-up consumption) of Algorithms 2/3 are realized as a single bitwise
  OR all-reduce of the next-frontier flags — fixed-size, batched, exactly one
  collective per BSP round (the paper's batch-communication optimization).
* **Deferred parent aggregation** (§3.1): during traversal each device only
  scatters parent *candidates* into a device-local array; one min-all-reduce
  after termination assembles the BFS tree. Only visited bits travel per
  round.
* **Direction switching** (§3.3): every device evaluates the switch statistic
  locally. In `coordinator="hub"` mode the statistic uses only the hub slice
  of the frontier (ids < hub_count) — the paper's trick that the hubs alone
  predict frontier growth, so no extra collective or vote is ever issued; the
  bottom-up→top-down return is a fixed step count, also communication-free.

The per-level compute mirrors `bfs.py` (chunked push queue; slab pull with
block early exit) but runs on the device's `local_row_gid` row set, which
uniformly expresses owned leaves, the hub0 layout, and delegated hub slices
(see `partition.py`).

Like `bfs.py`, every per-level step has two interchangeable formulations:
the XLA reference loops and a Pallas kernel path
(`BFSConfig.backend_kernels`) over per-device ELL tiles. On the kernel path
the per-level frontier statistics (count, edge mass, packed bitmap) come
from one fused VMEM pass (`kernels.ops.frontier_fused`) and are carried in
the BSP loop state, and the `exchange="bitmap"` collective consumes the
kernel's already-packed words instead of re-packing.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import ell as ELL
from repro.core import frontier as fr
from repro.core.bfs import BFSConfig, INT_MAX, kernels_enabled
from repro.core.partition import PartitionedGraph, PartitionPlan, unpermute, unpermute_ids
from repro.kernels import ops as K


@dataclasses.dataclass(frozen=True)
class HybridConfig:
    bfs: BFSConfig = BFSConfig()
    coordinator: str = "hub"      # "hub" (paper §3.3) | "global"
    exchange: str = "psum"        # "psum" (uint8 flags) | "bitmap" (packed OR)
    axis_name: str = "part"


# ------------------------------------------------------------- collectives --

def _or_exchange(flags: jax.Array, cfg: HybridConfig,
                 packed: Optional[jax.Array] = None) -> jax.Array:
    """Merge per-device next-frontier flags: the push/pull of Algs. 2/3.

    `packed` short-circuits the pack pass when the caller already holds the
    bitmap words (the kernel path's fused frontier pass emits them for free).
    """
    ax = cfg.axis_name
    if cfg.exchange == "psum":
        # Sum of 0/1 contributions then clamp. Wire: one V-byte ring reduce.
        summed = jax.lax.psum(flags.astype(jnp.int32), ax)
        return (summed > 0).astype(jnp.uint8)
    # Packed-bitmap variant: V/8 bytes per hop, OR-folded after all-gather.
    if packed is None:
        packed = fr.pack(flags)
    gathered = jax.lax.all_gather(packed, ax)          # [P, W]
    merged = jax.lax.reduce(gathered, np.uint32(0), jax.lax.bitwise_or, (0,))
    return fr.unpack(merged, flags.shape[0])


# ---------------------------------------------------------------- per-level --

def _local_top_down(pg_shapes, cfg: BFSConfig, indptr, indices, row_gid,
                    visited, frontier):
    """Push step over this device's rows. Returns (next_flags, parent_cand)."""
    v_pad, r, e_local = pg_shapes
    c = cfg.td_chunk
    # Local rows whose global id is in the frontier (phantoms map to fill 0).
    frontier_ext = jnp.concatenate([frontier, jnp.zeros(1, jnp.uint8)])
    row_active = frontier_ext[jnp.minimum(row_gid, v_pad)]
    queue, _n = fr.compact(row_active)                 # local row indices; fill==r
    ldeg = indptr[1:] - indptr[:-1]
    ldeg_ext = jnp.concatenate([ldeg, jnp.zeros(1, jnp.int32)])
    degq = ldeg_ext[jnp.minimum(queue, r)]
    cum = jnp.cumsum(degq, dtype=jnp.int32)
    total = cum[-1]

    def body(carry):
        base, next_flags, pcand = carry
        slots = base + jnp.arange(c, dtype=jnp.int32)
        valid = slots < total
        owner = jnp.searchsorted(cum, slots, side="right").astype(jnp.int32)
        owner = jnp.minimum(owner, r - 1)
        lrow = jnp.minimum(queue[owner], r - 1)
        start = cum[owner] - degq[owner]
        eidx = jnp.clip(indptr[lrow] + (slots - start), 0, e_local - 1)
        dst = jnp.where(valid, indices[eidx], 0)
        fresh = valid & (visited[dst] == 0)
        src_gid = row_gid[lrow]
        next_flags = next_flags.at[dst].max(fresh.astype(jnp.uint8))
        pcand = pcand.at[dst].min(jnp.where(fresh, src_gid, INT_MAX))
        return base + c, next_flags, pcand

    init = (jnp.int32(0), jnp.zeros(v_pad, jnp.uint8),
            jnp.full(v_pad, INT_MAX, jnp.int32))
    _, next_flags, pcand = jax.lax.while_loop(
        lambda cy: cy[0] < total, body, init)
    return next_flags, pcand


def _local_bottom_up(pg_shapes, cfg: BFSConfig, indptr, indices, row_gid,
                     visited, frontier):
    """Pull step over this device's unvisited rows (slab early exit).

    Under `cfg.hub_split` the local row queue splits by the snapped hub
    degree floor into a tail pass (degree-bounded rows, 4x wider chunks —
    no convoy risk) and a hub pass (few very-wide rows, small chunks of
    `hub_slab`-wide scans), and zero-degree rows leave the queue entirely.
    Pure load-balance reorganization: per-row first-hit parents are
    invariant under any partition of the rows, so the union of the two
    passes is bitwise the unsplit pull. (The BSP path keeps ONE direction
    decision — per-side asymmetric choice lives on the fused cohort path.)
    """
    v_pad, r, e_local = pg_shapes
    visited_ext = jnp.concatenate([visited, jnp.ones(1, jnp.uint8)])  # phantom=visited
    row_unvisited = (visited_ext[jnp.minimum(row_gid, v_pad)] == 0)
    ldeg = indptr[1:] - indptr[:-1]
    ldeg_ext = jnp.concatenate([ldeg, jnp.zeros(1, jnp.int32)])

    def pull_pass(row_sel, rc, w, next_flags, pcand):
        queue, m = fr.compact(row_sel.astype(jnp.uint8))  # local idx; fill==r

        def chunk_body(carry):
            base, next_flags, pcand = carry
            lrows = jax.lax.dynamic_slice(queue, (base,), (rc,))
            rdeg = ldeg_ext[jnp.minimum(lrows, r)]
            lrows_c = jnp.minimum(lrows, r - 1)
            rptr = indptr[lrows_c]
            gid = row_gid[lrows_c]                      # scatter target (global)

            def slab_cond(sc):
                s, found, _ = sc
                return jnp.any(~found & (rdeg > s * w))

            def slab_body(sc):
                s, found, par = sc
                col = s * w + jnp.arange(w, dtype=jnp.int32)
                nvalid = (col[None, :] < rdeg[:, None]) & ~found[:, None]
                nidx = jnp.clip(rptr[:, None] + col[None, :], 0, e_local - 1)
                nbr = jnp.where(nvalid, indices[nidx], 0)
                hit = nvalid & (frontier[nbr] > 0)
                anyhit = jnp.any(hit, axis=1)
                first = jnp.argmax(hit, axis=1)
                pc = nbr[jnp.arange(rc), first]
                par = jnp.where(~found & anyhit, pc, par)
                return s + 1, found | anyhit, par

            _, found, par = jax.lax.while_loop(
                slab_cond, slab_body,
                (jnp.int32(0), jnp.zeros(rc, bool),
                 jnp.full(rc, INT_MAX, jnp.int32)))
            found = found & (lrows < r)
            tgt = jnp.where(lrows < r, gid, v_pad)      # drop fill rows
            next_flags = next_flags.at[tgt].max(found.astype(jnp.uint8),
                                                mode="drop")
            pcand = pcand.at[tgt].min(jnp.where(found, par, INT_MAX),
                                      mode="drop")
            return base + rc, next_flags, pcand

        _, next_flags, pcand = jax.lax.while_loop(
            lambda cy: cy[0] < m, chunk_body, (jnp.int32(0), next_flags,
                                               pcand))
        return next_flags, pcand

    next_flags = jnp.zeros(v_pad, jnp.uint8)
    pcand = jnp.full(v_pad, INT_MAX, jnp.int32)
    if not cfg.hub_split:
        return pull_pass(row_unvisited, min(cfg.bu_chunk, r), cfg.bu_slab,
                         next_flags, pcand)
    floor = ELL.hub_degree_floor(cfg.hub_deg)
    tail_sel = row_unvisited & (ldeg > 0) & (ldeg <= floor)
    hub_sel = row_unvisited & (ldeg > floor)
    next_flags, pcand = pull_pass(tail_sel, min(4 * cfg.bu_chunk, r),
                                  cfg.bu_slab, next_flags, pcand)
    return pull_pass(hub_sel, min(cfg.bu_chunk, 128, r), cfg.hub_slab,
                     next_flags, pcand)


# ------------------------------------------------------- kernel-path steps --
#
# Pallas-backed formulations of the local steps, over per-device ELL tiles
# (`ell.build_hybrid_ell`). Inactive rows are masked to degree 0 instead of
# being compacted away; padding rows carry gid == v_pad and are discarded by
# the mode="drop" scatters. Tiles preserve local CSR slot order, so parent
# candidates match the XLA slab scan bitwise.

def _unstack_ell(ell):
    """Per-device view inside shard_map: drop the leading [1, ...] axis."""
    return tuple(ELL.EllBucket(b.rows.reshape(b.rows.shape[-1]),
                               b.deg.reshape(b.deg.shape[-1]),
                               b.nbrs.reshape(b.nbrs.shape[-2:]))
                 for b in ell)


def _local_top_down_kernels(pg_shapes, cfg: BFSConfig, ell, visited, frontier):
    """Push step via `kernels.ops.topdown`; scatter-max/min stays in XLA."""
    v_pad, _r, _e = pg_shapes
    frontier_ext = jnp.concatenate([frontier, jnp.zeros(1, jnp.uint8)])
    next_flags = jnp.zeros(v_pad, jnp.uint8)
    pcand = jnp.full(v_pad, INT_MAX, jnp.int32)
    for gid, deg, nbrs in ell:
        # padding rows carry gid == v_pad exactly -> the _ext sentinel slot
        act_deg = jnp.where(frontier_ext[gid] > 0, deg, 0)
        fresh, dst = K.topdown(act_deg, nbrs, visited)
        next_flags = next_flags.at[dst].max(fresh)
        src = jnp.broadcast_to(gid[:, None], dst.shape)
        pcand = pcand.at[dst].min(jnp.where(fresh > 0, src, INT_MAX))
    return next_flags, pcand


def _local_bottom_up_kernels(pg_shapes, cfg: BFSConfig, ell, visited, frontier):
    """Pull step via `kernels.ops.bottomup` (block early exit per tile)."""
    v_pad, _r, _e = pg_shapes
    visited_ext = jnp.concatenate([visited, jnp.ones(1, jnp.uint8)])
    next_flags = jnp.zeros(v_pad, jnp.uint8)
    pcand = jnp.full(v_pad, INT_MAX, jnp.int32)
    for gid, deg, nbrs in ell:
        act_deg = jnp.where(visited_ext[gid] == 0, deg, 0)
        found, par = K.bottomup(act_deg, nbrs, frontier,
                                slab=min(cfg.bu_slab, nbrs.shape[1]))
        next_flags = next_flags.at[gid].max(found, mode="drop")
        pcand = pcand.at[gid].min(jnp.where(found > 0, par, INT_MAX),
                                  mode="drop")
    return next_flags, pcand


def _frontier_stats(use_kernels: bool, flags, deg, dec_hub: int):
    """(nf, mf_full, mf_dec) of `flags` in as few V-passes as possible.

    Kernel path: one fused VMEM pass (`ops.frontier_fused`); XLA path: two
    reductions. `dec_hub` > 0 restricts the §3.3 decision statistic to the
    hub slice — a static id *prefix* [0, dec_hub), so it costs an
    O(hub_count) slice reduction, not a second V-pass (0 = decide on the
    full edge mass).
    """
    if use_kernels:
        _, nf, mf_full = K.frontier_fused(flags, deg)
    else:
        nf = fr.count(flags)
        mf_full = fr.edge_count(flags, deg)
    if not dec_hub:
        return nf, mf_full, mf_full
    return nf, mf_full, fr.edge_count(flags[:dec_hub], deg[:dec_hub])


def _dec_hub(hcfg: HybridConfig, hub_count: int) -> int:
    """Hub-slice length for the decision statistic (0 = use full mass)."""
    return hub_count if hcfg.coordinator == "hub" else 0


def _init_mf_dec(root, deg, dec_hub: int):
    """Decision statistic of the initial {root} frontier."""
    return jnp.where(root < dec_hub, deg[root], 0) if dec_hub else deg[root]


def _hybrid_ell(pg: PartitionedGraph, cfg: BFSConfig):
    """Stacked per-device tiles for the kernel path; () when XLA runs."""
    return ELL.build_hybrid_ell(pg) if kernels_enabled(cfg) else ()


# -------------------------------------------------------------- level loop --

def _decide(hcfg: HybridConfig, cfg: BFSConfig, v_pad, e_total,
            nf, mf, bu_mode, bu_steps, mu):
    """Direction decision; identical on every device (no collective).

    `nf`/`mf` are the carried frontier statistics — computed once when the
    frontier was produced (§3.3 hub-slice mf under the hub coordinator), not
    re-scanned here.
    """
    if cfg.heuristic == "topdown":
        return jnp.bool_(False), bu_steps
    if cfg.heuristic == "beamer":
        go_down = ~bu_mode & (mf.astype(jnp.float32) > mu.astype(jnp.float32) / cfg.alpha)
        go_up = bu_mode & (nf.astype(jnp.float32) < v_pad / cfg.beta)
        bu = (bu_mode | go_down) & ~go_up
        return bu, jnp.where(bu, bu_steps + 1, 0)
    go_down = ~bu_mode & (mf.astype(jnp.float32) > cfg.gamma * e_total)
    stay_down = bu_mode & (bu_steps < cfg.fixed_bu_steps)
    bu = go_down | stay_down
    return bu, jnp.where(bu, bu_steps + 1, 0)


def _device_bfs(pg_shapes, e_total, hub_count, hcfg: HybridConfig,
                indptr, indices, row_gid, deg_ext, ell, root):
    """Whole-search body run per device inside shard_map."""
    v_pad, r, e_local = pg_shapes
    cfg = hcfg.bfs
    use_kernels = kernels_enabled(cfg)
    indptr = indptr.reshape(-1)
    indices = indices.reshape(-1)
    row_gid = row_gid.reshape(-1)
    ell = _unstack_ell(ell)
    deg = deg_ext[:-1]
    dec_hub = _dec_hub(hcfg, hub_count)

    visited = jnp.zeros(v_pad, jnp.uint8).at[root].set(1)
    frontier = visited
    pcand = jnp.full(v_pad, INT_MAX, jnp.int32).at[root].set(root)
    lcand = jnp.full(v_pad, INT_MAX, jnp.int32).at[root].set(0)
    mu = deg.sum(dtype=jnp.int32) - deg_ext[root]
    nf0 = jnp.int32(1)
    mf0 = _init_mf_dec(root, deg, dec_hub)

    def level(carry):
        (visited, frontier, pcand, lcand, cur, bu_mode, bu_steps, mu,
         nf, mf_dec) = carry
        bu, bu_steps = _decide(hcfg, cfg, v_pad, e_total,
                               nf, mf_dec, bu_mode, bu_steps, mu)
        if use_kernels:
            nxt_local, pc_local = jax.lax.cond(
                bu,
                lambda: _local_bottom_up_kernels(pg_shapes, cfg, ell,
                                                 visited, frontier),
                lambda: _local_top_down_kernels(pg_shapes, cfg, ell,
                                                visited, frontier))
        else:
            nxt_local, pc_local = jax.lax.cond(
                bu,
                lambda: _local_bottom_up(pg_shapes, cfg, indptr, indices,
                                         row_gid, visited, frontier),
                lambda: _local_top_down(pg_shapes, cfg, indptr, indices,
                                        row_gid, visited, frontier))
        # ---- the one collective per BSP round (Algorithms 2/3) ----
        if use_kernels and hcfg.exchange == "bitmap":
            # The fused pass emits the wire words; no separate pack pass.
            packed_local, _, _ = K.frontier_fused(nxt_local, deg)
            nxt = _or_exchange(nxt_local, hcfg, packed=packed_local)
        else:
            nxt = _or_exchange(nxt_local, hcfg)
        newly = jnp.where(visited > 0, 0, nxt).astype(jnp.uint8)
        pcand = jnp.where(newly > 0, jnp.minimum(pcand, pc_local), pcand)
        lcand = jnp.where(newly > 0, jnp.minimum(lcand, cur + 1), lcand)
        visited = jnp.maximum(visited, newly)
        nf, mf_full, mf_dec = _frontier_stats(use_kernels, newly, deg, dec_hub)
        mu = mu - mf_full
        return (visited, newly, pcand, lcand, cur + 1, bu, bu_steps, mu,
                nf, mf_dec)

    def cond(carry):
        nf, cur = carry[8], carry[4]
        return (nf > 0) & (cur < v_pad)

    carry = (visited, frontier, pcand, lcand, jnp.int32(0),
             jnp.bool_(False), jnp.int32(0), mu, nf0, mf0)
    visited, _, pcand, lcand, levels, _, _, _, _, _ = jax.lax.while_loop(
        cond, level, carry)
    # ---- deferred parent aggregation (§3.1): one min-reduce at the end ----
    parent = jax.lax.pmin(pcand, hcfg.axis_name)
    level_arr = jax.lax.pmin(lcand, hcfg.axis_name)
    return parent, level_arr, levels


def default_mesh(n_parts: int, axis_name: str = "part") -> Mesh:
    """1-D mesh over the first `n_parts` devices (helpful error otherwise)."""
    devs = jax.devices()
    if len(devs) < n_parts:
        raise RuntimeError(
            f"need {n_parts} devices for {n_parts} partitions, have "
            f"{len(devs)} {devs[0].platform} device(s); on a CPU host, "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n_parts} "
            f"emulates them")
    return Mesh(np.array(devs[:n_parts]), (axis_name,))


def make_root_mapper(plan: PartitionPlan):
    """Returns orig-id -> new-id root translation for a partition plan."""
    inv = np.full(plan.v_orig, -1, dtype=np.int64)
    real = plan.perm_new_to_old >= 0
    inv[plan.perm_new_to_old[real]] = np.flatnonzero(real)

    def root_mapper(root_orig: int) -> int:
        root_new = int(inv[root_orig])
        assert root_new >= 0, f"root {root_orig} not in plan"
        return root_new

    return root_mapper


class HybridGraph(NamedTuple):
    """A partitioning's device arrays: the sharded programs' graph arguments.

    The `[P, ...]` arrays (and the stacked ELL tiles) are split over the mesh
    axis, one partition per device; `deg_ext` is replicated. Passed to every
    compiled sharded program as an argument, never closed over.
    """
    indptr: jax.Array     # int32[P, R+1]
    indices: jax.Array    # int32[P, Emax]
    row_gid: jax.Array    # int32[P, R]
    deg_ext: jax.Array    # int32[v_pad+1]
    ell: tuple            # stacked per-device ELL tiles; () on the XLA path


@dataclasses.dataclass(frozen=True)
class HybridShapes:
    """The static facts a sharded program is compiled for."""
    v_pad: int
    rows: int             # local rows per device (R)
    e_local: int          # padded local edge slots per device (Emax)
    e_total: int          # directed edges of the whole graph
    hub_count: int

    @classmethod
    def of(cls, pg: PartitionedGraph) -> "HybridShapes":
        return cls(pg.plan.v_pad, pg.num_local_rows,
                   pg.local_indices.shape[1], pg.total_directed_edges,
                   pg.plan.hub_count)

    @property
    def local(self) -> tuple:
        return (self.v_pad, self.rows, self.e_local)


def place_hybrid_graph(pg: PartitionedGraph, mesh: Mesh, axis_name: str,
                       ell=()) -> HybridGraph:
    """Commit a partitioning to the mesh: partition p's rows on device p."""
    split = NamedSharding(mesh, P(axis_name))
    rep = NamedSharding(mesh, P())
    return HybridGraph(
        indptr=jax.device_put(pg.local_indptr, split),
        indices=jax.device_put(pg.local_indices, split),
        row_gid=jax.device_put(pg.local_row_gid, split),
        deg_ext=jax.device_put(pg.deg_ext, rep),
        ell=jax.device_put(ell, split))


def hybrid_search_program(shapes: HybridShapes, hcfg: HybridConfig,
                          mesh: Mesh):
    """The partitioned whole search as a pure traceable function
    `(HybridGraph, root_new) -> (parent_new, level_new, levels)`."""
    fn = functools.partial(_device_bfs, shapes.local, shapes.e_total,
                           shapes.hub_count, hcfg)
    ax = hcfg.axis_name
    shmapped = jax.shard_map(
        fn, mesh=mesh,
        in_specs=(P(ax), P(ax), P(ax), P(), P(ax), P()),
        out_specs=(P(), P(), P()), check_vma=False)

    def search_fn(graph: HybridGraph, root_new):
        return shmapped(graph.indptr, graph.indices, graph.row_gid,
                        graph.deg_ext, graph.ell,
                        jnp.asarray(root_new, jnp.int32))

    return search_fn


def finalize_hybrid(plan: PartitionPlan, parent_new, level_new):
    """Padded new-id results -> original ids, Graph500 conventions (-1)."""
    parent_new = np.asarray(parent_new)
    level_new = np.asarray(level_new)
    parent_new = np.where(parent_new == INT_MAX, -1, parent_new)
    level_new = np.where(level_new == INT_MAX, -1, level_new)
    parent = unpermute_ids(plan, parent_new)
    level = unpermute(plan, level_new.astype(np.int64)).astype(np.int32)
    return parent.astype(np.int32), level


def hybrid_bfs(pg: PartitionedGraph, root_orig: int,
               hcfg: HybridConfig = HybridConfig(),
               mesh: Optional[Mesh] = None):
    """Run the partitioned BFS on `pg.n_parts` devices; returns orig-id results.

    `root_orig` is in original vertex ids; results are mapped back through the
    plan's permutation (parents as original ids, -1 unreached). One-shot
    convenience: places the graph and compiles per call. For repeated
    queries use `repro.engine`, which caches the placed graph
    (`GraphSession.hybrid_graph`) and the `hybrid_search_program` executable.
    """
    if mesh is None:
        mesh = default_mesh(pg.plan.n_parts, hcfg.axis_name)
    graph = place_hybrid_graph(pg, mesh, hcfg.axis_name,
                               _hybrid_ell(pg, hcfg.bfs))
    run = jax.jit(hybrid_search_program(HybridShapes.of(pg), hcfg, mesh))
    root = jnp.int32(make_root_mapper(pg.plan)(root_orig))
    parent_new, level_new, levels = run(graph, root)
    parent, level = finalize_hybrid(pg.plan, parent_new, level_new)
    return parent, level, int(levels)


# -------------------------------------------------- instrumented BSP loop --

def make_hybrid_stepper(pg: PartitionedGraph, hcfg: HybridConfig,
                        mesh: Optional[Mesh] = None,
                        graph: Optional[HybridGraph] = None):
    """Level-by-level driver pieces for the Fig. 3/4 benchmarks.

    Returns (init_fn, compute_fn, exchange_fn, finalize_fn, root_mapper):
    `compute_fn` runs one level's local TD/BU work on every partition (no
    communication); `exchange_fn` is exactly the per-round push/pull merge +
    state update; `finalize_fn` yields (parent_new, level_new) in the padded
    id space (map back with `finalize_hybrid`). Timing compute vs exchange
    separately reproduces the paper's computation-vs-communication breakdown
    with real collectives.

    State carries the frontier statistics (`nf` full count, `mf` full edge
    mass, `mf_dec` the direction-decision statistic) so the host loop reads
    two scalars per level instead of re-reducing the V-byte frontier.

    `graph` is the partitioning already committed to `mesh`
    (`place_hybrid_graph`; `GraphSession.hybrid_graph` caches one); it is
    placed here when omitted. The returned pieces bind it as an argument
    of their jitted programs.
    """
    plan = pg.plan
    n = plan.n_parts
    if mesh is None:
        mesh = default_mesh(n, hcfg.axis_name)
    shapes = HybridShapes.of(pg)
    v_pad, pg_shapes = shapes.v_pad, shapes.local
    cfg = hcfg.bfs
    use_kernels = kernels_enabled(cfg)
    ax = hcfg.axis_name
    if graph is None:
        graph = place_hybrid_graph(pg, mesh, ax,
                                   _hybrid_ell(pg, cfg))
    dec_hub = _dec_hub(hcfg, plan.hub_count)

    @jax.jit
    def init_prog(g: HybridGraph, root):
        deg = g.deg_ext[:-1]
        visited = jnp.zeros(v_pad, jnp.uint8).at[root].set(1)
        pcand = jnp.full((n, v_pad), INT_MAX, jnp.int32).at[:, root].set(root)
        lcand = jnp.full(v_pad, INT_MAX, jnp.int32).at[root].set(0)
        mu = deg.sum(dtype=jnp.int32) - g.deg_ext[root]
        return dict(visited=visited, frontier=visited, pcand=pcand,
                    lcand=lcand, cur=jnp.int32(0), bu=jnp.bool_(False),
                    bu_steps=jnp.int32(0), mu=mu, nf=jnp.int32(1),
                    mf=deg[root], mf_dec=_init_mf_dec(root, deg, dec_hub))

    def _compute(indptr, indices, row_gid, ell_dev, visited, frontier, bu):
        indptr, indices, row_gid = (indptr.reshape(-1), indices.reshape(-1),
                                    row_gid.reshape(-1))
        if use_kernels:
            ell_local = _unstack_ell(ell_dev)
            nxt, pc = jax.lax.cond(
                bu,
                lambda: _local_bottom_up_kernels(pg_shapes, cfg, ell_local,
                                                 visited, frontier),
                lambda: _local_top_down_kernels(pg_shapes, cfg, ell_local,
                                                visited, frontier))
        else:
            nxt, pc = jax.lax.cond(
                bu,
                lambda: _local_bottom_up(pg_shapes, cfg, indptr, indices,
                                         row_gid, visited, frontier),
                lambda: _local_top_down(pg_shapes, cfg, indptr, indices,
                                        row_gid, visited, frontier))
        return nxt[None], pc[None]

    shm = jax.shard_map(_compute, mesh=mesh,
                        in_specs=(P(ax), P(ax), P(ax), P(ax), P(), P(), P()),
                        out_specs=(P(ax), P(ax)), check_vma=False)

    @jax.jit
    def compute_prog(g: HybridGraph, state):
        bu, bu_steps = _decide(hcfg, cfg, v_pad, shapes.e_total,
                               state["nf"], state["mf_dec"], state["bu"],
                               state["bu_steps"], state["mu"])
        nxt_stack, pc_stack = shm(g.indptr, g.indices, g.row_gid, g.ell,
                                  state["visited"], state["frontier"], bu)
        return nxt_stack, pc_stack, bu, bu_steps

    @jax.jit
    def exchange_prog(g: HybridGraph, state, nxt_stack, pc_stack, bu,
                      bu_steps):
        deg = g.deg_ext[:-1]
        merged = (jnp.sum(nxt_stack.astype(jnp.int32), axis=0) > 0)
        newly = jnp.where(state["visited"] > 0, 0, merged).astype(jnp.uint8)
        pcand = jnp.where(newly[None] > 0,
                          jnp.minimum(state["pcand"], pc_stack),
                          state["pcand"])
        lcand = jnp.where(newly > 0,
                          jnp.minimum(state["lcand"], state["cur"] + 1),
                          state["lcand"])
        visited = jnp.maximum(state["visited"], newly)
        nf, mf_full, mf_dec = _frontier_stats(use_kernels, newly, deg, dec_hub)
        mu = state["mu"] - mf_full
        return dict(visited=visited, frontier=newly, pcand=pcand, lcand=lcand,
                    cur=state["cur"] + 1, bu=bu, bu_steps=bu_steps, mu=mu,
                    nf=nf, mf=mf_full, mf_dec=mf_dec)

    @jax.jit
    def finalize_fn(state):
        return jnp.min(state["pcand"], axis=0), state["lcand"]

    return (functools.partial(init_prog, graph),
            functools.partial(compute_prog, graph),
            functools.partial(exchange_prog, graph), finalize_fn,
            make_root_mapper(plan))


def hybrid_bfs_instrumented(pg: PartitionedGraph, root_orig: int,
                            hcfg: HybridConfig = HybridConfig(),
                            mesh: Optional[Mesh] = None):
    """Per-level BSP search over the shared `LevelDriver`.

    Returns (parent_orig, level_orig, stats) where stats rows follow the
    driver schema — the (compute_s, exchange_s) split times real
    collectives per round. The loop itself lives in
    `repro.engine.level_loop` (imported lazily: `repro.engine` imports this
    module at package init).
    """
    from repro.engine.level_loop import BSPStepBackend, LevelDriver

    backend = BSPStepBackend(make_hybrid_stepper(pg, hcfg, mesh), pg.plan)
    parent, level, stats, _timings = LevelDriver(backend).run(int(root_orig))
    return parent, level, stats
