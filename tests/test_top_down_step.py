"""The XLA top-down push against the compaction-based push it replaced.

The push numbers the frontier's edge slots by a dense, vertex-order prefix
sum of the frontier's degrees and finds each slot's source with a
`searchsorted` over it. The push it replaced compacted the frontier into
a queue first, in ascending vertex order, and numbered the queue's slots:
every slot maps to the same (source, destination) pair, so flags and
parents must equal that push's bitwise. The push is driven through the
public cohort steps: "td" runs it once over the whole level, and "mixed"
under `hub_split` runs it once per side with a destination mask.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bfs as B
from repro.core import ell as ELL
from repro.core import frontier as fr
from repro.core import graph as G
from repro.engine import Engine

INT_MAX = B.INT_MAX
CHUNKS = (1, 7, 4096)
STAR = 42                        # the hub: 4,500 leaves


def _graph():
    """Vertices 0 and 41 isolated; 1-40 a random sparse graph; vertex 42
    a star whose leaves 43-4542 also reach back into 1-40."""
    rng = np.random.default_rng(17)
    small = rng.integers(1, 41, size=(150, 2))
    leaves = np.arange(STAR + 1, STAR + 4501)
    back = np.stack([rng.choice(leaves, 60), rng.integers(1, 41, 60)], 1)
    edges = np.concatenate([small, np.stack([np.full(4500, STAR), leaves],
                                            1), back])
    return G.from_edges(edges[:, 0], edges[:, 1], STAR + 4501)


GRAPH = _graph()
FRONTIERS = ("empty", "one", "all", "degree-0", "straddle")


def _inputs(g, kind, seed=0):
    """(frontier, visited, parent) of one lane: a random quarter of the
    graph visited, and the frontier, visited too."""
    v = g.num_vertices
    rng = np.random.default_rng(seed)
    frontier = np.zeros(v, np.uint8)
    if kind == "one":
        frontier[int(np.argmax(g.degrees[:STAR]))] = 1
    elif kind == "all":
        frontier[:] = 1
    elif kind == "degree-0":
        frontier[[0, 3, 41, 57, 4000]] = 1
    elif kind == "straddle":
        frontier[1:STAR + 1] = 1
    visited = ((rng.random(v) < 0.25) | (frontier > 0)).astype(np.uint8)
    parent = np.where(visited > 0, np.arange(v), INT_MAX).astype(np.int32)
    return frontier, visited, parent


def compaction_push(dg, chunk, frontier, visited, parent, dst_mask=None):
    """The push as it was: compact the frontier into a queue, gather the
    queue's degrees, and number the queue's slots."""
    v = dg.num_vertices
    queue, _n = fr.compact(frontier)             # fill entries == v
    degq = dg.deg_ext[queue]                     # 0 for fill
    cum = jnp.cumsum(degq, dtype=jnp.int32)
    total = cum[-1] if v else jnp.int32(0)

    def body(carry):
        base, next_flags, pcand = carry
        slots = base + jnp.arange(chunk, dtype=jnp.int32)
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
        return base + chunk, next_flags, pcand

    init = (jnp.int32(0), jnp.zeros(v, jnp.uint8),
            jnp.full(v, INT_MAX, jnp.int32))
    _, next_flags, pcand = jax.lax.while_loop(
        lambda c: c[0] < total, body, init)
    parent = jnp.where(next_flags > 0, jnp.minimum(parent, pcand), parent)
    return next_flags, parent


_compaction_push = jax.jit(compaction_push, static_argnums=(1,))


def reference(g, chunk, masked, frontier, visited, parent):
    """One level of the compaction push; with `masked`, one pass per side
    of the hub split, tail then hub, as the "mixed" step runs them."""
    dg = B.DeviceGraph.from_graph(g)
    args = (jnp.asarray(frontier), jnp.asarray(visited))
    if not masked:
        flags, parent = _compaction_push(dg, chunk, *args, jnp.asarray(parent))
        return np.asarray(flags), np.asarray(parent)
    floor = ELL.hub_degree_floor(B.BFSConfig().hub_deg)
    hub = jnp.asarray(g.degrees > floor)
    tail_flags, parent = _compaction_push(dg, chunk, *args,
                                          jnp.asarray(parent), ~hub)
    hub_flags, parent = _compaction_push(dg, chunk, *args, parent, hub)
    return np.asarray(jnp.maximum(tail_flags, hub_flags)), np.asarray(parent)


@functools.lru_cache(maxsize=None)
def _step(cfg, variant):
    """One jitted cohort step per config and variant, shared by the cases."""
    return jax.jit(B.make_batch_step(cfg, variant))


def _lane_state(dg, cfg, lanes):
    """A cohort state whose lanes (frontier, visited, parent) all push."""
    b = len(lanes)
    f, vis, par = (np.stack(x) for x in zip(*lanes))
    st = B.init_batch(dg, cfg, jnp.zeros(b, jnp.int32), jnp.ones(b, bool))
    no = jnp.zeros(b, bool)
    return dataclasses.replace(st, frontier=jnp.asarray(f),
                               visited=jnp.asarray(vis),
                               parent=jnp.asarray(par), bu_mode=no,
                               bu_hub=no)


def _push(g, chunk, masked, lanes):
    """Per-lane (flags, parent) of one top-down cohort level: "td", or
    "mixed" under the hub split, which masks each push's destinations."""
    cfg = B.BFSConfig(td_chunk=chunk, hub_split=masked)
    dg = B.DeviceGraph.from_graph(g)
    hubs = B.hub_rows(g.degrees, cfg.hub_deg) if masked else None
    out = _step(cfg, "mixed" if masked else "td")(
        B.CohortGraph(dg, None, hubs), _lane_state(dg, cfg, lanes))
    return np.asarray(out.frontier), np.asarray(out.parent)


def test_straddle_frontier_crosses_every_chunk_boundary():
    """The "straddle" frontier's star starts below slot 4,096 and ends past
    it: its edge range straddles a boundary of every chunk size."""
    frontier, _, _ = _inputs(GRAPH, "straddle")
    before = int(GRAPH.degrees[:STAR][frontier[:STAR] > 0].sum())
    assert 0 < before < max(CHUNKS) < before + GRAPH.degrees[STAR]


@pytest.mark.parametrize("masked", [False, True], ids=["nomask", "dstmask"])
@pytest.mark.parametrize("chunk", CHUNKS)
@pytest.mark.parametrize("kind", FRONTIERS)
def test_push_matches_compaction_push(kind, chunk, masked):
    lane = _inputs(GRAPH, kind)
    want = reference(GRAPH, chunk, masked, *lane)
    flags, parent = _push(GRAPH, chunk, masked, [lane])
    np.testing.assert_array_equal(flags[0], want[0])
    np.testing.assert_array_equal(parent[0], want[1])
    if kind == "empty":
        assert not flags[0].any()


def test_lanes_push_independently():
    """Lanes of one batch each get their own level, run one after
    another."""
    lanes = [_inputs(GRAPH, "one", seed=1), _inputs(GRAPH, "straddle",
                                                    seed=2)]
    flags, parent = _push(GRAPH, 7, False, lanes)
    for b, lane in enumerate(lanes):
        want = reference(GRAPH, 7, False, *lane)
        np.testing.assert_array_equal(flags[b], want[0])
        np.testing.assert_array_equal(parent[b], want[1])


def _gathers_and_scatters_outside_loops(jaxpr, path=()):
    """Names of the gather and scatter equations of `jaxpr`, its calls and
    branches, that no `while` body holds."""
    found = []
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name.startswith(("gather", "scatter")):
            found.append("/".join(path + (name,)))
        for key, param in eqn.params.items():
            if name == "while" and key == "body_jaxpr":
                continue
            for sub in param if isinstance(param, (list, tuple)) else [param]:
                if isinstance(sub, jax.extend.core.ClosedJaxpr):
                    sub = sub.jaxpr
                if isinstance(sub, jax.extend.core.Jaxpr):
                    found += _gathers_and_scatters_outside_loops(
                        sub, path + (name,))
    return found


@pytest.mark.parametrize("split", [False, True], ids=["unsplit", "split"])
def test_top_down_level_start_has_no_gather_or_scatter(split):
    """The "td" step runs nothing but the push and its level bookkeeping:
    outside the chunk loop's body it reads and writes V-sized arrays only
    with dense passes, so a whole-graph gather or scatter at level start
    cannot come back unnoticed."""
    g = G.rmat(6, seed=0)
    cfg = B.BFSConfig(td_chunk=7, hub_split=split)
    dg = B.DeviceGraph.from_graph(g)
    hubs = B.hub_rows(g.degrees, cfg.hub_deg) if split else None
    st = B.init_batch(dg, cfg, jnp.zeros(2, jnp.int32), jnp.ones(2, bool))
    jaxpr = jax.make_jaxpr(B.make_batch_step(cfg, "td"))(
        B.CohortGraph(dg, None, hubs), st)
    assert _gathers_and_scatters_outside_loops(jaxpr.jaxpr) == []
    assert "while" in str(jaxpr)


def _pull_oracle(g, frontier, visited, parent):
    """One bottom-up level: an unvisited row takes the neighbour in its
    lowest adjacency slot that is in the frontier."""
    flags, parent = np.zeros_like(frontier), parent.copy()
    for r in np.flatnonzero((visited == 0) & (g.degrees > 0)):
        adj = g.indices[g.indptr[r]:g.indptr[r + 1]]
        hits = np.flatnonzero(frontier[adj])
        if hits.size:
            flags[r], parent[r] = 1, min(parent[r], adj[hits[0]])
    return flags, parent


def reference_search(g, root, cfg):
    """A whole search with the compaction push for top-down levels and the
    first-hit pull for bottom-up ones, directions by the paper's rule (or
    top-down only): (parent, level), -1 where unreached."""
    v, e = g.num_vertices, g.num_directed_edges
    frontier = np.zeros(v, np.uint8)
    frontier[root] = 1
    visited = frontier.copy()
    parent = np.full(v, INT_MAX, np.int32)
    parent[root] = root
    level = np.full(v, -1, np.int32)
    level[root] = 0
    bu, bu_steps, mf, cur = False, 0, int(g.degrees[root]), 0
    while frontier.any():
        if cfg.heuristic == "paper":
            bu = ((not bu and np.float32(mf) > np.float32(cfg.gamma * e))
                  or (bu and bu_steps < cfg.fixed_bu_steps))
            bu_steps = bu_steps + 1 if bu else 0
        if bu:
            frontier, parent = _pull_oracle(g, frontier, visited, parent)
        else:
            frontier, parent = reference(g, cfg.td_chunk, False, frontier,
                                         visited, parent)
        cur += 1
        visited = np.maximum(visited, frontier)
        level[frontier > 0] = cur
        mf = int(g.degrees[frontier > 0].sum())
    return np.where(parent == INT_MAX, -1, parent), level


@pytest.mark.parametrize("heuristic,chunk", [("paper", 4096), ("paper", 7),
                                             ("topdown", 7)])
def test_searches_match_compaction_push_search(heuristic, chunk):
    """`Engine.bfs(batched=False)` and `bfs()` on a small Kronecker graph
    give the parents and levels of a search made with the old push."""
    g = G.rmat(9, seed=5)
    cfg = B.BFSConfig(heuristic=heuristic, td_chunk=chunk)
    roots = [int(np.argmax(g.degrees)), 3, 100]
    res = Engine(g).bfs(roots, cfg, batched=False)
    for i, root in enumerate(roots):
        want_parent, want_level = reference_search(g, root, cfg)
        np.testing.assert_array_equal(res.parent[i], want_parent)
        np.testing.assert_array_equal(res.level[i], want_level)
        parent, level = B.bfs(g, root, cfg)
        np.testing.assert_array_equal(parent, want_parent)
        np.testing.assert_array_equal(level, want_level)
