"""The XLA bottom-up pull against a numpy first-hit oracle.

The pull walks its queued rows slab by slab over a shrinking survivor list
(`PULL_LADDER` widths, capped at the widest slab). Whatever the grouping,
a row's parent is the neighbour in its lowest adjacency slot that is in
the frontier, so flags and parents must equal the oracle's bitwise, and
the counters must equal the oracle's count of queued rows and of the slots
the ladder gathers for them. The pull is driven through the public "bu"
cohort step (B lanes, the split's tail mask when `hub_split` is on).
"""
import dataclasses
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bfs as B
from repro.core import ell as ELL
from repro.core import graph as G
from repro.engine import Engine
from repro.runtime import spans

INT_MAX = B.INT_MAX
RUNG_DEGREES = (1, 2, 3, 4, 5, 8, 9, 16, 17, 31, 32, 33, 64, 65, 200)
HUB_DEG = 64                      # snapped floor 32: rows above 32 are hub


def _rung_graph():
    """A centre of each degree in `RUNG_DEGREES`, joined to that many
    distinct vertices of a shared pool (which get degrees of their own)."""
    rng = np.random.default_rng(14)
    pool, n_c = 250, len(RUNG_DEGREES)
    src, dst = [], []
    for c, d in enumerate(RUNG_DEGREES):
        src += [c] * d
        dst += list(n_c + rng.choice(pool, d, replace=False))
    return G.from_edges(np.asarray(src), np.asarray(dst), n_c + pool)


def _graphs():
    star = G.from_edges(np.zeros(200, np.int64), np.arange(1, 201), 201)
    path = G.from_edges(np.arange(59), np.arange(1, 60), 60)
    edgeless = G.from_edges(np.zeros(0, np.int64), np.zeros(0, np.int64), 12)
    return {"rungs": _rung_graph(), "star": star, "path": path,
            "edgeless": edgeless}


GRAPHS = _graphs()
DENSITIES = ("one", 0.1, 0.5, "all")


def _inputs(g, density, seed=0):
    """(frontier, visited, parent_in) for one lane: the frontier at
    `density` ("one": the last vertex only), a random quarter visited."""
    v = g.num_vertices
    rng = np.random.default_rng(seed)
    if density == "one":
        frontier = np.zeros(v, np.uint8)
        frontier[v - 1] = 1
    elif density == "all":
        frontier = np.ones(v, np.uint8)
    else:
        frontier = (rng.random(v) < density).astype(np.uint8)
    visited = (rng.random(v) < 0.25).astype(np.uint8)
    parent = np.where(visited > 0, np.arange(v), INT_MAX).astype(np.int32)
    return frontier, visited, parent


def _widths(cap):
    """The pull's slab widths for a widest slab `cap`, without end."""
    yield from (w for w in B.PULL_LADDER if w < cap)
    while True:
        yield cap


def oracle(g, frontier, visited, parent, cap, counted=None):
    """Flags, parents, queued rows and gathered slots of one pull level.

    `counted` (bool[V] or None) is the set of rows the counted pull queues
    (the split's tail side); every unvisited row of nonzero degree is
    pulled for the flags and parents.
    """
    flags, parent = np.zeros_like(frontier), parent.copy()
    rows = slots = 0
    for r in range(g.num_vertices):
        deg = int(g.degrees[r])
        if deg == 0 or visited[r]:
            continue
        adj = g.indices[g.indptr[r]:g.indptr[r + 1]]
        hits = np.flatnonzero(frontier[adj])
        first = int(hits[0]) if hits.size else None
        if first is not None:
            flags[r], parent[r] = 1, min(parent[r], adj[first])
        if counted is not None and not counted[r]:
            continue
        rows += 1
        used = 0
        for w in _widths(cap):
            slots += min(w, deg - used)
            if (first is not None and first < used + w) or used + w >= deg:
                break
            used += w
    return flags, parent, rows, slots


@functools.lru_cache(maxsize=None)
def _bu_step(cfg):
    """One jitted "bu" cohort step per config, shared by the cases."""
    return jax.jit(B.make_batch_step(cfg, "bu"))


def _pull(g, cfg, lanes):
    """Run the "bu" cohort step on `lanes` [(frontier, visited, parent) or
    None for an idle lane]; returns per-lane (flags, parent) and the
    counters."""
    dg = B.DeviceGraph.from_graph(g)
    hubs = B.hub_rows(g.degrees, cfg.hub_deg) if cfg.hub_split else None
    graph = B.CohortGraph(dg, None, hubs)
    b, v = len(lanes), g.num_vertices
    on = np.array([lane is not None for lane in lanes])
    blank = (np.zeros(v, np.uint8), np.zeros(v, np.uint8),
             np.full(v, INT_MAX, np.int32))
    f, vis, par = (np.stack(x) for x in zip(*[lane or blank
                                              for lane in lanes]))
    st = B.init_batch(dg, cfg, jnp.zeros(b, jnp.int32), jnp.asarray(on))
    yes = jnp.ones(b, bool)
    st = dataclasses.replace(st, frontier=jnp.asarray(f),
                             visited=jnp.asarray(vis),
                             parent=jnp.asarray(par), bu_mode=yes,
                             bu_hub=yes)
    out = _bu_step(cfg)(graph, st)
    return (np.asarray(out.frontier), np.asarray(out.parent),
            int(out.pull_rows), int(out.pull_slots))


@pytest.mark.parametrize("slab", [8, 32])
@pytest.mark.parametrize("split", [False, True], ids=["nomask", "rowmask"])
@pytest.mark.parametrize("density", DENSITIES)
@pytest.mark.parametrize("name", list(GRAPHS))
def test_pull_matches_first_hit_oracle(name, density, split, slab):
    g = GRAPHS[name]
    cfg = B.BFSConfig(bu_slab=slab, hub_split=split, hub_deg=HUB_DEG)
    frontier, visited, parent = _inputs(g, density)
    counted = None
    if split:
        floor = ELL.hub_degree_floor(HUB_DEG)
        counted = (g.degrees > 0) & (g.degrees <= floor)
    want = oracle(g, frontier, visited, parent, slab, counted)
    flags, par, rows, slots = _pull(g, cfg, [(frontier, visited, parent)])
    np.testing.assert_array_equal(flags[0], want[0])
    np.testing.assert_array_equal(par[0], want[1])
    assert (rows, slots) == want[2:]


def test_star_hub_takes_several_widest_slabs():
    """The hub's only frontier neighbour is its last slot: it walks the
    whole ladder and then every cap-width slab to its degree."""
    g = GRAPHS["star"]
    frontier, visited, parent = _inputs(g, "one")
    visited[:] = 1
    visited[0] = 0
    parent[0] = INT_MAX
    flags, par, rows, slots = _pull(g, B.BFSConfig(bu_slab=32),
                                    [(frontier, visited, parent)])
    assert flags[0][0] == 1 and par[0][0] == g.num_vertices - 1
    assert (rows, slots) == (1, 200)
    assert oracle(g, frontier, visited, parent, 32)[2:] == (1, 200)


def test_counters_sum_over_lanes_and_idle_lanes_count_nothing():
    g = GRAPHS["rungs"]
    a, b = _inputs(g, 0.1, seed=1), _inputs(g, "one", seed=2)
    flags, par, rows, slots = _pull(g, B.BFSConfig(), [a, None, b])
    want_a, want_b = oracle(g, *a, 32), oracle(g, *b, 32)
    np.testing.assert_array_equal(flags[0], want_a[0])
    np.testing.assert_array_equal(flags[2], want_b[0])
    assert not flags[1].any()
    np.testing.assert_array_equal(par[2], want_b[1])
    assert (rows, slots) == (want_a[2] + want_b[2], want_a[3] + want_b[3])


@pytest.mark.parametrize("name,heuristic", [("rmat", "paper"),
                                            ("uniform", "paper"),
                                            ("rmat", "bottomup")])
def test_counters_reach_level_rows_and_step_spans(name, heuristic):
    """A whole search: each level row carries the pull's counters, equal to
    the oracle's for that level (rebuilt from the search's levels), and the
    level's `repro.level.step` span carries the same numbers."""
    g = G.rmat(9, seed=11) if name == "rmat" \
        else G.uniform_random(600, 4000, seed=1)
    root = 5
    cfg = B.BFSConfig(heuristic=heuristic)
    rows = []
    t0 = time.perf_counter()
    res = Engine(g).bfs([root], cfg,
                        on_level=lambda _i, row: rows.append(row))
    level = res.level[0]
    assert any(r["direction"] == "bu" for r in rows)
    steps = [s for s in spans.records(since=t0)
             if s.name == "repro.level.step"]
    assert len(steps) == len(rows)
    for row, step in zip(rows, steps):
        cur = row["level"] - 1               # the level the step started at
        if row["direction"] == "bu":
            frontier = (level == cur).astype(np.uint8)
            visited = ((level >= 0) & (level <= cur)).astype(np.uint8)
            parent = np.full(g.num_vertices, INT_MAX, np.int32)
            want = oracle(g, frontier, visited, parent, cfg.bu_slab)[2:]
        else:
            want = (0, 0)
        assert (row["pull_rows"], row["pull_slots"]) == want
        assert (step.attrs["pull_rows"], step.attrs["pull_slots"]) == want
    assert any(r["pull_rows"] > 0 for r in rows)
