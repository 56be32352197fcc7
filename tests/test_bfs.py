"""Single-partition direction-optimized BFS vs the python oracle."""
import numpy as np
import pytest

from repro.core import graph as G, ref
from repro.core.bfs import BFSConfig, bfs, bfs_instrumented


@pytest.mark.parametrize("heuristic", ["paper", "beamer", "topdown", "bottomup"])
def test_bfs_matches_oracle(small_graph, heuristic):
    g = small_graph
    roots = [int(np.argmax(g.degrees)), 0, 17]
    for root in roots:
        parent, level = bfs(g, root, BFSConfig(heuristic=heuristic))
        ref.validate_parents(g, root, parent, level)


def test_bfs_uniform_graph():
    g = G.uniform_random(600, 4000, seed=1)
    parent, level = bfs(g, 5)
    ref.validate_parents(g, 5, parent, level)


def test_bfs_isolated_root():
    # a vertex with no edges: only itself reached
    g = G.from_edges(np.array([1, 2]), np.array([2, 3]), 5)
    iso = 4
    assert g.degrees[iso] == 0
    parent, level = bfs(g, iso)
    assert parent[iso] == iso and (parent[np.arange(5) != iso] == -1).all()


def test_bfs_instrumented_stats(small_graph):
    g = small_graph
    root = int(np.argmax(g.degrees))
    parent, level, stats = bfs_instrumented(g, root)
    ref.validate_parents(g, root, parent, level)
    assert stats[0]["direction"] == "td"          # starts top-down
    assert any(s["direction"] == "bu" for s in stats)  # switches on RMAT
    sizes = [s["frontier_size"] for s in stats]
    assert sizes[0] == 1


def test_direction_switch_reduces_levels_work(small_graph):
    # direction-optimized explores far fewer edge checks than topdown at the
    # big levels; proxy: bottom-up levels exist and frontier peaks mid-search
    g = small_graph
    root = int(np.argmax(g.degrees))
    _, _, stats = bfs_instrumented(g, root, BFSConfig(heuristic="paper"))
    peak = max(s["frontier_size"] for s in stats)
    assert peak > g.num_vertices // 10


@pytest.mark.parametrize("chunks", [(64, 16, 8), (4096, 512, 32)])
def test_bfs_chunk_insensitive(small_graph, chunks):
    td, bu, slab = chunks
    g = small_graph
    root = 3
    cfg = BFSConfig(td_chunk=td, bu_chunk=bu, bu_slab=slab)
    parent, level = bfs(g, root, cfg)
    ref.validate_parents(g, root, parent, level)


# ------------------------------------------------ vectorized validator --

def _validator_graphs():
    star = G.from_edges(np.zeros(12, np.int64), np.arange(1, 13), 13)
    path = G.from_edges(np.arange(29), np.arange(1, 30), 30)
    return {"rmat": G.rmat(9, seed=7),
            "uniform": G.uniform_random(600, 4000, seed=1),
            "star": star, "path": path}


VALIDATOR_GRAPHS = _validator_graphs()
MUTATIONS = ("none", "wrong_parent", "non_neighbour", "missing_vertex",
             "level_skew")


def _mutate(g, root, parent, level, mutation):
    """Break one Graph500 rule in a valid (parent, level) pair."""
    parent, level = parent.copy(), level.copy()
    reached = np.flatnonzero(level > 0)
    v = int(reached[-1])
    nbrs = set(g.neighbours(v).tolist())
    if mutation == "wrong_parent":            # rule 1: root is its own parent
        parent[root] = int(g.neighbours(root)[0])
    elif mutation == "non_neighbour":         # rule 3: parents are neighbours
        parent[v] = next(int(u) for u in np.flatnonzero(level >= 0)
                         if u != v and u not in nbrs)
    elif mutation == "missing_vertex":        # rule 2: every reached vertex
        parent[v] = -1
    elif mutation == "level_skew":            # rule 4: one level per edge
        skewed = [(int(w), int(u)) for w in reached for u in g.neighbours(w)
                  if level[u] >= level[w]]
        if skewed:
            w, u = skewed[0]
            parent[w] = u
        else:                                 # a tree: skew the levels
            level[v] += 1
    return parent, level


@pytest.mark.parametrize("mutation", MUTATIONS)
@pytest.mark.parametrize("name", sorted(VALIDATOR_GRAPHS))
def test_fast_validator_agrees_with_oracle(name, mutation):
    g = VALIDATOR_GRAPHS[name]
    root = int(np.argmax(g.degrees))
    parent, level = bfs(g, root)
    np.testing.assert_array_equal(ref.bfs_levels_fast(g, root),
                                  ref.bfs_levels(g, root))
    parent, level = _mutate(g, root, parent, level, mutation)
    verdicts = []
    for check in (ref.validate_parents, ref.validate_tree):
        try:
            check(g, root, parent, level)
            verdicts.append("valid")
        except AssertionError:
            verdicts.append("invalid")
    assert verdicts == ["valid" if mutation == "none" else "invalid"] * 2
