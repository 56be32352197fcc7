"""System-level property tests (hypothesis) for core invariants."""
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core import graph as G, ref
from repro.core.bfs import BFSConfig, bfs


@given(st.integers(0, 2**31 - 1), st.sampled_from(["paper", "beamer"]))
@settings(max_examples=8, deadline=None)
def test_bfs_valid_on_random_graphs(seed, heuristic):
    rng = np.random.default_rng(seed)
    v = int(rng.integers(8, 200))
    m = int(rng.integers(v, 6 * v))
    g = G.from_edges(rng.integers(0, v, m), rng.integers(0, v, m), v)
    root = int(rng.integers(0, v))
    parent, level = bfs(g, root, BFSConfig(heuristic=heuristic))
    ref.validate_parents(g, root, parent, level)


@given(st.integers(0, 2**31 - 1))
@settings(max_examples=6, deadline=None)
def test_heuristics_agree_on_levels(seed):
    """Direction choice must never change the level sets (only the work)."""
    rng = np.random.default_rng(seed)
    v = int(rng.integers(16, 150))
    m = int(rng.integers(v, 5 * v))
    g = G.from_edges(rng.integers(0, v, m), rng.integers(0, v, m), v)
    root = int(rng.integers(0, v))
    levels = {}
    for h in ("topdown", "bottomup", "paper", "beamer"):
        _, lv = bfs(g, root, BFSConfig(heuristic=h))
        levels[h] = lv
    for h in ("bottomup", "paper", "beamer"):
        np.testing.assert_array_equal(levels["topdown"], levels[h])


@given(st.integers(0, 2**31 - 1))
@settings(max_examples=6, deadline=None)
def test_partition_count_invariance(seed):
    """BFS result is invariant to partitioning (1 part == oracle)."""
    from repro.core import partition as PT
    from repro.core.hybrid_bfs import hybrid_bfs
    rng = np.random.default_rng(seed)
    v = int(rng.integers(16, 120))
    m = int(rng.integers(v, 4 * v))
    g = G.from_edges(rng.integers(0, v, m), rng.integers(0, v, m), v)
    root = int(rng.integers(0, v))
    for strat in ("random", "specialized"):
        plan = PT.make_plan(g, 1, strat)
        pg = PT.apply_plan(g, plan)
        parent, level, _ = hybrid_bfs(pg, root)
        ref.validate_parents(g, root, parent, level)
