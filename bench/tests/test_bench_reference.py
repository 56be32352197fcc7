"""The plain reference and its judge (CPU)."""
from collections import deque

import numpy as np
import pytest

import tiny  # noqa: F401
from bench.harness import reference


def deque_levels(src, dst, n, root, max_levels=0):
    nbrs = [set() for _ in range(n)]
    for s, d in zip(src.tolist(), dst.tolist()):
        if s != d:
            nbrs[s].add(d)
            nbrs[d].add(s)
    level = np.full(n, -1, np.int32)
    level[root] = 0
    q = deque([root])
    while q:
        v = q.popleft()
        if max_levels and level[v] >= max_levels:
            continue
        for u in nbrs[v]:
            if level[u] < 0:
                level[u] = level[v] + 1
                q.append(u)
    return level


@pytest.fixture(scope="module")
def edges():
    rng = np.random.default_rng(11)
    n = 300
    src = rng.integers(0, n, 900)
    dst = (src + rng.integers(0, 5, 900) ** 3) % n
    return src, dst, n


@pytest.mark.parametrize("max_levels", [0, 1, 2, 3])
def test_levels_match_a_queue_bfs(edges, max_levels):
    src, dst, n = edges
    adj = reference.Adjacency.from_edges(src, dst, n)
    for root in (0, 7, 150):
        parent, level = reference.search(adj, root, max_levels)
        np.testing.assert_array_equal(
            level, deque_levels(src, dst, n, root, max_levels))
        assert reference.judge(adj, root, parent, level, max_levels) == \
            dict(wrong_levels=0, bad_parents=0)


def test_judge_counts_every_kind_of_fault(edges):
    src, dst, n = edges
    adj = reference.Adjacency.from_edges(src, dst, n)
    parent, level = reference.search(adj, 3)
    deep = int(np.argmax(level))
    lv = level.copy()
    lv[deep] += 1
    assert reference.judge(adj, 3, parent, lv)["wrong_levels"] == 1
    par = parent.copy()
    par[deep] = deep                     # not a neighbour (no self loops)
    assert reference.judge(adj, 3, par, level)["bad_parents"] == 1
    par = parent.copy()
    par[3] = -1                          # the root lost its parent
    assert reference.judge(adj, 3, par, level)["bad_parents"] == 2
    par = parent.copy()
    par[deep] = -1                       # a reached vertex without parent
    assert reference.judge(adj, 3, par, level)["bad_parents"] == 1
    unreached = np.flatnonzero(level < 0)
    if unreached.size:
        par = parent.copy()
        par[unreached[0]] = 3
        assert reference.judge(adj, 3, par, level)["bad_parents"] == 1


def test_capped_control_is_wrong_where_degrees_exceed_the_cap():
    rng = np.random.default_rng(2)
    n = 200
    hub = np.zeros(150, np.int64)
    src = np.concatenate([hub, rng.integers(0, n, 50)])
    dst = np.concatenate([np.arange(1, 151), rng.integers(0, n, 50)])
    adj = reference.Adjacency.from_edges(src, dst, n)
    parent, level = reference.search(adj, 0, capped=32)
    got = reference.judge(adj, 0, parent, level)
    assert got["wrong_levels"] > 0 and got["bad_parents"] > 0


def test_adjacency_is_every_edge_both_ways_sorted_without_repeats():
    rng = np.random.default_rng(4)
    n = 64
    src = rng.integers(0, n, 500)
    dst = rng.integers(0, n, 500)
    adj = reference.Adjacency.from_edges(src, dst, n)
    keep = src != dst
    want = np.unique(np.concatenate([src[keep] * n + dst[keep],
                                     dst[keep] * n + src[keep]]))
    np.testing.assert_array_equal(adj.keys, want)
    np.testing.assert_array_equal(adj.cols, want % n)
    np.testing.assert_array_equal(
        adj.degrees, np.bincount(want // n, minlength=n))
