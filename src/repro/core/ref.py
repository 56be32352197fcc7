"""Pure-Python/numpy BFS oracle + Graph500-style result validation.

Two validators of the same four Graph500 rules:

* `validate_parents` over the deque oracle `bfs_levels` — the plain
  reference the tests hold every BFS implementation to (single-device,
  partitioned, and the Pallas kernels' chunk processors);
* `validate_tree`, vectorized numpy (frontier BFS for the levels, parent
  edges looked up in the sorted edge keys) — what `TraversalResult.validate`
  runs, fast enough for the graphs that fill a chip (seconds per root at
  4M vertices, where the oracle takes minutes).
"""
from __future__ import annotations

from collections import deque

import numpy as np

from repro.core.graph import Graph


def bfs_levels(g: Graph, root: int) -> np.ndarray:
    """Classic queue BFS. Returns int32 levels, -1 for unreachable."""
    level = np.full(g.num_vertices, -1, dtype=np.int32)
    level[root] = 0
    q = deque([root])
    while q:
        v = q.popleft()
        for n in g.neighbours(v):
            if level[n] < 0:
                level[n] = level[v] + 1
                q.append(int(n))
    return level


def validate_parents(g: Graph, root: int, parent: np.ndarray,
                     level: np.ndarray | None = None) -> None:
    """Graph500-style validation of a BFS parent tree.

    Checks (per the Graph500 validation spec, adapted):
      1. parent[root] == root.
      2. Exactly the reachable vertices have a parent.
      3. Every non-root parent is an actual neighbour.
      4. Tree edges span exactly one BFS level: level[v] == level[parent]+1.
    """
    ref_level = bfs_levels(g, root)
    reachable = ref_level >= 0
    has_parent = parent >= 0
    assert parent[root] == root, "root must be its own parent"
    np.testing.assert_array_equal(
        has_parent, reachable, err_msg="parent-tree coverage != reachable set")
    vs = np.flatnonzero(reachable)
    vs = vs[vs != root]
    for v in vs:
        p = parent[v]
        assert p in g.neighbours(v), f"parent[{v}]={p} is not a neighbour"
        assert ref_level[v] == ref_level[p] + 1, (
            f"tree edge {p}->{v} spans levels {ref_level[p]}->{ref_level[v]}")
    if level is not None:
        np.testing.assert_array_equal(level, ref_level)


def bfs_levels_fast(g: Graph, root: int) -> np.ndarray:
    """Level-synchronous numpy BFS; the same levels as `bfs_levels`."""
    level = np.full(g.num_vertices, -1, dtype=np.int32)
    level[root] = 0
    frontier = np.array([root], dtype=np.int64)
    depth = 0
    while frontier.size:
        starts = g.indptr[frontier]
        degs = g.indptr[frontier + 1] - starts
        total = int(degs.sum())
        if total == 0:
            break
        # Edge slots of every frontier vertex, in one gather.
        slots = (np.repeat(starts - (np.cumsum(degs) - degs), degs)
                 + np.arange(total, dtype=np.int64))
        nbrs = g.indices[slots]
        nbrs = np.unique(nbrs[level[nbrs] < 0])
        depth += 1
        level[nbrs] = depth
        frontier = nbrs.astype(np.int64)
    return level


def edge_keys(g: Graph) -> np.ndarray:
    """Sorted int64 keys `row * V + col` of every directed CSR edge."""
    rows = np.repeat(np.arange(g.num_vertices, dtype=np.int64), g.degrees)
    keys = rows * g.num_vertices + g.indices
    keys.sort()
    return keys


def validate_tree(g: Graph, root: int, parent: np.ndarray,
                  level: np.ndarray | None = None,
                  keys: np.ndarray | None = None,
                  ref_level: np.ndarray | None = None) -> None:
    """`validate_parents`' four rules, vectorized.

    `keys` is `edge_keys(g)`; pass it when validating many roots of one
    graph so the sort is paid once. `ref_level` is `bfs_levels_fast(g,
    root)` when the caller already holds it (two results for one root).
    """
    if ref_level is None:
        ref_level = bfs_levels_fast(g, root)
    reachable = ref_level >= 0
    assert parent[root] == root, "root must be its own parent"
    np.testing.assert_array_equal(
        parent >= 0, reachable, err_msg="parent-tree coverage != reachable set")
    vs = np.flatnonzero(reachable)
    vs = vs[vs != root]
    ps = parent[vs].astype(np.int64)
    if keys is None:
        keys = edge_keys(g)
    want = vs.astype(np.int64) * g.num_vertices + ps
    at = np.minimum(np.searchsorted(keys, want), max(keys.size - 1, 0))
    bad = (keys[at] != want) if keys.size else np.ones(vs.size, bool)
    assert not bad.any(), (
        f"parent[{vs[bad][0]}]={ps[bad][0]} is not a neighbour")
    skew = ref_level[vs] != ref_level[ps] + 1
    assert not skew.any(), (
        f"tree edge {ps[skew][0]}->{vs[skew][0]} spans levels "
        f"{ref_level[ps[skew][0]]}->{ref_level[vs[skew][0]]}")
    if level is not None:
        np.testing.assert_array_equal(level, ref_level)


def teps(g: Graph, seconds: float) -> float:
    """Undirected traversed-edges-per-second (Graph500 reporting rule)."""
    return g.num_undirected_edges / max(seconds, 1e-12)
