"""Plain breadth-first-search reference over the benchmark's own edge list.

Nothing here imports the program or reads what it built. One sort, on the
device, of both directions of every generated edge by (row, col), self
loops dropped and duplicates merged, gives both the adjacency the searches
walk and the lookup (keys `row * V + col`) that decides whether a reported
parent is a neighbour.
The level search and the four Graph500 tree rules follow the program's
`core/ref.py` (`bfs_levels_fast`, `validate_tree`), written again here so
that the yardstick cannot move with the program.

`capped=k` turns the reference into the control: every vertex scans only
the first `k` entries of its adjacency, the shortcut an early-exit pull
would take. It breaks the guarantee the configurations state (exact
levels), so a sound comparison has to catch it.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


@jax.jit
def _directed_sorted(src, dst):
    """Both directions of every edge, sorted by (row, col), and the mask of
    the entries kept: the first of each run of equal pairs, no self loop."""
    rows, cols = jax.lax.sort((jnp.concatenate([src, dst]),
                               jnp.concatenate([dst, src])), num_keys=2)
    fresh = jnp.concatenate([jnp.ones(1, bool), (rows[1:] != rows[:-1])
                             | (cols[1:] != cols[:-1])])
    return rows, cols, fresh & (rows != cols)


@dataclasses.dataclass
class Adjacency:
    """Sorted, deduplicated directed edge keys and the rows they form."""

    num_vertices: int
    keys: np.ndarray      # int64[E], sorted: row * V + col
    indptr: np.ndarray    # int64[V + 1]
    cols: np.ndarray      # int64[E]

    @classmethod
    def from_edges(cls, src: np.ndarray, dst: np.ndarray,
                   num_vertices: int) -> "Adjacency":
        n = int(num_vertices)
        edges = (jnp.asarray(src, jnp.int32), jnp.asarray(dst, jnp.int32))
        out = _directed_sorted(*edges)
        keep = np.asarray(out[2])
        rows = np.asarray(out[0])[keep].astype(np.int64)
        cols = np.asarray(out[1])[keep].astype(np.int64)
        for a in (*edges, *out):
            a.delete()
        indptr = np.searchsorted(rows, np.arange(n + 1, dtype=np.int64))
        return cls(n, rows * n + cols, indptr, cols)

    @property
    def degrees(self) -> np.ndarray:
        return np.diff(self.indptr)


def search(adj: Adjacency, root: int, max_levels: int = 0,
           capped: Optional[int] = None, with_parent: bool = True
           ) -> tuple[Optional[np.ndarray], np.ndarray]:
    """Level-synchronous search from `root`: (parent, level), int32, -1
    where unreached (parent None unless `with_parent`). Each vertex's
    parent is the lowest-numbered frontier vertex that finds it.

    `max_levels` > 0 stops after that many levels (a k-hop query);
    `capped` scans only the first `capped` neighbours of each vertex.
    """
    level = np.full(adj.num_vertices, -1, dtype=np.int32)
    level[root] = 0
    parent = None
    if with_parent:
        parent = np.full(adj.num_vertices, -1, dtype=np.int32)
        parent[root] = root
    frontier = np.array([root], dtype=np.int64)
    depth = 0
    while frontier.size and (max_levels <= 0 or depth < max_levels):
        starts = adj.indptr[frontier]
        degs = adj.indptr[frontier + 1] - starts
        if capped is not None:
            degs = np.minimum(degs, capped)
        total = int(degs.sum())
        if total == 0:
            break
        # Edge slots of every frontier vertex, in one gather.
        slots = (np.repeat(starts - (np.cumsum(degs) - degs), degs)
                 + np.arange(total, dtype=np.int64))
        nbrs = adj.cols[slots]
        fresh = level[nbrs] < 0
        depth += 1
        if parent is None:
            seen = np.zeros(adj.num_vertices, dtype=bool)
            seen[nbrs[fresh]] = True
            frontier = np.flatnonzero(seen)
        else:
            # The frontier is sorted, so a vertex's first slot carries its
            # lowest-numbered finder.
            owner = np.repeat(frontier, degs)[fresh]
            frontier, first = np.unique(nbrs[fresh], return_index=True)
            parent[frontier] = owner[first]
        level[frontier] = depth
    return parent, level


def judge(adj: Adjacency, root: int, parent: np.ndarray, level: np.ndarray,
          max_levels: int = 0) -> dict:
    """Count what an answer gets wrong against the reference.

    `wrong_levels`: vertices whose reported level differs from the hop
    distance (bounded by `max_levels`). `bad_parents`: vertices breaking a
    Graph500 tree rule — the root is not its own parent, a parent is set on
    a vertex the reference does not reach (or missing on one it does), a
    parent is not a neighbour, or a tree edge does not span one level.
    """
    want = search(adj, root, max_levels, with_parent=False)[1]
    parent = np.asarray(parent)
    level = np.asarray(level)
    wrong_levels = int(np.count_nonzero(level != want))
    reached = want >= 0
    bad = int(parent[root] != root)
    bad += int(np.count_nonzero((parent >= 0) != reached))
    vs = np.flatnonzero(reached & (parent >= 0))
    vs = vs[vs != root]
    ps = parent[vs].astype(np.int64)
    ps_safe = np.clip(ps, 0, adj.num_vertices - 1)
    key = vs.astype(np.int64) * adj.num_vertices + ps_safe
    at = np.minimum(np.searchsorted(adj.keys, key), max(adj.keys.size - 1, 0))
    edge = (adj.keys[at] == key) if adj.keys.size else np.zeros(vs.size, bool)
    edge &= ps == ps_safe
    span = want[ps_safe] == want[vs] - 1
    bad += int(np.count_nonzero(~(edge & span)))
    return dict(wrong_levels=wrong_levels, bad_parents=bad)
