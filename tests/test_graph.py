"""Graph substrate unit + property tests."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import graph as G


def test_rmat_basic():
    g = G.rmat(8, seed=0)
    g.validate()
    assert g.num_vertices == 256
    assert g.num_directed_edges % 2 == 0  # symmetrized
    # scale-free-ish: max degree far above mean
    assert g.max_degree > 4 * g.degrees.mean()


def test_rmat_deterministic():
    a = G.rmat(8, seed=5)
    b = G.rmat(8, seed=5)
    np.testing.assert_array_equal(a.indices, b.indices)
    c = G.rmat(8, seed=6)
    assert not np.array_equal(a.indices, c.indices)


def test_adjacency_degree_sorted():
    g = G.rmat(9, seed=1)
    for v in [0, 3, int(np.argmax(g.degrees))]:
        nbrs = g.neighbours(v)
        d = g.degrees[nbrs]
        assert (np.diff(d.astype(np.int64)) <= 0).all()


def test_symmetry():
    g = G.rmat(8, seed=2)
    # every directed edge has its reverse
    fwd = set()
    for v in range(g.num_vertices):
        for n in g.neighbours(v):
            fwd.add((v, int(n)))
    for (a, b) in list(fwd)[:500]:
        assert (b, a) in fwd


@given(st.integers(0, 2**31 - 1))
@settings(max_examples=10, deadline=None)
def test_from_edges_random(seed):
    rng = np.random.default_rng(seed)
    v = int(rng.integers(2, 40))
    m = int(rng.integers(1, 120))
    src = rng.integers(0, v, m)
    dst = rng.integers(0, v, m)
    g = G.from_edges(src, dst, v)
    g.validate()
    assert not any(n == i for i in range(v) for n in g.neighbours(i))  # no loops


def test_relabel_preserves_structure():
    from repro.core import ref
    g = G.rmat(8, seed=3)
    rng = np.random.default_rng(0)
    perm = rng.permutation(g.num_vertices)
    g2 = G.relabel(g, perm)
    root_old = int(np.argmax(g.degrees))
    inv = np.empty(g.num_vertices, dtype=np.int64)
    inv[perm] = np.arange(g.num_vertices)
    lv1 = ref.bfs_levels(g, root_old)
    lv2 = ref.bfs_levels(g2, int(inv[root_old]))
    np.testing.assert_array_equal(lv1, lv2[inv])


def test_real_world_standins():
    for name in G.REAL_WORLD_STANDINS:
        g = G.real_world_standin(name)
        g.validate()
        assert g.num_vertices >= 1 << 14
