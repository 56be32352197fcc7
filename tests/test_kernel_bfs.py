"""Kernel-backed traversal path vs the XLA reference path.

Acceptance gate for the Pallas hot-path wiring: the two formulations must be
bitwise-equivalent (identical level arrays AND parent arrays — the kernels
preserve CSR slot order, so even first-hit parent tie-breaks coincide) on
RMAT, star, path, and edgeless graphs; ELL preprocessing must round-trip the
adjacency; ragged batches must share one bucketed executable.
"""
import numpy as np
import pytest

from conftest import run_in_devices
from repro.core import ell as ELL
from repro.core import graph as G, ref
from repro.core.bfs import BFSConfig, kernels_enabled
from repro.engine import Engine, GraphSession


def _graph_cases():
    star = G.from_edges(np.zeros(12, np.int64), np.arange(1, 13), 13)
    path = G.from_edges(np.arange(29), np.arange(1, 30), 30)
    edgeless = G.from_edges(np.array([], np.int64), np.array([], np.int64), 9)
    return [("rmat", G.rmat(8, seed=5)), ("star", star), ("path", path),
            ("edgeless", edgeless)]


GRAPHS = _graph_cases()


@pytest.mark.parametrize("name,g", GRAPHS, ids=[n for n, _ in GRAPHS])
@pytest.mark.parametrize("heuristic", ["paper", "beamer"])
def test_fused_search_kernel_equivalence(name, g, heuristic):
    roots = [0, g.num_vertices - 1]
    if g.num_directed_edges:
        roots.append(int(np.argmax(g.degrees)))
    res_x = Engine(g).bfs(roots, BFSConfig(heuristic=heuristic,
                                           backend_kernels=False))
    res_k = Engine(g).bfs(roots, BFSConfig(heuristic=heuristic,
                                           backend_kernels=True))
    np.testing.assert_array_equal(res_x.level, res_k.level)
    np.testing.assert_array_equal(res_x.parent, res_k.parent)
    for i, r in enumerate(roots):
        ref.validate_parents(g, int(r), res_k.parent[i], res_k.level[i])


def test_stepper_kernel_equivalence(small_graph):
    g = small_graph
    root = int(np.argmax(g.degrees))
    res_x = Engine(g).bfs(root, BFSConfig(backend_kernels=False),
                          backend="stepper")
    res_k = Engine(g).bfs(root, BFSConfig(backend_kernels=True),
                          backend="stepper", validate=True)
    np.testing.assert_array_equal(res_x.level, res_k.level)
    np.testing.assert_array_equal(res_x.parent, res_k.parent)
    sx = res_x.per_level_stats[0]
    sk = res_k.per_level_stats[0]
    assert [s["direction"] for s in sx] == [s["direction"] for s in sk]
    assert [s["frontier_size"] for s in sx] == [s["frontier_size"] for s in sk]


def test_backend_kernels_auto_resolution():
    # unset runs the XLA step on every backend (Mosaic refuses the kernels
    # for TPU, tests/test_tpu_compile.py); the kernels stay opt-in, and a
    # session-private runtime decides exactly as the process one does
    from repro.runtime import RuntimeConfig, runtime_scope
    assert kernels_enabled(BFSConfig()) is False
    with runtime_scope(kernel_backend="on"):
        assert kernels_enabled(BFSConfig()) is True
        assert kernels_enabled(BFSConfig(),
                               RuntimeConfig(kernel_backend="off")) is False
    assert kernels_enabled(BFSConfig(),
                           RuntimeConfig(kernel_backend="on")) is True
    assert kernels_enabled(BFSConfig(backend_kernels=True)) is True
    assert kernels_enabled(BFSConfig(backend_kernels=False)) is False


# ------------------------------------------------------------ ELL building --

def test_ell_tiles_roundtrip_adjacency(small_graph):
    g = small_graph
    tiles = GraphSession(g).ell_tiles()
    seen = {}
    for rows, deg, nbrs in tiles:
        rows, deg, nbrs = map(np.asarray, (rows, deg, nbrs))
        for i, r in enumerate(rows):
            seen[int(r)] = nbrs[i, :deg[i]].tolist()
    for v in range(g.num_vertices):
        adj = g.indices[g.indptr[v]:g.indptr[v + 1]].tolist()
        # CSR slot order must be preserved exactly (parent tie-break parity).
        assert seen.get(v, []) == adj, f"vertex {v} adjacency mismatch"


def test_ell_bucket_padding_bounded(small_graph):
    tiles = GraphSession(small_graph).ell_tiles(base=32, growth=2)
    for rows, deg, nbrs in tiles:
        deg = np.asarray(deg)
        w = nbrs.shape[1]
        assert deg.min() > 0 and deg.max() <= w
        # bucket holds degrees in (w/growth, w]: per-row padding < growth x
        assert w <= max(32, 2 * int(deg.min()))


def test_ell_session_cache_is_shared(small_graph):
    session = GraphSession(small_graph)
    assert session.ell_tiles() is session.ell_tiles()


def test_ell_edgeless_graph_has_no_buckets():
    g = G.from_edges(np.array([], np.int64), np.array([], np.int64), 5)
    assert GraphSession(g).ell_tiles() == ()


# ----------------------------------------------------- batched ragged roots --

def test_ragged_batches_share_one_executable(small_graph):
    """Acceptance: batches of 3/5/7 pad (with inactive lanes) to ONE
    bucket-8 cohort executable set — init + td/bu/mixed steps + sync —
    traced once each, however many ragged sizes run."""
    g = small_graph
    session = GraphSession(g)
    engine = Engine(session)
    cfg = BFSConfig()
    for b in (3, 5, 7):
        roots = np.arange(b) + 1
        res = engine.bfs(roots, cfg, backend="fused")
        assert res.parent.shape == (b, g.num_vertices)
        for i, r in enumerate(roots):
            ref.validate_parents(g, int(r), res.parent[i], res.level[i])
    keys = [k for k in session.cache_info()["plan_sources"]
            if k[0] == "cohort"]
    assert len(keys) == 5, keys
    assert {k[2] for k in keys} == {8}           # every ragged size: bucket 8
    assert all(session.materialize_count(k) == 1 for k in keys)
    assert session.total_materialized == 5


def test_batch_bucket_boundaries():
    from repro.engine.engine import _bucket_batch
    assert _bucket_batch(1) == 1
    assert [_bucket_batch(b) for b in (2, 3, 5, 7, 8)] == [8] * 5
    assert _bucket_batch(9) == 16
    assert _bucket_batch(16) == 16


# ------------------------------------------------------------- hybrid (4dev) --

HYBRID_KERNEL_CODE = """
import numpy as np
from repro.core import graph as G, ref
from repro.core.bfs import BFSConfig
from repro.core.hybrid_bfs import HybridConfig
from repro.engine import Engine

g = G.rmat(9, seed=3)
roots = [int(np.argmax(g.degrees)), 0, 19]
for exchange in ("psum", "bitmap"):
    rx = Engine(g).bfs(roots, HybridConfig(bfs=BFSConfig(backend_kernels=False),
                                           exchange=exchange), n_parts=4)
    rk = Engine(g).bfs(roots, HybridConfig(bfs=BFSConfig(backend_kernels=True),
                                           exchange=exchange), n_parts=4)
    assert rx.backend == rk.backend == "sharded"
    np.testing.assert_array_equal(rx.level, rk.level)
    np.testing.assert_array_equal(rx.parent, rk.parent)
    for i, r in enumerate(roots):
        ref.validate_parents(g, int(r), rk.parent[i], rk.level[i])
res = Engine(g).bfs(roots[0], backend="stepper", n_parts=4,
                    cfg=HybridConfig(bfs=BFSConfig(backend_kernels=True)),
                    validate=True)
assert res.per_level_stats[0]
print("HYBRID_KERNEL_OK")
"""


@pytest.mark.slow
def test_hybrid_kernel_equivalence_4dev():
    out = run_in_devices(HYBRID_KERNEL_CODE, 4, timeout=560)
    assert "HYBRID_KERNEL_OK" in out
