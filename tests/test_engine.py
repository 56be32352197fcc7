"""repro.engine: batched multi-root BFS vs oracle, compiled-plan cache hits,
backend selection, and the no-private-imports API boundary."""
import os

import numpy as np
import pytest

from conftest import run_in_devices
from repro.core import graph as G, ref
from repro.core.bfs import BFSConfig
from repro.engine import Engine, GraphSession, TraversalResult


def _fused_keys(session):
    return [k for k in session.cache_info()["plan_sources"]
            if k[0] == "fused"]


def _cohort_keys(session):
    return [k for k in session.cache_info()["plan_sources"]
            if k[0] == "cohort"]


# One cohort plan = init + 3 step variants (td/bu/mixed) + the sync payload.
COHORT_EXECUTABLES = 5


def test_batched_multiroot_matches_reference(medium_graph):
    g = medium_graph
    rng = np.random.default_rng(0)
    roots = rng.choice(np.flatnonzero(g.degrees > 0), 8, replace=False)
    res = Engine(g).bfs(roots, BFSConfig())
    assert isinstance(res, TraversalResult)
    assert res.parent.shape == (8, g.num_vertices)
    assert res.backend == "fused" and res.batch_size == 8
    for b, root in enumerate(roots):
        ref.validate_parents(g, int(root), res.parent[b], res.level[b])


def test_batch_of_8_roots_single_trace(small_graph):
    """Acceptance: a >=8-root batch materializes its cohort executable set
    exactly once per (config, bucket) — one trace cold, one disk load under
    a warm artifact cache, never both — and identical follow-up queries
    never rebuild anything."""
    session = GraphSession(small_graph)
    engine = Engine(session)
    cfg = BFSConfig(heuristic="paper")
    roots = np.arange(8)
    engine.bfs(roots, cfg)
    keys = _cohort_keys(session)
    assert len(keys) == COHORT_EXECUTABLES, keys
    assert all(session.materialize_count(k) == 1 for k in keys)
    # same config + batch shape, different roots: pure cache hit
    engine.bfs(roots + 100, cfg)
    engine.bfs(roots, BFSConfig(heuristic="paper"))  # equal config, new object
    assert all(session.materialize_count(k) == 1 for k in keys)
    assert session.total_materialized == COHORT_EXECUTABLES
    # a different config is a different plan: one more executable set,
    # old keys untouched
    engine.bfs(roots, BFSConfig(heuristic="beamer"))
    assert all(session.materialize_count(k) == 1 for k in keys)
    assert session.total_materialized == 2 * COHORT_EXECUTABLES


def test_unbatched_mode_is_b1_cohort(small_graph):
    session = GraphSession(small_graph)
    engine = Engine(session)
    res = engine.bfs([3, 5, 9], batched=False, validate=True)
    assert res.per_root_seconds.shape == (3,)
    # The scalar path IS the cohort path at bucket 1: no separate
    # whole-search executable, just the one shared cohort set, materialized
    # once across all 3 roots.
    assert _fused_keys(session) == []
    keys = _cohort_keys(session)
    assert keys and all(k[2] == 1 for k in keys), keys
    assert len(keys) == COHORT_EXECUTABLES, keys
    assert session.total_materialized == COHORT_EXECUTABLES
    assert all(session.materialize_count(k) == 1 for k in keys)
    assert res.teps_hmean > 0


def test_scalar_root_and_empty_batch(small_graph):
    engine = Engine(small_graph)
    res = engine.bfs(7)
    assert res.parent.shape == (1, small_graph.num_vertices)
    empty = engine.bfs(np.array([], dtype=np.int64))
    assert empty.parent.shape == (0, small_graph.num_vertices)
    assert empty.seconds == 0.0


def test_degenerate_edgeless_graph():
    g = G.from_edges(np.array([], np.int64), np.array([], np.int64), 6)
    res = Engine(g).bfs([0, 3, 5])
    for b, root in enumerate([0, 3, 5]):
        assert res.parent[b, root] == root and res.level[b, root] == 0
        others = np.arange(6) != root
        assert (res.parent[b, others] == -1).all()
        ref.validate_parents(g, root, res.parent[b], res.level[b])


def test_degenerate_star_graph():
    center, leaves = 0, np.arange(1, 7)
    g = G.from_edges(np.zeros(6, np.int64), leaves, 7)
    res = Engine(g).bfs([center, 3], validate=True)
    assert res.num_levels[0] == 1 and res.num_levels[1] == 2
    assert (res.level[0, leaves] == 1).all()


def test_degenerate_disconnected_graph():
    # two components: {0,1,2} path and {3,4} edge; 5 isolated
    g = G.from_edges(np.array([0, 1, 3]), np.array([1, 2, 4]), 6)
    res = Engine(g).bfs([0, 4, 5], validate=True)
    assert (res.level[0, [3, 4, 5]] == -1).all()
    assert (res.level[1, [0, 1, 2, 5]] == -1).all()
    assert res.reached(2).tolist() == [5]


def test_component_teps_accounting():
    """Graph500 rule: a root is credited only with its component's edges.

    Two components ({0,1,2} path: 2 edges; {3,4}: 1 edge) plus isolated 5.
    The old accounting divided every root by the whole-graph edge count,
    inflating TEPS for small components; that figure survives as
    `teps_global`.
    """
    from repro.engine import edges_traversed_from_levels
    g = G.from_edges(np.array([0, 1, 3]), np.array([1, 2, 4]), 6)
    res = Engine(g).bfs([0, 4, 5], validate=True)
    assert res.edges_traversed.tolist() == [2, 1, 0]
    np.testing.assert_array_equal(
        edges_traversed_from_levels(g.degrees, res.level),
        res.edges_traversed)
    # aggregate: 3 traversed edges, vs 3 roots x 3 global edges
    assert res.teps == pytest.approx(3 / res.seconds, rel=1e-9)
    assert res.teps_global == pytest.approx(9 / res.seconds, rel=1e-9)
    per = res.teps_per_root
    assert per[2] == 0.0                      # isolated root traverses nothing
    # zero-TEPS roots are excluded from the harmonic mean (a single isolated
    # root would otherwise zero out the whole batch's reported throughput)
    import statistics
    assert res.teps_hmean == pytest.approx(
        statistics.harmonic_mean(per[:2].tolist()))
    # single-component queries: both figures coincide
    res2 = Engine(g).bfs([3], validate=True)
    assert res2.edges_traversed.tolist() == [1]
    assert res2.teps == pytest.approx(res2.teps_global / 3, rel=1e-9)


def test_teps_hmean_guards_zero_teps_roots():
    """Regression: a batch containing an edgeless/isolated root used to
    report hmean 0 (or raise, interpreter-dependent) — the zero-TEPS root
    must be excluded, and an all-zero batch must report 0.0, not raise."""
    g = G.from_edges(np.array([0, 1, 3]), np.array([1, 2, 4]), 6)
    mixed = Engine(g).bfs([0, 5])             # one real root, one isolated
    assert mixed.teps_hmean > 0.0
    assert mixed.teps_hmean == pytest.approx(float(mixed.teps_per_root[0]))
    only_isolated = Engine(g).bfs([5])
    assert only_isolated.teps_hmean == 0.0
    edgeless = G.from_edges(np.array([], np.int64), np.array([], np.int64), 4)
    assert Engine(edgeless).bfs([0, 1, 2]).teps_hmean == 0.0


def test_result_split():
    g = G.from_edges(np.array([0, 1, 3]), np.array([1, 2, 4]), 6)
    res = Engine(g).bfs([0, 4, 5, 1])
    parts = res.split([1, 2, 1])
    assert [p.batch_size for p in parts] == [1, 2, 1]
    np.testing.assert_array_equal(parts[1].roots, [4, 5])
    np.testing.assert_array_equal(parts[1].parent, res.parent[1:3])
    np.testing.assert_array_equal(parts[1].edges_traversed,
                                  res.edges_traversed[1:3])
    assert sum(p.seconds for p in parts) == pytest.approx(res.seconds)
    with pytest.raises(ValueError):
        res.split([2, 3])


def test_session_mesh_axis_validation(small_graph):
    """A user-supplied mesh with a mismatched axis must fail up front with a
    clear message, not deep inside shard_map."""
    import jax
    from jax.sharding import Mesh
    mesh = Mesh(np.array(jax.devices()[:1]), ("x",))
    session = GraphSession(small_graph, mesh=mesh)
    with pytest.raises(ValueError, match="axis 'part'"):
        session.mesh_for(1, "part")
    assert session.mesh_for(1, "x") is mesh
    with pytest.raises(ValueError, match="devices"):
        session.mesh_for(2, "x")


def test_stepper_backend_stats(small_graph):
    g = small_graph
    root = int(np.argmax(g.degrees))
    res = Engine(g).bfs(root, backend="stepper", validate=True)
    stats = res.per_level_stats[0]
    # one BSP round per discovered level + the final empty-discovery round
    assert len(stats) == res.num_levels[0] + 1
    assert stats[0]["direction"] == "td" and stats[0]["frontier_size"] == 1
    for s in stats:
        assert s["seconds"] >= s["compute_s"] >= 0
    assert set(res.timings[0]) == {"init_s", "agg_s", "driver_overhead_s"}


def test_backend_validation_errors(small_graph):
    engine = Engine(small_graph)
    with pytest.raises(ValueError):
        engine.bfs(0, backend="warp")
    with pytest.raises(ValueError):
        engine.bfs(0, backend="fused", n_parts=2)
    with pytest.raises(ValueError):
        engine.bfs(0, backend="sharded", n_parts=1)
    with pytest.raises(ValueError):
        engine.bfs(small_graph.num_vertices)  # root out of range


def test_no_private_core_imports_outside_core():
    """API boundary: `_bfs_jit` / `_device_bfs` / other core-private symbols
    must not be referenced outside src/repro/core."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    offenders = []
    for base in ("src", "examples", "benchmarks", "tests"):
        for dirpath, _dirs, files in os.walk(os.path.join(repo, base)):
            if os.path.join("repro", "core") in dirpath:
                continue
            for fname in files:
                if not fname.endswith(".py"):
                    continue
                path = os.path.join(dirpath, fname)
                text = open(path, encoding="utf-8").read()
                for sym in ("_bfs_jit", "_device_bfs", "_top_down_step",
                            "_bottom_up_step", "_local_top_down",
                            "_local_bottom_up"):
                    if sym in text and fname != os.path.basename(__file__):
                        offenders.append(f"{path}: {sym}")
    assert not offenders, "\n".join(offenders)


SHARDED_CODE = """
import numpy as np
from repro.core import graph as G, ref
from repro.core.bfs import BFSConfig
from repro.engine import Engine, GraphSession

g = G.rmat(9, seed=3)
session = GraphSession(g)
engine = Engine(session)
roots = [int(np.argmax(g.degrees)), 0, 7, 19, 30, 41, 52, 63]
res = engine.bfs(roots, BFSConfig(), n_parts=4)
assert res.backend == "sharded" and res.parent.shape == (8, g.num_vertices)
for b, root in enumerate(roots):
    ref.validate_parents(g, root, res.parent[b], res.level[b])
# pipelined batch + per-root mode + a second batch: still ONE trace
engine.bfs(roots[:2], BFSConfig(), n_parts=4, batched=False)
engine.bfs([11, 13], BFSConfig(), n_parts=4)
counts = list(session.cache_info()["trace_counts"].values())
assert counts == [1], counts
# stepper backend on the same session, multi-partition
res2 = engine.bfs(roots[0], backend="stepper", n_parts=4)
st = res2.per_level_stats[0]
assert st and all(s["exchange_s"] >= 0 for s in st)
ref.validate_parents(g, roots[0], res2.parent[0], res2.level[0])
print("ENGINE_SHARDED_OK")
"""


@pytest.mark.slow
def test_engine_sharded_4dev():
    out = run_in_devices(SHARDED_CODE, 4, timeout=420)
    assert "ENGINE_SHARDED_OK" in out


def _cohort_step_hlo_chars(scale: int) -> dict:
    """HLO text length of each cohort executable the engine dispatches."""
    import jax.numpy as jnp
    g = G.rmat(scale, seed=1)
    backend = Engine(g)._cohort_backend(BFSConfig(), 8)
    roots = jnp.asarray(np.arange(8), jnp.int32)
    state = backend.init((roots, jnp.ones(8, bool)))
    sizes = {}
    for variant, step in backend._steps.items():
        step(state)                       # resolve the plan executable
        sizes[variant] = len(step.func._fn.lower(*step.args, state).as_text())
    return sizes


def test_cohort_steps_take_the_graph_as_an_argument():
    """No cohort executable embeds the graph: from scale 10 to 14 the edge
    count grows 16x, and a closed-over CSR would grow the HLO with it
    (megabytes of constants); as arguments only shape digits change."""
    small, large = _cohort_step_hlo_chars(10), _cohort_step_hlo_chars(14)
    for variant in small:
        assert large[variant] < 1.05 * small[variant] + 2000, (
            variant, small[variant], large[variant])
