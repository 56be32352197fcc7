"""Spans (`repro.runtime.spans`): the record ring, where the program opens
them, and that a profiler trace holds them on the device ops' clock."""
import glob
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import graph as G
from repro.core.bfs import BFSConfig
from repro.engine import Engine, LevelDriver
from repro.runtime import spans


@pytest.fixture(autouse=True)
def _fresh_ring():
    spans.reset()
    yield
    spans.reset()


@pytest.fixture(scope="module")
def graph():
    return G.rmat(8, seed=3)


def _named(recs, name):
    return [r for r in recs if r.name == name]


def test_nesting_sets_parent_and_search_ids():
    with spans.span("outside"):
        with spans.span(spans.SEARCH, backend="fused") as search:
            with spans.span("repro.level.step", level=1) as step:
                with spans.span("repro.level.sync") as inner:
                    pass
    with spans.span("after"):
        pass
    outside, after = spans.records()[-2], spans.records()[-1]
    assert outside.name == "outside" and outside.parent_id is None
    assert outside.search_id is None and after.search_id is None
    assert search.parent_id == outside.span_id
    assert search.search_id == search.span_id
    assert step.parent_id == search.span_id
    assert inner.parent_id == step.span_id
    assert step.search_id == inner.search_id == search.span_id
    assert step.attrs == dict(level=1)
    assert outside.t0 <= search.t0 <= step.t0 <= inner.t0 <= inner.t1 \
        <= step.t1 <= search.t1 <= outside.t1
    # Records close innermost first.
    assert [r.name for r in spans.records()] == [
        "repro.level.sync", "repro.level.step", spans.SEARCH, "outside",
        "after"]


def test_ring_keeps_the_newest_and_oldest_says_what_it_dropped():
    spans.reset(capacity=4)
    for i in range(4):
        with spans.span("s", i=i):
            pass
    assert spans.oldest() == float("-inf")
    first = spans.records()
    for i in range(4, 6):
        with spans.span("s", i=i):
            pass
    kept = spans.records()
    assert [r.attrs["i"] for r in kept] == [2, 3, 4, 5]
    assert spans.oldest() == first[1].t1
    assert spans.records(since=kept[2].t0) == kept[2:]
    assert spans.records(until=kept[1].t1) == kept[:2]


def test_two_threads_keep_their_own_nesting():
    barrier = threading.Barrier(2)
    seen = {}

    def work(tag):
        with spans.span(spans.SEARCH, tag=tag) as outer:
            barrier.wait(timeout=10)
            for level in range(20):
                with spans.span("repro.level.step", tag=tag, level=level):
                    pass
        seen[tag] = outer

    threads = [threading.Thread(target=work, args=(t,)) for t in "ab"]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
        assert not t.is_alive()
    assert seen["a"].span_id != seen["b"].span_id
    for tag, outer in seen.items():
        steps = [r for r in _named(spans.records(), "repro.level.step")
                 if r.attrs["tag"] == tag]
        assert len(steps) == 20
        assert {r.parent_id for r in steps} == {outer.span_id}
        assert {r.search_id for r in steps} == {outer.span_id}


def _cohort_run(engine, root):
    backend = engine._cohort_backend(BFSConfig(), 1)
    return LevelDriver(backend).run(
        (jnp.asarray([root], jnp.int32), jnp.ones(1, bool)))


def _single_run(engine, root):
    backend = engine._stepper_backend_single(BFSConfig())
    return LevelDriver(backend).run(root)


@pytest.mark.parametrize("run", [_cohort_run, _single_run],
                         ids=["cohort", "single"])
def test_one_step_and_one_sync_per_driver_row(graph, run):
    """Each row is one `repro.level.step` and the sync after it (one more
    sync reads the state init made); rows and timings are the spans'
    timestamps."""
    engine = Engine(graph)
    root = int(np.argmax(graph.degrees))
    run(engine, root)                     # compile outside the records
    spans.reset()
    _, _, rows, timings = run(engine, root)
    recs = spans.records()
    steps = _named(recs, "repro.level.step")
    syncs = _named(recs, "repro.level.sync")
    assert len(rows) >= 2
    assert len(steps) == len(rows) and len(syncs) == len(rows) + 1
    assert [s.attrs["level"] for s in syncs] == list(range(len(rows) + 1))
    for row, step in zip(rows, steps):
        assert step.attrs["level"] == row["level"]
        assert step.attrs["variant"] == row["direction"]
        assert step.attrs["nf"] == row["frontier_size"]
        assert step.attrs["mf"] == row["frontier_edges"]
        assert row["compute_s"] == step.t1 - step.t0
        assert row["seconds"] == row["compute_s"]
        assert row["exchange_s"] == 0.0
    init, = _named(recs, "repro.level.init")
    fin, = _named(recs, "repro.level.finalize")
    assert timings["init_s"] == init.t1 - init.t0
    assert timings["agg_s"] == fin.t1 - fin.t0
    assert timings["driver_overhead_s"] == pytest.approx(
        max((fin.t1 - init.t0) - sum(r.t1 - r.t0 for r in (init, fin))
            - sum(r["seconds"] for r in rows), 0.0))
    assert not _named(recs, "repro.level.exchange")


def test_plan_resolve_fires_once_per_executable(graph):
    engine = Engine(graph)
    roots = np.argsort(graph.degrees)[-3:]
    engine.bfs([int(roots[0])], batched=False)
    resolved = _named(spans.records(), "repro.plan.resolve")
    keys = sorted(r.attrs["key"] for r in resolved)
    assert keys == sorted(["cohort_b1_init", "cohort_b1_scalars",
                           "cohort_b1_td", "cohort_b1_bu", "cohort_b1_mixed"])
    assert {r.attrs["source"] for r in resolved} == {"traced"}
    assert all(r.t1 > r.t0 for r in resolved)
    spans.reset()
    engine.bfs([int(r) for r in roots[1:]], batched=False)
    engine.bfs([int(roots[0])], batched=False)
    recs = spans.records()
    assert not _named(recs, "repro.plan.resolve")
    assert len(_named(recs, spans.SEARCH)) == 2


def test_result_and_ingest_spans_carry_their_sizes(graph):
    src = np.repeat(np.arange(8), 3)
    dst = np.tile([1, 2, 2], 8)
    g = G.from_edges(src, dst, 8)
    dedupe, = _named(spans.records(), "repro.ingest.dedupe")
    assert dedupe.attrs == dict(edges_in=24, edges_out=g.num_undirected_edges)
    assert [r.name for r in spans.records()] == [
        "repro.ingest.dedupe", "repro.ingest.sort", "repro.ingest.validate"]
    spans.reset()
    Engine(graph).bfs([0], batched=False)
    transfer, = _named(spans.records(), "repro.result.transfer")
    assert transfer.attrs["bytes"] == 2 * 4 * graph.num_vertices
    search, = _named(spans.records(), spans.SEARCH)
    assert search.attrs == dict(backend="fused", batch=1)
    for name in ("repro.result.convert", "repro.result.assemble",
                 "repro.level.init", "repro.level.step"):
        assert {r.search_id for r in _named(spans.records(), name)} == \
            {search.span_id}


def test_profiler_trace_holds_every_span_on_the_host_plane(graph, tmp_path):
    """Each record of a traced search is a host event of the same name in
    the `.xplane.pb`, inside the annotation the search ran under."""
    from jax.profiler import ProfileData, TraceAnnotation
    engine = Engine(graph)
    root = int(np.argmax(graph.degrees))
    engine.bfs([root], batched=False)     # compile outside the trace
    spans.reset()
    with jax.profiler.trace(str(tmp_path)):
        with TraceAnnotation("test.outer"):
            engine.bfs([root], batched=False)
    recs = spans.records()
    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    events = [(ev.name, ev.start_ns, ev.end_ns)
              for plane in ProfileData.from_file(path).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for ev in line.events
              if ev.name.startswith(("repro.", "test.outer"))]
    outer, = [e for e in events if e[0] == "test.outer"]
    inner = sorted(e[0] for e in events if e[0].startswith("repro."))
    assert inner == sorted(r.name for r in recs)
    assert len(_named(recs, "repro.level.step")) >= 2
    for _, start, end in events:
        assert outer[1] <= start <= end <= outer[2]


def test_cohort_modules_are_named_by_what_they_run(graph):
    engine = Engine(graph)
    backend = engine._cohort_backend(BFSConfig(), 1)
    roots, active = jnp.asarray([0], jnp.int32), jnp.ones(1, bool)
    state = backend.init((roots, active))
    backend.scalars(state)

    def module(partial_or_fn, *args):
        fn = getattr(partial_or_fn, "func", partial_or_fn)
        args = getattr(partial_or_fn, "args", ()) + args
        return fn._fn.lower(*args).as_text().split("\n", 1)[0]

    assert "@jit_cohort_b1_init" in module(backend._init, roots, active)
    assert "@jit_cohort_b1_scalars" in module(backend._scalars, state)
    for variant in ("td", "bu"):
        backend._steps[variant](state)
        assert f"@jit_cohort_b1_{variant}" in module(backend._steps[variant],
                                                     state)
