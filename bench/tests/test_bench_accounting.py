"""The benchmark's arithmetic and its metric readers (CPU)."""
import types

import numpy as np
import pytest

import tiny  # noqa: F401
from bench.harness import accounting
from bench.harness.spec import Spec


def test_edges_traversed_matches_the_programs_accounting():
    from repro.core import graph as G
    from repro.engine import Engine, edges_traversed_from_levels
    g = G.rmat(9, seed=4)
    res = Engine(g).bfs([1, 2, 3])
    np.testing.assert_array_equal(
        accounting.edges_traversed(g.degrees, res.level),
        edges_traversed_from_levels(g.degrees, res.level))


def test_search_mteps_is_a_rate_over_the_whole_window():
    run = types.SimpleNamespace(t0=10.0, calls=[
        dict(start=10.0, end=12.0, edges=3_000_000),
        dict(start=12.0, end=15.0, edges=5_000_000)])
    got = Spec.reader("search_mteps").read(run)
    assert got == pytest.approx(8.0 / 5.0)
    assert Spec.reader("search_mteps").read(
        types.SimpleNamespace(calls=[])) is None


def test_per_search_readers_count_only_searches_in_the_traced_window():
    run = types.SimpleNamespace(
        trace=dict(busy_s=0.004, idle_s=0.001, window_s=0.005),
        trace_window=(1.0, 2.0),
        calls=[dict(start=1.1, end=1.4), dict(start=1.4, end=1.9),
               dict(start=1.9, end=2.5)])
    assert Spec.reader("device_ms_per_search.g500").read(run) == \
        pytest.approx(2.0)
    assert Spec.reader("host_gap_ms_per_search.g500").read(run) == \
        pytest.approx(0.5)
    assert Spec.reader("device_idle_share.g500").read(run) == \
        pytest.approx(20.0)
    run.calls = run.calls[2:]
    assert Spec.reader("host_gap_ms_per_search.g500").read(run) is None
