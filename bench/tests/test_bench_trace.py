"""The trace reduction, on synthetic traces (CPU)."""
import pytest

import tiny  # noqa: F401
from bench.harness import trace

MS = 1_000_000


def spans(*extra):
    return [(trace.BEGIN, 0, 10), (trace.END, 100 * MS, 100 * MS + 5)] + \
        list(extra)


def test_busy_is_the_union_of_overlapping_ops_clipped_to_the_window():
    ops = [("fusion", -5 * MS, 10 * MS), ("scatter", 5 * MS, 20 * MS),
           ("while", 50 * MS, 60 * MS), ("late", 95 * MS, 130 * MS)]
    got = trace.reduce_events([ops], spans())
    assert got["window_s"] == pytest.approx(0.1 + 5e-9)
    assert got["busy_s"] == pytest.approx((20 + 10 + 5) / 1e3 + 5e-9,
                                          abs=1e-8)
    assert got["idle_s"] == pytest.approx(got["window_s"] - got["busy_s"])
    assert got["gaps"] == 2
    assert got["longest_gaps_s"][0] == pytest.approx(0.035)
    names = dict(got["device_ops"])
    assert names["scatter"] == pytest.approx(0.015)
    assert names["fusion"] == pytest.approx(0.005)   # its overlap is scatter's


def test_nested_ops_count_their_self_time_under_short_names():
    loop = "%while.4 = (s32[]{:T(128)}, u8[4194304]{0:T(1024)}) while((s32[]) %t)"
    body = "%fusion.34 = s32[16384]{0:T(1024)S(1)} fusion(s32[128]{0} %g)"
    ops = [(loop, 0, 10 * MS), (body, 2 * MS, 5 * MS), (body, 6 * MS, 7 * MS)]
    got = trace.reduce_events([ops], spans())
    names = dict(got["device_ops"])
    assert names == {"%while.4 while": pytest.approx(0.006),
                     "%fusion.34 fusion": pytest.approx(0.004)}
    assert got["busy_s"] == pytest.approx(0.010, abs=1e-8)


def test_gaps_are_named_by_the_innermost_open_span():
    ops = [("a", 0, 10 * MS), ("b", 40 * MS, 70 * MS),
           ("c", 90 * MS, 100 * MS)]
    host = spans(("bench.search", 5 * MS, 80 * MS),
                 ("bench.wait", 15 * MS, 35 * MS))
    got = trace.reduce_events([ops], host)
    idle = dict(got["idle_gaps"])
    assert idle["idle in bench.wait"] == pytest.approx(0.030)
    assert idle["idle in no harness span"] == pytest.approx(0.020)
    assert got["busy_s"] + sum(idle.values()) == pytest.approx(
        got["window_s"])


def test_busy_is_averaged_over_devices():
    a = [("x", 0, 50 * MS)]
    b = [("y", 0, 100 * MS)]
    got = trace.reduce_events([a, b], spans())
    assert got["busy_s"] == pytest.approx(0.075, abs=1e-8)


def test_a_trace_without_the_window_markers_is_refused():
    with pytest.raises(ValueError):
        trace.reduce_events([[]], [("bench.search", 0, 1)])
