"""The reader of the top-down level floor, on synthetic calls and span
records, and on a traced run of a cell at a tiny scale (CPU)."""
import types

import pytest

import tiny
from bench.harness import spanwin
from bench.harness.spec import Spec

INF = float("inf")
METRIC = "td_level_floor_ms.g500"


def rec(name, t0, t1, **attrs):
    return types.SimpleNamespace(name=name, t0=t0, t1=t1, attrs=attrs)


class FakeSpans:
    """`repro.runtime.spans` as a reader sees it, over fixed records."""

    def __init__(self, recs, oldest=-INF):
        self.recs, self._oldest = recs, oldest

    def records(self, since=None, until=None):
        return [r for r in self.recs
                if (since is None or r.t0 >= since)
                and (until is None or r.t1 <= until)]

    def oldest(self):
        return self._oldest


# Two searches in the window, [10, 14] and [14.5, 18].
RUN = types.SimpleNamespace(t0=10.0, setup_s=8.0, calls=[
    dict(start=10.0, end=14.0, edges=1), dict(start=14.5, end=18.0, edges=1)])
RECORDS = [
    # the warm-up search, before the window
    rec("repro.level.step", 9.5, 9.6, variant="td", mf=1),
    # search 1: two one-trip levels, a wide one, a bottom-up one
    rec("repro.level.step", 10.00, 10.03, variant="td", mf=2),
    rec("repro.level.step", 10.10, 10.60, variant="td", mf=12290),
    rec("repro.level.step", 10.60, 11.60, variant="bu", mf=3),
    rec("repro.level.step", 11.60, 11.65, variant="td", mf=4096),
    # search 2: a one-trip level, a mixed one, a span of another name
    rec("repro.level.step", 15.00, 15.01, variant="td", mf=17),
    rec("repro.level.step", 15.10, 15.90, variant="mixed", mf=5),
    rec("repro.level.sync", 15.90, 15.95, variant="td", mf=5),
    # between the searches, and after the window
    rec("repro.level.step", 14.1, 14.4, variant="td", mf=1),
    rec("repro.level.step", 18.5, 19.0, variant="td", mf=1),
]


@pytest.fixture
def fake(monkeypatch):
    def install(recs=RECORDS, oldest=-INF):
        spans = FakeSpans(recs, oldest)
        monkeypatch.setattr(spanwin, "_spans", lambda: spans)
    return install


def read():
    return Spec.reader(METRIC).read(RUN)


def test_reader_takes_the_median_of_the_windows_one_trip_td_steps(fake):
    fake()
    assert read() == pytest.approx(30.0)           # of 30, 50 and 10 ms


def test_reader_takes_the_middle_pair_of_an_even_count(fake):
    fake(recs=RECORDS[1:3] + RECORDS[4:6]
         + [rec("repro.level.step", 16.0, 16.07, variant="td", mf=0)])
    assert read() == pytest.approx((30.0 + 50.0) / 2)    # of 10, 30, 50, 70


@pytest.mark.parametrize("recs", [
    [rec("repro.search", 10.0, 14.0)],
    [rec("repro.level.step", 10.4, 11.4, variant="td")],
    [rec("repro.level.step", 10.4, 11.4, variant="td", mf=4097)],
    [rec("repro.level.step", 10.4, 11.4, variant="bu", mf=3),
     rec("repro.level.step", 15.0, 15.1, variant="mixed", mf=3)],
    [rec("repro.level.step", 9.0, 9.1, variant="td", mf=3)],
], ids=["no-steps", "no-mf", "wide-td-only", "no-td-step", "outside-window"])
def test_reader_is_silent_without_a_one_trip_td_step(fake, recs):
    fake(recs=recs)
    assert read() is None


def test_reader_is_silent_without_spans_or_a_whole_ring(fake, monkeypatch):
    fake(oldest=RUN.calls[0]["start"] + 1e-3)
    assert read() is None
    fake(oldest=RUN.calls[0]["start"] - 1e-3)
    assert read() is not None
    monkeypatch.setattr(spanwin, "_spans", lambda: None)
    assert read() is None


@pytest.mark.parametrize("cell", ["kron22.g500", "urand22.g500"])
def test_traced_run_reports_the_floor(cell):
    """At a tiny scale on the CPU every Graph500 search opens with a
    one-vertex top-down level, which the reader finds."""
    _rec, res = tiny.run(cell, traced=True, seconds=1.0)
    assert res["metrics"][METRIC]["value"] > 0
