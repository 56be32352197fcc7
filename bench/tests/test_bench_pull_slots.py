"""The reader of the bottom-up pull's counters, on synthetic calls and span
records, and on a traced run of a cell at a tiny scale (CPU)."""
import types

import pytest

import tiny
from bench.harness import spanwin
from bench.harness.spec import Spec

INF = float("inf")
METRIC = "bu_slots_per_row.g500"


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
    rec("repro.level.step", 9.5, 9.9, variant="bu", pull_rows=7,
        pull_slots=700),
    # search 1
    rec("repro.level.step", 10.2, 10.3, variant="td", pull_rows=0,
        pull_slots=0),
    rec("repro.level.step", 10.4, 11.4, variant="bu", pull_rows=100,
        pull_slots=130),
    rec("repro.level.step", 11.4, 11.9, variant="bu", pull_rows=20,
        pull_slots=50),
    # search 2: a mixed level's counters belong to no bottom-up step
    rec("repro.level.step", 15.0, 15.2, variant="mixed", pull_rows=9,
        pull_slots=90),
    rec("repro.level.step", 15.2, 15.9, variant="bu", pull_rows=80,
        pull_slots=100),
    # after the window
    rec("repro.level.step", 18.5, 19.0, variant="bu", pull_rows=5,
        pull_slots=500),
]


@pytest.fixture
def fake(monkeypatch):
    def install(recs=RECORDS, oldest=-INF):
        spans = FakeSpans(recs, oldest)
        monkeypatch.setattr(spanwin, "_spans", lambda: spans)
    return install


def read():
    return Spec.reader(METRIC).read(RUN)


def test_reader_divides_slots_by_rows_over_the_windows_bu_steps(fake):
    fake()
    assert read() == pytest.approx((130 + 50 + 100) / (100 + 20 + 80))


@pytest.mark.parametrize("recs", [
    [rec("repro.search", 10.0, 14.0)],
    [rec("repro.level.step", 10.4, 11.4, variant="bu")],
    [rec("repro.level.step", 10.4, 11.4, variant="td", pull_rows=3,
         pull_slots=3)],
    [rec("repro.level.step", 10.4, 11.4, variant="bu", pull_rows=0,
         pull_slots=0)],
], ids=["no-steps", "no-counters", "no-bu-step", "no-rows"])
def test_reader_is_silent_without_counters_to_read(fake, recs):
    """The parent program's steps carry no counters: nothing to read."""
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
def test_traced_run_reports_slots_per_row(cell):
    """At a tiny scale on the CPU the real program's counters feed the
    reader: at least one slot per queued row."""
    _rec, res = tiny.run(cell, traced=True, seconds=1.0)
    assert res["metrics"][METRIC]["value"] >= 1.0
