"""The readers of the program's spans, on synthetic calls and span records,
and on a traced run of a cell at a tiny scale (CPU)."""
import types

import pytest

import tiny
from bench.harness import spanwin
from bench.harness.spec import Spec

INF = float("inf")


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


# Two searches in the window, [10, 14] and [14.5, 18]; set-up from 2 to 10.
RUN = types.SimpleNamespace(t0=10.0, setup_s=8.0, calls=[
    dict(start=10.0, end=14.0, edges=1), dict(start=14.5, end=18.0, edges=1)])
RECORDS = [
    # an earlier run in the same process, before this one's start
    rec("repro.ingest.dedupe", 0.5, 1.0), rec("repro.plan.resolve", 1.0, 1.5,
                                              key="cohort_b1_td"),
    # set-up
    rec("repro.ingest.dedupe", 3.0, 4.0, edges_in=9, edges_out=7),
    rec("repro.ingest.sort", 4.0, 6.5), rec("repro.ingest.validate", 6.5, 7.0),
    rec("repro.plan.resolve", 8.0, 8.75, key="cohort_b1_init"),
    rec("repro.plan.resolve", 8.75, 9.0, key="cohort_b1_td"),
    rec("repro.level.step", 9.5, 9.9, variant="bu"),   # the warm-up search
    # search 1
    rec("repro.level.sync", 10.1, 10.15, level=0),
    rec("repro.level.step", 10.2, 10.3, variant="td", level=1),
    rec("repro.level.sync", 10.3, 10.35, level=1),
    rec("repro.level.step", 10.4, 11.4, variant="bu", level=2),
    rec("repro.level.sync", 11.4, 11.5, level=2),
    rec("repro.result.transfer", 11.6, 11.7, bytes=8),
    rec("repro.result.convert", 11.7, 11.75),
    rec("repro.result.assemble", 11.75, 11.8),
    # search 2
    rec("repro.level.sync", 14.6, 14.7, level=0),
    rec("repro.level.step", 14.8, 15.0, variant="td", level=1),
    rec("repro.level.step", 15.0, 15.2, variant="mixed", level=2),
    rec("repro.result.transfer", 15.3, 15.6, bytes=8),
    rec("repro.result.assemble", 15.6, 15.7),
    # after the window: the check has no spans, but a straggler might
    rec("repro.level.step", 18.5, 19.0, variant="td"),
]

EXPECTED = {
    "td_step_ms_per_search.g500": (0.1 + 0.2) / 2 * 1e3,
    "bu_step_ms_per_search.g500": 1.0 / 2 * 1e3,
    "level_sync_ms_per_search.g500": (0.05 + 0.05 + 0.1 + 0.1) / 2 * 1e3,
    "result_transfer_ms_per_search.g500": (0.1 + 0.3) / 2 * 1e3,
    "result_host_ms_per_search.g500": (0.05 + 0.05 + 0.1) / 2 * 1e3,
    "ingest_dedupe_s": 1.0,
    "ingest_sort_s": 2.5,
    "plan_resolve_s": 1.0,
}
PER_SEARCH = [m for m in EXPECTED if m.endswith(".g500")]
SETUP = [m for m in EXPECTED if not m.endswith(".g500")]


@pytest.fixture
def fake(monkeypatch):
    def install(recs=RECORDS, oldest=-INF):
        spans = FakeSpans(recs, oldest)
        monkeypatch.setattr(spanwin, "_spans", lambda: spans)
    return install


@pytest.mark.parametrize("metric", list(EXPECTED))
def test_reader_sums_its_spans_in_its_range(fake, metric):
    fake()
    assert Spec.reader(metric).read(RUN) == pytest.approx(EXPECTED[metric])


@pytest.mark.parametrize("metric", list(EXPECTED))
def test_reader_is_silent_where_the_ring_dropped_its_range(fake, metric):
    start = RUN.calls[0]["start"] if metric in PER_SEARCH \
        else RUN.t0 - RUN.setup_s
    fake(oldest=start + 1e-3)
    assert Spec.reader(metric).read(RUN) is None
    fake(oldest=start - 1e-3)
    assert Spec.reader(metric).read(RUN) is not None


@pytest.mark.parametrize("metric", list(EXPECTED))
def test_reader_is_silent_without_spans_to_read(fake, monkeypatch, metric):
    """A program with no span module (the parent of this reader), or one
    that recorded no span of the reader's names, reads as nothing."""
    fake(recs=[rec("repro.search", 10.0, 14.0)])
    assert Spec.reader(metric).read(RUN) is None
    monkeypatch.setattr(spanwin, "_spans", lambda: None)
    assert Spec.reader(metric).read(RUN) is None


def test_step_of_another_direction_reads_zero_not_nothing(fake):
    fake(recs=[rec("repro.level.step", 10.2, 10.3, variant="td")])
    assert Spec.reader("bu_step_ms_per_search.g500").read(RUN) == 0.0


def test_traced_run_reports_every_span_metric():
    """At a tiny scale on the CPU the real program's spans feed every
    reader: each value is there and no larger than what it is part of."""
    rec_, res = tiny.run("kron22.g500", traced=True, seconds=1.0)
    got = {k: v["value"] for k, v in res["metrics"].items()}
    assert set(EXPECTED) <= set(got)
    assert all(got[m] >= 0 for m in EXPECTED)
    assert got["td_step_ms_per_search.g500"] > 0
    per_search_ms = sum(c["end"] - c["start"] for c in rec_.calls) * 1e3 \
        / len(rec_.calls)
    assert sum(got[m] for m in PER_SEARCH) <= per_search_ms
    assert got["ingest_dedupe_s"] + got["ingest_sort_s"] <= got["csr_build_s"]
    assert got["plan_resolve_s"] <= got["warm_s"]
