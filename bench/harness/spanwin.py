"""Helpers the readers of the program's own spans share.

The program records spans (`repro.runtime.spans`) on `time.perf_counter`,
the clock the harness stamps each call with. A per-search reader sums, for
each search in `run.calls` (the whole window, not only the traced part),
the spans of its names whose `[t0, t1]` lies inside that call's
`[start, end]`, and reports the mean over the searches in ms. A set-up
reader sums those that lie between the run's start and the window's start,
in s. Both return None where there is nothing to read: a program without
spans, no span of those names in the range, or a ring that dropped part of
the range (`spans.oldest()` later than its start).
"""
from __future__ import annotations


def _spans():
    """The program's span module, or None for a program that has none."""
    try:
        from repro.runtime import spans
    except ImportError:
        return None
    return spans


def per_search_ms(run, names, **attrs):
    """Mean ms per search of the spans named in `names` (those whose
    `attrs` match where given) inside each search of the window."""
    spans = _spans()
    calls = getattr(run, "calls", None)
    if spans is None or not calls:
        return None
    start = min(c["start"] for c in calls)
    if spans.oldest() > start:
        return None
    recs = [r for r in spans.records(since=start) if r.name in names]
    if not recs:
        return None
    recs = [r for r in recs
            if all(r.attrs.get(k) == v for k, v in attrs.items())]
    total = sum(r.t1 - r.t0 for c in calls for r in recs
                if c["start"] <= r.t0 and r.t1 <= c["end"])
    return total * 1e3 / len(calls)


def setup_s(run, name):
    """Seconds in spans named `name` from the run's start to the window's
    start."""
    spans = _spans()
    if spans is None:
        return None
    start = run.t0 - run.setup_s
    if spans.oldest() > start:
        return None
    recs = [r for r in spans.records(since=start, until=run.t0)
            if r.name == name]
    if not recs:
        return None
    return sum(r.t1 - r.t0 for r in recs)
