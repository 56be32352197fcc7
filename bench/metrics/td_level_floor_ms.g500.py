"""td_level_floor_ms.g500: the median milliseconds of a top-down level
whose whole frontier fits one trip of the push's chunk loop: the
program's `repro.level.step` spans with variant `td` and frontier edge
mass `mf` of at most 4,096 slots (the default `td_chunk`), inside the
window's searches. Such a level pushes a handful of edges, so its time is
what any top-down level pays before its first slot. Silent where no such
span lies in the window, and where the ring dropped part of it."""
import statistics

from bench.harness import spanwin

ONE_TRIP = 4096


def read(run):
    spans = spanwin._spans()
    calls = getattr(run, "calls", None)
    if spans is None or not calls:
        return None
    start = min(c["start"] for c in calls)
    if spans.oldest() > start:
        return None
    ms = [(r.t1 - r.t0) * 1e3 for r in spans.records(since=start)
          if r.name == "repro.level.step"
          and r.attrs.get("variant") == "td"
          and r.attrs.get("mf", ONE_TRIP + 1) <= ONE_TRIP
          and any(c["start"] <= r.t0 and r.t1 <= c["end"] for c in calls)]
    return statistics.median(ms) if ms else None
