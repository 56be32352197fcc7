"""bu_slots_per_row.g500: adjacency slots the bottom-up pull gathered per
row it queued, over the window: the sum of `pull_slots` over the sum of
`pull_rows` on the program's `repro.level.step` spans with variant `bu`
inside the window's searches (`engine/level_loop.py` copies the cohort
step's pull counters onto them). Silent for a program whose spans carry
no such counters, and where no such span queued a row."""
from bench.harness import spanwin


def read(run):
    spans = spanwin._spans()
    calls = getattr(run, "calls", None)
    if spans is None or not calls:
        return None
    start = min(c["start"] for c in calls)
    if spans.oldest() > start:
        return None
    steps = [r for r in spans.records(since=start)
             if r.name == "repro.level.step"
             and r.attrs.get("variant") == "bu" and "pull_rows" in r.attrs
             and any(c["start"] <= r.t0 and r.t1 <= c["end"] for c in calls)]
    rows = sum(r.attrs["pull_rows"] for r in steps)
    if not rows:
        return None
    return sum(r.attrs["pull_slots"] for r in steps) / rows
