"""search_mteps: millions of traversed undirected edges per second over the
window (Graph500 accounting, the benchmark's own degrees): every search
completed, over the wall time from the window's start to the end of the
last search that started in it. A rate over the whole window, not a mean of
per-search rates."""
from bench.harness import accounting


def read(run):
    if not getattr(run, "calls", None):
        return None
    last_end = max(c["end"] for c in run.calls)
    return accounting.rate_mteps([c["edges"] for c in run.calls], run.t0,
                                 last_end)
