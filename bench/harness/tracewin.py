"""Helpers the per-layer metric readers share for the traced window."""
from __future__ import annotations


def traced_calls(run) -> list:
    """Calls of a closed loop that ran wholly inside the traced window."""
    if run.trace is None or not getattr(run, "calls", None):
        return []
    t0, t1 = run.trace_window
    return [c for c in run.calls if c["start"] >= t0 and c["end"] <= t1]


def idle_share(run):
    """Share of the traced window with no operation on the device, in %."""
    if run.trace is None or run.trace["window_s"] <= 0:
        return None
    return 100.0 * run.trace["idle_s"] / run.trace["window_s"]
