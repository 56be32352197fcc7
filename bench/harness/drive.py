"""Drive a system through one measured window: one caller, closed loop.

A system is an adapter from `systems.py` (the program, or the control in
its place). The loop runs one caller's searches back to back in a worker
thread and waits for a late answer up to `grace` seconds past the window's
close: an answer that comes late is late, not wrong; one that never comes
is missing.
"""
from __future__ import annotations

import threading
import time

import numpy as np
from jax.profiler import TraceAnnotation


def closed_loop(system, roots: np.ndarray, seconds: float, grace: float,
                tracer=None) -> dict:
    """Searches `roots` in turn until `seconds` have passed since the start.

    Returns `t0` (window start), `records` (one per search: start, end,
    root, and parent/level [V] or `error`), and `stuck` (a search was still
    running `grace` seconds after the close: its answer never came).
    """
    records: list = []
    t0 = time.perf_counter()
    if tracer is not None:
        tracer.start()

    def worker():
        i = 0
        while time.perf_counter() - t0 < seconds:
            rec = dict(start=time.perf_counter(), root=int(roots[i % len(roots)]))
            i += 1
            records.append(rec)
            try:
                with TraceAnnotation("bench.search"):
                    rec["parent"], rec["level"] = system.search(rec["root"])
            except Exception as e:  # noqa: BLE001 — a failed call is data
                rec["error"] = repr(e)
            rec["end"] = time.perf_counter()
            if tracer is not None:
                tracer.maybe_stop()

    th = threading.Thread(target=worker, name="bench-closed-loop",
                          daemon=True)
    th.start()
    th.join(max(t0 + seconds + grace - time.perf_counter(), 0.0))
    stuck = th.is_alive()
    if tracer is not None:
        tracer.stop()
    return dict(t0=t0, records=list(records), stuck=stuck)
