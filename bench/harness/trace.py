"""Profile part of a window and reduce the trace to device time.

`Tracer` runs JAX's profiler from the window's start until `seconds` have
passed (stopping at the first call boundary after that), and marks both
ends with harness spans so the reduction knows the traced window on the
trace's own clock. `reduce_trace` reads the `.xplane.pb` with
`jax.profiler.ProfileData` only:

- busy: the union of the intervals in which an operation ran on a device
  (its "XLA Ops" line), clipped to the window, averaged over devices;
- idle gaps: the rest of the window, each named by the innermost harness
  span (`bench.*`) open at its midpoint;
- device ops: the summed self time (nested ops taken out) of each
  operation, named by its HLO instruction name and opcode.
"""
from __future__ import annotations

import glob
import os
import re
import shutil
import threading
import time
from collections import defaultdict

import numpy as np

BEGIN, END = "bench.trace_begin", "bench.trace_end"
OPS_LINE = "XLA Ops"


class Tracer:
    def __init__(self, directory: str, seconds: float):
        self.directory = directory
        self.seconds = seconds
        self._lock = threading.Lock()
        self._t0 = None
        self.t_stop = None
        self._stopped = False

    def start(self) -> None:
        import jax
        from jax.profiler import TraceAnnotation
        shutil.rmtree(self.directory, ignore_errors=True)
        # Harness spans and device activity only: no Python call tracing
        # (it slows the host), no HLO protos (they bloat the file).
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        options.enable_hlo_proto = False
        jax.profiler.start_trace(self.directory, profiler_options=options)
        with TraceAnnotation(BEGIN):
            self._t0 = time.perf_counter()

    @property
    def window(self) -> tuple:
        """(start, stop) of the traced part on `time.perf_counter`."""
        return self._t0, self.t_stop

    def maybe_stop(self) -> None:
        if time.perf_counter() - self._t0 >= self.seconds:
            self.stop()

    def stop(self) -> None:
        import jax
        from jax.profiler import TraceAnnotation
        with self._lock:
            if self._stopped or self._t0 is None:
                return
            self._stopped = True
            with TraceAnnotation(END):
                self.t_stop = time.perf_counter()
            jax.profiler.stop_trace()

    def path(self) -> str:
        found = sorted(glob.glob(os.path.join(
            self.directory, "plugins", "profile", "*", "*.xplane.pb")))
        if not found:
            raise FileNotFoundError(f"no trace under {self.directory}")
        return found[-1]


_OP = re.compile(r"^(%?[\w.\-]+) = .*?\s([a-z][\w\-]*)\(")


def _short(name: str) -> str:
    """`%fusion.34 = s32[...] fusion(...)` -> `%fusion.34 fusion`."""
    m = _OP.match(name)
    return f"{m.group(1)} {m.group(2)}" if m else name[:80]


def _union(intervals):
    """Merge [start, end) intervals; returns the sorted disjoint list."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def reduce_events(device_ops: list, host_spans: list, top: int = 10) -> dict:
    """The reduction, on plain data (tested on synthetic traces).

    `device_ops`: per device, a list of (name, start_ns, end_ns).
    `host_spans`: (name, start_ns, end_ns) harness spans, including the
    `bench.trace_begin` / `bench.trace_end` markers that bound the window.
    """
    begins = [s for n, s, _ in host_spans if n == BEGIN]
    ends = [e for n, _, e in host_spans if n == END]
    if not begins or not ends:
        raise ValueError("trace lacks the harness window markers")
    w0, w1 = min(begins), max(ends)
    window_ns = w1 - w0
    spans = [(n, s, e) for n, s, e in host_spans if n not in (BEGIN, END)]
    busy_total = 0
    op_time: dict = defaultdict(int)
    gaps = []
    for ops in device_ops:
        clipped = sorted((max(s, w0), -min(e, w1), _short(name))
                         for name, s, e in ops if e > w0 and s < w1)
        merged = _union([(s, -ne) for s, ne, _ in clipped])
        busy_total += sum(e - s for s, e in merged)
        # Self time: an op nested in another (a loop body in its `while`)
        # is taken out of its parent's time.
        stack = []
        for s, ne, name in clipped:
            e = -ne
            while stack and stack[-1][0] <= s:
                stack.pop()
            if stack:
                op_time[stack[-1][1]] -= min(e, stack[-1][0]) - s
            op_time[name] += e - s
            stack.append((e, name))
        cursor = w0
        for s, e in merged + [[w1, w1]]:
            if s > cursor:
                gaps.append((cursor, s))
            cursor = max(cursor, e)
    n_dev = max(len(device_ops), 1)
    # Name every gap by the innermost harness span open at its midpoint:
    # spans are few (one per call), gaps many, so walk the spans.
    gaps.sort()
    mids = np.array([(s + e) / 2 for s, e in gaps])
    length = np.array([e - s for s, e in gaps], dtype=np.int64)
    owner = np.full(len(gaps), -1)
    owner_len = np.full(len(gaps), np.iinfo(np.int64).max)
    for k, (_, s, e) in enumerate(spans):
        lo, hi = np.searchsorted(mids, [s, e])
        inner = owner_len[lo:hi] > e - s
        owner[lo:hi][inner] = k
        owner_len[lo:hi][inner] = e - s
    named: dict = defaultdict(int)
    for k, ln in zip(owner.tolist(), length.tolist()):
        named["idle in " + (spans[k][0] if k >= 0
                            else "no harness span")] += ln
    longest = np.sort(length)[::-1][:top]
    return dict(
        window_s=window_ns / 1e9,
        busy_s=busy_total / n_dev / 1e9,
        idle_s=(window_ns * n_dev - busy_total) / n_dev / 1e9,
        gaps=len(gaps),
        device_ops=[[k, v / 1e9] for k, v in sorted(
            op_time.items(), key=lambda kv: kv[1], reverse=True)[:top]],
        idle_gaps=[[k, v / 1e9] for k, v in sorted(
            named.items(), key=lambda kv: kv[1], reverse=True)[:top]],
        longest_gaps_s=[int(x) / 1e9 for x in longest],
    )


def read_xplane(path: str):
    """(device_ops, host_spans) from an `.xplane.pb` file."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    device_ops, host_spans = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device_ops.append([(ev.name, ev.start_ns, ev.end_ns)
                                       for ev in line.events])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        host_spans.append((ev.name, ev.start_ns, ev.end_ns))
    return device_ops, host_spans


def reduce_trace(path: str) -> dict:
    return reduce_events(*read_xplane(path))
