"""Helpers the benchmark's CPU tests share: the real cells at a tiny scale."""
from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench.harness.spec import Spec  # noqa: E402

class TinySpec(Spec):
    """`BENCHMARK.json` as committed, each configuration cut to `scale`."""

    def __init__(self, scale: int = 10):
        super().__init__()
        self.scale = scale

    def config(self, name):
        return dict(super().config(name), scale=self.scale)


def tiny_mix(spec: Spec, cell: str, **changes) -> dict:
    """The cell's own traffic mix with `changes` applied (top-level keys)."""
    return dict(spec.traffic(spec.workload(cell)["traffic"]), **changes)


def run(cell: str, seed: int = 5, seconds: float = 1.0, traced=False,
        use_control=False, grace: float = 20.0, scale: int = 10, **changes):
    """`run_cell` on the CPU at a tiny scale; returns (record, result)."""
    import time

    from bench.harness.cell import run_cell
    spec = TinySpec(scale)
    return run_cell(spec, cell, seed, seconds, traced, time.perf_counter(),
                    use_control=use_control, grace=grace,
                    trace_dir=os.path.join(ROOT, ".bench", f"trace-{os.getpid()}"),
                    mix=tiny_mix(spec, cell, **changes))
