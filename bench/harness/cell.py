"""One run of one cell: data, set-up, the measured window, the check.

    data     the configuration's edge list, generated on the device from
             its fixed `graph_seed`, moved to the host (device buffers freed)
    set-up   the program's ingest (`repro.core.graph.from_edges`, the CSR
             build), the entry point's construction and its warm-up
    window   the mix's search keys for `seconds`, optionally profiled in part
    check    after the window, with the program's state freed: a sample of
             the answers, drawn from the run's seed, against the plain
             reference over the same edge list

`run_cell` returns the run's record and its result line; `run.py` adds the
device, prints, and exits.
"""
from __future__ import annotations

import gc
import shutil
import time
import types

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from . import accounting, drive, reference, systems, trace, traffic
from .spec import Spec

GRACE_S = 60.0          # how long past the close an answer may still come
LIMITS = dict(wrong_levels=0, bad_parents=0, missing_answers=0)


def seed_key(seed: int):
    """A JAX key for any non-negative seed (wider than 32 bits too)."""
    seed = int(seed)
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


class _CompileCounter:
    """Counts XLA backend compiles while armed (a window must see none)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.armed = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if self.armed and event == self.EVENT:
            self.count += 1


def make_data(spec: Spec, config: dict):
    """The configuration's edge list, (src, dst, V): int32 host arrays
    generated on the device from the configuration's `graph_seed`, self
    loops and duplicates included (the ingest drops them). The graph is
    the configuration's, as GAP's inputs are: every run searches the same
    graph."""
    gen = spec.generator(config["generator"])
    src_d, dst_d = gen.edges(config, seed_key(config["graph_seed"]))
    src, dst = np.array(src_d), np.array(dst_d)
    src_d.delete()
    dst_d.delete()
    return src, dst, gen.num_vertices(config)


def nonzero_vertices(src: np.ndarray, dst: np.ndarray, n: int) -> np.ndarray:
    """Vertices with at least one edge other than a self loop."""
    keep = src != dst
    seen = np.zeros(n, dtype=bool)
    seen[src[keep]] = True
    seen[dst[keep]] = True
    return np.flatnonzero(seen)


def run_cell(spec: Spec, cell_name: str, seed: int, seconds: float,
             traced: bool, t_start: float, use_control: bool = False,
             grace: float = GRACE_S, trace_dir: str = None,
             after_window=None, mix: dict = None):
    """Run `cell_name` once; returns (record, result without `device`).

    `use_control` puts the capped reference in the program's place.
    `after_window()` runs once the window has closed, before anything is
    freed (the caller reads the device's peak memory there). `mix`
    overrides the cell's traffic file (tests).
    """
    cell = spec.workload(cell_name)
    config = spec.config(cell["config"])
    mix = mix if mix is not None else spec.traffic(cell["traffic"])
    traffic.validate_mix(mix)
    compiles = _CompileCounter()
    rec = types.SimpleNamespace(cell=cell_name, config=config, mix=mix,
                                seconds=seconds, traced=traced)

    t = time.perf_counter()
    src, dst, n = make_data(spec, config)
    rec.num_vertices = n
    rec.data_s = time.perf_counter() - t
    nonzero = nonzero_vertices(src, dst, n)
    roots = traffic.search_keys(nonzero, mix["roots"])
    warm_root = int(traffic.rng_for(seed, 2).choice(nonzero))

    adj = None
    t = time.perf_counter()
    if use_control:
        adj = reference.Adjacency.from_edges(src, dst, n)
        system = systems.ControlSearch(adj, mix.get("bfs", {}))
        rec.csr_build_s = time.perf_counter() - t
    else:
        from repro.core.graph import from_edges
        with TraceAnnotation("bench.csr_build"):
            graph = from_edges(src, dst, n)
        rec.csr_build_s = time.perf_counter() - t
        t = time.perf_counter()
        with TraceAnnotation("bench.warm"):
            system = systems.EngineSearch(graph, mix.get("bfs", {}))
            system.warm(warm_root)
        del graph
    rec.warm_s = time.perf_counter() - t if not use_control else 0.0
    rec.setup_s = time.perf_counter() - t_start

    tracer = None
    if traced:
        tracer = trace.Tracer(trace_dir, float(mix.get("trace_seconds", 5)))
    compiles.armed = True
    run = drive.closed_loop(system, roots, seconds, grace, tracer)
    compiles.armed = False
    rec.compiles_in_window = compiles.count
    if after_window is not None:
        after_window()
    if not run["stuck"]:
        system.close()
    del system
    gc.collect()

    rec.trace = None
    if traced:
        rec.trace_window = tracer.window
        rec.trace = trace.reduce_trace(tracer.path())
        shutil.rmtree(trace_dir, ignore_errors=True)

    t = time.perf_counter()
    if adj is None:
        adj = reference.Adjacency.from_edges(src, dst, n)
    checks = dict(wrong_levels=0, bad_parents=0, missing_answers=0)
    _record(rec, run, adj, seed, mix, checks)
    rec.check_s = time.perf_counter() - t
    correct = all(checks[k] <= LIMITS[k] for k in LIMITS) and rec.checked > 0

    metrics = {}
    for m in spec.metrics(cell_name, traced):
        value = spec.reader(m["name"]).read(rec)
        if value is not None:
            metrics[m["name"]] = dict(value=value, unit=m["unit"])
    result = dict(correct=bool(correct), attempted=rec.attempted,
                  failed=rec.failed, metrics=metrics)
    if traced:
        result["breakdown"] = dict(device_ops=rec.trace["device_ops"],
                                   idle_gaps=rec.trace["idle_gaps"])
    result["checks"] = {k: dict(value=checks[k], limit=LIMITS[k])
                        for k in LIMITS}
    result["checks"]["answers_checked"] = dict(value=rec.checked, limit=">=1")
    return rec, result


def _record(rec, run, adj, seed, mix, checks):
    """The window's searches as the metric readers see them, and the
    sampled answers judged against the reference."""
    max_levels = int(mix.get("bfs", {}).get("max_levels", 0))
    searches = run["records"]
    done = [c for c in searches if "level" in c]
    rec.t0 = run["t0"]
    rec.attempted = len(searches)
    rec.failed = rec.attempted - len(done)
    checks["missing_answers"] = rec.failed
    rec.calls = [dict(start=c["start"], end=c["end"],
                      edges=int(accounting.edges_traversed(
                          adj.degrees, c["level"])))
                 for c in done]
    pick = traffic.rng_for(seed, 3).choice(
        len(done), min(int(mix["check"]["sample"]), len(done)),
        replace=False) if done else []
    for i in pick:
        c = done[i]
        got = reference.judge(adj, c["root"], c["parent"], c["level"],
                              max_levels=max_levels)
        for k, v in got.items():
            checks[k] += v
    rec.checked = len(pick)

