"""Smoke run of the BFS engine on a TPU at LiveJournal scale.

    python chip_smoke.py                # one chip, phases (a)-(f)
    python chip_smoke.py --four-chips   # four chips: sharded vs fused only

The graph is Graph500 RMAT (A/B/C 0.57/0.19/0.19, edgefactor 16) at scale
22: 4,194,304 vertices and 128,302,398 directed edges after dedupe (seed
0) — the size of LiveJournal, one of the paper's real-world graphs. It is
generated from `--seed`; the script reads no other input.

One chip:
  (a) fail at once unless JAX's first device is a TPU;
  (b) build the graph (set-up time);
  (c) Graph500 mode through `repro.launch.bfs_run.run`, every root validated;
  (d) one batched `Engine.bfs` of 16 roots, validated;
  (e) a `BFSServer` answering concurrent queries, one streamed, with every
      failure/degradation counter at 0 and every tree validated against
      the levels (d) validated;
  (f) peak device memory.

`--four-chips` runs only the partitioned search on four chips (8 roots,
validated) and what it is compared with: the fused path on one of those
chips for the same roots, levels equal.

Every phase raises on failure, so the exit code is non-zero; the last
line of a successful run is the JSON object
`{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}`.
These timings are smoke figures, not benchmark numbers.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def require_tpu(n_chips: int):
    """Phase (a): the run is meaningless off the chip, so refuse it."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU (JAX found {len(devs)} "
                         f"{devs[0].platform} device(s))")
    if len(devs) < n_chips:
        raise SystemExit(f"chip_smoke: need {n_chips} chips, JAX found "
                         f"{len(devs)}")
    log(f"(a) jax {jax.__version__}: {len(devs)} x {devs[0].device_kind}")
    return devs


def build_graph(scale: int, seed: int):
    from repro.core import graph as G
    t0 = time.perf_counter()
    g = G.rmat(scale, edgefactor=16, seed=seed)
    log(f"(b) set-up: RMAT scale {scale} built in "
        f"{time.perf_counter() - t0:.1f}s: V={g.num_vertices} "
        f"E_directed={g.num_directed_edges} max_degree={g.max_degree}")
    return g


def phase_graph500(g, scale: int, seed: int) -> None:
    from repro.launch import bfs_run
    t0 = time.perf_counter()
    res = bfs_run.run(scale=scale, nparts=1, roots=8, seed=seed, graph=g)
    log(f"(c) Graph500 mode, 8 roots validated: "
        f"{res['teps_hmean'] / 1e6:.2f} MTEPS hmean, "
        f"{res['mean_s'] * 1e3:.1f} ms/search; phase "
        f"{time.perf_counter() - t0:.1f}s incl. compile and validation")


def phase_batched(engine, roots):
    t0 = time.perf_counter()
    res = engine.bfs(roots, validate=True)
    levels = " ".join(f"{r['direction']}:{r['seconds'] * 1e3:.0f}ms"
                      for r in res.batch_level_stats)
    log(f"(d) batched fused, {len(roots)} roots validated: "
        f"{res.seconds * 1e3:.1f} ms for the batch, "
        f"{res.teps / 1e6:.2f} MTEPS aggregate; phase "
        f"{time.perf_counter() - t0:.1f}s incl. compile and validation; "
        f"levels {levels}")
    return res


SERVER_FAILURE_COUNTERS = ("dispatch_failures", "retries", "worker_crashes",
                           "degraded_backend", "degraded_scalar")


def phase_server(session, g, checked) -> None:
    """Serve 11 of the roots `checked` (phase (d)'s validated result) and
    hold each served tree to that root's validated levels."""
    from repro.core import ref
    from repro.engine import BFSServer
    t0 = time.perf_counter()
    roots = checked.roots[:11]
    want = {int(r): checked.level[b] for b, r in enumerate(roots)}
    server = BFSServer({"lj": session})
    try:
        handles, rows = [], []
        queries = [roots[i:i + 3] for i in (0, 3, 6)]

        def submit(i, q):
            handles.append(server.submit("lj", q, client=f"client{i}"))

        threads = [threading.Thread(target=submit, args=(i, q))
                   for i, q in enumerate(queries)]
        for t in threads:
            t.start()
        streamed = server.submit("lj", roots[9:11], backend="fused",
                                 stream=True, client="stream")
        rows.extend(streamed.stream(timeout=600))
        for t in threads:
            t.join(timeout=60)
            assert not t.is_alive(), "submit thread hung"
        results = [h.result(timeout=600) for h in handles + [streamed]]
        assert rows, "the streamed query delivered no level rows"
        keys = ref.edge_keys(g)
        for res in results:
            for b, r in enumerate(res.roots):
                ref.validate_tree(g, int(r), res.parent[b], res.level[b],
                                  keys=keys, ref_level=want[int(r)])
        totals = server.stats()["totals"]
    finally:
        server.close()
    bad = {k: totals[k] for k in SERVER_FAILURE_COUNTERS if totals[k]}
    assert not bad, f"server failure counters not zero: {bad}"
    log(f"(e) server: {totals['served']} queries "
        f"({sum(len(r.roots) for r in results)} roots, all validated) in "
        f"{totals['batches']} dispatches, {len(rows)} streamed level rows, "
        f"failure counters all 0; phase {time.perf_counter() - t0:.1f}s")


def phase_memory() -> None:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    log(f"(f) peak_bytes_in_use: {peak} "
        f"({peak / 2**30:.2f} GiB)" if peak is not None
        else "(f) peak_bytes_in_use: not reported by this backend")


def four_chips(g, roots) -> None:
    import numpy as np
    from repro.core import ref
    from repro.core.hybrid_bfs import HybridShapes
    from repro.engine import Engine
    engine = Engine(g)
    t0 = time.perf_counter()
    plan = engine.plan(backend="sharded", n_parts=4)
    sharded = engine.bfs_plan(roots, plan)
    t1 = time.perf_counter()
    _, pg = engine.session.partitioned(4, plan.strategy,
                                       plan.hub_edge_fraction)
    log(f"{plan.strategy} 4-way partition: {HybridShapes.of(pg)}")
    fused = engine.bfs(roots, backend="fused")
    t2 = time.perf_counter()
    np.testing.assert_array_equal(sharded.level, fused.level,
                                  err_msg="sharded levels != fused levels")
    # One reference BFS per root serves both results: their levels are
    # equal, and the sharded ones are checked against the reference.
    keys = ref.edge_keys(g)
    for b, r in enumerate(roots):
        want = ref.bfs_levels_fast(g, int(r))
        for res in (sharded, fused):
            ref.validate_tree(g, int(r), res.parent[b], res.level[b],
                              keys=keys, ref_level=want)
    t3 = time.perf_counter()
    log(f"sharded on 4 chips, {len(roots)} roots: "
        f"{sharded.seconds * 1e3:.1f} ms pipelined "
        f"({sharded.teps / 1e6:.2f} MTEPS aggregate); phase {t1 - t0:.1f}s "
        f"incl. partitioning and compile")
    log(f"fused on chip 0, same roots: {fused.seconds * 1e3:.1f} ms batched "
        f"({fused.teps / 1e6:.2f} MTEPS aggregate); phase {t2 - t1:.1f}s "
        f"incl. compile")
    log(f"levels equal; both parent trees validated in {t3 - t2:.1f}s")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded search on four chips and "
                         "the fused path it is compared with")
    ap.add_argument("--scale", type=int, default=22)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    t_start = time.perf_counter()
    n_chips = 4 if args.four_chips else 1
    devs = require_tpu(n_chips)
    from repro.launch.bfs_run import sample_roots
    from repro.runtime import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    g = build_graph(args.scale, args.seed)
    if args.four_chips:
        four_chips(g, sample_roots(g, 8, args.seed + 1))
    else:
        from repro.engine import Engine
        phase_graph500(g, args.scale, args.seed)
        engine = Engine(g)
        checked = phase_batched(engine, sample_roots(g, 16, args.seed + 1))
        phase_server(engine.session, g, checked)
        phase_memory()
    log(f"total {time.perf_counter() - t_start:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
