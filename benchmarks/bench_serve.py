"""Serving benchmark -> benchmarks/results/BENCH_serve.json.

Measures the `BFSServer` under synthetic concurrent load:

* **load** — N client threads x M graph sessions: sustained QPS and
  aggregate component-TEPS (traversed edges per wall second across every
  concurrently served query), latency p50/p95, micro-batch coalescing ratio
  (queries per dispatch), and the queue high-water mark vs its bound.
* **trace proof** — per-session `GraphSession.total_traces` after the load:
  with a fixed per-query batch and `max_batch_roots` equal to its pow2
  bucket, every dispatch (coalesced or not) reuses ONE cohort executable
  set per session (init + td/bu/mixed steps + sync = 5 traces), so traces
  stay constant — zero per-query recompiles under concurrency.
* **overload** — a deliberately tiny server (depth 2, in-flight cap 2,
  workers not started): counts `ServerOverloaded` rejections by reason,
  then starts the workers and proves every *admitted* query completes.
* **cancellation** — `repro.launch.bfs_serve.run_cancel_probe`: submit N
  long-path traversals, cancel every other one after its first level, and
  prove the survivors' wall time matches a no-cancellation baseline
  (cancelled queries free the session worker within one level), every
  admission slot frees, and the worker survives.
* **fused cancellation** — `run_fused_cancel_probe`: cancel an in-flight
  FUSED batch (the cohort path runs on the level driver, so batched
  dispatches — not just streamed stepper queries — abort between levels):
  the abort must land within a few levels of a ~2048-level traversal and
  cost a small fraction of its wall time.
* **driver overhead** — one streamed stepper query per session records the
  unified `LevelDriver` loop's host-side cost per level
  (`timings.driver_overhead_s`), so the one-loop refactor's overhead is
  visible next to the per-level device times.
* **restart probe** — `repro.launch.bfs_serve.run_restart_probe`: two
  child processes attach the same graph against a shared artifact cache
  (`--cache-dir`, default a fresh temp dir). Records `cold_start_s`,
  `warm_start_s`, `hit_rate`; acceptance requires the warm restart to
  perform ZERO retraces and start faster than the cold one.
* **chaos probe** — `repro.launch.bfs_serve.run_chaos_probe`: 8 clients
  under a seeded fault schedule (worker crash, stragglers, dispatch and
  trace faults), then degradation (pallas->xla, batch->scalar, bitwise
  vs fault-free oracle), circuit-breaker trip+recovery, and artifact-cache
  corruption. Acceptance: zero lost queries, availability >= 0.9, every
  degradation/recovery gate green (`chaos.ok`).

Usage: python benchmarks/bench_serve.py [--scale 12] [--smoke]
"""
import argparse
import json
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np

from benchmarks.common import RESULTS, emit


def _overload_probe(graph):
    """Deterministic admission-control exercise on a not-yet-started server."""
    from repro.engine import BFSServer, ServerOverloaded

    srv = BFSServer({"g": graph}, max_queue_depth=3,
                    max_inflight_per_client=2, autostart=False)
    rejections = {"queue_full": 0, "client_inflight": 0}
    admitted = []
    # Two clients x 4 submits against depth 3 / cap 2: three enqueue, then
    # the hog hits its in-flight cap while the other client hits the full
    # queue — both rejection reasons are exercised deterministically
    # (workers start only after the burst).
    for i in range(4):
        for client in ("hog", "other"):
            try:
                admitted.append(srv.submit("g", [i], client=client))
            except ServerOverloaded as e:
                rejections[e.reason] += 1
    srv.start()
    completed = sum(1 for h in admitted if h.result(timeout=300) is not None)
    srv.close()
    return dict(submitted=8, admitted=len(admitted), completed=completed,
                rejections=rejections)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--graphs", type=int, default=2)
    ap.add_argument("--scale", type=int, default=12)
    ap.add_argument("--edgefactor", type=int, default=16)
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--queries", type=int, default=6)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--stream-every", type=int, default=3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run: scale 9, fewer queries")
    ap.add_argument("--cache-dir", default=None,
                    help="artifact-cache dir for the restart probe "
                         "(default: fresh temp dir, deleted afterwards)")
    ap.add_argument("--out", default=os.path.join(RESULTS, "BENCH_serve.json"))
    args = ap.parse_args(argv)
    if args.smoke:
        args.scale, args.queries = 9, 3

    import jax
    from repro.engine.engine import _bucket_batch
    from repro.launch.bfs_serve import (build_server, run_cancel_probe,
                                        run_chaos_probe,
                                        run_fused_cancel_probe, run_load,
                                        run_restart_probe)

    from repro.runtime import enable_compile_cache
    enable_compile_cache()
    t0 = time.time()
    # Cold-vs-warm restart accounting: two child processes share one
    # artifact cache; the warm child must retrace nothing. It runs first,
    # while this process holds no JAX backend: each child needs the device.
    cache_dir = args.cache_dir
    tmp_cache = cache_dir is None
    if tmp_cache:
        cache_dir = tempfile.mkdtemp(prefix="bench-serve-cache-")
    try:
        restart = run_restart_probe(cache_dir,
                                    scale=9 if args.smoke
                                    else min(args.scale, 10),
                                    edgefactor=args.edgefactor,
                                    seed=args.seed)
    finally:
        if tmp_cache:
            shutil.rmtree(cache_dir, ignore_errors=True)

    # max_batch_roots == bucket(batch): every coalesced dispatch lands in
    # the same pow2 bucket, making the trace proof exact. Must be the
    # engine's own bucket formula (batch 1 keeps its dedicated bucket).
    bucket = _bucket_batch(args.batch)
    server, graphs = build_server(args.graphs, args.scale,
                                  edgefactor=args.edgefactor, seed=args.seed,
                                  max_batch_roots=bucket)
    try:
        load = run_load(server, graphs, clients=args.clients,
                        queries_per_client=args.queries, batch=args.batch,
                        seed=args.seed, stream_every=args.stream_every,
                        validate=1)
        # Per-level driver overhead: one streamed stepper query per session
        # exposes `timings.driver_overhead_s` — the unified level loop's
        # host-side cost outside the timed device work.
        driver = {}
        for name, g in sorted(graphs.items()):
            root = int(np.argmax(g.degrees))
            res = server.submit(name, root, stream=True,
                                client="driver-probe").result(timeout=600)
            t = res.timings[0]
            n_levels = max(len(res.per_level_stats[0]), 1)
            driver[name] = dict(
                levels=n_levels,
                overhead_us_per_level=t["driver_overhead_s"] / n_levels * 1e6,
                level_us_mean=sum(r["seconds"]
                                  for r in res.per_level_stats[0])
                / n_levels * 1e6,
                init_ms=t["init_s"] * 1e3, agg_ms=t["agg_s"] * 1e3)
        # Snapshot load-phase stats/traces before the cancel probe adds its
        # own session (the probe's streamed queries never coalesce and would
        # skew the coalescing ratio).
        stats = server.stats()
        traces = {name: s.total_materialized
                  for name, s in server.sessions.items()}
        cancel = run_cancel_probe(server,
                                  levels=512 if args.smoke else 2048)
        fused_cancel = run_fused_cancel_probe(
            server, levels=512 if args.smoke else 2048)
    finally:
        server.close()
    probe = _overload_probe(graphs[sorted(graphs)[0]])

    # Chaos: the serving layer must self-heal under injected faults —
    # supervised worker restart, bounded retry, degradation chain, breaker
    # trip+recovery, cache-corruption eviction. Deterministic seeded
    # schedule; gates are timing-invariant.
    chaos = run_chaos_probe(scale=9 if args.smoke else min(args.scale, 10),
                            edgefactor=min(args.edgefactor, 8),
                            seed=args.seed)

    out = dict(
        config=dict(graphs=args.graphs, scale=args.scale,
                    edgefactor=args.edgefactor, clients=args.clients,
                    queries_per_client=args.queries, batch=args.batch,
                    stream_every=args.stream_every, seed=args.seed,
                    max_batch_roots=bucket),
        backend=jax.default_backend(),
        n_devices=len(jax.devices()),
        load=load,
        coalescing=dict(
            queries=stats["totals"]["served"],
            dispatches=stats["totals"]["batches"],
            queries_per_dispatch=(stats["totals"]["served"]
                                  / max(stats["totals"]["batches"], 1)),
            queue_high_water={n: c["queue_high_water"]
                              for n, c in stats["sessions"].items()},
            queue_depth_bound=stats["max_queue_depth"]),
        trace_proof=dict(
            per_session_traces=traces,
            note="cohort executable set (init + td/bu/mixed + sync) + "
                 "stepper plan per session after full load (traces + disk "
                 "loads); independent of query count == zero per-query "
                 "recompiles"),
        driver=driver,
        cancellation=cancel,
        fused_cancellation=fused_cancel,
        overload=probe,
        chaos=chaos,
        cold_start=restart,
        cold_start_s=restart["cold_start_s"],
        warm_start_s=restart["warm_start_s"],
        hit_rate=restart["hit_rate"],
        smoke=args.smoke,
        wall_s=time.time() - t0,
    )
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)

    emit("serve_query_latency_p50", load["latency_p50_ms"] * 1e3,
         f"QPS={load['qps']:.1f}")
    emit("serve_query_latency_p95", load["latency_p95_ms"] * 1e3,
         f"TEPS_sustained={load['teps_sustained']:.3e}")
    print(f"# coalescing: {out['coalescing']['queries']} queries in "
          f"{out['coalescing']['dispatches']} dispatches "
          f"({out['coalescing']['queries_per_dispatch']:.2f}/dispatch); "
          f"traces {traces}")
    print(f"# overload probe: {probe['rejections']} rejected, "
          f"{probe['completed']}/{probe['admitted']} admitted completed")
    print(f"# cancel probe: {cancel['cancelled']} cancelled / "
          f"{cancel['served']} served, wall ratio "
          f"{cancel['wall_ratio']:.2f} (1.0 = cancellation is free), "
          f"partial levels {cancel['cancelled_partial_levels']} "
          f"of {cancel['levels']}")
    print(f"# fused cancel probe: in-flight batch of "
          f"{fused_cancel['batch']} aborted at level "
          f"{fused_cancel['levels_before_abort']}/{fused_cancel['levels']} "
          f"({fused_cancel['wall_fraction']:.2%} of the full batch's wall)")
    cl = chaos["load"]
    print(f"# chaos probe: {'OK' if chaos['ok'] else 'FAILED'} | "
          f"{cl['ok']}/{cl['submitted']} ok, lost {cl['lost']}, "
          f"availability {cl['availability']:.2f}, crashes "
          f"{cl['worker_crashes']}, restarts {cl['worker_restarts']}, "
          f"retries {cl['retries']} | degraded backend="
          f"{chaos['degrade']['degraded_backend']} scalar="
          f"{chaos['degrade']['degraded_scalar']} | breaker trips="
          f"{chaos['breaker']['trips']} recovered="
          f"{chaos['breaker']['recovered']} | cache corrupt_evictions="
          f"{chaos['cache']['corrupt_evictions']}")
    print(f"# restart probe: cold {restart['cold_start_s']:.2f}s "
          f"({restart['cold_traces']} traces) -> warm "
          f"{restart['warm_start_s']:.2f}s ({restart['warm_traces']} traces, "
          f"{restart['warm_loads']} loads, hit rate "
          f"{restart['hit_rate']:.2f}) = {restart['speedup']:.1f}x")
    for name, d in sorted(driver.items()):
        print(f"# driver overhead {name}: "
              f"{d['overhead_us_per_level']:.0f} us/level over "
              f"{d['levels']} levels (device level mean "
              f"{d['level_us_mean']:.0f} us)")
    print(f"# wrote {args.out}")

    ok = (probe["completed"] == probe["admitted"]
          and probe["rejections"]["queue_full"] > 0
          and probe["rejections"]["client_inflight"] > 0
          and load["teps_sustained"] > 0
          # cancellation acceptance: every cancel landed, every slot freed,
          # the worker survived, and the cancelled half cost ~no service
          # time (generous 2x bound: CI timing noise, not a perf gate)
          and cancel["cancelled"] == cancel["queries"] // 2
          and cancel["served"] == cancel["queries"] - cancel["cancelled"]
          and cancel["inflight_after"] == 0
          and cancel["worker_alive"]
          and cancel["wall_ratio"] < 2.0
          # fused-batch cancellation acceptance: the in-flight batched
          # dispatch aborted at level granularity (a few levels in, far
          # from the end), freeing its admission slot
          and fused_cancel["cancelled"]
          and 1 <= fused_cancel["levels_before_abort"] < fused_cancel["levels"]
          and fused_cancel["inflight_after"] == 0
          # restart acceptance: the warm process retraced NOTHING (every
          # plan materialized from the shared artifact cache) and started
          # faster than the cold one
          and restart["warm_traces"] == 0
          and restart["warm_loads"] > 0
          and restart["warm_start_s"] < restart["cold_start_s"]
          # chaos acceptance: zero lost queries under injected faults,
          # availability floor, and every degradation/recovery gate green
          # (worker restart, retry, pallas->xla and batch->scalar bitwise
          # vs oracle, breaker trip+close, cache corruption evicted)
          and chaos["ok"]
          and chaos["load"]["zero_lost"]
          and chaos["load"]["availability"] >= 0.9)
    if not ok:
        print("# ERROR: serving acceptance conditions not met",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
