"""TEPS trajectory benchmark -> benchmarks/results/BENCH_bfs.json.

Tracks, from this PR onward:

* **traversal** — TEPS for the fused and sharded backends, XLA reference path
  vs the Pallas kernel path (`BFSConfig.backend_kernels`), on a fixed-seed
  RMAT graph. Off-TPU the kernels run under the Pallas *interpreter* — those
  numbers measure correctness plumbing, not kernel speed — so the kernel
  traversal runs at `--kernel-scale` to stay sane on CPU containers; on a
  real TPU backend it runs at full `--scale`.
* **bookkeeping** — the per-level frontier bookkeeping microbenchmark: three
  separate passes/dispatches (pack + count + edge-mass, the pre-PR per-level
  cost) vs the fused single-dispatch formulations (XLA fused and the Pallas
  `frontier_fused` kernel). The acceptance bar is >= 1.2x for the fused
  bookkeeping; both kernel and XLA numbers are reported.
* **ragged_batch** — trace-count proof that ragged batch sizes (3/5/7) share
  one bucketed executable (set) instead of compiling one each.
* **cohort** — the batch-native cohort fused path vs the old
  vmap-of-whole-search baseline on a direction-mixed batch (hub + low-degree
  + isolated roots): wall/TEPS for both, the per-level direction split
  (td/bu/mixed cohort sizes), and the wasted-lane fraction the cohort model
  reclaims (lane-levels where a lane is finished — work the vmap select
  still paid for, in both directions). `hetero_occupancy` breaks that
  fraction down by hub/tail side with per-level frontier masses.
* **hetero** — the heterogeneous hub/tail split (`BFSConfig.hub_split`) vs
  the unsplit cohort path on the XLA reference backend: a small `hub_deg`
  sweep, bitwise parents/levels checks, and the >= 1.15x TEPS acceptance
  bar on the skewed RMAT graph.
* **energy** — `benchmarks/energy_model.py` applied to the measured TEPS:
  MTEPS/watt and joules/search for the cpu-only (unsplit) vs hybrid
  (split / sharded) configurations, the paper's GreenGraph500 angle.

Usage: python benchmarks/bench_teps.py [--scale 16] [--smoke]
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import RESULTS, emit


def _time_calls(fn, *, warmup=2, iters=20):
    for _ in range(warmup):
        jax.block_until_ready(fn())
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn()
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def _traversal(graph, roots, cfg, backend, n_parts):
    from repro.engine import Engine
    engine = Engine(graph)
    res = engine.bfs(roots, cfg, backend=backend, n_parts=n_parts)
    # second run: cache-hot, compile excluded by the engine's warm step
    res = engine.bfs(roots, cfg, backend=backend, n_parts=n_parts)
    # teps uses Graph500 component accounting (edges actually traversed);
    # teps_global keeps the pre-accounting-fix whole-graph figure so the
    # trajectory in BENCH_bfs.json stays comparable across PRs.
    return dict(teps=res.teps, teps_hmean=res.teps_hmean,
                teps_global=res.teps_global,
                seconds=res.seconds, batch=res.batch_size,
                backend=res.backend, n_parts=res.n_parts)


def _bookkeeping(v, seed, iters):
    """Per-level frontier bookkeeping: 3 separate passes vs fused."""
    from repro.core import frontier as fr
    from repro.kernels import ops

    rng = np.random.default_rng(seed)
    flags = jnp.asarray((rng.random(v) < 0.1).astype(np.uint8))
    deg = jnp.asarray(rng.integers(0, 64, v).astype(np.int32))

    pack_j = jax.jit(fr.pack)
    count_j = jax.jit(fr.count)
    edge_j = jax.jit(fr.edge_count)

    def separate():
        # the pre-PR per-level cost: three dispatches, three V-passes
        return pack_j(flags), count_j(flags), edge_j(flags, deg)

    fused_xla = jax.jit(
        lambda f, d: (fr.pack(f), fr.count(f), fr.edge_count(f, d)))

    sep_s = _time_calls(separate, iters=iters)
    fx_s = _time_calls(lambda: fused_xla(flags, deg), iters=iters)
    fp_s = _time_calls(lambda: ops.frontier_fused(flags, deg), iters=iters)
    return dict(
        v=v,
        separate_passes_us=sep_s * 1e6,
        fused_xla_us=fx_s * 1e6,
        fused_pallas_us=fp_s * 1e6,
        pallas_mode=("mosaic" if jax.default_backend() == "tpu"
                     else "interpret"),
        speedup_fused_xla=sep_s / fx_s,
        speedup_fused_pallas=sep_s / fp_s,
    )


def _ragged_proof(graph):
    from repro.core.bfs import BFSConfig
    from repro.engine import Engine, GraphSession

    session = GraphSession(graph)
    engine = Engine(session)
    for b in (3, 5, 7):
        engine.bfs(np.arange(b), BFSConfig(), backend="fused")
    cohort_keys = [k for k in session.cache_info()["plan_sources"]
                   if k[0] == "cohort"]
    counts = {repr(k): session.materialize_count(k) for k in cohort_keys}
    return dict(batches=[3, 5, 7],
                cohort_executables=len(cohort_keys),
                cohort_buckets=sorted({k[2] for k in cohort_keys}),
                total_traces=session.total_materialized, trace_counts=counts)


def _cohort_vs_vmap(graph, seed):
    """Direction-mixed fused batch: cohort path vs vmap-of-whole-search.

    The baseline is the pre-cohort formulation this PR replaced: `vmap`
    over `search_state`, whose per-level `lax.cond` lowers to a select —
    every lane executes BOTH directions every level and the batch runs
    until its slowest member finishes. The batch mixes a hub root, a few
    low-degree roots, and isolated roots, so lanes disagree on direction
    and finish at very different levels.
    """
    import jax
    import jax.numpy as jnp
    from repro.core import bfs as B
    from repro.core.bfs import BFSConfig
    from repro.engine import Engine, GraphSession

    cfg = BFSConfig()
    session = GraphSession(graph)
    engine = Engine(session)
    rng = np.random.default_rng(seed)
    deg = graph.degrees
    pos = np.flatnonzero(deg > 0)
    iso = np.flatnonzero(deg == 0)
    lows = pos[deg[pos] <= np.percentile(deg[pos], 30)]
    roots = [int(np.argmax(deg))]
    roots += rng.choice(lows, min(4, len(lows)), replace=False).tolist()
    filler = iso if len(iso) >= 8 - len(roots) else pos
    roots += rng.choice(filler, 8 - len(roots), replace=False).tolist()
    roots = np.asarray(roots)

    # backend pinned: "auto" would pick sharded on multi-device containers
    # at full scale, and the comparison is fused-batching formulations.
    engine.bfs(roots, cfg, backend="fused")      # warm the cohort plan
    res = engine.bfs(roots, cfg, backend="fused")

    dg = session.device_graph()
    base = jax.jit(
        lambda d, rr: jax.vmap(lambda r: B.search_state(d, r, cfg))(rr))
    dev_roots = jnp.asarray(roots, jnp.int32)
    jax.block_until_ready(base(dg, dev_roots).frontier)  # compile outside
    t0 = time.perf_counter()
    st = base(dg, dev_roots)
    jax.block_until_ready(st.frontier)
    vmap_s = time.perf_counter() - t0
    _, level_v = B.finalize(st)
    np.testing.assert_array_equal(level_v, res.level)  # same answers

    rows = res.batch_level_stats
    per_level = [dict(level=r["level"], direction=r["direction"],
                      td_lanes=r["td_lanes"], bu_lanes=r["bu_lanes"],
                      active_lanes=r["active_lanes"], batch=r["batch"])
                 for r in rows]
    lane_levels = sum(r["batch"] for r in rows)
    wasted = sum(r["batch"] - r["active_lanes"] for r in rows)
    edges = float(res.edges_traversed.sum())
    return dict(
        batch=len(roots), roots=[int(r) for r in roots],
        levels=len(rows),
        vmap_seconds=vmap_s, cohort_seconds=res.seconds,
        speedup_cohort=vmap_s / max(res.seconds, 1e-12),
        teps_vmap=edges / max(vmap_s, 1e-12), teps_cohort=res.teps,
        mixed_levels=sum(r["direction"] == "mixed" for r in per_level),
        wasted_lane_fraction=wasted / max(lane_levels, 1),
        per_level=per_level,
    )


def _hetero(graph, seed, repeats=5):
    """Heterogeneous hub/tail split vs unsplit on the XLA fused path.

    The tentpole's headline gate: split dispatch (per-side direction
    choice, static-row hub pull, degree-bounded tail chunks) must beat the
    unsplit cohort baseline by >= 1.15x TEPS on the skewed RMAT graph with
    bitwise-identical parents/levels (the paper heuristic's sides always
    agree, so the split is a pure execution reorganization). A small
    `hub_deg` sweep is reported; `best` is the winning knob setting.
    """
    from repro.core.bfs import BFSConfig
    from repro.core.partition import hub_tail_masses
    from repro.engine import Engine, GraphSession

    rng = np.random.default_rng(seed)
    cand = np.flatnonzero(graph.degrees > 0)
    roots = rng.choice(cand, min(8, len(cand)), replace=False)
    session = GraphSession(graph)
    engine = Engine(session)

    def median_teps(cfg):
        engine.bfs(roots, cfg, backend="fused")          # warm
        return float(np.median([
            engine.bfs(roots, cfg, backend="fused").teps_hmean
            for _ in range(repeats)]))

    base_cfg = BFSConfig(heuristic="paper")
    base_res = engine.bfs(roots, base_cfg, backend="fused")
    base_teps = median_teps(base_cfg)

    max_deg = int(graph.degrees.max())
    sweep = [d for d in (512, 1024, 2048) if d <= max(max_deg, 32)] or [32]
    configs, best = [], None
    for hub_deg in sweep:
        cfg = BFSConfig(heuristic="paper", hub_split=True, hub_deg=hub_deg)
        res = engine.bfs(roots, cfg, backend="fused")
        bitwise = bool(
            np.array_equal(np.asarray(base_res.parent), np.asarray(res.parent))
            and np.array_equal(np.asarray(base_res.level),
                               np.asarray(res.level)))
        teps = median_teps(cfg)
        row = dict(hub_deg=hub_deg, split_teps=teps,
                   speedup=teps / max(base_teps, 1e-12), bitwise=bitwise,
                   masses=hub_tail_masses(graph.degrees, hub_deg))
        configs.append(row)
        if best is None or row["speedup"] > best["speedup"]:
            best = row
    return dict(
        roots=[int(r) for r in roots], heuristic="paper",
        unsplit_teps=base_teps, sweep=configs, best=best,
        speedup=best["speedup"], bitwise=best["bitwise"],
        target_speedup=1.15,
    )


def _hetero_occupancy(graph, roots, hub_deg=1024):
    """Per-level hub/tail occupancy of a split run (the wasted-lane
    breakdown the cohort section recorded but never decomposed)."""
    from repro.core.bfs import BFSConfig
    from repro.engine import Engine

    cfg = BFSConfig(heuristic="paper", hub_split=True, hub_deg=hub_deg)
    res = Engine(graph).bfs(roots, cfg, backend="fused")
    rows = res.batch_level_stats or []
    per_level = [dict(level=r["level"], direction=r["direction"],
                      td_lanes=r["td_lanes"], bu_lanes=r["bu_lanes"],
                      hub_td_lanes=r.get("hub_td_lanes", 0),
                      hub_bu_lanes=r.get("hub_bu_lanes", 0),
                      frontier_hub=r.get("frontier_hub", 0),
                      frontier_tail=r.get("frontier_tail", 0),
                      active_lanes=r["active_lanes"], batch=r["batch"])
                 for r in rows]
    lane_levels = sum(r["batch"] for r in rows)
    wasted = sum(r["batch"] - r["active_lanes"] for r in rows)
    hub_front = sum(r["frontier_hub"] for r in per_level)
    tail_front = sum(r["frontier_tail"] for r in per_level)
    return dict(
        hub_deg=hub_deg,
        wasted_lane_fraction=wasted / max(lane_levels, 1),
        frontier_mass_hub=hub_front, frontier_mass_tail=tail_front,
        hub_frontier_share=hub_front / max(hub_front + tail_front, 1),
        asymmetric_levels=sum(
            r["direction"] == "mixed" and
            (bool(r["hub_bu_lanes"]) != bool(r["bu_lanes"] - r["hub_bu_lanes"]
                                             > 0) if r["bu_lanes"] else False)
            for r in per_level),
        per_level=per_level,
    )


def _energy(graph, hetero, traversal):
    """The paper's GreenGraph500 angle over OUR measured TEPS.

    `benchmarks/energy_model.py`'s calibrated utilization model, applied to
    this container's numbers: the unsplit fused path plays the CPU-only 2S
    config; the heterogeneous split plays the hybrid 2S2G config (the hub
    side is the latency-element workload the paper gives the CPUs, the
    tail the throughput mass); the sharded run (when devices allow) is
    reported under the same hybrid draw.
    """
    from benchmarks.energy_model import (busy_power, joules_per_search,
                                         mteps_per_watt)

    edges = 2.0 * graph.num_undirected_edges
    cpu_teps = hetero["unsplit_teps"]
    hyb_teps = hetero["best"]["split_teps"]
    rows = dict(
        cpu_only=dict(teps=cpu_teps, n_cpu=2, n_gpu=0,
                      busy_watts=busy_power(2, 0),
                      mteps_per_watt=mteps_per_watt(cpu_teps, 2, 0),
                      joules_per_search=joules_per_search(cpu_teps, edges,
                                                          2, 0)),
        hybrid_split=dict(teps=hyb_teps, n_cpu=2, n_gpu=2,
                          busy_watts=busy_power(2, 2),
                          mteps_per_watt=mteps_per_watt(hyb_teps, 2, 2),
                          joules_per_search=joules_per_search(hyb_teps, edges,
                                                              2, 2)),
    )
    sh = traversal.get("sharded_xla")
    if isinstance(sh, dict):
        rows["hybrid_sharded"] = dict(
            teps=sh["teps"], n_cpu=2, n_gpu=2, busy_watts=busy_power(2, 2),
            mteps_per_watt=mteps_per_watt(sh["teps"], 2, 2),
            joules_per_search=joules_per_search(sh["teps"], edges, 2, 2))
    ratio = (rows["hybrid_split"]["mteps_per_watt"]
             / max(rows["cpu_only"]["mteps_per_watt"], 1e-12))
    return dict(
        model="benchmarks.energy_model (utilization-calibrated, paper §4.3)",
        edges_per_search=edges,
        configs=rows,
        hybrid_over_cpu_mteps_per_watt=ratio,
        masses=hetero["best"]["masses"],
    )


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=int, default=16)
    ap.add_argument("--edgefactor", type=int, default=16)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--roots", type=int, default=8)
    ap.add_argument("--kernel-scale", type=int, default=11,
                    help="graph scale for interpret-mode kernel traversal "
                         "(ignored on TPU, where full --scale is used)")
    ap.add_argument("--iters", type=int, default=30)
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run: scale 9, 2 roots, few iters")
    ap.add_argument("--out", default=os.path.join(RESULTS, "BENCH_bfs.json"))
    args = ap.parse_args(argv)
    if args.smoke:
        args.scale, args.kernel_scale, args.roots, args.iters = 9, 9, 2, 5

    from repro.core import graph as G
    from repro.core.bfs import BFSConfig
    from repro.runtime import enable_compile_cache

    enable_compile_cache()
    on_tpu = jax.default_backend() == "tpu"
    kscale = args.scale if on_tpu else min(args.scale, args.kernel_scale)
    n_dev = len(jax.devices())
    n_parts = min(n_dev, 4)

    t0 = time.time()
    g = G.rmat(args.scale, edgefactor=args.edgefactor, seed=args.seed)
    gk = g if kscale == args.scale else G.rmat(
        kscale, edgefactor=args.edgefactor, seed=args.seed)
    rng = np.random.default_rng(args.seed)
    cand = np.flatnonzero(g.degrees > 0)
    roots = rng.choice(cand, min(args.roots, len(cand)), replace=False)
    candk = np.flatnonzero(gk.degrees > 0)
    rootsk = rng.choice(candk, min(args.roots, len(candk)), replace=False)

    traversal = {}
    traversal["fused_xla"] = _traversal(
        g, roots, BFSConfig(backend_kernels=False), "fused", 1)
    traversal["fused_pallas"] = _traversal(
        gk, rootsk, BFSConfig(backend_kernels=True), "fused", 1)
    if n_parts >= 2:
        traversal["sharded_xla"] = _traversal(
            g, roots, BFSConfig(backend_kernels=False), "sharded", n_parts)
        traversal["sharded_pallas"] = _traversal(
            gk, rootsk, BFSConfig(backend_kernels=True), "sharded", n_parts)
    else:
        traversal["sharded_skipped"] = f"only {n_dev} device(s)"

    book = _bookkeeping(g.num_vertices, args.seed, args.iters)
    ragged = _ragged_proof(g)
    cohort = _cohort_vs_vmap(g, args.seed)
    hetero = _hetero(g, args.seed, repeats=3 if args.smoke else 5)
    # Decompose the cohort section's wasted-lane fraction by hub/tail side
    # on the same direction-mixed batch the cohort comparison used.
    cohort["hetero_occupancy"] = _hetero_occupancy(
        g, np.asarray(cohort["roots"]), hub_deg=hetero["best"]["hub_deg"])
    energy = _energy(g, hetero, traversal)

    out = dict(
        graph=dict(scale=args.scale, edgefactor=args.edgefactor,
                   seed=args.seed, V=g.num_vertices,
                   E_undirected=g.num_undirected_edges),
        kernel_graph=dict(scale=kscale, V=gk.num_vertices,
                          note=("full scale on TPU; interpret-mode kernels "
                                "run a reduced scale on CPU")),
        backend=jax.default_backend(),
        n_devices=n_dev,
        traversal=traversal,
        bookkeeping=book,
        ragged_batch=ragged,
        cohort=cohort,
        hetero=hetero,
        energy=energy,
        smoke=args.smoke,
        wall_s=time.time() - t0,
    )
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1)

    for name, row in traversal.items():
        if isinstance(row, dict):
            emit(f"bfs_teps_{name}",
                 row["seconds"] * 1e6 / max(row["batch"], 1),
                 f"TEPS={row['teps']:.3e}")
    emit("frontier_bookkeeping_separate", book["separate_passes_us"], "")
    emit("frontier_bookkeeping_fused_xla", book["fused_xla_us"],
         f"speedup={book['speedup_fused_xla']:.2f}x")
    emit("frontier_bookkeeping_fused_pallas", book["fused_pallas_us"],
         f"speedup={book['speedup_fused_pallas']:.2f}x "
         f"({book['pallas_mode']})")
    print(f"# ragged batches 3/5/7 -> {ragged['cohort_executables']} cohort "
          f"executable(s) in bucket(s) {ragged['cohort_buckets']}, "
          f"{ragged['total_traces']} trace(s)")
    emit("fused_batch_vmap_baseline", cohort["vmap_seconds"] * 1e6,
         f"TEPS={cohort['teps_vmap']:.3e}")
    emit("fused_batch_cohort", cohort["cohort_seconds"] * 1e6,
         f"TEPS={cohort['teps_cohort']:.3e} "
         f"speedup={cohort['speedup_cohort']:.2f}x")
    print(f"# cohort mixed batch: {cohort['mixed_levels']}/{cohort['levels']} "
          f"mixed levels, wasted-lane fraction "
          f"{cohort['wasted_lane_fraction']:.2f} "
          f"(lane-levels the cohort model skips, vmap paid)")
    occ = cohort["hetero_occupancy"]
    print(f"# hetero occupancy (hub_deg={occ['hub_deg']}): hub frontier "
          f"share {occ['hub_frontier_share']:.3f}, wasted-lane fraction "
          f"{occ['wasted_lane_fraction']:.2f}")
    best = hetero["best"]
    emit("bfs_hetero_split",
         1e6 / max(best["split_teps"], 1e-12),
         f"TEPS={best['split_teps']:.3e} hub_deg={best['hub_deg']} "
         f"speedup={best['speedup']:.2f}x bitwise={best['bitwise']}")
    e = energy["configs"]
    print(f"# energy: cpu-only {e['cpu_only']['mteps_per_watt']:.3f} "
          f"MTEPS/W vs hybrid split {e['hybrid_split']['mteps_per_watt']:.3f}"
          f" MTEPS/W (x{energy['hybrid_over_cpu_mteps_per_watt']:.2f})")
    print(f"# wrote {args.out}")

    rc = 0
    if book["speedup_fused_xla"] < 1.2 and book["speedup_fused_pallas"] < 1.2:
        print("# WARNING: fused bookkeeping below the 1.2x acceptance bar",
              file=sys.stderr)
        # Smoke mode is a CI build step on shared runners: microsecond-scale
        # timings are too noisy to gate a build, so warn without failing.
        rc = 0 if args.smoke else 1
    if not hetero["bitwise"]:
        print("# ERROR: hetero split not bitwise vs unsplit", file=sys.stderr)
        rc = 1
    if hetero["speedup"] < hetero["target_speedup"]:
        print(f"# WARNING: hetero split {hetero['speedup']:.2f}x below the "
              f"{hetero['target_speedup']}x acceptance bar", file=sys.stderr)
        # Same noise argument as above; the smoke graph (scale 9) is also
        # too small to show the split's convoy-effect win.
        rc = rc if args.smoke else 1
    return rc


if __name__ == "__main__":
    sys.exit(main())
