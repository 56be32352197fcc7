"""Run one benchmark cell once, on the chip, and print its result line.

    python bench/run.py --workload kron22.g500 --seed 7 --seconds 30 --trace 0

`--workload` names a cell of `BENCHMARK.json`; its configuration, traffic
mix and metrics are found by name under `bench/` (see `harness/spec.py`).
The run refuses to measure anywhere but on a TPU whose `device_kind` is in
`bench/peaks.json`, with as many chips as the cell asks for: it exits
non-zero and prints no result. `--trace 1` profiles part of the window and
reports the cell's per-layer metrics instead of its end-to-end ones.

The last line of standard output is one JSON object: `correct`,
`attempted`, `failed`, `metrics`, `device`, with `--trace 1` also
`breakdown`, and last `checks`: every number compared with the reference,
beside its limit. The same checks are the last lines of standard error.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench.harness.spec import Spec  # noqa: E402


def require_chip(chips: int):
    """The TPU devices and their peaks, or exit: no CPU fallback and no
    device missing from `bench/peaks.json`."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"bench: no TPU (JAX found {len(devs)} "
                         f"{devs[0].platform} device(s)); refusing to measure")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX found "
                         f"{len(devs)}")
    try:
        peaks = Spec.peaks(devs[0].device_kind)
    except KeyError as e:
        raise SystemExit(f"bench: {e.args[0]}") from None
    return devs[:chips], peaks


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at the fixed `<checkout>/.jax_cache`
    (or where `JAX_COMPILATION_CACHE_DIR` already puts it); every program
    is cached, however fast it compiled, so later runs load them all."""
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    path = os.path.join(ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def main(argv=None, use_control: bool = False) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    spec = Spec(ROOT)
    try:
        cell = spec.workload(args.workload)
    except KeyError as e:
        raise SystemExit(f"bench: {e.args[0]}") from None
    devs, _ = require_chip(int(cell["chips"]))
    enable_compile_cache()
    from bench.harness.cell import run_cell

    memory = {}

    def read_memory():
        peak = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
        memory["peak"] = max((p for p in peak if p is not None), default=None)

    rec, result = run_cell(spec, args.workload, args.seed, args.seconds,
                           bool(args.trace), T_START, use_control=use_control,
                           trace_dir=os.path.join(ROOT, ".bench", "trace"),
                           after_window=read_memory)
    device = dict(platform=devs[0].platform, kind=devs[0].device_kind,
                  count=len(devs), memory_peak_bytes=memory.get("peak"))
    if rec.trace is not None:
        device.update(busy_s=rec.trace["busy_s"],
                      window_s=rec.trace["window_s"])
    checks = result.pop("checks")
    breakdown = result.pop("breakdown", None)
    line = dict(result, device=device)
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = checks
    log = sys.stderr
    print(f"bench: data {rec.data_s:.3f}s, csr build {rec.csr_build_s:.3f}s, "
          f"warm {rec.warm_s:.3f}s, setup {rec.setup_s:.3f}s, "
          f"compiles in window {rec.compiles_in_window}, "
          f"check {rec.check_s:.3f}s over {rec.checked} answers", file=log)
    if rec.trace is not None:
        print(f"bench: trace window {rec.trace['window_s']:.6f}s, busy "
              f"{rec.trace['busy_s']:.6f}s, {rec.trace['gaps']} idle gaps, "
              f"longest {rec.trace['longest_gaps_s'][:3]}", file=log)
    for name, c in checks.items():
        print(f"check {name}: {c['value']} (limit {c['limit']})", file=log)
    log.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    # A search that never returned leaves its worker thread in the program;
    # the result is printed, so leave without waiting on it.
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)
