"""Shared benchmark helpers."""
import os
import subprocess
import sys
import json

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")
RESULTS = os.path.join(REPO, "benchmarks", "results")


def emit(name: str, us_per_call: float, derived: str = ""):
    """Scaffold contract: ``name,us_per_call,derived`` CSV on stdout."""
    print(f"{name},{us_per_call:.1f},{derived}", flush=True)


def run_with_devices(module: str, n_devices: int, args=(), timeout=900):
    """Run `python -m module` on N emulated CPU devices.

    A CPU rehearsal of a multi-device path (the figure scripts' partition
    sweeps), never a chip measurement: the child is pinned to
    `JAX_PLATFORMS=cpu`, so it cannot contend for an accelerator the parent
    process may hold."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    res = subprocess.run([sys.executable, "-m", module, *map(str, args)],
                         env=env, capture_output=True, text=True,
                         timeout=timeout, cwd=REPO)
    if res.returncode != 0:
        raise RuntimeError(f"{module} failed:\n{res.stdout}\n{res.stderr}")
    return res.stdout
