"""Shared fixtures. NOTE: no XLA_FLAGS here by design — smoke tests and
benches must see 1 device; multi-device tests spawn subprocesses."""
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")
for _p in (SRC, REPO):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def run_in_devices(code: str, n_devices: int, timeout: int = 420):
    """Run python `code` in a subprocess with N emulated CPU devices.

    A rehearsal of a multi-device path on the CPU backend, never a chip run:
    the child is pinned to `JAX_PLATFORMS=cpu`, so it cannot contend for an
    accelerator the parent process may hold."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    res = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=timeout)
    if res.returncode != 0:
        raise AssertionError(
            f"subprocess failed (rc={res.returncode}):\n{res.stdout}\n{res.stderr}")
    return res.stdout


@pytest.fixture(autouse=True)
def _reset_plan_registry():
    """Isolate cross-session plan sharing between tests.

    The runtime layer's in-process registry shares compiled plans across
    sessions by graph *content* hash — and the session-scoped graph
    fixtures reuse one graph across many tests, so without this reset a
    test's trace counts would depend on which tests ran before it.
    Sharing-specific tests exercise the registry within their own body.
    """
    from repro.runtime import registry_reset
    registry_reset()
    yield
    registry_reset()


@pytest.fixture(scope="session", autouse=True)
def _sanitizer_cycle_gate():
    """Under REPRO_SANITIZE=1 the whole test session doubles as a deadlock
    audit: if the env-installed concurrency sanitizer observed a lock-order
    cycle anywhere in the run, fail at teardown with the full report."""
    yield
    from repro.analysis import concurrency as _conc
    san = _conc.active()
    if san is not None:
        rep = san.report()
        assert rep["cycles"] == [], (
            f"lock-order cycles observed during the test session: "
            f"{rep['cycles']} (edges: {rep['edges']})")


@pytest.fixture(scope="session")
def small_graph():
    from repro.core import graph as G
    return G.rmat(9, seed=7)


@pytest.fixture(scope="session")
def medium_graph():
    from repro.core import graph as G
    return G.rmat(11, seed=3)
