"""repro.runtime: RuntimeConfig resolution, fingerprints, the artifact
cache (atomicity, corruption tolerance, LRU cap), in-process cross-session
plan sharing, and the subprocess cold/warm restart proof."""
import json
import os
import pickle
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from conftest import SRC
from repro.core import graph as G
from repro.core.bfs import BFSConfig
from repro.engine import Engine, GraphSession
from repro.runtime import (ArtifactCache, RuntimeConfig, artifact_cache_for,
                           graph_fingerprint, plan_fingerprint, registry_size,
                           runtime_scope)
from repro.runtime.config import _parse_size

COHORT_EXECUTABLES = 5


# --------------------------------------------------------- RuntimeConfig --

def test_config_precedence_explicit_over_env_over_default(tmp_path):
    env = {"REPRO_CACHE_DIR": "/env/dir", "REPRO_PREWARM": "0",
           "REPRO_CACHE_MAX_BYTES": "2MB"}
    # env beats defaults
    cfg = RuntimeConfig.resolve(env)
    assert cfg.cache_dir == "/env/dir"
    assert cfg.prewarm is False
    assert cfg.cache_max_bytes == 2 << 20
    assert cfg.share_plans is True               # untouched default
    # explicit beats env
    cfg = RuntimeConfig.resolve(env, cache_dir=str(tmp_path), prewarm=True)
    assert cfg.cache_dir == str(tmp_path)
    assert cfg.prewarm is True
    assert cfg.cache_max_bytes == 2 << 20        # env still wins over default
    # explicit None falls through to env; explicit "" disables
    assert RuntimeConfig.resolve(env, cache_dir=None).cache_dir == "/env/dir"
    assert RuntimeConfig.resolve(env, cache_dir="").cache_dir is None


def test_config_parsing_and_validation():
    assert _parse_size("1048576", name="x") == 1 << 20
    assert _parse_size("512MB", name="x") == 512 << 20
    assert _parse_size("2gb", name="x") == 2 << 30
    assert _parse_size("1.5 KB", name="x") == 1536
    with pytest.raises(ValueError, match="cannot parse size"):
        _parse_size("lots", name="x")
    for env, match in (
            ({"REPRO_KERNELS": "maybe"}, "REPRO_KERNELS"),
            ({"REPRO_KERNELS": "auto"}, "REPRO_KERNELS"),
            ({"REPRO_PREWARM": "sometimes"}, "REPRO_PREWARM")):
        with pytest.raises(ValueError, match=match):
            RuntimeConfig.resolve(env)
    with pytest.raises(ValueError, match="cache_max_bytes"):
        RuntimeConfig(cache_max_bytes=0)
    with pytest.raises(ValueError, match="kernel_backend"):
        RuntimeConfig(kernel_backend="gpuish")
    assert RuntimeConfig.resolve({"REPRO_KERNELS": "1"}).kernel_backend == "on"
    assert RuntimeConfig.resolve({}).kernel_backend == "off"


def test_launch_env_shape():
    env = RuntimeConfig(device_count=4, cache_dir="/tmp/c").launch_env()
    assert env["XLA_FLAGS"] == "--xla_force_host_platform_device_count=4"
    assert env["REPRO_CACHE_DIR"] == "/tmp/c"
    assert env["TF_CPP_MIN_LOG_LEVEL"] == "4"
    # LD_PRELOAD only when the library exists on this machine
    missing = RuntimeConfig(tcmalloc_path="/no/such/lib.so").launch_env()
    assert "LD_PRELOAD" not in missing


# ---------------------------------------------------------- fingerprints --

def test_compile_cache_honours_env_else_fixed_checkout_path(monkeypatch):
    """`enable_compile_cache` leaves a placed JAX_COMPILATION_CACHE_DIR to
    JAX; without one it points the cache at the checkout's fixed
    `.jax_cache` — the same path on every call, so runs share it."""
    import jax
    from repro.runtime import enable_compile_cache
    from repro.runtime.config import CHECKOUT
    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/elsewhere")
        jax.config.update("jax_compilation_cache_dir", None)
        assert enable_compile_cache() == "/placed/elsewhere"
        assert jax.config.jax_compilation_cache_dir is None   # set nothing
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        first, second = enable_compile_cache(), enable_compile_cache()
        assert first == second == os.path.join(CHECKOUT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == first
        assert os.path.isfile(os.path.join(CHECKOUT, "chip_smoke.py"))
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_graph_fingerprint_content_not_identity():
    a = G.rmat(8, seed=5)
    b = G.rmat(8, seed=5)       # rebuilt: same content, different object
    c = G.rmat(8, seed=6)
    assert graph_fingerprint(a) == graph_fingerprint(b)
    assert graph_fingerprint(a) != graph_fingerprint(c)
    # memoized: repeated calls on one object stay stable
    assert graph_fingerprint(a) == graph_fingerprint(a)


def test_plan_fingerprint_sensitivity():
    gh = "abc123"
    base = plan_fingerprint(gh, ("cohort", BFSConfig(), 8, "td"))
    assert base == plan_fingerprint(gh, ("cohort", BFSConfig(), 8, "td"))
    assert base != plan_fingerprint(gh, ("cohort", BFSConfig(), 16, "td"))
    assert base != plan_fingerprint(
        gh, ("cohort", BFSConfig(heuristic="beamer"), 8, "td"))
    assert base != plan_fingerprint("other", ("cohort", BFSConfig(), 8, "td"))


# --------------------------------------------------------- artifact cache --

def _populated_session(graph, cache_dir):
    """Run one fused batch with the cache at `cache_dir`; the session."""
    with runtime_scope(cache_dir=str(cache_dir), prewarm=False):
        s = GraphSession(graph)
        Engine(s).bfs(np.arange(8), BFSConfig(), backend="fused")
    return s


def test_store_load_roundtrip_and_counters(small_graph, tmp_path):
    s = _populated_session(small_graph, tmp_path)
    assert s.total_traces == COHORT_EXECUTABLES
    cache = s._artifacts
    st = cache.stats()
    assert st["stores"] == COHORT_EXECUTABLES
    assert st["entries"] == COHORT_EXECUTABLES
    assert st["bytes"] > 0
    # every stored entry loads back into a callable with readable metadata
    gh = s.graph_fingerprint
    for fp, meta in cache.scan():
        assert meta["graph_hash"] == gh
        assert meta["payload_bytes"] > 0
        assert cache.load(fp) is not None
    assert cache.stats()["hits"] == COHORT_EXECUTABLES


def test_corrupt_entry_evicted_and_silently_retraced(small_graph, tmp_path):
    """Truncating a cache entry must not break anything: the load fails,
    the entry is evicted, and the plan silently retraces."""
    s = _populated_session(small_graph, tmp_path)
    entries = sorted(os.listdir(s._artifacts.plans_dir))
    assert len(entries) == COHORT_EXECUTABLES
    for name in entries:                      # truncate every entry mid-file
        path = os.path.join(s._artifacts.plans_dir, name)
        with open(path, "r+b") as f:
            f.truncate(os.path.getsize(path) // 2)
    with runtime_scope(cache_dir=str(tmp_path), prewarm=False):
        from repro.runtime import registry_reset
        registry_reset()                      # force disk consultation
        g2 = G.rmat(9, seed=7)                # same content as small_graph
        s2 = GraphSession(g2)
        res = Engine(s2).bfs(np.arange(8), BFSConfig(), backend="fused")
        assert res.parent.shape[0] == 8
    # every corrupt entry was evicted, every plan retraced (never loaded)
    assert s2.total_traces == COHORT_EXECUTABLES
    assert s2.total_loads == 0
    assert s2._artifacts.stats()["corrupt_evictions"] >= COHORT_EXECUTABLES
    # the retrace re-published fresh entries
    assert len(s2._artifacts) == COHORT_EXECUTABLES


def test_unpicklable_garbage_entry_is_not_fatal(tmp_path):
    cache = ArtifactCache(str(tmp_path), max_bytes=1 << 20)
    with open(cache._path("deadbeef"), "wb") as f:
        f.write(b"\x00not a pickle at all")
    assert cache.load("deadbeef") is None
    assert "deadbeef" not in cache
    assert cache.scan() == []
    st = cache.stats()
    assert st["corrupt_evictions"] >= 1 and st["misses"] >= 1


def test_lru_cap_evicts_oldest_first(tmp_path):
    """Entries are evicted in least-recently-used order (loads refresh)."""
    cache = ArtifactCache(str(tmp_path), max_bytes=1 << 20)
    payload = (b"x" * 300, None, None)

    def put(fp, mtime):
        with open(cache._path(fp), "wb") as f:
            pickle.dump({"fp": fp}, f)
            pickle.dump(payload, f)
        os.utime(cache._path(fp), (mtime, mtime))

    for i, fp in enumerate(["old", "mid", "new"]):
        put(fp, 1_000_000 + i)
    total = cache.total_bytes()
    each = total // 3
    # cap so exactly one entry must go: the oldest
    cache.max_bytes = total - 1
    cache._evict_over_cap()
    assert "old" not in cache and "mid" in cache and "new" in cache
    # touch "mid" (a load refreshes mtime), then cap to one entry:
    # "new" is now the LRU and must go, "mid" survives
    os.utime(cache._path("mid"))
    cache.max_bytes = each
    cache._evict_over_cap()
    assert "mid" in cache and "new" not in cache
    assert cache.stats()["evictions"] == 2


def test_artifact_cache_disabled_without_dir():
    with runtime_scope(cache_dir=None):
        assert artifact_cache_for() is None
        s = GraphSession(G.rmat(7, seed=1))
        assert s._artifacts is None and s.prewarm_progress is None


# ------------------------------------------------- cross-session sharing --

def test_sessions_share_plans_by_content_hash(small_graph):
    """Satellite bugfix: the in-process plan cache keys on CSR content, not
    object identity — a second session over a byte-identical rebuilt graph
    reuses every compiled plan with ZERO traces."""
    with runtime_scope(cache_dir=None, share_plans=True):
        s1 = GraphSession(small_graph)
        r1 = Engine(s1).bfs(np.arange(8), BFSConfig(), backend="fused")
        assert s1.total_traces == COHORT_EXECUTABLES
        assert registry_size() == COHORT_EXECUTABLES
        g2 = G.rmat(9, seed=7)               # rebuilt, same content
        assert g2 is not small_graph
        s2 = GraphSession(g2)
        r2 = Engine(s2).bfs(np.arange(8), BFSConfig(), backend="fused")
        assert s2.total_materialized == 0    # no trace, no load: pure reuse
        assert sum(s2.cache_info()["shared_counts"].values()) \
            == COHORT_EXECUTABLES
        assert np.array_equal(np.asarray(r1.parent), np.asarray(r2.parent))
        # a *different* graph shares nothing
        s3 = GraphSession(G.rmat(9, seed=8))
        Engine(s3).bfs(np.arange(8), BFSConfig(), backend="fused")
        assert s3.total_traces == COHORT_EXECUTABLES


def test_share_plans_off_keeps_sessions_isolated(small_graph):
    with runtime_scope(cache_dir=None, share_plans=False):
        for _ in range(2):
            s = GraphSession(small_graph)
            Engine(s).bfs(np.arange(8), BFSConfig(), backend="fused")
            assert s.total_traces == COHORT_EXECUTABLES
        assert registry_size() == 0


# ------------------------------------------------ subprocess cold / warm --

_RESTART_CODE = textwrap.dedent("""
    import json, sys, time
    import numpy as np
    from repro.core import graph as G
    from repro.core.bfs import BFSConfig
    from repro.engine import Engine, GraphSession
    from repro.runtime import runtime_scope

    cache_dir = sys.argv[1]
    g = G.rmat(9, seed=7)
    with runtime_scope(cache_dir=cache_dir):
        t0 = time.perf_counter()
        s = GraphSession(g)
        res = Engine(s).bfs(np.arange(8), BFSConfig(), backend="fused")
        dt = time.perf_counter() - t0
        s.prewarm_wait(120)
        print(json.dumps(dict(
            traces=s.total_traces, loads=s.total_loads, seconds=dt,
            prewarm=s.prewarm_progress.as_dict(),
            parent_head=np.asarray(res.parent)[0, :32].tolist())))
""")


def _run_restart_child(cache_dir):
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("REPRO_CACHE_DIR", None)         # the argv dir is authoritative
    res = subprocess.run(
        [sys.executable, "-c", _RESTART_CODE, str(cache_dir)],
        capture_output=True, text=True, env=env, timeout=420)
    if res.returncode != 0:
        raise AssertionError(
            f"restart child failed (rc={res.returncode}):\n"
            f"{res.stdout}\n{res.stderr}")
    return json.loads(res.stdout.strip().splitlines()[-1])


@pytest.mark.slow
def test_subprocess_cold_then_warm_zero_retrace(tmp_path):
    """Acceptance: process A populates the cache; process B re-attaches the
    identical graph and performs ZERO retraces of the 5-executable cohort
    set (trace-counter proven), materializing every plan from disk."""
    cold = _run_restart_child(tmp_path)
    assert cold["traces"] == COHORT_EXECUTABLES
    assert cold["loads"] == 0
    warm = _run_restart_child(tmp_path)
    assert warm["traces"] == 0, warm
    assert warm["loads"] == COHORT_EXECUTABLES
    # the attach-time pre-warm found and deserialized the cohort set
    assert warm["prewarm"]["loaded"] == COHORT_EXECUTABLES
    assert warm["prewarm"]["failed"] == 0
    # loaded executables compute the same traversal
    assert warm["parent_head"] == cold["parent_head"]
