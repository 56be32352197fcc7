"""Disk-backed store for compiled BFS executables (the persistent plan cache).

Layout (under `RuntimeConfig.cache_dir`):

    <cache_dir>/plans/<fingerprint>.exe     one file per executable
    <cache_dir>/hillclimb/...               autotuning measurements
                                            (benchmarks/bfs_hillclimb.py)

Each `.exe` file holds two consecutive pickles: a small metadata dict
(graph hash, plan key repr, environment facts, payload size — readable
without deserializing the executable, which is what pre-warm scans), then
the `jax.experimental.serialize_executable` triple
`(payload_bytes, in_tree, out_tree)`.

Guarantees:

* **atomic publish** — entries are written to a same-directory temp file
  and `os.replace`d into place, so a crashed writer can never publish a
  half-written entry and concurrent processes see either nothing or a
  complete file;
* **corruption-tolerant loads** — any failure while reading an entry
  (truncation, unpicklable bytes, stale pytree types, aval mismatch at
  deserialize) evicts that entry and reports a miss; a bad cache file is
  never fatal;
* **size-capped LRU eviction** — after each store, oldest-used entries
  (mtime order; loads touch mtime) are deleted until the total is back
  under `cache_max_bytes`;
* **environment invalidation for free** — the fingerprint folds in jax
  version / backend / device kind+count (`runtime.fingerprint`), so stale
  entries are simply never looked up again and age out via the LRU cap;
* **counters** — hits / misses / stores / evictions / corrupt evictions,
  cumulative load and store seconds, and per-entry hit/load-time counters
  (`stats()`; `BFSServer.stats()` surfaces them per session).

JAX's own persistent compilation cache (XLA level: retraces still happen,
compiles do not) is separate: `repro.runtime.config.enable_compile_cache`
places it; this module never touches it.
"""
from __future__ import annotations

import os
import pickle
import threading
import time
from typing import Any, Optional

from repro.runtime.faults import fault_point

PLANS_SUBDIR = "plans"
ENTRY_SUFFIX = ".exe"
_TMP_PREFIX = ".tmp-"


class ArtifactCache:
    """One directory of serialized executables with LRU cap + counters."""

    def __init__(self, cache_dir: str, max_bytes: int):
        self.root = os.path.abspath(cache_dir)
        self.plans_dir = os.path.join(self.root, PLANS_SUBDIR)
        self.max_bytes = int(max_bytes)
        from repro.analysis.concurrency import make_lock
        self._lock = make_lock("artifact_cache")
        self._counts = dict(hits=0, misses=0, stores=0, store_errors=0,
                            evictions=0, corrupt_evictions=0)
        self._load_s = 0.0
        self._store_s = 0.0
        self._entries: dict = {}     # fingerprint -> dict(hits, load_s, ...)
        os.makedirs(self.plans_dir, exist_ok=True)

    # -------------------------------------------------------------- paths --

    def _path(self, fingerprint: str) -> str:
        return os.path.join(self.plans_dir, fingerprint + ENTRY_SUFFIX)

    def __contains__(self, fingerprint: str) -> bool:
        return os.path.exists(self._path(fingerprint))

    def __len__(self) -> int:
        try:
            return sum(1 for n in os.listdir(self.plans_dir)
                       if n.endswith(ENTRY_SUFFIX))
        except OSError:
            return 0

    def total_bytes(self) -> int:
        total = 0
        try:
            for name in os.listdir(self.plans_dir):
                if name.endswith(ENTRY_SUFFIX):
                    try:
                        total += os.path.getsize(
                            os.path.join(self.plans_dir, name))
                    except OSError:
                        pass
        except OSError:
            pass
        return total

    # ------------------------------------------------------------ counters --

    def _entry_counts(self, fingerprint: str) -> dict:
        e = self._entries.get(fingerprint)
        if e is None:
            # repro-ok: LS001 only caller is _count, which holds _lock across this call
            e = self._entries[fingerprint] = dict(hits=0, misses=0,
                                                  load_s=0.0)
        return e

    def _count(self, fingerprint: Optional[str] = None, *, load_s: float = 0.0,
               store_s: float = 0.0, **deltas) -> None:
        with self._lock:
            for k, v in deltas.items():
                self._counts[k] += v
            self._load_s += load_s
            self._store_s += store_s
            if fingerprint is not None:
                e = self._entry_counts(fingerprint)
                e["hits"] += deltas.get("hits", 0)
                e["misses"] += deltas.get("misses", 0)
                e["load_s"] += load_s

    # --------------------------------------------------------------- store --

    def store(self, fingerprint: str, compiled, meta: dict) -> bool:
        """Serialize a jax `Compiled` and atomically publish it.

        Never raises: serialization failures (backend without executable
        export, unpicklable pytree, disk full) count as `store_errors` and
        return False — the caller keeps its in-memory executable either way.
        """
        t0 = time.perf_counter()
        tmp = None
        try:
            from jax.experimental import serialize_executable as se
            payload, in_tree, out_tree = se.serialize(compiled)
            full_meta = dict(meta)
            full_meta["payload_bytes"] = len(payload)
            full_meta["created"] = time.time()
            tmp = os.path.join(
                self.plans_dir,
                f"{_TMP_PREFIX}{fingerprint}.{os.getpid()}."
                f"{threading.get_ident()}")
            with open(tmp, "wb") as f:
                pickle.dump(full_meta, f, protocol=pickle.HIGHEST_PROTOCOL)
                pickle.dump((payload, in_tree, out_tree), f,
                            protocol=pickle.HIGHEST_PROTOCOL)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, self._path(fingerprint))
        except Exception:  # noqa: BLE001 — persistence is best-effort
            self._count(store_errors=1, store_s=time.perf_counter() - t0)
            if tmp is not None:
                try:
                    os.unlink(tmp)
                except OSError:
                    pass
            return False
        self._count(stores=1, store_s=time.perf_counter() - t0)
        self._evict_over_cap()
        return True

    # ---------------------------------------------------------------- load --

    def load(self, fingerprint: str):
        """Deserialize one entry -> callable, or None (miss / corrupt).

        A corrupt entry (truncated file, unpicklable payload, deserialize
        failure) is evicted and reported as a miss — never fatal. A
        successful load touches the entry's mtime (the LRU clock).
        """
        path = self._path(fingerprint)
        t0 = time.perf_counter()
        if not os.path.exists(path):
            self._count(fingerprint, misses=1)
            return None
        try:
            # Chaos hook inside the try: an injected cache_load fault takes
            # the exact corrupt-entry path (evict + miss), proving the
            # corruption tolerance the docstring promises.
            fault_point("cache_load", fingerprint=fingerprint)
            with open(path, "rb") as f:
                meta = pickle.load(f)
                payload, in_tree, out_tree = pickle.load(f)
            from jax.experimental import serialize_executable as se
            fn = se.deserialize_and_load(payload, in_tree, out_tree)
        except Exception:  # noqa: BLE001 — corrupt entry: evict, miss
            self._evict(path, corrupt=True)
            self._count(fingerprint, misses=1,
                        load_s=time.perf_counter() - t0)
            return None
        try:
            os.utime(path)
        except OSError:
            pass
        self._count(fingerprint, hits=1, load_s=time.perf_counter() - t0)
        return fn

    def read_meta(self, fingerprint: str) -> Optional[dict]:
        """The entry's metadata dict without deserializing the executable."""
        path = self._path(fingerprint)
        try:
            with open(path, "rb") as f:
                return pickle.load(f)
        except Exception:  # noqa: BLE001
            return None

    def scan(self) -> list:
        """[(fingerprint, meta)] for every readable entry (pre-warm input).

        Unreadable metadata marks the entry corrupt and evicts it.
        """
        out = []
        try:
            names = sorted(os.listdir(self.plans_dir))
        except OSError:
            return out
        for name in names:
            if not name.endswith(ENTRY_SUFFIX):
                continue
            fp = name[:-len(ENTRY_SUFFIX)]
            meta = self.read_meta(fp)
            if meta is None:
                self._evict(os.path.join(self.plans_dir, name), corrupt=True)
            else:
                out.append((fp, meta))
        return out

    # ------------------------------------------------------------- eviction --

    def _evict(self, path: str, *, corrupt: bool = False) -> None:
        try:
            os.unlink(path)
            self._count(evictions=1, corrupt_evictions=int(corrupt))
        except OSError:
            pass

    def _evict_over_cap(self) -> None:
        """Delete least-recently-used entries until under `max_bytes`."""
        try:
            entries = []
            for name in os.listdir(self.plans_dir):
                if not name.endswith(ENTRY_SUFFIX):
                    continue
                path = os.path.join(self.plans_dir, name)
                try:
                    st = os.stat(path)
                    entries.append((st.st_mtime, st.st_size, path))
                except OSError:
                    pass
        except OSError:
            return
        total = sum(size for _, size, _ in entries)
        if total <= self.max_bytes:
            return
        for _mtime, size, path in sorted(entries):
            self._evict(path)
            total -= size
            if total <= self.max_bytes:
                break

    # ---------------------------------------------------------------- stats --

    def stats(self) -> dict:
        with self._lock:
            counts = dict(self._counts)
            per_entry = {fp: dict(e) for fp, e in self._entries.items()}
            load_s, store_s = self._load_s, self._store_s
        requests = counts["hits"] + counts["misses"]
        return dict(
            dir=self.root, entries=len(self), bytes=self.total_bytes(),
            max_bytes=self.max_bytes,
            hit_rate=counts["hits"] / requests if requests else 0.0,
            load_s=load_s, store_s=store_s, per_entry=per_entry, **counts)


# ------------------------------------------------- per-directory singletons --

_caches_lock = threading.Lock()
_caches: dict = {}


def artifact_cache_for(runtime=None) -> Optional[ArtifactCache]:
    """The shared `ArtifactCache` for a config's cache dir (None = disabled).

    One instance per directory per process, so counters aggregate across
    every session using that directory (what `BFSServer.stats()` reports).
    """
    from repro.runtime.config import get_runtime_config
    runtime = runtime or get_runtime_config()
    if not runtime.cache_enabled:
        return None
    key = (os.path.abspath(runtime.cache_dir), int(runtime.cache_max_bytes))
    with _caches_lock:
        cache = _caches.get(key)
        if cache is None:
            cache = _caches[key] = ArtifactCache(runtime.cache_dir,
                                                 runtime.cache_max_bytes)
        return cache


def reset_artifact_caches() -> None:
    """Test hook: drop per-directory cache instances (files stay on disk)."""
    with _caches_lock:
        _caches.clear()
