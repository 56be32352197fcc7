"""In-process cross-session compiled-plan registry.

`GraphSession` used to key compiled executables on session identity: two
sessions over the same graph (or over a rebuilt, byte-identical graph) each
traced their own copy of every plan. The registry fixes that by keying on
*content*: `(graph_fingerprint, plan key)`. Sessions consult it before
building; whoever builds first publishes the (possibly still-unresolved)
executable wrapper, and later sessions — or later `Engine`s over a rebuilt
identical graph — reuse it with zero traces.

Entries hold `_PlanExecutable` wrappers (see `repro.engine.session`), which
resolve lazily on first call and carry their own internal lock, so a plan
compiles at most once *process-wide*, not once per session.

The registry lives for the process (mirroring the old per-session caches,
which were equally unbounded but per session — strictly worse). Tests that
assert exact trace counts reset it between tests via `registry_reset()`
(an autouse fixture in `tests/conftest.py`), so counts stay deterministic
under any test ordering.
"""
from __future__ import annotations

import threading
from typing import Any, Optional

_lock = threading.Lock()
_plans: dict = {}


def registry_get(key) -> Optional[Any]:
    """The shared executable for `(graph_hash, plan_key)`, if published."""
    with _lock:
        return _plans.get(key)


def registry_put(key, fn) -> Any:
    """Publish an executable; first writer wins (returns the winner)."""
    with _lock:
        return _plans.setdefault(key, fn)


def registry_size() -> int:
    with _lock:
        return len(_plans)


def registry_reset() -> None:
    """Drop every shared plan (tests / explicit invalidation)."""
    with _lock:
        _plans.clear()


def reset_process_caches() -> None:
    """Full runtime reset: registry, fingerprint memos, cache singletons.

    The disk cache itself is untouched — this only drops in-process state,
    returning the process to a just-started view of the runtime layer.
    """
    from repro.runtime.artifact_cache import reset_artifact_caches
    from repro.runtime.fingerprint import reset_fingerprint_memos
    registry_reset()
    reset_fingerprint_memos()
    reset_artifact_caches()
