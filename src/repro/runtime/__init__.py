"""Process runtime: one authoritative config + persistent compiled-plan store.

Serving BFS to a fleet means rolling restarts, and every restarted process
used to retrace its whole executable set from scratch — cold-start cost was
invisible and unbounded. This package is the layer under everything that
compiles:

* `config`    — `RuntimeConfig`, the single validated object folding the
  scattered env/device flags (kernel backend, interpret mode, cache dir,
  eviction cap, plan sharing, device count) with explicit-arg > env >
  default precedence, plus the `launch_env()` XLA/tcmalloc launch hygiene
  and `enable_compile_cache()`, which places JAX's persistent compilation
  cache for the entry points.
* `fingerprint` — canonical content fingerprints: graph CSR hash, the
  jax/backend environment, and the full plan fingerprint an executable is
  keyed by on disk.
* `artifact_cache` — the disk-backed store for compiled executables
  (`jax.experimental.serialize_executable` export/import), atomic
  write-rename, size-capped LRU eviction, corruption-tolerant loads, and
  hit/miss/load-time counters.
* `plan_registry` — the in-process cross-session plan cache, keyed by
  (graph content hash, plan key) instead of session identity, so two
  sessions over the same graph share compiled plans.
* `faults` — the deterministic fault-injection switchboard (`fault_point`
  hook sites through compile/cache/dispatch/worker paths, seeded
  schedule grammar via `REPRO_FAULTS`), zero overhead when disabled.

`GraphSession` wires all four together: executables consult the registry,
then the disk store, and only then trace; a session pre-warms its plan set
from disk on attach (background thread, observable progress).
"""
from repro.runtime.artifact_cache import ArtifactCache, artifact_cache_for
from repro.runtime.config import (RuntimeConfig, configure,
                                  enable_compile_cache, get_runtime_config,
                                  launch_env, reset_runtime_config,
                                  runtime_scope)
from repro.runtime.faults import (DevicePressure, FaultInjected,
                                  FaultInjector, FaultSpec, fault_point,
                                  fault_scope, install_faults,
                                  parse_fault_schedule, uninstall_faults)
from repro.runtime.fingerprint import (environment_fingerprint,
                                       graph_fingerprint, plan_fingerprint)
from repro.runtime.plan_registry import (registry_reset, registry_size,
                                         reset_process_caches)

__all__ = [
    "RuntimeConfig", "configure", "enable_compile_cache",
    "get_runtime_config", "launch_env",
    "reset_runtime_config", "runtime_scope",
    "ArtifactCache", "artifact_cache_for",
    "DevicePressure", "FaultInjected", "FaultInjector", "FaultSpec",
    "fault_point", "fault_scope", "install_faults", "parse_fault_schedule",
    "uninstall_faults",
    "environment_fingerprint", "graph_fingerprint", "plan_fingerprint",
    "registry_reset", "registry_size", "reset_process_caches",
]
