"""`RuntimeConfig`: the one authoritative runtime-configuration object.

Before this module the repo's runtime knobs were scattered: kernel backend
selection lived in `BFSConfig.backend_kernels` + a TPU autodetect, Pallas
interpret mode in `repro.kernels.ops._auto_interpret`, device counts in
ad-hoc `XLA_FLAGS` strings, and there was nowhere to hang a cache directory
or an eviction cap. `RuntimeConfig` folds them into one validated object
(alpa's `GlobalConfig` pattern) with a strict precedence rule:

    explicit argument  >  environment variable  >  built-in default

Environment variables (all optional):

=====================  =====================================================
REPRO_CACHE_DIR        persistent artifact-cache directory ('' = disabled)
REPRO_CACHE_MAX_BYTES  cache eviction cap; int bytes or '512MB'/'2GB'
REPRO_PREWARM          '1'/'0': background pre-warm on `GraphSession` attach
REPRO_PREWARM_LIMIT    max executables one pre-warm pass deserializes
REPRO_SHARE_PLANS      '1'/'0': in-process cross-session plan sharing
REPRO_KERNELS          'on' | 'off' (default off): Pallas kernel path when
                       `BFSConfig.backend_kernels` is None (off = the XLA
                       step: Mosaic refuses the kernels for TPU)
REPRO_INTERPRET        'auto' | 'on' | 'off': Pallas interpret mode when a
                       kernel call leaves it unset (auto = interpret off-TPU,
                       Mosaic lowering on TPU)
REPRO_DEVICE_COUNT     fake host device count `launch_env()` bakes into
                       XLA_FLAGS (emulated-mesh runs; ignored when unset)
REPRO_FAULTS           fault-injection schedule (see `repro.runtime.faults`;
                       '' = disabled). Chaos testing only.
REPRO_FAULTS_SEED      int seed for probabilistic fault selectors
REPRO_SANITIZE         '1'/'0': concurrency sanitizer — instrumented lock/
                       timer wrappers recording the lock-order graph
                       (see `repro.analysis.concurrency`). Testing only.
REPRO_VMEM_BUDGET      per-core VMEM budget the kernel-contract verifier
                       checks against; int bytes or '16MB' (default 16 MiB)
REPRO_STRICT_CONTRACTS '1'/'0': `GraphSession.executable` refuses (instead
                       of warns) when a plan's kernels exceed the budget
=====================  =====================================================

`launch_env()` documents the XLA/tcmalloc launch hygiene from the
HomebrewNLP / olmax run.sh recipes as code: it returns the environment a
launcher shell should export *before* the python process starts (tcmalloc
must be LD_PRELOADed and XLA_FLAGS read at jax import, so a running process
cannot apply them to itself — hence a helper that emits them, not sets them).

The module keeps one process-wide singleton (`get_runtime_config`), replaced
by `configure(...)` and scoped by the `runtime_scope(...)` context manager
(tests); sessions may also carry a private `RuntimeConfig` instance.
"""
from __future__ import annotations

import contextlib
import dataclasses
import os
import threading
from typing import Optional

from repro.analysis.vmem import DEFAULT_VMEM_BUDGET

_TRISTATE = ("auto", "on", "off")
_ONOFF = ("on", "off")

# SNIPPETS §2-3 launch hygiene: the conventional tcmalloc path on the
# TPU-VM/linux images this repo targets, and the matching allocator knobs.
DEFAULT_TCMALLOC = "/usr/lib/x86_64-linux-gnu/libtcmalloc.so.4"
DEFAULT_CACHE_MAX_BYTES = 1 << 30            # 1 GiB
DEFAULT_PREWARM_LIMIT = 64

_SIZE_SUFFIXES = {"KB": 1 << 10, "MB": 1 << 20, "GB": 1 << 30,
                  "K": 1 << 10, "M": 1 << 20, "G": 1 << 30, "B": 1}


def _parse_size(text: str, *, name: str) -> int:
    """'1048576' | '512MB' | '2gb' -> bytes (int)."""
    s = str(text).strip().upper().replace(" ", "")
    for suffix in sorted(_SIZE_SUFFIXES, key=len, reverse=True):
        if s.endswith(suffix):
            body = s[:-len(suffix)]
            try:
                return int(float(body) * _SIZE_SUFFIXES[suffix])
            except ValueError:
                break
    try:
        return int(s)
    except ValueError:
        raise ValueError(
            f"{name}: cannot parse size {text!r}; want an integer byte "
            f"count or a number with a KB/MB/GB suffix") from None


def _parse_bool(text: str, *, name: str) -> bool:
    s = str(text).strip().lower()
    if s in ("1", "true", "yes", "on"):
        return True
    if s in ("0", "false", "no", "off", ""):
        return False
    raise ValueError(f"{name}: cannot parse boolean {text!r}")


def _parse_tristate(text: str, *, name: str, allowed=_TRISTATE) -> str:
    s = str(text).strip().lower()
    if s in ("1", "true", "yes"):
        s = "on"
    elif s in ("0", "false", "no"):
        s = "off"
    if s in allowed:
        return s
    raise ValueError(f"{name}: want one of {allowed}, got {text!r}")


@dataclasses.dataclass(frozen=True)
class RuntimeConfig:
    """Validated, immutable runtime configuration (see module docstring).

    Build with `RuntimeConfig.resolve(...)` so env overrides apply; the bare
    constructor takes the values as final (the "explicit argument" tier).
    """

    # -- persistent artifact cache -------------------------------------------
    cache_dir: Optional[str] = None          # None = persistence disabled
    cache_max_bytes: int = DEFAULT_CACHE_MAX_BYTES
    prewarm: bool = True                     # background pre-warm on attach
    prewarm_limit: int = DEFAULT_PREWARM_LIMIT
    # -- in-process plan sharing ---------------------------------------------
    share_plans: bool = True                 # content-hash cross-session cache
    # -- kernel / device selection -------------------------------------------
    kernel_backend: str = "off"              # BFSConfig.backend_kernels=None
    interpret: str = "auto"                  # Pallas interpret when unset
    device_count: Optional[int] = None       # fake host devices (launch_env)
    # -- launch hygiene (SNIPPETS §2-3) --------------------------------------
    tcmalloc_path: str = DEFAULT_TCMALLOC
    # -- chaos testing -------------------------------------------------------
    faults: Optional[str] = None             # fault schedule ('' / None = off)
    faults_seed: int = 0
    # -- concurrency sanitizer -----------------------------------------------
    sanitize: bool = False                   # instrumented locks/timers
    # -- kernel contracts ----------------------------------------------------
    vmem_budget_bytes: int = DEFAULT_VMEM_BUDGET   # per-core VMEM budget
    strict_contracts: bool = False           # over-budget plan: raise vs warn

    def __post_init__(self):
        if self.vmem_budget_bytes <= 0:
            raise ValueError(
                f"vmem_budget_bytes must be > 0, got {self.vmem_budget_bytes}")
        if self.kernel_backend not in _ONOFF:
            raise ValueError(f"kernel_backend: want one of {_ONOFF}, "
                             f"got {self.kernel_backend!r}")
        if self.interpret not in _TRISTATE:
            raise ValueError(f"interpret: want one of {_TRISTATE}, "
                             f"got {self.interpret!r}")
        if self.cache_max_bytes <= 0:
            raise ValueError(
                f"cache_max_bytes must be > 0, got {self.cache_max_bytes}")
        if self.prewarm_limit < 0:
            raise ValueError(
                f"prewarm_limit must be >= 0, got {self.prewarm_limit}")
        if self.device_count is not None and self.device_count < 1:
            raise ValueError(
                f"device_count must be >= 1, got {self.device_count}")
        if self.cache_dir is not None and not str(self.cache_dir):
            object.__setattr__(self, "cache_dir", None)
        if self.faults is not None and not str(self.faults).strip():
            object.__setattr__(self, "faults", None)
        if self.faults is not None:
            # Validate the schedule grammar eagerly: a typo'd REPRO_FAULTS
            # must fail loudly at config time, not silently inject nothing.
            from repro.runtime.faults import parse_schedule
            parse_schedule(self.faults)

    # ------------------------------------------------------------ resolution --

    @classmethod
    def resolve(cls, env: Optional[dict] = None, **explicit) -> "RuntimeConfig":
        """Defaults <- env <- explicit kwargs (later tiers win).

        Explicit kwargs set to None mean "not given" and fall through to
        the env/default tiers; pass `cache_dir=""` to explicitly disable a
        cache the env enables (it normalizes to a disabled cache).
        """
        env = os.environ if env is None else env
        values: dict = {}
        if "REPRO_CACHE_DIR" in env:
            values["cache_dir"] = env["REPRO_CACHE_DIR"] or None
        if "REPRO_CACHE_MAX_BYTES" in env:
            values["cache_max_bytes"] = _parse_size(
                env["REPRO_CACHE_MAX_BYTES"], name="REPRO_CACHE_MAX_BYTES")
        if "REPRO_PREWARM" in env:
            values["prewarm"] = _parse_bool(env["REPRO_PREWARM"],
                                            name="REPRO_PREWARM")
        if "REPRO_PREWARM_LIMIT" in env:
            values["prewarm_limit"] = int(env["REPRO_PREWARM_LIMIT"])
        if "REPRO_SHARE_PLANS" in env:
            values["share_plans"] = _parse_bool(env["REPRO_SHARE_PLANS"],
                                                name="REPRO_SHARE_PLANS")
        if "REPRO_KERNELS" in env:
            values["kernel_backend"] = _parse_tristate(
                env["REPRO_KERNELS"], name="REPRO_KERNELS", allowed=_ONOFF)
        if "REPRO_INTERPRET" in env:
            values["interpret"] = _parse_tristate(env["REPRO_INTERPRET"],
                                                  name="REPRO_INTERPRET")
        if "REPRO_DEVICE_COUNT" in env:
            values["device_count"] = int(env["REPRO_DEVICE_COUNT"])
        if "REPRO_FAULTS" in env:
            values["faults"] = env["REPRO_FAULTS"] or None
        if "REPRO_FAULTS_SEED" in env:
            values["faults_seed"] = int(env["REPRO_FAULTS_SEED"])
        if "REPRO_SANITIZE" in env:
            values["sanitize"] = _parse_bool(env["REPRO_SANITIZE"],
                                             name="REPRO_SANITIZE")
        if "REPRO_VMEM_BUDGET" in env:
            values["vmem_budget_bytes"] = _parse_size(
                env["REPRO_VMEM_BUDGET"], name="REPRO_VMEM_BUDGET")
        if "REPRO_STRICT_CONTRACTS" in env:
            values["strict_contracts"] = _parse_bool(
                env["REPRO_STRICT_CONTRACTS"], name="REPRO_STRICT_CONTRACTS")
        for key, val in explicit.items():
            if val is None:
                continue
            values[key] = val
        return cls(**values)

    def replace(self, **changes) -> "RuntimeConfig":
        return dataclasses.replace(self, **changes)

    @property
    def cache_enabled(self) -> bool:
        return self.cache_dir is not None

    # ---------------------------------------------------------- launch env --

    def launch_env(self) -> dict:
        """Env a launcher should export before starting python (SNIPPETS §2-3).

        tcmalloc replaces glibc malloc (the CSR/ELL build path is large-
        allocation heavy) and is only included when the library actually
        exists on this machine; the allocation-report threshold silences
        tcmalloc's large-alloc warnings for graph-sized buffers;
        TF_CPP_MIN_LOG_LEVEL silences XLA's C++ chatter; XLA_FLAGS pins the
        emulated host-device count when `device_count` is set (fake-mesh
        runs — harmless and omitted otherwise).
        """
        env = {
            "TF_CPP_MIN_LOG_LEVEL": "4",
            "TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD": "60000000000",
        }
        if self.tcmalloc_path and os.path.exists(self.tcmalloc_path):
            env["LD_PRELOAD"] = self.tcmalloc_path
        if self.device_count is not None:
            env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                                f"{self.device_count}")
        if self.cache_dir is not None:
            env["REPRO_CACHE_DIR"] = self.cache_dir
        return env


# ------------------------------------------------------- process singleton --

_lock = threading.Lock()
_current: Optional[RuntimeConfig] = None


def get_runtime_config() -> RuntimeConfig:
    """The process-wide `RuntimeConfig` (env-resolved on first use)."""
    global _current
    if _current is None:
        with _lock:
            if _current is None:
                _current = RuntimeConfig.resolve()
    return _current


def configure(**explicit) -> RuntimeConfig:
    """Replace the process config: explicit args > env > defaults."""
    global _current
    with _lock:
        _current = RuntimeConfig.resolve(**explicit)
        return _current


def reset_runtime_config() -> None:
    """Drop the singleton; the next `get_runtime_config` re-reads the env."""
    global _current
    with _lock:
        _current = None


@contextlib.contextmanager
def runtime_scope(**explicit):
    """Temporarily install a config (tests); restores the previous one."""
    global _current
    with _lock:
        prev = _current
        _current = RuntimeConfig.resolve(**explicit)
        cfg = _current
    try:
        yield cfg
    finally:
        with _lock:
            _current = prev


# The checkout root (src/repro/runtime/config.py -> four levels up).
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself and this
    sets nothing, so whoever launches the process places the cache.
    Otherwise the cache goes to the fixed `<checkout>/.jax_cache`: a cache
    directory is part of what it is keyed by, so one that moved between
    runs would never hit. Entry points call this before their first
    compile (`bfs_run`, `bfs_serve`, `chip_smoke.py`, the benchmarks).
    """
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax
    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def launch_env(**explicit) -> dict:
    """`RuntimeConfig.resolve(**explicit).launch_env()` — launcher shorthand."""
    return RuntimeConfig.resolve(**explicit).launch_env()
