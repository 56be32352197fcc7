"""The one traffic generator: search keys from a mix file.

A mix (`bench/traffic/<name>.json`) is data only:

    bfs          `BFSConfig` fields every search carries, e.g. {"max_levels": 2}
    roots        {"count": n, "seed": s}: n distinct vertices of nonzero
                 degree drawn with the fixed seed s, searched in that order
                 (and again from the first when the window outlasts them)
    check        {"sample": k}: answers compared with the reference, drawn
                 from the run's seed among those the window returned
    trace_seconds  length of the profiled part of a `--trace 1` window

One caller searches the keys one at a time, back to back (closed loop), as
Graph500 and GAP time their search keys. The keys are the mix's, not the
run's: every seed asks for the same work, the way GAP's source picker draws
its sources from one fixed seed. The run's seed draws the sample that is
checked and the root that warms the program up.
"""
from __future__ import annotations

import math

import numpy as np


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """Independent numpy stream `stream` of `seed` (any non-negative int)."""
    return np.random.default_rng([int(seed), int(stream)])


def search_keys(nonzero: np.ndarray, roots: dict) -> np.ndarray:
    """The mix's search keys: `roots["count"]` distinct vertices of
    `nonzero`, drawn with the mix's own seed (clamped to the vertices)."""
    rng = np.random.default_rng(int(roots["seed"]))
    return rng.choice(nonzero, size=min(int(roots["count"]), nonzero.size),
                      replace=False)


def validate_mix(mix: dict) -> None:
    """Refuse a mix file the generator cannot run."""
    roots = mix.get("roots", {})
    if int(roots.get("count", 0)) < 1 or "seed" not in roots:
        raise ValueError("a mix needs roots {count >= 1, seed}")
    if int(mix.get("check", {}).get("sample", 0)) < 1:
        raise ValueError("a mix needs check {sample >= 1}")
    if not math.isfinite(float(mix.get("trace_seconds", 1))):
        raise ValueError("trace_seconds must be finite")
