"""The benchmark's arithmetic: traversed edges and the window's rate.

Kept here, with the benchmark, so that no change to the program can change
how its work is counted.
"""
from __future__ import annotations

from typing import Sequence

import numpy as np


def edges_traversed(degrees: np.ndarray, level: np.ndarray) -> np.ndarray:
    """Undirected edges each search traversed (Graph500 accounting).

    Half the degree sum over the reached set (`level >= 0`); a copy of the
    program's `engine/result.py::edges_traversed_from_levels`. Every edge
    incident to a reached vertex stays inside the root's component, so the
    sum counts each traversed undirected edge twice. `level` is [V] or
    [B, V].
    """
    deg = np.asarray(degrees, dtype=np.int64)
    reached = np.asarray(level) >= 0
    return (reached @ deg) // 2


def rate_mteps(edges: Sequence[int], window_start: float,
               last_end: float) -> float:
    """Millions of traversed undirected edges per second over the window:
    all edges of the searches completed, over the wall time from the
    window's start to the end of the last search that started in it."""
    return float(np.sum(edges, dtype=np.int64)) / (last_end - window_start) / 1e6
