"""What a window drives: the program's entry point, or the control.

Each adapter answers `search(root) -> (parent, level)` ([V] int32, -1 where
unreached). The program adapter calls only the program's public entry
point, `Engine.bfs(..., batched=False)`; everything below it is the system
under test. The control puts the reference, with its adjacency scans capped
(`reference.search(capped=...)`), in the program's place.
"""
from __future__ import annotations

import numpy as np

from . import reference

CONTROL_CAP = 32   # neighbours a control vertex scans: one 32-wide slab


class EngineSearch:
    """`Engine(graph).bfs([root], cfg, batched=False)`, the Graph500 path."""

    def __init__(self, graph, bfs: dict):
        from repro.core.bfs import BFSConfig
        from repro.engine import Engine
        self.cfg = BFSConfig(**bfs)
        self.engine = Engine(graph)

    def warm(self, root: int) -> None:
        """One search compiles and runs every executable the window uses."""
        self.search(root)

    def search(self, root: int):
        res = self.engine.bfs(np.asarray([root]), self.cfg, batched=False)
        return res.parent[0], res.level[0]

    def close(self) -> None:
        self.engine = None


class ControlSearch:
    """The reference with capped scans, in `EngineSearch`'s place."""

    def __init__(self, adj: reference.Adjacency, bfs: dict):
        self.adj = adj
        self.max_levels = int(bfs.get("max_levels", 0))

    def warm(self, root: int) -> None:
        pass

    def search(self, root: int):
        return reference.search(self.adj, root, self.max_levels,
                                capped=CONTROL_CAP)

    def close(self) -> None:
        pass
