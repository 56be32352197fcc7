"""The paper's workload driver: graph -> engine session -> BFS -> TEPS.

Graph500-style methodology: N search roots sampled from non-isolated
vertices, harmonic-mean TEPS (undirected edges / time), parent-tree
validation per run. All traversal goes through `repro.engine` — one
`GraphSession` per graph, one compiled executable per (config, backend).

  PYTHONPATH=src python -m repro.launch.bfs_run --scale 14 --nparts 4 \
      --strategy specialized     # needs XLA_FLAGS device_count >= nparts

`--cache-dir DIR` (or REPRO_CACHE_DIR) enables the persistent artifact
cache: the first run compiles and serializes its executables; later runs
of the same graph + config restart warm (zero retraces — the reported
`warm` block shows traces vs disk loads).
"""
from __future__ import annotations

import argparse
import warnings

import numpy as np


def sample_roots(g, roots: int, seed: int = 0) -> np.ndarray:
    """Sample distinct non-isolated roots, clamped to what the graph has.

    Small/sparse graphs can hold fewer non-isolated vertices than requested
    roots; `rng.choice(..., replace=False)` would crash. Clamp and warn
    instead (falling back to all vertices when every vertex is isolated).
    """
    rng = np.random.default_rng(seed)
    candidates = np.flatnonzero(g.degrees > 0)
    if candidates.size == 0:
        warnings.warn("graph has no edges; sampling roots from all vertices")
        candidates = np.arange(g.num_vertices)
    k = min(roots, candidates.size)
    if k < roots:
        warnings.warn(
            f"requested {roots} roots but only {candidates.size} candidate "
            f"vertices exist; clamping to {k}")
    return rng.choice(candidates, size=k, replace=False)


def run(scale: int, nparts: int, strategy: str = "specialized", roots: int = 8,
        heuristic: str = "paper", edgefactor: int = 16, seed: int = 0,
        validate: bool = True, graph=None, cache_dir=None):
    from repro.core import graph as G
    from repro.core.bfs import BFSConfig
    from repro.engine import Engine
    from repro.runtime import configure

    if cache_dir is not None:
        configure(cache_dir=cache_dir)
    g = graph if graph is not None else G.rmat(scale, edgefactor=edgefactor,
                                               seed=seed)
    if roots < 1:
        raise ValueError(f"need at least one search root, got roots={roots}")
    root_list = sample_roots(g, roots, seed)
    engine = Engine(g, default_strategy=strategy)
    # batched=False: Graph500 measurement mode — every root individually
    # timed against the one cached executable (first query pays the compile,
    # outside the timed region).
    res = engine.bfs(root_list, BFSConfig(heuristic=heuristic),
                     n_parts=nparts, batched=False, validate=validate)
    teps = res.teps_per_root
    rt = engine.session.runtime_stats()
    return {"scale": scale, "nparts": nparts, "strategy": strategy,
            "heuristic": heuristic, "teps_hmean": res.teps_hmean,
            "teps_min": float(teps.min()), "teps_max": float(teps.max()),
            "mean_s": float(res.per_root_seconds.mean()),
            "V": g.num_vertices, "E_undirected": g.num_undirected_edges,
            "warm": {"traces": rt["traces"], "loads": rt["loads"],
                     "cache_enabled": rt["cache_enabled"]}}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=int, default=14)
    ap.add_argument("--edgefactor", type=int, default=16)
    ap.add_argument("--nparts", type=int, default=1)
    ap.add_argument("--strategy", default="specialized",
                    choices=("random", "hub0", "specialized"))
    ap.add_argument("--heuristic", default="paper",
                    choices=("paper", "beamer", "topdown", "bottomup"))
    ap.add_argument("--roots", type=int, default=8)
    ap.add_argument("--no-validate", action="store_true")
    ap.add_argument("--cache-dir", default=None,
                    help="persistent compiled-executable cache directory "
                         "(default: REPRO_CACHE_DIR if set, else disabled)")
    args = ap.parse_args(argv)
    from repro.runtime import enable_compile_cache
    enable_compile_cache()
    res = run(args.scale, args.nparts, args.strategy, args.roots,
              args.heuristic, args.edgefactor, validate=not args.no_validate,
              cache_dir=args.cache_dir)
    warm = res["warm"]
    cache_note = (f" cache[traces={warm['traces']} loads={warm['loads']}]"
                  if warm["cache_enabled"] else "")
    print(f"[bfs] scale={res['scale']} V={res['V']} E={res['E_undirected']} "
          f"P={res['nparts']} {res['strategy']}/{res['heuristic']}: "
          f"{res['teps_hmean'] / 1e6:.2f} MTEPS (hmean over {args.roots} "
          f"roots){cache_note}")
    return res


if __name__ == "__main__":
    main()
