"""Uniform random edge list (GAP "Urand"), generated on the device.

`edgefactor * 2**scale` edges whose endpoints are drawn independently and
uniformly from the `2**scale` vertices, as the program's
`core/graph.py::uniform_random` draws them; self loops and duplicates are
left for the ingest to drop.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def num_vertices(config: dict) -> int:
    return 1 << int(config["scale"])


@functools.partial(jax.jit, static_argnums=(1, 2))
def _edges(key, scale, edgefactor):
    n = 1 << scale
    m = n * edgefactor
    ks, kd = jax.random.split(key)
    return (jax.random.randint(ks, (m,), 0, n, dtype=jnp.int32),
            jax.random.randint(kd, (m,), 0, n, dtype=jnp.int32))


def edges(config: dict, key):
    """(src, dst) int32 device arrays of the configuration's edge list."""
    return _edges(key, int(config["scale"]), int(config["edgefactor"]))
