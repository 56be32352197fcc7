"""Graph500 Kronecker (R-MAT) edge list, generated on the device.

The arithmetic of the Graph500 reference generator, as the program's
`core/graph.py::rmat` has it: for each of `scale` bits, one uniform draw
picks the row half (above A+B: the lower half) and a second picks the column
half against C/(C+D) or A/(A+B); then a random permutation of the vertex ids
removes any locality. `edgefactor * 2**scale` edges, self loops and
duplicates included (the ingest drops them).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def num_vertices(config: dict) -> int:
    return 1 << int(config["scale"])


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5))
def _edges(key, scale, edgefactor, a, b, c):
    n = 1 << scale
    m = n * edgefactor
    ab = a + b
    c_norm = c / (1.0 - ab)
    a_norm = a / ab
    k_bits, k_perm = jax.random.split(key)

    def bit(i, carry):
        src, dst = carry
        ku, kv = jax.random.split(jax.random.fold_in(k_bits, i))
        u = jax.random.uniform(ku, (m,))
        v = jax.random.uniform(kv, (m,))
        lower = u > ab
        src = (src << 1) | lower.astype(jnp.int32)
        dst = (dst << 1) | (v > jnp.where(lower, c_norm, a_norm)).astype(
            jnp.int32)
        return src, dst

    zeros = jnp.zeros(m, jnp.int32)
    src, dst = jax.lax.fori_loop(0, scale, bit, (zeros, zeros))
    perm = jax.random.permutation(k_perm, n).astype(jnp.int32)
    return perm[src], perm[dst]


def edges(config: dict, key):
    """(src, dst) int32 device arrays of the configuration's edge list."""
    return _edges(key, int(config["scale"]), int(config["edgefactor"]),
                  float(config["a"]), float(config["b"]), float(config["c"]))
