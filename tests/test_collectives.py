"""Bitmap collectives + int8 gradient compression."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.parallel.collectives import dequantize_int8, quantize_int8
from conftest import run_in_devices


@given(st.integers(0, 2**31 - 1))
@settings(max_examples=20, deadline=None)
def test_quantize_roundtrip_bounded(seed):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal(64).astype(np.float32) *
                    rng.random() * 10)
    q, scale = quantize_int8(x, jax.random.PRNGKey(seed % 2**31))
    err = np.abs(np.asarray(dequantize_int8(q, scale)) - np.asarray(x))
    assert (err <= float(scale) + 1e-6).all()


def test_quantize_unbiased():
    x = jnp.full((2000,), 0.3141592)
    qs = []
    for i in range(64):
        q, s = quantize_int8(x, jax.random.PRNGKey(i))
        qs.append(np.asarray(dequantize_int8(q, s)))
    mean = np.stack(qs).mean()
    assert abs(mean - 0.3141592) < 2e-4


CODE = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, PartitionSpec as P
from repro.parallel.collectives import (compressed_psum, or_allreduce_flags,
                                        or_allreduce_bitmap, shard_map_compat)
from repro.core import frontier as fr

mesh = Mesh(np.array(jax.devices()[:4]), ("d",))
def f(x):
    g = {"w": x * (jax.lax.axis_index("d") + 1.0)}
    return compressed_psum(g, "d", jax.random.PRNGKey(0))["w"]
xs = jnp.ones((4, 256), jnp.float32)
out = jax.jit(shard_map_compat(f, mesh=mesh, in_specs=P("d"),
              out_specs=P("d")))(xs)
want = (1 + 2 + 3 + 4) / 4.0
np.testing.assert_allclose(np.asarray(out), want, atol=0.05)

def g(flags):
    flags = flags.reshape(-1)
    return or_allreduce_flags(flags, "d")[None]
flags = (np.arange(4)[:, None] == np.arange(4)[None]).astype(np.uint8)
merged = jax.jit(shard_map_compat(g, mesh=mesh, in_specs=P("d"),
                 out_specs=P("d")))(jnp.asarray(flags))
np.testing.assert_array_equal(np.asarray(merged), np.ones((4, 4), np.uint8))
print("COLLECTIVES_OK")
"""


@pytest.mark.slow
def test_compressed_psum_4dev():
    out = run_in_devices(CODE, 4, timeout=300)
    assert "COLLECTIVES_OK" in out
