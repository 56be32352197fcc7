"""Bitmap frontier ops: unit + property tests."""
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core import frontier as fr


@given(st.integers(1, 300), st.integers(0, 2**31 - 1))
@settings(max_examples=25, deadline=None)
def test_pack_unpack_roundtrip(v, seed):
    rng = np.random.default_rng(seed)
    flags = (rng.random(v) < 0.4).astype(np.uint8)
    packed = fr.pack(jnp.asarray(flags))
    back = fr.unpack(packed, v)
    np.testing.assert_array_equal(np.asarray(back), flags)


@given(st.integers(1, 300), st.integers(0, 2**31 - 1))
@settings(max_examples=25, deadline=None)
def test_popcount_matches_numpy(v, seed):
    rng = np.random.default_rng(seed)
    flags = (rng.random(v) < 0.3).astype(np.uint8)
    packed = fr.pack(jnp.asarray(flags))
    assert int(fr.popcount(packed)) == int(flags.sum())


@given(st.integers(1, 200), st.integers(0, 2**31 - 1))
@settings(max_examples=25, deadline=None)
def test_compact(v, seed):
    rng = np.random.default_rng(seed)
    flags = (rng.random(v) < 0.3).astype(np.uint8)
    q, n = fr.compact(jnp.asarray(flags))
    q = np.asarray(q)
    want = np.flatnonzero(flags)
    assert int(n) == len(want)
    np.testing.assert_array_equal(q[:len(want)], want)
    assert (q[len(want):] == v).all()


def test_edge_count():
    flags = jnp.asarray(np.array([1, 0, 1, 0], np.uint8))
    deg = jnp.asarray(np.array([3, 5, 7, 9], np.int32))
    assert int(fr.edge_count(flags, deg)) == 10
