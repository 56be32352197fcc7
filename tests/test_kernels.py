"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps + hypothesis."""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref


@pytest.mark.parametrize("r,w,v", [(128, 32, 1024), (300, 17, 513),
                                   (64, 96, 4096), (1, 1, 32), (257, 33, 100)])
def test_bottomup_sweep(r, w, v):
    rng = np.random.default_rng(r * 1000 + w)
    deg = rng.integers(0, w + 1, r).astype(np.int32)
    nbrs = rng.integers(0, v, (r, w)).astype(np.int32)
    frontier = (rng.random(v) < 0.1).astype(np.uint8)
    f1, p1 = ops.bottomup(jnp.asarray(deg), jnp.asarray(nbrs),
                          jnp.asarray(frontier))
    f2, p2 = ref.bottomup_ref(jnp.asarray(deg), jnp.asarray(nbrs),
                              jnp.asarray(frontier))
    np.testing.assert_array_equal(np.asarray(f1), np.asarray(f2))
    np.testing.assert_array_equal(np.asarray(p1), np.asarray(p2))


@given(st.integers(0, 2**31 - 1))
@settings(max_examples=15, deadline=None)
def test_bottomup_property(seed):
    rng = np.random.default_rng(seed)
    r = int(rng.integers(1, 80))
    w = int(rng.integers(1, 40))
    v = int(rng.integers(8, 600))
    deg = rng.integers(0, w + 1, r).astype(np.int32)
    nbrs = rng.integers(0, v, (r, w)).astype(np.int32)
    frontier = (rng.random(v) < rng.random() * 0.5).astype(np.uint8)
    f1, p1 = ops.bottomup(jnp.asarray(deg), jnp.asarray(nbrs),
                          jnp.asarray(frontier), slab=8, rblk=32)
    f2, p2 = ref.bottomup_ref(jnp.asarray(deg), jnp.asarray(nbrs),
                              jnp.asarray(frontier))
    np.testing.assert_array_equal(np.asarray(f1), np.asarray(f2))
    np.testing.assert_array_equal(np.asarray(p1), np.asarray(p2))


@pytest.mark.parametrize("c,w,v", [(128, 16, 512), (77, 9, 300), (1, 1, 32)])
def test_topdown_sweep(c, w, v):
    rng = np.random.default_rng(c)
    deg = rng.integers(0, w + 1, c).astype(np.int32)
    nbrs = rng.integers(0, v, (c, w)).astype(np.int32)
    visited = (rng.random(v) < 0.5).astype(np.uint8)
    f1, d1 = ops.topdown(jnp.asarray(deg), jnp.asarray(nbrs),
                         jnp.asarray(visited))
    f2, d2 = ref.topdown_ref(jnp.asarray(deg), jnp.asarray(nbrs),
                             jnp.asarray(visited))
    np.testing.assert_array_equal(np.asarray(f1), np.asarray(f2))
    np.testing.assert_array_equal(np.asarray(d1), np.asarray(d2))


@pytest.mark.parametrize("v", [32, 100, 8192, 1])
def test_frontier_fused_sweep(v):
    rng = np.random.default_rng(v)
    flags = (rng.random(v) < 0.3).astype(np.uint8)
    deg = rng.integers(0, 50, v).astype(np.int32)
    pk1, nf1, mf1 = ops.frontier_fused(jnp.asarray(flags), jnp.asarray(deg))
    pk2, nf2, mf2 = ref.frontier_fused_ref(jnp.asarray(flags), jnp.asarray(deg))
    np.testing.assert_array_equal(np.asarray(pk1), np.asarray(pk2))
    assert int(nf1) == int(nf2) and int(mf1) == int(mf2)


@pytest.mark.parametrize("r,w,v", [(5, 7, 100),      # R not an rblk multiple
                                   (130, 33, 257),   # W not a slab multiple
                                   (3, 96, 50)])     # tiny R, wide W
def test_bottomup_ragged_padding(r, w, v):
    rng = np.random.default_rng(r * 7 + w)
    deg = rng.integers(0, w + 1, r).astype(np.int32)
    nbrs = rng.integers(0, v, (r, w)).astype(np.int32)
    frontier = (rng.random(v) < 0.2).astype(np.uint8)
    f1, p1 = ops.bottomup(jnp.asarray(deg), jnp.asarray(nbrs),
                          jnp.asarray(frontier))
    f2, p2 = ref.bottomup_ref(jnp.asarray(deg), jnp.asarray(nbrs),
                              jnp.asarray(frontier))
    np.testing.assert_array_equal(np.asarray(f1), np.asarray(f2))
    np.testing.assert_array_equal(np.asarray(p1), np.asarray(p2))


def test_bottomup_empty_frontier_finds_nothing():
    rng = np.random.default_rng(0)
    deg = rng.integers(1, 9, 40).astype(np.int32)
    nbrs = rng.integers(0, 64, (40, 8)).astype(np.int32)
    f, p = ops.bottomup(jnp.asarray(deg), jnp.asarray(nbrs),
                        jnp.zeros(64, jnp.uint8))
    assert int(np.asarray(f).sum()) == 0
    assert (np.asarray(p) == 2**31 - 1).all()


def test_bottomup_empty_tile_short_circuits():
    f, p = ops.bottomup(jnp.zeros(0, jnp.int32), jnp.zeros((0, 4), jnp.int32),
                        jnp.ones(16, jnp.uint8))
    assert f.shape == (0,) and p.shape == (0,)


def test_topdown_ragged_padding():
    rng = np.random.default_rng(3)
    c, w, v = 9, 5, 333                       # C not a cblk multiple
    deg = rng.integers(0, w + 1, c).astype(np.int32)
    nbrs = rng.integers(0, v, (c, w)).astype(np.int32)
    visited = (rng.random(v) < 0.5).astype(np.uint8)
    f1, d1 = ops.topdown(jnp.asarray(deg), jnp.asarray(nbrs),
                         jnp.asarray(visited))
    f2, d2 = ref.topdown_ref(jnp.asarray(deg), jnp.asarray(nbrs),
                             jnp.asarray(visited))
    np.testing.assert_array_equal(np.asarray(f1), np.asarray(f2))
    np.testing.assert_array_equal(np.asarray(d1), np.asarray(d2))


@pytest.mark.parametrize("v", [31, 33, 8191, 8193])  # around word/block edges
def test_frontier_fused_nonmultiple_v(v):
    rng = np.random.default_rng(v)
    flags = (rng.random(v) < 0.4).astype(np.uint8)
    deg = rng.integers(0, 9, v).astype(np.int32)
    pk1, nf1, mf1 = ops.frontier_fused(jnp.asarray(flags), jnp.asarray(deg))
    pk2, nf2, mf2 = ref.frontier_fused_ref(jnp.asarray(flags), jnp.asarray(deg))
    np.testing.assert_array_equal(np.asarray(pk1), np.asarray(pk2))
    assert int(nf1) == int(nf2) and int(mf1) == int(mf2)


def test_frontier_fused_empty_frontier():
    pk, nf, mf = ops.frontier_fused(jnp.zeros(100, jnp.uint8),
                                    jnp.ones(100, jnp.int32))
    assert int(nf) == 0 and int(mf) == 0
    assert (np.asarray(pk) == 0).all() and pk.shape == (4,)


def test_bottomup_first_hit_parent_is_slab_ordered():
    # degree-sorted adjacency => the chosen parent must be the FIRST slot hit
    deg = jnp.asarray(np.array([3], np.int32))
    nbrs = jnp.asarray(np.array([[5, 6, 7]], np.int32))
    frontier = np.zeros(10, np.uint8); frontier[6] = 1; frontier[7] = 1
    f, p = ops.bottomup(deg, nbrs, jnp.asarray(frontier), slab=2, rblk=1)
    assert int(f[0]) == 1 and int(p[0]) == 6


@pytest.mark.parametrize("b,s,k,g,h,cap", [(2, 1024, 4, 2, 64, 0.0),
                                           (3, 700, 2, 5, 32, 50.0),
                                           (1, 64, 1, 1, 16, 0.0)])
def test_decode_attention_sweep(b, s, k, g, h, cap):
    rng = np.random.default_rng(b * 100 + s)
    q = jnp.asarray(rng.standard_normal((b, k, g, h)), jnp.float32)
    kc = jnp.asarray(rng.standard_normal((b, s, k, h)), jnp.float32)
    vc = jnp.asarray(rng.standard_normal((b, s, k, h)), jnp.float32)
    clen = jnp.asarray(rng.integers(1, s + 1, b), jnp.int32)
    o1 = ops.decode_attention(q, kc, vc, clen, blk=256, logit_cap=cap)
    o2 = ref.decode_attention_ref(q, kc, vc, clen, logit_cap=cap)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               rtol=2e-5, atol=2e-5)


@given(st.integers(0, 2**31 - 1))
@settings(max_examples=8, deadline=None)
def test_decode_attention_property(seed):
    rng = np.random.default_rng(seed)
    b = int(rng.integers(1, 4))
    s = int(rng.integers(4, 300))
    k = int(rng.integers(1, 4))
    g = int(rng.integers(1, 4))
    h = int(rng.choice([8, 16, 32]))
    q = jnp.asarray(rng.standard_normal((b, k, g, h)), jnp.float32)
    kc = jnp.asarray(rng.standard_normal((b, s, k, h)), jnp.float32)
    vc = jnp.asarray(rng.standard_normal((b, s, k, h)), jnp.float32)
    clen = jnp.asarray(rng.integers(1, s + 1, b), jnp.int32)
    o1 = ops.decode_attention(q, kc, vc, clen, blk=64)
    o2 = ref.decode_attention_ref(q, kc, vc, clen)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               rtol=3e-5, atol=3e-5)
