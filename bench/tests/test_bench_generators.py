"""The device generators and the data a run builds from its seed (CPU)."""
import numpy as np
import pytest

import tiny
from bench.harness import cell

SPEC = tiny.TinySpec(9)


@pytest.mark.parametrize("config", ["gap-kron-s22", "gap-urand-s22"])
def test_edges_deterministic_per_graph_seed_with_right_counts(config):
    cfg = SPEC.config(config)
    a = cell.make_data(SPEC, cfg)
    b = cell.make_data(SPEC, cfg)
    c = cell.make_data(SPEC, dict(cfg, graph_seed=2**31 + 18))
    src, dst, n = a
    assert n == 512
    assert src.shape == dst.shape == (cfg["edgefactor"] * n,)
    assert src.dtype == dst.dtype == np.int32
    assert 0 <= min(src.min(), dst.min()) and max(src.max(), dst.max()) < n
    np.testing.assert_array_equal(a[0], b[0])
    np.testing.assert_array_equal(a[1], b[1])
    assert not np.array_equal(a[0], c[0])


def test_seeds_wider_than_32_bits_differ():
    keys = [cell.seed_key(s) for s in (5, 5 + 2**32, 5 + 2**33)]
    import jax
    data = {tuple(np.asarray(jax.random.key_data(k)).tolist()) for k in keys}
    assert len(data) == 3


def test_kronecker_is_skewed_and_uniform_is_not():
    deg = {}
    for config in ("gap-kron-s22", "gap-urand-s22"):
        spec = tiny.TinySpec(12)
        src, dst, n = cell.make_data(spec, spec.config(config))
        deg[config] = np.bincount(np.concatenate([src, dst]), minlength=n)
    kron, urand = deg["gap-kron-s22"], deg["gap-urand-s22"]
    assert kron.max() > 10 * urand.max()
    assert (kron == 0).mean() > 0.1 > (urand == 0).mean()


def test_nonzero_vertices_ignore_self_loops():
    src = np.array([0, 1, 3, 3], np.int32)
    dst = np.array([2, 1, 3, 0], np.int32)
    np.testing.assert_array_equal(cell.nonzero_vertices(src, dst, 5),
                                  [0, 2, 3])
