"""Compile the main path for a described TPU v5e, without the chip.

The TPU compiler is installed with jaxlib, and it compiles for a chip that
is described rather than attached (`jax.experimental.topologies`). These
tests compile, at the shapes of the one-chip smoke cell (RMAT scale 22:
4.19M vertices, 128M directed edges):

* the XLA cohort executables (init, td/bu/mixed steps, sync payload) for
  B=1 and B=8, from `ShapeDtypeStruct`s — possible because the graph is an
  argument of every executable — and check they fit one chip's 16 GB;
* the partitioned search on a described 2x2 mesh;
* each Pallas kernel with `interpret=False`. Mosaic refuses every one of
  them today; each case is a strict xfail that names the refusal, so the
  change that repairs a kernel sees its case flip.

Nothing runs: a passing compile says nothing about results or times.

The topology is described inside a module fixture, never at import time:
only one process at a time may load the TPU library, and every pytest
worker imports every test file.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core import bfs as B
from repro.core.hybrid_bfs import (HybridConfig, HybridGraph, HybridShapes,
                                   hybrid_search_program)
from repro.kernels import ops as K

HBM_BYTES = 16 * 10**9            # one v5e chip
V22 = 1 << 22                     # RMAT scale 22
E22 = 128_302_398                 # its directed edges (seed 0)
I32, U8 = jnp.int32, jnp.uint8


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure: cannot describe
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _placed(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


# ------------------------------------------------------- cohort executables --

@pytest.mark.parametrize("batch", [1, 8])
def test_cohort_executables_compile_at_scale22(one_chip, batch):
    cfg = B.BFSConfig()
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    dg = B.DeviceGraph(indptr=s((V22 + 1,), I32), indices=s((E22,), I32),
                       deg_ext=s((V22 + 1,), I32), pull_order=s((V22,), I32),
                       num_vertices=V22, num_directed_edges=E22)
    graph = B.CohortGraph(dg, None, None)
    roots, active = s((batch,), I32), s((batch,), jnp.bool_)

    def init(g, r, a):
        return B.init_batch(g.dg, cfg, r, a)

    compiled = jax.jit(init).lower(graph, roots, active).compile()
    sizes = {"init": _device_bytes(compiled)}
    state = _placed(jax.eval_shape(init, graph, roots, active), one_chip)
    for variant in B.reachable_variants(cfg):
        step = jax.jit(B.make_batch_step(cfg, variant))
        sizes[variant] = _device_bytes(step.lower(graph, state).compile())
    sizes["scalars"] = _device_bytes(
        jax.jit(B.batch_scalars).lower(state).compile())
    assert max(sizes.values()) < HBM_BYTES, sizes
    # the graph is an argument: every step program carries the CSR once
    assert min(sizes[v] for v in B.reachable_variants(cfg)) > 4 * E22


# ---------------------------------------------------------- sharded search --

def test_sharded_search_compiles_on_2x2_mesh(topo):
    n = 4
    mesh = Mesh(np.asarray(topo.devices).reshape(n), ("part",))
    split = NamedSharding(mesh, P("part"))
    rep = NamedSharding(mesh, P())
    # The engine's default 4-way "specialized" partition of rmat(22, seed=0),
    # as `HybridShapes.of(pg)`: the line `chip_smoke.py --four-chips` logs
    # (the partition is host numpy, so the same on any machine).
    shapes = HybridShapes(v_pad=4_194_306, rows=1_070_838,
                          e_local=32_086_652, e_total=E22, hub_count=29_682)
    s = jax.ShapeDtypeStruct
    graph = HybridGraph(
        indptr=s((n, shapes.rows + 1), I32, sharding=split),
        indices=s((n, shapes.e_local), I32, sharding=split),
        row_gid=s((n, shapes.rows), I32, sharding=split),
        deg_ext=s((shapes.v_pad + 1,), I32, sharding=rep), ell=())
    search = hybrid_search_program(shapes, HybridConfig(), mesh)
    compiled = jax.jit(search).lower(graph, s((), I32, sharding=rep)).compile()
    assert _device_bytes(compiled) < HBM_BYTES
    assert "all-reduce" in compiled.as_text()


# ------------------------------------------------------------ Pallas kernels --

class MosaicRefused(Exception):
    """The compile failed with exactly the quoted Mosaic refusal."""


R, W, HUB_R, HUB_W, LANES = 65536, 32, 64, 4096, 8
RANK1_BLOCK = "rank 1 block shapes"
TILE_8_128 = "divisible by 8 and 128"
KERNELS = {
    # name: (call, shapes, the refusal Mosaic gives)
    "bottomup": (lambda d, n, f: K.bottomup(d, n, f, interpret=False),
                 [((R,), I32), ((R, W), I32), ((V22,), U8)], RANK1_BLOCK),
    "bottomup_batch": (
        lambda d, n, f: K.bottomup_batch(d, n, f, interpret=False),
        [((LANES, R), I32), ((R, W), I32), ((LANES, V22), U8)], TILE_8_128),
    "topdown": (lambda d, n, v: K.topdown(d, n, v, interpret=False),
                [((R,), I32), ((R, W), I32), ((V22,), U8)],
                "Only 2D gather is supported"),
    "topdown_batch": (
        lambda d, n, v: K.topdown_batch(d, n, v, interpret=False),
        [((LANES, R), I32), ((R, W), I32), ((LANES, V22), U8)], TILE_8_128),
    "frontier_fused": (
        lambda f, d: K.frontier_fused(f, d, interpret=False),
        [((V22,), U8), ((V22,), I32)],
        "Reductions over unsigned integers not implemented"),
    "frontier_fused_batch": (
        lambda f, d: K.frontier_fused_batch(f, d, interpret=False),
        [((LANES, V22), U8), ((V22,), I32)], TILE_8_128),
    "hub_bottomup": (
        lambda d, n, f: K.hub_bottomup(d, n, f, interpret=False),
        [((HUB_R,), I32), ((HUB_R, HUB_W), I32), ((V22,), U8)], RANK1_BLOCK),
    "hub_bottomup_batch": (
        lambda d, n, f: K.hub_bottomup_batch(d, n, f, interpret=False),
        [((LANES, HUB_R), I32), ((HUB_R, HUB_W), I32), ((LANES, V22), U8)],
        TILE_8_128),
}


@pytest.mark.parametrize("name", [
    pytest.param(name, marks=pytest.mark.xfail(
        strict=True, raises=MosaicRefused, reason=f"Mosaic: {refusal}"))
    for name, (_call, _shapes, refusal) in KERNELS.items()])
def test_kernel_compiles_for_tpu(one_chip, name):
    call, shapes, refusal = KERNELS[name]
    args = [jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
            for shape, dt in shapes]
    try:
        jax.jit(call).lower(*args).compile()
    except (ValueError, NotImplementedError) as e:
        if refusal in str(e):
            raise MosaicRefused(refusal) from e
        raise
