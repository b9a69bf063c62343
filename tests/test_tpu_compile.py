"""Ahead-of-time compiles of the Pallas kernels for a TPU v5e chip.

The TPU compiler installed with jaxlib compiles for a described chip that
is not attached, so these tests check on any host that Mosaic accepts the
kernels of the main path — tiling, layouts, VMEM — at real widths
(``tile_m=256``, ``block_v=512``; depth cut to 2^16 vertices).  Nothing
runs: results are pinned by the interpret-mode parity tests.

The topology is described inside a module fixture, never at import time:
only one process may load the TPU library, and under pytest-xdist every
worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.coalesce import bucket_count_pallas
from repro.kernels.coarse_commit import coarse_commit_pallas
from repro.kernels.fused_wave import fused_route_commit_pallas

V, N = 1 << 16, 1 << 17
KERNEL_OPS = [("min", jnp.int32), ("add", jnp.float32), ("first", jnp.int32)]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiles_to_kernel(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("stats", [False, True])
@pytest.mark.parametrize("op,dtype", KERNEL_OPS)
@pytest.mark.parametrize("kernel", ["coarse", "fused"])
def test_commit_kernel_compiles(one_chip, kernel, op, dtype, stats):
    entry = (coarse_commit_pallas if kernel == "coarse"
             else fused_route_commit_pallas)
    _compiles_to_kernel(
        one_chip,
        lambda s, i, x: entry(s, i, x, op=op, tile_m=256, block_v=512,
                              interpret=False, stats=stats),
        ((V,), dtype), ((N,), jnp.int32), ((N,), dtype))


@pytest.mark.parametrize("stats", [False, True])
@pytest.mark.parametrize("op,dtype", KERNEL_OPS)
def test_fused_site_lane_base_compiles(one_chip, op, dtype, stats):
    """The engine's owner-side site: lane ids, a traced base, width 8."""
    _compiles_to_kernel(
        one_chip,
        lambda s, i, x, lane, base: fused_route_commit_pallas(
            s, i, x, lane=lane, base=base, width=8, op=op, tile_m=256,
            block_v=512, interpret=False, stats=stats),
        ((V,), dtype), ((N,), jnp.int32), ((N,), dtype),
        ((N,), jnp.int32), ((), jnp.int32))


@pytest.mark.parametrize("tile_m", [16, 48, 4096])
def test_ladder_transaction_sizes_compile(one_chip, tile_m):
    """Ladder sizes below one 128-lane window (several transactions per
    grid step) and the largest, whose ``first`` working set needs the
    raised VMEM limit."""
    _compiles_to_kernel(
        one_chip,
        lambda s, i, x: fused_route_commit_pallas(
            s, i, x, op="first", tile_m=tile_m, block_v=512,
            interpret=False, stats=True),
        ((V,), jnp.int32), ((N,), jnp.int32), ((N,), jnp.int32))


def test_bucket_count_compiles(one_chip):
    _compiles_to_kernel(
        one_chip,
        lambda o: bucket_count_pallas(o, num_buckets=4, tile_m=256,
                                      interpret=False),
        ((N,), jnp.int32))
