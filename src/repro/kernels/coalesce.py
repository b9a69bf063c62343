"""Pallas TPU kernel: bucket histogram (coalescing planner hot-spot).

Counting messages per destination shard / expert is the first step of every
coalescing round (paper §4.2).  One grid step processes a tile of M owner
ids against the full [num_buckets] count vector in VMEM via a one-hot
column-sum — the same M×B tile pattern as the commit kernel with op=add on
unit payloads, in the same layout: owner ids as a ``[1, N]`` row
transposed per tile, the counts as a ``[1, num_buckets]`` row.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.coarse_commit import message_window, pad_message_row


def _count_kernel(owner_ref, out_ref, *, tile_m: int, nb: int):
    m = pl.program_id(0)

    @pl.when(m == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    owner = owner_ref[...].T                            # [M, 1]
    mask = (owner >= 0) & (owner < nb)
    safe = jnp.where(mask, owner, 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (tile_m, nb), 1)
    onehot = (lane == safe) & mask
    out_ref[...] += jnp.sum(onehot.astype(jnp.int32), axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("num_buckets", "tile_m",
                                             "interpret"))
def bucket_count_pallas(owner, *, num_buckets: int, tile_m: int = 512,
                        interpret: bool):
    """owner: [N] int32 (-1 = masked) -> counts [num_buckets] int32."""
    tile_m = message_window(tile_m)
    nb = num_buckets + (-num_buckets) % 128
    owner_p = pad_message_row(owner, tile_m, -1)
    out = pl.pallas_call(
        functools.partial(_count_kernel, tile_m=tile_m, nb=nb),
        grid=(owner_p.shape[1] // tile_m,),
        in_specs=[pl.BlockSpec((1, tile_m), lambda m: (0, m))],
        out_specs=pl.BlockSpec((1, nb), lambda m: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, nb), jnp.int32),
        interpret=interpret,
    )(owner_p)
    return out[0, :num_buckets]
