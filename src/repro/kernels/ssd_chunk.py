"""Pallas TPU kernel: fused SSD intra-chunk block (Mamba2 hot-spot).

Computes the quadratic intra-chunk term of the state-space duality
y[t] = Σ_{s≤t} (C_t·B_s) · exp(Σ_{s<u≤t} a_u) · x_s for one (batch·chunk,
head) grid cell, fusing the C·Bᵀ matmul, the decay/causal mask, and the
·x contraction in VMEM — three MXU/VPU ops with no HBM round-trip for the
L×L Gram matrix (on HBM that matrix dominates traffic: L²·4B per head per
chunk).  The inter-chunk recurrence stays a lax.scan (tiny state).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _ssd_kernel(C_ref, B_ref, x_ref, a_ref, out_ref, *, L: int):
    C = C_ref[0].astype(jnp.float32)          # [L, N]
    B = B_ref[0].astype(jnp.float32)          # [L, N]
    x = x_ref[0].astype(jnp.float32)          # [L, P]
    a = a_ref[0].astype(jnp.float32)          # [1, L] (2-D for TPU layout)
    cs = jnp.cumsum(a[0])
    diff = cs[:, None] - cs[None, :]
    t_idx = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    s_idx = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    mask = s_idx <= t_idx
    G = jax.lax.dot(C, B.T, precision=jax.lax.Precision.HIGHEST)
    G = jnp.where(mask, G * jnp.exp(diff), 0.0)
    y = jax.lax.dot(G, x, precision=jax.lax.Precision.HIGHEST)
    out_ref[0] = y.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_chunk_pallas(C, B, x, a, *, interpret: bool):
    """C, B: [G, L, N]; x: [G, L, P]; a: [G, L] log-decays.

    G = batch·chunks·heads flattened grid dim. Returns y [G, L, P]."""
    g, L, n = C.shape
    p = x.shape[-1]
    a2 = a[:, None, :]                        # [G, 1, L]
    out = pl.pallas_call(
        functools.partial(_ssd_kernel, L=L),
        grid=(g,),
        in_specs=[
            pl.BlockSpec((1, L, n), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, L, n), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, L, p), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1, L), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, L, p), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((g, L, p), x.dtype),
        interpret=interpret,
    )(C, B, x, a2)
    return out
