"""Error-feedback int8 gradient compression for the cross-pod DP all-reduce.

Cross-pod gradient reduction rides the slowest links (inter-pod DCN/ICI);
int8 quantization cuts wire bytes 4x while error feedback (Karimireddy et
al., 2019) keeps convergence — the quantization residual is carried into
the next step instead of dropped.  Implemented as an explicit shard_map
reduction over the ``pod`` axis: each pod quantizes (grad + ef) per leaf
with a shared symmetric scale, all-gathers the int8 payloads (+ f32 scales,
negligible), and dequantize-averages locally.

Convergence is regression-tested (tests/test_grad_compression.py): tiny-LM
training with compression tracks the uncompressed loss curve.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as Ps


def init_error_feedback(params):
    return jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)


def _quantize(g, ef):
    x = g.astype(jnp.float32) + ef
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-12) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    err = x - q.astype(jnp.float32) * scale
    return q, scale, err


def compressed_psum_mean(grads, ef, axis: str):
    """Per-leaf int8 all-gather + local dequant-mean over ``axis``.
    Call INSIDE shard_map.  Returns (mean_grads, new_ef)."""
    n = jax.lax.psum(1, axis)

    def per_leaf(g, e):
        q, scale, err = _quantize(g, e)
        qs = jax.lax.all_gather(q, axis)                 # int8 on the wire
        ss = jax.lax.all_gather(scale, axis)             # [n] f32
        deq = qs.astype(jnp.float32) * ss.reshape(
            (n,) + (1,) * g.ndim)
        return jnp.mean(deq, axis=0).astype(g.dtype), err

    out = jax.tree.map(per_leaf, grads, ef)
    mean = jax.tree.map(lambda o: o[0], out,
                        is_leaf=lambda x: isinstance(x, tuple))
    new_ef = jax.tree.map(lambda o: o[1], out,
                          is_leaf=lambda x: isinstance(x, tuple))
    return mean, new_ef


def make_compressed_dp_step(loss_fn, opt, mesh, axis: str = "pod"):
    """Explicit-DP train step: per-shard grads -> compressed mean -> update.

    loss_fn(params, batch) -> (loss, metrics); batch sharded on ``axis``.
    Everything else (params, opt state, ef) is replicated over ``axis``.
    """
    def step(params, opt_state, ef, step_i, batch):
        def shard_fn(params, opt_state, ef, step_i, batch):
            batch = jax.tree.map(lambda x: x[0], batch)   # strip axis dim
            (l, metrics), g = jax.value_and_grad(
                lambda p: loss_fn(p, batch), has_aux=True)(params)
            g, ef2 = compressed_psum_mean(g, ef, axis)
            new_p, new_o = opt.update(g, opt_state, params, step_i)
            l = jax.lax.pmean(l, axis)
            return new_p, new_o, ef2, l
        fn = jax.shard_map(
            shard_fn, mesh=mesh,
            in_specs=(Ps(), Ps(), Ps(), Ps(), Ps(axis)),
            out_specs=(Ps(), Ps(), Ps(), Ps()),
            check_vma=False)
        return fn(params, opt_state, ef, step_i, batch)

    return jax.jit(step)
