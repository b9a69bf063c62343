"""GPipe-style pipeline parallelism over a mesh axis.

Stages hold contiguous slices of the stacked block parameters (the leading
``num_blocks`` axis), microbatches stream through a ``collective_permute``
chain, and the whole schedule differentiates through ``jax.grad`` (ppermute
has a transpose rule), so PP composes with the existing optimizer stack.
Stage 0 embeds; the last stage computes logits/loss; intermediate
activations are the only cross-stage traffic (one [mb, S, d] tensor per
microbatch per boundary — DCN-friendly, which is why PP is the alternative
to DP across pods: config ``pipeline_stages`` on the ``pod`` axis).

Forward-equivalence vs the plain stack is tested on a 2-stage host mesh
(tests/test_pipeline.py).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as Ps

from repro.configs.base import ModelConfig, RunConfig
from repro.models import layers as L
from repro.models import lm


def _stage_blocks(params_blocks, stage, num_stages, num_blocks):
    """Slice each pattern-position stack to this stage's block range."""
    per = num_blocks // num_stages
    return jax.tree.map(
        lambda x: jax.lax.dynamic_slice_in_dim(x, stage * per, per, axis=0),
        params_blocks)


def pipeline_forward(cfg: ModelConfig, rcfg: RunConfig, mesh, axis: str,
                     num_microbatches: int):
    """Returns f(params, tokens) -> logits, running the block stack as
    ``axis``-many pipeline stages.  num_blocks must divide evenly."""
    num_stages = mesh.shape[axis]
    assert cfg.num_blocks % num_stages == 0, (cfg.num_blocks, num_stages)

    def shard_fn(params, tokens):
        stage = jax.lax.axis_index(axis)
        nmb = num_microbatches
        b = tokens.shape[0]
        mb = b // nmb
        blocks = _stage_blocks(params["blocks"], stage, num_stages,
                               cfg.num_blocks)

        def run_stage(x):
            def block_fn(x, bp):
                for i, spec in enumerate(cfg.full_pattern):
                    x, _, _ = lm.apply_layer(cfg, rcfg, spec, bp[i], x,
                                             positions, mode="train")
                return x, None
            x, _ = jax.lax.scan(block_fn, x, blocks)
            return x

        s = tokens.shape[1]
        positions = jnp.broadcast_to(
            jnp.arange(s, dtype=jnp.int32)[None], (mb, s))
        cd = jnp.dtype(rcfg.compute_dtype)

        # schedule: nmb + num_stages - 1 ticks
        ticks = nmb + num_stages - 1
        outs = []
        carry = jnp.zeros((mb, s, cfg.d_model), cd)
        for t in range(ticks):
            # stage 0 ingests microbatch t (if any)
            mb_idx = min(t, nmb - 1)
            tok_mb = jax.lax.dynamic_slice_in_dim(tokens, mb_idx * mb, mb, 0)
            fresh = L.embed_tokens(cfg, params["embed"], tok_mb, cd)
            x = jnp.where(stage == 0, fresh, carry)
            y = run_stage(x)
            # pass activations down the chain
            perm = [(i, i + 1) for i in range(num_stages - 1)]
            carry = jax.lax.ppermute(y, axis, perm)
            if t >= num_stages - 1:
                outs.append(y)          # last stage's finished microbatch
        out = jnp.concatenate(outs, axis=0)
        x = L.rmsnorm(out, params["final_norm"], cfg.norm_eps,
                      zero_centered=cfg.use_post_norm)
        logits = L.lm_logits(cfg, params["embed"], x)
        # only the last stage's logits are real; broadcast them
        src = num_stages - 1
        perm = [(src, i) for i in range(num_stages) if i != src]
        logits = jnp.where(stage == src, logits,
                           jnp.zeros_like(logits))
        logits = jax.lax.psum(logits, axis)
        return logits

    return jax.jit(jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(Ps(), Ps()),
        out_specs=Ps(), check_vma=False))
