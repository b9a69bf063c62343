"""Explicit expert-parallel MoE dispatch under shard_map (§Perf iteration 2).

The SPMD-auto AAM path (moe_layer.moe_apply_aam) leaves the token→expert
reshard to XLA, which gives up on the scatter and FULLY REPLICATES the
dispatch buffers ("involuntary full rematerialization" warnings) — measured
at ~85% of train-step wire bytes on the MoE cells.

This module is the paper-faithful fix: the owner-routing is EXPLICIT, like
an AAM coalescing round.  Tokens stay sharded over ('pod','data'); experts
are owned by 'model' shards.  Each device already holds its token slice
(activations are replicated over 'model'), so dispatch needs NO token
traffic at all: every (data, model) device locally selects the tokens bound
for its experts (bucket plan = the coalescing planner), runs them, and one
psum over 'model' combines the partial outputs — the FF&AS commit.  Expert
weights FSDP-sharded over 'data' are all-gathered once per layer
(unavoidable under FSDP; hoisted out of remat by XLA).

Collective bytes per layer pass drop from O(T·d·E-replication) to
O(T_local·d) psum + O(layer weights/16) gather — measured in
EXPERIMENTS.md §Perf (≈50x less wire on qwen3-moe train_4k).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core.coalescing import plan_buckets_sorted, scatter_to_buckets
from repro.moe.moe_layer import _capacity, _route, aux_loss
from repro.runtime import sharding as shd


def _data_axes(mesh):
    return tuple(a for a in ("pod", "data") if a in mesh.shape)


def moe_apply_shmap(cfg: ModelConfig, p, x2d):
    """x2d: [T, d] (T sharded over pod/data; replicated over model)."""
    mesh = shd.get_abstract_mesh()
    if mesh is None or "model" not in mesh.shape:
        from repro.moe.moe_layer import moe_apply_aam
        return moe_apply_aam(cfg, p, x2d)
    daxes = _data_axes(mesh)
    n_model = mesh.shape["model"]
    n_data = 1
    for a in daxes:
        n_data *= mesh.shape[a]
    e, k = cfg.num_experts, cfg.experts_per_token
    e_local = e // n_model
    t_local = x2d.shape[0] // n_data
    cap = _capacity(cfg, t_local)
    has_gate = "wi_gate" in p

    # weights are FSDP-sharded over "data" only (never over "pod");
    # tokens are sharded over all data axes (pod + data).
    wg_axes = tuple(a for a in ("data",) if a in mesh.shape)

    def inner(router, wi, wi_gate, wo, x):
        j = jax.lax.axis_index("model")
        # assemble full expert weights for the local experts (FSDP gather)
        router = jax.lax.all_gather(router, "model", axis=1, tiled=True)
        for a in wg_axes:
            router = jax.lax.all_gather(router, a, axis=0, tiled=True)
            wi = jax.lax.all_gather(wi, a, axis=1, tiled=True)
            if wi_gate is not None:
                wi_gate = jax.lax.all_gather(wi_gate, a, axis=1, tiled=True)
            wo = jax.lax.all_gather(wo, a, axis=2, tiled=True)
        cd = x.dtype
        logits = (x @ router.astype(cd)).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        w, experts = jax.lax.top_k(probs, k)
        w = (w / jnp.sum(w, axis=-1, keepdims=True)).astype(cd)
        experts = experts.astype(jnp.int32)

        # local-owner selection: this shard owns experts [j*e_local, ...)
        owner = experts.reshape(-1) - j * e_local          # [T_local*k]
        token = jnp.repeat(jnp.arange(t_local, dtype=jnp.int32), k)
        mine = (owner >= 0) & (owner < e_local)
        plan, _ = plan_buckets_sorted(jnp.clip(owner, 0, e_local - 1),
                                      mine, e_local, cap)
        xb = scatter_to_buckets(plan, x[token], e_local, cap, fill=0)

        h = jnp.einsum("ecd,edf->ecf", xb, wi.astype(cd))
        if wi_gate is not None:
            h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xb,
                                       wi_gate.astype(cd))) * h
        else:
            h = jax.nn.gelu(h)
        yb = jnp.einsum("ecf,efd->ecd", h, wo.astype(cd))

        # FR return: tokens gather their local-expert outputs; psum over
        # 'model' completes the FF&AS combine across expert owners.
        pos = plan.position.reshape(t_local, k)
        kept = plan.kept.reshape(t_local, k)
        eloc = jnp.clip(experts - j * e_local, 0, e_local - 1)
        flat = eloc * cap + jnp.clip(pos, 0, cap - 1)
        y = yb.reshape(e_local * cap, -1)[flat]            # [T_local, k, d]
        wk = jnp.where(kept, w, 0.0)
        out = jnp.einsum("tkd,tk->td", y, wk)
        out = jax.lax.psum(out, "model")
        dropped = jax.lax.psum(plan.dropped, ("model",) + tuple(daxes))
        aux = jax.lax.pmean(aux_loss(cfg, probs, experts),
                            ("model",) + tuple(daxes))
        return out, dropped, aux

    dspec = daxes if len(daxes) > 1 else (daxes[0] if daxes else None)
    if has_gate:
        fn = jax.shard_map(
            inner, mesh=mesh,
            in_specs=(P("data", "model"),             # router [d, E]
                      P("model", "data", None),       # wi [E, d, f]
                      P("model", "data", None),       # wi_gate
                      P("model", None, "data"),       # wo [E, f, d]
                      P(dspec, None)),                # x [T, d]
            out_specs=(P(dspec, None), P(), P()),
            check_vma=False)
        out, dropped, aux = fn(p["router"], p["wi"], p["wi_gate"], p["wo"],
                               x2d)
    else:
        def inner4(router, wi, wo, x):
            return inner(router, wi, None, wo, x)
        fn = jax.shard_map(
            inner4, mesh=mesh,
            in_specs=(P("data", "model"), P("model", "data", None),
                      P("model", None, "data"), P(dspec, None)),
            out_specs=(P(dspec, None), P(), P()),
            check_vma=False)
        out, dropped, aux = fn(p["router"], p["wi"], p["wo"], x2d)
    return out, {"moe_dropped": dropped, "moe_aux": aux}
