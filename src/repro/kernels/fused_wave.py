"""Pallas TPU kernel: fused route+commit wave pass (ROADMAP item 3).

The unfused owner-side path of :func:`repro.core.engine.route_wave`
materializes the routed messages one more time after the exchange:
``local_idx = clip(rt - shard*block)`` and (for fused batch axes)
``fuse_keys(local_idx, lane, width)`` are full [P*C] jnp intermediates,
then a separate :func:`repro.kernels.coarse_commit.coarse_commit_pallas`
launch re-reads them.  The paper's HTM never pays that traffic — a
transaction reorders and commits inside its speculative read/write set —
and IARU/PIUMA (PAPERS.md) recover it on GPU/graph pipelines by fusing
the reorder with the update.

This kernel is the software analogue: ONE launch takes the post-exchange
bucket buffers ``rt``/``rp`` exactly as the all_to_all left them (global
target ids with ``-1`` empty-slot sentinels, optional per-message lane
ids) and, per grid step,

1. computes the local composite key in registers:
   ``key = (tgt - base) * width + lane`` — the ``local_idx``/
   ``fuse_keys`` arithmetic that was a jnp materialization;
2. reorders/coalesces the tile against the VMEM-resident state block via
   the M×B one-hot incidence (the in-VMEM analogue of sort-by-target:
   every message lands on its state column regardless of arrival order);
3. applies the commit op (``min``/``max``/``add``/``or``/``first`` with
   the pinned lowest-global-message-id ``first`` tiebreak, identical to
   the coarse kernel so cross-backend parity holds bit-for-bit);
4. (``stats=True``) accumulates the in-transaction duplicate-target
   count — the abort-statistics analogue — per state column.

Grid/tiling/identity-padding follow :mod:`repro.kernels.coarse_commit`:
grid = (state_blocks, message_tiles), message tiles innermost so a state
block stays VMEM-resident while every transaction visits it; the (M × B)
working set is the HTM speculative-capacity analogue and M is the
paper's transaction-size knob (the adaptive ladder moves it per round).

``base`` is a traced scalar (the owner shard's first global vertex id,
``shard * block`` under ``shard_map``) carried as a (1,) int32 array in
SMEM so the same compiled kernel serves every shard.  Message arrays
and the state travel as lane-dense rows, the layout
:mod:`repro.kernels.coarse_commit` explains.  With ``base=None`` and
``width == 1`` the key computation folds away and the kernel degenerates
to the plain coarse-commit tile loop — that specialization is what
``CommitSpec(backend="fused")`` runs through the generic
:func:`repro.core.commit.commit` dispatch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.coarse_commit import (COMMIT_OPS, check_block,
                                         commit_grid, commit_window,
                                         message_window, pad_message_row,
                                         pad_state_row, vmem_limit)


def _fused_kernel(*refs, op: str, tile_m: int, block_v: int, width: int,
                  nrows: int, with_lane: bool, with_base: bool,
                  stats: bool):
    it = iter(refs)
    idx_ref, val_ref, state_ref = next(it), next(it), next(it)
    lane_ref = next(it) if with_lane else None
    base_ref = next(it) if with_base else None
    out_ref = next(it)
    conf_ref = next(it) if stats else None

    b = pl.program_id(0)
    w = pl.program_id(1)

    @pl.when(w == 0)
    def _init():
        out_ref[...] = state_ref[...]
        if conf_ref is not None:
            conf_ref[...] = jnp.zeros_like(conf_ref)

    idx = idx_ref[...].T                                 # [W, 1] global ids
    # --- in-kernel composite key: the fused local_idx/fuse_keys step ---
    rel = idx - (base_ref[0] if with_base else 0)
    ok = (idx >= 0) & (rel >= 0) & (rel < nrows)         # -1 = empty slot
    if with_lane:
        lane = lane_ref[...].T
        ok = ok & (lane >= 0) & (lane < width)
        key = rel * width + jnp.where(ok, lane, 0)
    else:
        key = rel
    # --- in-VMEM reorder/coalesce: one-hot incidence vs this block ---
    kk = key - b * block_v
    mask = ok & (kk >= 0) & (kk < block_v)

    # Tile skip — the fusion dividend the unfused path cannot claim:
    # bucketed traffic is clustered (contention concentrates keys in few
    # state blocks), so most (block, tile) grid steps touch nothing and
    # the whole M×B incidence/commit is elided.  The separate-launch
    # pipeline can't do this: its commit kernel sees pre-flattened keys
    # with no cheap per-tile routing test left.
    @pl.when(jnp.max(mask.astype(jnp.int32)) > 0)
    def _commit_tile():
        commit_window(out_ref, conf_ref, jnp.where(mask, kk, 0), mask,
                      val_ref[...].T, op=op, first_id=w * idx.shape[0],
                      tile_m=tile_m, block_v=block_v)


@functools.partial(jax.jit, static_argnames=("op", "width", "tile_m",
                                             "block_v", "interpret",
                                             "stats"))
def fused_route_commit_pallas(state, tgt, val, *, lane=None, base=None,
                              width: int = 1, op: str = "min",
                              tile_m: int = 256, block_v: int = 512,
                              interpret: bool, stats: bool = False):
    """One launch from exchanged bucket buffers to committed state.

    state: [R * width] local composite-key slice (R vertex rows × width
    batch items, vertex-major — exactly the owner slice layout of
    :func:`repro.core.engine.route_wave`); tgt: [N] int32 GLOBAL vertex
    ids straight off the all_to_all (``-1`` = empty slot); val: [N]
    payloads; lane: [N] int32 per-message item ids (required iff
    ``width > 1``); base: traced scalar int32 — global id of local row 0
    (``None`` = 0, the single-shard case).

    Returns the committed state; ``stats=True`` returns
    ``(state, conflicts)`` with the grid-summed duplicate-target count.
    ``interpret`` has no default: :func:`repro.kernels.interpret_default`
    decides it for the running backend.
    """
    if (lane is None) == (width > 1):
        raise ValueError(f"lane ids are required iff width > 1 "
                         f"(width={width}, lane={'set' if lane is not None else 'None'})")
    if op not in COMMIT_OPS:
        raise ValueError(op)
    check_block(block_v, interpret)
    v = state.shape[0]
    n = tgt.shape[0]
    if v % width:
        raise ValueError(f"state length {v} not divisible by width {width}")
    if n == 0 or v == 0:
        return (state, jnp.zeros((), jnp.int32)) if stats else state
    window = message_window(tile_m)
    state_p = pad_state_row(state, op, block_v)

    row_spec = pl.BlockSpec((1, block_v), lambda b, w: (0, b))
    msg_spec = pl.BlockSpec((1, window), lambda b, w: (0, w))
    in_specs = [msg_spec, msg_spec, row_spec]
    inputs = [pad_message_row(tgt.astype(jnp.int32), window, -1),
              pad_message_row(val, window), state_p]
    if lane is not None:
        in_specs.append(msg_spec)
        inputs.append(pad_message_row(lane.astype(jnp.int32), window))
    if base is not None:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        inputs.append(jnp.reshape(jnp.asarray(base, jnp.int32), (1,)))
    out_specs = [row_spec]
    out_shape = [jax.ShapeDtypeStruct(state_p.shape, state.dtype)]
    if stats:
        out_specs.append(row_spec)
        out_shape.append(jax.ShapeDtypeStruct(state_p.shape, jnp.int32))
    res = pl.pallas_call(
        functools.partial(_fused_kernel, op=op, tile_m=tile_m,
                          block_v=block_v, width=width, nrows=v // width,
                          with_lane=lane is not None,
                          with_base=base is not None, stats=stats),
        grid=commit_grid(v, n, tile_m, block_v),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit(window, block_v)),
    )(*inputs)
    if stats:
        out, conf = res
        return out[0, :v], jnp.sum(conf)
    return res[0][0, :v]
