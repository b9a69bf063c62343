"""Pallas TPU kernel: coarse conflict-resolving commit (DESIGN.md §2.1).

One *transaction* = a tile of M messages ``(idx, val)`` resolved against a
``B``-vertex block of the state array entirely in VMEM.  The M×B one-hot
incidence is materialized in registers/VMEM and reduced:

* ``add`` (Always-Succeed accumulate): ``contrib = valᵀ · onehot`` — an MXU
  matmul (this is why the AS commit is *serialization-free* on TPU, unlike
  the paper's HTM abort storm for ACC in §5.4.2);
* ``min``/``max`` (May-Fail): masked VPU reduction over the tile dim;
* ``or`` (AS mark): any-reduction of truthy payloads;
* ``first`` (MF first-writer-wins into empty ``<0`` slots, ties broken by
  lowest global message id — payloads must be non-negative since negative
  state encodes "empty").

The (M × B) working set is the transaction's read/write set and must fit
VMEM — the exact analogue of the paper's HTM speculative-state capacity
(L1/L2): oversized M spills and "aborts" become tile re-fetches.  M is the
paper's transaction-size knob; the roofline sweep lives in
``benchmarks/fig4_coarsening.py``.

Grid = (state_blocks, message_windows); message windows iterate innermost
so a state block stays resident while every transaction visits it.
Messages sorted by target (coalescing) make non-incident (window, block)
pairs cheap (all-masked compare, no state traffic); unsorted messages
model the paper's uncoalesced baseline.

Layout (what the TPU compiler accepts): the state travels as a ``[1, V]``
row, one ``(1, block_v)`` block per state block, and every per-message
array as a ``[1, N]`` row.  XLA keeps both lane-dense in HBM (an
``[N, 1]`` column would be padded 128-fold).  A grid step loads a
128-aligned *window* of messages (:func:`message_window`: the smallest
multiple of M that 128 divides) and transposes it once to a column, which
broadcasts against the ``[window, block_v]`` iota; every reduction over
the tile lands directly in the ``(1, block_v)`` state row.  A window
holds ``window // M`` transactions, committed in order, so any M keeps
its exact per-transaction semantics.  Conflict counts accumulate per
state column in a row of the same shape, summed outside.  On the chip
``block_v`` must be a multiple of 128.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _identity(op: str, dtype):
    if op == "min":
        return (jnp.iinfo(dtype).max if jnp.issubdtype(dtype, jnp.integer)
                else jnp.inf)
    if op == "max":
        return (jnp.iinfo(dtype).min if jnp.issubdtype(dtype, jnp.integer)
                else -jnp.inf)
    if op == "first":
        return -1                                        # empty-slot marker
    return 0


_RANK_INF = 2 ** 30     # plain int: jnp constants can't be kernel captures

COMMIT_OPS = ("add", "min", "max", "or", "first")


def message_window(tile_m: int) -> int:
    """Messages per grid step: the smallest multiple of ``tile_m`` that a
    ``(1, k)`` block may span (``k % 128 == 0``)."""
    return tile_m * (128 // math.gcd(tile_m, 128))


def commit_grid(v: int, n: int, tile_m: int, block_v: int) -> tuple:
    """(state blocks, message windows) of one launch over a [v] state and
    n messages — its grid, whose product is the launch's step count."""
    return pl.cdiv(v, block_v), pl.cdiv(n, message_window(tile_m))


def check_block(block_v: int, interpret: bool) -> None:
    """The compiled kernel's tiling rule for the ``(1, block_v)`` state
    block.  Interpret mode takes any size."""
    if not interpret and block_v % 128:
        raise ValueError(f"compiled commit kernels need block_v divisible "
                         f"by 128, got {block_v}")


def vmem_limit(window: int, block_v: int) -> int:
    """Scoped-VMEM budget of one grid step: a handful of live
    ``[window, block_v]`` 32-bit temporaries (the ``first`` op keeps the
    most), floored at the compiler's 16 MiB default."""
    return max(16 << 20, 8 * 4 * window * block_v)


def pad_state_row(state, op: str, block_v: int):
    """[V] state -> identity-padded [1, V + pad] row."""
    vpad = (-state.shape[0]) % block_v
    fill = (state.dtype.type(_identity(op, state.dtype))
            if op not in ("add", "or") else 0)
    return jnp.pad(state, (0, vpad), constant_values=fill).reshape(1, -1)


def pad_message_row(x, window: int, fill=0):
    """[N] per-message array -> [1, N + pad] row of whole windows."""
    return jnp.pad(x, (0, (-x.shape[0]) % window),
                   constant_values=fill).reshape(1, -1)


def commit_window(out_ref, conf_ref, col, mask, val, *, op: str, first_id,
                  tile_m: int, block_v: int):
    """Commit one window of transactions, in order, against the resident
    state block.

    ``col``/``mask``/``val`` are ``[window, 1]`` columns: each message's
    column inside this block, whether it lands here, and its payload;
    ``first_id`` is the global id of the window's first message.
    ``out_ref`` is the ``(1, block_v)`` state row, ``conf_ref`` (or None)
    the per-column duplicate-count row.  Shared by the coarse and fused
    kernels, so both tiers commit bit-identically."""
    window = col.shape[0]
    if window == tile_m:
        _commit_tile(out_ref, conf_ref, col, mask, val, op=op,
                     first_id=first_id, block_v=block_v)
        return
    row = jax.lax.broadcasted_iota(jnp.int32, (window, 1), 0)

    def one_transaction(t, carry):
        lo = t * tile_m
        _commit_tile(out_ref, conf_ref, col,
                     mask & (row >= lo) & (row < lo + tile_m), val, op=op,
                     first_id=first_id, block_v=block_v)
        return carry

    jax.lax.fori_loop(0, window // tile_m, one_transaction, 0)


def _commit_tile(out_ref, conf_ref, col, mask, val, *, op: str, first_id,
                 block_v: int):
    window = col.shape[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (window, block_v), 1)
    onehot = (lane == col) & mask                        # [M, B]

    if conf_ref is not None:
        # conflict telemetry: in-transaction messages sharing a target
        # (the abort-statistics analogue; summed over columns outside)
        cnt = jnp.sum(onehot.astype(jnp.int32), axis=0, keepdims=True)
        conf_ref[...] += jnp.where(cnt > 1, cnt, 0)

    if op == "add":
        if jnp.issubdtype(val.dtype, jnp.floating):
            contrib = jax.lax.dot_general(                # MXU path
                val.astype(jnp.float32), onehot.astype(jnp.float32),
                (((0,), (0,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)       # [1, B]
        else:
            contrib = jnp.sum(jnp.where(onehot, val, 0), axis=0,
                              keepdims=True)
        out_ref[...] += contrib.astype(out_ref.dtype)
    elif op in ("min", "max"):
        cand = jnp.where(onehot, val, _identity(op, val.dtype))
        if op == "min":
            out_ref[...] = jnp.minimum(
                out_ref[...], jnp.min(cand, axis=0, keepdims=True))
        else:
            out_ref[...] = jnp.maximum(
                out_ref[...], jnp.max(cand, axis=0, keepdims=True))
    elif op == "or":
        hit = jnp.max(jnp.where(onehot & (val != 0), 1, 0), axis=0,
                      keepdims=True)
        out_ref[...] = jnp.maximum(out_ref[...], hit.astype(out_ref.dtype))
    elif op == "first":
        # first-writer-wins into empty (<0) slots; tie-break = lowest
        # global message id.  Transactions execute in order, so the
        # in-tile winner composes to the batch-wide lowest id.
        cur = out_ref[...]
        empty = cur < 0                                  # [1, B]
        rank = first_id + jax.lax.broadcasted_iota(
            jnp.int32, (window, block_v), 0)
        key = jnp.where(onehot & empty, rank, _RANK_INF)
        win = jnp.min(key, axis=0, keepdims=True)        # [1, B]
        wsel = onehot & (key == win) & (win < _RANK_INF)
        wval = jnp.sum(jnp.where(wsel, val, 0), axis=0, keepdims=True)
        out_ref[...] = jnp.where(empty & (win < _RANK_INF),
                                 wval.astype(cur.dtype), cur)
    else:
        raise ValueError(op)


def _commit_kernel(idx_ref, val_ref, state_ref, out_ref, conf_ref=None, *,
                   op: str, tile_m: int, block_v: int):
    b = pl.program_id(0)
    w = pl.program_id(1)

    @pl.when(w == 0)
    def _init():
        out_ref[...] = state_ref[...]
        if conf_ref is not None:
            conf_ref[...] = jnp.zeros_like(conf_ref)

    idx = idx_ref[...].T                                 # [W, 1] int32
    rel = idx - b * block_v
    mask = (rel >= 0) & (rel < block_v) & (idx >= 0)     # idx -1 = invalid
    commit_window(out_ref, conf_ref, jnp.where(mask, rel, 0), mask,
                  val_ref[...].T, op=op, first_id=w * idx.shape[0],
                  tile_m=tile_m, block_v=block_v)


@functools.partial(jax.jit, static_argnames=("op", "tile_m", "block_v",
                                             "interpret", "stats"))
def coarse_commit_pallas(state, idx, val, *, op: str = "min",
                         tile_m: int = 256, block_v: int = 512,
                         interpret: bool, stats: bool = False):
    """state: [V]; idx: [N] int32 (-1 = masked); val: [N].

    Returns the committed state; with ``stats=True`` returns
    ``(state, conflicts)`` where ``conflicts`` is the int32 count of
    in-transaction duplicate-target messages accumulated over the grid
    (one transaction = one ``tile_m`` tile), so :class:`CommitResult`
    telemetry is available from the kernel path too.  ``interpret`` has
    no default: :func:`repro.kernels.interpret_default` decides it for
    the running backend.
    """
    if op not in COMMIT_OPS:
        raise ValueError(op)
    check_block(block_v, interpret)
    v = state.shape[0]
    n = idx.shape[0]
    if n == 0 or v == 0:
        return (state, jnp.zeros((), jnp.int32)) if stats else state
    window = message_window(tile_m)
    state_p = pad_state_row(state, op, block_v)

    row_spec = pl.BlockSpec((1, block_v), lambda b, w: (0, b))
    msg_spec = pl.BlockSpec((1, window), lambda b, w: (0, w))
    out_specs = [row_spec]
    out_shape = [jax.ShapeDtypeStruct(state_p.shape, state.dtype)]
    if stats:
        out_specs.append(row_spec)
        out_shape.append(jax.ShapeDtypeStruct(state_p.shape, jnp.int32))
    res = pl.pallas_call(
        functools.partial(_commit_kernel, op=op, tile_m=tile_m,
                          block_v=block_v),
        grid=commit_grid(v, n, tile_m, block_v),
        in_specs=[msg_spec, msg_spec, row_spec],
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem_limit(window, block_v)),
    )(pad_message_row(idx, window, -1), pad_message_row(val, window),
      state_p)
    if stats:
        out, conf = res
        return out[0, :v], jnp.sum(conf)
    return res[0][0, :v]
