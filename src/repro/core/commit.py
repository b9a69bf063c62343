"""Commit engines — the HTM-transaction analogue (DESIGN.md §2).

One semantic operation — "commit a batch of atomic active messages" —
executed by interchangeable mechanisms, mirroring the paper's
atomics → HTM spectrum (AAM §4–§5):

* ``atomic`` — :func:`atomic_commit`: one scatter element per message
  (XLA scatter with conflict semantics resolved by the memory system).
  The *fine-grained atomics* baseline the paper compares against
  (Graph500-style CAS/ACC).
* ``coarse`` — :func:`coarse_commit`: the AAM path — messages are
  processed in "transactions" of M messages; each transaction's conflicts
  are resolved on-chip (sort + segment reduction over the tile) and the
  state is written once per distinct target.
* ``pallas`` — :mod:`repro.kernels.coarse_commit` executes one
  transaction per grid step against VMEM-resident state blocks (interpret
  mode on CPU, compiled on real TPU).
* ``fused`` — :mod:`repro.kernels.fused_wave`: the pallas tile loop with
  the route-side key computation folded INTO the kernel — one launch
  from the post-exchange bucket buffers (global ids + ``-1`` sentinels,
  optional lane ids) to committed state, no ``local_idx``/
  ``make_messages`` materialization.  Through the generic :func:`commit`
  entry (plain local targets) it matches ``pallas`` launch-for-launch;
  the engine's :func:`fused_commit_site` fast path is where the
  intermediate drop happens.

:func:`commit` is the single entry point: a :class:`CommitSpec` names the
backend and its knobs, and every backend returns the same
:class:`CommitResult` carrying MF success flags (the "did my transaction
win" bit routed back for FR messages) and conflict telemetry (the
abort-statistics analogue of paper Tables 3c/3f).  Backends that cannot
execute a request (e.g. ``pallas`` on vector payloads or unsupported
dtypes) fall back to ``coarse`` automatically.
"""
from __future__ import annotations

import dataclasses
import os

import jax
import jax.numpy as jnp

from repro.core.messages import Messages, make_messages
from repro.kernels import interpret_default

OPS = ("min", "max", "add", "or", "first")
BACKENDS = ("atomic", "coarse", "pallas", "fused")
AUTO = "auto"   # CommitSpec(backend="auto"): online-calibrated backend + M


def _identity(op: str, dtype):
    if op == "min":
        return jnp.array(jnp.iinfo(dtype).max if jnp.issubdtype(dtype, jnp.integer)
                         else jnp.inf, dtype)
    if op == "max":
        return jnp.array(jnp.iinfo(dtype).min if jnp.issubdtype(dtype, jnp.integer)
                         else -jnp.inf, dtype)
    if op == "add":
        return jnp.array(0, dtype)
    if op == "or":
        return jnp.array(False, bool)
    if op == "first":
        return jnp.array(-1, dtype)     # "empty slot" marker
    raise ValueError(op)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class CommitResult:
    state: jax.Array        # updated state array [V] (or [V, d])
    success: jax.Array      # bool [n] — MF: message won; AS: valid mask
    conflicts: jax.Array    # int32 — duplicate-target messages this batch
    applied: jax.Array      # int32 — messages that changed state


@dataclasses.dataclass(frozen=True)
class CommitSpec:
    """How to execute a commit — the mechanism, not the semantics.

    backend:   one of :data:`BACKENDS`, or ``"auto"`` — the
               :mod:`repro.core.autotune` tuner calibrates the §5.3 perf
               model at trace time (timed micro-commits of a synthetic
               workload sized to this call's batch) and picks the
               backend and transaction size M*; the kernel tiers
               (``pallas``/``fused``) fall back to ``coarse`` for
               payload shapes/dtypes the kernel does not support.
    m:         transaction size (messages per transaction); ``None`` = the
               whole batch is one transaction.
    sort:      coalesce by sorting messages by target before resolution
               (jnp tiers only; the kernel always resolves in-VMEM).
    stats:     compute full MF success flags + O(V) telemetry.  ``False``:
               the sorted jnp tiers keep cheap O(N) conflict/applied
               counters; the unsorted scatter path and the ``pallas``
               kernel (which then skips its in-kernel conflict reduction
               and extra output entirely) report zero conflicts.
    tile_m:    pallas transaction tile (used when ``m`` is None).
    block_v:   pallas state block resident in VMEM.
    interpret: force pallas interpret mode; ``None`` = off-TPU auto.
    seed_m:    warm-start hint for ``backend="auto"``: seed the
               conflict-feedback ladder at this transaction size instead
               of the calibrated M* (0 = whole batch).  Unlike ``m`` this
               does NOT pin the size — the ladder still adapts.  Restored
               services use it to re-enter at the learned level.
    sanitize:  shadow every commit with a permuted-message-order replay
               and assert the state is reorder-invariant (bit-identical;
               float ``add`` to documented rounding tolerance) — the
               runtime conflict sanitizer of :mod:`repro.analysis`.
               ``REPRO_SANITIZE=1`` in the environment turns it on
               globally without touching specs.  Mismatches raise
               :class:`repro.analysis.sanitize.SanitizeError` (surfaced
               as ``XlaRuntimeError`` under jit) and are recorded in
               :func:`repro.analysis.sanitize.reports`.
    trace:     stream per-commit telemetry (conflicts, applied, routed
               messages, ladder level) to the host through
               :mod:`repro.obs.wavetap` — an ``io_callback`` per commit
               inside the jitted loop.  ``REPRO_TRACE=1`` in the
               environment turns it on globally without touching specs;
               with both off the tap never enters the jaxpr
               (``aamlint --trace-off-clean`` proves it).

    Frozen + hashable so a spec can be a ``static_argnames`` entry of any
    jitted caller.
    """
    backend: str = "coarse"
    m: int | None = None
    sort: bool = True
    stats: bool = True
    tile_m: int = 256
    block_v: int = 512
    interpret: bool | None = None
    seed_m: int | None = None
    sanitize: bool = False
    trace: bool = False

    def __post_init__(self):
        if self.m is not None and self.m < 1:
            raise ValueError(f"transaction size m must be >= 1, got {self.m}")
        if self.seed_m is not None and self.seed_m < 0:
            raise ValueError(f"seed_m must be >= 0 (0 = whole batch), "
                             f"got {self.seed_m}")
        if self.tile_m < 1 or self.block_v < 1:
            raise ValueError(f"tile_m/block_v must be >= 1, got "
                             f"{self.tile_m}/{self.block_v}")


def commit(state: jax.Array, msgs: Messages, op: str,
           spec: CommitSpec | None = None) -> CommitResult:
    """Commit a batch of atomic active messages via ``spec.backend``.

    The single dispatch point for every mechanism tier — algorithm code
    names *what* (``op``) and the spec names *how*.  All backends agree on
    the final state for every op in :data:`OPS`; ``success`` masks agree
    whenever the whole batch is one transaction (``m=None`` — tiled
    commits may legitimately report one winner per tile, like back-to-back
    HTM transactions).
    """
    spec = spec if spec is not None else CommitSpec()
    if op not in OPS:
        raise ValueError(f"op {op!r} not in {OPS}")
    if spec.backend not in BACKENDS + (AUTO,):
        raise ValueError(f"backend {spec.backend!r} not in "
                         f"{BACKENDS + (AUTO,)}")
    if msgs.capacity == 0:
        z = jnp.zeros((), jnp.int32)
        return CommitResult(state, jnp.zeros((0,), bool), z, z)
    if spec.backend == AUTO:
        from repro.core.autotune import resolve_spec   # lazy: no cycle
        spec = resolve_spec(spec, state, msgs, op)
    backend = spec.backend
    if backend in ("pallas", "fused") and not _pallas_supported(state, msgs,
                                                                op):
        backend = "coarse"
    # the named scope marks every scatter/gather of the conflict-resolved
    # write path in traced jaxprs — repro.analysis.waverace keys its
    # in-wave-race rule on it (raw state writes OUTSIDE this scope are
    # unserialized and get flagged)
    with jax.named_scope("aam_commit"):
        res = _dispatch(state, msgs, op, spec, backend)
        if (spec.sanitize or _sanitize_env()) and msgs.capacity > 1:
            from repro.analysis.sanitize import shadow_check  # lazy: no cycle
            shadow_check(state, msgs, op, spec, backend, res.state)
    return res


def _sanitize_env() -> bool:
    return os.environ.get("REPRO_SANITIZE", "").lower() in (
        "1", "true", "on", "yes")


def _dispatch(state: jax.Array, msgs: Messages, op: str, spec: CommitSpec,
              backend: str) -> CommitResult:
    """Backend dispatch with fallback already resolved — shared by
    :func:`commit` and the sanitizer's shadow replay (which must NOT
    re-enter :func:`commit`, or the shadow would shadow itself)."""
    if backend == "atomic":
        return atomic_commit(state, msgs, op, stats=spec.stats)
    if backend == "coarse":
        return coarse_commit(state, msgs, op, m=spec.m, sort=spec.sort,
                             stats=spec.stats)
    if backend == "fused":
        return _fused_commit(state, msgs, op, spec)
    return _pallas_commit(state, msgs, op, spec)


def commit_batched(state: jax.Array, msgs: Messages, op: str,
                   spec: CommitSpec | None = None, *,
                   axis) -> CommitResult:
    """Commit an axis-fused batch against the axis's flat key space.

    ``axis`` is a batch axis (:class:`repro.core.coalescing.QueryLanes`,
    :class:`~repro.core.coalescing.GraphBatch`, or their composition
    :class:`~repro.core.coalescing.ProductAxis`); ``state`` is the
    flat [axis.flat_size] array and ``msgs.target`` carries flat keys
    (build them with :func:`repro.core.messages.batch_messages`), so
    ONE ``commit()`` call — any backend, including ``"auto"`` —
    resolves conflicts for every batch item at once.  Items occupy
    disjoint key ranges, so the result equals the looped per-item
    commits (bit-for-bit for the order-independent ops; float ``add``
    to rounding, exactly like any transaction-size change)."""
    if state.shape[0] != axis.flat_size:
        raise ValueError(f"state leading dim {state.shape[0]} != "
                         f"axis flat size {axis.flat_size}")
    return commit(state, msgs, op, spec)


def commit_lanes(state: jax.Array, msgs: Messages, op: str,
                 spec: CommitSpec | None = None) -> CommitResult:
    """Thin wrapper over :func:`commit_batched` for the query-lane axis:
    commit a lane-fused batch against [L, V] lane-major state (composite
    keys ``lane * V + v`` from :func:`repro.core.messages.lane_messages`).
    """
    from repro.core.coalescing import QueryLanes
    lanes, v = state.shape
    res = commit_batched(state.reshape(lanes * v), msgs, op, spec,
                         axis=QueryLanes(lanes, v))
    return dataclasses.replace(res, state=res.state.reshape(lanes, v))


def commit_product(state: jax.Array, msgs: Messages, op: str,
                   spec: CommitSpec | None = None, *,
                   axis) -> CommitResult:
    """Thin wrapper over :func:`commit_batched` for the lanes×graphs
    product axis: commit a product-fused batch against [L, Vtot]
    lane-major union state (composite keys ``lane * Vtot + flat`` from
    :func:`repro.core.messages.product_messages`); ``axis`` is the
    :class:`repro.core.coalescing.ProductAxis`."""
    lanes, vtot = state.shape
    if (lanes, vtot) != (axis.lanes, axis.num_vertices):
        raise ValueError(f"state shape {state.shape} != product axis "
                         f"({axis.lanes}, {axis.num_vertices})")
    res = commit_batched(state.reshape(lanes * vtot), msgs, op, spec,
                         axis=axis)
    return dataclasses.replace(res, state=res.state.reshape(lanes, vtot))


_PALLAS_DTYPES = (jnp.int32, jnp.float32)


def _pallas_supported(state, msgs: Messages, op: str) -> bool:
    payload = msgs.payload
    return (isinstance(payload, jax.Array) and payload.ndim == 1
            and state.ndim == 1
            and state.dtype in _PALLAS_DTYPES
            and payload.dtype in _PALLAS_DTYPES)


def _pallas_commit(state, msgs: Messages, op: str,
                   spec: CommitSpec) -> CommitResult:
    from repro.kernels.coarse_commit import coarse_commit_pallas
    idx = jnp.where(msgs.valid, msgs.target, -1).astype(jnp.int32)
    interpret = interpret_default(spec.interpret)
    tile_m = spec.m if spec.m is not None else spec.tile_m
    if not spec.stats:
        # cheap path: the kernel skips the per-block conflict reduction
        # and its extra output entirely
        new = coarse_commit_pallas(
            state, idx, msgs.payload, op=op, tile_m=tile_m,
            block_v=spec.block_v, interpret=interpret, stats=False)
        z = jnp.zeros((), jnp.int32)
        return CommitResult(new, msgs.valid, z, z)
    new, conflicts = coarse_commit_pallas(
        state, idx, msgs.payload, op=op, tile_m=tile_m,
        block_v=spec.block_v, interpret=interpret, stats=True)
    if op == "first":
        success, _, applied = _first_stats(state, msgs)
    else:
        success, _, applied = _success_stats(state, new, msgs, op)
    return CommitResult(new, success, conflicts, applied)


def _fused_commit(state, msgs: Messages, op: str,
                  spec: CommitSpec) -> CommitResult:
    """Generic-entry fused tier: plain local targets, no base/lane —
    the kernel's key computation folds away and this is launch-for-launch
    the pallas tier (the parity matrix and the tuner race treat it as
    such); the engine's :func:`fused_commit_site` is the fast path."""
    from repro.kernels.fused_wave import fused_route_commit_pallas
    idx = jnp.where(msgs.valid, msgs.target, -1).astype(jnp.int32)
    interpret = interpret_default(spec.interpret)
    tile_m = spec.m if spec.m is not None else spec.tile_m
    if not spec.stats:
        new = fused_route_commit_pallas(
            state, idx, msgs.payload, op=op, tile_m=tile_m,
            block_v=spec.block_v, interpret=interpret, stats=False)
        z = jnp.zeros((), jnp.int32)
        return CommitResult(new, msgs.valid, z, z)
    new, conflicts = fused_route_commit_pallas(
        state, idx, msgs.payload, op=op, tile_m=tile_m,
        block_v=spec.block_v, interpret=interpret, stats=True)
    if op == "first":
        success, _, applied = _first_stats(state, msgs)
    else:
        success, _, applied = _success_stats(state, new, msgs, op)
    return CommitResult(new, success, conflicts, applied)


def fused_site_supported(state, payload) -> bool:
    """Kernel envelope of the engine's fused fast path: 1-D int32/float32
    state slice, scalar-per-message payload leaf (flat [n] or the [P, C]
    exchanged buffer).  Vector payloads / other dtypes take the unfused
    per-leaf fallback in :func:`repro.core.engine.route_wave`."""
    return (isinstance(payload, jax.Array)
            and getattr(state, "ndim", 0) == 1
            and payload.ndim <= 2
            and state.dtype in _PALLAS_DTYPES
            and payload.dtype in _PALLAS_DTYPES)


def fused_commit_site(state, tgt, payload, op: str, spec: CommitSpec, *,
                      lane=None, base=None, width: int = 1) -> CommitResult:
    """Owner-side fused route+commit — THE commit site of the engine's
    fused fast path (:func:`repro.core.engine.route_wave`).

    ``tgt``/``payload``/``lane`` are the flattened post-exchange bucket
    buffers exactly as the all_to_all left them (``tgt`` global ids with
    ``-1`` empty-slot sentinels); ``base`` is the owner's first global
    vertex id (``shard * block``, traced) and ``width`` the batch-axis
    wave width.  One kernel launch computes local composite keys,
    reorders in VMEM, and commits — the ``local_idx``/``fuse_keys``/
    ``make_messages`` jnp intermediates never materialize.

    ``stats=False`` (the hot path) reports ``success = slot occupied``
    like every backend's cheap mode; ``stats=True`` reconstructs the
    local keys jnp-side ONLY for the MF success/applied accounting (the
    committed state still comes from the single launch).

    Runs under ``jax.named_scope("aam_commit")`` — the aamlint waverace
    pass recognizes in-scope ``pallas_call`` writes as the protected
    commit site and flags out-of-scope kernel writes.
    """
    interpret = interpret_default(spec.interpret)
    tile_m = spec.m if spec.m is not None else spec.tile_m
    kw = dict(lane=lane, base=base, width=width, op=op, tile_m=tile_m,
              block_v=spec.block_v, interpret=interpret)
    from repro.kernels.fused_wave import fused_route_commit_pallas
    with jax.named_scope("aam_commit"):
        if not spec.stats:
            new = fused_route_commit_pallas(state, tgt, payload,
                                            stats=False, **kw)
            z = jnp.zeros((), jnp.int32)
            return CommitResult(new, tgt >= 0, z, z)
        new, conflicts = fused_route_commit_pallas(state, tgt, payload,
                                                   stats=True, **kw)
        nrows = state.shape[0] // width
        rel = tgt - (0 if base is None else base)
        ok = (tgt >= 0) & (rel >= 0) & (rel < nrows)   # mirror the kernel
        local = jnp.where(ok, rel, 0)
        if width > 1:
            ok = ok & (lane >= 0) & (lane < width)
            local = local * width + jnp.where(ok, lane, 0)
        msgs = make_messages(local.astype(jnp.int32), payload, ok)
        if op == "first":
            success, _, applied = _first_stats(state, msgs)
        else:
            success, _, applied = _success_stats(state, new, msgs, op)
        return CommitResult(new, success, conflicts, applied)


# ---------------------------------------------------------------------------
# Tier 1: fine-grained baseline (per-message scatter = atomics analogue)
# ---------------------------------------------------------------------------


def atomic_commit(state: jax.Array, msgs: Messages, op: str,
                  stats: bool = True) -> CommitResult:
    """One scatter element per message; conflicts resolved by scatter
    semantics (the TPU analogue of a CAS/FAO per vertex)."""
    idx = jnp.where(msgs.valid, msgs.target, state.shape[0])  # OOB -> dropped
    val = msgs.payload
    old = state
    mode = jax.lax.GatherScatterMode.FILL_OR_DROP
    if op == "min":
        new = state.at[idx].min(val, mode=mode)
    elif op == "max":
        new = state.at[idx].max(val, mode=mode)
    elif op == "add":
        new = state.at[idx].add(jnp.where(
            _bcast(msgs.valid, val), val, jnp.zeros_like(val)), mode=mode)
    elif op == "or":
        # payload is a truth value: all tiers agree on max(state, val != 0)
        new = state.at[idx].max((val != 0).astype(state.dtype), mode=mode)
    elif op == "first":
        # first-writer-wins on empty slots (id -1 = empty), ties -> min msg id
        return _first_commit(state, msgs)
    else:
        raise ValueError(op)
    if not stats:
        z = jnp.zeros((), jnp.int32)
        return CommitResult(new, msgs.valid, z, z)
    success, conflicts, applied = _success_stats(old, new, msgs, op)
    return CommitResult(new, success, conflicts, applied)


def _bcast(mask, val):
    return mask.reshape(mask.shape + (1,) * (val.ndim - mask.ndim))


# ---------------------------------------------------------------------------
# Tier 2: coarse transactions (sort + in-tile conflict resolution)
# ---------------------------------------------------------------------------


def coarse_commit(state: jax.Array, msgs: Messages, op: str,
                  m: int | None = None, sort: bool = True,
                  stats: bool = True) -> CommitResult:
    """AAM coarse commit.

    Conflict resolution happens *before* touching state: duplicate targets
    inside the batch are reduced to one update per distinct target (sort by
    target + segment reduce), then committed with one conflict-free scatter.
    ``m`` is the transaction size — the batch is processed in ceil(n/m)
    tiles via ``lax.map`` (each tile = one "transaction"; the Pallas kernel
    executes one tile per grid step).  ``sort=False`` models uncoalesced
    message streams (pure in-tile resolution, cross-tile conflicts still hit
    the scatter path) — the benchmark knob for paper Fig 4.
    """
    n = msgs.capacity
    if m is None or m >= n:
        return _resolved_commit(state, msgs, op, sort=sort, stats=stats)

    pad = (-n) % m
    msgs_p = jax.tree.map(
        lambda x: jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1)), msgs)
    msgs_p = dataclasses.replace(
        msgs_p, valid=jnp.pad(msgs.valid, (0, pad), constant_values=False))
    tiles = jax.tree.map(
        lambda x: x.reshape((n + pad) // m, m) if x.ndim == 1
        else x.reshape(((n + pad) // m, m) + x.shape[1:]), msgs_p)

    def tx(state, tile):
        r = _resolved_commit(state, tile, op, sort=sort, stats=stats)
        return r.state, (r.success, r.conflicts, r.applied)

    new_state, (succ, conf, app) = jax.lax.scan(tx, state, tiles)
    succ = succ.reshape(-1)[:n]
    return CommitResult(new_state, succ, jnp.sum(conf), jnp.sum(app))


def _resolved_commit(state, msgs: Messages, op: str, sort: bool,
                     stats: bool = True) -> CommitResult:
    """One transaction: resolve in-batch conflicts, then write state.

    sorted path (coalesced AAM): sort by target, reduce each run of equal
    targets with a sorted segment reduction over run ids (O(N log N), no
    O(V) buffers — the jnp mirror of the Pallas kernel's in-VMEM
    resolution), then ONE conflict-free scatter (unique targets).  A
    segmented associative scan is the textbook form of the run reduction,
    but its TPU compile time roughly triples per doubling of the batch
    (minutes at 2^20 messages), so it is not used.
    unsorted path: the uncoalesced stream — duplicates go straight to the
    scatter and conflicts serialize in the memory system (atomics-like).
    ``stats=False`` skips the O(V) success accounting and reports cheap
    O(N) conflict/applied counts (success == valid placeholder).
    """
    v = state.shape[0]
    idx = jnp.where(msgs.valid, msgs.target, v)
    if op == "first":
        return _first_commit(state, msgs)
    val = msgs.payload
    old = state
    mode = jax.lax.GatherScatterMode.FILL_OR_DROP

    if not sort:
        return atomic_commit(state, msgs, op, stats=stats)

    order = jnp.argsort(idx, stable=True)          # coalescing: sort by target
    s_idx = idx[order]
    s_val = val[order]
    s_valid = msgs.valid[order]

    if op == "add":
        s_val = jnp.where(_bcast(s_valid, s_val), s_val,
                          jnp.zeros_like(s_val))
    elif op == "or":
        s_val = (s_valid & s_val.astype(bool))

    # reduce each sorted run of equal target; every run member reads its
    # run's total (only the `last` member's is written)
    first = jnp.concatenate([jnp.ones((1,), bool), s_idx[1:] != s_idx[:-1]])
    run = jnp.cumsum(first.astype(jnp.int32)) - 1
    seg = {"min": jax.ops.segment_min, "max": jax.ops.segment_max,
           "add": jax.ops.segment_sum, "or": jax.ops.segment_max}[op]
    totals = seg(s_val.astype(jnp.int32) if op == "or" else s_val, run,
                 num_segments=run.shape[0], indices_are_sorted=True)
    scanned = totals[run]
    last = jnp.concatenate([first[1:], jnp.ones((1,), bool)])
    # one conflict-free write per distinct target (run reductions at `last`)
    w_idx = jnp.where(last, s_idx, v)
    if op == "add":
        new = state.at[w_idx].add(scanned.astype(state.dtype), mode=mode)
    elif op == "min":
        new = state.at[w_idx].min(scanned.astype(state.dtype), mode=mode)
    elif op == "max":
        new = state.at[w_idx].max(scanned.astype(state.dtype), mode=mode)
    else:  # or
        new = state.at[w_idx].max(scanned.astype(state.dtype), mode=mode)
    if stats:
        success, conflicts, applied = _success_stats(old, new, msgs, op)
    else:
        n_valid = jnp.sum(s_valid.astype(jnp.int32))
        n_runs = jnp.sum((first & s_valid).astype(jnp.int32))
        conflicts = n_valid - n_runs
        changed = new[jnp.clip(s_idx, 0, v - 1)] != old[jnp.clip(s_idx, 0, v - 1)]
        if changed.ndim > 1:    # vector payload: any component changed
            changed = jnp.any(changed, axis=tuple(range(1, changed.ndim)))
        applied = jnp.sum((last & s_valid & changed).astype(jnp.int32))
        success = msgs.valid
    return CommitResult(new, success, conflicts, applied)


def _first_winner(state, msgs: Messages, rank=None):
    """(winner_rank [V], takes [V]) for first-writer-wins into empty (-1)
    slots; in-batch ties -> lowest message index.

    ``rank`` overrides the per-message tiebreak key (default: position in
    the batch).  The sanitizer's permuted-order shadow replay passes the
    original indices here so the winner is order-independent."""
    v = state.shape[0]
    n = msgs.capacity
    idx = jnp.where(msgs.valid, msgs.target, v)
    msg_rank = (jnp.arange(n, dtype=jnp.int32) if rank is None
                else jnp.asarray(rank, jnp.int32))
    winner_rank = jax.ops.segment_min(msg_rank, idx, num_segments=v + 1)[:v]
    takes = (state < 0) & (winner_rank < n)
    return winner_rank, takes


def _first_stats(state, msgs: Messages):
    """(success, conflicts, applied) of a whole-batch 'first' commit
    against the pre-commit ``state``."""
    v = state.shape[0]
    winner_rank, takes = _first_winner(state, msgs)
    tgt = jnp.clip(msgs.target, 0, v - 1)
    msg_rank = jnp.arange(msgs.capacity, dtype=jnp.int32)
    success = msgs.valid & (msg_rank == winner_rank[tgt]) & (state < 0)[tgt]
    conflicts = jnp.sum(msgs.valid) - jnp.sum(takes)
    return success, conflicts.astype(jnp.int32), \
        jnp.sum(takes).astype(jnp.int32)


def _first_commit(state, msgs: Messages) -> CommitResult:
    """First-writer-wins into empty (-1) slots; in-batch ties -> lowest
    message index (the paper's 'one of them succeeds')."""
    n = msgs.capacity
    winner_rank, takes = _first_winner(state, msgs)
    winner_val = jnp.where(
        takes, msgs.payload[jnp.clip(winner_rank, 0, n - 1)], state)
    new = jnp.where(takes, winner_val, state)
    success, conflicts, applied = _first_stats(state, msgs)
    return CommitResult(new, success, conflicts, applied)


def _success_stats(old, new, msgs: Messages, op: str):
    n = msgs.capacity
    v = old.shape[0]
    tgt = jnp.clip(msgs.target, 0, v - 1)
    if op == "add":
        success = msgs.valid
        applied = jnp.sum(msgs.valid)
    elif op == "or":
        success = msgs.valid & ~old[tgt].astype(bool)
        applied = jnp.sum((new != old).astype(jnp.int32))
    else:  # min/max — MF: message wins iff it set the final value
        val = msgs.payload
        final = new[tgt]
        improved = (val == final) & (final != old[tgt]) & msgs.valid
        # first among equal winners
        msg_rank = jnp.arange(n, dtype=jnp.int32)
        rank_key = jnp.where(improved, msg_rank, n)
        idx = jnp.where(improved, msgs.target, v)
        first_rank = jax.ops.segment_min(rank_key, idx, num_segments=v + 1)[:v]
        success = improved & (msg_rank == first_rank[tgt])
        applied = jnp.sum((new != old).astype(jnp.int32))
    # conflicts = valid messages sharing a target with another message
    idx = jnp.where(msgs.valid, msgs.target, v)
    counts = jax.ops.segment_sum(jnp.ones((n,), jnp.int32), idx,
                                 num_segments=v + 1)[:v]
    conflicts = jnp.sum(jnp.where(msgs.valid & (counts[tgt] > 1), 1, 0))
    return success, conflicts.astype(jnp.int32), applied.astype(jnp.int32)
