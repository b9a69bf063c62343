"""Distributed AAM engine — shard_map execution of atomic active messages.

Vertices are 1-D partitioned into contiguous owner ranges (paper §3.1); each
shard holds its vertex state slice and the edges whose source it owns.  One
*wave* = route all pending messages to their owners and commit:

  1. bucket messages per destination shard (coalescing, capacity C);
  2. one ``all_to_all`` exchanges the coalesced [P, C] buffers;
  3. owners run the commit (transactions of size M, any backend);
  4. (FR) success flags return to spawners by the reverse ``all_to_all``.

Messages beyond C stay *pending* and go in the next sub-round — the
coalescing factor literally is the paper's C: fewer, larger network
messages, amortized per-message overhead (§5.6).

The public surface is the *harness*: :func:`run_distributed` executes an
:class:`AlgorithmSpec` — an ``init`` hook producing sharded state and a
``round_fn`` hook emitting one round of messages through a
:class:`WaveRuntime` — and owns partitioning, the round loop, the FR return
path, and conflict/sub-round telemetry.  All six paper case-studies
(`repro.graphs.algorithms`) are instances; ``distributed_bfs`` and
``distributed_pagerank`` re-export from their algorithm modules.

Payloads are *pytrees*: a routed message may carry several fields (e.g.
SSSP's f32 distances next to i32 targets, ST-connectivity's two frontier
bits) through one bucket plan and one exchange per field.

.. deprecated::
   Calling :func:`route_wave` directly is deprecated — it is a single
   sub-round with no requeue of coalescing overflow and no delivery
   guarantee.  Go through :func:`run_distributed` (algorithms) or
   :func:`wave_until_delivered` (custom protocols, e.g.
   `repro.core.ownership`) instead.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.core import autotune as AT
from repro.core import commit as C
from repro.obs import trace as OT
from repro.core.coalescing import (BucketPlan, fuse_keys,
                                   gather_from_buckets, plan_buckets_sorted,
                                   require_key_space, scatter_to_buckets)
from repro.core.messages import make_messages


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    num_shards: int
    block: int              # vertices per shard
    capacity: int           # coalescing factor C (messages per dest/round)
    axis: str = "data"
    m: int | None = None    # transaction size (None = whole batch)
    op: str = "min"
    spec: C.CommitSpec | None = None   # commit backend; None = coarse(m)
    tuner: AT.TunerPolicy | None = None  # set by run_distributed for "auto"
    batch: Any = None       # default BatchAxis for waves (QueryLanes /
    #                         GraphBatch; None = unbatched targets)

    @property
    def commit_spec(self) -> C.CommitSpec:
        if self.spec is not None:
            return self.spec
        return C.CommitSpec(backend="coarse", m=self.m)

    def _commit(self, state, msgs, level=None):
        """Owner-side commit: calibrated ladder when a tuner policy is
        bound (``backend="auto"``), the static spec otherwise."""
        if self.tuner is not None and level is not None:
            return AT.ladder_commit(state, msgs, self.op, self.tuner, level)
        return C.commit(state, msgs, self.op, self.commit_spec)


def _tree_all_to_all(x, axis: str):
    return jax.tree.map(
        lambda a: jax.lax.all_to_all(a, axis, 0, 0, tiled=True), x)


def _fused_commit_leaf(ecfg: EngineConfig, st, tgt, payload, lane, base,
                       width, level):
    """Owner-side fused route+commit for one state/payload leaf —
    calibrated ladder when a tuner policy is bound (``backend="auto"``
    raced to the fused tier), the static spec otherwise."""
    if ecfg.tuner is not None:
        return AT.ladder_fused_site(st, tgt, payload, ecfg.op, ecfg.tuner,
                                    level, lane=lane, base=base,
                                    width=width)
    return C.fused_commit_site(st, tgt, payload, ecfg.op, ecfg.commit_spec,
                               lane=lane, base=base, width=width)


def route_wave(ecfg: EngineConfig, state_l, target, payload, pending,
               level=None, major=None, batch=None):
    """One coalescing sub-round under shard_map (DEPRECATED for direct use —
    see module docstring; overflow beyond C is NOT requeued here).

    state_l: pytree of [block] local owner slices; payload: matching pytree
    of [n] fields; target: [n] GLOBAL vertex ids; pending: [n] bool;
    level: traced ladder index for an ``ecfg.tuner`` adaptive commit.
    major/batch: the batch axis — ``batch`` is a
    :class:`repro.core.coalescing.QueryLanes`/``GraphBatch`` and
    ``major`` [n] int32 per-message item ids.  When
    ``batch.wave_width > 1`` (query lanes) the ids ride the exchange as
    one more payload field, state leaves are vertex-major
    [block * width] slices, and owners commit on composite local keys
    ``local_v * width + major`` so ONE commit resolves every item's
    conflicts (see ``repro.core.coalescing.fuse_keys``).  A
    ``GraphBatch`` has ``wave_width == 1`` — its targets are already
    flat union-graph ids, so owner slices and coalescing buckets are
    keyed by flat id with no extra field.  A
    :class:`~repro.core.coalescing.ProductAxis` composes both: targets
    are union-flat ids (graph coordinate pre-folded, so buckets/owners
    need nothing new) while the LANE id rides as ``major`` —
    ``wave_width == lanes`` and one commit resolves every
    (lane, graph) cell.
    Returns (state_l, delivered_mask, success pytree, conflicts)."""
    P, Cp = ecfg.num_shards, ecfg.capacity
    batch = batch if batch is not None else ecfg.batch
    width = batch.wave_width if batch is not None else 1
    if width > 1:   # block/width are static: a trace-time guard is free
        require_key_space(ecfg.block * width,
                          where="route_wave(block * wave_width)")
    owner = target // ecfg.block
    plan, _ = plan_buckets_sorted(owner, pending, P, Cp)
    kept = plan.kept
    # sentinel -1 marks empty slots through the exchange
    buf_t = scatter_to_buckets(plan, jnp.where(kept, target, -1), P, Cp,
                               fill=-1)
    buf_p = scatter_to_buckets(plan, payload, P, Cp, fill=0)
    rt = jax.lax.all_to_all(buf_t, ecfg.axis, 0, 0, tiled=True)
    rp = _tree_all_to_all(buf_p, ecfg.axis)
    # local commit at the owner, one per (state, payload) field pair
    shard = jax.lax.axis_index(ecfg.axis)
    rt_flat = rt.reshape(-1)
    rl_flat = None
    if width > 1:
        if major is None:
            raise ValueError("batch axis with wave_width > 1 needs "
                             "per-message `major` item ids")
        buf_l = scatter_to_buckets(plan, major, P, Cp, fill=0)
        rl = jax.lax.all_to_all(buf_l, ecfg.axis, 0, 0, tiled=True)
        rl_flat = rl.reshape(-1)
    valid = (rt_flat >= 0)
    st_leaves, tdef = jax.tree_util.tree_flatten(state_l)
    pl_leaves = tdef.flatten_up_to(rp)
    # fused fast path (backend="fused", static or tuner-raced): the
    # exchanged buffers go STRAIGHT into one kernel launch that computes
    # local composite keys, reorders in VMEM, and commits — the
    # local_idx/fuse_keys/make_messages intermediates below never
    # materialize.  Per-leaf: leaves outside the kernel envelope (vector
    # payloads, non-int32/f32 dtypes) take the unfused path.
    backend = (ecfg.tuner.backend if ecfg.tuner is not None
               else ecfg.commit_spec.backend)
    fused = [backend == "fused" and C.fused_site_supported(st, p)
             for st, p in zip(st_leaves, pl_leaves)]
    local_idx = None
    if not all(fused):
        local_idx = jnp.clip(rt_flat - shard * ecfg.block, 0,
                             ecfg.block - 1)
        if width > 1:
            local_idx = fuse_keys(
                local_idx, jnp.clip(rl_flat, 0, width - 1), width)
    new_st, succs = [], []
    conflicts = jnp.zeros((), jnp.int32)
    for i, (st, pl) in enumerate(zip(st_leaves, pl_leaves)):
        if fused[i]:
            res = _fused_commit_leaf(ecfg, st, rt_flat, pl.reshape(-1),
                                     rl_flat, shard * ecfg.block, width,
                                     level)
        else:
            res = ecfg._commit(st, make_messages(local_idx, pl.reshape(-1),
                                                 valid), level)
        new_st.append(res.state)
        if i == 0:
            # slot collisions depend on (target, valid) only, which every
            # payload field shares — count conflicts once per routed
            # message, not once per field
            conflicts = res.conflicts
        succs.append(res.success)
    # FR return path: ONE reverse exchange carries every field's flags
    back = jax.lax.all_to_all(
        jnp.stack(succs, axis=-1).reshape(P, Cp, len(succs)),
        ecfg.axis, 0, 0, tiled=True)
    succ = tdef.unflatten(
        [gather_from_buckets(back[..., i], plan, Cp, fill=False)
         for i in range(len(succs))])
    return tdef.unflatten(new_st), kept, succ, conflicts


def wave_until_delivered(ecfg: EngineConfig, state_l, target, payload,
                         valid, max_subrounds: int = 64, level=None,
                         major=None, batch=None):
    """Deliver ALL messages (sub-rounds until nothing pending).

    Returns (state_l, success pytree, conflicts, subrounds, delivered_all).
    ``delivered_all`` is False when ``max_subrounds`` was exhausted with
    messages still pending — callers MUST surface it instead of silently
    dropping the tail (the capacity-C requeue loop normally terminates for
    any C >= 1: each sub-round delivers up to C messages per owner).
    ``level`` is the (constant-per-wave) adaptive-ladder index when
    ``ecfg.tuner`` is set; ``major``/``batch`` thread the batch axis
    through every sub-round (see :func:`route_wave`)."""
    n = target.shape[0]
    st_leaves, tdef = jax.tree_util.tree_flatten(state_l)
    succ0 = tdef.unflatten([jnp.zeros((n,), bool) for _ in st_leaves])

    def cond(c):
        _, pending, _, _, it = c
        return (jax.lax.psum(jnp.sum(pending.astype(jnp.int32)), ecfg.axis)
                > 0) & (it < max_subrounds)

    def body(c):
        state_l, pending, success, conflicts, it = c
        state_l, kept, succ, cf = route_wave(ecfg, state_l, target, payload,
                                             pending, level, major, batch)
        success = jax.tree.map(lambda sn, so: jnp.where(kept, sn, so),
                               succ, success)
        return (state_l, pending & ~kept, success, conflicts + cf, it + 1)

    state_l, pending, success, conflicts, subrounds = jax.lax.while_loop(
        cond, body, (state_l, valid, succ0,
                     jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32)))
    delivered_all = (jax.lax.psum(jnp.sum(pending.astype(jnp.int32)),
                                  ecfg.axis) == 0)
    # commits run at the owners: the Tables-3c/3f conflict total is the
    # sum over shards (replicated, so Ps() out-specs stay consistent)
    conflicts = jax.lax.psum(conflicts, ecfg.axis)
    return state_l, success, conflicts, subrounds, delivered_all


def route_messages(ecfg: EngineConfig, target, payload, valid):
    """Route one sub-round of messages to owners WITHOUT committing —
    callers implement custom owner-side handlers (ownership protocol,
    pointer-jumping reads).  ``payload`` may be a pytree of [n] fields, or
    ``None`` for pure read requests (skips the payload exchange).

    Returns (local_idx [P*C], payload pytree of [P*C] or None,
    rvalid [P*C], plan, kept)."""
    P, Cp = ecfg.num_shards, ecfg.capacity
    owner = target // ecfg.block
    plan, _ = plan_buckets_sorted(owner, valid, P, Cp)
    kept = plan.kept
    buf_t = scatter_to_buckets(plan, jnp.where(kept, target, -1), P, Cp,
                               fill=-1)
    rt = jax.lax.all_to_all(buf_t, ecfg.axis, 0, 0, tiled=True)
    if payload is None:
        rp_flat = None
    else:
        buf_p = scatter_to_buckets(plan, payload, P, Cp, fill=0)
        rp = _tree_all_to_all(buf_p, ecfg.axis)
        rp_flat = jax.tree.map(lambda b: b.reshape(-1), rp)
    shard = jax.lax.axis_index(ecfg.axis)
    local_idx = rt.reshape(-1) - shard * ecfg.block
    rvalid = rt.reshape(-1) >= 0
    return local_idx, rp_flat, rvalid, plan, kept


def return_to_spawners(ecfg: EngineConfig, reply, plan: BucketPlan, fill=0):
    """Reverse all_to_all of per-slot replies (FR return path).  ``reply``
    may be a pytree of [P*C] fields; unkept slots read as ``fill``."""
    P, Cp = ecfg.num_shards, ecfg.capacity
    back = _tree_all_to_all(
        jax.tree.map(lambda r: r.reshape(P, Cp), reply), ecfg.axis)
    return gather_from_buckets(back, plan, Cp, fill=fill)


def gather_until_answered(ecfg: EngineConfig, arr_l, idx, valid, fill=0,
                          max_subrounds: int = 64):
    """Remote gather: read the distributed array ``arr_l`` (pytree of
    [block] owner slices) at GLOBAL indices ``idx`` [n], requeueing
    coalescing overflow until every valid request is answered.  This is the
    FR read path (``route_messages`` + owner lookup + ``return_to_spawners``)
    — the ownership-protocol building block Boruvka's pointer-jumping uses.

    Returns (values pytree of [n] — ``fill`` where ~valid, subrounds,
    delivered_all)."""
    n = idx.shape[0]
    leaves, tdef = jax.tree_util.tree_flatten(arr_l)
    out0 = tdef.unflatten([jnp.full((n,), fill, a.dtype) for a in leaves])

    def cond(c):
        _, pending, it = c
        return (jax.lax.psum(jnp.sum(pending.astype(jnp.int32)), ecfg.axis)
                > 0) & (it < max_subrounds)

    def body(c):
        out, pending, it = c
        local_idx, _, rvalid, plan, kept = route_messages(
            ecfg, idx, None, pending)
        lidx = jnp.clip(local_idx, 0, ecfg.block - 1)
        reply = jax.tree.map(
            lambda a: jnp.where(rvalid, a[lidx], jnp.asarray(fill, a.dtype)),
            arr_l)
        back = return_to_spawners(ecfg, reply, plan, fill=fill)
        out = jax.tree.map(lambda o, b: jnp.where(kept, b, o), out, back)
        return out, pending & ~kept, it + 1

    out, pending, subrounds = jax.lax.while_loop(
        cond, body, (out0, valid, jnp.zeros((), jnp.int32)))
    delivered_all = (jax.lax.psum(jnp.sum(pending.astype(jnp.int32)),
                                  ecfg.axis) == 0)
    return out, subrounds, delivered_all


# ---------------------------------------------------------------------------
# Coalescing-capacity auto-sizing (paper §5.6)
# ---------------------------------------------------------------------------

# ``capacity="auto"``: C starts from the average per-shard inbound load and
# then a process-level feedback cache grows it for the NEXT run whenever a
# run's waves persistently overflowed (sub-rounds per round above
# OVERFLOW_RATIO means messages kept getting requeued past C) — the same
# measure-then-adapt loop the autotuner closes for backend/M.  C never
# exceeds the largest shard's edge count (rounded up to a power of two):
# one wave of one-message-per-edge traffic then always fits a sub-round,
# so a round delivers everything within ``max_subrounds`` at any scale.
CAPACITY_MIN = 64
OVERFLOW_RATIO = 2.0
_CAPACITY_CACHE: dict = {}


def _pow2_at_least(n: int) -> int:
    return 1 << (max(int(n), 1) - 1).bit_length()


def capacity_cap(shard_edges: int) -> int:
    """Largest C ``capacity="auto"`` picks for shards of ``shard_edges``
    edges."""
    return max(CAPACITY_MIN, _pow2_at_least(shard_edges))


def auto_capacity(g, num_shards: int, shard_edges: int) -> int:
    """Current C for (graph shape, shard count): the cached feedback value
    when a previous run reported overflow, the static heuristic otherwise
    (power of two ~2x the average per-shard inbound load, clamped to
    :func:`capacity_cap`).  ``shard_edges`` is the fullest shard's edge
    count (the padded width of :func:`repro.graphs.csr.partition_edges`)."""
    key = (g.num_vertices, g.num_edges, num_shards)
    hit = _CAPACITY_CACHE.get(key)
    if hit is not None:
        return hit
    per_shard = max(1, (2 * g.num_edges) // max(num_shards, 1))
    return min(max(CAPACITY_MIN, _pow2_at_least(per_shard)),
               capacity_cap(shard_edges))


def _capacity_feedback(g, num_shards: int, capacity: int,
                       subrounds: int, rounds: int, cap: int) -> None:
    """Grow the cached C (up to ``cap``) when waves persistently
    overflowed this run.

    Algorithms issuing several waves per round (Boruvka) inflate the
    sub-round count without real overflow; the growth is monotone and
    capped, so a spurious doubling costs padding, never correctness."""
    if subrounds > OVERFLOW_RATIO * max(rounds, 1) and capacity < cap:
        _CAPACITY_CACHE[(g.num_vertices, g.num_edges, num_shards)] = \
            min(capacity * 2, cap)


# ---------------------------------------------------------------------------
# The distributed-algorithm harness
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ShardLayout:
    """Static shapes of one distributed run (1-D partition, paper §3.1)."""
    num_shards: int
    block: int          # vertices per shard (padded)
    emax: int           # edges per shard (padded)
    num_vertices: int
    num_edges: int

    @property
    def vpad(self) -> int:
        return self.num_shards * self.block


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class EdgeSlice:
    """One shard's edge slice (sources owned locally, padded to emax)."""
    src: jax.Array      # int32 [emax] GLOBAL source ids
    dst: jax.Array      # int32 [emax] GLOBAL destination ids
    weight: jax.Array   # float32 [emax]
    valid: jax.Array    # bool [emax]
    eid: jax.Array      # int32 [emax] ORIGINAL edge ids (tie-breaking)
    my_src: jax.Array   # int32 [emax] local row of src (clipped to block)


class WaveRuntime:
    """Per-round handle the harness passes to ``round_fn``.

    Wraps the wave primitives with an :class:`EngineConfig` bound to the
    run and accumulates telemetry (conflicts, sub-rounds, delivery flag)
    across every wave/gather the round issues.  Do NOT call its methods
    from inside ``lax.scan``/``lax.while_loop`` bodies of the round — the
    accumulators are trace-level.
    """

    def __init__(self, ecfg: EngineConfig, layout: ShardLayout,
                 max_subrounds: int, level=None):
        self.ecfg = ecfg
        self.layout = layout
        self.max_subrounds = max_subrounds
        self.level = level          # adaptive-ladder index (traced int32)
        self.conflicts = jnp.zeros((), jnp.int32)
        self.subrounds = jnp.zeros((), jnp.int32)
        self.messages = jnp.zeros((), jnp.int32)   # routed msgs this round
        self.delivered_all = jnp.ones((), bool)

    @property
    def shard(self) -> jax.Array:
        return jax.lax.axis_index(self.ecfg.axis)

    @property
    def gid(self) -> jax.Array:
        """GLOBAL vertex ids of the local block."""
        return self.shard * self.ecfg.block + jnp.arange(
            self.ecfg.block, dtype=jnp.int32)

    def psum(self, x):
        return jax.lax.psum(x, self.ecfg.axis)

    def any(self, mask) -> jax.Array:
        """Global any() over a per-shard bool array."""
        return self.psum(jnp.sum(mask.astype(jnp.int32))) > 0

    def wave(self, state_l, target, payload, valid, *, op: str,
             major=None, batch=None):
        """Deliver + commit messages ``(target, payload)`` with ``op``;
        returns (state_l, success pytree).  state_l/payload are matching
        pytrees of [block]/[n] fields sharing one bucket plan.  With a
        ``batch`` axis of ``wave_width`` W > 1 (query lanes) the state
        leaves are vertex-major [block * W] item slices and the
        ``major`` item ids ride the same bucket plan; a ``GraphBatch``
        (W == 1, flat union-graph targets) routes like a single graph.
        ``batch=None`` falls back to the axis the run was configured
        with (``run_distributed(batch=...)``)."""
        ecfg = dataclasses.replace(self.ecfg, op=op)
        state_l, success, cf, sr, dall = wave_until_delivered(
            ecfg, state_l, target, payload, valid, self.max_subrounds,
            self.level, major, batch)
        self.conflicts = self.conflicts + cf
        self.subrounds = self.subrounds + sr
        self.messages = self.messages + self.psum(
            jnp.sum(valid.astype(jnp.int32)))
        self.delivered_all = self.delivered_all & dall
        return state_l, success

    def gather(self, arr_l, idx, valid=None, *, fill=0):
        """Remote gather of the distributed array ``arr_l`` at GLOBAL
        indices ``idx`` (``fill`` where ~valid)."""
        if valid is None:
            valid = jnp.ones(idx.shape, bool)
        out, sr, dall = gather_until_answered(
            self.ecfg, arr_l, idx, valid, fill=fill,
            max_subrounds=self.max_subrounds)
        self.subrounds = self.subrounds + sr
        self.delivered_all = self.delivered_all & dall
        return out


@dataclasses.dataclass(frozen=True)
class AlgorithmSpec:
    """One irregular algorithm expressed as AAM rounds.

    name:         display/registry name.
    message_type: AAM taxonomy tag of the dominant message ("FF&AS",
                  "FF&MF", "FR&AS", "FR&MF") — documentation/telemetry.
    init:         ``(g, layout) -> (state, scalars)``; ``state`` is a
                  pytree of GLOBAL arrays whose leading dim is divisible by
                  ``num_shards`` ([vpad] vertex state, [P*emax] edge
                  state), ``scalars`` a pytree of replicated scalars.
    round_fn:     ``(rt, edges, state, scalars, it) ->
                  (state, scalars, active)`` — one round: read the local
                  :class:`EdgeSlice`, issue waves/gathers through the
                  :class:`WaveRuntime`, return the globally-consistent
                  ``active`` bool (False terminates the loop).
    max_rounds:   ``(g, layout) -> int`` round cap.
    """
    name: str
    message_type: str
    init: Callable[..., Any]
    round_fn: Callable[..., Any]
    max_rounds: Callable[..., int]


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class DistributedResult:
    """Harness output: final state + the telemetry the paper tabulates.

    delivered_all is the anti-wedge flag: False means some wave hit
    ``max_subrounds`` with messages still pending, i.e. the returned state
    is NOT the fixed point — assert on it (the parity matrix does)."""
    state: Any              # pytree of GLOBAL (padded) arrays
    scalars: Any            # replicated scalar pytree
    rounds: jax.Array       # int32 — algorithm rounds executed
    conflicts: jax.Array    # int32 — commit conflicts across all waves
    subrounds: jax.Array    # int32 — coalescing sub-rounds across all waves
    delivered_all: jax.Array  # bool
    m_final: jax.Array      # int32 — final adaptive transaction size M
    #                         (0 = whole batch, -1 = static spec, no tuner)
    capacity: jax.Array     # int32 — the coalescing factor C the run used
    #                         (resolved value when capacity="auto")
    degraded: jax.Array = None  # bool — True when the run survived a mesh
    #                         shrink (host drop) by re-deriving ownership
    #                         and replaying from the last round snapshot


def telemetry_return(base, res: "DistributedResult", telemetry: bool):
    """THE ``telemetry=`` return-shape convention, shared by every
    ``distributed_*`` algorithm entry point (regression-pinned by
    ``tests/test_obs.py::test_telemetry_return_shapes``):

    * ``telemetry=False`` — return ``base`` unchanged (the entry
      point's documented plain shape);
    * ``telemetry=True`` — APPEND the :class:`DistributedResult` as one
      trailing element: a tuple ``base`` gains ``res`` at the end, a
      non-tuple ``base`` becomes the pair ``(base, res)``.

    So ``*out, res = distributed_x(..., telemetry=True)`` always works,
    and the plain positions never shift between the two modes.
    """
    if not telemetry:
        return base
    if isinstance(base, tuple):
        return base + (res,)
    return (base, res)


class _Runner:
    """One compiled round-loop over one mesh shape.

    Owns the partition, layout, calibrated tuner policy, and the jitted
    shard_map'd loop body for a fixed (mesh, P).  The loop carry
    ``(conflicts, subrounds, delivered_all, level, it, active)`` enters
    and leaves as replicated scalars, and the round cap is a TRACED
    ``limit`` — so the same compiled function serves both the single-shot
    path (limit = max_rounds) and the chunked/degraded path (limit = next
    snapshot boundary), and a degraded continuation re-enters mid-run.
    """

    def __init__(self, alg: AlgorithmSpec, mesh, g, *, axis: str,
                 capacity: int, m, spec, batch, max_subrounds: int,
                 edges=None):
        from jax.sharding import PartitionSpec as Ps
        from repro.graphs.csr import partition_edges

        self.P = mesh.shape[axis]
        self.mesh = mesh
        if edges is None:
            edges = partition_edges(g, self.P)
        (src, dst, w, val, eid), part = edges
        self.arrays = (src, dst, w, val, eid)
        self.layout = ShardLayout(self.P, part.block, src.shape[1],
                                  g.num_vertices, g.num_edges)
        ecfg = EngineConfig(self.P, part.block, capacity, axis=axis, m=m,
                            spec=spec, batch=batch)
        self.state0, self.scalars0 = alg.init(g, self.layout)
        self.tuner = None
        if ecfg.commit_spec.backend == C.AUTO:
            # stage-1 calibration BEFORE tracing: per-shard commits see a
            # [block] state slice and up to P*C routed messages/sub-round
            leaf = jax.tree_util.tree_leaves(self.state0)[0]
            self.tuner = AT.policy_for(
                ecfg.commit_spec,
                jax.ShapeDtypeStruct((part.block,), leaf.dtype),
                n=min(self.P * capacity, g.num_edges or 1),
                axis_width=batch.race_width if batch is not None else 1)
            ecfg = dataclasses.replace(ecfg, spec=None, tuner=self.tuner)
        self.max_rounds = int(alg.max_rounds(g, self.layout))
        tuner = self.tuner
        # wave telemetry tap, decided AT TRACE TIME (a _Runner is built
        # per run_distributed call, so flipping REPRO_TRACE takes effect
        # on the next run): one unordered io_callback per round per
        # shard — unordered so a multi-device mesh never serializes on
        # the host; the round index rides in the payload
        trace_cb = None
        if (spec is not None and spec.trace) or OT.trace_enabled():
            from repro.obs import wavetap
            trace_cb = wavetap.round_recorder(alg.name)

        def shard_fn(state, scalars, carry, limit,
                     src_l, dst_l, w_l, val_l, eid_l):
            shard = jax.lax.axis_index(axis)
            edges = EdgeSlice(
                src=src_l[0], dst=dst_l[0], weight=w_l[0], valid=val_l[0],
                eid=eid_l[0],
                my_src=jnp.clip(src_l[0] - shard * part.block, 0,
                                part.block - 1))

            def cond(c):
                return c[-1] & (c[-2] < limit)

            def body(c):
                state, scalars, conflicts, subrounds, dall, level, it, _ = c
                rt = WaveRuntime(ecfg, self.layout, max_subrounds,
                                 level=level)
                state, scalars, active = alg.round_fn(rt, edges, state,
                                                      scalars, it)
                if trace_cb is not None:
                    from jax.experimental import io_callback
                    io_callback(trace_cb, None, it, rt.conflicts,
                                rt.subrounds, rt.messages, level, shard,
                                ordered=False)
                if tuner is not None:
                    # stage-2 feedback: this round's psum'd conflicts vs
                    # routed messages move the ladder (replicated =>
                    # every shard steps identically)
                    level = AT.next_level(tuner, level, rt.conflicts,
                                          rt.messages)
                return (state, scalars, conflicts + rt.conflicts,
                        subrounds + rt.subrounds, dall & rt.delivered_all,
                        level, it + 1, active)

            out = jax.lax.while_loop(cond, body, (state, scalars) + carry)
            return out[:2], out[2:]

        st_specs = jax.tree.map(lambda _: Ps(axis), self.state0)
        sc_specs = jax.tree.map(lambda _: Ps(), self.scalars0)
        fn = jax.shard_map(
            shard_fn, mesh=mesh,
            in_specs=(st_specs, sc_specs, (Ps(),) * 6, Ps())
            + (Ps(axis),) * 5,
            out_specs=((st_specs, sc_specs), (Ps(),) * 6),
            check_vma=False)
        self._jfn = jax.jit(fn)

    def zero_carry(self) -> tuple:
        z = jnp.zeros((), jnp.int32)
        level0 = jnp.asarray(self.tuner.init_level if self.tuner else 0,
                             jnp.int32)
        return (z, z, jnp.ones((), bool), level0, z, jnp.ones((), bool))

    def run(self, state, scalars, carry, limit: int):
        (state, scalars), carry = self._jfn(
            state, scalars, carry, jnp.asarray(limit, jnp.int32),
            *self.arrays)
        return state, scalars, carry

    def m_final(self, level) -> jax.Array:
        if self.tuner is None:
            return jnp.full((), -1, jnp.int32)
        ms = jnp.asarray([m or 0 for m in self.tuner.ladder], jnp.int32)
        return ms[jnp.clip(level, 0, len(self.tuner.ladder) - 1)]


def _shrink_mesh(mesh, axis: str, new_size: int):
    """The surviving sub-mesh after a host drop: slice the device array
    along ``axis`` (the simulation of 'P-1 hosts remain')."""
    import numpy as np
    devs = np.asarray(mesh.devices)
    sl = [slice(None)] * devs.ndim
    sl[list(mesh.axis_names).index(axis)] = slice(0, new_size)
    return jax.sharding.Mesh(devs[tuple(sl)], mesh.axis_names)


def _remap_state(alg: AlgorithmSpec, g, old_layout: ShardLayout,
                 new_layout: ShardLayout, state):
    """Re-home a round-snapshot state onto a smaller mesh.

    The 1-D partition puts vertex v at GLOBAL index v with padding only at
    the tail, so vertex-state leaves ([vpad, ...]) carry over by value:
    a fresh ``alg.init`` on the new layout supplies the canonical padding
    rows, and the first V rows are overwritten with the snapshot.  Leaves
    NOT shaped by vpad (per-edge state — the partition order changed under
    them) cannot be re-homed; returns None => restart from round 0.
    """
    V = g.num_vertices
    fresh, _ = alg.init(g, new_layout)
    conforms = all(
        getattr(o, "ndim", 0) >= 1 and o.shape[0] == old_layout.vpad
        and n.shape[0] == new_layout.vpad and o.shape[1:] == n.shape[1:]
        for o, n in zip(jax.tree.leaves(state), jax.tree.leaves(fresh)))
    if not conforms:
        return None
    return jax.tree.map(lambda n, o: n.at[:V].set(o[:V]), fresh, state)


_LINT_CAPTURE = False   # toggled by repro.analysis.waverace.capture()


class LintCapture(Exception):
    """Carries the normalized (alg, graph, batch) out of
    :func:`run_distributed` when the analyzer only wants the round
    function, not a mesh execution."""

    def __init__(self, alg, g, batch):
        super().__init__(f"lint capture: {alg.name}")
        self.alg, self.g, self.batch = alg, g, batch


def run_distributed(alg: AlgorithmSpec, mesh, g, *,
                    capacity: int | str = 4096,
                    m: int | None = None, axis: str = "data",
                    spec: C.CommitSpec | None = None,
                    max_subrounds: int = 64,
                    edges=None, batch=None,
                    snapshot_rounds: int | None = None,
                    fault_injector=None,
                    max_faults: int = 8) -> DistributedResult:
    """Execute ``alg`` over ``mesh[axis]`` shards — the one distributed
    driver behind all six ``distributed_*`` algorithms.

    Owns: 1-D edge partitioning, the shard_map wrapper, the round loop
    (``while active and rounds < max_rounds``), and telemetry aggregation.
    ``capacity``/``m`` are the paper's C (coalescing factor) and M
    (transaction size); ``capacity="auto"`` sizes C from the per-shard
    load heuristic plus the sub-round overflow telemetry of previous runs
    on the same (graph shape, shard count) — see :func:`auto_capacity`.
    ``spec`` picks the commit backend per
    :class:`repro.core.commit.CommitSpec` — ``backend="auto"`` calibrates
    the perf model once per run (backend + ladder seed M*) and then
    adapts the transaction size per round from the psum'd conflict
    telemetry (Tables 3c/3f feedback).  ``edges`` accepts a precomputed
    ``partition_edges(g, mesh.shape[axis])`` result so wrappers that also
    need the lane layout (Boruvka's edge-state finalize) partition only
    once.

    ``g`` may be a :class:`repro.graphs.csr.GraphSet`: the run executes
    over its disjoint-union graph (per-graph CSR slices gathered from the
    stacked edge arrays), which IS the graph-batch axis — flat union ids
    key the owner slices and coalescing buckets.  ``batch`` names the
    run's default batch axis (``QueryLanes``/``GraphBatch``/
    ``ProductAxis``); waves issued without an explicit ``batch=`` use
    it, and its ``race_width`` (L lanes / G graphs / L·G cells) keys
    the tuner's axis-aware race.  A ``ProductAxis`` run passes a
    GraphSet here with ``batch=ProductAxis(L, gs.axis.sizes)``: union
    ids route exactly as the graph batch while lane ids ride as
    ``major`` (see :func:`route_wave`) — e.g.
    :func:`repro.graphs.algorithms.bfs.distributed_product_bfs`.

    **Degraded-mesh mode.**  ``snapshot_rounds`` chunks the round loop:
    every chunk boundary the (replicated) carry and global state come
    back to the host as a round snapshot.  ``fault_injector(chunk,
    rounds_done)`` raising simulates a host drop — instead of failing the
    query, the run shrinks the mesh by one device along ``axis``,
    re-derives the 1-D ownership for the smaller mesh, re-homes the last
    snapshot onto it (see :func:`_remap_state`; per-edge state restarts
    from round 0), and finishes there.  ``DistributedResult.degraded``
    reports it.  With neither parameter set the loop runs single-shot and
    any error propagates at once.
    """
    from repro.graphs.csr import GraphSet, partition_edges

    if isinstance(g, GraphSet):
        batch = batch if batch is not None else g.axis
        g = g.union()
    if _LINT_CAPTURE:
        # repro.analysis.waverace sets this flag, calls the public
        # distributed_* wrappers (so their own state/payload plumbing
        # runs), and catches the normalized (alg, graph, axis) triple
        # here instead of executing the mesh program.
        raise LintCapture(alg, g, batch)
    P = mesh.shape[axis]
    if edges is None:
        edges = partition_edges(g, P)
    auto_cap = capacity == "auto"
    if auto_cap:
        shard_edges = edges[0][0].shape[1]       # [P, emax] src array
        cap = capacity_cap(shard_edges)
        capacity = auto_capacity(g, P, shard_edges)
    kw = dict(axis=axis, capacity=capacity, m=m, spec=spec, batch=batch,
              max_subrounds=max_subrounds)
    r = _Runner(alg, mesh, g, edges=edges, **kw)
    state, scalars, carry = r.state0, r.scalars0, r.zero_carry()
    degraded, faults, chunk_i = False, 0, 0
    chunk = (snapshot_rounds if snapshot_rounds
             else max(r.max_rounds, 1))
    snap = (state, scalars, carry)
    # only a run that asked for fault tolerance treats an error as a
    # host drop; otherwise the first error (a compile failure, an OOM)
    # propagates instead of shrinking the mesh or retrying in place
    tolerate = fault_injector is not None or snapshot_rounds is not None
    while bool(carry[5]) and int(carry[4]) < r.max_rounds:
        limit = min(int(carry[4]) + chunk, r.max_rounds)
        try:
            if fault_injector is not None:
                fault_injector(chunk_i, int(carry[4]))
            state, scalars, carry = r.run(state, scalars, carry, limit)
            jax.block_until_ready(carry)     # surface device faults HERE
            snap = (state, scalars, carry)
        except KeyboardInterrupt:
            raise
        except Exception:
            faults += 1
            if not tolerate or faults > max_faults:
                raise
            degraded = True
            tr = OT.get_tracer()
            if tr.active:
                tr.instant("mesh_shrink", cat="engine",
                           args={"alg": alg.name, "P": r.P,
                                 "survivors": max(r.P - 1, 1),
                                 "rounds_done": int(carry[4]),
                                 "faults": faults})
            state, scalars, carry = snap     # last completed chunk
            if r.P > 1:
                new_mesh = _shrink_mesh(r.mesh, axis, r.P - 1)
                old_layout = r.layout
                r = _Runner(alg, new_mesh, g, **kw)
                remapped = _remap_state(alg, g, old_layout, r.layout,
                                        state)
                if remapped is None:
                    # per-edge state can't be re-homed: restart the
                    # query from round 0 on the surviving mesh
                    state, scalars = r.state0, r.scalars0
                    carry = r.zero_carry()
                else:
                    state = remapped
            # P == 1: nothing to shrink — retry the snapshot in place
        chunk_i += 1
    conflicts, subrounds, dall, level, rounds, _ = carry
    if auto_cap:
        _capacity_feedback(g, P, capacity, int(subrounds), int(rounds),
                           cap)
    return DistributedResult(state=state, scalars=scalars, rounds=rounds,
                             conflicts=conflicts, subrounds=subrounds,
                             delivered_all=dall, m_final=r.m_final(level),
                             capacity=jnp.asarray(capacity, jnp.int32),
                             degraded=jnp.asarray(degraded))


# Legacy entry points live with their algorithms now; keep the old import
# path (`from repro.core.engine import distributed_bfs`) working without a
# circular import at module load.
def __getattr__(name):
    if name == "distributed_bfs":
        from repro.graphs.algorithms.bfs import distributed_bfs
        return distributed_bfs
    if name == "distributed_pagerank":
        from repro.graphs.algorithms.pagerank import distributed_pagerank
        return distributed_pagerank
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
