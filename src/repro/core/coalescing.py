"""Coalescing — bucket messages per destination shard (paper §4.2, §5.6).

On BG/Q the paper aggregates activities flowing to the same node into one
network message (factor C).  The TPU analogue: messages are bucketed into a
fixed-capacity ``[num_owners, C]`` buffer and exchanged with one
``all_to_all`` per round — C is the coalescing factor.  The same planning
code is the MoE token-dispatch planner (experts = owners, capacity factor =
C / expected load): DESIGN.md §3.

All shapes are static; overflow beyond capacity is *counted and kept* — the
caller re-queues dropped messages next round (label-correcting algorithms
tolerate deferral; MoE drops by priority like every capacity-factor router).
"""
from __future__ import annotations

import dataclasses
import os
from typing import Any

import jax
import jax.numpy as jnp

from repro.core.messages import Messages


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class BucketPlan:
    """Routing plan for one coalescing round."""
    owner: jax.Array          # int32 [n] destination bucket per message
    position: jax.Array       # int32 [n] slot within the bucket (may exceed C)
    counts: jax.Array         # int32 [num_buckets] messages per bucket
    kept: jax.Array           # bool [n] — within capacity
    dropped: jax.Array        # int32 — overflow count (requeued by caller)


# Above this many buckets the dense planner's O(n·num_buckets) one-hot
# dominates memory (large-mesh routing); the sort-based planner computes the
# SAME stable ranks in O(n log n) — test_bucket_roundtrip pins the equality.
DENSE_PLANNER_MAX_BUCKETS = 32


# ---------------------------------------------------------------------------
# Batch axes — the first-class fusion dimension (ISSUE 5)
#
# The paper's lever is amortization: coarsening and coalescing pack many
# irregular updates into one atomic region so per-batch overhead is paid
# once (§4).  One level up, a server packs many independent WORK ITEMS
# into one wave.  A BatchAxis names what the items are and how they share
# one flat commit-key space:
#
# * QueryLanes(L, V)  — L queries over ONE graph: item l's private copy
#   of vertex v lives at flat key ``l * V + v``;
# * GraphBatch(sizes) — ONE query each over G graphs: graph g's vertex v
#   lives at flat key ``offset[g] + v`` (the disjoint-union key space of
#   ``repro.graphs.csr.GraphSet``);
# * ProductAxis(L, sizes) — the PRODUCT: up to L queries over EACH of G
#   graphs, flat key ``lane * Vtot + offset[g] + v`` (lane axis nested
#   over the graph axis — one wave serves many queries on many tenant
#   graphs at once, ISSUE 7).
#
# Items never collide (disjoint flat ranges), so conflict resolution over
# flat keys is exactly per-item conflict resolution: one commit() — any
# backend — equals the looped per-item commits (bit-for-bit for
# order-independent ops).  The axis-generic entry points are
# fuse_keys/split_keys here, batch_messages (repro.core.messages) and
# commit_batched (repro.core.commit); the lane-named forms are thin
# wrappers kept for the PR-4 surface.
# ---------------------------------------------------------------------------


# Largest admissible flat key space for int32 composite keys.  Commit
# backends reserve one slot PAST the state (``idx = flat_size`` is the
# drop sentinel and ``num_segments = flat_size + 1`` sizes the segment
# reductions), so the bound is iinfo(int32).max - 1, not .max: both the
# sentinel id and the segment count must stay representable.  Checked
# statically wherever a composite key space is born (the batch axes
# below, ``repro.core.engine.route_wave``'s vertex-major local keys) —
# the aamlint keyspace pass (repro.analysis.keyspace) re-derives the
# same bound as a diagnostic for axis shapes that never get built.
MAX_FLAT_KEYS = 2 ** 31 - 2


def require_key_space(flat_size: int, *, where: str) -> int:
    """Static int32-overflow guard for a composite commit-key space.

    Raises ``OverflowError`` when ``flat_size`` flat keys cannot be
    carried in int32 (keys are ``major * stride + minor`` int32
    arithmetic — beyond the bound they silently wrap and items ALIAS
    each other's state).  Call with python ints at trace/build time;
    returns ``flat_size`` so it can be used inline."""
    flat_size = int(flat_size)
    if flat_size > MAX_FLAT_KEYS:
        raise OverflowError(
            f"{where}: {flat_size} flat keys exceed the int32 key space "
            f"(max {MAX_FLAT_KEYS}; commit needs one extra slot for the "
            f"drop sentinel).  Shrink the batch (fewer lanes/graphs per "
            f"wave) or upcast the key pipeline to int64 "
            f"(jax.config.update('jax_enable_x64', True) plus int64 "
            f"targets end-to-end — fuse_keys, messages, commit).")
    return flat_size


def fuse_keys(major: jax.Array, minor: jax.Array, stride: int) -> jax.Array:
    """Axis-generic composite commit key ``major * stride + minor`` —
    THE place the composite-key convention lives; both layouts go
    through it:

    * major-major (single-shard [L, V] lane state):
      ``fuse_keys(lane, vertex, V)`` — see
      :func:`repro.core.messages.batch_messages`;
    * vertex-major (distributed [block * W] owner slices, all batch
      items of a vertex co-located on its owner shard):
      ``fuse_keys(local_vertex, item, W)`` — see
      :func:`repro.core.engine.route_wave`.

    Items never collide: conflict resolution over composite keys is
    exactly per-item conflict resolution, so one ``commit()`` call
    resolves every item's conflicts bit-identically to separate calls
    (for order-independent ops)."""
    return major.astype(jnp.int32) * stride + minor.astype(jnp.int32)


def split_keys(key: jax.Array, stride: int):
    """Inverse of :func:`fuse_keys`: ``(major, minor)``."""
    return key // stride, key % stride


def fuse_lane_keys(major: jax.Array, minor: jax.Array,
                   stride: int) -> jax.Array:
    """PR-4 name for :func:`fuse_keys` (the query-lane axis)."""
    return fuse_keys(major, minor, stride)


def split_lane_keys(key: jax.Array, stride: int):
    """PR-4 name for :func:`split_keys`."""
    return split_keys(key, stride)


@dataclasses.dataclass(frozen=True)
class QueryLanes:
    """Batch axis: L independent queries over one V-vertex graph.

    Flat key = ``lane * num_vertices + v`` (lane-major — each lane owns
    a contiguous [V] block, the layout the single-shard fused loops
    commit against).  Frozen + hashable: rides in jit static args and
    :class:`repro.core.engine.EngineConfig`."""
    lanes: int
    num_vertices: int

    def __post_init__(self):
        if int(self.lanes) < 1 or int(self.num_vertices) < 1:
            raise ValueError(f"QueryLanes needs lanes/num_vertices >= 1, "
                             f"got {self.lanes}/{self.num_vertices}")
        require_key_space(int(self.lanes) * int(self.num_vertices),
                          where="QueryLanes(L, V)")

    @property
    def flat_size(self) -> int:
        return self.lanes * self.num_vertices

    @property
    def wave_width(self) -> int:
        """Items co-located per vertex in the distributed vertex-major
        layout ([block * lanes] owner slices)."""
        return self.lanes

    @property
    def race_width(self) -> int:
        """Batch width the autotuner's race key records (the argsort of
        a fused wave spans all L lanes' messages)."""
        return self.lanes

    def flatten(self, major, minor) -> jax.Array:
        return fuse_keys(jnp.asarray(major), jnp.asarray(minor),
                         self.num_vertices)

    def unflatten(self, key):
        return split_keys(key, self.num_vertices)


@dataclasses.dataclass(frozen=True)
class GraphBatch:
    """Batch axis: one query each over G independent graphs.

    ``sizes[g]`` is graph g's vertex count; flat key = ``offset[g] + v``
    with ``offset`` the exclusive prefix sum — the disjoint-union key
    space of :class:`repro.graphs.csr.GraphSet` (heterogeneous sizes,
    no padding).  Because the target ids of a stacked edge array are
    ALREADY flat, a graph-batched wave needs no extra item field:
    ``wave_width == 1`` and the engine routes/commits it exactly like a
    single graph (owner slices and coalescing buckets keyed by flat
    id)."""
    sizes: tuple

    def __post_init__(self):
        if not self.sizes or any(int(s) < 1 for s in self.sizes):
            raise ValueError(f"GraphBatch needs positive per-graph sizes, "
                             f"got {self.sizes}")
        require_key_space(sum(int(s) for s in self.sizes),
                          where="GraphBatch(sizes)")

    @property
    def offsets(self) -> tuple:
        out, acc = [], 0
        for s in self.sizes:
            out.append(acc)
            acc += int(s)
        return tuple(out)

    @property
    def flat_size(self) -> int:
        return sum(int(s) for s in self.sizes)

    @property
    def wave_width(self) -> int:
        return 1        # keys are already globally flat

    @property
    def race_width(self) -> int:
        """A graph-batched wave still fuses G graphs' messages into one
        commit — the race must not inherit the width-1 verdict even
        though no extra item field rides the exchange."""
        return len(self.sizes)

    def flatten(self, major, minor) -> jax.Array:
        offs = jnp.asarray(self.offsets, jnp.int32)
        return offs[jnp.asarray(major)] + jnp.asarray(minor, jnp.int32)

    def unflatten(self, key):
        bounds = jnp.asarray(self.offsets[1:] + (self.flat_size,),
                             jnp.int32)
        key = jnp.asarray(key, jnp.int32)
        major = jnp.searchsorted(bounds, key, side="right").astype(jnp.int32)
        offs = jnp.asarray(self.offsets, jnp.int32)
        return major, key - offs[jnp.clip(major, 0, len(self.sizes) - 1)]


@dataclasses.dataclass(frozen=True)
class ProductAxis:
    """Batch axis PRODUCT: up to L queries over EACH of G tenant graphs.

    The composite key nests the lane axis over the graph axis::

        flat = lane * Vtot + (offset[g] + v),   Vtot = sum(sizes)

    i.e. ``fuse_keys(lane, GraphBatch(sizes).flatten(g, v), Vtot)`` —
    lane-major over the disjoint-union key space, exactly the 2-mark
    nesting ``_union_stconn`` already uses (grey marks at ``[0, Vtot)``,
    green at ``[Vtot, 2*Vtot)``).  Cells (lane, graph) are independent
    work items occupying disjoint flat ranges, so one ``commit()`` over
    product keys resolves every cell's conflicts bit-identically to
    per-cell commits (order-independent ops).

    Degenerate forms collapse key-for-key onto the single axes
    (pinned by tests/test_product_axis.py)::

        ProductAxis(1, sizes).flatten3(0, g, v) == GraphBatch(sizes).flatten(g, v)
        ProductAxis(L, (V,)).flatten3(l, 0, v)  == QueryLanes(L, V).flatten(l, v)

    Frozen + hashable: rides in jit static args and
    :class:`repro.core.engine.EngineConfig` like the other axes."""
    lanes: int
    sizes: tuple

    def __post_init__(self):
        if int(self.lanes) < 1:
            raise ValueError(f"ProductAxis needs lanes >= 1, got {self.lanes}")
        if not self.sizes or any(int(s) < 1 for s in self.sizes):
            raise ValueError(f"ProductAxis needs positive per-graph sizes, "
                             f"got {self.sizes}")
        # L × Vtot is where the int32 hazard actually bites (a modest lane
        # budget times a big tenant union overflows long before either
        # axis would alone) — flatten3 arithmetic wraps silently past it
        require_key_space(int(self.lanes) * sum(int(s) for s in self.sizes),
                          where="ProductAxis(L, sizes): L * Vtot")

    @property
    def graph_axis(self) -> GraphBatch:
        """The inner (minor) axis — the union key space."""
        return GraphBatch(self.sizes)

    @property
    def num_graphs(self) -> int:
        return len(self.sizes)

    @property
    def num_vertices(self) -> int:
        """Union vertex count Vtot — the lane stride."""
        return sum(int(s) for s in self.sizes)

    @property
    def offsets(self) -> tuple:
        return self.graph_axis.offsets

    @property
    def flat_size(self) -> int:
        return self.lanes * self.num_vertices

    @property
    def wave_width(self) -> int:
        """Distributed vertex-major layout co-locates all L lanes of a
        union vertex on its owner shard ([block * lanes] slices); the
        graph coordinate is already folded into the flat vertex id, so
        only the lane id rides the exchange — same as QueryLanes."""
        return self.lanes

    @property
    def race_width(self) -> int:
        """The autotuner race key: a product wave's argsort spans every
        cell's messages — L lanes × G graphs."""
        return self.lanes * len(self.sizes)

    def flatten(self, major, minor) -> jax.Array:
        """2-part key: (lane, flat_union_vertex) -> product key."""
        return fuse_keys(jnp.asarray(major), jnp.asarray(minor),
                         self.num_vertices)

    def unflatten(self, key):
        """Inverse of :func:`flatten`: (lane, flat_union_vertex)."""
        return split_keys(key, self.num_vertices)

    def flatten3(self, lane, graph, v) -> jax.Array:
        """3-part key: (lane, graph, LOCAL vertex) -> product key."""
        return self.flatten(lane, self.graph_axis.flatten(graph, v))

    def split3(self, key):
        """Inverse of :func:`flatten3`: (lane, graph, local_vertex)."""
        lane, flat = self.unflatten(key)
        g, v = self.graph_axis.unflatten(flat)
        return lane, g, v


def plan_buckets(owner: jax.Array, valid: jax.Array, num_buckets: int,
                 capacity: int) -> BucketPlan:
    """Stable bucketing: position = rank of the message within its bucket
    in original order (priority = arrival order, like the paper's queues and
    like position-priority MoE routers).

    Dispatches to :func:`plan_buckets_sorted` above
    :data:`DENSE_PLANNER_MAX_BUCKETS` so large-mesh routing never
    materializes the O(n·num_buckets) one-hot; both planners produce
    identical plans (stable arrival-order ranks)."""
    if num_buckets > DENSE_PLANNER_MAX_BUCKETS:
        return plan_buckets_sorted(owner, valid, num_buckets, capacity)[0]
    return plan_buckets_dense(owner, valid, num_buckets, capacity)


def plan_buckets_dense(owner: jax.Array, valid: jax.Array, num_buckets: int,
                       capacity: int) -> BucketPlan:
    """The dense one-hot planner (O(n·num_buckets) — small bucket counts)."""
    n = owner.shape[0]
    owner = jnp.where(valid, owner, num_buckets)
    onehot = jax.nn.one_hot(owner, num_buckets + 1, dtype=jnp.int32)
    # rank within bucket = exclusive cumsum of one-hot along messages
    ranks = jnp.cumsum(onehot, axis=0) - onehot
    position = jnp.take_along_axis(ranks, owner[:, None], axis=1)[:, 0]
    counts = jnp.sum(onehot, axis=0)[:num_buckets]
    kept = valid & (position < capacity)
    dropped = jnp.sum(valid) - jnp.sum(kept)
    return BucketPlan(owner=owner.astype(jnp.int32),
                      position=position.astype(jnp.int32),
                      counts=counts, kept=kept,
                      dropped=dropped.astype(jnp.int32))


# Histogram backend for plan_buckets_sorted: "jnp" (bincount, default) or
# "pallas" (kernels.coalesce.bucket_count_pallas — one-hot tile sums in
# VMEM).  The env var sets the default; the keyword wins when given.
BUCKET_COUNT_ENV = "REPRO_BUCKET_COUNT"
_COUNT_BACKENDS = ("jnp", "pallas")


def _bucket_counts(owner_c: jax.Array, valid: jax.Array, num_buckets: int,
                   count_backend: str | None) -> jax.Array:
    backend = count_backend or os.environ.get(BUCKET_COUNT_ENV, "jnp")
    if backend not in _COUNT_BACKENDS:
        raise ValueError(
            f"count_backend={backend!r} not in {_COUNT_BACKENDS}")
    if backend == "pallas":
        from repro.kernels import interpret_default
        from repro.kernels.coalesce import bucket_count_pallas
        masked = jnp.where(valid, owner_c, -1).astype(jnp.int32)
        return bucket_count_pallas(masked, num_buckets=num_buckets,
                                   interpret=interpret_default())
    return jnp.bincount(owner_c, length=num_buckets + 1)[:num_buckets]


def plan_buckets_sorted(owner: jax.Array, valid: jax.Array, num_buckets: int,
                        capacity: int,
                        count_backend: str | None = None,
                        ) -> tuple[BucketPlan, jax.Array]:
    """Sort-based planner (O(n log n) instead of O(n·buckets)); used when
    num_buckets is large (MoE with 128 experts).  Returns (plan, sort_order).

    ``count_backend`` selects the histogram path ("jnp" | "pallas"); unset
    it falls back to ``$REPRO_BUCKET_COUNT`` and then "jnp".
    """
    n = owner.shape[0]
    owner_c = jnp.where(valid, owner, num_buckets)
    order = jnp.argsort(owner_c, stable=True)
    sorted_owner = owner_c[order]
    counts = _bucket_counts(owner_c, valid, num_buckets, count_backend)
    starts = jnp.concatenate([jnp.zeros((1,), counts.dtype),
                              jnp.cumsum(counts)])[:num_buckets + 1]
    pos_sorted = jnp.arange(n, dtype=jnp.int32) - starts[
        jnp.clip(sorted_owner, 0, num_buckets)].astype(jnp.int32)
    position = jnp.zeros((n,), jnp.int32).at[order].set(pos_sorted)
    kept = valid & (position < capacity)
    dropped = jnp.sum(valid) - jnp.sum(kept)
    return BucketPlan(owner=owner_c.astype(jnp.int32), position=position,
                      counts=counts.astype(jnp.int32), kept=kept,
                      dropped=dropped.astype(jnp.int32)), order


def scatter_to_buckets(plan: BucketPlan, payload: Any, num_buckets: int,
                       capacity: int, fill=0) -> Any:
    """Build the [num_buckets, capacity, ...] coalesced buffer (payload may
    be a pytree; int payloads fill with ``fill``)."""
    flat = plan.owner * capacity + jnp.where(plan.kept, plan.position, capacity)
    flat = jnp.where(plan.kept, flat, num_buckets * capacity)  # OOB drop

    def scat(x):
        buf = jnp.full((num_buckets * capacity + 1,) + x.shape[1:], fill,
                       x.dtype)
        buf = buf.at[flat].set(x, mode="drop")
        return buf[:-1].reshape((num_buckets, capacity) + x.shape[1:])
    return jax.tree.map(scat, payload)


def bucket_message_ids(plan: BucketPlan, num_buckets: int,
                       capacity: int) -> jax.Array:
    """[num_buckets, capacity] original message index per slot (-1 empty)."""
    ids = jnp.arange(plan.owner.shape[0], dtype=jnp.int32)
    buf = scatter_to_buckets(plan, ids + 1, num_buckets, capacity, fill=0)
    return buf - 1


def gather_from_buckets(buf: Any, plan: BucketPlan, capacity: int,
                        fill=0) -> Any:
    """Inverse of scatter_to_buckets: per-message gather of returned values
    (the FR return path)."""
    pos = jnp.where(plan.kept, plan.position, 0)
    def gat(x):
        nb, cap = x.shape[0], x.shape[1]
        flatx = x.reshape((nb * cap,) + x.shape[2:])
        idx = jnp.clip(plan.owner, 0, nb - 1) * cap + jnp.clip(pos, 0, cap - 1)
        out = flatx[idx]
        mask = plan.kept.reshape(plan.kept.shape + (1,) * (out.ndim - 1))
        return jnp.where(mask, out, fill)
    return jax.tree.map(gat, buf)
