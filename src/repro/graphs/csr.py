"""Graph containers: CSR + COO edge arrays, 1-D partitioning (paper §3.1).

Algorithms here are *edge-centric*: one vectorized pass over the edge arrays
generates the round's atomic active messages (src active -> message to dst).
This is the TPU-native layout — per-vertex ragged neighbor loops become
masked dense ops (DESIGN.md §2).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import trace as OT


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class Graph:
    """CSR + COO. ``src``/``dst`` are edge-parallel arrays sorted by src."""
    indptr: jax.Array            # int32 [V+1]
    src: jax.Array               # int32 [E]
    dst: jax.Array               # int32 [E]
    weights: jax.Array           # float32 [E]
    num_vertices: int = dataclasses.field(metadata=dict(static=True))
    num_edges: int = dataclasses.field(metadata=dict(static=True))

    @property
    def degrees(self) -> jax.Array:
        return self.indptr[1:] - self.indptr[:-1]

    def out_degree(self, v) -> jax.Array:
        return self.indptr[v + 1] - self.indptr[v]

    @property
    def avg_degree(self) -> float:
        return self.num_edges / max(self.num_vertices, 1)


def from_edges(src: np.ndarray, dst: np.ndarray, num_vertices: int,
               weights: np.ndarray | None = None, *,
               symmetrize: bool = False, dedupe: bool = True) -> Graph:
    with OT.span("ingest", cat="ingest"):
        src = np.asarray(src)
        dst = np.asarray(dst)
        unweighted = weights is None
        if unweighted:
            weights = np.ones(src.shape, np.float32)
        keep = src != dst                       # drop self-loops
        src, dst, weights = src[keep], dst[keep], weights[keep]
        if symmetrize:
            src, dst = np.concatenate([src, dst]), np.concatenate([dst, src])
            weights = np.concatenate([weights, weights])
        if dedupe and unweighted:
            # no weights to carry along: the sorted distinct keys alone give
            # the src-ordered edge list (a plain sort, no stable argsort)
            key = np.unique(src.astype(np.int64) * num_vertices + dst)
            src, dst = np.divmod(key, num_vertices)
            weights = np.ones(src.shape, np.float32)
        elif dedupe and len(src):
            src = src.astype(np.int64)
            # np.unique returns the keys sorted, i.e. already ordered by src
            key = src * num_vertices + dst
            _, idx = np.unique(key, return_index=True)
            src, dst, weights = src[idx], dst[idx], weights[idx]
        else:
            order = np.argsort(src, kind="stable")
            src, dst, weights = src[order], dst[order], weights[order]
        indptr = np.zeros(num_vertices + 1, np.int64)
        indptr[1:] = np.cumsum(np.bincount(src, minlength=num_vertices))
        return Graph(
            indptr=jnp.asarray(indptr, jnp.int32),
            src=jnp.asarray(src, jnp.int32),
            dst=jnp.asarray(dst, jnp.int32),
            weights=jnp.asarray(weights, jnp.float32),
            num_vertices=int(num_vertices),
            num_edges=int(len(src)),
        )


# ---------------------------------------------------------------------------
# GraphSet: G tenant graphs stacked into one flat vertex/edge space
# ---------------------------------------------------------------------------


class GraphSet:
    """A batch of G independent graphs sharing one flat key space.

    The serving layer's *graph* batch axis (ISSUE 5): graph ``i``'s
    vertices occupy the contiguous range ``[vertex_offset(i),
    vertex_offset(i) + V_i)`` of the flat space, its edges the range
    ``[edge_offset(i), edge_offset(i) + E_i)`` of the stacked edge
    arrays.  :meth:`union` materialises the disjoint-union
    :class:`Graph` (per-graph CSR slices gathered from the stacked
    arrays) — running a wave algorithm over the union IS running it on
    every member at once, because components never exchange messages
    and the flat ranges never collide in the commit key space (the same
    disjointness argument as the query-lane composite keys,
    ``repro.core.coalescing``).

    The container is python-side/static: sizes and offsets are plain
    ints so they can live in jit static args via
    :class:`repro.core.coalescing.GraphBatch` (``self.axis``).
    """

    def __init__(self, graphs):
        self.graphs = tuple(graphs)
        if not self.graphs:
            raise ValueError("GraphSet needs at least one graph")
        self.vsizes = tuple(int(g.num_vertices) for g in self.graphs)
        self.esizes = tuple(int(g.num_edges) for g in self.graphs)
        self.voffs = np.concatenate(
            [[0], np.cumsum(self.vsizes)]).astype(np.int64)
        self.eoffs = np.concatenate(
            [[0], np.cumsum(self.esizes)]).astype(np.int64)
        self._union: Graph | None = None

    @property
    def num_graphs(self) -> int:
        return len(self.graphs)

    @property
    def num_vertices(self) -> int:
        return int(self.voffs[-1])

    @property
    def num_edges(self) -> int:
        return int(self.eoffs[-1])

    def vertex_offset(self, i: int) -> int:
        return int(self.voffs[i])

    @property
    def axis(self):
        """The :class:`repro.core.coalescing.GraphBatch` batch axis of
        this set (static, hashable)."""
        from repro.core.coalescing import GraphBatch
        return GraphBatch(sizes=self.vsizes)

    def union(self) -> Graph:
        """The disjoint-union graph (cached): stacked edge arrays with
        per-graph vertex offsets applied, concatenated CSR indptr."""
        if self._union is None:
            src = jnp.concatenate(
                [g.src + jnp.int32(self.voffs[i])
                 for i, g in enumerate(self.graphs)])
            dst = jnp.concatenate(
                [g.dst + jnp.int32(self.voffs[i])
                 for i, g in enumerate(self.graphs)])
            w = jnp.concatenate([g.weights for g in self.graphs])
            indptr = jnp.concatenate(
                [g.indptr[:-1] + jnp.int32(self.eoffs[i])
                 for i, g in enumerate(self.graphs)]
                + [jnp.asarray([self.num_edges], jnp.int32)])
            self._union = Graph(indptr=indptr, src=src, dst=dst, weights=w,
                                num_vertices=self.num_vertices,
                                num_edges=self.num_edges)
        return self._union

    def flat_vertices(self, per_graph) -> jax.Array:
        """Map per-graph vertex ids ``per_graph`` ([G] int) into the
        flat space: ``voffs[i] + per_graph[i]``."""
        ids = np.asarray(per_graph, np.int64)
        if ids.shape != (self.num_graphs,):
            raise ValueError(f"expected one vertex per graph "
                             f"({self.num_graphs}), got shape {ids.shape}")
        return jnp.asarray(self.voffs[:-1] + ids, jnp.int32)

    def split_vertex(self, flat) -> list:
        """Slice a flat [num_vertices] (or [num_vertices, ...]) array
        back into per-graph rows."""
        return [flat[self.voffs[i]:self.voffs[i + 1]]
                for i in range(self.num_graphs)]

    def split_edge(self, flat) -> list:
        return [flat[self.eoffs[i]:self.eoffs[i + 1]]
                for i in range(self.num_graphs)]

    def graph_of_vertex(self) -> jax.Array:
        """int32 [num_vertices] graph index per flat vertex id."""
        return jnp.asarray(np.repeat(np.arange(self.num_graphs),
                                     self.vsizes), jnp.int32)

    def graph_of_edge(self) -> jax.Array:
        """int32 [num_edges] graph index per stacked edge id."""
        return jnp.asarray(np.repeat(np.arange(self.num_graphs),
                                     self.esizes), jnp.int32)


# ---------------------------------------------------------------------------
# 1-D partitioning (paper §3.1: V split into contiguous owner ranges)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Partition:
    num_shards: int
    block: int          # vertices per shard (padded)

    def owner(self, v):
        return v // self.block

    def local(self, v):
        return v % self.block


def partition_edges(g: Graph, num_shards: int):
    """Split edges by OWNER OF THE SOURCE (each shard expands its own
    vertices), padded to equal length.  Returns numpy arrays shaped
    [num_shards, E_max]: (src, dst, w, valid, eid) + Partition.

    ``eid`` carries each lane's ORIGINAL edge index (``num_edges`` in
    padding lanes) so distributed algorithms can tie-break identically to
    their single-shard counterparts (Boruvka's lexicographic (weight, edge
    id) selection) and so per-edge shard state maps back to ``g``'s edge
    order."""
    v = g.num_vertices
    block = -(-v // num_shards)
    src = np.asarray(g.src)
    dst = np.asarray(g.dst)
    w = np.asarray(g.weights)
    owner = src // block
    counts = np.bincount(owner, minlength=num_shards)
    emax = max(int(counts.max()), 1)
    s_out = np.zeros((num_shards, emax), np.int32)
    d_out = np.zeros((num_shards, emax), np.int32)
    w_out = np.zeros((num_shards, emax), np.float32)
    valid = np.zeros((num_shards, emax), bool)
    eid = np.full((num_shards, emax), g.num_edges, np.int32)
    all_eids = np.arange(g.num_edges, dtype=np.int32)
    for p in range(num_shards):
        m = owner == p
        n = int(m.sum())
        s_out[p, :n] = src[m]
        d_out[p, :n] = dst[m]
        w_out[p, :n] = w[m]
        valid[p, :n] = True
        eid[p, :n] = all_eids[m]
    return (s_out, d_out, w_out, valid, eid), Partition(num_shards, block)


def balanced_numbering(degrees, num_shards: int):
    """A renumbering of the vertices under which the contiguous owner
    ranges of :func:`partition_edges` hold equal shares of the edges.

    Vertices are dealt by degree, heaviest first, to the shards in snake
    order (0..P-1, then P-1..0, ...), each into the next local row of
    its shard; the ``vpad - V`` padding ids take the rows left over.  A
    shard's edge count is then a function of the sorted degree sequence
    alone, so it is the same under every labelling of the graph (with
    plain owner ranges the fullest shard moves with the labels).
    Returns ``(to_orig, to_int)``, int32 ``[vpad]`` inverse permutations:
    internal id ``u`` is vertex ``to_orig[u]``, vertex ``v`` is internal
    id ``to_int[v]``."""
    deg = np.asarray(degrees)
    v, p = deg.shape[0], num_shards
    block = -(-v // p)
    vpad = p * block
    k, j = np.divmod(np.arange(v, dtype=np.int64), p)
    shard = np.where(k % 2 == 0, j, p - 1 - j)
    to_int = np.empty(vpad, np.int64)
    to_int[np.argsort(-deg, kind="stable")] = shard * block + k
    free = np.ones(vpad, bool)
    free[to_int[:v]] = False
    to_int[v:] = np.flatnonzero(free)
    to_orig = np.empty(vpad, np.int64)
    to_orig[to_int] = np.arange(vpad)
    return to_orig.astype(np.int32), to_int.astype(np.int32)


def partition_edges_balanced(g: Graph, num_shards: int):
    """:func:`partition_edges` of ``g`` renumbered by
    :func:`balanced_numbering`: ``src``/``dst`` are internal ids, each
    shard's lanes sorted by internal source; ``eid`` stays the original
    edge index.  Returns ``(arrays, Partition, (to_orig, to_int))``."""
    indptr = np.asarray(g.indptr, np.int64)
    deg = np.diff(indptr)
    to_orig, to_int = balanced_numbering(deg, num_shards)
    vpad = to_orig.shape[0]
    block = vpad // num_shards
    # each vertex's CSR row, in internal order (padding ids have none)
    real = to_orig < g.num_vertices
    lens = np.where(real, deg[np.minimum(to_orig, g.num_vertices - 1)], 0)
    starts = indptr[np.minimum(to_orig, g.num_vertices)]
    ends = np.cumsum(lens)
    idx = np.repeat(starts - (ends - lens), lens) + np.arange(ends[-1])
    src = np.repeat(np.arange(vpad, dtype=np.int32), lens)
    dst = to_int[np.asarray(g.dst)[idx]]
    w = np.asarray(g.weights)[idx]
    bounds = np.concatenate([[0], ends[block - 1::block]])
    counts = np.diff(bounds)
    emax = max(int(counts.max()), 1)
    s_out = np.zeros((num_shards, emax), np.int32)
    d_out = np.zeros((num_shards, emax), np.int32)
    w_out = np.zeros((num_shards, emax), np.float32)
    valid = np.zeros((num_shards, emax), bool)
    eid = np.full((num_shards, emax), g.num_edges, np.int32)
    for p in range(num_shards):
        lo, hi = bounds[p], bounds[p + 1]
        n = hi - lo
        s_out[p, :n] = src[lo:hi]
        d_out[p, :n] = dst[lo:hi]
        w_out[p, :n] = w[lo:hi]
        valid[p, :n] = True
        eid[p, :n] = idx[lo:hi]
    return ((s_out, d_out, w_out, valid, eid), Partition(num_shards, block),
            (to_orig, to_int))
