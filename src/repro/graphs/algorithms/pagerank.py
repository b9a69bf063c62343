"""PageRank — FF&AS atomic active messages (paper §3.3.1, Listing 3).

Every edge carries ``d * rank[src] / out_deg[src]`` to its destination; the
commit is an Always-Succeed accumulate.  A round forms ``d * rank / deg``
once per vertex and gathers it over ``src`` ONCE: the same float32
multiply and divide on the same operands as per-edge
``d * rank[src] / deg[src]``, so the messages are bit-identical to that
formula's, for one edge-sized gather a round.  On TPU the AS commit is a
conflict-free segment-sum — the paper's HTM abort storm for ACC (§5.4.2)
disappears by construction (DESIGN.md §2).  ``pagerank_baseline`` is the
PBGL-like per-edge scatter path used as the Fig-7 comparison.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core import autotune as AT
from repro.core import commit as C
from repro.core.messages import lane_messages, make_messages
from repro.graphs.csr import Graph


@partial(jax.jit, static_argnames=("iters", "commit", "m", "sort", "spec"))
def pagerank(g: Graph, *, d: float = 0.85, iters: int = 20,
             commit: str = "coarse", m: int | None = None, sort: bool = True,
             spec: C.CommitSpec | None = None):
    if spec is None:
        spec = C.CommitSpec(backend=commit, m=m, sort=sort, stats=False)
    v = g.num_vertices
    deg = jnp.maximum(g.degrees, 1).astype(jnp.float32)
    dangling = g.degrees == 0
    acc0 = jnp.zeros((v,), jnp.float32)
    step, lvl0 = AT.make_commit_step(spec, "add", acc0, n=g.src.shape[0])

    def body(carry, _):
        rank, conflicts, lvl = carry
        with jax.named_scope(C.MESSAGES_SCOPE):
            contrib = (d * rank / deg)[g.src]   # one E-gather (module doc)
            msgs = make_messages(g.dst, contrib,
                                 jnp.ones_like(g.src, bool))
        res, lvl = step(acc0, msgs, lvl)
        dangle = d * jnp.sum(jnp.where(dangling, rank, 0.0)) / v
        rank = (1.0 - d) / v + res.state + dangle
        return (rank, conflicts + res.conflicts, lvl), None

    rank0 = jnp.full((v,), 1.0 / v, jnp.float32)
    (rank, conflicts, _), _ = jax.lax.scan(
        body, (rank0, jnp.zeros((), jnp.int32), lvl0), None, length=iters)
    return rank, conflicts


@partial(jax.jit, static_argnames=("iters", "commit", "m", "sort", "spec"))
def personalized_pagerank(g: Graph, source, *, d: float = 0.85,
                          iters: int = 20, commit: str = "coarse",
                          m: int | None = None, sort: bool = True,
                          spec: C.CommitSpec | None = None):
    """Personalized PageRank: the restart distribution is concentrated at
    ``source`` (random surfer teleports home) — the single-query form the
    serving layer lane-batches.  Dangling mass also returns to the source,
    so per-lane mass is conserved at 1."""
    if spec is None:
        spec = C.CommitSpec(backend=commit, m=m, sort=sort, stats=False)
    v = g.num_vertices
    deg = jnp.maximum(g.degrees, 1).astype(jnp.float32)
    dangling = g.degrees == 0
    restart = jnp.zeros((v,), jnp.float32).at[source].set(1.0)
    acc0 = jnp.zeros((v,), jnp.float32)
    step, lvl0 = AT.make_commit_step(spec, "add", acc0, n=g.src.shape[0])

    def body(carry, _):
        rank, conflicts, lvl = carry
        with jax.named_scope(C.MESSAGES_SCOPE):
            contrib = (d * rank / deg)[g.src]   # one E-gather (module doc)
            msgs = make_messages(g.dst, contrib,
                                 jnp.ones_like(g.src, bool))
        res, lvl = step(acc0, msgs, lvl)
        dangle = d * jnp.sum(jnp.where(dangling, rank, 0.0))
        rank = restart * ((1.0 - d) + dangle) + res.state
        return (rank, conflicts + res.conflicts, lvl), None

    (rank, conflicts, _), _ = jax.lax.scan(
        body, (restart, jnp.zeros((), jnp.int32), lvl0), None, length=iters)
    return rank, conflicts


@partial(jax.jit, static_argnames=("iters", "commit", "m", "sort", "spec"))
def multi_source_pagerank(g: Graph, sources, *, d: float = 0.85,
                          iters: int = 20, commit: str = "coarse",
                          m: int | None = None, sort: bool = True,
                          spec: C.CommitSpec | None = None):
    """L personalized-PageRank queries as lanes of one fused wave.

    Returns (rank [L, V], conflicts).  Row l matches
    ``personalized_pagerank(g, sources[l])`` to float-add rounding (the
    composite-key commit reorders each lane's accumulate exactly like any
    transaction-size change does)."""
    if spec is None:
        spec = C.CommitSpec(backend=commit, m=m, sort=sort, stats=False)
    v = g.num_vertices
    sources = jnp.asarray(sources, jnp.int32)
    lanes = sources.shape[0]
    lidx = jnp.arange(lanes, dtype=jnp.int32)
    deg = jnp.maximum(g.degrees, 1).astype(jnp.float32)
    dangling = g.degrees == 0
    restart = jnp.zeros((lanes, v), jnp.float32) \
        .at[lidx, sources].set(1.0)
    e = g.src.shape[0]
    dst_l = jnp.broadcast_to(g.dst, (lanes, e))
    valid_l = jnp.ones((lanes, e), bool)
    acc0 = jnp.zeros((lanes * v,), jnp.float32)
    step, lvl0 = AT.make_commit_step(spec, "add", acc0, n=lanes * e,
                                     axis_width=lanes)

    def body(carry, _):
        rank, conflicts, lvl = carry
        contrib = (d * rank / deg[None, :])[:, g.src]   # one gather, exact
        msgs = lane_messages(dst_l, contrib, valid_l, v)
        res, lvl = step(acc0, msgs, lvl)
        dangle = d * jnp.sum(jnp.where(dangling[None, :], rank, 0.0),
                             axis=1)                      # [L]
        rank = restart * ((1.0 - d) + dangle[:, None]) \
            + res.state.reshape(lanes, v)
        return (rank, conflicts + res.conflicts, lvl), None

    (rank, conflicts, _), _ = jax.lax.scan(
        body, (restart, jnp.zeros((), jnp.int32), lvl0), None, length=iters)
    return rank, conflicts


@partial(jax.jit, static_argnames=("iters", "spec", "num_graphs",
                                   "axis_width"))
def _union_ppr(g: Graph, sources_flat, gov, d, *, iters: int,
               spec: C.CommitSpec | None, num_graphs: int,
               axis_width: int):
    """Personalized PageRank over a disjoint-union graph with PER-GRAPH
    dangling mass (segment sums by ``gov``, the graph-of-vertex map)."""
    v = g.num_vertices
    deg = jnp.maximum(g.degrees, 1).astype(jnp.float32)
    dangling = g.degrees == 0
    restart = jnp.zeros((v,), jnp.float32).at[sources_flat].set(1.0)
    acc0 = jnp.zeros((v,), jnp.float32)
    step, lvl0 = AT.make_commit_step(spec, "add", acc0, n=g.src.shape[0],
                                     axis_width=axis_width)

    def body(carry, _):
        rank, lvl = carry
        contrib = (d * rank / deg)[g.src]       # one E-gather, exact
        msgs = make_messages(g.dst, contrib, jnp.ones_like(g.src, bool))
        res, lvl = step(acc0, msgs, lvl)
        dm = jax.ops.segment_sum(jnp.where(dangling, rank, 0.0), gov,
                                 num_segments=num_graphs)       # [G]
        rank = restart * ((1.0 - d) + d * dm[gov]) + res.state
        return (rank, lvl), None

    (rank, _), _ = jax.lax.scan(body, (restart, lvl0), None, length=iters)
    return rank


def batched_over_graphs_pagerank(gs, sources, *, d: float = 0.85,
                                 iters: int = 20,
                                 spec: C.CommitSpec | None = None,
                                 mesh=None, capacity: int | str = 4096,
                                 axis: str = "data",
                                 max_subrounds: int = 64):
    """G personalized-PageRank queries, one per tenant graph, fused on
    the graph batch axis (disjoint-union flat keys).  ``sources[g]`` is
    graph g's LOCAL restart vertex; all queries share the trace-time
    (iters, d) knobs — the admission fuse key.  Returns per-graph rank
    rows matching ``personalized_pagerank(gs.graphs[g], sources[g])`` to
    float-add rounding (the fused commit reorders each graph's
    accumulate exactly like any transaction-size change; per-graph
    dangling mass is a segment sum over the union)."""
    if spec is None:
        spec = C.CommitSpec(backend="coarse", stats=False)
    flat = gs.flat_vertices(sources)
    gov = gs.graph_of_vertex()
    if mesh is not None:
        rank = _distributed_union_ppr(
            mesh, gs, flat, d=d, iters=iters, spec=spec,
            capacity=capacity, axis=axis, max_subrounds=max_subrounds)
    else:
        rank = _union_ppr(gs.union(), flat, gov, d, iters=iters, spec=spec,
                          num_graphs=gs.num_graphs,
                          axis_width=gs.num_graphs)
    return gs.split_vertex(rank)


def _distributed_union_ppr(mesh, gs, sources_flat, *, d, iters, spec,
                           capacity, axis, max_subrounds):
    """Graph-batched personalized PageRank on the shared harness: FF&AS
    accumulate waves over the union's flat owner slices, per-graph
    dangling mass psum'd as a [G] vector."""
    from repro.core.engine import (AlgorithmSpec, run_distributed,
                                   telemetry_return)
    g = gs.union()
    v = g.num_vertices
    num_graphs = gs.num_graphs
    gov_np = gs.graph_of_vertex()

    def init(g, layout):
        vpad = layout.vpad
        restart = jnp.zeros((vpad,), jnp.float32).at[sources_flat].set(1.0)
        gov = jnp.full((vpad,), num_graphs - 1, jnp.int32) \
            .at[:v].set(gov_np)
        state = {
            "rank": restart,
            "restart": restart,
            "deg": jnp.zeros((vpad,), jnp.int32).at[:v].set(
                jnp.maximum(g.degrees, 1)),
            "dangling": jnp.zeros((vpad,), bool).at[:v].set(g.degrees == 0),
            "real": jnp.zeros((vpad,), bool).at[:v].set(True),
            "gov": gov,
        }
        return state, {}

    def round_fn(rt, e, st, sc, it):
        rank = st["rank"]
        contrib = (d * rank[e.my_src]
                   / st["deg"][e.my_src].astype(jnp.float32))
        acc0 = jnp.zeros(rank.shape, jnp.float32)
        acc, _ = rt.wave(acc0, e.dst, contrib, e.valid, op="add")
        dm = rt.psum(jax.ops.segment_sum(
            jnp.where(st["dangling"], rank, 0.0), st["gov"],
            num_segments=num_graphs))                           # [G]
        rank = jnp.where(st["real"],
                         st["restart"] * ((1.0 - d) + d * dm[st["gov"]])
                         + acc, 0.0)
        return dict(st, rank=rank), sc, jnp.ones((), bool)

    alg = AlgorithmSpec("graphs_ppr", "FF&AS", init, round_fn,
                        lambda g, layout: iters)
    res = run_distributed(alg, mesh, gs, capacity=capacity, axis=axis,
                          spec=spec, max_subrounds=max_subrounds)
    return res.state["rank"][:v]


def distributed_pagerank(mesh, g: Graph, *, iters: int = 20,
                         capacity: int | str = 4096, m: int | None = None,
                         axis: str = "data", d: float = 0.85,
                         spec: C.CommitSpec | None = None,
                         max_subrounds: int = 64, telemetry: bool = False):
    """PageRank over a mesh axis — FF&AS accumulate waves on the shared
    harness.  Returns rank [V]; ``telemetry=True`` returns
    (rank, DistributedResult)."""
    from repro.core.engine import (AlgorithmSpec, run_distributed,
                                   telemetry_return)
    v = g.num_vertices

    def init(g, layout):
        vpad = layout.vpad
        realv = jnp.zeros((vpad,), bool).at[:v].set(True)
        state = {
            "rank": jnp.where(realv, 1.0 / v, 0.0).astype(jnp.float32),
            "deg": jnp.zeros((vpad,), jnp.int32).at[:v].set(
                jnp.maximum(g.degrees, 1)),
            "dangling": jnp.zeros((vpad,), bool).at[:v].set(g.degrees == 0),
            "real": realv,
        }
        return state, {}

    def round_fn(rt, e, st, sc, it):
        rank = st["rank"]
        contrib = (d * rank[e.my_src]
                   / st["deg"][e.my_src].astype(jnp.float32))
        acc0 = jnp.zeros(rank.shape, jnp.float32)
        acc, _ = rt.wave(acc0, e.dst, contrib, e.valid, op="add")
        dm = rt.psum(jnp.sum(jnp.where(st["dangling"], rank, 0.0)))
        rank = jnp.where(st["real"], (1.0 - d) / v + acc + d * dm / v, 0.0)
        return dict(st, rank=rank), sc, jnp.ones((), bool)

    alg = AlgorithmSpec("pagerank", "FF&AS", init, round_fn,
                        lambda g, layout: iters)
    res = run_distributed(alg, mesh, g, capacity=capacity, m=m, axis=axis,
                          spec=spec, max_subrounds=max_subrounds)
    rank = res.state["rank"][:v]
    return telemetry_return(rank, res, telemetry)


def distributed_multi_source_pagerank(mesh, g: Graph, sources, *,
                                      iters: int = 20,
                                      capacity: int | str = 4096,
                                      m: int | None = None,
                                      axis: str = "data", d: float = 0.85,
                                      spec: C.CommitSpec | None = None,
                                      max_subrounds: int = 64,
                                      telemetry: bool = False):
    """Lane-batched personalized PageRank over a mesh axis — FF&AS
    accumulate waves on vertex-major [vpad * L] state, per-lane dangling
    mass psum'd as an [L] vector.  Returns rank [L, V];
    ``telemetry=True`` returns (rank, DistributedResult)."""
    from repro.core.coalescing import QueryLanes
    from repro.core.engine import (AlgorithmSpec, run_distributed,
                                   telemetry_return)
    v = g.num_vertices

    sources = jnp.asarray(sources, jnp.int32)
    lanes = sources.shape[0]
    lidx = jnp.arange(lanes, dtype=jnp.int32)

    def init(g, layout):
        vpad = layout.vpad
        restart = jnp.zeros((vpad * lanes,), jnp.float32) \
            .at[sources * lanes + lidx].set(1.0)
        state = {
            "rank": restart,
            "restart": restart,
            "deg": jnp.zeros((vpad,), jnp.int32).at[:v].set(
                jnp.maximum(g.degrees, 1)),
            "dangling": jnp.zeros((vpad,), bool).at[:v].set(g.degrees == 0),
        }
        return state, {}

    def round_fn(rt, e, st, sc, it):
        rank = st["rank"]                      # [block * L]
        emax = e.dst.shape[0]
        fl = e.my_src[:, None] * lanes + lidx[None, :]
        contrib = d * rank[fl] / st["deg"][e.my_src] \
            .astype(jnp.float32)[:, None]
        tgt = jnp.broadcast_to(e.dst[:, None], (emax, lanes))
        lane = jnp.broadcast_to(lidx[None, :], (emax, lanes))
        valid = jnp.broadcast_to(e.valid[:, None], (emax, lanes))
        acc0 = jnp.zeros(rank.shape, jnp.float32)
        acc, _ = rt.wave(acc0, tgt.reshape(-1), contrib.reshape(-1),
                         valid.reshape(-1), op="add",
                         major=lane.reshape(-1))
        rk = rank.reshape(-1, lanes)
        dm = rt.psum(jnp.sum(
            jnp.where(st["dangling"][:, None], rk, 0.0), axis=0))   # [L]
        rank2 = st["restart"].reshape(-1, lanes) \
            * ((1.0 - d) + d * dm[None, :]) + acc.reshape(-1, lanes)
        return dict(st, rank=rank2.reshape(-1)), sc, jnp.ones((), bool)

    alg = AlgorithmSpec("multi_ppr", "FF&AS", init, round_fn,
                        lambda g, layout: iters)
    res = run_distributed(alg, mesh, g, capacity=capacity, m=m, axis=axis,
                          spec=spec, max_subrounds=max_subrounds,
                          batch=QueryLanes(lanes, v))
    rank = res.state["rank"].reshape(-1, lanes).T[:, :v]
    return telemetry_return(rank, res, telemetry)


def pagerank_reference(g: Graph, d=0.85, iters=20):
    """NumPy oracle."""
    import numpy as np
    v = g.num_vertices
    src = np.asarray(g.src)
    dst = np.asarray(g.dst)
    indptr = np.asarray(g.indptr)
    deg = np.maximum(indptr[1:] - indptr[:-1], 1)
    dangling = (indptr[1:] - indptr[:-1]) == 0
    rank = np.full(v, 1.0 / v)
    for _ in range(iters):
        acc = np.zeros(v)
        np.add.at(acc, dst, d * rank[src] / deg[src])
        acc += d * rank[dangling].sum() / v
        rank = (1 - d) / v + acc
    return rank
