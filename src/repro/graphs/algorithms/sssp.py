"""SSSP (Bellman-Ford label-correcting) — FF&MF messages, weighted ``min``
commit.  Same AAM structure as BFS with ``dist[src] + w`` payloads, built
from the same one-gather fold: ``fd = where(frontier, dist, INF)[src]``,
active where ``fd < INF``, payload ``fd + w`` (see :mod:`.bfs`)."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core import autotune as AT
from repro.core import commit as C
from repro.core.messages import lane_messages, make_messages
from repro.graphs.csr import Graph

INF = jnp.float32(3.0e38)


@partial(jax.jit, static_argnames=("commit", "m", "sort", "spec"))
def sssp(g: Graph, source, *, commit: str = "coarse", m: int | None = None,
         sort: bool = True, spec: C.CommitSpec | None = None):
    if spec is None:
        spec = C.CommitSpec(backend=commit, m=m, sort=sort, stats=False)
    v = g.num_vertices
    dist0 = jnp.full((v,), INF, jnp.float32).at[source].set(0.0)
    frontier0 = jnp.zeros((v,), bool).at[source].set(True)
    step, lvl0 = AT.make_commit_step(spec, "min", dist0,
                                     n=g.src.shape[0])

    def cond(state):
        _, frontier, it, _ = state
        return jnp.any(frontier) & (it < v)

    def body(state):
        dist, frontier, it, lvl = state
        # one E-gather; lanes off the frontier read INF, dropped by valid
        fd = jnp.where(frontier, dist, INF)[g.src]
        active = fd < INF
        msgs = make_messages(g.dst, fd + g.weights, active)
        res, lvl = step(dist, msgs, lvl)
        return res.state, res.state != dist, it + 1, lvl

    dist, _, rounds, _ = jax.lax.while_loop(
        cond, body, (dist0, frontier0, jnp.zeros((), jnp.int32), lvl0))
    return dist, rounds


@partial(jax.jit, static_argnames=("commit", "m", "sort", "spec"))
def multi_source_sssp(g: Graph, sources, *, commit: str = "coarse",
                      m: int | None = None, sort: bool = True,
                      spec: C.CommitSpec | None = None):
    """L independent SSSP roots as lanes of one fused wave.

    Returns (dist [L, V], rounds); row l is bit-identical to
    ``sssp(g, sources[l])`` — f32 ``min`` over the same relaxation
    multiset is order-independent, so the composite-key commit
    (``lane * V + v``) changes nothing per lane."""
    if spec is None:
        spec = C.CommitSpec(backend=commit, m=m, sort=sort, stats=False)
    v = g.num_vertices
    sources = jnp.asarray(sources, jnp.int32)
    lanes = sources.shape[0]
    lidx = jnp.arange(lanes, dtype=jnp.int32)
    dist0 = jnp.full((lanes, v), INF, jnp.float32) \
        .at[lidx, sources].set(0.0)
    frontier0 = jnp.zeros((lanes, v), bool).at[lidx, sources].set(True)
    e = g.src.shape[0]
    dst_l = jnp.broadcast_to(g.dst, (lanes, e))
    step, lvl0 = AT.make_commit_step(spec, "min", dist0.reshape(-1),
                                     n=lanes * e, axis_width=lanes)

    def cond(state):
        _, frontier, it, _ = state
        return jnp.any(frontier) & (it < v)

    def body(state):
        dist, frontier, it, lvl = state
        # one [L, E] gather; lanes off the frontier read INF, dropped
        fd = jnp.where(frontier, dist, INF)[:, g.src]
        active = fd < INF
        msgs = lane_messages(dst_l, fd + g.weights[None, :], active, v)
        res, lvl = step(dist.reshape(-1), msgs, lvl)
        dist2 = res.state.reshape(lanes, v)
        return dist2, dist2 != dist, it + 1, lvl

    dist, _, rounds, _ = jax.lax.while_loop(
        cond, body, (dist0, frontier0, jnp.zeros((), jnp.int32), lvl0))
    return dist, rounds


def distributed_sssp(mesh, g: Graph, source: int, *,
                     capacity: int | str = 4096,
                     m: int | None = None, axis: str = "data",
                     spec: C.CommitSpec | None = None,
                     max_subrounds: int = 64, telemetry: bool = False):
    """Bellman-Ford SSSP on the shared harness — FF&MF waves whose f32
    relaxation payloads ride next to the i32 targets in the same coalescing
    buckets.  Returns (dist [V], rounds); ``telemetry=True`` appends
    the DistributedResult: (dist, rounds, res) — see
    :func:`repro.core.engine.telemetry_return`."""
    from repro.core.engine import (AlgorithmSpec, run_distributed,
                                   telemetry_return)

    def init(g, layout):
        dist0 = jnp.full((layout.vpad,), INF, jnp.float32).at[source].set(0.0)
        frontier0 = jnp.zeros((layout.vpad,), bool).at[source].set(True)
        return {"dist": dist0, "frontier": frontier0}, {}

    def round_fn(rt, e, st, sc, it):
        dist = st["dist"]
        active = st["frontier"][e.my_src] & e.valid
        dist2, _ = rt.wave(dist, e.dst, dist[e.my_src] + e.weight, active,
                           op="min")
        changed = dist2 != dist
        return {"dist": dist2, "frontier": changed}, sc, rt.any(changed)

    alg = AlgorithmSpec("sssp", "FF&MF", init, round_fn,
                        lambda g, layout: layout.vpad)
    res = run_distributed(alg, mesh, g, capacity=capacity, m=m, axis=axis,
                          spec=spec, max_subrounds=max_subrounds)
    dist = res.state["dist"][:g.num_vertices]
    return telemetry_return((dist, res.rounds), res, telemetry)


def distributed_multi_source_sssp(mesh, g: Graph, sources, *,
                                  capacity: int | str = 4096,
                                  m: int | None = None, axis: str = "data",
                                  spec: C.CommitSpec | None = None,
                                  max_subrounds: int = 64,
                                  telemetry: bool = False):
    """Lane-batched Bellman-Ford over a mesh axis (vertex-major
    [vpad * L] state, lane ids riding the coalescing buckets) — the
    distributed mirror of :func:`multi_source_sssp`.  Returns
    (dist [L, V], rounds); ``telemetry=True`` appends the
    DistributedResult: (dist, rounds, res)."""
    from repro.core.coalescing import QueryLanes
    from repro.core.engine import (AlgorithmSpec, run_distributed,
                                   telemetry_return)

    sources = jnp.asarray(sources, jnp.int32)
    lanes = sources.shape[0]
    lidx = jnp.arange(lanes, dtype=jnp.int32)

    def init(g, layout):
        flat = sources * lanes + lidx
        dist0 = jnp.full((layout.vpad * lanes,), INF, jnp.float32) \
            .at[flat].set(0.0)
        frontier0 = jnp.zeros((layout.vpad * lanes,), bool) \
            .at[flat].set(True)
        return {"dist": dist0, "frontier": frontier0}, {}

    def round_fn(rt, e, st, sc, it):
        dist = st["dist"]
        emax = e.dst.shape[0]
        fl = e.my_src[:, None] * lanes + lidx[None, :]
        active = st["frontier"][fl] & e.valid[:, None]
        tgt = jnp.broadcast_to(e.dst[:, None], (emax, lanes))
        lane = jnp.broadcast_to(lidx[None, :], (emax, lanes))
        dist2, _ = rt.wave(dist, tgt.reshape(-1),
                           (dist[fl] + e.weight[:, None]).reshape(-1),
                           active.reshape(-1), op="min",
                           major=lane.reshape(-1))
        changed = dist2 != dist
        return {"dist": dist2, "frontier": changed}, sc, rt.any(changed)

    alg = AlgorithmSpec("multi_sssp", "FF&MF", init, round_fn,
                        lambda g, layout: layout.vpad)
    res = run_distributed(alg, mesh, g, capacity=capacity, m=m, axis=axis,
                          spec=spec, max_subrounds=max_subrounds,
                          batch=QueryLanes(lanes, g.num_vertices))
    dist = res.state["dist"].reshape(-1, lanes).T[:, :g.num_vertices]
    return telemetry_return((dist, res.rounds), res, telemetry)


def batched_over_graphs_sssp(gs, sources, *,
                             spec: C.CommitSpec | None = None,
                             mesh=None, capacity: int | str = 4096,
                             axis: str = "data", max_subrounds: int = 64):
    """G independent SSSP queries, one per tenant graph, fused on the
    graph batch axis (disjoint-union flat keys — see
    :func:`repro.graphs.algorithms.bfs.batched_over_graphs_bfs`).
    ``sources[g]`` is graph g's LOCAL root.  Returns per-graph f32
    distance rows, bit-identical to ``sssp(gs.graphs[g], sources[g])``
    on every backend (f32 ``min`` over the same relaxation multiset is
    order-independent)."""
    flat = gs.flat_vertices(sources)
    if mesh is not None:
        dist, _ = distributed_sssp(mesh, gs, flat, spec=spec,
                                   capacity=capacity, axis=axis,
                                   max_subrounds=max_subrounds)
    else:
        dist, _ = sssp(gs.union(), flat, spec=spec)
    return gs.split_vertex(dist)


def sssp_reference(g: Graph, source: int):
    import heapq
    import numpy as np
    indptr = np.asarray(g.indptr)
    dst = np.asarray(g.dst)
    w = np.asarray(g.weights)
    dist = np.full(g.num_vertices, np.inf)
    dist[source] = 0.0
    pq = [(0.0, source)]
    while pq:
        du, u = heapq.heappop(pq)
        if du > dist[u]:
            continue
        for e in range(indptr[u], indptr[u + 1]):
            nd = du + w[e]
            if nd < dist[dst[e]]:
                dist[dst[e]] = nd
                heapq.heappush(pq, (nd, int(dst[e])))
    return dist
