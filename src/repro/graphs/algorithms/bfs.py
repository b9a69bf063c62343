"""BFS — FF&MF atomic active messages (paper §3.3.2, Listing 4).

Label-correcting edge-centric formulation: every round, each edge whose
source is in the frontier emits a message ``(dst, dist[src]+1)``; messages
commit with the MF ``min`` operator (losers fail silently — no rollback
needed on TPU, DESIGN.md §2); the next frontier is the set of vertices whose
distance changed.  ``commit="atomic"`` is the fine-grained Graph500-style
baseline; ``commit="coarse"`` is AAM with transaction size ``m``.

A round builds its messages from ONE edge-sized gather: the frontier
folds into the vertex-sized ``fd = where(frontier, dist, INF)``, and
``fd[src]`` gives both the activity (``fd < INF``: every frontier vertex
has a finite distance) and the payload ``fd + 1``.  That is exact, not
approximate: on active lanes the payload is ``dist[src] + 1``, and every
commit tier drops inactive lanes by ``valid`` without reading their
payload.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from repro.core import autotune as AT
from repro.core import commit as C
from repro.core.messages import Messages, lane_messages, make_messages
from repro.graphs.csr import Graph

INF = jnp.int32(2 ** 30)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class BfsResult:
    dist: jax.Array
    rounds: jax.Array
    messages: jax.Array
    conflicts: jax.Array
    applied: jax.Array


@partial(jax.jit, static_argnames=("commit", "m", "sort", "spec"))
def bfs(g: Graph, source, *, commit: str = "coarse", m: int | None = None,
        sort: bool = True, spec: C.CommitSpec | None = None) -> BfsResult:
    """``spec`` names the commit backend directly; the legacy
    ``commit``/``m``/``sort`` knobs build one when it is omitted."""
    if spec is None:
        spec = C.CommitSpec(backend=commit, m=m, sort=sort, stats=False)
    v = g.num_vertices
    dist0 = jnp.full((v,), INF, jnp.int32).at[source].set(0)
    frontier0 = jnp.zeros((v,), bool).at[source].set(True)
    # backend="auto": calibrated ladder commit; the level rides the carry
    step, lvl0 = AT.make_commit_step(spec, "min", dist0,
                                     n=g.src.shape[0])

    def cond(state):
        _, frontier, it, *_ = state
        return jnp.any(frontier) & (it < v)

    def body(state):
        dist, frontier, it, lvl, nmsg, ncf, nap = state
        # one E-gather of the frontier folded into dist; lanes off the
        # frontier read INF and are dropped by ``valid`` (module doc)
        with jax.named_scope(C.MESSAGES_SCOPE):
            fd = jnp.where(frontier, dist, INF)[g.src]
            active = fd < INF
            msgs = make_messages(g.dst, fd + 1, active)
        res, lvl = step(dist, msgs, lvl)
        changed = res.state != dist
        return (res.state, changed, it + 1, lvl,
                nmsg + jnp.sum(active.astype(jnp.int32)),
                ncf + res.conflicts, nap + res.applied)

    z = jnp.zeros((), jnp.int32)
    dist, _, rounds, _, nmsg, ncf, nap = jax.lax.while_loop(
        cond, body, (dist0, frontier0, z, lvl0, z, z, z))
    return BfsResult(dist, rounds, nmsg, ncf, nap)


@partial(jax.jit, static_argnames=("commit", "m", "sort", "spec"))
def multi_source_bfs(g: Graph, sources, *, commit: str = "coarse",
                     m: int | None = None, sort: bool = True,
                     spec: C.CommitSpec | None = None) -> BfsResult:
    """L independent BFS queries as lanes of ONE fused wave.

    ``sources`` is int32 [L]; the result's ``dist`` is [L, V] — row l
    bit-identical to ``bfs(g, sources[l])`` (``min`` is order-independent,
    and lanes occupy disjoint composite key ranges ``lane * V + v``, so
    one commit per round resolves every query's conflicts at once).
    Converged lanes stop emitting messages (per-query early exit) while
    the wave keeps serving the stragglers."""
    if spec is None:
        spec = C.CommitSpec(backend=commit, m=m, sort=sort, stats=False)
    v = g.num_vertices
    sources = jnp.asarray(sources, jnp.int32)
    lanes = sources.shape[0]
    lidx = jnp.arange(lanes, dtype=jnp.int32)
    dist0 = jnp.full((lanes, v), INF, jnp.int32).at[lidx, sources].set(0)
    frontier0 = jnp.zeros((lanes, v), bool).at[lidx, sources].set(True)
    e = g.src.shape[0]
    dst_l = jnp.broadcast_to(g.dst, (lanes, e))
    # [L, E] flat ids into the lane-major state: a scalar gather keeps
    # the edge axis minor (``x[:, g.src]`` lays [L, E] out lane-minor,
    # which a TPU pads 32-fold)
    src_l = lidx[:, None] * v + g.src[None, :]
    step, lvl0 = AT.make_commit_step(spec, "min", dist0.reshape(-1),
                                     n=lanes * e, axis_width=lanes)

    def cond(state):
        _, frontier, it, *_ = state
        return jnp.any(frontier) & (it < v)

    def body(state):
        dist, frontier, it, lvl, nmsg, ncf, nap = state
        # one [L·E] gather of the frontier folded into dist (module doc);
        # converged lanes read INF and emit nothing: per-lane early exit
        with jax.named_scope(C.MESSAGES_SCOPE):
            fd = jnp.where(frontier, dist, INF).reshape(-1)[src_l]
            active = fd < INF
            msgs = lane_messages(dst_l, fd + 1, active, v)
        res, lvl = step(dist.reshape(-1), msgs, lvl)
        dist2 = res.state.reshape(lanes, v)
        return (dist2, dist2 != dist, it + 1, lvl,
                nmsg + jnp.sum(active.astype(jnp.int32)),
                ncf + res.conflicts, nap + res.applied)

    z = jnp.zeros((), jnp.int32)
    dist, _, rounds, _, nmsg, ncf, nap = jax.lax.while_loop(
        cond, body, (dist0, frontier0, z, lvl0, z, z, z))
    return BfsResult(dist, rounds, nmsg, ncf, nap)


@partial(jax.jit, static_argnums=0)
def _distributed_bfs_state(vpad: int, source):
    return {"dist": jnp.full((vpad,), INF, jnp.int32).at[source].set(0),
            "frontier": jnp.zeros((vpad,), bool).at[source].set(True)}


def _distributed_bfs_round(rt, e, st, sc, it):
    dist = st["dist"]
    active = st["frontier"][e.my_src] & e.valid
    dist2, _ = rt.wave(dist, e.dst, dist[e.my_src] + 1, active, op="min")
    changed = dist2 != dist
    return {"dist": dist2, "frontier": changed}, sc, rt.any(changed)


def _vpad_rounds(g, layout) -> int:
    return layout.vpad


def distributed_bfs(mesh, g: Graph, source: int, *,
                    capacity: int | str = 4096,
                    m: int | None = None, axis: str = "data",
                    spec: C.CommitSpec | None = None, max_subrounds: int = 64,
                    telemetry: bool = False,
                    snapshot_rounds: int | None = None,
                    fault_injector=None):
    """BFS over a mesh axis — FF&MF ``min`` waves on the shared harness.

    Returns (dist [V], rounds); ``telemetry=True`` appends the
    DistributedResult: (dist, rounds, res) — see
    :func:`repro.core.engine.telemetry_return`.  ``snapshot_rounds``/``fault_injector``
    enable the engine's degraded-mesh mode (survive a host drop by
    shrinking the mesh and replaying the last round snapshot — see
    :func:`repro.core.engine.run_distributed`).

    The round function and the round cap are module-level and the source
    enters only through ``init``'s state, so every search on one graph
    and mesh reuses one partition and one compiled loop
    (``repro.core.engine.RUNNERS``).  Over several shards the vertices are
    numbered so that every owner range holds an equal share of the edges
    (``AlgorithmSpec.balance``); distances come back by vertex id."""
    from repro.core.engine import (AlgorithmSpec, run_distributed,
                                   telemetry_return)

    def init(g, layout):
        return _distributed_bfs_state(layout.vpad, source), {}

    alg = AlgorithmSpec("bfs", "FF&MF", init, _distributed_bfs_round,
                        _vpad_rounds, reusable=True, balance=True)
    res = run_distributed(alg, mesh, g, capacity=capacity, m=m, axis=axis,
                          spec=spec, max_subrounds=max_subrounds,
                          snapshot_rounds=snapshot_rounds,
                          fault_injector=fault_injector)
    dist = res.state["dist"][:g.num_vertices]
    return telemetry_return((dist, res.rounds), res, telemetry)


def distributed_multi_source_bfs(mesh, g: Graph, sources, *,
                                 capacity: int | str = 4096,
                                 m: int | None = None, axis: str = "data",
                                 spec: C.CommitSpec | None = None,
                                 max_subrounds: int = 64,
                                 telemetry: bool = False,
                                 snapshot_rounds: int | None = None,
                                 fault_injector=None):
    """Lane-batched BFS over a mesh axis: L queries share every wave.

    Vertex state is vertex-major [vpad * L] (all lanes of a vertex live on
    its owner shard), lane ids ride the coalescing buckets as one more
    payload field, and owners commit on composite local keys — the
    distributed mirror of :func:`multi_source_bfs`.  Returns
    (dist [L, V], rounds); ``telemetry=True`` appends the
    DistributedResult: (dist, rounds, res).  ``snapshot_rounds``/
    ``fault_injector`` enable degraded-mesh mode (the vertex-major
    [vpad*L] state is not vpad-shaped, so a shrink restarts the query
    from round 0 on the surviving mesh rather than replaying)."""
    from repro.core.coalescing import QueryLanes
    from repro.core.engine import (AlgorithmSpec, run_distributed,
                                   telemetry_return)

    sources = jnp.asarray(sources, jnp.int32)
    lanes = sources.shape[0]
    lidx = jnp.arange(lanes, dtype=jnp.int32)

    def init(g, layout):
        flat = sources * lanes + lidx           # vertex-major composite
        dist0 = jnp.full((layout.vpad * lanes,), INF, jnp.int32) \
            .at[flat].set(0)
        frontier0 = jnp.zeros((layout.vpad * lanes,), bool) \
            .at[flat].set(True)
        return {"dist": dist0, "frontier": frontier0}, {}

    def round_fn(rt, e, st, sc, it):
        dist = st["dist"]                       # [block * L]
        emax = e.dst.shape[0]
        fl = e.my_src[:, None] * lanes + lidx[None, :]      # [emax, L]
        active = st["frontier"][fl] & e.valid[:, None]
        tgt = jnp.broadcast_to(e.dst[:, None], (emax, lanes))
        lane = jnp.broadcast_to(lidx[None, :], (emax, lanes))
        dist2, _ = rt.wave(dist, tgt.reshape(-1),
                           (dist[fl] + 1).reshape(-1),
                           active.reshape(-1), op="min",
                           major=lane.reshape(-1))
        changed = dist2 != dist
        return {"dist": dist2, "frontier": changed}, sc, rt.any(changed)

    alg = AlgorithmSpec("multi_bfs", "FF&MF", init, round_fn,
                        lambda g, layout: layout.vpad)
    res = run_distributed(alg, mesh, g, capacity=capacity, m=m, axis=axis,
                          spec=spec, max_subrounds=max_subrounds,
                          batch=QueryLanes(lanes, g.num_vertices),
                          snapshot_rounds=snapshot_rounds,
                          fault_injector=fault_injector)
    dist = res.state["dist"].reshape(-1, lanes).T[:, :g.num_vertices]
    return telemetry_return((dist, res.rounds), res, telemetry)


def distributed_product_bfs(mesh, gs, sources, *,
                            capacity: int | str = 4096,
                            m: int | None = None, axis: str = "data",
                            spec: C.CommitSpec | None = None,
                            max_subrounds: int = 64,
                            telemetry: bool = False):
    """Product-axis BFS over a mesh axis: L queries over EACH graph of a
    :class:`repro.graphs.csr.GraphSet` share every wave — the
    distributed proof that :class:`repro.core.coalescing.ProductAxis`
    threads through the harness unchanged.

    ``sources`` is int32 [L, G], graph-LOCAL source ids (cell (l, g)
    answers BFS from ``sources[l, g]`` in graph g).  State is
    vertex-major [vpad * L] over the UNION — the graph coordinate is
    pre-folded into the union vertex id, so each union vertex's L lanes
    live on its owner shard and the lane id rides the exchange as
    ``major`` exactly as in :func:`distributed_multi_source_bfs`; only
    ``batch=ProductAxis(L, sizes)`` (race width L·G) differs.  Returns
    (dist [L, Vtot], rounds), ``telemetry=True`` appending the
    DistributedResult; split per graph with
    ``gs.split_vertex(dist[l])``."""
    from repro.core.coalescing import ProductAxis
    from repro.core.engine import (AlgorithmSpec, run_distributed,
                                   telemetry_return)

    sources = jnp.asarray(sources, jnp.int32)
    lanes = sources.shape[0]
    lidx = jnp.arange(lanes, dtype=jnp.int32)
    product = ProductAxis(lanes, gs.axis.sizes)
    # per-cell union-flat source ids [L, G]
    flat_src = sources + jnp.asarray(gs.voffs[:-1], jnp.int32)[None, :]

    def init(g, layout):
        flat = flat_src * lanes + lidx[:, None]  # vertex-major composite
        dist0 = jnp.full((layout.vpad * lanes,), INF, jnp.int32) \
            .at[flat.reshape(-1)].set(0)
        frontier0 = jnp.zeros((layout.vpad * lanes,), bool) \
            .at[flat.reshape(-1)].set(True)
        return {"dist": dist0, "frontier": frontier0}, {}

    def round_fn(rt, e, st, sc, it):
        dist = st["dist"]                       # [block * L]
        emax = e.dst.shape[0]
        fl = e.my_src[:, None] * lanes + lidx[None, :]      # [emax, L]
        active = st["frontier"][fl] & e.valid[:, None]
        tgt = jnp.broadcast_to(e.dst[:, None], (emax, lanes))
        lane = jnp.broadcast_to(lidx[None, :], (emax, lanes))
        dist2, _ = rt.wave(dist, tgt.reshape(-1),
                           (dist[fl] + 1).reshape(-1),
                           active.reshape(-1), op="min",
                           major=lane.reshape(-1))
        changed = dist2 != dist
        return {"dist": dist2, "frontier": changed}, sc, rt.any(changed)

    alg = AlgorithmSpec("product_bfs", "FF&MF", init, round_fn,
                        lambda g, layout: layout.vpad)
    res = run_distributed(alg, mesh, gs, capacity=capacity, m=m,
                          axis=axis, spec=spec,
                          max_subrounds=max_subrounds, batch=product)
    dist = res.state["dist"].reshape(-1, lanes).T[:, :product.num_vertices]
    return telemetry_return((dist, res.rounds), res, telemetry)


def batched_over_graphs_bfs(gs, sources, *, spec: C.CommitSpec | None = None,
                            mesh=None, capacity: int | str = 4096,
                            axis: str = "data", max_subrounds: int = 64):
    """G independent BFS queries, one per tenant graph, as ONE AAM wave
    over the :class:`repro.graphs.csr.GraphSet` union (the *graph*
    batch axis — flat keys ``offset[g] + v``, see
    ``repro.core.coalescing.GraphBatch``).

    ``sources[g]`` is graph g's LOCAL source id.  Returns a list of
    per-graph distance rows, each bit-identical to
    ``bfs(gs.graphs[g], sources[g])`` on every backend including
    ``auto``: graphs exchange no messages in the union and occupy
    disjoint commit-key ranges, so the fused run IS the looped runs.
    ``mesh=`` executes through ``run_distributed`` (the union's flat
    ids key the owner slices and coalescing buckets directly)."""
    flat = gs.flat_vertices(sources)
    if mesh is not None:
        # run_distributed resolves the GraphSet itself: union edges,
        # batch=gs.axis (the tuner's axis-width key)
        dist, _ = distributed_bfs(mesh, gs, flat, spec=spec,
                                  capacity=capacity, axis=axis,
                                  max_subrounds=max_subrounds)
    else:
        dist = bfs(gs.union(), flat, spec=spec).dist
    return gs.split_vertex(dist)


def bfs_reference(g: Graph, source: int):
    """Pure-python BFS oracle (tests)."""
    import collections
    import numpy as np
    indptr = np.asarray(g.indptr)
    dst = np.asarray(g.dst)
    dist = np.full(g.num_vertices, 2 ** 30, np.int64)
    dist[source] = 0
    q = collections.deque([source])
    while q:
        u = q.popleft()
        for e in range(indptr[u], indptr[u + 1]):
            w_ = dst[e]
            if dist[w_] > dist[u] + 1:
                dist[w_] = dist[u] + 1
                q.append(w_)
    return dist
