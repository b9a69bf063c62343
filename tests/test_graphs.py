"""Graph algorithms vs networkx / reference oracles (property-based over
generated graph families)."""
from functools import partial

import jax
import jax.numpy as jnp
import networkx as nx
import numpy as np
import pytest
from _hypothesis_compat import given, settings, strategies as st

from repro.core import autotune as AT
from repro.core.commit import CommitSpec
from repro.core.messages import lane_messages, make_messages
from repro.graphs.csr import from_edges
from repro.graphs.generators import (erdos_renyi, grid2d, kronecker,
                                     preferential, random_weights)
from repro.graphs.algorithms import bfs as bfs_mod, sssp as sssp_mod
from repro.graphs.algorithms.bfs import bfs, bfs_reference, multi_source_bfs
from repro.graphs.algorithms.boruvka import boruvka, mst_reference
from repro.graphs.algorithms.coloring import coloring, validate_coloring
from repro.graphs.algorithms.pagerank import (multi_source_pagerank,
                                              pagerank, pagerank_reference,
                                              personalized_pagerank)
from repro.graphs.algorithms.sssp import (multi_source_sssp, sssp,
                                          sssp_reference)
from repro.graphs.algorithms.stconn import st_connectivity, st_reference

SET = dict(max_examples=10, deadline=None)
GRAPHS = [
    kronecker(8, 8, seed=1),
    erdos_renyi(300, 6.0, seed=2),
    grid2d(12),
    preferential(200, 3, seed=3),
]


@st.composite
def random_graph(draw):
    n = draw(st.integers(5, 120))
    m = draw(st.integers(0, 400))
    rng = np.random.default_rng(draw(st.integers(0, 1000)))
    src = rng.integers(0, n, m)
    dst = rng.integers(0, n, m)
    return from_edges(src, dst, n, symmetrize=True), \
        draw(st.integers(0, n - 1))


@pytest.mark.parametrize("g", GRAPHS, ids=["kron", "er", "grid", "pref"])
@pytest.mark.parametrize("commit,m", [("atomic", None), ("coarse", None),
                                      ("coarse", 64), ("coarse", 1024)])
def test_bfs_families(g, commit, m):
    src = int(np.argmax(np.asarray(g.degrees)))
    r = bfs(g, src, commit=commit, m=m)
    np.testing.assert_array_equal(np.asarray(r.dist, np.int64),
                                  bfs_reference(g, src))


@given(random_graph())
@settings(**SET)
def test_bfs_property(gs):
    g, src = gs
    if g.num_edges == 0:
        return
    r = bfs(g, src, commit="coarse", m=32)
    np.testing.assert_array_equal(np.asarray(r.dist, np.int64),
                                  bfs_reference(g, src))


@pytest.mark.parametrize("g", GRAPHS, ids=["kron", "er", "grid", "pref"])
def test_pagerank_families(g):
    pr, _ = pagerank(g, iters=15)
    ref = pagerank_reference(g, iters=15)
    assert float(np.abs(np.asarray(pr) - ref).max()) < 1e-5
    assert abs(float(jnp.sum(pr)) - 1.0) < 1e-3


def test_pagerank_atomic_equals_coarse():
    g = GRAPHS[0]
    pa, _ = pagerank(g, iters=10, commit="atomic")
    pc, _ = pagerank(g, iters=10, commit="coarse", m=256)
    np.testing.assert_allclose(np.asarray(pa), np.asarray(pc), atol=1e-6)


@pytest.mark.parametrize("g", GRAPHS, ids=["kron", "er", "grid", "pref"])
def test_sssp_families(g):
    gw = random_weights(g, seed=7)
    src = int(np.argmax(np.asarray(g.degrees)))
    d, _ = sssp(gw, src)
    ref = sssp_reference(gw, src)
    reach = ref < 1e38
    np.testing.assert_allclose(np.asarray(d)[reach], ref[reach], rtol=1e-5)


@pytest.mark.parametrize("g", GRAPHS, ids=["kron", "er", "grid", "pref"])
def test_coloring_families(g):
    col, rounds, failed = coloring(g, seed=11)
    assert not bool(failed)
    assert validate_coloring(g, col)


@given(random_graph())
@settings(**SET)
def test_coloring_property(gs):
    g, _ = gs
    if g.num_edges == 0:
        return
    col, _, failed = coloring(g, seed=3)
    assert not bool(failed) and validate_coloring(g, col)


def test_stconn_connected_and_disconnected():
    g = grid2d(10)
    f, _ = st_connectivity(g, 0, 99)
    assert bool(f) == st_reference(g, 0, 99) is True
    # two disjoint grids
    side = 6
    a = grid2d(side)
    src = np.concatenate([np.asarray(a.src), np.asarray(a.src) + side * side])
    dst = np.concatenate([np.asarray(a.dst), np.asarray(a.dst) + side * side])
    g2 = from_edges(src, dst, 2 * side * side)
    f2, _ = st_connectivity(g2, 0, side * side)
    assert not bool(f2)
    assert not st_reference(g2, 0, side * side)


@pytest.mark.parametrize("g", GRAPHS, ids=["kron", "er", "grid", "pref"])
def test_boruvka_families(g):
    gw = random_weights(g, seed=13)
    _, w, ne, _ = boruvka(gw)
    ref = mst_reference(gw)
    assert abs(float(w) - ref) / max(ref, 1) < 1e-4
    # forest size = V - #components
    G = nx.Graph()
    G.add_nodes_from(range(g.num_vertices))
    G.add_edges_from(zip(np.asarray(g.src).tolist(),
                         np.asarray(g.dst).tolist()))
    ncc = nx.number_connected_components(G)
    assert int(ne) == g.num_vertices - ncc


def test_bfs_conflict_telemetry_nonzero_on_dense_graph():
    """The abort-statistics analogue (paper Tables 3c/3f): dense graphs
    produce duplicate-target messages."""
    g = kronecker(8, 16, seed=5)
    src = int(np.argmax(np.asarray(g.degrees)))
    r = bfs(g, src, commit="coarse", m=128)
    assert int(r.conflicts) > 0
    assert int(r.applied) <= int(r.messages)


# --- one edge-sized gather a round, bit for bit --------------------------
# The round bodies as they were before the message build was folded onto
# one gather: the activity and the payload gathered over ``src`` apart.


@partial(jax.jit, static_argnames=("spec", "weighted"))
def _two_gather_min(g, sources, *, spec, weighted):
    """``bfs``/``sssp`` (``sources`` a scalar) or their lane forms (a
    vector): ``frontier[src]`` and ``dist[src]`` gathered apart.
    Returns (dist, rounds, messages, conflicts, applied)."""
    inf, dtype = ((sssp_mod.INF, jnp.float32) if weighted
                  else (bfs_mod.INF, jnp.int32))
    lanes = sources.ndim == 1
    v, e = g.num_vertices, g.src.shape[0]
    srcs = jnp.atleast_1d(sources)
    nl = srcs.shape[0]
    lidx = jnp.arange(nl, dtype=jnp.int32)
    dist0 = jnp.full((nl, v), inf, dtype).at[lidx, srcs].set(0)
    frontier0 = jnp.zeros((nl, v), bool).at[lidx, srcs].set(True)
    src_l = (lidx[:, None] * v + g.src[None, :]).reshape(-1)
    dst_l = jnp.broadcast_to(g.dst, (nl, e))
    w = g.weights if weighted else 1
    step, lvl0 = AT.make_commit_step(spec, "min", dist0.reshape(-1),
                                     n=nl * e, axis_width=nl)

    def cond(state):
        _, frontier, it, *_ = state
        return jnp.any(frontier) & (it < v)

    def body(state):
        dist, frontier, it, lvl, nmsg, ncf, nap = state
        active = frontier[src_l]
        payload = dist[src_l].reshape(nl, e) + w
        if lanes:
            msgs = lane_messages(dst_l, payload, active.reshape(nl, e), v)
        else:
            msgs = make_messages(g.dst, payload[0], active)
        res, lvl = step(dist, msgs, lvl)
        return (res.state, res.state != dist, it + 1, lvl,
                nmsg + jnp.sum(active.astype(jnp.int32)),
                ncf + res.conflicts, nap + res.applied)

    z = jnp.zeros((), jnp.int32)
    dist, _, rounds, _, nmsg, ncf, nap = jax.lax.while_loop(
        cond, body, (dist0.reshape(-1), frontier0.reshape(-1), z, lvl0,
                     z, z, z))
    return (dist.reshape(nl, v) if lanes else dist), rounds, nmsg, ncf, nap


@partial(jax.jit, static_argnames=("spec", "kind", "iters"))
def _two_gather_pagerank(g, sources, *, spec, kind, d, iters):
    """``pagerank`` (``kind`` "global"), ``personalized_pagerank``
    ("ppr") or ``multi_source_pagerank`` ("lanes"): ``rank[src]`` and
    ``deg[src]`` gathered apart.  Returns (rank, conflicts)."""
    v, e = g.num_vertices, g.src.shape[0]
    deg = jnp.maximum(g.degrees, 1).astype(jnp.float32)
    dangling = g.degrees == 0
    z = jnp.zeros((), jnp.int32)
    if kind == "lanes":
        nl = sources.shape[0]
        lidx = jnp.arange(nl, dtype=jnp.int32)
        restart = jnp.zeros((nl, v), jnp.float32).at[lidx, sources].set(1.0)
        dst_l = jnp.broadcast_to(g.dst, (nl, e))
        acc0 = jnp.zeros((nl * v,), jnp.float32)
        step, lvl0 = AT.make_commit_step(spec, "add", acc0, n=nl * e,
                                         axis_width=nl)

        def body(carry, _):
            rank, conflicts, lvl = carry
            contrib = d * rank[:, g.src] / deg[g.src][None, :]
            msgs = lane_messages(dst_l, contrib, jnp.ones((nl, e), bool), v)
            res, lvl = step(acc0, msgs, lvl)
            dangle = d * jnp.sum(jnp.where(dangling[None, :], rank, 0.0),
                                 axis=1)
            rank = restart * ((1.0 - d) + dangle[:, None]) \
                + res.state.reshape(nl, v)
            return (rank, conflicts + res.conflicts, lvl), None

        (rank, conflicts, _), _ = jax.lax.scan(
            body, (restart, z, lvl0), None, length=iters)
        return rank, conflicts
    acc0 = jnp.zeros((v,), jnp.float32)
    step, lvl0 = AT.make_commit_step(spec, "add", acc0, n=e)
    restart = jnp.zeros((v,), jnp.float32).at[sources].set(1.0)

    def body(carry, _):
        rank, conflicts, lvl = carry
        contrib = d * rank[g.src] / deg[g.src]
        msgs = make_messages(g.dst, contrib, jnp.ones_like(g.src, bool))
        res, lvl = step(acc0, msgs, lvl)
        if kind == "global":
            dangle = d * jnp.sum(jnp.where(dangling, rank, 0.0)) / v
            rank = (1.0 - d) / v + res.state + dangle
        else:
            dangle = d * jnp.sum(jnp.where(dangling, rank, 0.0))
            rank = restart * ((1.0 - d) + dangle) + res.state
        return (rank, conflicts + res.conflicts, lvl), None

    rank0 = (jnp.full((v,), 1.0 / v, jnp.float32) if kind == "global"
             else restart)
    (rank, conflicts, _), _ = jax.lax.scan(
        body, (rank0, z, lvl0), None, length=iters)
    return rank, conflicts


FOLD_GRAPH = random_weights(kronecker(9, 8, seed=4), seed=3)
FOLD_SPECS = {"atomic": CommitSpec(backend="atomic"),
              "coarse": CommitSpec(backend="coarse", m=256)}


def _fold_sources(g):
    """The hub, a vertex of median degree, and an isolated vertex (its
    lane converges in round one)."""
    deg = np.asarray(g.degrees)
    order = np.argsort(deg, kind="stable")
    return jnp.asarray([order[-1], order[len(order) // 2], order[0]],
                       jnp.int32)


@pytest.mark.parametrize("backend", sorted(FOLD_SPECS))
@pytest.mark.parametrize("entry", [
    "bfs", "multi_source_bfs", "sssp", "multi_source_sssp", "pagerank",
    "personalized_pagerank", "multi_source_pagerank"])
def test_one_gather_messages_match_two_gather_body(entry, backend):
    """Folding each source vertex's activity and payload (or PageRank's
    ``d * rank / deg``) into one vertex-sized vector before the gather
    leaves every output bit-identical: distances and ranks, rounds, and
    the message, conflict and applied counters."""
    g, spec = FOLD_GRAPH, FOLD_SPECS[backend]
    srcs = _fold_sources(g)
    assert int(g.degrees[srcs[2]]) == 0 and int(g.degrees[srcs[0]]) > 0
    if entry in ("bfs", "multi_source_bfs", "sssp", "multi_source_sssp"):
        weighted = entry.endswith("sssp")
        sources = srcs if entry.startswith("multi") else srcs[0]
        fn = {"bfs": bfs, "multi_source_bfs": multi_source_bfs,
              "sssp": sssp, "multi_source_sssp": multi_source_sssp}[entry]
        out = fn(g, sources, spec=spec)
        got = ((out.dist, out.rounds, out.messages, out.conflicts,
                out.applied) if not weighted else tuple(out))
        want = _two_gather_min(g, sources, spec=spec, weighted=weighted)
        assert int(want[1]) > 2
    else:
        kw = dict(d=0.85, iters=10, spec=spec)
        if entry == "pagerank":
            got = pagerank(g, **kw)
            want = _two_gather_pagerank(g, srcs[0], kind="global", **kw)
        elif entry == "personalized_pagerank":
            got = personalized_pagerank(g, srcs[0], **kw)
            want = _two_gather_pagerank(g, srcs[0], kind="ppr", **kw)
        else:
            got = multi_source_pagerank(g, srcs, **kw)
            want = _two_gather_pagerank(g, srcs, kind="lanes", **kw)
    for a, b in zip(got, want, strict=False):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
