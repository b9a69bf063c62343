"""Batch-axis serving tests (ISSUE 4 lanes + ISSUE 5 graph batches).

Four layers:

* the QueryLanes parity matrix — ``multi_source_*`` with L lanes must
  equal L looped single-query runs bit-for-bit (float ``add`` to
  rounding) on every commit backend including ``auto``, and the 1-shard
  ``run_distributed`` lane path must match the single-shard fused loops
  (the 8-device version lives in tests/test_distributed.py under the
  ``slow`` marker);
* the GraphBatch parity matrix — ``batched_over_graphs_*`` for all SIX
  algorithms (including coloring and Boruvka, which have no lane form)
  must equal the looped single-graph runs on every backend, single-shard
  and through the 1-device ``run_distributed`` union path;
* the GraphService batching layer — admission/axis choice, per-axis
  ladder padding, in-flight dedup, result cache, re-registration
  invalidation, telemetry counters;
* the satellites — per-op/axis-width autotune calibration keys, the
  persistent calibration cache, and ``capacity="auto"``
  overflow-feedback sizing.
"""
import dataclasses
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import autotune as AT
from repro.core.commit import (BACKENDS, CommitSpec, commit, commit_batched,
                               commit_lanes)
from repro.core.coalescing import GraphBatch, QueryLanes
from repro.core.messages import batch_messages, lane_messages, make_messages
from repro.graphs.csr import GraphSet
from repro.graphs.generators import (erdos_renyi, grid2d, kronecker,
                                     random_weights)
from repro.graphs.algorithms import bfs as B
from repro.graphs.algorithms import boruvka as BO
from repro.graphs.algorithms import coloring as CO
from repro.graphs.algorithms import pagerank as PR
from repro.graphs.algorithms import sssp as S
from repro.graphs.algorithms import stconn as ST

ALL_BACKENDS = BACKENDS + ("auto",)


def _graphs():
    return [("kron", kronecker(7, 8, seed=3)),
            ("uniform", erdos_renyi(150, 5.0, seed=9))]


def _sources(g, n=4):
    deg = np.asarray(g.degrees)
    picks = [int(np.argmax(deg)), 0, min(5, g.num_vertices - 1),
             int(np.argmin(deg))]
    return np.asarray(picks[:n], np.int32)


# ---------------------------------------------------------------------------
# commit_lanes / lane_messages: the composite-key layer itself
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ALL_BACKENDS)
def test_commit_lanes_equals_per_lane_commits(backend):
    """One composite-key commit == L independent commits, every backend."""
    rng = np.random.default_rng(0)
    lanes, v, n = 4, 33, 80
    state = jnp.asarray(rng.integers(0, 1000, (lanes, v)), jnp.int32)
    tgt = jnp.asarray(rng.integers(0, v, (lanes, n)), jnp.int32)
    val = jnp.asarray(rng.integers(-50, 50, (lanes, n)), jnp.int32)
    valid = jnp.asarray(rng.random((lanes, n)) < 0.8)
    spec = CommitSpec(backend=backend)
    res = commit_lanes(state, lane_messages(tgt, val, valid, v), "min",
                       spec)
    assert res.state.shape == (lanes, v)
    for l in range(lanes):
        ref = commit(state[l], make_messages(tgt[l], val[l], valid[l]),
                     "min", spec)
        np.testing.assert_array_equal(np.asarray(res.state[l]),
                                      np.asarray(ref.state),
                                      err_msg=f"lane {l} ({backend})")


# ---------------------------------------------------------------------------
# the lane-parity matrix: fused == L looped single-query runs
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ALL_BACKENDS)
@pytest.mark.parametrize("gname,g", _graphs())
def test_multi_source_bfs_parity(gname, g, backend):
    srcs = _sources(g)
    spec = CommitSpec(backend=backend, stats=False)
    ms = B.multi_source_bfs(g, jnp.asarray(srcs), spec=spec)
    assert ms.dist.shape == (len(srcs), g.num_vertices)
    for l, s in enumerate(srcs):
        one = B.bfs(g, int(s), spec=spec)
        np.testing.assert_array_equal(
            np.asarray(ms.dist[l]), np.asarray(one.dist),
            err_msg=f"{gname}/{backend} lane {l}")


@pytest.mark.parametrize("backend", ALL_BACKENDS)
def test_multi_source_sssp_parity(backend):
    g = random_weights(kronecker(7, 8, seed=3), seed=4)
    srcs = _sources(g)
    spec = CommitSpec(backend=backend, stats=False)
    dist, _ = S.multi_source_sssp(g, jnp.asarray(srcs), spec=spec)
    for l, s in enumerate(srcs):
        one, _ = S.sssp(g, int(s), spec=spec)
        np.testing.assert_array_equal(np.asarray(dist[l]), np.asarray(one),
                                      err_msg=f"{backend} lane {l}")


@pytest.mark.parametrize("backend", ALL_BACKENDS)
def test_multi_source_pagerank_parity(backend):
    g = kronecker(7, 8, seed=3)
    srcs = _sources(g)
    spec = CommitSpec(backend=backend, stats=False)
    rank, _ = PR.multi_source_pagerank(g, jnp.asarray(srcs), iters=6,
                                       spec=spec)
    for l, s in enumerate(srcs):
        one, _ = PR.personalized_pagerank(g, int(s), iters=6, spec=spec)
        # float add: the fused commit reorders each lane's accumulate
        # exactly like any transaction-size change -> rounding tolerance
        np.testing.assert_allclose(np.asarray(rank[l]), np.asarray(one),
                                   atol=1e-6, err_msg=f"{backend} lane {l}")
    # per-lane probability mass conserved
    np.testing.assert_allclose(np.asarray(rank.sum(axis=1)),
                               np.ones(len(srcs)), atol=1e-4)


@pytest.mark.parametrize("backend", ALL_BACKENDS)
def test_multi_source_stconn_parity(backend):
    g = kronecker(7, 8, seed=3)
    deg = np.asarray(g.degrees)
    # mix connected, (possibly) disconnected, and s == t lanes
    ss = np.asarray([int(np.argmax(deg)), 0, 5, 9], np.int32)
    ts = np.asarray([3, 0, int(np.argmin(deg)), 17], np.int32)
    spec = CommitSpec(backend=backend)
    found, _ = ST.multi_source_stconn(g, jnp.asarray(ss), jnp.asarray(ts),
                                      spec=spec)
    for l in range(len(ss)):
        ref = ST.st_reference(g, int(ss[l]), int(ts[l]))
        one, _ = ST.st_connectivity(g, int(ss[l]), int(ts[l]), spec=spec)
        assert bool(found[l]) == bool(one) == ref, (backend, l)


def test_multi_source_stconn_disconnected_lane():
    g = erdos_renyi(200, 1.2, seed=7)   # sparse: disconnected components
    deg = np.asarray(g.degrees)
    iso = int(np.argmin(deg))
    found, _ = ST.multi_source_stconn(g, jnp.asarray([0, iso]),
                                      jnp.asarray([3, 0]))
    for l, (a, b) in enumerate([(0, 3), (iso, 0)]):
        assert bool(found[l]) == ST.st_reference(g, a, b), l


def test_st_connectivity_s_equals_t():
    """s == t is connected by the empty path on every entry point."""
    g = kronecker(6, 4, seed=1)
    one, _ = ST.st_connectivity(g, 3, 3)
    multi, _ = ST.multi_source_stconn(g, jnp.asarray([3]), jnp.asarray([3]))
    assert bool(one) and bool(multi[0])


# ---------------------------------------------------------------------------
# single-shard fused loops == 1-shard run_distributed lane path
# ---------------------------------------------------------------------------


def test_multi_source_distributed_matches_single_shard_1dev():
    """The lane-tagged engine path on a 1-device mesh (capacity below the
    hub in-degree forces sub-round requeue of lane-tagged messages)."""
    from repro.launch.mesh import make_host_mesh
    mesh = make_host_mesh(1, 1)
    g = kronecker(7, 8, seed=3)
    gw = random_weights(g, seed=4)
    srcs = jnp.asarray(_sources(g))
    kw = dict(capacity=64, max_subrounds=256, telemetry=True)

    ms = B.multi_source_bfs(g, srcs)
    dist, _, res = B.distributed_multi_source_bfs(mesh, g, srcs, **kw)
    assert bool(res.delivered_all) and res.subrounds > res.rounds
    np.testing.assert_array_equal(np.asarray(dist), np.asarray(ms.dist))

    md, _ = S.multi_source_sssp(gw, srcs)
    dd, _, res = S.distributed_multi_source_sssp(mesh, gw, srcs, **kw)
    assert bool(res.delivered_all)
    np.testing.assert_array_equal(np.asarray(dd), np.asarray(md))

    mr, _ = PR.multi_source_pagerank(g, srcs, iters=6)
    dr, res = PR.distributed_multi_source_pagerank(mesh, g, srcs, iters=6,
                                                   **kw)
    assert bool(res.delivered_all)
    np.testing.assert_allclose(np.asarray(dr), np.asarray(mr), atol=1e-6)

    ts = jnp.asarray([3, 0, int(np.argmin(np.asarray(g.degrees))), 17],
                     jnp.int32)
    mf, _ = ST.multi_source_stconn(g, srcs, ts)
    df, _, res = ST.distributed_multi_source_stconn(mesh, g, srcs, ts, **kw)
    assert bool(res.delivered_all)
    np.testing.assert_array_equal(np.asarray(df), np.asarray(mf))


# ---------------------------------------------------------------------------
# the GraphBatch parity matrix: one fused wave over G graphs == G loops
# (the QueryLanes half of the 6-alg x backend x axis matrix is the
# multi_source_* section above; coloring/Boruvka exist only on this axis)
# ---------------------------------------------------------------------------


def _tenant_graphs(weighted: bool = False):
    """Four heterogeneous tenants: power-law, uniform, lattice, denser
    power-law — different V, E, degree regimes."""
    gs = [kronecker(5, 4, seed=1), erdos_renyi(50, 3.0, seed=2),
          grid2d(6), kronecker(6, 3, seed=7)]
    if weighted:
        gs = [random_weights(g, seed=i) for i, g in enumerate(gs)]
    return gs


GB_ALGS = ("bfs", "sssp", "ppr", "stconn", "coloring", "boruvka")


def _assert_graph_batch_parity(alg: str, backend: str, mesh=None):
    spec = CommitSpec(backend=backend, stats=False)
    kw = {} if mesh is None else dict(mesh=mesh, capacity=64,
                                      max_subrounds=256)
    graphs = _tenant_graphs(weighted=alg in ("sssp", "boruvka"))
    gs = GraphSet(graphs)
    srcs = [0, 3, 5, 1]
    tag = f"{alg}/{backend}"
    if alg == "bfs":
        rows = B.batched_over_graphs_bfs(gs, srcs, spec=spec, **kw)
        for i, (g, s) in enumerate(zip(graphs, srcs)):
            np.testing.assert_array_equal(
                np.asarray(rows[i]), np.asarray(B.bfs(g, s, spec=spec).dist),
                err_msg=f"{tag} graph {i}")
    elif alg == "sssp":
        rows = S.batched_over_graphs_sssp(gs, srcs, spec=spec, **kw)
        for i, (g, s) in enumerate(zip(graphs, srcs)):
            np.testing.assert_array_equal(
                np.asarray(rows[i]), np.asarray(S.sssp(g, s, spec=spec)[0]),
                err_msg=f"{tag} graph {i}")
    elif alg == "ppr":
        rows = PR.batched_over_graphs_pagerank(gs, srcs, iters=5, spec=spec,
                                               **kw)
        for i, (g, s) in enumerate(zip(graphs, srcs)):
            ref, _ = PR.personalized_pagerank(g, s, iters=5, spec=spec)
            # float add: the fused commit reorders each graph's
            # accumulate like any transaction-size change
            np.testing.assert_allclose(np.asarray(rows[i]), np.asarray(ref),
                                       atol=1e-6, err_msg=f"{tag} graph {i}")
    elif alg == "stconn":
        ts = [7, 7, 0, 0]
        found = ST.batched_over_graphs_stconn(gs, srcs, ts, spec=spec, **kw)
        for i, (g, s, t) in enumerate(zip(graphs, srcs, ts)):
            one, _ = ST.st_connectivity(g, s, t, spec=spec)
            ref = ST.st_reference(g, s, t)
            assert bool(found[i]) == bool(one) == ref, (tag, i)
    elif alg == "coloring":
        colors, _, not_conv = CO.batched_over_graphs_coloring(
            gs, seed=0, spec=spec, **kw)
        for i, g in enumerate(graphs):
            c1, _, nc1 = CO.coloring(g, seed=0, spec=spec)
            np.testing.assert_array_equal(np.asarray(colors[i]),
                                          np.asarray(c1),
                                          err_msg=f"{tag} graph {i}")
            assert bool(not_conv[i]) == bool(nc1), (tag, i)
            assert CO.validate_coloring(g, colors[i]), (tag, i)
    else:   # boruvka
        out, _ = BO.batched_over_graphs_boruvka(gs, spec=spec, **kw)
        for i, g in enumerate(graphs):
            comp1, w1, ne1, _ = BO.boruvka(g, spec=spec)
            comp, w, ne = out[i]
            np.testing.assert_array_equal(np.asarray(comp),
                                          np.asarray(comp1),
                                          err_msg=f"{tag} graph {i}")
            assert float(w) == float(w1) and int(ne) == int(ne1), (tag, i)


@pytest.mark.parametrize("backend", ALL_BACKENDS)
@pytest.mark.parametrize("alg", GB_ALGS)
def test_batched_over_graphs_parity_matrix(alg, backend):
    """All six algorithms x every backend (incl. auto): each batched
    element bit-identical to its unbatched run (ppr to float-add
    rounding)."""
    _assert_graph_batch_parity(alg, backend)


@pytest.mark.parametrize("alg", GB_ALGS)
def test_batched_over_graphs_distributed_1dev(alg):
    """The mesh= union path on a 1-device run_distributed (capacity 64
    forces sub-round requeue of the flat-keyed messages); the 8-device
    version lives in tests/test_distributed.py under `slow`."""
    from repro.launch.mesh import make_host_mesh
    _assert_graph_batch_parity(alg, "coarse", mesh=make_host_mesh(1, 1))


@pytest.mark.parametrize("backend", ALL_BACKENDS)
def test_graph_batch_commit_equals_per_graph_commits(backend):
    """commit_batched over GraphBatch flat keys == per-graph commits,
    every backend — the axis-level disjointness argument itself."""
    rng = np.random.default_rng(1)
    sizes = (17, 33, 8)
    ax = GraphBatch(sizes=sizes)
    states = [jnp.asarray(rng.integers(0, 1000, s), jnp.int32)
              for s in sizes]
    n = 60
    major = jnp.asarray(rng.integers(0, len(sizes), n), jnp.int32)
    minor = jnp.asarray([rng.integers(0, sizes[m]) for m in
                         np.asarray(major)], jnp.int32)
    val = jnp.asarray(rng.integers(-50, 50, n), jnp.int32)
    valid = jnp.asarray(rng.random(n) < 0.8)
    spec = CommitSpec(backend=backend)
    msgs = batch_messages(ax, major, minor, val, valid)
    res = commit_batched(jnp.concatenate(states), msgs, "min", spec,
                         axis=ax)
    offs = ax.offsets
    for gi, s in enumerate(sizes):
        mask = np.asarray(major) == gi
        ref = commit(states[gi],
                     make_messages(minor[mask], val[mask], valid[mask]),
                     "min", spec)
        np.testing.assert_array_equal(
            np.asarray(res.state[offs[gi]:offs[gi] + s]),
            np.asarray(ref.state), err_msg=f"graph {gi} ({backend})")


# ---------------------------------------------------------------------------
# GraphService: admission, lane ladder, dedup, cache
# ---------------------------------------------------------------------------


def _service(**kw):
    from repro.serve.graph_service import GraphService
    kw.setdefault("spec", CommitSpec(backend="coarse", stats=False))
    return GraphService(**kw)


def test_service_batches_pads_and_answers_correctly():
    from repro.serve.queries import BfsQuery
    g = kronecker(7, 8, seed=3)
    svc = _service(max_lanes=4)
    svc.register_graph("g", g)
    qs = [BfsQuery(int(s)) for s in (0, 5, 9)]      # 3 queries -> 4 lanes
    out = svc.run("g", qs)
    for q, row in zip(qs, out):
        ref = B.bfs(g, q.source, spec=svc.spec)
        np.testing.assert_array_equal(np.asarray(row), np.asarray(ref.dist))
    assert svc.stats.waves == 1
    assert svc.stats.lanes_executed == 4            # padded up the ladder
    assert svc.stats.lanes_padded == 1
    assert svc.pending() == 0


def test_service_lane_ladder_bounds_jit_shapes():
    from repro.serve.graph_service import _lane_ladder
    assert _lane_ladder(8) == (1, 2, 4, 8)
    assert _lane_ladder(1) == (1,)
    with pytest.raises(ValueError):
        _service(max_lanes=6)


def test_service_chunks_above_max_lanes():
    from repro.serve.queries import BfsQuery
    g = kronecker(6, 4, seed=1)
    svc = _service(max_lanes=2)
    svc.register_graph("g", g)
    out = svc.run("g", [BfsQuery(i) for i in range(5)])  # 2 + 2 + 1 lanes
    assert svc.stats.waves == 3
    assert svc.stats.lanes_executed == 5
    for i, row in enumerate(out):
        np.testing.assert_array_equal(
            np.asarray(row), np.asarray(B.bfs(g, i, spec=svc.spec).dist))


def test_service_cache_and_inflight_dedup():
    from repro.serve.queries import BfsQuery
    g = kronecker(6, 4, seed=1)
    svc = _service(max_lanes=4)
    svc.register_graph("g", g)
    t1 = svc.submit("g", BfsQuery(2))
    t2 = svc.submit("g", BfsQuery(2))        # in-flight duplicate
    assert svc.stats.deduped == 1 and svc.pending() == 1
    svc.drain()
    assert svc.stats.waves == 1 and svc.stats.lanes_executed == 1
    np.testing.assert_array_equal(np.asarray(svc.result(t1)),
                                  np.asarray(svc.result(t2)))
    t3 = svc.submit("g", BfsQuery(2))        # cache hit: no new wave
    assert svc.stats.cache_hits == 1
    np.testing.assert_array_equal(np.asarray(svc.result(t3)),
                                  np.asarray(svc.result(t1)))
    assert svc.pending() == 0 and svc.stats.waves == 1


def test_service_mixed_kinds_and_fuse_keys():
    """Different kinds (and different PPR static knobs) never share a
    wave; same-kind queries do."""
    from repro.serve.queries import BfsQuery, PprQuery, StConnQuery
    g = kronecker(6, 4, seed=1)
    svc = _service(max_lanes=4)
    svc.register_graph("g", g)
    tickets = [svc.submit("g", q) for q in (
        BfsQuery(0), PprQuery(0, iters=4), BfsQuery(3),
        PprQuery(5, iters=8), StConnQuery(0, 9), PprQuery(1, iters=4))]
    svc.drain()
    # bfs{0,3} fuse; ppr iters=4 {0,1} fuse; ppr iters=8 alone; stconn alone
    assert svc.stats.waves == 4
    ref, _ = PR.personalized_pagerank(g, 5, iters=8, spec=svc.spec)
    np.testing.assert_allclose(np.asarray(svc.result(tickets[3])),
                               np.asarray(ref), atol=1e-6)
    assert svc.result(tickets[4]) == ST.st_reference(g, 0, 9)


def test_service_distributed_execution_1dev():
    """mesh= routes waves through the distributed harness (1-device mesh
    in-process) with capacity="auto"; answers match single-shard runs."""
    from repro.launch.mesh import make_host_mesh
    from repro.serve.queries import BfsQuery, StConnQuery
    g = kronecker(6, 4, seed=1)
    svc = _service(max_lanes=2, mesh=make_host_mesh(1, 1),
                   capacity="auto")
    svc.register_graph("g", g)
    out = svc.run("g", [BfsQuery(0), BfsQuery(7), StConnQuery(0, 9)])
    for src, row in zip((0, 7), out):
        np.testing.assert_array_equal(
            np.asarray(row), np.asarray(B.bfs(g, src, spec=svc.spec).dist))
    assert out[2] == ST.st_reference(g, 0, 9)


def test_lane_key_fuse_split_roundtrip():
    from repro.core.coalescing import fuse_lane_keys, split_lane_keys
    rng = np.random.default_rng(3)
    major = jnp.asarray(rng.integers(0, 97, 50), jnp.int32)
    minor = jnp.asarray(rng.integers(0, 13, 50), jnp.int32)
    key = fuse_lane_keys(major, minor, 13)
    ma, mi = split_lane_keys(key, 13)
    np.testing.assert_array_equal(np.asarray(ma), np.asarray(major))
    np.testing.assert_array_equal(np.asarray(mi), np.asarray(minor))


def test_service_rejects_out_of_range_vertices():
    """Admission is the error boundary: under jit an out-of-range source
    would be silently dropped by the scatter (all-INF answer, cached)."""
    from repro.serve.queries import BfsQuery, StConnQuery
    g = kronecker(5, 4, seed=0)          # V=32
    svc = _service()
    svc.register_graph("g", g)
    with pytest.raises(ValueError):
        svc.submit("g", BfsQuery(g.num_vertices))
    with pytest.raises(ValueError):
        svc.submit("g", StConnQuery(0, -1))
    svc.submit("g", BfsQuery(g.num_vertices - 1))    # boundary ok


def test_service_result_retention_is_bounded():
    from repro.serve.queries import BfsQuery
    g = kronecker(5, 4, seed=0)
    svc = _service(max_lanes=2, max_results=3, max_cache=2)
    svc.register_graph("g", g)
    tickets = [svc.submit("g", BfsQuery(i)) for i in range(6)]
    svc.drain()
    assert len(svc._results) == 3 and len(svc._cache) == 2
    svc.result(tickets[-1])                      # newest retained
    with pytest.raises(KeyError):
        svc.result(tickets[0])                   # oldest evicted


def test_service_rejects_unknown_graph_and_pending_result():
    from repro.serve.queries import BfsQuery
    svc = _service()
    with pytest.raises(KeyError):
        svc.submit("nope", BfsQuery(0))
    svc.register_graph("g", kronecker(5, 4, seed=0))
    t = svc.submit("g", BfsQuery(0))
    with pytest.raises(KeyError):
        svc.result(t)                        # not drained yet
    svc.drain()
    svc.result(t)


def test_service_mixed_axes_routing():
    """Axis choice at drain with the product axis OFF: same-graph
    requests fuse as lanes, same-kind single requests across graphs
    fuse as a graph batch, and the whole-graph kinds (coloring, mst)
    ride the graph axis they finally have.  (With the default
    ``product=True`` the mixed bfs group fuses as ONE lanes×graphs
    product wave instead — tests/test_product_axis.py.)"""
    from repro.serve.queries import BfsQuery, ColoringQuery, MstQuery
    g1, g2, g3 = (kronecker(6, 4, seed=1), erdos_renyi(60, 3.0, seed=2),
                  kronecker(5, 4, seed=9))
    svc = _service(max_lanes=4, max_graphs=4, product=False)
    for gid, g in (("a", g1), ("b", g2), ("c", g3)):
        svc.register_graph(gid, g)
    ta = [svc.submit("a", BfsQuery(s)) for s in (0, 1, 2)]   # lane wave
    tb = svc.submit("b", BfsQuery(5))                        # graph batch
    tc = svc.submit("c", BfsQuery(7))
    tcol = [svc.submit(gid, ColoringQuery()) for gid in ("a", "b", "c")]
    tmst = svc.submit("b", MstQuery())
    svc.drain()
    assert svc.stats.waves == 1                  # bfs{a x3} as lanes
    assert svc.stats.graph_waves == 3            # bfs{b,c}, coloring, mst
    assert svc.stats.graphs_padded == 1          # coloring 3 -> ladder 4
    for t, s in zip(ta, (0, 1, 2)):
        np.testing.assert_array_equal(
            np.asarray(svc.result(t)),
            np.asarray(B.bfs(g1, s, spec=svc.spec).dist))
    np.testing.assert_array_equal(
        np.asarray(svc.result(tb)),
        np.asarray(B.bfs(g2, 5, spec=svc.spec).dist))
    np.testing.assert_array_equal(
        np.asarray(svc.result(tc)),
        np.asarray(B.bfs(g3, 7, spec=svc.spec).dist))
    for t, g in zip(tcol, (g1, g2, g3)):
        c1, _, _ = CO.coloring(g, seed=0)
        np.testing.assert_array_equal(np.asarray(svc.result(t)),
                                      np.asarray(c1))
    comp, w, ne = svc.result(tmst)
    bcomp, bw, bne, _ = BO.boruvka(g2)
    np.testing.assert_array_equal(np.asarray(comp), np.asarray(bcomp))
    assert float(w) == float(bw) and int(ne) == int(bne)


def test_service_graph_ladder_and_chunking():
    """> max_graphs single-query tenants chunk into several graph waves,
    each padded up the graph ladder; results stay per-tenant correct."""
    from repro.serve.queries import BfsQuery
    graphs = [kronecker(5, 4, seed=i) for i in range(5)]
    svc = _service(max_graphs=2)
    for i, g in enumerate(graphs):
        svc.register_graph(i, g)
    tickets = [svc.submit(i, BfsQuery(0)) for i in range(5)]
    svc.drain()
    assert svc.stats.graph_waves == 3            # 2 + 2 + 1
    assert svc.stats.graphs_batched == 5 and svc.stats.graphs_padded == 0
    for i, t in enumerate(tickets):
        np.testing.assert_array_equal(
            np.asarray(svc.result(t)),
            np.asarray(B.bfs(graphs[i], 0, spec=svc.spec).dist))
    with pytest.raises(ValueError):
        _service(max_graphs=3)


def test_service_reregister_invalidates_cache_and_inflight():
    """The re-registration bugfix: different topology under the same
    graph_id must never serve answers computed on the old graph —
    cached rows are purged, queued tickets void (KeyError), and
    same-topology re-registration keeps the cache warm."""
    from repro.serve.queries import BfsQuery
    g_old = kronecker(6, 4, seed=1)
    g_new = kronecker(6, 4, seed=42)             # same V, different edges
    svc = _service(max_lanes=2)
    svc.register_graph("g", g_old)
    svc.run("g", [BfsQuery(0)])                  # populates the cache
    t_inflight = svc.submit("g", BfsQuery(3))    # queued, not drained
    svc.register_graph("g", g_new)
    assert svc.stats.invalidated == 1
    with pytest.raises(KeyError):
        svc.result(t_inflight)                   # voided forever
    t = svc.submit("g", BfsQuery(0))             # would have been a stale hit
    assert svc.stats.cache_hits == 0
    svc.drain()
    np.testing.assert_array_equal(
        np.asarray(svc.result(t)),
        np.asarray(B.bfs(g_new, 0, spec=svc.spec).dist))
    svc.register_graph("g", g_new)               # same topology: no purge
    svc.submit("g", BfsQuery(0))
    assert svc.stats.cache_hits == 1 and svc.stats.invalidated == 1


# ---------------------------------------------------------------------------
# satellite: per-op / axis-width calibration keys
# ---------------------------------------------------------------------------


def test_autotune_calibration_is_per_op(tmp_path, monkeypatch):
    """`add` (MXU path) and vector payloads get their own affine fits:
    the fit cache — in-memory and on disk — is keyed by (op, payload
    dtype, payload width), not just the knob set."""
    monkeypatch.setenv(AT._CACHE_ENV, str(tmp_path / "c.json"))
    t = AT.AutoTuner(ns=(4, 16), v_cal=256, repeats=1, warmup=0)
    base = dict(sort=True, stats=False, tile_m=64, block_v=128,
                interpret=None, with_pallas=False)
    c_min = t.calibrate(**base)
    c_add = t.calibrate(op="add", dtype=jnp.float32, **base)
    c_vec = t.calibrate(op="add", dtype=jnp.float32, width=4, **base)
    assert c_min is not c_add and c_add is not c_vec
    keys = list(json.loads((tmp_path / "c.json").read_text())["entries"])
    assert len(keys) == 3
    assert any("op=add|dtype=float32|w=1" in k for k in keys)
    assert any("op=add|dtype=float32|w=4" in k for k in keys)
    assert any("op=min|dtype=int32|w=1" in k for k in keys)


def test_autotune_race_key_records_axis_width(tmp_path, monkeypatch):
    """The race is re-run (and cached) per batch-axis width: a fused
    8-wide wave must not inherit the width-1 sort-vs-scatter verdict."""
    monkeypatch.setenv(AT._CACHE_ENV, str(tmp_path / "c.json"))
    t = AT.AutoTuner(ns=(4, 16), v_cal=256, repeats=1, warmup=0)
    base = dict(sort=True, stats=False, tile_m=64, block_v=128,
                interpret=None)
    finalists = {"coarse": None, "atomic": None}
    w1 = t.race(finalists, 64, **base)
    w8 = t.race(finalists, 64, axis_width=8, **base)
    assert w1 in finalists and w8 in finalists
    race_keys = [k for k in t._cache if k[0] == "race"]
    assert len(race_keys) == 2               # distinct cache rows per width
    dkeys = list(json.loads((tmp_path / "c.json").read_text())["entries"])
    assert any("|aw=1|" in k for k in dkeys)
    assert any("|aw=8|" in k for k in dkeys)


def test_policy_for_reads_payload_dtype_and_width():
    """policy_for must hand the tuner the payload's op/dtype/width so
    vector-payload callers calibrate their own workload."""
    state = jnp.zeros((64, 4), jnp.float32)
    msgs = make_messages(jnp.asarray([1, 2], jnp.int32),
                         jnp.zeros((2, 4), jnp.float32))
    monkey_calls = {}
    tuner = AT.AutoTuner(ns=(4, 16), v_cal=256, repeats=1, warmup=0)
    orig = tuner.policy

    def spy(spec, **kw):
        monkey_calls.update(kw)
        return orig(spec, **kw)

    tuner.policy = spy
    AT.policy_for(CommitSpec(backend="auto"), state, msgs, op="add",
                  tuner=tuner, axis_width=3)
    assert monkey_calls["op"] == "add"
    assert jnp.dtype(monkey_calls["dtype"]) == jnp.float32
    assert monkey_calls["width"] == 4 and monkey_calls["axis_width"] == 3


# ---------------------------------------------------------------------------
# satellite: persistent autotune calibration cache
# ---------------------------------------------------------------------------


def test_autotune_cache_persists_across_tuners(tmp_path, monkeypatch):
    path = tmp_path / "cache.json"
    monkeypatch.setenv(AT._CACHE_ENV, str(path))
    t1 = AT.AutoTuner(ns=(4, 16), v_cal=256, repeats=1, warmup=0)
    c1 = t1.calibrate(sort=True, stats=False, tile_m=64, block_v=128,
                      interpret=None, with_pallas=False)
    doc = json.loads(path.read_text())
    assert doc["schema"] == AT.CACHE_SCHEMA and doc["entries"]
    # a fresh tuner (fresh process stand-in) must load the fits from disk
    # without running a single timed micro-commit
    t2 = AT.AutoTuner(ns=(4, 16), v_cal=256, repeats=1, warmup=0)
    monkeypatch.setattr(t2, "_time", lambda *a: pytest.fail(
        "timed micro-commit ran despite a warm disk cache"))
    c2 = t2.calibrate(sort=True, stats=False, tile_m=64, block_v=128,
                      interpret=None, with_pallas=False)
    assert c2.tiers == c1.tiers and c2.fine == c1.fine


def test_autotune_cache_off_and_corrupt(tmp_path, monkeypatch):
    # escape hatch: no file is written
    monkeypatch.setenv(AT._CACHE_ENV, "off")
    t = AT.AutoTuner(ns=(4, 16), v_cal=256, repeats=1, warmup=0)
    t.calibrate(sort=True, stats=False, tile_m=64, block_v=128,
                interpret=None, with_pallas=False)
    assert not list(tmp_path.iterdir())
    # a corrupt cache file is ignored, never fatal
    path = tmp_path / "corrupt.json"
    path.write_text("{not json")
    monkeypatch.setenv(AT._CACHE_ENV, str(path))
    t2 = AT.AutoTuner(ns=(4, 16), v_cal=256, repeats=1, warmup=0)
    cal = t2.calibrate(sort=True, stats=False, tile_m=64, block_v=128,
                       interpret=None, with_pallas=False)
    assert cal.fine.slope >= 0
    # and gets overwritten with a valid one
    assert json.loads(path.read_text())["schema"] == AT.CACHE_SCHEMA


def test_autotune_cache_keys_include_device_kind(tmp_path, monkeypatch):
    monkeypatch.setenv(AT._CACHE_ENV, str(tmp_path / "c.json"))
    t = AT.AutoTuner(ns=(4, 16), v_cal=256, repeats=1, warmup=0)
    t.calibrate(sort=True, stats=False, tile_m=64, block_v=128,
                interpret=None, with_pallas=False)
    import jax
    doc = json.loads((tmp_path / "c.json").read_text())
    assert all(k.split("|")[1] == jax.devices()[0].device_kind
               for k in doc["entries"])


# ---------------------------------------------------------------------------
# satellite: capacity="auto" overflow-feedback sizing
# ---------------------------------------------------------------------------


def test_capacity_auto_grows_on_persistent_overflow():
    from repro.core import engine as E
    from repro.launch.mesh import make_host_mesh
    mesh = make_host_mesh(1, 1)
    g = kronecker(7, 8, seed=3)
    src = int(np.argmax(np.asarray(g.degrees)))
    key = (g.num_vertices, g.num_edges, 1)
    old = E._CAPACITY_CACHE.pop(key, None)
    try:
        E._CAPACITY_CACHE[key] = 64          # force overflow on run 1
        d1, _, r1 = B.distributed_bfs(mesh, g, src, capacity="auto",
                                   max_subrounds=256, telemetry=True)
        d2, _, r2 = B.distributed_bfs(mesh, g, src, capacity="auto",
                                   max_subrounds=256, telemetry=True)
        ref = B.bfs_reference(g, src)
        for d, r in ((d1, r1), (d2, r2)):
            assert bool(r.delivered_all)
            np.testing.assert_array_equal(np.asarray(d, np.int64), ref)
        assert int(r1.capacity) == 64
        assert int(r2.capacity) > int(r1.capacity)     # telemetry grew C
        assert int(r2.subrounds) < int(r1.subrounds)
    finally:
        E._CAPACITY_CACHE.pop(key, None)
        if old is not None:
            E._CAPACITY_CACHE[key] = old


def test_capacity_auto_heuristic_bounds():
    from repro.core import engine as E
    from repro.graphs.csr import partition_edges
    g = kronecker(6, 4, seed=1)
    for p in (1, 2, 8):
        emax = partition_edges(g, p)[0][0].shape[1]
        c = E.auto_capacity(g, p, emax)
        assert E.CAPACITY_MIN <= c <= E.capacity_cap(emax)
        assert c & (c - 1) == 0              # power of two
    # quiet runs leave the cache alone; overflowing runs double it
    key = (g.num_vertices, g.num_edges, 2)
    old = E._CAPACITY_CACHE.pop(key, None)
    try:
        E._capacity_feedback(g, 2, 256, subrounds=10, rounds=10, cap=1024)
        assert key not in E._CAPACITY_CACHE
        E._capacity_feedback(g, 2, 256, subrounds=50, rounds=10, cap=1024)
        assert E._CAPACITY_CACHE[key] == 512
        E._capacity_feedback(g, 2, 512, subrounds=50, rounds=10, cap=512)
        assert E._CAPACITY_CACHE[key] == 512     # held at the cap
    finally:
        E._CAPACITY_CACHE.pop(key, None)
        if old is not None:
            E._CAPACITY_CACHE[key] = old
