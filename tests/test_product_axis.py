"""The lanes×graphs product axis (ISSUE 7): ProductAxis flat keys must
be a bijection that exactly composes QueryLanes over GraphBatch
(degenerate cases equivalent key-for-key), commit_product must equal
per-cell commits on every backend, and the product wave executor must
return each cell the answer its single-query run would — including
cells inserted at a round boundary of a RUNNING wave — so the service
can fuse a mixed tenant load into ONE wave."""
import numpy as np
import jax.numpy as jnp
import pytest

from _hypothesis_compat import given, settings, strategies as st

from repro.core import commit as C
from repro.core.coalescing import GraphBatch, ProductAxis, QueryLanes
from repro.core.commit import BACKENDS, CommitSpec
from repro.core.messages import make_messages, product_messages
from repro.graphs.csr import GraphSet
from repro.graphs.generators import erdos_renyi, kronecker
from repro.serve.graph_service import GraphService
from repro.serve.product_wave import PRODUCT_KINDS, ProductWave
from repro.serve.queries import (BfsQuery, PprQuery, SsspQuery,
                                 StConnQuery)

ALL_BACKENDS = BACKENDS + ("auto",)


@st.composite
def _axes(draw):
    lanes = draw(st.integers(1, 5))
    sizes = tuple(draw(st.lists(st.integers(1, 9), min_size=1,
                                max_size=5)))
    return ProductAxis(lanes, sizes)


CELLS = 256     # >= 5 lanes x 5 graphs x 9 vertices, the largest _axes


def _key_ops(axis, cells):
    """flatten3 then split3 over a [CELLS, 3] (lane, graph, v) block."""
    keys = axis.flatten3(*(jnp.asarray(c) for c in cells.T))
    back = np.stack([np.asarray(x) for x in axis.split3(keys)], axis=1)
    return np.asarray(keys), back


@pytest.fixture(scope="module")
def key_ops_compiled():
    """Compile the key ops for every graph count the strategies draw
    (their shapes depend on G only) before the properties run; otherwise
    the first examples pay the compiles inside Hypothesis's 200 ms
    deadline."""
    for g in range(1, 6):
        sizes = (3,) * g
        axis = ProductAxis(2, sizes)
        _key_ops(axis, np.zeros((CELLS, 3), np.int32))
        axis.split3(int(axis.flatten3(1, g - 1, 2)))
        int(GraphBatch(sizes).flatten(g - 1, 2))
    keys = jnp.zeros((16,), jnp.int32)
    axis.unflatten(axis.flatten(keys, keys))


@pytest.mark.usefixtures("key_ops_compiled")
@settings(max_examples=40)
@given(_axes(), st.integers(0, 2 ** 31 - 1))
def test_product_flat_keys_bijective(axis, seed):
    """flatten3 over every (lane, graph, v) cell-vertex hits each key in
    [0, flat_size) exactly once, and split3 inverts it — including the
    L=1 and G=1 degenerate shapes.  The cells go through in one
    fixed-size block (padded with copies of the first cell)."""
    cells = np.asarray([(lane, g, v) for lane in range(axis.lanes)
                        for g, sz in enumerate(axis.sizes)
                        for v in range(sz)], np.int32)
    padded = np.concatenate([cells, np.repeat(cells[:1], CELLS - len(cells),
                                              axis=0)])
    keys, back = _key_ops(axis, padded)
    np.testing.assert_array_equal(back, padded)
    keys = keys[:len(cells)].tolist()
    assert sorted(keys) == list(range(axis.flat_size))
    # the two-level protocol agrees with the three-level helper
    rng = np.random.default_rng(seed)
    lane = jnp.asarray(rng.integers(0, axis.lanes, 16), jnp.int32)
    minor = jnp.asarray(rng.integers(0, axis.num_vertices, 16), jnp.int32)
    major2, minor2 = axis.unflatten(axis.flatten(lane, minor))
    np.testing.assert_array_equal(np.asarray(major2), np.asarray(lane))
    np.testing.assert_array_equal(np.asarray(minor2), np.asarray(minor))


@pytest.mark.usefixtures("key_ops_compiled")
@settings(max_examples=25)
@given(st.lists(st.integers(1, 9), min_size=1, max_size=5))
def test_product_of_one_lane_is_graph_batch(sizes):
    """ProductAxis(1, sizes) IS GraphBatch(sizes), key for key."""
    sizes = tuple(sizes)
    prod, gb = ProductAxis(1, sizes), GraphBatch(sizes)
    assert prod.flat_size == gb.flat_size
    for g, sz in enumerate(sizes):
        for v in range(sz):
            assert int(prod.flatten3(0, g, v)) == int(gb.flatten(g, v))


@settings(max_examples=25)
@given(st.integers(1, 6), st.integers(1, 12))
def test_product_of_one_graph_is_query_lanes(lanes, v):
    """ProductAxis(L, (V,)) IS QueryLanes(L, V), key for key."""
    prod, ql = ProductAxis(lanes, (v,)), QueryLanes(lanes, v)
    assert prod.flat_size == ql.flat_size
    assert prod.wave_width == ql.wave_width
    for lane in range(lanes):
        for u in range(v):
            assert int(prod.flatten3(lane, 0, u)) == \
                int(ql.flatten(lane, u))


def test_product_axis_validation():
    with pytest.raises(ValueError):
        ProductAxis(0, (3,))
    with pytest.raises(ValueError):
        ProductAxis(2, ())
    with pytest.raises(ValueError):
        ProductAxis(2, (3, 0))
    axis = ProductAxis(3, (4, 2))
    assert axis.wave_width == 3 and axis.race_width == 6
    assert axis.flat_size == 18


@pytest.mark.parametrize("backend", ALL_BACKENDS)
@pytest.mark.parametrize("op", ["min", "add"])
def test_commit_product_equals_per_cell_commits(backend, op):
    """One product commit over [L * Vtot] == L × G independent per-cell
    commits: disjoint composite key ranges mean no cross-cell race, on
    every backend (bit-identical — per-cell message multisets match)."""
    rng = np.random.default_rng(7)
    axis = ProductAxis(3, (5, 8, 4))
    L, vt = axis.lanes, axis.num_vertices
    spec = CommitSpec(backend=backend, stats=False)
    dtype = np.float32 if op == "add" else np.int32
    state = rng.integers(1, 50, (L, vt)).astype(dtype)
    n = 60
    lane = rng.integers(0, L, n).astype(np.int32)
    gsel = rng.integers(0, axis.num_graphs, n).astype(np.int32)
    local = (rng.integers(0, 100, n) % np.asarray(axis.sizes)[gsel]) \
        .astype(np.int32)
    tgt_union = np.asarray(axis.offsets)[gsel] + local
    pay = rng.integers(0, 30, n).astype(dtype)
    valid = rng.random(n) < 0.8

    # [L, n] layout: message j is live only on its own lane's row
    msgs = product_messages(
        jnp.asarray(np.where(lane[None, :] == np.arange(L)[:, None],
                             tgt_union[None, :], 0), jnp.int32),
        jnp.asarray(np.where(lane[None, :] == np.arange(L)[:, None],
                             pay[None, :], 0).astype(dtype)),
        jnp.asarray((lane[None, :] == np.arange(L)[:, None])
                    & valid[None, :]),
        axis)
    res = C.commit(jnp.asarray(state.reshape(-1)), msgs, op, spec)
    fused = np.asarray(res.state).reshape(L, vt)

    # reference: each (lane, graph) cell commits alone
    expect = state.copy()
    for l in range(L):
        for g in range(axis.num_graphs):
            lo, hi = int(axis.offsets[g]), int(axis.offsets[g]) \
                + axis.sizes[g]
            sel = (lane == l) & (gsel == g) & valid
            cell = C.commit(jnp.asarray(state[l, lo:hi]),
                            make_messages(local[sel],
                                          jnp.asarray(pay[sel]),
                                          jnp.ones(sel.sum(), bool)),
                            op, spec)
            expect[l, lo:hi] = np.asarray(cell.state)
    np.testing.assert_array_equal(fused, expect)


def _gs():
    return GraphSet([kronecker(5, 6, seed=3), erdos_renyi(40, 4.0, seed=9),
                     erdos_renyi(24, 3.0, seed=1)])


def _cells(kind):
    if kind == "bfs":
        return [(0, 0, BfsQuery(1)), (1, 0, BfsQuery(5)),
                (0, 1, BfsQuery(0)), (1, 2, BfsQuery(7))]
    if kind == "sssp":
        return [(0, 0, SsspQuery(2)), (1, 1, SsspQuery(8)),
                (0, 2, SsspQuery(3))]
    if kind == "ppr":
        return [(0, 0, PprQuery(2, iters=6)), (1, 2, PprQuery(3, iters=6)),
                (0, 1, PprQuery(0, iters=6))]
    return [(0, 0, StConnQuery(0, 17)), (1, 1, StConnQuery(2, 2)),
            (0, 2, StConnQuery(0, 23))]


def _solo(kind, g, q, spec):
    """The single-query reference each cell must reproduce."""
    if kind == "bfs":
        from repro.graphs.algorithms.bfs import bfs
        return np.asarray(bfs(g, q.source, spec=spec).dist)
    if kind == "sssp":
        from repro.graphs.algorithms.sssp import sssp
        return np.asarray(sssp(g, q.source, spec=spec)[0])
    if kind == "ppr":
        from repro.graphs.algorithms.pagerank import personalized_pagerank
        return np.asarray(personalized_pagerank(g, q.source, iters=q.iters,
                                                d=q.d, spec=spec)[0])
    from repro.graphs.algorithms.stconn import st_connectivity
    return bool(st_connectivity(g, q.s, q.t, spec=spec)[0])


def _check(kind, got, want):
    if kind == "stconn":
        assert got == want
    elif kind == "ppr":      # float add: rounding-level, like any M change
        np.testing.assert_allclose(np.asarray(got), want, atol=1e-6)
    else:
        np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("kind", PRODUCT_KINDS)
@pytest.mark.parametrize("backend", ("coarse", "pallas", "auto"))
def test_product_wave_matches_solo_runs(kind, backend):
    """A partially-occupied L×G product wave answers every cell exactly
    as the cell's own single-query run (int kinds bit-identical; ppr to
    float-add rounding)."""
    gs = _gs()
    spec = CommitSpec(backend=backend, stats=False)
    fuse = {"iters": 6, "d": 0.85} if kind == "ppr" else {}
    wave = ProductWave(kind, gs, 2, spec=spec, fuse=fuse)
    for lane, g, q in _cells(kind):
        wave.insert(lane, g, q)
    wave.run()
    for lane, g, q in _cells(kind):
        assert wave.cell_done(lane, g)
        _check(kind, wave.extract(lane, g),
               _solo(kind, gs.graphs[g], q, spec))


@pytest.mark.parametrize("kind", PRODUCT_KINDS)
def test_product_wave_insert_mid_run_parity(kind):
    """A cell inserted at round k of a RUNNING wave (the continuous-
    batching boarding step) gets the same answer as boarding at round 0:
    disjoint key ranges make its per-round message multiset identical to
    an idle run's."""
    gs = _gs()
    spec = CommitSpec(backend="coarse", stats=False)
    fuse = {"iters": 6, "d": 0.85} if kind == "ppr" else {}
    cells = _cells(kind)
    wave = ProductWave(kind, gs, 2, spec=spec, fuse=fuse, round_chunk=2)
    lane0, g0, q0 = cells[0]
    wave.insert(lane0, g0, q0)
    wave.run_chunk()                       # 2 rounds in
    for lane, g, q in cells[1:]:
        wave.insert(lane, g, q)            # board the running wave
    while not wave.run_chunk():
        pass
    for lane, g, q in cells:
        _check(kind, wave.extract(lane, g),
               _solo(kind, gs.graphs[g], q, spec))


def test_product_wave_release_reuses_slot():
    gs = _gs()
    wave = ProductWave("bfs", gs, 1, round_chunk=3)
    wave.insert(0, 0, BfsQuery(1))
    wave.run()
    first = np.asarray(wave.extract(0, 0))
    wave.release(0, 0)
    assert wave.done and not wave.occupied.any()
    wave.insert(0, 0, BfsQuery(9))
    wave.run()
    _check("bfs", wave.extract(0, 0), _solo("bfs", gs.graphs[0],
                                            BfsQuery(9), wave.spec))
    assert not np.array_equal(np.asarray(wave.extract(0, 0)), first)


def test_graph_only_kinds_refused():
    with pytest.raises(ValueError):
        ProductWave("coloring", _gs(), 2)


def _mixed_service(**kw):
    svc = GraphService(**kw)
    svc.register_graph("hot", kronecker(5, 6, seed=3))
    for i in range(5):
        svc.register_graph(f"t{i}", erdos_renyi(30 + 6 * i, 4.0, seed=i))
    return svc


def test_mixed_workload_drains_as_one_product_wave():
    """THE acceptance shape: 1 hot graph × 3 lane queries + 5 single-
    query tenants is ONE product wave — not a lane wave plus a graph
    batch — and the answers match the single-axis drain bit-for-bit."""
    svc = _mixed_service()
    tickets = [svc.submit("hot", BfsQuery(s)) for s in (1, 5, 9)]
    tickets += [svc.submit(f"t{i}", BfsQuery(i + 2)) for i in range(5)]
    svc.drain()
    st = svc.stats
    assert st.product_waves == 1
    assert st.waves == 0 and st.graph_waves == 0
    assert st.product_cells == 4 * 6          # ladder width 4 × 6 graphs
    assert st.product_cells_padded == 4 * 6 - 8
    ref = _mixed_service(product=False)
    rt = [ref.submit("hot", BfsQuery(s)) for s in (1, 5, 9)]
    rt += [ref.submit(f"t{i}", BfsQuery(i + 2)) for i in range(5)]
    ref.drain()
    assert ref.stats.product_waves == 0
    assert ref.stats.waves >= 1 and ref.stats.graph_waves >= 1
    for a, b in zip(tickets, rt):
        np.testing.assert_array_equal(np.asarray(svc.result(a)),
                                      np.asarray(ref.result(b)))


def test_single_axis_groups_keep_their_axes():
    """Pure shapes stay on the cheaper single axis: all-singles still
    graph-batch, one multi-query graph still lane-fuses — the product
    path only fires on genuinely mixed groups."""
    svc = _mixed_service()
    for i in range(4):
        svc.submit(f"t{i}", BfsQuery(1))
    svc.drain()
    assert svc.stats.graph_waves == 1 and svc.stats.product_waves == 0
    svc2 = _mixed_service()
    for s in (1, 5, 9):
        svc2.submit("hot", BfsQuery(s))
    svc2.drain()
    assert svc2.stats.waves == 1 and svc2.stats.product_waves == 0


def test_product_snapshot_roundtrip():
    """The product flag rides the snapshot config."""
    svc = _mixed_service(product=False)
    restored = GraphService.restore(svc.snapshot())
    assert restored.product is False
    assert GraphService.restore(_mixed_service().snapshot()).product


def test_distributed_product_bfs_parity():
    """The engine-level proof: run_distributed with
    batch=ProductAxis(L, sizes) serves L queries over each tenant graph
    in one harness run, bit-identical per cell."""
    import jax
    from jax.sharding import Mesh
    from repro.graphs.algorithms.bfs import bfs, distributed_product_bfs

    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    gs = GraphSet([kronecker(5, 6, seed=3), erdos_renyi(40, 4.0, seed=9)])
    sources = jnp.asarray([[1, 0], [5, 7]], jnp.int32)      # [L=2, G=2]
    dist, rounds = distributed_product_bfs(mesh, gs, sources)
    assert int(rounds) > 0
    for lane in range(2):
        rows = gs.split_vertex(dist[lane])
        for g in range(2):
            np.testing.assert_array_equal(
                np.asarray(rows[g]),
                np.asarray(bfs(gs.graphs[g],
                               int(sources[lane, g])).dist))
