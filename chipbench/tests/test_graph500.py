"""The benchmark's copy of the Graph500 generator and its references."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

from chipbench.lib import graph500, reference  # noqa: E402


@pytest.mark.parametrize("scale,seed", [(8, 0), (10, 3), (11, 2 ** 31 + 7)])
def test_copied_generator_ingests_to_the_programs_graph(scale, seed):
    from repro.graphs.csr import from_edges
    from repro.graphs.generators import kronecker
    src, dst = graph500.kronecker_edges(scale, 16, seed,
                                        a=0.57, b=0.19, c=0.19)
    mine = from_edges(src, dst, 1 << scale, symmetrize=True)
    theirs = kronecker(scale, 16, seed=seed)
    for field in ("indptr", "src", "dst"):
        np.testing.assert_array_equal(np.asarray(getattr(mine, field)),
                                      np.asarray(getattr(theirs, field)))
    indptr, adj = graph500.csr(src, dst, 1 << scale)
    np.testing.assert_array_equal(indptr, np.asarray(theirs.indptr))
    np.testing.assert_array_equal(adj, np.asarray(theirs.dst))


def test_search_keys_have_edges_and_follow_the_seed():
    src, dst = graph500.kronecker_edges(10, 16, 5, a=0.57, b=0.19, c=0.19)
    deg = graph500.degree(src, dst, 1 << 10)
    keys = graph500.search_keys(deg, 64, 5)
    indptr, _ = graph500.csr(src, dst, 1 << 10)
    np.testing.assert_array_equal(deg > 0, np.diff(indptr) > 0)
    assert len(set(keys.tolist())) == 64
    assert (np.diff(indptr)[keys] > 0).all()
    again = graph500.search_keys(deg, 64, 5)
    np.testing.assert_array_equal(keys, again)


def test_label_seed_changes_only_the_labels():
    n = 1 << 10
    kw = dict(a=0.57, b=0.19, c=0.19)
    src1, dst1 = graph500.kronecker_edges(10, 16, 4, **kw)
    src2, dst2 = graph500.kronecker_edges(10, 16, 4, label_seed=9, **kw)
    assert not np.array_equal(src1, src2)
    relabel = graph500.relabelling(n, 4, 9)
    assert sorted(relabel.tolist()) == list(range(n))
    np.testing.assert_array_equal(relabel[src1], src2)
    np.testing.assert_array_equal(relabel[dst1], dst2)
    np.testing.assert_array_equal(graph500.relabelling(n, 4, 4),
                                  np.arange(n))
    # the edges themselves are the structure seed's, not the label seed's
    src3, _ = graph500.kronecker_edges(10, 16, 9, **kw)
    assert not np.array_equal(np.sort(np.bincount(src3, minlength=n)),
                              np.sort(np.bincount(src2, minlength=n)))


def test_a_runs_keys_are_the_configurations_keys_relabelled():
    from chipbench.drivers.common import GraphData
    conf = {"structure_seed": 3, "scale": 10, "edge_factor": 16,
            "A": 0.57, "B": 0.19, "C": 0.19}
    base = GraphData(conf, 3, None)
    run = GraphData(conf, 2 ** 31 + 11, None)
    relabel = graph500.relabelling(1 << 10, 3, 2 ** 31 + 11)
    np.testing.assert_array_equal(relabel[base.search_keys(64)],
                                  run.search_keys(64))
    for data in (base, run):
        assert graph500.degree(data.src, data.dst, 1 << 10)[
            data.isolated_vertex()] == 0


def test_references_match_plain_loops():
    src, dst = graph500.kronecker_edges(8, 8, 1, a=0.57, b=0.19, c=0.19)
    n = 1 << 8
    indptr, adj = graph500.csr(src, dst, n)
    key = int(graph500.search_keys(graph500.degree(src, dst, n), 1, 1)[0])
    # breadth-first by a queue
    want = np.full(n, reference.BFS_INF)
    want[key] = 0
    queue = [key]
    for u in queue:
        for w in adj[indptr[u]:indptr[u + 1]]:
            if want[w] == reference.BFS_INF:
                want[w] = want[u] + 1
                queue.append(w)
    dist = reference.bfs_reference(indptr, adj, key)
    np.testing.assert_array_equal(dist, want)
    assert reference.reached_edges(indptr, dist) == \
        np.diff(indptr)[dist < reference.BFS_INF].sum() // 2
    # PageRank by a loop over vertices
    deg = np.diff(indptr)
    rank = np.full(n, 1.0 / n)
    for _ in range(5):
        new = np.full(n, 0.15 / n + 0.85 * rank[deg == 0].sum() / n)
        for u in range(n):
            for w in adj[indptr[u]:indptr[u + 1]]:
                new[w] += 0.85 * rank[u] / deg[u]
        rank = new
    got = reference.pagerank_reference(indptr, adj, d=0.85, iters=5)
    np.testing.assert_allclose(got, rank, rtol=1e-12)
    assert reference.rank_error(got * (1 + 1e-3), got) == \
        pytest.approx(1e-3)


def test_depth_stratified_order_gives_every_seed_the_same_depths():
    levels = [6] * 39 + [7] * 25
    orders = [graph500.depth_stratified(levels, seed)
              for seed in (1, 2, 2 ** 31 + 3)]
    depths = [[levels[i] for i in o] for o in orders]
    assert depths[0] == depths[1] == depths[2]
    assert len({tuple(o) for o in orders}) == 3
    for o in orders:
        assert sorted(o) == list(range(64))
    for j in range(1, 65):           # every prefix holds each depth's share
        sevens = depths[0][:j].count(7)
        assert abs(sevens - 25 * j / 64) < 1
    three = [5, 6, 6, 7, 6, 9]
    o = graph500.depth_stratified(three, 4)
    assert sorted(o) == list(range(6))


def test_configured_key_levels_match_the_reference():
    conf = json.loads((ROOT / "chipbench" / "configs"
                       / "graph500-s19.json").read_text())
    from chipbench.drivers.common import GraphData
    data = GraphData(conf, conf["structure_seed"], None)
    keys = data.search_keys(conf["search_keys"])
    indptr, adj = data.csr()
    got = [reference.levels(reference.bfs_reference(indptr, adj, int(k)))
           for k in keys]
    assert got == conf["key_levels"]
