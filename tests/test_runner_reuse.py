"""Runner reuse in ``run_distributed``: searches on one graph and mesh
share one partition and one compiled round loop (``engine.RUNNERS``),
while every change of graph, spec or wave tap builds a runner of its
own, and a round function that captures per-call data builds one per
call that is never kept.

The scenarios run in one child on 4 forced host devices (the
``run_devices`` pattern of ``tests/test_distributed.py``); each test
checks its own part of the child's result.
"""
import pytest

from test_distributed import run_devices

CHILD = """
import json, os
import numpy as np, jax, jax.numpy as jnp
from repro.core import engine as E
from repro.core.commit import CommitSpec
from repro.graphs.csr import from_edges
from repro.graphs.generators import kronecker
from repro.graphs.algorithms import bfs as B, pagerank as PR, sssp as S
from repro.launch.mesh import make_host_mesh

traces = [0]
def _count(event, secs, **_):
    if event == "/jax/core/compile/jaxpr_trace_duration":
        traces[0] += 1
jax.monitoring.register_event_duration_secs_listener(_count)

mesh = make_host_mesh(4, 1)
out = {}

def hubs(g, n):
    deg = np.asarray(g.degrees)
    return [int(k) for k in np.argsort(-deg, kind="stable")[:n]]

def newest():
    return E.RUNNERS._entries[next(reversed(E.RUNNERS._entries))][2]

def search(g, key, **kw):
    d, rounds, res = B.distributed_bfs(mesh, g, key, telemetry=True, **kw)
    return dict(ok=bool(np.array_equal(np.asarray(d, np.int64),
                                       B.bfs_reference(g, key))),
                delivered=bool(res.delivered_all),
                rounds=int(rounds), subrounds=int(res.subrounds))

# 1) eight searches on one graph: one build, seven hits, one loop trace
g = kronecker(10, 16, seed=1)
b0, h0 = E.RUNNERS.builds, E.RUNNERS.hits
runs, later_traces = [], 0
for i, k in enumerate(hubs(g, 8)):
    t = traces[0]
    runs.append(search(g, k, capacity="auto"))
    if i:
        later_traces += traces[0] - t
base = newest()
out["reuse"] = dict(runs=runs, builds=E.RUNNERS.builds - b0,
                    hits=E.RUNNERS.hits - h0,
                    loop_traces=base._jfn._cache_size(),
                    later_traces=later_traces)

# 1b) distributed_sssp (its round function is built per call) between
# BFS searches on one graph: its runners are built per call and never
# kept, each on a plain partition of its own (BFS's is renumbered), and
# the BFS runner stays with its one placement
placements = [0]
place = E._place_edges
def counting_place(*a, **k):
    placements[0] += 1
    return place(*a, **k)
E._place_edges = counting_place
b0, h0, n0 = E.RUNNERS.builds, E.RUNNERS.hits, len(E.RUNNERS)
mixed, sssp_builds = [], 0
for k in hubs(g, 3):
    b1 = E.RUNNERS.builds
    dist, _, res = S.distributed_sssp(mesh, g, k, capacity="auto",
                                      telemetry=True)
    sssp_builds += E.RUNNERS.builds - b1
    ref = S.sssp_reference(g, k)
    d = np.asarray(dist, np.float64)
    reach = np.isfinite(ref)
    mixed.append(dict(ok=bool(np.allclose(d[reach], ref[reach], rtol=1e-5)
                              and (d[~reach] > 1e37).all()),
                      delivered=bool(res.delivered_all)))
    mixed.append(search(g, k, capacity="auto"))
E._place_edges = place
out["interleaved"] = dict(
    runs=mixed, sssp_builds=sssp_builds,
    bfs_builds=E.RUNNERS.builds - b0 - sssp_builds,
    bfs_hits=E.RUNNERS.hits - h0, placements=placements[0],
    sizes=[n0, len(E.RUNNERS)], bfs_newest=newest() is base,
    bfs_placed=newest().placed is base.placed)

# 2) a second graph of the same shape (the first relabelled)
perm = np.random.default_rng(0).permutation(g.num_vertices)
g2 = from_edges(perm[np.asarray(g.src)], perm[np.asarray(g.dst)],
                g.num_vertices)
b0 = E.RUNNERS.builds
runs2 = [search(g2, k, capacity="auto") for k in hubs(g2, 3)]
other = newest()
built2 = E.RUNNERS.builds - b0
b0, h0 = E.RUNNERS.builds, E.RUNNERS.hits
back = search(g, hubs(g, 1)[0], capacity="auto")
out["second_graph"] = dict(
    runs=runs2 + [back], builds=built2,
    same_shape=[g2.num_vertices, g2.num_edges] == [g.num_vertices,
                                                   g.num_edges],
    own_runner=other is not base,
    own_shards=other.arrays[1] is not base.arrays[1],
    widths=[base.layout.emax, other.layout.emax],
    back_hits=E.RUNNERS.hits - h0, back_builds=E.RUNNERS.builds - b0)

# 3) a small fixed capacity forces sub-rounds on a reused runner
b0, h0 = E.RUNNERS.builds, E.RUNNERS.hits
runs3 = [search(g, k, capacity=64, max_subrounds=256) for k in hubs(g, 3)]
out["subrounds"] = dict(runs=runs3, builds=E.RUNNERS.builds - b0,
                        hits=E.RUNNERS.hits - h0,
                        shares_shards=newest().arrays[1] is base.arrays[1])

# 4) a new spec, or the wave tap switched on, builds a new runner
key = hubs(g, 1)[0]
b0 = E.RUNNERS.builds
spec_run = search(g, key, capacity="auto",
                  spec=CommitSpec(backend="coarse", m=48))
spec_builds = E.RUNNERS.builds - b0
spec_shares = newest().arrays[1] is base.arrays[1]
os.environ["REPRO_TRACE"] = "1"
b0 = E.RUNNERS.builds
tap_run = search(g, key, capacity="auto")
tap_builds = E.RUNNERS.builds - b0
del os.environ["REPRO_TRACE"]
b0, h0 = E.RUNNERS.builds, E.RUNNERS.hits
off_run = search(g, key, capacity="auto")
out["spec"] = dict(runs=[spec_run], builds=spec_builds, shares=spec_shares)
out["tap"] = dict(runs=[tap_run, off_run], builds=tap_builds,
                  off_builds=E.RUNNERS.builds - b0,
                  off_hits=E.RUNNERS.hits - h0)

# 5) round functions that capture per-call data never hit
srcs = jnp.asarray(hubs(g, 4), jnp.int32)
def ppr_pair(kw_a, kw_b):
    b0, h0 = E.RUNNERS.builds, E.RUNNERS.hits
    gaps = []
    for kw in (kw_a, kw_b):
        dist = PR.distributed_multi_source_pagerank(
            mesh, g, srcs, capacity=64, max_subrounds=256, **kw)
        ref, _ = PR.multi_source_pagerank(g, srcs, **kw)
        gaps.append(float(np.max(np.abs(np.asarray(dist)
                                        - np.asarray(ref)))))
    return dict(gaps=gaps, builds=E.RUNNERS.builds - b0,
                hits=E.RUNNERS.hits - h0)
out["ppr_d"] = ppr_pair(dict(d=0.5, iters=6), dict(d=0.85, iters=6))
out["ppr_iters"] = ppr_pair(dict(d=0.85, iters=4), dict(d=0.85, iters=7))

# 6) the cache never holds more than its bound; least recent out first
sizes = []
graphs = [kronecker(7, 8, seed=s) for s in range(E.RUNNERS.size + 2)]
for gi in graphs:
    search(gi, 0, capacity=256)
    sizes.append(len(E.RUNNERS))
b0 = E.RUNNERS.builds
search(graphs[-1], 0, capacity=256)
newest_hit = E.RUNNERS.builds == b0
search(graphs[0], 0, capacity=256)
out["bound"] = dict(size=E.RUNNERS.size, sizes=sizes,
                    newest_hit=newest_hit,
                    oldest_rebuilt=E.RUNNERS.builds - b0 == 1)
print("RESULT", json.dumps(out))
"""


@pytest.fixture(scope="module")
def child():
    return run_devices(CHILD, n=4, timeout=900)


def _all_correct(part):
    return all(r["ok"] and r["delivered"] for r in part["runs"])


def test_searches_on_one_graph_build_once_and_trace_once(child):
    r = child["reuse"]
    assert len(r["runs"]) == 8 and _all_correct(r), r
    assert (r["builds"], r["hits"]) == (1, 7), r
    assert r["loop_traces"] == 1, r         # one compiled loop entry
    assert r["later_traces"] == 0, r        # searches 2-8 trace nothing


def test_per_call_round_functions_keep_one_placement_and_evict_nothing(
        child):
    """``distributed_sssp`` between BFS searches on one graph: a runner
    per call, none of them kept, each on a plain partition that it drops
    on return (BFS's shards are renumbered, so they are not shared), and
    every BFS search still hits its one placement."""
    r = child["interleaved"]
    assert len(r["runs"]) == 6 and _all_correct(r), r
    assert (r["sssp_builds"], r["bfs_builds"], r["bfs_hits"]) == (3, 0, 3), r
    assert r["placements"] == 3, r          # one per SSSP call, none kept
    assert r["sizes"][0] == r["sizes"][1] and r["bfs_newest"], r
    assert r["bfs_placed"], r


def test_second_graph_of_the_same_shape_gets_its_own_runner(child):
    r = child["second_graph"]
    assert r["same_shape"] and _all_correct(r), r
    assert r["builds"] == 1 and r["own_runner"] and r["own_shards"], r
    assert (r["back_builds"], r["back_hits"]) == (0, 1), r


def test_renumbered_shards_are_as_wide_under_every_labelling(child):
    """BFS's runner numbers the vertices by degree, so the relabelled
    graph's shards are exactly as wide as the original's."""
    r = child["second_graph"]
    assert r["widths"][0] == r["widths"][1], r


def test_reused_runner_still_delivers_under_subrounds(child):
    r = child["subrounds"]
    assert _all_correct(r), r
    assert all(x["subrounds"] > x["rounds"] for x in r["runs"]), r
    assert (r["builds"], r["hits"]) == (1, 2), r
    assert r["shares_shards"], r            # one partition per graph


def test_a_new_spec_builds_a_new_runner(child):
    r = child["spec"]
    assert _all_correct(r) and r["builds"] == 1 and r["shares"], r


def test_the_wave_tap_builds_a_new_runner(child):
    r = child["tap"]
    assert _all_correct(r) and r["builds"] == 1, r
    assert (r["off_builds"], r["off_hits"]) == (0, 1), r


@pytest.mark.parametrize("part", ["ppr_d", "ppr_iters"])
def test_captured_per_call_data_never_hits(child, part):
    """``d`` lives in the round function's closure, ``iters`` in the
    round cap's: runs that differ only there must not share a loop."""
    r = child[part]
    assert (r["builds"], r["hits"]) == (2, 0), r
    assert max(r["gaps"]) < 1e-6, r


def test_cache_never_holds_more_than_its_bound(child):
    r = child["bound"]
    assert max(r["sizes"]) == r["size"], r
    assert r["newest_hit"] and r["oldest_rebuilt"], r


def test_shard_slices_are_as_wide_as_the_fullest_shard():
    """Each shard's edge slice is padded only to the fullest shard's edge
    count, and each shard keeps exactly its own edges."""
    import numpy as np
    from repro.graphs.csr import from_edges, partition_edges
    from repro.graphs.generators import kronecker

    g = kronecker(9, 16, seed=1)
    perm = np.random.default_rng(0).permutation(g.num_vertices)
    g2 = from_edges(perm[np.asarray(g.src)], perm[np.asarray(g.dst)],
                    g.num_vertices)
    for gi in (g, g2):
        (src, _, _, valid, eid), part = partition_edges(gi, 4)
        counts = np.bincount(np.asarray(gi.src) // part.block, minlength=4)
        np.testing.assert_array_equal(valid.sum(axis=1), counts)
        assert sorted(eid[valid]) == list(range(gi.num_edges))
        assert np.all(src[valid] // part.block
                      == np.repeat(np.arange(4), counts))
        assert src.shape[1] == counts.max()


@pytest.mark.parametrize("shards", [2, 4, 7])
def test_balanced_partition_is_the_same_under_every_labelling(shards):
    """``partition_edges_balanced``: every edge once, between the internal
    ids of its endpoints, on the shard that owns its source, each shard's
    lanes sorted by source; the shards' edge counts are the same under
    three labellings of one graph and differ by at most the largest
    degree."""
    import numpy as np
    from repro.graphs.csr import from_edges, partition_edges_balanced
    from repro.graphs.generators import kronecker

    g = kronecker(9, 16, seed=1)
    counts = []
    for seed in (None, 0, 1):
        gi = g
        if seed is not None:
            perm = np.random.default_rng(seed).permutation(g.num_vertices)
            gi = from_edges(perm[np.asarray(g.src)], perm[np.asarray(g.dst)],
                            g.num_vertices)
        (src, dst, _, valid, eid), part, (to_orig, to_int) = \
            partition_edges_balanced(gi, shards)
        np.testing.assert_array_equal(to_orig[to_int],
                                      np.arange(to_int.size))
        e = eid[valid]
        assert sorted(e) == list(range(gi.num_edges))
        np.testing.assert_array_equal(to_orig[src[valid]],
                                      np.asarray(gi.src)[e])
        np.testing.assert_array_equal(to_orig[dst[valid]],
                                      np.asarray(gi.dst)[e])
        n = valid.sum(axis=1)
        assert np.all(src[valid] // part.block
                      == np.repeat(np.arange(shards), n))
        assert all(np.all(np.diff(src[p, :n[p]]) >= 0)
                   for p in range(shards))
        assert src.shape[1] == n.max()
        counts.append(sorted(n))
    assert counts[0] == counts[1] == counts[2], counts
    assert max(counts[0]) - min(counts[0]) <= int(np.max(g.degrees))
