"""``run_distributed`` on a one-device mesh: errors surface at once unless
the run asked for fault tolerance, and ``capacity="auto"`` delivers every
message of a large Kronecker BFS."""
import numpy as np
import pytest

from repro.core import engine as E
from repro.core.commit import CommitSpec
from repro.graphs.algorithms.bfs import bfs_reference, distributed_bfs
from repro.graphs.csr import partition_edges
from repro.graphs.generators import kronecker
from repro.launch.mesh import make_host_mesh


class _Boom(RuntimeError):
    pass


def _failing_run(calls):
    def run(self, state, scalars, carry, limit):
        calls.append(limit)
        raise _Boom("device fault")
    return run


def test_first_error_propagates_without_fault_tolerance(monkeypatch):
    calls = []
    monkeypatch.setattr(E._Runner, "run", _failing_run(calls))
    g = kronecker(6, 4, seed=0)
    with pytest.raises(_Boom):
        distributed_bfs(make_host_mesh(1, 1), g, 0)
    assert len(calls) == 1          # no retry, no mesh shrink


def test_snapshot_runs_still_retry_faults(monkeypatch):
    calls = []
    monkeypatch.setattr(E._Runner, "run", _failing_run(calls))
    g = kronecker(6, 4, seed=0)
    with pytest.raises(_Boom):
        distributed_bfs(make_host_mesh(1, 1), g, 0, snapshot_rounds=2)
    assert len(calls) == 9          # max_faults=8 retries, then raise


def test_injected_fault_on_one_device_retries_in_place():
    g = kronecker(7, 8, seed=2)
    root = int(np.argmax(np.asarray(g.degrees)))
    seen = []

    def inject(chunk, rounds_done):
        seen.append(chunk)
        if chunk == 0:
            raise _Boom("host drop")

    dist, _, res = distributed_bfs(make_host_mesh(1, 1), g, root,
                                   snapshot_rounds=2, fault_injector=inject,
                                   telemetry=True)
    assert bool(res.degraded) and bool(res.delivered_all)
    np.testing.assert_array_equal(np.asarray(dist, np.int64),
                                  bfs_reference(g, root))


def test_auto_capacity_is_capped_by_shard_edges():
    g = kronecker(8, 8, seed=1)
    for p in (1, 2, 4):
        emax = partition_edges(g, p)[0][0].shape[1]
        assert emax <= E.capacity_cap(emax) < 2 * max(emax, E.CAPACITY_MIN)
        assert E.auto_capacity(g, p, emax) <= E.capacity_cap(emax)


def test_auto_capacity_delivers_scale18_bfs():
    """2^18 vertices, edge factor 16: one round emits more messages than
    the old fixed cap (2^15) times 64 sub-rounds could deliver."""
    g = kronecker(18, 16, seed=0)
    assert g.num_edges > 64 * (1 << 15)
    deg = np.asarray(g.degrees)
    root = int(np.argmax(deg))
    dist, rounds, res = distributed_bfs(
        make_host_mesh(1, 1), g, root, capacity="auto",
        spec=CommitSpec(backend="coarse", stats=False), telemetry=True)
    assert bool(res.delivered_all) and not bool(res.degraded)
    assert int(res.capacity) >= g.num_edges
    assert int(res.subrounds) == int(rounds)     # one sub-round per round
    # exact BFS distances, checked in O(E): the root is 0, no edge spans
    # more than one level (so no reached vertex neighbours an unreached
    # one), and every other reached vertex has a neighbour one level up
    dist = np.asarray(dist, np.int64)
    src, dst = np.asarray(g.src), np.asarray(g.dst)
    assert dist[root] == 0
    assert np.all(np.abs(dist[src] - dist[dst]) <= 1)
    has_parent = np.zeros(g.num_vertices, bool)
    has_parent[dst[dist[src] == dist[dst] - 1]] = True
    reached = (dist > 0) & (dist < 2 ** 30)
    assert reached.sum() > g.num_vertices // 4
    assert np.all(has_parent[reached])
