"""Faults planted under the benchmark's timed path, for the tests.

    python faults.py <cell> <fault> <scale> [--trace]

applies one fault to the program, runs one rehearsal of ``<cell>`` at
``<scale>`` through the harness (on the CPU, without the harness's look
for a chip) and prints the result line.  Faults:

* ``none`` — the program as it is;
* ``unchanged`` — the entry point returns its initial state;
* ``half`` — every commit drops the second half of its messages;
* ``exchange`` — the all-to-all between chips moves nothing;
* ``altered`` — one answer is changed where the entry produces it.
"""
from __future__ import annotations

import json
import sys
import types
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))


def _half(commit):
    import jax.numpy as jnp

    def dropped(state, msgs, op, spec=None):
        n = msgs.valid.shape[0]
        keep = jnp.arange(n) < n // 2
        msgs = type(msgs)(msgs.target, msgs.payload, msgs.valid & keep)
        return commit(state, msgs, op, spec)
    return dropped


def apply(fault: str) -> None:
    import jax
    import jax.numpy as jnp
    from repro.core import autotune, commit
    from repro.graphs.algorithms import bfs as bfs_mod
    from repro.graphs.algorithms import pagerank as pr_mod

    if fault == "half":
        commit.commit = _half(commit.commit)
        autotune.commit = _half(autotune.commit)
    elif fault == "exchange":
        jax.lax.all_to_all = lambda x, *a, **k: x
    elif fault == "none":
        pass
    elif fault in ("unchanged", "altered"):
        bfs, pagerank, dist_bfs = (bfs_mod.bfs, pr_mod.pagerank,
                                   bfs_mod.distributed_bfs)

        def fix_dist(dist, source):
            if fault == "unchanged":
                return jnp.full_like(dist, 2 ** 30).at[source].set(0)
            return dist.at[source].add(1)

        def bad_bfs(g, source, **kw):
            r = bfs(g, source, **kw)
            return types.SimpleNamespace(
                dist=fix_dist(r.dist, source), rounds=r.rounds,
                messages=r.messages)

        def bad_pagerank(g, **kw):
            rank, conflicts = pagerank(g, **kw)
            if fault == "unchanged":
                return jnp.full_like(rank, 1.0 / g.num_vertices), conflicts
            return rank.at[0].multiply(1.01), conflicts

        def bad_dist_bfs(mesh, g, source, **kw):
            dist, rounds, res = dist_bfs(mesh, g, source, **kw)
            return fix_dist(dist, source), rounds, res

        bfs_mod.bfs = bad_bfs
        pr_mod.pagerank = bad_pagerank
        bfs_mod.distributed_bfs = bad_dist_bfs
    else:
        raise ValueError(f"no fault {fault!r}")


def main(cell: str, fault: str, scale: int, trace: bool = False) -> None:
    sys.path.insert(0, str(Path(__file__).resolve().parent))
    from chipbench import harness
    from unlisted import bench_with_x4
    bench = bench_with_x4()
    apply(fault)
    r = harness.run(bench.cell(cell), seed=11, seconds=0.2, trace=trace,
                    rehearse=True, scale=scale)
    print(json.dumps(r))


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2], int(sys.argv[3]), "--trace" in sys.argv)
