"""Paper Fig 7 — scalability: strong scaling of ALL SIX distributed
algorithms over shard counts (one `run_distributed` harness), plus the
distributed PageRank AAM (coalesced accumulate) vs the PBGL-like per-edge
baseline.  Child processes force 1/2/4/8 host devices; ``--backend``
(or ``benchmarks.run --backend``) sweeps the commit mechanism."""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

from benchmarks.common import emit, timeit

ALGOS = ("bfs", "pagerank", "sssp", "coloring", "stconn", "boruvka")

CHILD = """
import json, time, numpy as np, jax
from repro.launch.mesh import make_host_mesh
from repro.graphs.generators import kronecker, random_weights
from repro.core.commit import CommitSpec
from repro.graphs.algorithms.bfs import distributed_bfs
from repro.graphs.algorithms.pagerank import distributed_pagerank
from repro.graphs.algorithms.sssp import distributed_sssp
from repro.graphs.algorithms.coloring import distributed_coloring
from repro.graphs.algorithms.stconn import distributed_stconn
from repro.graphs.algorithms.boruvka import distributed_boruvka
P = {P}
mesh = make_host_mesh(P, 1)
g = kronecker({scale}, 8, seed=5)
gw = random_weights(g, seed=2)
deg = np.asarray(g.degrees)
src = int(np.argmax(deg))
far = int(next(i for i in np.argsort(deg)[::-1] if i != src))
spec = CommitSpec(backend="{backend}", stats=False)
kw = dict(capacity=8192, spec=spec)
RUNS = {{
    "bfs": lambda: distributed_bfs(mesh, g, src, **kw)[0]
        .block_until_ready(),
    "pagerank": lambda: distributed_pagerank(mesh, g, iters=5, **kw)
        .block_until_ready(),
    "sssp": lambda: distributed_sssp(mesh, gw, src, **kw)[0]
        .block_until_ready(),
    "coloring": lambda: distributed_coloring(mesh, g, seed=0, **kw)[0]
        .block_until_ready(),
    "stconn": lambda: distributed_stconn(mesh, g, src, far, **kw)[0]
        .block_until_ready(),
    "boruvka": lambda: distributed_boruvka(mesh, gw, **kw)[0]
        .block_until_ready(),
}}

def t(fn, reps=3):
    fn(); ts = []
    for _ in range(reps):
        t0 = time.perf_counter(); fn(); ts.append(time.perf_counter()-t0)
    ts.sort(); return ts[len(ts)//2]

out = {{name: t(fn) for name, fn in RUNS.items()}}
print("RESULT", json.dumps(out))
"""


def main(backend: str = "coarse", scale: int = 11):
    # single-shard PBGL-like baseline: per-edge atomic accumulate PR
    from repro.core.commit import CommitSpec
    from repro.graphs.algorithms.pagerank import pagerank
    from repro.graphs.generators import kronecker
    g = kronecker(scale, 8, seed=5)
    tb = timeit(lambda: pagerank(
        g, iters=5, spec=CommitSpec(backend="atomic", stats=False))[0]
        .block_until_ready(), repeats=2)
    ta = timeit(lambda: pagerank(
        g, iters=5, spec=CommitSpec(backend="coarse", sort=False,
                                    stats=False))[0]
        .block_until_ready(), repeats=2)
    emit("fig7/pr/1shard/pbgl_like", tb)
    emit("fig7/pr/1shard/aam", ta, f"T1_ratio={tb/ta:.2f}")

    env_base = dict(os.environ)
    env_base["PYTHONPATH"] = str(Path(__file__).resolve().parent.parent /
                                 "src")
    for p_ in (2, 4, 8):
        env = dict(env_base)
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={p_}"
        env["JAX_PLATFORMS"] = "cpu"    # forced host devices, never the chip
        r = subprocess.run(
            [sys.executable, "-c", textwrap.dedent(
                CHILD.format(P=p_, scale=scale, backend=backend))],
            capture_output=True, text=True, env=env, timeout=2400)
        if r.returncode != 0:
            raise RuntimeError(f"fig7 P={p_} child failed: "
                               f"{r.stderr[-200:]}")
        line = [l for l in r.stdout.splitlines()
                if l.startswith("RESULT ")][-1]
        out = json.loads(line[len("RESULT "):])
        for name in ALGOS:
            emit(f"fig7/{name}/{backend}/P={p_}", out[name])


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--backend", default="coarse",
                    choices=("atomic", "coarse", "pallas"))
    ap.add_argument("--scale", type=int, default=11)
    args = ap.parse_args()
    main(backend=args.backend, scale=args.scale)
