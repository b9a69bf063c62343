"""Paper Fig 5 — inter-node activities: remote commit throughput vs
coalescing factor C, and the distributed-transaction scenarios O-1..O-4
(§5.7 ownership protocol).  Runs in a child process with 8 forced host
devices (the parent bench process keeps 1 device, per the assignment)."""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

from benchmarks.common import emit
from repro.core.commit import BACKENDS

CHILD = """
import json, os, time, numpy as np, jax, jax.numpy as jnp
from repro.launch.mesh import make_host_mesh
from repro.graphs.generators import kronecker
from repro.core.commit import CommitSpec
from repro.core.engine import distributed_bfs, distributed_pagerank
from repro.core.ownership import run_transactions

spec = CommitSpec(backend=os.environ.get("AAM_BACKEND", "coarse"),
                  stats=True)
mesh = make_host_mesh(8, 1)
g = kronecker(13, 8, seed=2)
src = int(np.argmax(np.asarray(g.degrees)))
out = {}

def t(fn, reps=3):
    fn(); ts = []
    for _ in range(reps):
        t0 = time.perf_counter(); fn(); ts.append(time.perf_counter() - t0)
    ts.sort(); return ts[len(ts)//2]

# remote marking (BFS-wave) vs coalescing factor C  [Fig 5c/5d analogue]
for C in (64, 256, 1024, 4096, 16384):
    out[f"bfs_C={C}"] = t(lambda C=C: distributed_bfs(
        mesh, g, src, capacity=C, spec=spec)[0].block_until_ready())

# remote accumulate (PR) vs C  [Fig 5e/5f analogue]
for C in (256, 4096, 16384):
    out[f"pr_C={C}"] = t(lambda C=C: distributed_pagerank(
        mesh, g, iters=3, capacity=C, spec=spec).block_until_ready(),
        reps=2)

# ownership-protocol scenarios [Fig 5i]: x txns of a local + b remote
rng = np.random.default_rng(0)
V = 1 << 14
for name, x, a, b in (("O-1", 100, 5, 1), ("O-2", 1000, 5, 1),
                      ("O-3", 100, 7, 3), ("O-4", 1000, 7, 3)):
    block = V // 8
    local = rng.integers(0, block, (8, x, a))
    local += (np.arange(8)[:, None, None] * block)
    remote = rng.integers(0, V, (8, x, b))
    txns = jnp.asarray(np.concatenate([local, remote], axis=2), jnp.int32)
    def run(txns=txns):
        vis, st = run_transactions(mesh, txns, V, capacity=8192)
        return vis.block_until_ready(), int(st.rounds), int(st.retries)
    _, rounds, retries = run()
    out[name] = {"s": t(lambda: run()[0], reps=2), "rounds": rounds,
                 "retries": retries}
print("RESULT", json.dumps(out))
"""


def main(backend: str = "coarse"):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"        # forced host devices, never the chip
    env["PYTHONPATH"] = str(Path(__file__).resolve().parent.parent / "src")
    env["AAM_BACKEND"] = backend
    p = subprocess.run([sys.executable, "-c", textwrap.dedent(CHILD)],
                       capture_output=True, text=True, env=env, timeout=1200)
    if p.returncode != 0:
        raise RuntimeError(f"fig5 child failed: {p.stderr[-300:]}")
    line = [l for l in p.stdout.splitlines() if l.startswith("RESULT ")][-1]
    out = json.loads(line[len("RESULT "):])
    for k, v in out.items():
        if isinstance(v, dict):
            emit(f"fig5/own/{k}", v["s"],
                 f"rounds={v['rounds']} retries={v['retries']}")
        else:
            emit(f"fig5/{backend}/{k}", v)


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--backend", choices=BACKENDS, default="coarse",
                    help="commit backend used by the owner-side commits")
    main(ap.parse_args().backend)
