"""Distributed PageRank + BFS over 8 shards (the paper's §6.2 scenario):
coalesced accumulate waves over all_to_all, with sub-round requeue.

Re-execs itself with 8 forced host devices.

  PYTHONPATH=src python examples/distributed_pagerank.py
"""
import os
import subprocess
import sys

if os.environ.get("_REPRO_CHILD") != "1":
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"        # forced host devices, never the chip
    env["_REPRO_CHILD"] = "1"
    raise SystemExit(subprocess.run([sys.executable] + sys.argv,
                                    env=env).returncode)

import time

import numpy as np

from repro.core.engine import distributed_bfs, distributed_pagerank
from repro.graphs.algorithms.bfs import bfs_reference
from repro.graphs.algorithms.pagerank import pagerank_reference
from repro.graphs.generators import kronecker
from repro.launch.mesh import make_host_mesh

mesh = make_host_mesh(8, 1)
g = kronecker(scale=13, edge_factor=8, seed=5)
src = int(np.argmax(np.asarray(g.degrees)))
print(f"8-shard mesh; graph |V|={g.num_vertices} |E|={g.num_edges}")

t0 = time.perf_counter()
dist, rounds = distributed_bfs(mesh, g, src, capacity=8192)
dt = time.perf_counter() - t0
ok = np.array_equal(np.asarray(dist, np.int64), bfs_reference(g, src))
print(f"distributed BFS : {dt*1e3:7.1f} ms rounds={int(rounds)} "
      f"correct={ok}")

t0 = time.perf_counter()
pr = distributed_pagerank(mesh, g, iters=10, capacity=8192)
dt = time.perf_counter() - t0
err = float(np.abs(np.asarray(pr) - pagerank_reference(g, iters=10)).max())
print(f"distributed PR  : {dt*1e3:7.1f} ms max|err|={err:.2e}")
