"""All six paper case-studies (§3.3) on the AAM engine, with telemetry.

  PYTHONPATH=src python examples/graph_analytics.py
  PYTHONPATH=src python examples/graph_analytics.py --distributed
    # re-execs with 8 forced host devices and additionally runs all six
    # algorithms through the shared run_distributed harness (§6.2)
"""
import os
import subprocess
import sys
import time

import numpy as np

DISTRIBUTED = "--distributed" in sys.argv
if DISTRIBUTED and os.environ.get("_REPRO_CHILD") != "1":
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["JAX_PLATFORMS"] = "cpu"        # forced host devices, never the chip
    env["_REPRO_CHILD"] = "1"
    raise SystemExit(subprocess.run([sys.executable] + sys.argv,
                                    env=env).returncode)

from repro.core.commit import CommitSpec
from repro.graphs.generators import (erdos_renyi, grid2d, kronecker,
                                     random_weights)
from repro.graphs.algorithms.bfs import bfs
from repro.graphs.algorithms.boruvka import boruvka, mst_reference
from repro.graphs.algorithms.coloring import coloring, validate_coloring
from repro.graphs.algorithms.pagerank import pagerank
from repro.graphs.algorithms.sssp import sssp
from repro.graphs.algorithms.stconn import st_connectivity

g = kronecker(scale=13, edge_factor=16, seed=1)
gw = random_weights(g, seed=2)
src = int(np.argmax(np.asarray(g.degrees)))
far = int(np.argsort(np.asarray(g.degrees))[-2])
print(f"Kronecker graph |V|={g.num_vertices} |E|={g.num_edges}\n")


def run(name, msg_type, fn):
    t0 = time.perf_counter()
    out = fn()
    dt = time.perf_counter() - t0
    print(f"{name:18s} [{msg_type}]  {dt*1e3:8.1f} ms   {out}")


run("BFS", "FF&MF", lambda: (lambda r:
    f"rounds={int(r.rounds)} conflicts={int(r.conflicts)}")(
    bfs(g, src, spec=CommitSpec(backend="coarse", m=4096, stats=False))))
run("BFS (auto-tuned)", "FF&MF", lambda: (lambda r:
    f"rounds={int(r.rounds)} conflicts={int(r.conflicts)} "
    f"(calibrated backend+M, conflict-feedback sizing)")(
    bfs(g, src, spec=CommitSpec(backend="auto", stats=False))))
run("PageRank", "FF&AS", lambda: (lambda r:
    f"sum={float(r[0].sum()):.4f} conflicting-accs={int(r[1])}")(
    pagerank(g, iters=20)))
run("SSSP", "FF&MF", lambda: (lambda d, rr:
    f"rounds={int(rr)} reached={int((d < 1e38).sum())}")(
    *sssp(gw, src)))
run("ST-connectivity", "FR&AS", lambda: (lambda f, r:
    f"connected={bool(f)} rounds={int(r)}")(
    *st_connectivity(g, src, far)))
run("Boman coloring", "FR&MF", lambda: (lambda c, r, failed:
    f"colors={int(np.asarray(c).max())+1} rounds={int(r)} "
    f"valid={validate_coloring(g, c)}")(
    *coloring(g, seed=0)))
gw_small = random_weights(erdos_renyi(2000, 8.0, seed=3), seed=4)
run("Boruvka MST", "FR&MF", lambda: (lambda comp, w, ne, r:
    f"weight={float(w):.1f} (ref {mst_reference(gw_small):.1f}) "
    f"edges={int(ne)} rounds={int(r)}")(
    *boruvka(gw_small)))

if DISTRIBUTED:
    from repro.launch.mesh import make_host_mesh
    from repro.graphs.algorithms.bfs import distributed_bfs
    from repro.graphs.algorithms.boruvka import distributed_boruvka
    from repro.graphs.algorithms.coloring import distributed_coloring
    from repro.graphs.algorithms.pagerank import distributed_pagerank
    from repro.graphs.algorithms.sssp import distributed_sssp
    from repro.graphs.algorithms.stconn import distributed_stconn

    mesh = make_host_mesh(8, 1)
    gd = kronecker(scale=10, edge_factor=8, seed=1)
    gdw = random_weights(gd, seed=2)
    sd = int(np.argmax(np.asarray(gd.degrees)))
    fd = int(np.argsort(np.asarray(gd.degrees))[-2])
    print(f"\n8-shard run_distributed harness; "
          f"|V|={gd.num_vertices} |E|={gd.num_edges}")

    def rund(name, msg_type, fn):
        t0 = time.perf_counter()
        out, res = fn()
        dt = time.perf_counter() - t0
        print(f"{name:18s} [{msg_type}]  {dt*1e3:8.1f} ms   {out}  "
              f"rounds={int(res.rounds)} conflicts={int(res.conflicts)} "
              f"subrounds={int(res.subrounds)} "
              f"delivered_all={bool(res.delivered_all)}")

    rund("BFS", "FF&MF", lambda: (lambda d, ro, r:
        (f"reached={int((np.asarray(d) < 2**30).sum())}", r))(
        *distributed_bfs(mesh, gd, sd, capacity=2048, telemetry=True)))
    rund("PageRank", "FF&AS", lambda: (lambda pr, r:
        (f"sum={float(pr.sum()):.4f}", r))(
        *distributed_pagerank(mesh, gd, iters=10, capacity=2048,
                              telemetry=True)))
    rund("SSSP", "FF&MF", lambda: (lambda d, ro, r:
        (f"reached={int((np.asarray(d) < 1e38).sum())}", r))(
        *distributed_sssp(mesh, gdw, sd, capacity=2048, telemetry=True)))
    rund("ST-connectivity", "FR&AS", lambda: (lambda f, ro, r:
        (f"connected={bool(f)}", r))(
        *distributed_stconn(mesh, gd, sd, fd, capacity=2048,
                            telemetry=True)))
    rund("Boman coloring", "FR&MF", lambda: (lambda c, ro, nc, r:
        (f"colors={int(np.asarray(c).max())+1} "
         f"valid={validate_coloring(gd, c)}", r))(
        *distributed_coloring(mesh, gd, seed=0, capacity=2048,
                              telemetry=True)))
    rund("Boruvka MST", "FR&MF", lambda: (lambda comp, w, ne, ro, r:
        (f"weight={float(w):.1f} edges={int(ne)}", r))(
        *distributed_boruvka(mesh, gdw, capacity=2048, telemetry=True)))
