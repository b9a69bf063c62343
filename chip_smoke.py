#!/usr/bin/env python3
"""On-chip bring-up check of the AAM graph engine.

Drives the main path once, in one process, through the entry points a
user calls, on a Graph500 Kronecker graph (A/B/C = 0.57/0.19/0.19, edge
factor 16, ``generators.kronecker`` from ``--seed``) at scale 19: about
524k vertices and 16M directed edges once symmetrized and de-duplicated.
The run has to finish in 20 minutes on one v5e, compiles included.  A
``coarse`` BFS round (a sort and scatters over every edge) takes about
4 s at scale 20 there, so scale 22 would need about 130 s per BFS and
scale 20 leaves less than twice the run's estimated length as margin.
Every answer is checked against an independent, vectorized NumPy
reference.  Each phase prints one JSON line with its wall time, backend
compile time, persistent-cache hits and peak HBM, and one line per run
as it ends.  The last line of stdout is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

One chip (the default) runs four phases:

  kernels     commit() on every tier (atomic, coarse, pallas, fused) for
              min / add (float32) / or / first, stats on and off, plus the
              engine's fused site (lane + base, width 8), against a NumPy
              oracle.  Kernel tiers must compile (``tpu_custom_call``).
  algorithms  bfs from 4 seeded non-isolated roots with the coarse and
              auto specs, and pagerank(iters=20).
  service     GraphService with its default spec answering 4 BfsQuery +
              4 PprQuery (lanes: the largest power of two <= 4 whose
              programs fit HBM per ``memory_analysis``).
  engine      distributed_bfs on a 1-chip mesh, capacity="auto": coarse at
              the full scale, fused at scale 16.

``--chips 4`` runs only the distributed path over a 4-chip ``data`` mesh:
distributed_bfs + distributed_pagerank, coarse at the full scale and fused
at scale 16, with the state checked to sit on 4 distinct devices.

Usage (from the repository root):

    python chip_smoke.py                        # one chip
    python chip_smoke.py --chips 4              # four chips
    python chip_smoke.py --rehearse --scale 8   # CPU rehearsal

Without a TPU it exits nonzero and prints no result, unless ``--rehearse``
is given; a rehearsal prints the platform it really ran on and never the
ok line.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

FUSED_SCALE = 16        # fused grid is blocks x tiles: full scale waits
ROOTS = 4
PR_ITERS = 20
BFS_INF = 2 ** 30       # repro.graphs.algorithms.bfs.INF


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: every phase on one chip; 4: only the "
                         "distributed path over a 4-chip mesh")
    ap.add_argument("--scale", type=int, default=19,
                    help="Kronecker scale of the main graph (default 19)")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the graphs, roots and kernel batches")
    ap.add_argument("--rehearse", action="store_true",
                    help="run on whatever backend JAX finds (Pallas in "
                         "interpret mode off the TPU); never prints the "
                         "ok line")
    return ap.parse_args(argv)


# -- NumPy references ---------------------------------------------------------


def bfs_reference(indptr, dst, root: int) -> np.ndarray:
    """Level-synchronous BFS: each level expands the frontier's CSR ranges
    in one vectorized gather and marks the unseen neighbours."""
    dist = np.full(indptr.size - 1, BFS_INF, np.int32)
    dist[root] = 0
    frontier = np.asarray([root], np.int64)
    level = 0
    while frontier.size:
        starts = indptr[frontier]
        counts = indptr[frontier + 1] - starts
        total = int(counts.sum())
        if total == 0:
            break
        offs = (np.repeat(starts - np.cumsum(counts) + counts, counts)
                + np.arange(total))
        nbrs = dst[offs]
        level += 1
        dist[nbrs[dist[nbrs] == BFS_INF]] = level
        frontier = np.flatnonzero(dist == level)
    return dist


def pagerank_references(indptr, dst, restarts, *, d=0.85, iters=PR_ITERS,
                        pool=None) -> np.ndarray:
    """[len(restarts), V] ranks: PageRank for a ``None`` restart,
    personalized PageRank restarting at vertex ``r`` otherwise, with the
    engine's dangling-mass rule.

    The graph is symmetric (every generator here symmetrizes), so the
    in-edges of ``v`` are its CSR row: each iteration pulls every rank
    vector with one gather and one ``np.add.reduceat`` per block of rows,
    the (vector, block) pairs spread over ``pool``'s threads."""
    v = indptr.size - 1
    deg = np.diff(indptr)
    if not np.array_equal(np.bincount(dst, minlength=v), deg):
        raise ValueError("pagerank_references needs a symmetric graph")
    k = len(restarts)
    pers = np.asarray([r is not None for r in restarts])[:, None]
    home = np.zeros((k, v))
    for j, r in enumerate(restarts):
        if r is not None:
            home[j, r] = 1.0
    rank = np.where(pers, home, 1.0 / v)
    inv = d / np.maximum(deg, 1)
    rows = np.flatnonzero(deg)                     # reduceat needs runs > 0
    blocks = np.array_split(rows, max(1, min(rows.size, 16)))
    tasks = [(j, blk) for j in range(k) for blk in blocks if blk.size]

    def pull(y, acc, task):
        j, blk = task
        e0, e1 = indptr[blk[0]], indptr[blk[-1] + 1]
        acc[j, blk] = np.add.reduceat(y[j][dst[e0:e1]], indptr[blk] - e0)

    for _ in range(iters):
        y = rank * inv
        acc = np.zeros_like(rank)
        list((pool.map if pool else map)(lambda t: pull(y, acc, t), tasks))
        dm = d * rank[:, deg == 0].sum(axis=1, keepdims=True)
        rank = np.where(pers, home * ((1 - d) + dm) + acc,
                        (1 - d) / v + acc + dm / v)
    return rank


def commit_reference(op, state, tgt, val, valid):
    """Final state of one commit batch (``first``: lowest message id wins
    an empty slot)."""
    t, x = tgt[valid], val[valid]
    if op == "first":
        new = state.copy()
        uniq, pos = np.unique(t, return_index=True)
        take = new[uniq] < 0
        new[uniq[take]] = x[pos[take]]
        return new
    new = state.astype(np.float64 if op == "add" else state.dtype)
    if op == "min":
        np.minimum.at(new, t, x)
    elif op == "add":
        np.add.at(new, t, x.astype(np.float64))
    else:                                            # or
        np.maximum.at(new, t, (x != 0).astype(state.dtype))
    return new


def references(host, roots, *, ppr: bool) -> dict:
    """BFS distances from ``roots``, PageRank and (``ppr``) personalized
    PageRank from ``roots``, on the host's cores (NumPy's gathers, sorts
    and ``reduceat`` release the GIL)."""
    ip, dst = host["indptr"], host["dst"]
    with ThreadPoolExecutor(max_workers=os.cpu_count() or 1) as pool:
        bfs = {r: pool.submit(bfs_reference, ip, dst, r) for r in roots}
        restarts = [None] + (list(roots) if ppr else [])
        ranks = pagerank_references(ip, dst, restarts, pool=pool)
        refs = {"bfs": {r: f.result() for r, f in bfs.items()},
                "pr": ranks[0]}
    if ppr:
        refs["ppr"] = dict(zip(roots, ranks[1:]))
    return refs


def same_ranks(got, want) -> bool:
    got = np.asarray(got, np.float64)
    return bool(np.allclose(got, want, rtol=1e-3, atol=1e-3 / want.size))


# -- per-phase accounting -----------------------------------------------------


class Meter:
    """Backend compile seconds and persistent-cache hits of this process,
    from JAX's monitoring events."""

    def __init__(self):
        import jax
        self.compile_s = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def hbm(dev) -> dict:
    stats = dev.memory_stats() or {}
    return {"peak_hbm_bytes": stats.get("peak_bytes_in_use"),
            "hbm_limit_bytes": stats.get("bytes_limit")}


class PhaseLog(dict):
    """A phase's result fields; :meth:`step` also prints each run as it
    ends, with the compile seconds spent since the previous step, so a
    phase cut short still shows how far it got."""

    def __init__(self, name: str, meter):
        super().__init__()
        self.name, self.meter = name, meter
        self.compile_mark = meter.compile_s

    def step(self, key: str, row: dict) -> None:
        self.setdefault(key, []).append(row)
        spent = self.meter.compile_s - self.compile_mark
        self.compile_mark = self.meter.compile_s
        print(json.dumps({"phase": self.name, "step": row,
                          "compile_s": spent}), flush=True)


@contextlib.contextmanager
def phase(name, meter, dev):
    """Times one phase; prints its JSON line when the phase succeeds."""
    t0, c0, h0 = time.perf_counter(), meter.compile_s, meter.cache_hits
    info = PhaseLog(name, meter)
    yield info
    print(json.dumps({"phase": name,
                      "wall_s": time.perf_counter() - t0,
                      "compile_s": meter.compile_s - c0,
                      "cache_hits": meter.cache_hits - h0,
                      **hbm(dev), **info}), flush=True)


def resolved_tiers(since: int) -> list:
    """Backends the auto tuner picked since audit entry ``since``."""
    from repro.core.autotune import DEFAULT_TUNER
    return sorted({e["backend"] for e in DEFAULT_TUNER.audit[since:]
                   if e.get("event") == "policy"})


def build_graph(scale: int, seed: int):
    from repro.graphs.generators import kronecker
    t0 = time.perf_counter()
    g = kronecker(scale, 16, seed=seed)
    host = {"indptr": np.asarray(g.indptr).astype(np.int64),
            "dst": np.asarray(g.dst)}
    deg = np.diff(host["indptr"])
    rng = np.random.default_rng(seed)
    roots = [int(r) for r in rng.choice(np.flatnonzero(deg > 0), ROOTS,
                                        replace=False)]
    print(json.dumps({"phase": "setup", "scale": scale,
                      "vertices": g.num_vertices, "edges": g.num_edges,
                      "graph_build_s": time.perf_counter() - t0,
                      "roots": roots}), flush=True)
    return g, host, roots


# -- phases -------------------------------------------------------------------


def kernels_phase(info, *, seed: int, small: bool, compiled: bool):
    import jax
    import jax.numpy as jnp
    from repro.core.autotune import KERNEL_BACKENDS
    from repro.core.commit import (BACKENDS, CommitSpec, commit,
                                   fused_commit_site)
    from repro.core.messages import make_messages
    from repro.kernels.coarse_commit import commit_grid

    v, n = (512, 1024) if small else (1 << 16, 1 << 17)
    rng = np.random.default_rng(seed)
    tgt = rng.integers(0, v, n).astype(np.int32)
    valid = rng.random(n) < 0.9

    def run(name, fn, args, want, **row):
        prog = jax.jit(fn).lower(*args).compile()
        got = np.asarray(prog(*args))
        ok = (np.allclose(got, want, rtol=1e-5, atol=1e-4)
              if got.dtype == np.float32 else np.array_equal(got, want))
        custom = "tpu_custom_call" in prog.as_text()
        row.update(call=name, match=ok, tpu_custom_call=custom)
        info.step("calls", row)
        if not ok:
            raise AssertionError(f"kernels: {row} differs from the oracle")
        if compiled and row["tier"] in KERNEL_BACKENDS and not custom:
            raise AssertionError(f"kernels: {row} ran without a compiled "
                                 f"Pallas kernel")

    def batch(op, size):
        dtype = np.float32 if op == "add" else np.int32
        if op == "add":
            val = rng.random(n).astype(dtype)
        elif op == "or":
            val = rng.integers(0, 2, n).astype(dtype)
        else:
            val = rng.integers(0, 1000, n).astype(dtype)
        if op == "min":
            state = np.full(size, 1 << 20, dtype)
        elif op == "first":                   # some slots already taken
            state = np.where(rng.random(size) < 0.3, 7, -1).astype(dtype)
        else:
            state = np.zeros(size, dtype)
        return state, val

    for op in ("min", "add", "or", "first"):
        state, val = batch(op, v)
        want = commit_reference(op, state, tgt, val, valid)
        for backend in BACKENDS:
            for stats in (False, True):
                spec = CommitSpec(backend=backend, stats=stats)
                steps = (int(np.prod(commit_grid(v, n, spec.tile_m,
                                                 spec.block_v)))
                         if backend in KERNEL_BACKENDS else 0)
                run("commit", lambda s, t, x, ok, op=op, spec=spec:
                    commit(s, make_messages(t, x, ok), op, spec).state,
                    (state, tgt, val, valid), want, tier=backend, op=op,
                    stats=stats, grid_steps=steps)

    # the engine's owner-side site: global ids around a traced base,
    # -1 sentinels, 8 lanes per vertex row
    width, rows = 8, v // 8
    base = np.int32(3 * rows)
    gtgt = (base - 16 + rng.integers(0, rows + 32, n)).astype(np.int32)
    gtgt[rng.random(n) < 0.1] = -1
    lane = rng.integers(0, width, n).astype(np.int32)
    rel = gtgt - base
    ok = (gtgt >= 0) & (rel >= 0) & (rel < rows)
    key = (np.where(ok, rel, 0) * width + lane).astype(np.int32)
    for op in ("min", "add", "first"):
        state, val = batch(op, rows * width)
        want = commit_reference(op, state, key, val, ok)
        for stats in (False, True):
            spec = CommitSpec(backend="fused", stats=stats)
            steps = int(np.prod(commit_grid(rows * width, n, spec.tile_m,
                                            spec.block_v)))
            run("fused_commit_site",
                lambda s, t, x, ln, b, op=op, spec=spec: fused_commit_site(
                    s, t, x, op, spec, lane=ln, base=b, width=width).state,
                (state, gtgt, val, lane, jnp.int32(base)), want,
                tier="fused", op=op, stats=stats, grid_steps=steps)


def algorithms_phase(info, g, roots, refs):
    from repro.core.autotune import DEFAULT_TUNER
    from repro.core.commit import CommitSpec
    from repro.graphs.algorithms.bfs import bfs
    from repro.graphs.algorithms.pagerank import pagerank

    for name in ("coarse", "auto"):
        spec = CommitSpec(backend=name, stats=False)
        mark = len(DEFAULT_TUNER.audit)
        for r in roots:
            t0 = time.perf_counter()
            res = bfs(g, r, spec=spec)
            dist = np.asarray(res.dist)
            row = {"alg": "bfs", "spec": name, "root": r,
                   "wall_s": time.perf_counter() - t0,
                   "rounds": int(res.rounds),
                   "match": bool(np.array_equal(dist, refs["bfs"][r]))}
            if name == "auto":
                row["tier"] = resolved_tiers(mark)
            info.step("runs", row)
            if not row["match"]:
                raise AssertionError(f"algorithms: {row}")
    t0 = time.perf_counter()
    rank, _ = pagerank(g, iters=PR_ITERS)
    rank = np.asarray(rank)
    row = {"alg": "pagerank", "spec": "coarse", "iters": PR_ITERS,
           "wall_s": time.perf_counter() - t0,
           "match": same_ranks(rank, refs["pr"])}
    info.step("runs", row)
    if not row["match"]:
        raise AssertionError(f"algorithms: {row}")


def _service_lanes(g, spec, dev) -> tuple:
    """Largest power-of-two lane count <= 4 whose lane-fused BFS and PPR
    programs fit the chip's free HBM per ``memory_analysis`` (a program
    the compiler itself finds too large counts as not fitting)."""
    import jax
    import jax.numpy as jnp
    from repro.graphs.algorithms.bfs import multi_source_bfs
    from repro.graphs.algorithms.pagerank import multi_source_pagerank
    stats = dev.memory_stats() or {}
    free = stats.get("bytes_limit", 0) - stats.get("bytes_in_use", 0)
    need = {}
    for lanes in (4, 2, 1):
        srcs = jnp.zeros((lanes,), jnp.int32)
        try:
            progs = (multi_source_bfs.lower(g, srcs, spec=spec).compile(),
                     multi_source_pagerank.lower(g, srcs, iters=PR_ITERS,
                                                 spec=spec).compile())
        except jax.errors.JaxRuntimeError as e:
            if "RESOURCE_EXHAUSTED" not in str(e):
                raise
            need[lanes] = "exceeds HBM"
            continue
        need[lanes] = max((p.memory_analysis().temp_size_in_bytes
                           + p.memory_analysis().output_size_in_bytes)
                          for p in progs)
        if not stats or need[lanes] < 0.9 * free:
            return lanes, need
    raise AssertionError(f"service: no lane count fits {free} free bytes "
                         f"({need})")


def service_phase(info, g, roots, refs, dev):
    from repro.core.autotune import DEFAULT_TUNER
    from repro.serve.graph_service import GraphService
    from repro.serve.queries import BfsQuery, PprQuery

    svc = GraphService()
    mark = len(DEFAULT_TUNER.audit)
    lanes, need = _service_lanes(g, svc.spec, dev)
    info.step("fit", {"lanes": lanes, "program_bytes": need})
    svc = GraphService(max_lanes=lanes)
    svc.register_graph("g", g)
    queries = ([BfsQuery(r) for r in roots]
               + [PprQuery(r, iters=PR_ITERS) for r in roots])
    tickets = [svc.submit("g", q) for q in queries]
    t0 = time.perf_counter()
    svc.drain()
    answers = []
    for q, t in zip(queries, tickets):
        row = np.asarray(svc.result(t))
        ok = (np.array_equal(row, refs["bfs"][q.source]) if q.kind == "bfs"
              else same_ranks(row, refs["ppr"][q.source]))
        answers.append({"kind": q.kind, "source": q.source, "match": ok})
        if not ok:
            raise AssertionError(f"service: {q} differs from the reference")
    info.update(lanes=lanes, drain_s=time.perf_counter() - t0,
                waves=svc.stats.waves, tier=resolved_tiers(mark),
                answers=answers)


def engine_runs(info, mesh, graphs, *, pagerank: bool):
    """distributed_bfs (and distributed_pagerank) over ``mesh`` for each
    (backend, graph, host arrays, root, refs) of ``graphs``."""
    from repro.core.commit import CommitSpec
    from repro.graphs.algorithms.bfs import distributed_bfs
    from repro.graphs.algorithms.pagerank import distributed_pagerank

    for backend, g, root, refs in graphs:
        spec = CommitSpec(backend=backend, stats=False)
        t0 = time.perf_counter()
        dist, _, res = distributed_bfs(mesh, g, root, capacity="auto",
                                       spec=spec, telemetry=True)
        out = [("bfs", res, np.array_equal(np.asarray(dist),
                                           refs["bfs"][root]),
                time.perf_counter() - t0)]
        if pagerank:
            t0 = time.perf_counter()
            rank, pres = distributed_pagerank(mesh, g, iters=PR_ITERS,
                                              capacity="auto", spec=spec,
                                              telemetry=True)
            out.append(("pagerank", pres, same_ranks(rank, refs["pr"]),
                        time.perf_counter() - t0))
        for alg, r, match, wall in out:
            leaf = next(iter(r.state.values()))
            devices = {s.device for s in leaf.addressable_shards}
            row = {"alg": alg, "backend": backend,
                   "vertices": g.num_vertices, "wall_s": wall,
                   "capacity": int(r.capacity), "rounds": int(r.rounds),
                   "subrounds": int(r.subrounds),
                   "delivered_all": bool(r.delivered_all),
                   "degraded": bool(r.degraded),
                   "state_devices": len(devices), "match": bool(match)}
            info.step("runs", row)
            if not (row["match"] and row["delivered_all"]
                    and not row["degraded"]
                    and row["state_devices"] == mesh.size):
                raise AssertionError(f"engine: {row}")


# -- driver -------------------------------------------------------------------


def main(argv=None) -> int:
    args = parse_args(sys.argv[1:] if argv is None else argv)
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" and not args.rehearse:
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
              f"use --rehearse for a CPU rehearsal", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX found {len(devices)}", file=sys.stderr)
        return 1
    from repro.kernels import interpret_default
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_host_mesh

    cache = enable_compile_cache()
    meter = Meter()
    print(json.dumps({"phase": "start", "platform": dev.platform,
                      "kind": dev.device_kind, "count": len(devices),
                      "chips": args.chips, "scale": args.scale,
                      "seed": args.seed, "rehearse": args.rehearse,
                      "compile_cache": cache}), flush=True)

    if args.chips == 1:             # needs no graph: fail before building
        with phase("kernels", meter, dev) as info:
            kernels_phase(info, seed=args.seed, small=args.rehearse,
                          compiled=not interpret_default())

    small = min(args.scale, FUSED_SCALE)
    g, host, roots = build_graph(args.scale, args.seed)
    gs, hosts, roots_s = build_graph(small, args.seed + 1)
    t0 = time.perf_counter()
    refs = (references(host, roots, ppr=True) if args.chips == 1
            else references(host, roots[:1], ppr=False))
    refs_s = references(hosts, roots_s[:1], ppr=False)
    print(json.dumps({"phase": "references",
                      "wall_s": time.perf_counter() - t0}), flush=True)

    engine_graphs = [("coarse", g, roots[0], refs),
                     ("fused", gs, roots_s[0], refs_s)]
    if args.chips == 4:
        with phase("engine4", meter, dev) as info:
            engine_runs(info, make_host_mesh(4, 1), engine_graphs,
                        pagerank=True)
    else:
        with phase("algorithms", meter, dev) as info:
            algorithms_phase(info, g, roots, refs)
        with phase("service", meter, dev) as info:
            service_phase(info, g, roots, refs, dev)
        with phase("engine", meter, dev) as info:
            engine_runs(info, make_host_mesh(1, 1), engine_graphs,
                        pagerank=False)

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    if args.rehearse:
        print(json.dumps({"rehearsal": True, "device": device,
                          "compile_s": meter.compile_s}))
        return 0
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
