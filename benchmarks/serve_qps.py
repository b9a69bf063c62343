"""Serving throughput/latency — batch-axis waves vs the sequential loop.

The serving claim (ISSUE 4/5 / ROADMAP north star): fusing independent
work items into one wave amortizes the per-call overhead a
query-at-a-time loop pays per item.  Two batch axes:

* ``--axis lanes`` (default): L queries over ONE graph fuse as lanes —
  the benchmark drives a :class:`repro.serve.graph_service.GraphService`
  at every rung of its lane ladder;
* ``--axis graphs``: the same query kind over G tenant graphs fuses as
  a graph batch (the only axis coloring/Boruvka have) — the service is
  driven at every rung of its GRAPH ladder, G=1 being the sequential
  per-graph loop.

Both report QPS and per-query latency percentiles (a query's latency is
the wall time of the wave it rode — microbatching trades p50 for
throughput exactly like LLM serving batchers do), and both check that
every batch width returns the sequential loop's answers.  All widths are
measured INTERLEAVED round-robin (host noise arrives in multi-minute
waves; sequential per-width measurement would hand arbitrary widths a
2x win).

  PYTHONPATH=src python -m benchmarks.serve_qps [--backend auto]
      [--axis lanes|graphs] [--kinds bfs,ppr] [--lanes 1,2,4,8]
      [--graphs 1,2,4,8] [--scale 9] [--queries 32]

``--open-loop`` (ISSUE 7) switches from this closed loop to an OPEN one:
Poisson arrivals at each ``--qps`` level drive the asynchronous
continuous-batching server (:mod:`repro.serve.continuous`) over a
mixed-tenant workload — one hot graph absorbing lane pressure plus
``--tenants`` single-query tenants — and report p50/p99 submit-to-answer
latency vs offered QPS, with the lanes×graphs product axis on
(``product`` mode) and off (``single-axis``, the PR-5 two-axis drain).
``--json`` merges rows carrying ``offered_qps``/``p99_ms`` into the
``aam-bench/v1`` trajectory.

CSV rows: ``serve/<kind>/L=<l>/qps`` / ``serve/<kind>/G=<g>/qps`` with
us-per-query; ``benchmarks.run --json`` folds the same ``sweep(...)`` /
``sweep_graphs(...)`` measurements into the persistent ``aam-bench/v1``
trajectory as its serve suite.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit
from repro.core.commit import BACKENDS, CommitSpec
from repro.serve.graph_service import GraphService
from repro.serve.queries import (BfsQuery, PprQuery, SsspQuery,
                                 StConnQuery, ColoringQuery, MstQuery)

PPR_ITERS = 5


LANE_KINDS = ("bfs", "sssp", "ppr", "stconn")
GRAPH_KINDS = LANE_KINDS + ("coloring", "mst")


def _queries(kind: str, sources, extra):
    if kind == "bfs":
        return [BfsQuery(int(s)) for s in sources]
    if kind == "sssp":
        return [SsspQuery(int(s)) for s in sources]
    if kind == "ppr":
        return [PprQuery(int(s), iters=PPR_ITERS) for s in sources]
    if kind == "stconn":
        return [StConnQuery(int(s), int(t)) for s, t in zip(sources, extra)]
    raise ValueError(f"kind {kind!r} has no lane form; --axis lanes "
                     f"accepts {LANE_KINDS}")


def _spec(backend: str | None) -> CommitSpec | None:
    if backend is None or backend == "auto":
        return None                       # service default: calibrated auto
    return CommitSpec(backend=backend, stats=False)


def _pass(svc, qs, lanes: int):
    """One full pass of ``qs`` through ``svc`` in microbatches of
    ``lanes``: one timed drain per microbatch, so per-query latency =
    its wave's wall time.  Returns (wave_times, lat, results)."""
    wave_times, lat, results = [], [], []
    for lo in range(0, len(qs), lanes):
        chunk = qs[lo:lo + lanes]
        tickets = [svc.submit("g", q) for q in chunk]
        t0 = time.perf_counter()
        svc.drain()
        rows = [svc.result(t) for t in tickets]
        jax.block_until_ready([r for r in rows
                               if not isinstance(r, bool)])
        dt = time.perf_counter() - t0
        wave_times.append(dt)
        lat += [dt] * len(chunk)
        results += rows
    return wave_times, lat, results


def _stats(best, n_queries: int) -> dict:
    total, wave_times, lat, _ = best
    return {
        "qps": n_queries / total,
        "us_per_query": total / n_queries * 1e6,
        "p50_ms": float(np.percentile(lat, 50) * 1e3),
        "p99_ms": float(np.percentile(lat, 99) * 1e3),
        "waves": len(wave_times),
    }


def _interleaved_best(widths, pass_fn, n_queries: int,
                      repeats: int = 5) -> dict:
    """THE batch-width measurement protocol, shared by both axes:
    every width measured INTERLEAVED round-robin (rotating start),
    min-of-passes per width — host noise arrives in multi-second waves,
    so sequential per-width measurement would hand arbitrary widths a
    2x win; interleaving keeps the width-vs-width ratios honest even
    while the absolute times drift (same reasoning as the fig-row
    ``_measure_interleaved``).  ``pass_fn(width)`` runs one full
    workload pass and returns (wave_times, lat, results).  Returns
    {width: (stats dict, results)}."""
    best: dict = {}
    order = list(widths)
    for r in range(max(repeats, 1)):
        rot = order[r % len(order):] + order[:r % len(order)]
        for width in rot:
            wave_times, lat, results = pass_fn(width)
            if width not in best or sum(wave_times) < best[width][0]:
                best[width] = (sum(wave_times), wave_times, lat, results)
    return {w: (_stats(b, n_queries), b[3]) for w, b in best.items()}


def measure_kind(kind: str, g, sources, extra, lane_counts,
                 backend: str | None, repeats: int = 5) -> dict:
    """Lane-axis instance of :func:`_interleaved_best` (cache off so
    every query executes).  Returns {lanes: (stats dict, results)}."""
    qs = _queries(kind, sources, extra)
    svcs = {}
    for lanes in lane_counts:
        svc = GraphService(max_lanes=lanes, cache=False,
                           spec=_spec(backend))
        svc.register_graph("g", g)
        svc.run("g", qs[:lanes])    # compile (+ calibrate) per lane count
        svcs[lanes] = svc
    return _interleaved_best(lane_counts,
                             lambda lanes: _pass(svcs[lanes], qs, lanes),
                             len(qs), repeats)


def _same(kind: str, a, b) -> bool:
    if kind == "stconn":
        return all(x == y for x, y in zip(a, b))
    if kind == "mst":          # (comp, weight, n_edges) per graph
        return all(np.array_equal(np.asarray(x[0]), np.asarray(y[0]))
                   and float(x[1]) == float(y[1]) and int(x[2]) == int(y[2])
                   for x, y in zip(a, b))
    if kind == "ppr":          # float add: rounding-level, like any M change
        return all(np.allclose(np.asarray(x), np.asarray(y), atol=1e-6)
                   for x, y in zip(a, b))
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(a, b))


def sweep(kinds, lanes, *, scale: int, queries: int,
          backend: str | None = None, edge_factor: int = 8, seed: int = 0,
          repeats: int = 5):
    """Returns [{kind, lanes, qps, p50_ms, p99_ms, us_per_query,
    speedup_vs_seq, correct}, ...] — lanes=1 is the sequential loop."""
    from repro.graphs.generators import kronecker, random_weights
    g = kronecker(scale, edge_factor, seed=seed)
    if "sssp" in kinds:
        g = random_weights(g, seed=seed + 1)
    rng = np.random.default_rng(seed)
    sources = rng.choice(g.num_vertices, queries, replace=False)
    extra = rng.choice(g.num_vertices, queries, replace=False)
    out = []
    for kind in kinds:
        by_lane = measure_kind(kind, g, sources, extra, lanes, backend,
                               repeats=repeats)
        base = by_lane[lanes[0]]
        for lane in lanes:
            st, res = by_lane[lane]
            st["kind"], st["lanes"] = kind, lane
            st["speedup_vs_seq"] = base[0]["us_per_query"] \
                / st["us_per_query"]
            st["correct"] = _same(kind, base[1], res)
            out.append(st)
    return out


# ---------------------------------------------------------------------------
# The graph batch axis: one query each over G tenant graphs
# ---------------------------------------------------------------------------


def _tenant_graphs(n: int, *, scale: int, edge_factor: int, seed: int,
                   weighted: bool):
    """n HETEROGENEOUS tenant graphs (alternating scales, distinct
    seeds — different vertex counts and topologies)."""
    from repro.graphs.generators import kronecker, random_weights
    out = []
    for i in range(n):
        g = kronecker(scale - (i % 2), edge_factor, seed=seed + 17 * i)
        out.append(random_weights(g, seed=seed + i) if weighted else g)
    return out


def _graph_query(kind: str, g, rng):
    deg = np.asarray(g.degrees)
    hub = int(np.argmax(deg))
    if kind == "bfs":
        return BfsQuery(hub)
    if kind == "sssp":
        return SsspQuery(hub)
    if kind == "ppr":
        return PprQuery(hub, iters=PPR_ITERS)
    if kind == "stconn":
        return StConnQuery(hub, int(rng.integers(0, g.num_vertices)))
    if kind == "coloring":
        return ColoringQuery()
    if kind == "mst":
        return MstQuery()
    raise ValueError(f"unknown kind {kind!r}; --axis graphs accepts "
                     f"{GRAPH_KINDS}")


def _pass_graphs(svc, queries_by_gid: dict, width: int):
    """One full pass of one-query-per-graph through ``svc`` in
    graph-batches of ``width`` (== the service's max_graphs, so each
    drain is exactly one graph wave).  Returns (wave_times, lat,
    results)."""
    gids = list(queries_by_gid)
    wave_times, lat, results = [], [], []
    for lo in range(0, len(gids), width):
        chunk = gids[lo:lo + width]
        tickets = [svc.submit(gid, queries_by_gid[gid]) for gid in chunk]
        t0 = time.perf_counter()
        svc.drain()
        rows = [svc.result(t) for t in tickets]
        jax.block_until_ready([x for r in rows
                               for x in (r if isinstance(r, tuple) else (r,))
                               if not isinstance(x, bool)])
        dt = time.perf_counter() - t0
        wave_times.append(dt)
        lat += [dt] * len(chunk)
        results += rows
    return wave_times, lat, results


def measure_kind_graphs(kind: str, graphs, counts, backend: str | None,
                        repeats: int = 5) -> dict:
    """Graph-axis instance of :func:`_interleaved_best`.  Returns
    {width: (stats dict, results)}."""
    rng = np.random.default_rng(0)
    queries = {i: _graph_query(kind, g, rng) for i, g in enumerate(graphs)}
    svcs = {}
    for width in counts:
        svc = GraphService(max_graphs=width, cache=False,
                           spec=_spec(backend))
        for i, g in enumerate(graphs):
            svc.register_graph(i, g)
        svcs[width] = svc
        _pass_graphs(svc, queries, width)   # compile (+ calibrate)
    return _interleaved_best(
        counts, lambda w: _pass_graphs(svcs[w], queries, w), len(graphs),
        repeats)


def sweep_graphs(kinds, counts, *, scale: int, backend: str | None = None,
                 edge_factor: int = 8, seed: int = 0, repeats: int = 5):
    """Returns [{kind, graphs, qps, p50_ms, p99_ms, us_per_query,
    speedup_vs_seq, correct}, ...] — graphs=1 is the sequential
    per-graph loop.  The tenant set has max(counts) heterogeneous
    graphs; every width serves the SAME one-query-per-graph workload."""
    n = max(counts)
    out = []
    for kind in kinds:
        graphs = _tenant_graphs(n, scale=scale, edge_factor=edge_factor,
                                seed=seed, weighted=(kind in ("sssp",
                                                              "mst")))
        by_width = measure_kind_graphs(kind, graphs, counts, backend,
                                       repeats=repeats)
        base = by_width[counts[0]]
        for width in counts:
            st, res = by_width[width]
            st["kind"], st["graphs"] = kind, width
            st["speedup_vs_seq"] = base[0]["us_per_query"] \
                / st["us_per_query"]
            st["correct"] = _same(kind, base[1], res)
            out.append(st)
    return out


# ---------------------------------------------------------------------------
# Open-loop latency under load: Poisson arrivals against the continuous
# batching loop (ISSUE 7) — p50/p99 vs offered QPS
# ---------------------------------------------------------------------------


def _open_workload(kind: str, graphs_by_gid: dict, n: int, rng,
                   hot_frac: float = 0.5):
    """One mixed-tenant arrival sequence: ``hot_frac`` of queries hit
    the hot graph (lane pressure), the rest spread over the single-query
    tenants (graph pressure) — the shape only the PRODUCT axis serves as
    one wave."""
    gids = [g for g in graphs_by_gid if g != "hot"]
    subs = []
    for _ in range(n):
        gid = "hot" if rng.random() < hot_frac \
            else gids[int(rng.integers(len(gids)))]
        g = graphs_by_gid[gid]
        src = int(rng.integers(g.num_vertices))
        if kind == "bfs":
            q = BfsQuery(src)
        elif kind == "sssp":
            q = SsspQuery(src)
        elif kind == "ppr":
            q = PprQuery(src, iters=PPR_ITERS)
        elif kind == "stconn":
            q = StConnQuery(src, int(rng.integers(g.num_vertices)))
        else:
            raise ValueError(f"kind {kind!r} has no lane form; the "
                             f"open-loop bench accepts {LANE_KINDS}")
        subs.append((gid, q))
    return subs


def _trace_probe(kind: str, graphs: dict, backend: str | None,
                 product: bool, seed: int) -> dict:
    """Wave-level trace summary (rounds / mean commit density / ladder
    moves) for one (kind, mode) config: a tiny UNTIMED drain with
    ``CommitSpec(trace=True)`` feeds :func:`repro.obs.wavetap.summary`.
    The timed open-loop runs stay untraced — the p99 acceptance gate
    needs the clean jaxprs ``aamlint --trace-off-clean`` proves."""
    import dataclasses
    from repro.obs import wavetap as OW
    base = _spec(backend)
    if base is None:
        base = CommitSpec(backend="auto", sort=False)
    svc = GraphService(cache=False, product=product,
                       spec=dataclasses.replace(base, trace=True,
                                                stats=True))
    for gid, g in graphs.items():
        svc.register_graph(gid, g)
    for gid, q in _open_workload(kind, graphs, 4,
                                 np.random.default_rng(seed)):
        svc.submit(gid, q)
    OW.clear()
    svc.drain()
    return OW.summary(OW.collector().drain())


def open_loop(kinds=("bfs",), *, qps_levels=(20, 50), duration_s: float = 2.0,
              scale: int = 7, tenants: int = 5, backend: str | None = None,
              seed: int = 0, max_wait_s: float = 0.005,
              modes=("product", "single-axis")):
    """The latency-under-load benchmark: an OPEN loop (arrivals don't
    wait for completions — Poisson gaps at each offered QPS) drives the
    asynchronous :class:`repro.serve.continuous.ContinuousServer` over a
    mixed-tenant workload, once with the product axis on and once
    degraded to the PR-5 two-axis drain (``product=False``).  Per-query
    latency is submit-to-publish through the service clock; rows carry
    ``offered_qps``/``achieved_qps``/``p50_ms``/``p99_ms`` per
    (kind, mode, level)."""
    from repro.graphs.generators import kronecker, random_weights
    from repro.serve.continuous import ContinuousServer

    rows = []
    for kind in kinds:
        graphs = {"hot": kronecker(scale, 8, seed=seed)}
        for i in range(tenants):
            graphs[f"t{i}"] = kronecker(max(scale - 1, 2), 8,
                                        seed=seed + 17 * i + 1)
        if kind == "sssp":
            graphs = {gid: random_weights(g, seed=seed + 3)
                      for gid, g in graphs.items()}
        for mode in modes:
            probe = _trace_probe(kind, graphs, backend,
                                 mode == "product", seed)
            svc = GraphService(cache=False, product=(mode == "product"),
                               spec=_spec(backend))
            for gid, g in graphs.items():
                svc.register_graph(gid, g)
            # warm the jit ladder: one mixed drain compiles the shapes
            # the open loop will hit (hot lane pressure + tenant spread)
            warm = _open_workload(kind, graphs, 2 * (tenants + 1),
                                  np.random.default_rng(seed + 7))
            for gid, q in warm:
                svc.submit(gid, q)
            svc.drain()
            for qps in qps_levels:
                rng = np.random.default_rng(seed + 11)
                n = max(8, int(duration_s * qps))
                subs = _open_workload(kind, graphs, n, rng)
                gaps = rng.exponential(1.0 / qps, n)
                with ContinuousServer(svc, max_wait_s=max_wait_s) as cs:
                    t0 = time.perf_counter()
                    tickets = []
                    for (gid, q), gap in zip(subs, gaps):
                        time.sleep(gap)
                        tickets.append(cs.submit(gid, q))
                    cs.results(tickets, timeout=600)
                    total = time.perf_counter() - t0
                    if cs.last_error is not None:
                        raise cs.last_error
                lat = [(cs.done_at[t] - cs.submit_at[t]) * 1e3
                       for t in tickets]
                rows.append({
                    "kind": kind, "mode": mode, "offered_qps": qps,
                    "achieved_qps": round(len(tickets) / total, 1),
                    "p50_ms": round(float(np.percentile(lat, 50)), 2),
                    "p99_ms": round(float(np.percentile(lat, 99)), 2),
                    "mean_ms": round(float(np.mean(lat)), 2),
                    "n": len(tickets),
                    "product_waves": svc.stats.product_waves,
                    "trace_rounds": probe["rounds"],
                    "trace_mean_density": probe["mean_density"],
                    "trace_ladder_moves": probe["ladder_moves"],
                })
    return rows


def _open_rows_to_json(rows, json_path: str) -> None:
    """Land the open-loop rows in the persistent ``aam-bench/v1``
    trajectory (same merge protocol as :func:`_crash_rows_to_json`:
    replace previous ``serve_open`` rows, keep everything else)."""
    import json
    import os
    doc = None
    if os.path.exists(json_path):
        try:
            with open(json_path) as f:
                doc = json.load(f)
            if doc.get("schema") != "aam-bench/v1":
                doc = None
        except (OSError, ValueError):
            doc = None
    if doc is None:
        doc = {"schema": "aam-bench/v1", "sizes": "open",
               "platform": jax.default_backend(), "rows": [],
               "summary": {}}
    doc["rows"] = [r for r in doc.get("rows", [])
                   if r.get("suite") != "serve_open"]
    for r in rows:
        doc["rows"].append({
            "suite": "serve_open", "backend": "auto",
            "name": f"serve_open/{r['kind']}/{r['mode']}"
                    f"/qps={r['offered_qps']}",
            "us_per_call": round(r["p99_ms"] * 1e3, 1),
            "offered_qps": r["offered_qps"],
            "achieved_qps": r["achieved_qps"],
            "p50_ms": r["p50_ms"], "p99_ms": r["p99_ms"],
            "trace_rounds": r.get("trace_rounds", 0),
            "trace_mean_density": r.get("trace_mean_density", 0.0),
            "trace_ladder_moves": r.get("trace_ladder_moves", 0),
            "derived": f"n={r['n']} mean={r['mean_ms']}ms "
                       f"product_waves={r['product_waves']} "
                       f"rounds={r.get('trace_rounds', 0)} "
                       f"density={r.get('trace_mean_density', 0.0)}"})
    doc.setdefault("summary", {})["serve_open"] = {
        f"{r['kind']}/{r['mode']}/qps={r['offered_qps']}": {
            "p50_ms": r["p50_ms"], "p99_ms": r["p99_ms"],
            "achieved_qps": r["achieved_qps"]}
        for r in rows}
    with open(json_path, "w") as f:
        json.dump(doc, f, indent=1)
        f.write("\n")


# ---------------------------------------------------------------------------
# Crash-resume: kill mid-drain, restore from snapshot, finish the workload
# ---------------------------------------------------------------------------


def crash_resume(kinds=("bfs", "ppr"), *, scale: int = 8, queries: int = 32,
                 lanes: int = 8, crash_at: float = 0.5,
                 backend: str | None = None, seed: int = 0,
                 ckpt_dir: str | None = None):
    """The durability benchmark: a supervised service snapshots warm,
    takes the full workload (journaled tickets), crashes at
    ``crash_at`` of the way through its drain waves, restores, and
    finishes.  Reports restore latency and post-restore recovery QPS,
    and checks the recovered answers bit-match an uninterrupted
    service's.  Returns [{kind, restore_ms, recovery_qps, ...}] rows
    for the persistent bench trajectory."""
    import shutil
    import tempfile

    from repro.checkpoint.checkpointer import Checkpointer
    from repro.graphs.generators import kronecker, random_weights
    from repro.serve.durable import ServiceSupervisor

    g = kronecker(scale, 8, seed=seed)
    if "sssp" in kinds:
        g = random_weights(g, seed=seed + 1)
    rng = np.random.default_rng(seed)
    sources = rng.choice(g.num_vertices, queries, replace=False)
    extra = rng.choice(g.num_vertices, queries, replace=False)
    base_dir = ckpt_dir or tempfile.mkdtemp(prefix="aam_crash_bench_")
    rows = []
    try:
        for kind in kinds:
            qs = _queries(kind, sources, extra)
            # the uninterrupted reference (also warms jit/calibration)
            ref = GraphService(max_lanes=lanes, cache=False,
                               spec=_spec(backend))
            ref.register_graph("g", g)
            ref_rows = ref.run("g", qs)
            svc = GraphService(max_lanes=lanes, cache=False,
                               spec=_spec(backend))
            svc.register_graph("g", g)
            sup = ServiceSupervisor(
                svc, Checkpointer(f"{base_dir}/{kind}"),
                log=lambda *_: None)
            sup.save()                      # snapshot the warm service
            tickets = [sup.submit("g", q) for q in qs]
            n_waves = max(-(-len(qs) // lanes), 1)
            kill_at = min(int(n_waves * crash_at), n_waves - 1)

            def injector(where, i, kill_at=kill_at):
                if i == kill_at:
                    raise RuntimeError("injected host loss")

            svc.fault_injector = injector
            try:
                svc.drain()
                raise AssertionError("injector never fired")
            except RuntimeError:
                pass                        # the crash
            t0 = time.perf_counter()
            restored = sup.restore()        # snapshot + WAL replay
            restore_s = time.perf_counter() - t0
            t0 = time.perf_counter()
            restored.drain()
            out = [restored.result(t) for t in tickets]
            jax.block_until_ready(
                [x for r in out
                 for x in (r if isinstance(r, tuple) else (r,))
                 if not isinstance(x, bool)])
            recover_s = time.perf_counter() - t0
            rows.append({
                "kind": kind, "lanes": lanes, "queries": len(qs),
                "crash_wave": kill_at,
                "restore_ms": round(restore_s * 1e3, 2),
                "recovery_qps": round(len(qs) / recover_s, 1),
                "recovery_s": round(recover_s, 4),
                "timing_runs_post_restore": restored.stats.timing_runs,
                "tickets_recovered": len(out) == len(tickets),
                "correct": _same(kind, ref_rows, out),
            })
    finally:
        if ckpt_dir is None:
            shutil.rmtree(base_dir, ignore_errors=True)
    return rows


def _crash_rows_to_json(rows, json_path: str) -> None:
    """Land the crash-resume rows in the persistent ``aam-bench/v1``
    trajectory: merge into ``json_path`` if it exists (replacing any
    previous crash rows), create a minimal doc otherwise."""
    import json
    import os
    doc = None
    if os.path.exists(json_path):
        try:
            with open(json_path) as f:
                doc = json.load(f)
            if doc.get("schema") != "aam-bench/v1":
                doc = None
        except (OSError, ValueError):
            doc = None
    if doc is None:
        doc = {"schema": "aam-bench/v1", "sizes": "crash",
               "platform": jax.default_backend(), "rows": [],
               "summary": {}}
    doc["rows"] = [r for r in doc.get("rows", [])
                   if r.get("suite") != "crash"]
    for r in rows:
        doc["rows"].append({
            "suite": "crash", "backend": "auto",
            "name": f"crash/{r['kind']}/restore",
            "us_per_call": round(r["restore_ms"] * 1e3, 1),
            "derived": f"recovery_qps={r['recovery_qps']} "
                       f"crash_wave={r['crash_wave']} "
                       f"recovered={r['tickets_recovered']} "
                       f"correct={r['correct']} "
                       f"timing_runs={r['timing_runs_post_restore']}"})
    doc.setdefault("summary", {})["crash"] = {
        r["kind"]: {"restore_ms": r["restore_ms"],
                    "recovery_qps": r["recovery_qps"],
                    "recovered": r["tickets_recovered"],
                    "correct": r["correct"]}
        for r in rows}
    with open(json_path, "w") as f:
        json.dump(doc, f, indent=1)
        f.write("\n")


def main(kinds=("bfs", "ppr"), lanes=(1, 2, 4, 8), scale: int = 8,
         queries: int = 32, backend: str | None = None,
         axis: str = "lanes", graphs=(1, 2, 4, 8)):
    if axis == "graphs":
        for st in sweep_graphs(kinds, graphs, scale=scale,
                               backend=backend):
            assert st["correct"], (st["kind"], st["graphs"],
                                   "graph-batched results diverged from "
                                   "the sequential loop")
            emit(f"serve/{st['kind']}/G={st['graphs']}/qps",
                 st["us_per_query"] / 1e6,
                 f"qps={st['qps']:.0f} p50={st['p50_ms']:.1f}ms "
                 f"p99={st['p99_ms']:.1f}ms "
                 f"speedup_vs_seq={st['speedup_vs_seq']:.2f}")
        return
    for st in sweep(kinds, lanes, scale=scale, queries=queries,
                    backend=backend):
        assert st["correct"], (st["kind"], st["lanes"],
                               "lane-batched results diverged from the "
                               "sequential loop")
        emit(f"serve/{st['kind']}/L={st['lanes']}/qps",
             st["us_per_query"] / 1e6,
             f"qps={st['qps']:.0f} p50={st['p50_ms']:.1f}ms "
             f"p99={st['p99_ms']:.1f}ms "
             f"speedup_vs_seq={st['speedup_vs_seq']:.2f}")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--backend", default=None,
                    choices=BACKENDS + ("auto",),
                    help="commit backend (default: the service's "
                         "calibrated auto spec)")
    ap.add_argument("--axis", default="lanes", choices=("lanes", "graphs"),
                    help="batch axis to sweep: query lanes over one "
                         "graph, or a graph batch over tenant graphs")
    ap.add_argument("--kinds", default=None,
                    help="default: bfs,ppr (lanes) / bfs,coloring (graphs)")
    ap.add_argument("--lanes", default="1,2,4,8")
    ap.add_argument("--graphs", default="1,2,4,8")
    ap.add_argument("--scale", type=int, default=8)
    ap.add_argument("--queries", type=int, default=32)
    ap.add_argument("--crash-resume", action="store_true",
                    help="durability mode: snapshot, crash mid-drain, "
                         "restore, finish; reports restore latency and "
                         "recovery QPS")
    ap.add_argument("--crash-at", type=float, default=0.5,
                    help="fraction of drain waves before the injected "
                         "crash (default 0.5)")
    ap.add_argument("--open-loop", action="store_true",
                    help="latency-under-load mode: Poisson arrivals "
                         "against the continuous-batching loop; p50/p99 "
                         "vs offered QPS, product vs single-axis drain")
    ap.add_argument("--qps", default="20,50",
                    help="open-loop offered QPS levels (default 20,50)")
    ap.add_argument("--duration", type=float, default=2.0,
                    help="open-loop seconds of arrivals per level")
    ap.add_argument("--tenants", type=int, default=5,
                    help="open-loop single-query tenant graphs beside "
                         "the hot graph (default 5)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="with --crash-resume/--open-loop: merge the "
                         "rows into this aam-bench/v1 trajectory file")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.open_loop:
        kinds = tuple((args.kinds or "bfs").split(","))
        rows = open_loop(kinds,
                         qps_levels=tuple(int(x)
                                          for x in args.qps.split(",")),
                         duration_s=args.duration, scale=args.scale,
                         tenants=args.tenants, backend=args.backend)
        for r in rows:
            emit(f"serve_open/{r['kind']}/{r['mode']}"
                 f"/qps={r['offered_qps']}", r["p99_ms"] / 1e3,
                 f"p50={r['p50_ms']}ms p99={r['p99_ms']}ms "
                 f"achieved_qps={r['achieved_qps']} n={r['n']}")
        by_level: dict = {}
        for r in rows:
            by_level.setdefault((r["kind"], r["offered_qps"]),
                                {})[r["mode"]] = r["p99_ms"]
        for (kind, qps), modes in sorted(by_level.items()):
            if len(modes) == 2:
                print(f"# {kind} @ {qps} qps: p99 product="
                      f"{modes['product']}ms single-axis="
                      f"{modes['single-axis']}ms")
        if args.json:
            _open_rows_to_json(rows, args.json)
        raise SystemExit(0)
    if args.crash_resume:
        kinds = tuple((args.kinds or "bfs,ppr").split(","))
        lane = max(int(x) for x in args.lanes.split(","))
        rows = crash_resume(kinds, scale=args.scale, queries=args.queries,
                            lanes=lane, crash_at=args.crash_at,
                            backend=args.backend)
        for r in rows:
            assert r["tickets_recovered"], (r["kind"], "lost tickets")
            assert r["correct"], (r["kind"], "recovered answers diverged")
            emit(f"crash/{r['kind']}/restore", r["restore_ms"] / 1e3,
                 f"recovery_qps={r['recovery_qps']} "
                 f"crash_wave={r['crash_wave']} "
                 f"timing_runs={r['timing_runs_post_restore']}")
        if args.json:
            _crash_rows_to_json(rows, args.json)
        raise SystemExit(0)
    kinds = args.kinds or ("bfs,coloring" if args.axis == "graphs"
                           else "bfs,ppr")
    main(kinds=tuple(kinds.split(",")),
         lanes=tuple(int(x) for x in args.lanes.split(",")),
         graphs=tuple(int(x) for x in args.graphs.split(",")),
         scale=args.scale, queries=args.queries, backend=args.backend,
         axis=args.axis)
