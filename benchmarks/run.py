"""Benchmark runner — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  Figures map to the paper as
documented in DESIGN.md §6; fig5/fig7 spawn child processes with forced
host-device counts (this process keeps 1 device).

  PYTHONPATH=src python -m benchmarks.run [--only fig4,table1]
                                           [--backend atomic|coarse|pallas|auto]
                                           [--json BENCH_pr3.json [--sizes tiny]]

``--json`` runs the schema-stable tiny perf matrix (fig4/fig6 sweeps ×
every backend × the calibrated ``auto`` spec) and writes it as JSON — the
persistent bench trajectory every PR appends to and compares against.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback

from benchmarks import (bench_moe, fig2_perf_model, fig3_single_vertex,
                        fig4_coarsening, fig5_coalescing, fig6_bfs_scale,
                        fig7_scaling, serve_qps, table1_realworld)
from repro.core.commit import BACKENDS

SUITES = {
    "fig2": fig2_perf_model.main,
    "fig3": fig3_single_vertex.main,
    "fig4": fig4_coarsening.main,
    "fig5": fig5_coalescing.main,
    "fig6": fig6_bfs_scale.main,
    "table1": table1_realworld.main,
    "fig7": fig7_scaling.main,
    "moe": bench_moe.main,
    "serve": serve_qps.main,
}

# suites whose commit mechanism is a first-class CommitSpec axis:
# suite -> kwargs for a single-backend run
BACKEND_AWARE = {
    "fig3": lambda b: {"backends": (b,)},
    "fig4": lambda b: {"backend": b},
    "fig5": lambda b: {"backend": b},
    "fig6": lambda b: {"backend": b},
    "fig7": lambda b: {"backend": b},
    "table1": lambda b: {"backend": b},
    "serve": lambda b: {"backend": b},
}


# --json measurement matrix.  "tiny" backs the committed BENCH_*.json
# trajectory; "smoke" is the tier-1 CI schema check (seconds, not minutes).
# fig7 spawns forced-device-count children, so only "tiny" carries it.
SCHEMA = "aam-bench/v1"
JSON_SIZES = {
    "tiny": dict(fig4=dict(scale=10, edge_factor=8, ms=(64, 1024, None)),
                 fig6=dict(scales=(9, 10), densities=(16,), edge_factor=8,
                           density_scale=9),
                 fig3=dict(v=1 << 12, n=2048),
                 fused=dict(v=1 << 12, n=2048, width=4, base=1 << 20),
                 fig7=dict(scale=9, ps=(1, 2, 4), reps=3,
                           backends=("coarse",)),
                 serve=dict(kinds=("bfs", "ppr"), lanes=(1, 8), scale=7,
                            queries=16, repeats=7,
                            gkinds=("bfs", "coloring"), gcounts=(1, 8),
                            gscale=7),
                 backends=("atomic", "coarse", "pallas", "fused", "auto"),
                 repeats=7),
    "smoke": dict(fig4=dict(scale=8, edge_factor=4, ms=(64, None)),
                  fig6=dict(scales=(8,), densities=(4,), edge_factor=4,
                            density_scale=8),
                  fig3=dict(v=1 << 10, n=512),
                  serve=dict(kinds=("bfs",), lanes=(1, 4), scale=7,
                             queries=8, repeats=2,
                             gkinds=("bfs",), gcounts=(1, 4), gscale=6),
                  backends=("atomic", "coarse", "auto"), repeats=2),
}


def _summarize(rows: list) -> dict:
    """Per suite: calibrated-auto time over the best hand-picked static
    spec.

    "Best static spec" is ONE spec summed over the suite's points (what a
    user would actually pin), not a per-point min over every static row —
    the latter is winner's-curse-biased on a noisy host.  The per-point
    worst ratio is kept alongside for transparency."""
    out = {}
    for suite in ("fig4", "fig6"):
        srows = [r for r in rows if r["suite"] == suite
                 and "stats_" not in r["name"]]
        if not srows:
            continue

        def point(r):
            return r["name"].split("/")[1] if suite == "fig6" else "all"

        def spec_id(r):   # fig4 rows are one spec each; fig6 specs span points
            return r["name"] if suite == "fig4" else r["backend"]

        totals: dict = {}
        for r in srows:
            totals[spec_id(r)] = totals.get(spec_id(r), 0.0) \
                + r["us_per_call"]
        auto_keys = [k for k in totals if "auto" in str(k)]
        static = {k: v for k, v in totals.items() if k not in auto_keys}
        if not auto_keys or not static:
            continue
        auto_t = min(totals[k] for k in auto_keys)
        best_k = min(static, key=static.get)
        ratio = auto_t / static[best_k]
        worst_point = max(
            (min(r["us_per_call"] for r in srows
                 if point(r) == p and r["backend"] == "auto")
             / min(r["us_per_call"] for r in srows
                   if point(r) == p and r["backend"] != "auto"))
            for p in {point(r) for r in srows})
        out[suite] = {"auto_over_best_static": round(ratio, 3),
                      "best_static": str(best_k),
                      "worst_point_ratio": round(worst_point, 3),
                      "within_10pct": bool(ratio <= 1.10),
                      "points": len({point(r) for r in srows})}
    return out


def _diff_vs_previous(doc: dict, out_path: str) -> dict | None:
    """Auto-diff the fresh matrix against the most recent previous
    BENCH_*.json next to ``out_path`` (the persistent trajectory).

    Joins rows by name (so suites added later simply don't match) and
    reports the per-suite median current/previous time ratio — median,
    not mean, because one noisy row on a shared host must not flip the
    verdict.  Returns None when there is no usable baseline."""
    import statistics
    from pathlib import Path
    out = Path(out_path).resolve()
    try:
        cands = [p for p in out.parent.glob("BENCH_*.json")
                 if p.resolve() != out]
    except OSError:
        return None
    base = None
    for p in sorted(cands, key=lambda p: p.stat().st_mtime, reverse=True):
        try:
            bdoc = json.loads(p.read_text())
        except (OSError, ValueError):
            continue
        if bdoc.get("schema") == SCHEMA:
            base = (p, bdoc)
            break
    if base is None:
        return None
    prev = {r["name"]: r["us_per_call"]
            for r in base[1].get("rows", []) if r.get("us_per_call")}
    suites: dict = {}
    for r in doc["rows"]:
        p_us = prev.get(r["name"])
        if p_us:
            suites.setdefault(r["suite"], []).append(
                r["us_per_call"] / p_us)
    return {
        "baseline": base[0].name,
        "rows_compared": sum(len(v) for v in suites.values()),
        "suites": {s: {"median_ratio": round(statistics.median(v), 3),
                       "rows": len(v)}
                   for s, v in sorted(suites.items())},
    }


def _measure_interleaved(fns: dict, repeats: int, inner: int = 3) -> dict:
    """min-of-repeats per entry, measured ROUND-ROBIN so every entry sees
    the same host-noise environment (sequential per-spec timing lets CPU
    frequency drift hand arbitrary specs a 30%+ win).  Each sample
    averages ``inner`` consecutive calls to smooth dispatch jitter, and
    the order ROTATES every round so no entry systematically runs in the
    cache shadow of an expensive neighbor (e.g. always right after the
    interpret-mode pallas burst)."""
    import jax
    keys = list(fns)
    best = {}
    for k in keys:                      # warmup: compile + calibration
        jax.block_until_ready(fns[k]())
        jax.block_until_ready(fns[k]())
        best[k] = float("inf")
    for r in range(repeats):
        rot = keys[r % len(keys):] + keys[:r % len(keys)]
        for k in rot:
            t0 = time.perf_counter()
            for _ in range(inner):
                jax.block_until_ready(fns[k]())
            best[k] = min(best[k], (time.perf_counter() - t0) / inner)
    return best


def _count_kernel_launches(fn, *args) -> int:
    """pallas_call eqns in fn's jaxpr, descending into pjit/cond/scan
    sub-jaxprs (NOT into kernel bodies — they carry no pallas_call)."""
    import jax

    def cnt(jx):
        total = 0
        for eqn in jx.eqns:
            if eqn.primitive.name == "pallas_call":
                total += 1
                continue
            for v in eqn.params.values():
                for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                    inner = getattr(sub, "jaxpr", sub)
                    if hasattr(inner, "eqns"):
                        total += cnt(inner)
        return total
    return cnt(jax.make_jaxpr(fn)(*args).jaxpr)


def _fused_rows(fu: dict, reps: int) -> list:
    """fused-vs-unfused route-tail rows (fig3-style contention ladder).

    The unfused baselines run the pre-fused pipeline verbatim: jnp-side
    local-key computation + ``make_messages`` + a SEPARATE commit pass
    (coarse sort / pallas kernel launch).  The fused row is one
    ``fused_commit_site`` launch doing key+reorder+commit in-kernel."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import commit as C
    from repro.core.commit import CommitSpec, commit
    from repro.core.messages import make_messages
    from repro.kernels import interpret_default

    v, n, width, base = fu["v"], fu["n"], fu["width"], fu["base"]
    nrows = v // width
    interp = interpret_default()
    rng = np.random.default_rng(7)
    rows: list = []
    fsp = CommitSpec(backend="fused", sort=False, stats=False,
                     interpret=interp)
    for contention, conc in (("low", 10), ("high", 100)):
        tgt_np = base + rng.integers(0, max(nrows // conc, 1), n)
        tgt_np[rng.random(n) < 0.12] = -1        # bucket-fill slots
        tgt = jnp.asarray(tgt_np, jnp.int32)
        lane = jnp.asarray(rng.integers(0, width, n), jnp.int32)
        val = jnp.asarray(rng.integers(0, 100, n), jnp.int32)
        for op, st0 in (("min", jnp.full((v,), 2 ** 30, jnp.int32)),
                        ("add", jnp.zeros((v,), jnp.int32))):

            def unfused(s, t, vl, ln, sp, op=op):
                ok = t >= 0
                key = jnp.where(ok, t - base, 0) * width + ln
                return commit(s, make_messages(key.astype(jnp.int32),
                                               vl, ok), op, sp).state

            def fused(s, t, vl, ln, op=op):
                return C.fused_commit_site(
                    s, t, vl, op, fsp, lane=ln, base=base,
                    width=width).state

            specs = {"unfused_coarse":
                     CommitSpec(backend="coarse", sort=False, stats=False),
                     "unfused_pallas":
                     CommitSpec(backend="pallas", sort=False, stats=False,
                                interpret=interp)}
            fns = {k: (lambda f=jax.jit(lambda s, t, vl, ln, sp=sp:
                                        unfused(s, t, vl, ln, sp)):
                       f(st0, tgt, val, lane))
                   for k, sp in specs.items()}
            jfused = jax.jit(fused)
            fns["fused"] = lambda: jfused(st0, tgt, val, lane)
            np.testing.assert_array_equal(         # parity before timing
                fns["fused"](), fns["unfused_coarse"]())
            launches = {k: _count_kernel_launches(
                (lambda s, t, vl, ln, sp=sp: unfused(s, t, vl, ln, sp)),
                st0, tgt, val, lane) for k, sp in specs.items()}
            launches["fused"] = _count_kernel_launches(
                fused, st0, tgt, val, lane)
            best = _measure_interleaved(fns, reps)
            for k, t in best.items():
                derived = f"kernel_launches={launches[k]}"
                if k == "fused":
                    derived += (" separate_commit_launch=0"
                                f" speedup_vs_unfused_coarse="
                                f"{best['unfused_coarse'] / t:.2f}"
                                f" speedup_vs_unfused_pallas="
                                f"{best['unfused_pallas'] / t:.2f}")
                else:
                    derived += " separate_commit_launch=1"
                rows.append({"suite": "fused",
                             "backend": "fused" if k == "fused"
                             else k.replace("unfused_", ""),
                             "name": f"fused/{op}/{contention}/{k}",
                             "us_per_call": round(t * 1e6, 1),
                             "derived": derived})
    return rows


def _fused_suite_main() -> None:
    """CSV entry point: ``--suite fused`` route-tail comparison rows."""
    fu = JSON_SIZES["tiny"]["fused"]
    for r in _fused_rows(fu, JSON_SIZES["tiny"]["repeats"]):
        print(f"{r['name']},{r['us_per_call']},{r['derived']}")


SUITES["fused"] = _fused_suite_main


def bench_json(sizes: str) -> dict:
    """The fig4/fig6 tiny sweeps × every backend × auto, as stable rows."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import autotune as AT
    from repro.core.commit import CommitSpec
    from repro.graphs.algorithms.bfs import bfs
    from repro.graphs.generators import kronecker

    cfg = JSON_SIZES[sizes]
    reps = cfg["repeats"]
    rows: list = []

    def add(suite, backend, name, sec, derived="", **extra):
        rows.append({"suite": suite, "backend": backend, "name": name,
                     "us_per_call": round(sec * 1e6, 1),
                     "derived": derived, **extra})

    def spec_for(backend, m=None):
        if backend == "auto":
            # same sort/stats base as the static specs it races against
            return CommitSpec(backend="auto", sort=False, stats=False)
        if backend == "atomic":
            return CommitSpec(backend="atomic", stats=False)
        return CommitSpec(backend=backend, m=m, sort=False, stats=False)

    # fig4: BFS runtime vs transaction size M on one Kronecker graph
    f4 = cfg["fig4"]
    g = kronecker(f4["scale"], f4["edge_factor"], seed=1)
    src = int(np.argmax(np.asarray(g.degrees)))
    fns = {}
    for backend in cfg["backends"]:
        ms = (None,) if backend in ("atomic", "auto") else f4["ms"]
        for m in ms:
            sp = spec_for(backend, m)
            label = "auto" if backend == "auto" else f"M={m or 'inf'}"
            fns[(backend, label)] = (
                lambda sp=sp: bfs(g, src, spec=sp).dist)
    pol4 = AT.policy_for(spec_for("auto"),
                         jax.ShapeDtypeStruct((g.num_vertices,),
                                              jnp.int32),
                         n=g.src.shape[0])
    for (backend, label), t in _measure_interleaved(fns, reps).items():
        add("fig4", backend, f"fig4/{backend}/{label}", t,
            f"resolved={pol4.backend}" if backend == "auto" else "")
    if "pallas" in cfg["backends"]:
        # satellite: the no-stats kernel path must be the cheap one
        t_on, t_off = fig4_coarsening.stats_overhead(g, src, "pallas")
        add("fig4", "pallas", "fig4/pallas/stats_on", t_on)
        add("fig4", "pallas", "fig4/pallas/stats_off", t_off,
            f"nostats_cheaper={t_off < t_on}")

    # fig3: single-vertex commit under low/high contention, per backend
    f3 = cfg.get("fig3")
    if f3:
        from repro.core.commit import commit
        from repro.core.messages import make_messages
        rng = np.random.default_rng(0)
        v3, n3 = f3["v"], f3["n"]
        for contention, conc in (("low", 10), ("high", 100)):
            tgt = jnp.asarray(rng.integers(0, max(n3 // conc, 1), n3),
                              jnp.int32)
            val = jnp.asarray(rng.integers(0, 100, n3), jnp.int32)
            msgs = make_messages(tgt, val, jnp.ones((n3,), bool))
            for op, st0 in (("min", jnp.full((v3,), 2 ** 30, jnp.int32)),
                            ("add", jnp.zeros((v3,), jnp.int32))):
                fns = {}
                for b in cfg["backends"]:
                    f = jax.jit(lambda s, m, op=op, sp=spec_for(b):
                                commit(s, m, op, sp).state)
                    fns[b] = (lambda f=f, s=st0, m=msgs: f(s, m))
                for b, t in _measure_interleaved(fns, reps).items():
                    add("fig3", b, f"fig3/{op}/{contention}/{b}", t)

    # fused: the route tail after the all_to_all — key prep + separate
    # commit launch (pre-fused pipeline) vs ONE fused kernel launch
    fu = cfg.get("fused")
    if fu:
        for r in _fused_rows(fu, reps):
            rows.append(r)

    # fig6: BFS across |V| and density, per backend
    f6 = cfg["fig6"]
    points = [(f"V=2^{s}", kronecker(s, f6["edge_factor"], seed=3))
              for s in f6["scales"]]
    points += [(f"d={d}", kronecker(f6["density_scale"], d, seed=4))
               for d in f6["densities"]]
    for pname, gg in points:
        ss = int(np.argmax(np.asarray(gg.degrees)))
        fns = {b: (lambda sp=spec_for(b, 4096): bfs(gg, ss, spec=sp).dist)
               for b in cfg["backends"]}
        polp = AT.policy_for(spec_for("auto"),
                             jax.ShapeDtypeStruct((gg.num_vertices,),
                                                  jnp.int32),
                             n=gg.src.shape[0])
        for backend, t in _measure_interleaved(fns, reps).items():
            add("fig6", backend, f"fig6/{pname}/{backend}", t,
                f"resolved={polp.backend}" if backend == "auto" else "")

    # fig7: distributed strong scaling (forced-device-count children);
    # children resolve capacity="auto" (overflow-telemetry sizing) and the
    # derived column records the C they settled on
    f7 = cfg.get("fig7")
    if f7:
        for p_, child in _fig7_json(f7):
            for name, val in child.items():
                alg, backend, cap = name.split("/")
                add("fig7", backend, f"fig7/{alg}/{backend}/P={p_}", val,
                    f"capacity={cap}")

    # serve: lane-batched QPS vs the sequential loop (GraphService)
    sv = cfg.get("serve")
    if sv:
        # wave-level trace summary per kind: one tiny UNTIMED traced
        # drain (CommitSpec(trace=True)); the timed sweeps stay
        # untraced so their jaxprs are the shipped clean ones
        from repro.graphs.generators import random_weights

        def _probe(kind):
            gp = {"hot": kronecker(sv["scale"], 8, seed=1),
                  "t0": kronecker(max(sv["scale"] - 1, 2), 8, seed=2)}
            if kind == "sssp":
                gp = {k: random_weights(g, seed=3) for k, g in gp.items()}
            p = serve_qps._trace_probe(kind, gp, None, True, 0)
            return {"trace_rounds": p["rounds"],
                    "trace_mean_density": p["mean_density"],
                    "trace_ladder_moves": p["ladder_moves"]}

        probes = {k: _probe(k)
                  for k in dict.fromkeys(sv["kinds"] + sv["gkinds"])
                  if k in serve_qps.LANE_KINDS}
        stats = serve_qps.sweep(sv["kinds"], sv["lanes"], scale=sv["scale"],
                                queries=sv["queries"],
                                repeats=sv.get("repeats", 5))
        for st in stats:
            add("serve", "auto", f"serve/{st['kind']}/L={st['lanes']}",
                st["us_per_query"] / 1e6,
                f"qps={st['qps']:.0f} p50={st['p50_ms']:.1f}ms "
                f"p99={st['p99_ms']:.1f}ms "
                f"speedup_vs_seq={st['speedup_vs_seq']:.2f} "
                f"correct={st['correct']}",
                **probes.get(st["kind"], {}))
        serve_summary = {}
        for kind in sv["kinds"]:
            ks = [s for s in stats if s["kind"] == kind]
            top = max(ks, key=lambda s: s["lanes"])
            serve_summary[kind] = {
                "lanes": top["lanes"],
                "qps_vs_seq": round(top["speedup_vs_seq"], 3),
                "lane_batched_wins": bool(top["speedup_vs_seq"] > 1.0),
                "correct": all(s["correct"] for s in ks)}
        # the graph batch axis: same query kind over G tenant graphs
        # (interleaved with its G=1 sequential baseline inside
        # sweep_graphs, per the bench-host-noise rule)
        gstats = serve_qps.sweep_graphs(
            sv["gkinds"], sv["gcounts"], scale=sv["gscale"],
            repeats=sv.get("repeats", 5))
        for st in gstats:
            add("serve", "auto", f"serve/{st['kind']}/G={st['graphs']}",
                st["us_per_query"] / 1e6,
                f"qps={st['qps']:.0f} p50={st['p50_ms']:.1f}ms "
                f"p99={st['p99_ms']:.1f}ms "
                f"speedup_vs_seq={st['speedup_vs_seq']:.2f} "
                f"correct={st['correct']}",
                **probes.get(st["kind"], {}))
        for kind in sv["gkinds"]:
            ks = [s for s in gstats if s["kind"] == kind]
            top = max(ks, key=lambda s: s["graphs"])
            serve_summary[f"{kind}@graphs"] = {
                "graphs": top["graphs"],
                "qps_vs_seq": round(top["speedup_vs_seq"], 3),
                "graph_batched_wins": bool(top["speedup_vs_seq"] > 1.0),
                "correct": all(s["correct"] for s in ks)}
    else:
        serve_summary = None

    summary = _summarize(rows)
    if serve_summary is not None:
        summary["serve"] = serve_summary
    return {"schema": SCHEMA, "sizes": sizes,
            "platform": jax.default_backend(),
            "rows": rows, "summary": summary}


_F7_CHILD = """
import json, time, numpy as np, jax
from repro.launch.mesh import make_host_mesh
from repro.graphs.generators import kronecker
from repro.core.commit import CommitSpec
from repro.graphs.algorithms.bfs import distributed_bfs
from repro.graphs.algorithms.pagerank import distributed_pagerank
P = {P}
mesh = make_host_mesh(P, 1)
g = kronecker({scale}, 8, seed=5)
src = int(np.argmax(np.asarray(g.degrees)))
out = {{}}
for backend in {backends}:
    spec = CommitSpec(backend=backend, stats=False)
    # settle capacity="auto" first (growth recompiles), then time at the
    # resolved static C
    cap = None
    for _ in range(4):
        *_, r = distributed_bfs(mesh, g, src, spec=spec, capacity="auto",
                                telemetry=True)
        if cap == int(r.capacity):
            break
        cap = int(r.capacity)
    runs = {{
        "bfs": lambda: distributed_bfs(mesh, g, src, spec=spec,
                                       capacity=cap)[0].block_until_ready(),
        "pagerank": lambda: distributed_pagerank(
            mesh, g, iters=5, spec=spec, capacity=cap).block_until_ready(),
    }}
    for name, fn in runs.items():
        fn()
        ts = []
        for _ in range({reps}):
            t0 = time.perf_counter()
            fn()
            ts.append(time.perf_counter() - t0)
        out[name + "/" + backend + "/" + str(cap)] = min(ts)
print("RESULT", json.dumps(out))
"""


def _fig7_json(f7: dict):
    """Yield (P, {alg/backend/capacity: seconds}) per forced-device child."""
    import os
    import subprocess
    import textwrap
    from pathlib import Path
    env_base = dict(os.environ)
    env_base["PYTHONPATH"] = str(Path(__file__).resolve().parent.parent /
                                 "src")
    for p_ in f7["ps"]:
        env = dict(env_base)
        # forced host devices: the child stays off any accelerator the
        # parent may hold
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={p_}"
        env["JAX_PLATFORMS"] = "cpu"
        r = subprocess.run(
            [sys.executable, "-c", textwrap.dedent(_F7_CHILD.format(
                P=p_, scale=f7["scale"], reps=f7["reps"],
                backends=tuple(f7["backends"])))],
            capture_output=True, text=True, env=env, timeout=1200)
        if r.returncode != 0:
            raise RuntimeError(f"fig7 P={p_} child failed: "
                               f"{r.stderr[-400:]}")
        line = [l for l in r.stdout.splitlines()
                if l.startswith("RESULT ")][-1]
        yield p_, json.loads(line[len("RESULT "):])


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", "--suite", dest="only", default=None,
                    help="comma-separated subset of " + ",".join(SUITES))
    ap.add_argument("--backend", default=None,
                    choices=BACKENDS + ("auto",),
                    help="commit backend for the backend-aware suites")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write the schema-stable bench matrix to PATH "
                         "and exit (skips the CSV suites)")
    ap.add_argument("--sizes", default="tiny", choices=tuple(JSON_SIZES),
                    help="problem sizes for --json")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.json:
        doc = bench_json(args.sizes)
        diff = _diff_vs_previous(doc, args.json)
        if diff is not None:
            doc["diff"] = diff
        with open(args.json, "w") as f:
            json.dump(doc, f, indent=1)
            f.write("\n")
        print(f"wrote {args.json}: {len(doc['rows'])} rows, "
              f"summary={doc['summary']}", file=sys.stderr)
        if diff is not None:
            print(f"diff vs {diff['baseline']} "
                  f"({diff['rows_compared']} rows): "
                  + " ".join(f"{s}={d['median_ratio']}"
                             for s, d in diff["suites"].items()),
                  file=sys.stderr)
        return
    names = args.only.split(",") if args.only else list(SUITES)
    print("name,us_per_call,derived")
    failures = 0
    for n in names:
        t0 = time.time()
        try:
            if args.backend and n in BACKEND_AWARE:
                SUITES[n](**BACKEND_AWARE[n](args.backend))
            else:
                if args.backend and n not in BACKEND_AWARE:
                    print(f"{n}: --backend not applicable, ignored",
                          file=sys.stderr)
                SUITES[n]()
        except Exception:
            failures += 1
            traceback.print_exc()
            print(f"{n}/SUITE_ERROR,0,")
        print(f"{n}/total_wall,{(time.time() - t0) * 1e6:.0f},",
              file=sys.stderr)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
