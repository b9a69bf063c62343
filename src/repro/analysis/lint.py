"""aamlint CLI — ``python -m repro.analysis.lint``.

Runs every static pass over the shipped pipeline and exits nonzero on
findings:

* **algebra** — commit-op declarations verified exhaustively; no
  order-dependent op on a distributed/fused wave; every at-least-once
  replay site still carries its idempotence guard
  (:mod:`repro.analysis.algebra`);
* **keyspace** — composite-key disjointness + int32 bound for
  representative ``QueryLanes``/``GraphBatch``/``ProductAxis`` shapes
  (:mod:`repro.analysis.keyspace`);
* **waverace** — jaxpr race detection over all six algorithms on each
  axis kind plus the ``ProductWave`` chunk bodies
  (:mod:`repro.analysis.waverace`).

``--module pkg.mod`` additionally lints a module's declared surfaces —
``LINT_AXES`` (axis objects or ``(name, axis)`` pairs for the keyspace
pass), ``LINT_TRACEABLES`` (``(name, fn_of_state, example_state)`` for
the race pass), ``LINT_ALGORITHMS`` (``(name, AlgorithmSpec, graph)``
or ``AlgorithmSpec`` traced on a default tiny graph).  The seeded
violation fixtures under ``tests/fixtures/`` use exactly this hook.

``--bench-schema`` also validates the committed ``BENCH_*.json``
trajectory files (the ``make lint`` target runs both).
"""
from __future__ import annotations

import argparse
import dataclasses
import importlib
import json
import os
import pathlib
import sys
from types import SimpleNamespace


def _print(msg: str) -> None:
    print(msg, flush=True)


def run_algebra() -> list[str]:
    from repro.analysis import algebra
    return (algebra.check_algebra()
            + algebra.check_fused_order_dependence()
            + algebra.check_replay_paths())


# representative shapes: the tiny trio gets the exhaustive bijection
# proof, the serving-scale trio exercises the stride probe + int32
# headroom arithmetic near real deployments (L lanes x multi-M unions)
def _default_axes():
    from repro.core.coalescing import GraphBatch, ProductAxis, QueryLanes
    return [
        ("QueryLanes(8, 97)", QueryLanes(8, 97)),
        ("GraphBatch(7, 13, 29)", GraphBatch((7, 13, 29))),
        ("ProductAxis(4, (7, 13, 29))", ProductAxis(4, (7, 13, 29))),
        ("QueryLanes(64, 2^20)", QueryLanes(64, 1 << 20)),
        ("GraphBatch(3 x ~2^20)",
         GraphBatch((1 << 18, 1 << 19, 1 << 20))),
        ("ProductAxis(8, 3 x ~2^20)",
         ProductAxis(8, (1 << 18, 1 << 19, 1 << 20))),
    ]


def run_keyspace(axes=None) -> list[str]:
    from repro.analysis import keyspace
    findings = []
    for rep in keyspace.analyze_axes(axes if axes is not None
                                     else _default_axes()):
        proof = {True: "disjoint (exhaustive)", False: "NOT disjoint",
                 None: "bound-checked"}[rep.disjoint]
        _print(f"  keyspace {rep.name}: {rep.flat_size} keys, "
               f"headroom {rep.headroom}, {proof}")
        findings.extend(rep.findings)
    return findings


def run_waverace(extra_traceables=()) -> list[str]:
    from repro.analysis import waverace
    findings = []
    for rep in waverace.check_all(extra_traceables=extra_traceables):
        status = "ok" if rep.ok else "RACE"
        _print(f"  waverace {rep.name}: {status} "
               f"(commits={rep.commits}, state reads={rep.reads})")
        findings.extend(f"{f.where}: {f.detail}" for f in rep.findings)
    return findings


def run_module(modname: str) -> list[str]:
    """Lint one module's declared LINT_* surfaces."""
    from repro.analysis import keyspace, waverace
    mod = importlib.import_module(modname)
    findings = []
    for rep in keyspace.analyze_axes(getattr(mod, "LINT_AXES", ())):
        findings.extend(rep.findings)
    for name, fn, example in getattr(mod, "LINT_TRACEABLES", ()):
        rep = waverace.check_traceable(name, fn, example)
        findings.extend(f"{f.where}: {f.detail}" for f in rep.findings)
    algos = getattr(mod, "LINT_ALGORITHMS", ())
    if algos:
        g, _ = waverace._tiny_graphs()
        for item in algos:
            if isinstance(item, tuple):
                name, alg, graph = item
            else:
                name, alg, graph = item.name, item, g
            cap = SimpleNamespace(alg=alg, g=graph, batch=None)
            rep = waverace.check_algorithm(name, cap)
            findings.extend(f"{f.where}: {f.detail}"
                            for f in rep.findings)
    if not (hasattr(mod, "LINT_AXES") or hasattr(mod, "LINT_TRACEABLES")
            or hasattr(mod, "LINT_ALGORITHMS")):
        findings.append(
            f"module {modname} declares no LINT_AXES / LINT_TRACEABLES "
            f"/ LINT_ALGORITHMS — nothing to lint")
    return findings


def run_trace_off_clean() -> list[str]:
    """Prove the wavescope zero-impact-when-off guarantee: with tracing
    off (no ``REPRO_TRACE``, ``CommitSpec(trace=False)``) the jaxpr of
    every engine round loop and every ProductWave chunk body contains NO
    host-callback primitive; one positive control
    (``CommitSpec(trace=True)``) must show the callback, so the scan is
    never vacuous.  Also schema-smokes the trace and metrics JSON
    validators over freshly built documents."""
    import jax
    import jax.numpy as jnp
    from repro.analysis import waverace
    from repro.core import commit as Cm
    from repro.core import engine as E
    findings: list[str] = []
    saved = os.environ.pop("REPRO_TRACE", None)
    try:
        with waverace._no_env_sanitize():
            spec = Cm.CommitSpec()          # coarse: no calibration
            mesh = waverace._one_device_mesh()

            def runner_jaxpr(cap, sp):
                r = E._Runner(cap.alg, mesh, cap.g, axis="data",
                              capacity=64, m=8, spec=sp, batch=cap.batch,
                              max_subrounds=8)
                return str(jax.make_jaxpr(r._jfn)(
                    *cap.alg.init(cap.g, r.layout), r.zero_carry(),
                    jnp.asarray(1, jnp.int32), *r.arrays))

            # one runner per algorithm — the tap placement is per-engine,
            # not per-wrapper, so the distributed/lanes/graphs variants of
            # one algorithm share a round loop
            seen: dict[str, tuple] = {}
            for label, cap in waverace.capture_algorithms():
                seen.setdefault(cap.alg.name, (label, cap))
            for name, (label, cap) in sorted(seen.items()):
                dirty = "callback" in runner_jaxpr(cap, spec)
                _print(f"  trace-off engine {label}: "
                       f"{'CALLBACK IN JAXPR' if dirty else 'clean'}")
                if dirty:
                    findings.append(
                        f"trace-off: {label} round loop contains a host "
                        f"callback with tracing OFF")
            from repro.serve.product_wave import lint_traceables
            for name, fn, example in lint_traceables():
                dirty = "callback" in str(jax.make_jaxpr(fn)(example))
                _print(f"  trace-off product {name}: "
                       f"{'CALLBACK IN JAXPR' if dirty else 'clean'}")
                if dirty:
                    findings.append(
                        f"trace-off: product chunk {name} contains a "
                        f"host callback with tracing OFF")
            # positive control: trace=True MUST plant the tap, or the
            # "clean" verdicts above prove nothing
            label0, cap0 = sorted(seen.items())[0][1]
            on = dataclasses.replace(spec, trace=True)
            if "callback" not in runner_jaxpr(cap0, on):
                findings.append(
                    "trace-off: positive control failed — "
                    "CommitSpec(trace=True) planted no callback; the "
                    "jaxpr scan is vacuous")
            else:
                _print(f"  trace-off control {label0}: tap detected with "
                       f"trace=True")
    finally:
        if saved is not None:
            os.environ["REPRO_TRACE"] = saved
    # schema smoke: the validators must accept what we actually emit
    from repro.obs import metrics as OM
    from repro.obs import trace as OT
    tr = OT.Tracer(enabled=True)
    with tr.span("smoke", args={"k": 1}):
        tr.instant("mark")
    findings += [f"trace-off: {f}"
                 for f in OT.validate_trace(tr.to_chrome())]
    reg = OM.Registry()
    reg.counter("aam_smoke").inc(3)
    reg.gauge("aam_g").set(0.5)
    reg.histogram("aam_h").observe(0.01)
    findings += [f"trace-off: {f}"
                 for f in OM.validate_metrics_json(reg.snapshot())]
    assert reg.prometheus_text().endswith("\n")
    _print("  trace-off schemas: trace + metrics validators clean")
    return findings


BENCH_TOP_KEYS = {"schema", "sizes", "platform", "rows", "summary"}
BENCH_ROW_KEYS = {"suite", "backend", "name", "us_per_call", "derived"}
BENCH_SCHEMA = "aam-bench/v1"


def run_bench_schema(root: str = ".") -> list[str]:
    findings = []
    paths = sorted(pathlib.Path(root).glob("BENCH_*.json"))
    if not paths:
        _print("  bench-schema: no BENCH_*.json files found")
    for p in paths:
        try:
            d = json.loads(p.read_text())
        except (OSError, json.JSONDecodeError) as e:
            findings.append(f"bench: {p.name} unreadable: {e}")
            continue
        missing = BENCH_TOP_KEYS - set(d)
        if missing:
            findings.append(
                f"bench: {p.name} missing top-level keys {sorted(missing)}")
        if d.get("schema") != BENCH_SCHEMA:
            findings.append(
                f"bench: {p.name} schema {d.get('schema')!r} != "
                f"{BENCH_SCHEMA!r}")
        rows = d.get("rows", [])
        if not isinstance(rows, list) or not rows:
            findings.append(f"bench: {p.name} has no rows")
            continue
        for i, row in enumerate(rows):
            rmissing = BENCH_ROW_KEYS - set(row)
            if rmissing:
                findings.append(
                    f"bench: {p.name} row {i} missing {sorted(rmissing)}")
                break
            if not isinstance(row["us_per_call"], (int, float)):
                findings.append(
                    f"bench: {p.name} row {i} us_per_call not numeric")
                break
        _print(f"  bench-schema {p.name}: {len(rows)} rows")
    return findings


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis.lint",
        description="wave-safety static analysis for the AAM pipeline")
    ap.add_argument("--module", action="append", default=[],
                    help="additionally lint a module's LINT_* surfaces "
                         "(repeatable)")
    ap.add_argument("--bench-schema", action="store_true",
                    help="also validate BENCH_*.json trajectory files")
    ap.add_argument("--skip-waverace", action="store_true",
                    help="skip the (slow) jaxpr race pass — for quick "
                         "keyspace/algebra iterations")
    ap.add_argument("--trace-off-clean", action="store_true",
                    help="prove tracing-off jaxprs contain no host "
                         "callbacks + schema-smoke trace/metrics JSON")
    args = ap.parse_args(argv)

    findings: list[str] = []
    _print("aamlint: algebra")
    findings += run_algebra()
    _print("aamlint: keyspace")
    findings += run_keyspace()
    if not args.skip_waverace:
        _print("aamlint: waverace")
        findings += run_waverace()
    for modname in args.module:
        _print(f"aamlint: module {modname}")
        findings += run_module(modname)
    if args.bench_schema:
        _print("aamlint: bench-schema")
        findings += run_bench_schema()
    if args.trace_off_clean:
        _print("aamlint: trace-off-clean")
        findings += run_trace_off_clean()

    if findings:
        _print(f"\naamlint: {len(findings)} finding(s)")
        for f in findings:
            _print(f"  FINDING: {f}")
        return 1
    _print("\naamlint: clean")
    return 0


if __name__ == "__main__":
    sys.exit(main())
