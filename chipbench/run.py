#!/usr/bin/env python3
"""Chip benchmark of the AAM graph engine: one cell of ``BENCHMARK.json``.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A run makes its data from ``--seed``, sets up and warms every program the
window uses, then drives the cell's entry point from one caller, one
unit of work after another, with the mix's ``ahead`` units dispatched
beyond the one it waits for (``block_until_ready``).  Once ``--seconds``
have passed it starts no more units and waits for those it sent.  Rates
and times cover all of those units over the time from the window's start
to the last unit's end.  After the window every unit's output is compared with the plain
reference.  The last line of stdout is one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and, with
``--trace 1``, ``breakdown``) and, last, ``checks``: each number compared
beside its limit.  The same numbers end standard error.

Everything is found by name: the cell in ``BENCHMARK.json`` names its
configuration (``chipbench/configs/<config>.json``) and traffic mix
(``chipbench/traffic/<traffic>.json``), the mix names its driver
(``chipbench/drivers/<driver>.py``), and each per-layer metric is read by
``chipbench/metrics/<metric>.py``.

Without a TPU, or with fewer chips than the cell asks for, the run exits
nonzero and prints no result.  ``--rehearse`` runs on whatever JAX finds
(Pallas in interpret mode off the TPU) at ``--scale``, prints what it
measured to standard error and never prints the result line.
``--list`` prints the cells, configurations, mixes and metrics the
harness finds.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import harness  # noqa: E402


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None,
                    help="window length (default: run_seconds)")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="run on any backend at --scale; no result line")
    ap.add_argument("--scale", type=int, default=None,
                    help="Kronecker scale of a rehearsal")
    ap.add_argument("--list", action="store_true",
                    help="print what the harness finds and exit")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(sys.argv[1:] if argv is None else argv)
    bench = harness.Bench.load(ROOT)
    if args.list:
        print(json.dumps(bench.listing(), indent=1))
        return 0
    if not args.workload:
        print("chipbench: --workload is required", file=sys.stderr)
        return 2
    if args.scale is not None and not args.rehearse:
        print("chipbench: --scale is for rehearsals only", file=sys.stderr)
        return 2
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chipbench: the program under test (src/repro) is not in "
              f"{ROOT}", file=sys.stderr)
        return 2
    harness.clean_environment()
    cell = bench.cell(args.workload)
    seconds = bench.run_seconds if args.seconds is None else args.seconds
    try:
        result = harness.run(cell, seed=args.seed, seconds=seconds,
                             trace=bool(args.trace), rehearse=args.rehearse,
                             scale=args.scale, t_start=T_START)
    except harness.NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 1
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    if args.rehearse:
        print("rehearsal, not a device measurement: "
              + json.dumps(result), file=sys.stderr)
        return 0
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
