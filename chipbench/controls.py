#!/usr/bin/env python3
"""Readings of the controls: a cell run with the plain algorithm one
guarantee weaker (``chipbench.lib.controls``) in the program's place.

    python3 chipbench/controls.py --workload <cell> --seeds 1 2 3 [--seconds 10]

Each seed is one run of the cell at its own size through the harness,
with the program's entry point replaced by the control; the numbers the
comparison computes are printed one JSON line per seed, and a sound limit
has to fail each.  The benchmark's own runs never run this.  A cell on a
mesh is read on one chip: the control is the plain single-chip
algorithm, which needs no mesh, in place of the distributed search.
``--rehearse --scale N`` runs on the CPU at a small size.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import harness  # noqa: E402

ENTRIES = {"bfs": ("repro.graphs.algorithms.bfs", "bfs", "bfs_bounded"),
           "pagerank": ("repro.graphs.algorithms.pagerank", "pagerank",
                        "pagerank_bf16")}


def install(driver: str) -> None:
    """Replace the program's entry of ``driver`` by its control."""
    import importlib
    from chipbench.lib import controls
    module, name, control = ENTRIES[driver]
    setattr(importlib.import_module(module), name, getattr(controls, control))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--scale", type=int, default=None)
    args = ap.parse_args(argv)
    harness.clean_environment()
    sys.path.insert(0, str(ROOT / "src"))
    cell = harness.Bench.load(ROOT).cell(args.workload)
    if cell.config.get("mesh"):
        cell.config = {k: v for k, v in cell.config.items() if k != "mesh"}
        cell.workload = dict(cell.workload, chips=1)
    install(cell.traffic["driver"])
    for seed in args.seeds:
        r = harness.run(cell, seed=seed, seconds=args.seconds, trace=False,
                        rehearse=args.rehearse, scale=args.scale)
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "correct": r["correct"], "attempted": r["attempted"],
                          "failed": r["failed"], "checks": r["checks"],
                          "device": r["device"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
