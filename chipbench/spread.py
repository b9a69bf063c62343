#!/usr/bin/env python3
"""Spread of a cell's runs, as the bounds are set from it.

    python3 chipbench/spread.py results.jsonl [more.jsonl ...]

Each input line is one run's result line (JSON), optionally wrapped as
``{"set": <name>, "cell": <cell>, "seed": <n>, "result": {...}}``.  For every set and
metric it prints the median and the spread: the distance between the
first and third quartiles of ``statistics.quantiles(values, n=4)``, as a
share of the median.
"""
from __future__ import annotations

import json
import statistics
import sys
from collections import defaultdict


def spread(values) -> float:
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main(paths) -> int:
    sets = defaultdict(lambda: defaultdict(list))
    for path in paths:
        with open(path) as f:
            for line in f:
                if not line.startswith("{"):
                    continue
                row = json.loads(line)
                name = f'{row.get("cell", "")} {row.get("set", path)}'.strip()
                result = row.get("result", row)
                if not result:
                    continue
                for metric, m in result["metrics"].items():
                    sets[name][metric].append(m["value"])
    for name, metrics in sets.items():
        for metric, values in metrics.items():
            line = {"set": name, "metric": metric, "runs": len(values),
                    "median": statistics.median(values)}
            if len(values) >= 2:
                line["spread"] = spread(values)
            print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
