"""The four-chip cell that is prepared but not yet in ``BENCHMARK.json``
(it has not been measured on a four-chip host): its files are tested
here, as the entry a later change adds would name them."""
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

X4_WORKLOAD = {"name": "g500-s21-x4.bfs", "config": "graph500-s21-x4",
               "traffic": "bfs", "chips": 4, "why": "exchange"}
X4_CONFIG = {"name": "graph500-s21-x4", "source": "graph500.org",
             "file": "chipbench/configs/graph500-s21-x4.json",
             "reduced": ["scale"], "why": "four chips"}
X4_METRICS = [
    {"name": n, "unit": u, "better": "lower", "source": s, "layer": lay,
     "moves": "teps", "workloads": ["g500-s21-x4.bfs"]}
    for n, u, s, lay in (
        ("idle_share.x4", "%", "device_trace", "device"),
        ("collective_ms.x4", "ms/round", "device_trace", "exchange"),
        ("subrounds_per_round.x4", "count", "program_counter", "exchange"))]


def bench_with_x4():
    """``BENCHMARK.json`` with the four-chip cell's entries added."""
    import json
    from chipbench import harness
    doc = json.loads((ROOT / "BENCHMARK.json").read_text())
    doc["configs"].append(X4_CONFIG)
    doc["workloads"].append(X4_WORKLOAD)
    doc["per_layer"].extend(X4_METRICS)
    for m in doc["end_to_end"]:
        if m["name"] == "teps":
            m["workloads"].append(X4_WORKLOAD["name"])
    return harness.Bench(ROOT, doc)
