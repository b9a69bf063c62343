"""The harness finds cells, mixes and metrics by name, refuses to run
without a TPU, and prints the contract's line."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import harness  # noqa: E402

ENV = dict(os.environ, JAX_PLATFORMS="cpu")


def _copy_bench(dst: Path, with_program: bool) -> None:
    shutil.copy(ROOT / "BENCHMARK.json", dst / "BENCHMARK.json")
    shutil.copytree(ROOT / "chipbench", dst / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    if with_program:
        os.symlink(ROOT / "src", dst / "src")


def _run(cwd: Path, *args, env=ENV):
    return subprocess.run([sys.executable, "chipbench/run.py", *args],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=600)


def test_a_cell_and_a_metric_added_as_new_files_are_listed(tmp_path):
    _copy_bench(tmp_path, with_program=False)
    doc = json.loads((tmp_path / "BENCHMARK.json").read_text())
    base = tmp_path / "chipbench"
    conf = json.loads((base / "configs" / "graph500-s19.json").read_text())
    (base / "configs" / "dummy-s12.json").write_text(
        json.dumps(dict(conf, name="dummy-s12", scale=12)))
    (base / "traffic" / "dummy.json").write_text(
        json.dumps({"driver": "bfs", "keys": 4}))
    (base / "metrics" / "dummy_ms.dummy.py").write_text(
        "def read(ctx):\n    return 1.0\n")
    doc["configs"].append({"name": "dummy-s12", "source": "test",
                           "file": "chipbench/configs/dummy-s12.json",
                           "reduced": ["scale"], "why": "test"})
    doc["workloads"].append({"name": "dummy.bfs", "config": "dummy-s12",
                             "traffic": "dummy", "chips": 1, "why": "test"})
    e2e = [m for m in doc["end_to_end"] if m["name"] == "teps"][0]
    e2e["workloads"].append("dummy.bfs")
    doc["per_layer"].append({"name": "dummy_ms.dummy", "unit": "ms",
                             "better": "lower", "source": "device_trace",
                             "layer": "device", "moves": "teps",
                             "workloads": ["dummy.bfs"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))
    out = _run(tmp_path, "--list")
    assert out.returncode == 0, out.stderr
    listing = json.loads(out.stdout)
    cell = listing["cells"]["dummy.bfs"]
    assert cell["per_layer"] == ["dummy_ms.dummy"]
    assert cell["end_to_end"] == ["teps", "setup_s"]
    assert "dummy" in listing["traffic"]
    assert "dummy_ms.dummy" in listing["metrics"]
    # the cells already there are unchanged
    first = doc["workloads"][0]["name"]
    assert listing["cells"][first]["driver"] in ("bfs", "pagerank")


def test_without_a_tpu_the_run_fails_and_prints_no_result():
    cell = json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"][0]
    out = _run(ROOT, "--workload", cell["name"], "--seed", "3",
               "--seconds", "1", "--trace", "0")
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr


def test_without_the_program_the_run_fails(tmp_path):
    _copy_bench(tmp_path, with_program=False)
    cell = json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"][0]
    out = _run(tmp_path, "--workload", cell["name"], "--seed", "3",
               "--seconds", "1", "--trace", "0")
    assert out.returncode != 0
    assert out.stdout.strip() == ""


@pytest.mark.parametrize("name", [
    w["name"] for w in
    json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]])
def test_every_cell_resolves_to_files_of_its_own(name):
    cell = harness.Bench.load(ROOT).cell(name)
    assert cell.driver_path.is_file()
    assert all(p.is_file() for _, p in cell.per_layer)
    assert "limits" in cell.config
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}


def test_a_rehearsal_returns_the_contract_line():
    bench = harness.Bench.load(ROOT)
    name = next(w["name"] for w in bench.doc["workloads"]
                if w["chips"] == 1)
    r = harness.run(bench.cell(name), seed=2 ** 33 + 5, seconds=0.5,
                    trace=False, rehearse=True, scale=8)
    assert list(r)[:5] == ["correct", "attempted", "failed", "metrics",
                           "device"]
    assert list(r)[-1] == "checks"
    assert r["correct"] is True and r["failed"] == 0
    assert set(r["metrics"]) == {m["name"] for m in
                                 bench.cell(name).end_to_end}
    assert r["device"]["platform"] == "cpu"


def test_key_order_needs_no_reference_work_in_setup(monkeypatch):
    """Outside a rehearsal the keys' depths are the configuration's, so
    set-up (timed as ``setup_s``) runs no reference BFS."""
    from chipbench.drivers import bfs as bfs_driver
    from chipbench.lib import graph500, reference
    conf = {"structure_seed": 3, "scale": 10, "edge_factor": 16,
            "A": 0.57, "B": 0.19, "C": 0.19}
    base = bfs_driver.Driver(conf, {"keys": 64}, 3, None, scale=10)
    indptr, adj = base.data.csr()
    conf["key_levels"] = [
        reference.levels(reference.bfs_reference(indptr, adj, int(k)))
        for k in base.data.search_keys(64)]

    def refuse(*a, **k):
        raise AssertionError("reference work in set-up")

    monkeypatch.setattr(reference, "bfs_reference", refuse)
    monkeypatch.setattr(graph500, "csr", refuse)
    run = bfs_driver.Driver(conf, {"keys": 64}, 2 ** 31 + 9, None)
    keys = run._keys()
    assert sorted(keys) == sorted(int(k) for k in run.data.search_keys(64))
    assert [run.levels[k] for k in keys] == [
        conf["key_levels"][i]
        for i in graph500.depth_stratified(conf["key_levels"], 2 ** 31 + 9)]


@pytest.mark.parametrize("entry,op", [("bfs", "min"), ("pagerank", "add")])
def test_pretune_verdict_is_the_one_the_entry_traces(monkeypatch, entry, op):
    """Set-up resolves ``auto`` eagerly; the entry's own call at trace time
    then reuses that verdict and races nothing more."""
    import jax
    import jax.numpy as jnp
    sys.path.insert(0, str(ROOT / "src"))
    from chipbench.drivers import common
    from repro.core import autotune
    from repro.core.commit import CommitSpec
    from repro.graphs.algorithms.bfs import bfs
    from repro.graphs.algorithms.pagerank import pagerank
    from repro.graphs.generators import kronecker

    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", "off")
    tuner = autotune.AutoTuner()
    monkeypatch.setattr(autotune, "DEFAULT_TUNER", tuner)
    g = kronecker(8, 16, seed=11)
    spec = CommitSpec(backend="auto")
    dtype = jnp.int32 if op == "min" else jnp.float32
    tier = common.pretune(spec, op, g.num_vertices, dtype,
                          int(g.src.shape[0]))
    if entry == "bfs":
        jax.make_jaxpr(lambda g: bfs.__wrapped__(g, 0, spec=spec))(g)
    else:
        jax.make_jaxpr(lambda g: pagerank.__wrapped__(
            g, d=0.85, iters=2, spec=spec))(g)
    events = [e["event"] for e in tuner.audit]
    assert [e["backend"] for e in tuner.audit
            if e["event"] == "policy"] == [tier, tier]
    assert events.count("race") == 1 and events.count("calibrate") == 1


FAKE_DRIVER = '''
from chipbench.harness import Check


class Unit:
    def __init__(self, log, i):
        self.log, self.i = log, i

    def block_until_ready(self):
        self.log.append(("done", self.i))
        return self


class Driver:
    def __init__(self, config, traffic, seed, devices, scale=None):
        self.log = []

    def setup(self):
        pass

    def unit(self, i):
        self.log.append(("sent", i))
        return Unit(self.log, i)

    def report(self, records):
        return {"log": self.log}

    def check(self, records):
        return Check([True] * len(records), {}, [1] * len(records))

    def end_to_end(self, records, check, seconds):
        return {"units_s": len(records) / seconds}
'''


@pytest.mark.parametrize("ahead", [0, 1, 3])
def test_the_window_keeps_ahead_units_in_flight_and_waits_for_all(
        tmp_path, capsys, ahead):
    path = tmp_path / "fake.py"
    path.write_text(FAKE_DRIVER)
    cell = harness.Cell("fake", {"chips": 1}, {}, {"ahead": ahead}, path,
                        [{"name": "units_s", "unit": "1/s"},
                         {"name": "setup_s", "unit": "s"}], [])
    r = harness.run(cell, seed=1, seconds=0.02, trace=False, rehearse=True)
    window = next(json.loads(line) for line in
                  capsys.readouterr().err.splitlines()
                  if '"phase": "window"' in line)
    log, in_flight, most = window["log"], 0, 0
    for what, _ in log:
        in_flight += 1 if what == "sent" else -1
        most = max(most, in_flight)
    n = r["attempted"]
    assert [i for w, i in log if w == "sent"] == list(range(n))
    assert [i for w, i in log if w == "done"] == list(range(n))
    assert most == ahead + 1 and in_flight == 0
    assert window["ahead"] == ahead and r["correct"] is True
