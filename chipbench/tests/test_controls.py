"""The comparison that decides ``correct`` fails the controls and every
fault a cell can have (on the CPU, at small sizes)."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(HERE))
from unlisted import bench_with_x4  # noqa: E402

CELLS = {w["name"]: w for w in bench_with_x4().doc["workloads"]}
ENV = dict(os.environ, JAX_PLATFORMS="cpu",
           XLA_FLAGS="--xla_force_host_platform_device_count=4")
SCALE = 9


def _last_json(out):
    assert out.returncode == 0, out.stderr[-3000:]
    return [json.loads(line) for line in out.stdout.splitlines()
            if line.startswith("{")]


@pytest.mark.parametrize("cell", sorted(
    w["name"] for w in json.loads(
        (ROOT / "BENCHMARK.json").read_text())["workloads"]))
def test_controls_come_out_not_correct(cell):
    out = subprocess.run(
        [sys.executable, str(ROOT / "chipbench" / "controls.py"),
         "--workload", cell, "--seeds", "3", "4", "--seconds", "0.2",
         "--rehearse", "--scale", str(SCALE)],
        env=ENV, capture_output=True, text=True, timeout=600)
    rows = _last_json(out)
    assert len(rows) == 2
    assert not any(r["correct"] for r in rows)
    for r in rows:
        assert any(c["value"] > c["limit"] for c in r["checks"].values())


FAULTS = ["unchanged", "half", "altered"]
CASES = [(c, f) for c in sorted(CELLS) for f in FAULTS] + [
    (c, "exchange") for c in sorted(CELLS) if CELLS[c]["chips"] > 1]


@pytest.mark.parametrize("cell,fault", CASES)
def test_each_fault_comes_out_not_correct(cell, fault):
    out = subprocess.run(
        [sys.executable, str(HERE / "faults.py"), cell, fault, str(SCALE)],
        env=ENV, capture_output=True, text=True, timeout=600)
    r = _last_json(out)[-1]
    assert r["correct"] is False
    assert r["failed"] > 0


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_the_sound_program_comes_out_correct(cell):
    out = subprocess.run(
        [sys.executable, str(HERE / "faults.py"), cell, "none", str(SCALE)],
        env=ENV, capture_output=True, text=True, timeout=600)
    r = _last_json(out)[-1]
    assert r["correct"] is True and r["failed"] == 0


@pytest.mark.parametrize("cell", sorted(
    w["name"] for w in json.loads(
        (ROOT / "BENCHMARK.json").read_text())["workloads"]))
def test_a_rehearsal_prints_checks_and_no_result(cell):
    out = subprocess.run(
        [sys.executable, str(ROOT / "chipbench" / "run.py"), "--workload",
         cell, "--seed", "5", "--seconds", "0.2", "--trace", "0",
         "--rehearse", "--scale", str(SCALE)],
        env=ENV, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert out.stdout.strip() == ""          # a rehearsal prints no result
    assert "check " in out.stderr
    assert '"correct": true' in out.stderr.splitlines()[-1]


def test_the_unlisted_four_chip_cell_reads_its_layers():
    out = subprocess.run(
        [sys.executable, str(HERE / "faults.py"), "g500-s21-x4.bfs", "none",
         str(SCALE), "--trace"],
        env=ENV, capture_output=True, text=True, timeout=600)
    r = _last_json(out)[-1]
    assert r["correct"] is True
    assert set(r["metrics"]) == {"idle_share.x4", "collective_ms.x4",
                                 "subrounds_per_round.x4"}
    assert r["metrics"]["subrounds_per_round.x4"]["value"] >= 1
