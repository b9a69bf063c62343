"""Auto-tuner edge-case matrix + commit dispatch (ISSUE 3).

Covers: empty batch, all-invalid messages, single-vertex state, and
``backend="auto"`` parity against every concrete backend across all five
ops — plus the conflict-feedback ladder mechanics and the bench-JSON
schema smoke (``benchmarks.run --json``).

Calibration is timed micro-benchmarking; ``REPRO_AUTOTUNE=off`` pins the
deterministic heuristic policy for the tests that must not depend on
wall-clock noise.  Either way the FINAL STATE is backend-independent, so
every parity assertion below holds for any calibration outcome.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import autotune as AT
from repro.core.commit import BACKENDS, OPS, CommitSpec, commit
from repro.core.messages import make_messages

REPO = Path(__file__).resolve().parent.parent

AUTO_SPEC = CommitSpec(backend="auto")


def _init_state(op, v, rng):
    if op == "min":
        return np.full(v, 1000, np.int32)
    if op == "max":
        return np.full(v, -1000, np.int32)
    if op == "first":
        return np.where(rng.random(v) < 0.5, -1, 777).astype(np.int32)
    return np.zeros(v, np.int32)    # add / or


def _batch(op, v, n, rng, valid=None):
    lo = 0 if op == "first" else (0 if op == "or" else -50)
    hi = 2 if op == "or" else 50
    tgt = rng.integers(0, v, n).astype(np.int32)
    val = rng.integers(lo, hi, n).astype(np.int32)
    if valid is None:
        valid = rng.random(n) < 0.8
    return make_messages(jnp.asarray(tgt), jnp.asarray(val),
                         jnp.asarray(valid))


# ---------------------------------------------------------------------------
# edge-case matrix: auto == every concrete backend, including the corners
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("op", OPS)
@pytest.mark.parametrize("case", ["random", "all_invalid", "single_vertex",
                                  "empty_batch"])
def test_auto_parity_matrix(op, case):
    rng = np.random.default_rng(sum(map(ord, op + case)))
    if case == "empty_batch":
        v, n = 16, 0
        msgs = make_messages(jnp.zeros((0,), jnp.int32),
                             jnp.zeros((0,), jnp.int32),
                             jnp.zeros((0,), bool))
    elif case == "single_vertex":
        v, n = 1, 40
        msgs = _batch(op, v, n, rng)
    elif case == "all_invalid":
        v, n = 61, 120
        msgs = _batch(op, v, n, rng, valid=np.zeros(120, bool))
    else:
        v, n = 61, 120
        msgs = _batch(op, v, n, rng)
    state = _init_state(op, v, rng)
    res_auto = commit(jnp.asarray(state), msgs, op, AUTO_SPEC)
    for backend in BACKENDS:
        res = commit(jnp.asarray(state), msgs, op,
                     CommitSpec(backend=backend))
        np.testing.assert_array_equal(
            np.asarray(res_auto.state), np.asarray(res.state),
            err_msg=f"auto vs {backend} on {op}/{case}")


def test_auto_honors_pinned_m():
    """A user-pinned transaction size survives auto resolution on EVERY
    entry point: resolve_spec, the policy ladder (engine + algorithm
    steppers run spec_at over the ladder), and the stepper itself."""
    state = jnp.full((8,), 1000, jnp.int32)
    msgs = make_messages(jnp.asarray([1, 1, 2], jnp.int32),
                         jnp.asarray([5, 3, 9], jnp.int32))
    pinned = CommitSpec(backend="auto", m=2)
    spec = AT.resolve_spec(pinned, state, msgs, "min")
    assert spec.backend in BACKENDS
    assert spec.m == 2
    pol = AT.policy_for(pinned, state, msgs, op="min")
    assert pol.ladder == (2,) and not pol.adaptive
    assert pol.spec_at(pol.init_level).m == 2
    step, lvl0 = AT.make_commit_step(pinned, "min", state, msgs_like=msgs)
    res, lvl1 = step(state, msgs, lvl0)
    assert int(lvl1) == int(lvl0)        # no ladder movement when pinned
    ref = commit(state, msgs, "min", CommitSpec(m=2))
    np.testing.assert_array_equal(np.asarray(res.state),
                                  np.asarray(ref.state))


def test_auto_without_telemetry_degrades_to_static_m():
    """coarse with sort=False + stats=False has no conflict signal
    (scatter path reports 0): the policy must not pretend to adapt."""
    pol = AT.DEFAULT_TUNER.policy(
        CommitSpec(backend="auto", sort=False, stats=False), n=4096,
        pallas_ok=False)
    if pol.backend == "coarse":
        assert not pol.adaptive
    # with the cheap sorted counters or full stats, coarse stays adaptive
    pol2 = AT.DEFAULT_TUNER.policy(
        CommitSpec(backend="auto", sort=True, stats=True), n=4096,
        pallas_ok=False)
    if pol2.backend == "coarse":
        assert pol2.adaptive


def test_auto_rejects_nothing_new():
    """'auto' is a valid CommitSpec backend; junk still raises."""
    state = jnp.zeros((4,), jnp.int32)
    msgs = make_messages(jnp.asarray([0], jnp.int32),
                         jnp.asarray([1], jnp.int32))
    commit(state, msgs, "min", CommitSpec(backend="auto"))
    with pytest.raises(ValueError):
        commit(state, msgs, "min", CommitSpec(backend="autotune"))


# ---------------------------------------------------------------------------
# the conflict-feedback ladder
# ---------------------------------------------------------------------------


def _policy(**kw):
    kw.setdefault("backend", "coarse")
    return AT.TunerPolicy(**kw)


def test_next_level_shrinks_under_abort_storm_and_regrows():
    pol = _policy(init_level=3)
    lvl = jnp.asarray(3, jnp.int32)
    # abort storm: conflict density 0.9 -> shrink M
    down = AT.next_level(pol, lvl, jnp.asarray(90), jnp.asarray(100))
    assert int(down) == 2
    # quiet round: density 0.0 -> grow M
    up = AT.next_level(pol, lvl, jnp.asarray(0), jnp.asarray(100))
    assert int(up) == 4
    # hysteresis band: hold
    hold = AT.next_level(pol, lvl, jnp.asarray(15), jnp.asarray(100))
    assert int(hold) == 3
    # clamped at both ends
    assert int(AT.next_level(pol, jnp.asarray(0, jnp.int32),
                             jnp.asarray(99), jnp.asarray(100))) == 0
    top = len(pol.ladder) - 1
    assert int(AT.next_level(pol, jnp.asarray(top, jnp.int32),
                             jnp.asarray(0), jnp.asarray(100))) == top
    # zero messages must not divide by zero
    assert int(AT.next_level(pol, lvl, jnp.asarray(0),
                             jnp.asarray(0))) == 4


def test_next_level_static_policy_is_identity():
    pol = _policy(backend="atomic", adaptive=False)
    lvl = jnp.asarray(2, jnp.int32)
    assert int(AT.next_level(pol, lvl, jnp.asarray(99),
                             jnp.asarray(100))) == 2


@pytest.mark.parametrize("op", OPS)
def test_ladder_commit_matches_oracle_at_every_level(op):
    """Final state is M-independent: any traced level produces the same
    state as the whole-batch reference."""
    rng = np.random.default_rng(11)
    v, n = 61, 120
    state = _init_state(op, v, rng)
    msgs = _batch(op, v, n, rng)
    ref = commit(jnp.asarray(state), msgs, op, CommitSpec())
    pol = _policy()
    for level in range(len(pol.ladder)):
        res = AT.ladder_commit(jnp.asarray(state), msgs, op, pol,
                               jnp.asarray(level, jnp.int32))
        np.testing.assert_array_equal(
            np.asarray(res.state), np.asarray(ref.state),
            err_msg=f"{op} ladder level {level}")


def test_make_commit_step_adapts_and_matches_static():
    """The single-shard stepper: commits match the static path and the
    carried level actually moves under conflict pressure."""
    rng = np.random.default_rng(5)
    v, n = 32, 256
    state = jnp.full((v,), 1000, jnp.int32)
    # all messages hammer 2 vertices: guaranteed abort storm
    msgs = make_messages(jnp.asarray(rng.integers(0, 2, n), jnp.int32),
                         jnp.asarray(rng.integers(0, 100, n), jnp.int32))
    step, lvl0 = AT.make_commit_step(CommitSpec(backend="auto"), "min",
                                     state, msgs_like=msgs)
    res, lvl1 = step(state, msgs, lvl0)
    ref = commit(state, msgs, "min", CommitSpec())
    np.testing.assert_array_equal(np.asarray(res.state),
                                  np.asarray(ref.state))
    # under a >99% conflict density the level may only move DOWN
    assert int(lvl1) <= int(lvl0)
    # static spec: level is a passthrough dummy
    step_s, lvl_s = AT.make_commit_step(CommitSpec(backend="coarse"),
                                        "min", state, msgs_like=msgs)
    res_s, lvl_s2 = step_s(state, msgs, lvl_s)
    assert int(lvl_s2) == int(lvl_s)
    np.testing.assert_array_equal(np.asarray(res_s.state),
                                  np.asarray(ref.state))


def test_policy_deterministic_with_autotune_off(monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE", "off")
    tuner = AT.AutoTuner()
    spec = CommitSpec(backend="auto")
    p1 = tuner.policy(spec, n=5000, pallas_ok=True)
    p2 = tuner.policy(spec, n=5000, pallas_ok=True)
    assert p1 == p2
    assert p1.backend == "coarse" and p1.adaptive
    assert p1.ladder[p1.init_level] in p1.ladder


def test_calibration_is_cached():
    tuner = AT.AutoTuner(ns=(4, 16), v_cal=256, repeats=1, warmup=0)
    c1 = tuner.calibrate(sort=True, stats=False, tile_m=64, block_v=128,
                         interpret=None, with_pallas=False)
    c2 = tuner.calibrate(sort=True, stats=False, tile_m=64, block_v=128,
                         interpret=None, with_pallas=False)
    assert c1 is c2
    assert {b for b, _ in c1.tiers} == {"atomic", "coarse"}
    assert c1.fine.slope > 0


# ---------------------------------------------------------------------------
# all six single-shard algorithms: auto == their default static spec
# ---------------------------------------------------------------------------


def test_auto_matches_static_on_all_six_algorithms():
    from repro.graphs.generators import (erdos_renyi, kronecker,
                                         random_weights)
    from repro.graphs.algorithms import bfs as B, boruvka as BO, \
        coloring as CO, pagerank as PR, sssp as S, stconn as ST

    g = kronecker(7, 8, seed=3)
    gw = random_weights(g, seed=4)
    src = int(np.argmax(np.asarray(g.degrees)))
    t = int(np.argmin(np.asarray(g.degrees)))
    auto = CommitSpec(backend="auto", stats=False)

    r1 = B.bfs(g, src)
    r2 = B.bfs(g, src, spec=auto)
    np.testing.assert_array_equal(np.asarray(r1.dist), np.asarray(r2.dist))

    d1, _ = S.sssp(gw, src)
    d2, _ = S.sssp(gw, src, spec=auto)
    np.testing.assert_array_equal(np.asarray(d1), np.asarray(d2))

    p1, _ = PR.pagerank(g, iters=5)
    p2, _ = PR.pagerank(g, iters=5, spec=auto)
    # float add: tiled transactions reorder the accumulate (exactly like
    # any static m change) -> rounding-level tolerance
    np.testing.assert_allclose(np.asarray(p1), np.asarray(p2), atol=1e-6)

    c1, ro1, _ = CO.coloring(g, seed=0)
    c2, ro2, _ = CO.coloring(g, seed=0, spec=auto)
    np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))
    assert int(ro1) == int(ro2)

    b1 = BO.boruvka(gw)
    b2 = BO.boruvka(gw, spec=auto)
    np.testing.assert_array_equal(np.asarray(b1[0]), np.asarray(b2[0]))
    assert abs(float(b1[1]) - float(b2[1])) < 1e-5
    assert int(b1[2]) == int(b2[2])

    f1, _ = ST.st_connectivity(g, src, t)
    f2, _ = ST.st_connectivity(g, src, t, spec=auto)
    assert bool(f1) == bool(f2)

    gu = erdos_renyi(150, 5.0, seed=9)
    ru1 = B.bfs(gu, 0)
    ru2 = B.bfs(gu, 0, spec=auto)
    np.testing.assert_array_equal(np.asarray(ru1.dist),
                                  np.asarray(ru2.dist))


# ---------------------------------------------------------------------------
# pallas no-stats path (satellite): cheap path drops the conflict output
# ---------------------------------------------------------------------------


def test_pallas_commit_nostats_skips_conflict_reduction():
    from repro.kernels.coarse_commit import coarse_commit_pallas
    state = jnp.zeros((16,), jnp.int32)
    idx = jnp.asarray([1, 1, 2, 3, 3, 3, -1, -1], jnp.int32)
    val = jnp.ones((8,), jnp.int32)
    out = coarse_commit_pallas(state, idx, val, op="add", tile_m=8,
                               block_v=16, stats=False, interpret=True)
    assert isinstance(out, jnp.ndarray)          # single output, no tuple
    ref, conf = coarse_commit_pallas(state, idx, val, op="add", tile_m=8,
                                     block_v=16, stats=True, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    assert int(conf) == 5
    # the commit() wrapper: stats=False reports zero conflicts (cheap path)
    msgs = make_messages(idx, val, idx >= 0)
    res = commit(state, msgs, "add",
                 CommitSpec(backend="pallas", stats=False))
    np.testing.assert_array_equal(np.asarray(res.state), np.asarray(ref))
    assert int(res.conflicts) == 0


# ---------------------------------------------------------------------------
# bench JSON schema smoke (satellite: make bench-json / --json)
# ---------------------------------------------------------------------------


def test_bench_json_schema_smoke(tmp_path):
    """`benchmarks.run --json` emits a parseable, schema-stable document
    with the keys every future PR's trajectory comparison relies on."""
    out = tmp_path / "bench.json"
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"))
    p = subprocess.run(
        [sys.executable, "-m", "benchmarks.run", "--json", str(out),
         "--sizes", "smoke"],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    doc = json.loads(out.read_text())
    assert doc["schema"] == "aam-bench/v1"
    assert doc["sizes"] == "smoke"
    assert isinstance(doc["rows"], list) and doc["rows"]
    for row in doc["rows"]:
        # required keys are pinned; serve rows may additionally carry
        # wavescope trace_* telemetry fields (lint --bench-schema
        # enforces the same required-subset contract)
        assert {"suite", "backend", "name", "us_per_call",
                "derived"} <= set(row)
        extras = set(row) - {"suite", "backend", "name", "us_per_call",
                             "derived"}
        assert extras <= {"trace_rounds", "trace_mean_density",
                          "trace_ladder_moves"}, extras
        assert row["us_per_call"] >= 0
    backends = {r["backend"] for r in doc["rows"]}
    assert "auto" in backends and "coarse" in backends
    assert "fig4" in doc["summary"] and "fig6" in doc["summary"]
    for suite in ("fig4", "fig6"):
        assert {"auto_over_best_static", "within_10pct",
                "points"} <= set(doc["summary"][suite])
