"""aamlint static passes: registry checks, key-space bounds, race
detection, and the CLI smoke test (tier-1 gate of ISSUE 8).

The CLI must exit 0 on the shipped algorithms x axis kinds and nonzero
on each seeded violation fixture — that is, the analyzer demonstrably
catches the bug classes it exists for.
"""
import dataclasses

import numpy as np
import pytest

import jax.numpy as jnp

from repro.analysis import algebra, keyspace, lint, waverace
from repro.core.coalescing import (MAX_FLAT_KEYS, GraphBatch, ProductAxis,
                                   QueryLanes, require_key_space)


# -- satellite 1: int32 flat-key overflow guard -----------------------------

def test_key_space_boundary():
    """Exactly MAX_FLAT_KEYS is legal; one more raises with a clear
    message (regression for the fuse_keys/flatten3 silent wrap)."""
    assert require_key_space(MAX_FLAT_KEYS, where="x") == MAX_FLAT_KEYS
    with pytest.raises(OverflowError, match="int32 key space"):
        require_key_space(MAX_FLAT_KEYS + 1, where="x")


def test_axis_constructors_guard_key_space():
    # boundary: 2^31 - 2 cells exactly — constructs
    QueryLanes(2, (MAX_FLAT_KEYS // 2))
    with pytest.raises(OverflowError, match="QueryLanes"):
        QueryLanes(2, MAX_FLAT_KEYS // 2 + 1)
    with pytest.raises(OverflowError, match="GraphBatch"):
        GraphBatch((MAX_FLAT_KEYS, 2))
    # the L x Vtot product hazard: each factor fits easily, the product
    # does not
    with pytest.raises(OverflowError, match="L \\* Vtot"):
        ProductAxis(4096, (10 ** 6,) * 600)
    ProductAxis(4, (10 ** 6, 10 ** 6))      # same shapes, sane scale


# -- algebra registry -------------------------------------------------------

def test_algebra_registry_clean():
    assert algebra.check_algebra() == []


def test_algebra_covers_all_commit_ops():
    from repro.core.commit import OPS
    assert set(OPS) <= set(algebra.ALGEBRA)


def test_algebra_catches_bad_declaration(monkeypatch):
    """A stale declaration (add claimed idempotent) must be a finding."""
    bad = dict(algebra.ALGEBRA)
    bad["add"] = dataclasses.replace(algebra.ALGEBRA["add"],
                                     idempotent=True)
    monkeypatch.setattr(algebra, "ALGEBRA", bad)
    found = algebra.check_algebra()
    assert any("'add'" in f and "idempotent" in f for f in found)


def test_no_order_dependent_op_on_fused_waves():
    assert algebra.check_fused_order_dependence() == []


def test_replay_guards_verified():
    assert algebra.check_replay_paths() == []


def test_replay_guard_loss_is_detected(monkeypatch):
    """Rewriting a guard's witness away must produce a finding naming
    the non-idempotent ops at risk."""
    from repro.serve import durable
    broken = tuple(
        dataclasses.replace(s, witness="THIS STRING IS NOT IN THE SOURCE")
        if s.name == "wal-replay" else s
        for s in durable.REPLAY_GUARDS)
    monkeypatch.setattr(durable, "REPLAY_GUARDS", broken)
    found = algebra.check_replay_paths()
    assert len(found) == 1 and "wal-replay" in found[0] \
        and "add" in found[0]


# -- key-space pass ---------------------------------------------------------

def test_keyspace_exhaustive_disjointness():
    for ax in (QueryLanes(3, 11), GraphBatch((4, 9, 2)),
               ProductAxis(3, (4, 9, 2))):
        rep = keyspace.analyze_axis(ax)
        assert rep.ok and rep.disjoint is True


def test_keyspace_flags_colliding_axis():
    """A broken flatten (stride too small) collides cells — the
    exhaustive pass must prove NON-disjointness."""
    @dataclasses.dataclass(frozen=True)
    class Broken:
        lanes: int
        num_vertices: int

        def flatten(self, major, minor):
            # stride V-1 instead of V: lane k overlaps lane k+1
            return jnp.asarray(major) * (self.num_vertices - 1) \
                + jnp.asarray(minor)

    rep = keyspace.analyze_axis(Broken(4, 10))
    assert not rep.ok and any("NOT disjoint" in f for f in rep.findings)


def test_keyspace_flags_overflow_without_evaluating_int32():
    @dataclasses.dataclass(frozen=True)
    class Unchecked:
        lanes: int
        sizes: tuple

    rep = keyspace.analyze_axis(Unchecked(4096, (10 ** 6,) * 600))
    assert not rep.ok and "int32" in rep.findings[0]
    assert rep.flat_size == 4096 * 600 * 10 ** 6     # python ints, no wrap


# -- race pass (unit level; the full catalog runs via the CLI below) --------

def test_race_detector_fires_on_raw_scatter():
    def racy(state):
        d = state["dist"]
        return {"dist": d.at[jnp.arange(8) % 4].min(d[jnp.arange(8)] + 1)}

    rep = waverace.check_traceable("racy", racy,
                                   {"dist": jnp.zeros((8,), jnp.int32)})
    assert not rep.ok and rep.findings[0].primitive == "scatter-min"


def test_race_detector_accepts_commit_route():
    from repro.core.commit import CommitSpec, commit
    from repro.core.messages import make_messages

    def clean(state):
        d = state["dist"]
        res = commit(d, make_messages(jnp.arange(8) % 4,
                                      d[jnp.arange(8)] + 1), "min",
                     CommitSpec(backend="atomic", stats=False))
        return {"dist": res.state}

    rep = waverace.check_traceable("clean", clean,
                                   {"dist": jnp.zeros((8,), jnp.int32)})
    assert rep.ok and rep.commits == 1


def test_race_detector_sees_through_while_loop():
    """Raw writes hidden inside lax.while_loop bodies (where every
    production round loop lives) must still be found."""
    import jax

    def racy_loop(state):
        def body(c):
            d, it = c
            d2 = d.at[jnp.arange(8) % 4].add(d[jnp.arange(8)])
            return d2, it + 1

        d, _ = jax.lax.while_loop(lambda c: c[1] < 3, body,
                                  (state["x"], jnp.zeros((), jnp.int32)))
        return {"x": d}

    rep = waverace.check_traceable("racy-loop", racy_loop,
                                   {"x": jnp.zeros((8,), jnp.int32)})
    assert not rep.ok


def test_race_scope_is_a_whole_path_component():
    """A raw write under ``aam_commit/aam_commit_stats`` is still inside
    the commit; a scope whose name only starts with ``aam_commit`` is
    not.  The linter reads the scope name from the program's constant."""
    import jax
    from repro.core.commit import COMMIT_SCOPE, STATS_SCOPE

    def scatter(d):
        return d.at[jnp.arange(8) % 4].min(d[jnp.arange(8)] + 1)

    def nested(state):
        with jax.named_scope(COMMIT_SCOPE), jax.named_scope(STATS_SCOPE):
            return {"dist": scatter(state["dist"])}

    def prefix_only(state):
        with jax.named_scope(STATS_SCOPE):
            return {"dist": scatter(state["dist"])}

    st = {"dist": jnp.zeros((8,), jnp.int32)}
    assert waverace._SCOPE == COMMIT_SCOPE
    rep = waverace.check_traceable("nested", nested, st)
    assert rep.ok and rep.commits == 1
    rep = waverace.check_traceable("prefix", prefix_only, st)
    assert not rep.ok and rep.findings[0].primitive == "scatter-min"
    assert waverace.scope_components("vmap(aam_commit)/aam_commit_stats") \
        == ["vmap", "aam_commit", "aam_commit_stats"]


# -- CLI smoke (the tier-1 acceptance gate) ---------------------------------

@pytest.fixture(scope="module")
def _autotune_off():
    import os
    old = os.environ.get("REPRO_AUTOTUNE")
    os.environ["REPRO_AUTOTUNE"] = "off"
    yield
    if old is None:
        os.environ.pop("REPRO_AUTOTUNE", None)
    else:
        os.environ["REPRO_AUTOTUNE"] = old


def test_cli_clean_on_shipped_code(_autotune_off):
    """python -m repro.analysis.lint exits 0 over six algorithms x
    {QueryLanes, GraphBatch, ProductAxis} + ProductWave chunks."""
    assert lint.main([]) == 0


def test_cli_bench_schema(_autotune_off):
    assert lint.main(["--skip-waverace", "--bench-schema"]) == 0


def test_cli_catches_planted_overflow(_autotune_off):
    assert lint.main(["--skip-waverace",
                      "--module", "tests.fixtures.planted_overflow"]) == 1


def test_cli_catches_planted_race(_autotune_off):
    assert lint.main(["--skip-waverace",
                      "--module", "tests.fixtures.planted_race"]) == 1
