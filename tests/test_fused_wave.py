"""The fused route+commit tier (``backend="fused"``, ISSUE 10).

One Pallas launch takes the post-exchange bucket buffers (global target
ids with -1 sentinels, optional lane ids, traced base offset) and
computes composite keys, reorders in VMEM, and applies the commit op —
replacing the jnp-side ``local_idx``/``fuse_keys``/``make_messages``
materialization plus separate ``coarse_commit_pallas`` launch.

Parity contract: bit-identical to the ``pallas`` tier launch-for-launch
(same tile semantics, including the per-transaction conflict counts) and
state-identical to ``coarse``/``atomic``; the engine fast path
(``fused_commit_site`` with base/lane/width) must match the unfused
oracle on every batch axis.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import autotune as AT
from repro.core import commit as C
from repro.core import perf_model
from repro.core.commit import BACKENDS, CommitSpec, commit
from repro.core.messages import make_messages

OPS5 = ("min", "max", "add", "or", "first")


@pytest.fixture(autouse=True)
def _no_timed_autotune(monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE", "off")


def _init(op, v, dtype=jnp.int32):
    if op == "first":
        return jnp.full((v,), -1, dtype)
    if op in ("add", "or"):
        return jnp.zeros((v,), dtype)
    big = 1 << 30 if dtype == jnp.int32 else 1e9
    return jnp.full((v,), big if op == "min" else -big, dtype)


def _batch(v, n, seed=0, dtype=np.int32):
    rng = np.random.default_rng(seed)
    tgt = rng.integers(0, v, n).astype(np.int32)
    val = rng.integers(0, 50, n).astype(dtype)
    valid = rng.random(n) < 0.8
    return (jnp.asarray(tgt), jnp.asarray(val), jnp.asarray(valid))


def _spec(backend, stats, **kw):
    kw.setdefault("tile_m", 32)
    kw.setdefault("block_v", 64)
    return CommitSpec(backend=backend, stats=stats, interpret=True, **kw)


# -- generic commit() dispatch ----------------------------------------------


def test_fused_is_registered_backend():
    assert "fused" in BACKENDS


@pytest.mark.parametrize("op", OPS5)
@pytest.mark.parametrize("stats", [False, True])
def test_commit_parity_vs_all_tiers(op, stats):
    """fused == pallas bit-for-bit (full CommitResult, multi-tile grid)
    and state-identical to coarse and atomic."""
    v, n = 96, 70
    tgt, val, valid = _batch(v, n, seed=op.__hash__() % 97)
    if op == "or":
        val = val % 2
    msgs = make_messages(tgt, val, valid)
    st0 = _init(op, v)
    rf = commit(st0, msgs, op, _spec("fused", stats))
    rp = commit(st0, msgs, op, _spec("pallas", stats))
    for field in ("state", "success", "conflicts", "applied"):
        np.testing.assert_array_equal(np.asarray(getattr(rf, field)),
                                      np.asarray(getattr(rp, field)),
                                      err_msg=f"{op}/{field}")
    for ref_backend in ("coarse", "atomic"):
        rr = commit(st0, msgs, op, CommitSpec(backend=ref_backend,
                                              stats=stats))
        np.testing.assert_array_equal(np.asarray(rf.state),
                                      np.asarray(rr.state),
                                      err_msg=f"{op} vs {ref_backend}")
        if stats:
            np.testing.assert_array_equal(np.asarray(rf.success),
                                          np.asarray(rr.success))


@pytest.mark.parametrize("stats", [False, True])
def test_commit_float_add_tolerance(stats):
    """float32 add: bit-identical to pallas (same reduction), within the
    documented reassociation tolerance of coarse."""
    from repro.analysis.sanitize import ADD_ATOL, ADD_RTOL
    v, n = 96, 70
    tgt, val, valid = _batch(v, n, seed=5)
    valf = jnp.asarray(np.asarray(val), jnp.float32) / 7.0
    msgs = make_messages(tgt, valf, valid)
    st0 = jnp.zeros((v,), jnp.float32)
    rf = commit(st0, msgs, "add", _spec("fused", stats))
    rp = commit(st0, msgs, "add", _spec("pallas", stats))
    np.testing.assert_array_equal(np.asarray(rf.state),
                                  np.asarray(rp.state))
    rc = commit(st0, msgs, "add", CommitSpec(backend="coarse",
                                             stats=stats))
    np.testing.assert_allclose(np.asarray(rf.state),
                               np.asarray(rc.state),
                               rtol=ADD_RTOL, atol=ADD_ATOL)


def test_fused_falls_back_for_unsupported_payloads():
    """The kernel envelope is scalar int32/float32 [n] payloads — a bool
    state through backend="fused" silently takes the coarse path (same
    contract as the pallas tier), and the site-support predicate rejects
    what the engine fast path must not fuse."""
    msgs = make_messages(jnp.asarray([0, 1], jnp.int32),
                         jnp.asarray([True, False]))
    res = commit(jnp.zeros((4,), bool), msgs, "or",
                 CommitSpec(backend="fused"))
    np.testing.assert_array_equal(np.asarray(res.state),
                                  [True, False, False, False])
    st = jnp.zeros((8,), jnp.int32)
    assert C.fused_site_supported(st, jnp.zeros((4,), jnp.int32))
    assert C.fused_site_supported(st, jnp.zeros((2, 3), jnp.float32))
    assert not C.fused_site_supported(st, jnp.zeros((4,), bool))
    assert not C.fused_site_supported(st, jnp.zeros((2, 2, 2), jnp.int32))
    assert not C.fused_site_supported(jnp.zeros((4, 2), jnp.int32),
                                      jnp.zeros((4,), jnp.int32))


# -- the engine fast path: fused_commit_site --------------------------------


def _site_oracle(state, tgt, val, lane, base, width, op, stats):
    """The unfused route tail the kernel replaces: jnp key computation +
    make_messages + coarse commit."""
    nrows = state.shape[0] // width
    ok = (tgt >= 0) & (tgt - base >= 0) & (tgt - base < nrows)
    key = jnp.where(ok, tgt - base, 0) * width
    if lane is not None:
        ok = ok & (lane >= 0) & (lane < width)
        key = key + jnp.where(ok, lane, 0)
    msgs = make_messages(key.astype(jnp.int32), val, ok)
    return commit(state, msgs, op, CommitSpec(backend="coarse",
                                              stats=stats))


@pytest.mark.parametrize("stats", [False, True])
@pytest.mark.parametrize("op", ["min", "add", "first"])
def test_site_parity_base_lane_width(op, stats):
    width, nrows, base, n = 3, 40, 128, 90
    rng = np.random.default_rng(11)
    st0 = _init(op, nrows * width)
    tgt = rng.integers(base - 5, base + nrows + 5, n).astype(np.int32)
    tgt[rng.random(n) < 0.15] = -1            # bucket-fill sentinels
    lane = jnp.asarray(rng.integers(0, width, n), jnp.int32)
    val = jnp.asarray(rng.integers(0, 50, n), jnp.int32)
    tgt = jnp.asarray(tgt)
    rf = C.fused_commit_site(st0, tgt, val, op, _spec("fused", stats),
                             lane=lane, base=base, width=width)
    rr = _site_oracle(st0, tgt, val, lane, base, width, op, stats)
    np.testing.assert_array_equal(np.asarray(rf.state),
                                  np.asarray(rr.state))
    if stats:
        np.testing.assert_array_equal(np.asarray(rf.success),
                                      np.asarray(rr.success))
        assert int(rf.applied) == int(rr.applied)


def test_site_base_only_width1():
    nrows, base, n = 50, 64, 70
    rng = np.random.default_rng(12)
    st0 = _init("min", nrows)
    tgt = jnp.asarray(rng.integers(base - 8, base + nrows + 8, n),
                      jnp.int32)
    val = jnp.asarray(rng.integers(0, 50, n), jnp.int32)
    rf = C.fused_commit_site(st0, tgt, val, "min", _spec("fused", False),
                             base=base, width=1)
    rr = _site_oracle(st0, tgt, val, None, base, 1, "min", False)
    np.testing.assert_array_equal(np.asarray(rf.state),
                                  np.asarray(rr.state))


def test_lane_width_contract():
    from repro.kernels.fused_wave import fused_route_commit_pallas
    st0 = jnp.zeros((8,), jnp.int32)
    tgt = jnp.zeros((4,), jnp.int32)
    with pytest.raises(ValueError, match="lane ids"):
        fused_route_commit_pallas(st0, tgt, tgt, width=2, op="add",
                                  interpret=True)
    with pytest.raises(ValueError, match="lane ids"):
        fused_route_commit_pallas(st0, tgt, tgt, lane=tgt, width=1,
                                  op="add", interpret=True)


def test_ladder_fused_site_matches_static():
    """The lax.switch ladder twin must equal the static site at every
    traced level."""
    pol = AT.TunerPolicy(backend="fused", ladder=AT.M_LADDER,
                         init_level=1, adaptive=True, sort=False,
                         stats=False, tile_m=32, block_v=64,
                         interpret=True)
    width, nrows, base, n = 2, 30, 32, 50
    rng = np.random.default_rng(13)
    st0 = _init("min", nrows * width)
    tgt = jnp.asarray(rng.integers(base, base + nrows, n), jnp.int32)
    lane = jnp.asarray(rng.integers(0, width, n), jnp.int32)
    val = jnp.asarray(rng.integers(0, 50, n), jnp.int32)
    for level in (0, len(AT.M_LADDER) - 1):
        ra = AT.ladder_fused_site(st0, tgt, val, "min", pol,
                                  jnp.asarray(level, jnp.int32),
                                  lane=lane, base=base, width=width)
        rs = C.fused_commit_site(st0, tgt, val, "min",
                                 pol.spec_at(level), lane=lane,
                                 base=base, width=width)
        np.testing.assert_array_equal(np.asarray(ra.state),
                                      np.asarray(rs.state))


# -- the three batch axes through the distributed engine --------------------


def _mesh1():
    from repro.launch.mesh import make_host_mesh
    return make_host_mesh(1, 1)


def _tree_eq(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _engine_specs():
    return (CommitSpec(backend="fused", stats=False, interpret=True),
            CommitSpec(backend="coarse", stats=False))


def test_engine_single_query_parity():
    from repro.graphs.algorithms.bfs import distributed_bfs
    from repro.graphs.generators import kronecker
    g = kronecker(6, 4, seed=2)
    sf, sc = _engine_specs()
    mesh = _mesh1()
    _tree_eq(distributed_bfs(mesh, g, 3, spec=sf, capacity=256),
             distributed_bfs(mesh, g, 3, spec=sc, capacity=256))


def test_engine_query_lanes_parity():
    from repro.graphs.algorithms.bfs import distributed_multi_source_bfs
    from repro.graphs.generators import kronecker
    g = kronecker(6, 4, seed=2)
    sf, sc = _engine_specs()
    mesh = _mesh1()
    srcs = [1, 5, 9]
    _tree_eq(
        distributed_multi_source_bfs(mesh, g, srcs, spec=sf,
                                     capacity=256),
        distributed_multi_source_bfs(mesh, g, srcs, spec=sc,
                                     capacity=256))


def test_engine_graph_batch_parity():
    from repro.graphs.algorithms.bfs import batched_over_graphs_bfs
    from repro.graphs.csr import GraphSet
    from repro.graphs.generators import kronecker
    gs = GraphSet([kronecker(5, 4, seed=3), kronecker(5, 4, seed=4)])
    sf, sc = _engine_specs()
    _tree_eq(batched_over_graphs_bfs(gs, [1, 2], spec=sf, capacity=256),
             batched_over_graphs_bfs(gs, [1, 2], spec=sc, capacity=256))


def test_engine_product_axis_parity():
    from repro.graphs.algorithms.bfs import distributed_product_bfs
    from repro.graphs.csr import GraphSet
    from repro.graphs.generators import kronecker
    gs = GraphSet([kronecker(5, 4, seed=3), kronecker(5, 4, seed=4)])
    sources = jnp.asarray([[1, 2], [3, 4]], jnp.int32)   # [L=2, G=2]
    sf, sc = _engine_specs()
    mesh = _mesh1()
    _tree_eq(
        distributed_product_bfs(mesh, gs, sources, spec=sf,
                                capacity=256),
        distributed_product_bfs(mesh, gs, sources, spec=sc,
                                capacity=256))


# -- autotuner: interpret exclusion + escape hatch --------------------------


def _small_tuner():
    return AT.AutoTuner(ns=(4, 16), v_cal=256, warmup=0, repeats=1)


def test_autotune_excludes_interp_kernel_tiers(monkeypatch):
    """On a host where the kernels would run in interpret mode, neither
    pallas nor fused may enter the candidate set — simulator timings
    would mis-seed the cost model (the autotune-on-interpret fix)."""
    monkeypatch.setenv("REPRO_AUTOTUNE", "on")
    monkeypatch.delenv(AT._ALLOW_INTERP_ENV, raising=False)
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", "off")
    tuner = _small_tuner()
    st = jnp.zeros((256,), jnp.int32)
    msgs = make_messages(jnp.zeros((16,), jnp.int32),
                         jnp.zeros((16,), jnp.int32))
    spec = CommitSpec(backend="auto", stats=False, interpret=True)
    pol = AT.policy_for(spec, st, msgs, op="min", tuner=tuner)
    assert pol.backend not in AT.KERNEL_BACKENDS
    events = [e for e in tuner.audit
              if e.get("event") == "kernel_tiers_excluded"]
    assert events and set(events[0]["backends"]) == set(AT.KERNEL_BACKENDS)
    assert events[0]["escape_hatch"] == AT._ALLOW_INTERP_ENV


def test_allow_interp_escape_hatch(monkeypatch):
    monkeypatch.delenv(AT._ALLOW_INTERP_ENV, raising=False)
    assert not AT._kernel_compiled(CommitSpec(backend="auto",
                                              interpret=True))
    monkeypatch.setenv(AT._ALLOW_INTERP_ENV, "1")
    assert AT._kernel_compiled(CommitSpec(backend="auto",
                                          interpret=True))


def test_auto_can_select_fused(monkeypatch):
    """With the escape hatch set and a calibration that ranks the fused
    tier fastest, backend="auto" resolves to fused and the resulting
    spec commits with coarse-parity."""
    monkeypatch.setenv("REPRO_AUTOTUNE", "on")
    monkeypatch.setenv(AT._ALLOW_INTERP_ENV, "1")
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", "off")
    tuner = _small_tuner()
    fit = perf_model.LinearFit
    cal = AT.Calibration(
        fine=fit(intercept=0.0, slope=1e-6, r2=1.0),
        tiers=(("atomic", fit(intercept=1e-3, slope=1e-6, r2=1.0)),
               ("coarse", fit(intercept=1e-3, slope=1e-6, r2=1.0)),
               ("pallas", fit(intercept=9e-4, slope=1e-6, r2=1.0)),
               ("fused", fit(intercept=1e-5, slope=1e-8, r2=1.0))))
    monkeypatch.setattr(AT.AutoTuner, "calibrate",
                        lambda self, **kw: cal)
    monkeypatch.setattr(AT.AutoTuner, "race",
                        lambda self, finalists, n, **kw:
                        min(finalists, key=lambda b:
                            0 if b == "fused" else 1))
    st = jnp.full((96,), 1 << 30, jnp.int32)
    tgt, val, valid = _batch(96, 40, seed=21)
    msgs = make_messages(tgt, val, valid)
    spec = CommitSpec(backend="auto", stats=False, interpret=True)
    pol = AT.policy_for(spec, st, msgs, op="min", tuner=tuner)
    assert pol.backend == "fused"
    rf = commit(st, msgs, "min", pol.spec_at(pol.init_level))
    rc = commit(st, msgs, "min", CommitSpec(backend="coarse",
                                            stats=False))
    np.testing.assert_array_equal(np.asarray(rf.state),
                                  np.asarray(rc.state))


def test_auto_keeps_kernel_tiers_off_huge_grids(monkeypatch):
    """Even when the calibration ranks fused fastest, a commit whose
    kernel grid (state blocks x message tiles) exceeds
    KERNEL_MAX_GRID_STEPS resolves to a jnp tier, and its ladder keeps
    only transaction sizes that split the batch into at most
    MAX_TRANSACTIONS transactions."""
    monkeypatch.setenv("REPRO_AUTOTUNE", "on")
    monkeypatch.setenv(AT._ALLOW_INTERP_ENV, "1")
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", "off")
    fit = perf_model.LinearFit
    cal = AT.Calibration(
        fine=fit(intercept=0.0, slope=1e-6, r2=1.0),
        tiers=(("atomic", fit(intercept=1e-3, slope=1e-6, r2=1.0)),
               ("coarse", fit(intercept=1e-3, slope=1e-6, r2=1.0)),
               ("pallas", fit(intercept=9e-4, slope=1e-6, r2=1.0)),
               ("fused", fit(intercept=1e-5, slope=1e-8, r2=1.0))))
    jnp_only = AT.Calibration(fine=cal.fine, tiers=tuple(
        (b, f) for b, f in cal.tiers if b not in AT.KERNEL_BACKENDS))
    monkeypatch.setattr(AT.AutoTuner, "calibrate",
                        lambda self, with_pallas, **kw:
                        cal if with_pallas else jnp_only)
    rank = ("fused", "coarse", "pallas", "atomic")
    monkeypatch.setattr(AT.AutoTuner, "race",
                        lambda self, finalists, n, **kw:
                        min(finalists, key=rank.index))
    spec = CommitSpec(backend="auto", stats=False, interpret=True)
    state = jax.ShapeDtypeStruct((1 << 22,), jnp.int32)    # 8192 blocks
    tuner = _small_tuner()
    n = 1 << 25
    pol = AT.policy_for(spec, state, n=n, op="min", tuner=tuner)
    assert pol.backend == "coarse"
    assert pol.ladder == AT.feasible_ladder(n) == (None,)
    assert any(e.get("event") == "kernel_tiers_excluded"
               and "grid" in e["reason"] for e in tuner.audit)
    small = AT.policy_for(spec, jnp.zeros((96,), jnp.int32), n=96,
                          op="min", tuner=_small_tuner())
    assert small.backend == "fused" and small.ladder == AT.M_LADDER


# -- satellite: the pallas bucket-count path --------------------------------


def test_bucket_count_backends_agree():
    from repro.core.coalescing import plan_buckets_sorted
    rng = np.random.default_rng(31)
    owner = jnp.asarray(rng.integers(0, 40, 257), jnp.int32)
    valid = jnp.asarray(rng.random(257) < 0.8)
    pj, oj = plan_buckets_sorted(owner, valid, 40, 8)
    pp, op_ = plan_buckets_sorted(owner, valid, 40, 8,
                                  count_backend="pallas")
    for f in ("owner", "position", "counts", "kept", "dropped"):
        np.testing.assert_array_equal(np.asarray(getattr(pj, f)),
                                      np.asarray(getattr(pp, f)))
    np.testing.assert_array_equal(np.asarray(oj), np.asarray(op_))


def test_bucket_count_env_and_validation(monkeypatch):
    from repro.core import coalescing as CO
    rng = np.random.default_rng(32)
    owner = jnp.asarray(rng.integers(0, 10, 64), jnp.int32)
    valid = jnp.ones((64,), bool)
    monkeypatch.setenv(CO.BUCKET_COUNT_ENV, "pallas")
    pe, _ = CO.plan_buckets_sorted(owner, valid, 10, 8)
    monkeypatch.delenv(CO.BUCKET_COUNT_ENV)
    pj, _ = CO.plan_buckets_sorted(owner, valid, 10, 8)
    np.testing.assert_array_equal(np.asarray(pe.counts),
                                  np.asarray(pj.counts))
    with pytest.raises(ValueError, match="count_backend"):
        CO.plan_buckets_sorted(owner, valid, 10, 8, count_backend="nope")


# -- satellite: waverace knows the fused commit site ------------------------


def test_waverace_scoped_fused_commit_is_commit():
    from repro.analysis import waverace

    def scoped(state):
        msgs = make_messages(jnp.asarray([1, 2, 2], jnp.int32),
                             state[:3] + 1)
        return commit(state, msgs, "min",
                      _spec("fused", False, tile_m=4, block_v=8)).state

    rep = waverace.check_traceable("scoped fused", scoped,
                                   jnp.full((8,), 9, jnp.int32))
    assert rep.commits >= 1 and not rep.findings


def test_waverace_flags_unscoped_kernel_launch():
    from repro.analysis import waverace
    from tests.fixtures.planted_race import LINT_TRACEABLES
    name, fn, state = LINT_TRACEABLES[1]
    rep = waverace.check_traceable(name, fn, state)
    assert rep.findings
    assert any("pallas_call" in f.detail for f in rep.findings)
