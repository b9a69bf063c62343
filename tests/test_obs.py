"""Wavescope (observability PR): the telemetry-return convention is
pinned across every distributed entry point, the span tracer and the
metrics registry behave and export valid schemas, the io_callback wave
tap fires when tracing is on and provably vanishes from the jaxpr when
off, a crash -> restore -> re-drain run yields ONE well-formed trace
(no orphan spans, replay instants, exactly-once tickets), the latency
histogram agrees with the bench percentile within one bucket, and the
bench rows carry the trace-summary schema."""
import dataclasses
import json
import math
import os
import re
import shutil
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import autotune as AT
from repro.core.commit import CommitSpec
from repro.graphs.generators import erdos_renyi, kronecker, random_weights
from repro.obs import metrics as OM
from repro.obs import trace as OT
from repro.obs import wavetap as OW
from repro.serve.graph_service import GraphService, ServiceStats
from repro.serve.queries import BfsQuery, SsspQuery


class FakeClock:
    def __init__(self, now=100.0):
        self.now = now

    def __call__(self) -> float:
        return self.now

    def tick(self, dt: float) -> None:
        self.now += dt


class CountingClock(FakeClock):
    """Counts reads — span-accounting tests pin the exact number."""

    def __init__(self):
        super().__init__()
        self.reads = 0

    def __call__(self) -> float:
        self.reads += 1
        return self.now


def _mesh1():
    return jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ("data",))


# -- the telemetry= return-shape convention ---------------------------------


def test_telemetry_return_helper_semantics():
    from repro.core.engine import telemetry_return
    res = object()
    assert telemetry_return((1, 2), res, False) == (1, 2)
    assert telemetry_return((1, 2), res, True) == (1, 2, res)
    assert telemetry_return("x", res, False) == "x"
    assert telemetry_return("x", res, True) == ("x", res)


def test_telemetry_return_shapes():
    """Every distributed entry point: telemetry=True appends EXACTLY one
    trailing DistributedResult; the plain positions never shift."""
    from repro.core.engine import DistributedResult
    from repro.graphs.algorithms import (bfs, boruvka, coloring, pagerank,
                                         sssp, stconn)
    from repro.graphs.csr import GraphSet

    mesh = _mesh1()
    g = random_weights(erdos_renyi(16, 3.0, seed=0), seed=1)
    gs = GraphSet([erdos_renyi(7, 3.0, seed=1), erdos_renyi(9, 3.0,
                                                            seed=2)])
    srcL = jnp.zeros((2,), jnp.int32)
    srcG = jnp.zeros((2,), jnp.int32)
    srcLG = jnp.zeros((2, 2), jnp.int32)
    spec = CommitSpec()
    kw = dict(spec=spec, capacity=64)
    # entry -> plain arity (None = non-tuple plain return)
    cases = [
        (lambda t: bfs.distributed_bfs(mesh, g, 0, telemetry=t, **kw), 2),
        (lambda t: bfs.distributed_multi_source_bfs(
            mesh, g, srcL, telemetry=t, **kw), 2),
        (lambda t: bfs.distributed_product_bfs(
            mesh, gs, srcLG, telemetry=t, **kw), 2),
        (lambda t: sssp.distributed_sssp(mesh, g, 0, telemetry=t, **kw),
         2),
        (lambda t: sssp.distributed_multi_source_sssp(
            mesh, g, srcL, telemetry=t, **kw), 2),
        (lambda t: pagerank.distributed_pagerank(
            mesh, g, iters=2, telemetry=t, **kw), None),
        (lambda t: pagerank.distributed_multi_source_pagerank(
            mesh, g, srcL, iters=2, telemetry=t, **kw), None),
        (lambda t: coloring.distributed_coloring(
            mesh, g, telemetry=t, **kw), 3),
        (lambda t: stconn.distributed_stconn(
            mesh, g, 0, 1, telemetry=t, **kw), 2),
        (lambda t: stconn.distributed_multi_source_stconn(
            mesh, g, srcG, jnp.ones((2,), jnp.int32), telemetry=t, **kw),
         2),
        (lambda t: boruvka.distributed_boruvka(
            mesh, g, telemetry=t, **kw), 4),
    ]
    for entry, arity in cases:
        plain, full = entry(False), entry(True)
        if arity is None:
            assert not isinstance(plain, tuple)
            assert isinstance(full, tuple) and len(full) == 2
            assert isinstance(full[1], DistributedResult)
            np.testing.assert_array_equal(np.asarray(plain),
                                          np.asarray(full[0]))
        else:
            assert isinstance(plain, tuple) and len(plain) == arity
            assert len(full) == arity + 1
            assert isinstance(full[-1], DistributedResult)
            np.testing.assert_array_equal(np.asarray(plain[0]),
                                          np.asarray(full[0]))


# -- tracer -----------------------------------------------------------------


def test_tracer_span_nesting_and_export():
    clk = FakeClock(0.0)
    tr = OT.Tracer(clock=clk, enabled=True)
    with tr.span("outer", args={"a": 1}):
        clk.tick(1.0)
        with tr.span("inner"):
            clk.tick(0.5)
        clk.tick(0.25)
    tr.instant("mark")
    assert tr.open_spans() == []
    doc = tr.to_chrome()
    assert OT.validate_trace(doc) == []
    by_name = {e["name"]: e for e in doc["traceEvents"]}
    assert by_name["inner"]["dur"] == pytest.approx(0.5e6)
    assert by_name["outer"]["dur"] == pytest.approx(1.75e6)
    assert by_name["mark"]["ph"] == "i"
    assert doc["otherData"]["schema"] == OT.TRACE_SCHEMA


def test_tracer_span_closes_on_exception():
    tr = OT.Tracer(clock=FakeClock(), enabled=True)
    with pytest.raises(RuntimeError):
        with tr.span("faulty"):
            raise RuntimeError("boom")
    assert tr.open_spans() == []
    assert [e["name"] for e in tr.events] == ["faulty"]


def test_tracer_inactive_reads_no_clock_and_records_nothing():
    clk = CountingClock()
    tr = OT.Tracer(clock=clk, enabled=False)
    with tr.span("s"):
        pass
    tr.instant("i")
    tr.complete("c", 0.0, 1.0)
    assert clk.reads == 0 and tr.events == []


def test_tracer_enabled_none_follows_env(monkeypatch):
    tr = OT.Tracer()
    monkeypatch.delenv("REPRO_TRACE", raising=False)
    assert not tr.active
    monkeypatch.setenv("REPRO_TRACE", "1")
    assert tr.active
    monkeypatch.setenv("REPRO_TRACE", "0")
    assert not tr.active


def _profile(tmp_path, body):
    """Run ``body`` under a recorded CPU ``jax.profiler`` trace; return
    its ``/host:CPU`` plane as ``{line name: [(name, start, end, stats)]}``
    (one clock, nanoseconds)."""
    import glob
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    plane, = [p for p in pd.planes if p.name == "/host:CPU"]
    return {ln.name: [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                       dict(e.stats)) for e in ln.events]
            for ln in plane.lines}


def test_spans_land_on_the_profiler_host_line_around_their_ops(tmp_path):
    """Every span is also the profiler annotation ``aam.<name>``, active
    tracer or not: it sits on the host line of the thread that
    dispatched the jitted call, on the clock of the call's ops, and
    encloses them; the inactive tracer reads none of its clock."""
    @jax.jit
    def f(x):
        return jnp.sort(x * 3)

    x = jnp.arange(1 << 14, dtype=jnp.int32)[::-1]
    f(x).block_until_ready()
    clk = CountingClock()
    tr = OT.Tracer(clock=clk, enabled=False)

    def body():
        with OT.span("outer"):
            with tr.span("probe"):
                for _ in range(3):
                    f(x).block_until_ready()
            tr.begin("paired")
            f(x).block_until_ready()
            tr.end()

    lines = _profile(tmp_path, body)
    assert clk.reads == 0 and tr.events == []
    where = {ev[0]: ln for ln, evs in lines.items() for ev in evs
             if ev[0].startswith(OT.ANNOTATION_PREFIX)}
    assert set(where) == {"aam.outer", "aam.probe", "aam.paired"}
    host = lines[where["aam.probe"]]
    assert len(set(where.values())) == 1
    assert any(ev[0] == "PjitFunction(f)" for ev in host)
    span = {ev[0]: ev for ev in host}
    ops = sorted(ev for evs in lines.values() for ev in evs
                 if "hlo_op" in ev[3])
    assert len(ops) >= 4
    _, s0, e0, _ = span["aam.outer"]
    _, s1, e1, _ = span["aam.probe"]
    assert s0 <= s1 < e1 <= e0
    assert all(s0 <= s and e <= e0 for _, s, e, _ in ops)
    assert sum(s1 <= s and e <= e1 for _, s, e, _ in ops) >= 3


def _root_op_names(hlo_text: str, fused: bool = False) -> list:
    """``(opcode, op_name, shape)`` of each instruction of the entry and
    loop computations (fused computations' insides left out: a fusion
    runs as one device op, named by its root).  ``fused=True`` keeps
    the instructions inside fusions too."""
    import re
    out, inside = [], False
    for line in hlo_text.splitlines():
        if line and not line.startswith(" "):
            inside = line.startswith(("%fused", "fused"))
            continue
        m = re.match(
            r"\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(\S+)\s+([\w\-]+)\(", line)
        if not m or (inside and not fused):
            continue
        op = re.search(r'op_name="([^"]*)"', line)
        out.append((m.group(2), op.group(1) if op else "", m.group(1)))
    return out


PHASE_SCOPES = ("aam_messages", "aam_commit", "aam_commit_stats")


def _phase(op_name: str) -> str:
    import re
    parts = [c for c in re.split(r"[/()]", op_name) if c]
    for scope in ("aam_commit_stats", "aam_commit", "aam_messages"):
        if scope in parts:
            return scope
    return "other"


@pytest.mark.parametrize("alg", ["bfs", "pagerank"])
def test_round_body_ops_are_named_by_phase(alg):
    """The compiled round loop names its phases: message building under
    ``aam_messages``, the commit under ``aam_commit``, its bookkeeping
    under ``aam_commit_stats`` nested in it; every other op is
    ``other``.  The names live in the module constants."""
    from repro.core import commit as C
    from repro.graphs.algorithms.bfs import bfs
    from repro.graphs.algorithms.pagerank import pagerank
    assert (C.MESSAGES_SCOPE, C.COMMIT_SCOPE, C.STATS_SCOPE) == (
        "aam_messages", "aam_commit", "aam_commit_stats")
    g = kronecker(8, 8, seed=1)
    spec = CommitSpec(backend="atomic")           # stats on, as `auto`
    if alg == "bfs":
        text = bfs.lower(g, jnp.int32(0), spec=spec).compile().as_text()
    else:
        text = pagerank.lower(g, iters=3, spec=spec).compile().as_text()
    body = [(op, n) for op, n, _ in _root_op_names(text) if "/body/" in n]
    phases = {}
    for op, name in body:
        phases.setdefault(_phase(name), []).append(op)
        if "aam_commit_stats" in name:        # nested, never alone
            parts = name.split("/")
            assert parts.index("aam_commit") < \
                parts.index("aam_commit_stats"), name
    assert set(phases) <= {"aam_messages", "aam_commit",
                           "aam_commit_stats", "other"}
    assert {"aam_messages", "aam_commit", "aam_commit_stats"} <= \
        set(phases), phases
    # the min commit's scatter is the commit; its success bookkeeping
    # (a segment-min over the messages) is the nested stats scope
    if alg == "bfs":
        assert "scatter" in phases["aam_commit"]
        assert "scatter" in phases["aam_commit_stats"]


@pytest.mark.parametrize("alg", ["bfs", "pagerank"])
def test_round_messages_take_one_edge_sized_gather(alg):
    """A round builds its messages from ONE gather over the edge list:
    the frontier (BFS) or the degree (PageRank) is folded into a
    vertex-sized vector first.  Gathers are counted inside fusions too,
    where XLA may hide a second one in the first one's consumer; the
    commit's own bookkeeping gather is not a message gather."""
    from repro.graphs.algorithms.bfs import bfs
    from repro.graphs.algorithms.pagerank import pagerank
    g = kronecker(8, 8, seed=1)
    e = g.src.shape[0]
    spec = CommitSpec(backend="atomic")
    if alg == "bfs":
        text = bfs.lower(g, jnp.int32(0), spec=spec).compile().as_text()
    else:
        text = pagerank.lower(g, iters=3, spec=spec).compile().as_text()

    def elements(shape):
        dims = re.search(r"\[([\d,]*)\]", shape).group(1)
        return math.prod(int(x) for x in dims.split(",") if x)

    gathers = [n for op, n, shape in _root_op_names(text, fused=True)
               if op == "gather" and "/body/" in n
               and _phase(n) == "aam_messages" and elements(shape) == e]
    assert len(gathers) == 1, gathers


def test_compile_cache_keeps_each_programs_own_scopes(tmp_path):
    """Two programs that differ only in a scope get two entries of a
    shared persistent compile cache: an executable loaded from it
    carries this code's op_names, never another version's (by default
    JAX leaves metadata out of the key).  Importing the program keys
    the cache on metadata, with the checkout's directory cut."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    assert jax.config.jax_compilation_cache_include_metadata_in_key
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    regex = jax.config.jax_hlo_source_file_canonicalization_regex
    assert regex and re.sub(regex, "", os.path.join(
        root, "src", "repro", "core", "commit.py")) == \
        os.path.join("src", "repro", "core", "commit.py")
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    old = {k: getattr(jax.config, k) for k in keys}
    jax.config.update(keys[0], str(tmp_path))
    jax.config.update(keys[1], 0)
    jax.config.update(keys[2], 0)
    cc.reset_cache()

    def hlo(scope):
        def f(x):
            with jax.named_scope(scope):
                return jnp.sin(x) * 2
        return jax.jit(f).lower(jnp.ones(8)).compile().as_text()

    try:
        assert "scope_a" in hlo("scope_a")
        assert "scope_b" in hlo("scope_b")
        assert len(os.listdir(tmp_path)) >= 2
    finally:
        for k, v in old.items():
            jax.config.update(k, v)
        cc.reset_cache()


def test_tuner_tune_s_counts_races_not_cache_hits(monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", "off")
    rec = OT.Tracer(enabled=True)
    old = OT.get_tracer()
    OT.set_tracer(rec)
    try:
        tuner = AT.AutoTuner(ns=(8, 16), repeats=1)
        kw = dict(sort=True, stats=False, tile_m=256, block_v=512,
                  interpret=None, v=256, op="min")
        assert tuner.tune_s == 0.0
        tuner.race({"atomic": None, "coarse": None}, 64, **kw)
        after_race = tuner.tune_s
        runs = tuner.timed_runs
        assert after_race > 0 and runs == 2
        tuner.race({"atomic": None, "coarse": None}, 64, **kw)  # cached
        assert tuner.tune_s == after_race and tuner.timed_runs == runs
        tuner.calibrate(with_pallas=False, sort=True, stats=False,
                        tile_m=256, block_v=512, interpret=None)
        assert tuner.tune_s > after_race
    finally:
        OT.set_tracer(old)
    tunes = [e for e in rec.events if e["name"] == "tune"]
    assert [e["args"]["what"] for e in tunes] == ["race", "calibrate"]
    assert sum(e["dur"] for e in tunes) == pytest.approx(tuner.tune_s,
                                                         rel=0.05)


# -- metrics ----------------------------------------------------------------


def test_histogram_quantile_within_one_bucket_of_exact():
    h = OM.Histogram("h")
    rng = np.random.default_rng(0)
    vals = rng.exponential(0.01, 500)
    for v in vals:
        h.observe(v)
    for q in (0.5, 0.9, 0.99):
        exact = float(np.percentile(vals, q * 100))
        assert abs(h.bucket_of(exact) - h.bucket_of(h.quantile(q))) <= 1
    assert h.count == 500 and h.sum == pytest.approx(vals.sum())


def test_registry_exports_validate():
    reg = OM.Registry()
    reg.counter("aam_c", help="a counter").inc(2)
    reg.gauge("aam_g").set(1.5)
    reg.histogram("aam_h").observe(0.25)
    snap = reg.snapshot()
    assert OM.validate_metrics_json(snap) == []
    assert snap["counters"]["aam_c"] == 2
    text = reg.prometheus_text()
    assert "# TYPE aam_c counter" in text
    assert 'aam_h_bucket{le="+Inf"} 1' in text and "aam_h_count 1" in text
    # malformed documents are findings, not crashes
    assert OM.validate_metrics_json({"schema": "nope"})
    bad = json.loads(json.dumps(snap).replace('"count": 1', '"count": 9'))
    assert OM.validate_metrics_json(bad)


def test_service_stats_is_registry_view():
    st = ServiceStats()
    st.waves += 3
    st.graph_waves += 2
    st.product_waves += 1
    st.last_drain_s = 0.5
    assert st.total_waves == 6
    assert st.registry.counter("aam_waves").value == 3
    assert st.registry.gauge("aam_last_drain_s").value == 0.5
    assert "aam_waves 3" in st.registry.prometheus_text()
    assert "waves=3" in repr(st)
    with pytest.raises(AttributeError):
        st.nonexistent_field


# -- the wave tap -----------------------------------------------------------


def test_commit_tap_records_and_off_jaxpr_is_clean():
    spec_on = CommitSpec(trace=True, stats=True)
    spec_off = CommitSpec(stats=True)
    state = jnp.zeros((8,), jnp.int32)

    def run(spec):
        step, lvl0 = AT.make_commit_step(spec, "add", state, n=16,
                                         label="test:add")
        from repro.core.messages import make_messages
        msgs = make_messages(jnp.arange(16, dtype=jnp.int32) % 8,
                             jnp.ones((16,), jnp.int32),
                             jnp.ones((16,), bool))
        return step, msgs

    step_off, msgs = run(spec_off)
    jx = jax.make_jaxpr(lambda s, m: step_off(s, m, jnp.int32(0)))(
        state, msgs)
    assert "callback" not in str(jx), \
        "trace=False commit step leaked a host callback into the jaxpr"

    step_on, msgs = run(spec_on)
    jx = jax.make_jaxpr(lambda s, m: step_on(s, m, jnp.int32(0)))(
        state, msgs)
    assert "callback" in str(jx)
    OW.clear()
    res, _ = jax.jit(step_on)(state, msgs, jnp.int32(0))
    jax.block_until_ready(res.state)
    recs = OW.records()
    assert len(recs) == 1 and recs[0]["kind"] == "commit"
    assert recs[0]["label"] == "test:add" and recs[0]["messages"] == 16
    OW.clear()


def test_engine_round_tap_records_per_round():
    from repro.graphs.algorithms.bfs import distributed_bfs
    g = erdos_renyi(24, 3.0, seed=3)
    OW.clear()
    dist, rounds = distributed_bfs(_mesh1(), g, 0, capacity=64,
                                   spec=CommitSpec(trace=True, stats=True))
    recs = [r for r in OW.records() if r["kind"] == "round"]
    assert len(recs) == int(rounds)
    assert [r["round"] for r in recs] == list(range(int(rounds)))
    assert all(r["shard"] == 0 for r in recs)
    s = OW.summary()
    assert s["rounds"] == int(rounds) and s["commits"] >= 0
    OW.clear()


def test_wavetap_flush_renders_device_events():
    OW.clear()
    OW.collector().add({"kind": "round", "label": "x", "t": 1.0,
                        "round": 0, "conflicts": 2, "messages": 10,
                        "subrounds": 1, "level": 0, "shard": 0})
    OW.collector().add({"kind": "round", "label": "x", "t": 1.5,
                        "round": 1, "conflicts": 0, "messages": 4,
                        "subrounds": 1, "level": 1, "shard": 0})
    clk = CountingClock()
    tr = OT.Tracer(clock=clk, enabled=True)
    assert OW.flush_to(tr) == 2
    assert OW.records() == []           # drained
    assert [e["tid"] for e in tr.events] == [OT.TID_DEVICE] * 2
    # each record is an instant at its host arrival time, with its
    # fields: the gap between callbacks is no device duration
    assert [e["ph"] for e in tr.events] == ["i", "i"]
    assert [e["ts"] for e in tr.events] == [1.0, 1.5]
    assert all("dur" not in e for e in tr.events)
    assert tr.events[1]["name"] == "round[x]"
    assert tr.events[1]["args"] == {"round": 1, "conflicts": 0,
                                    "messages": 4, "subrounds": 1,
                                    "level": 1, "shard": 0}
    assert clk.reads == 0               # arrival times, not the clock
    assert OT.validate_trace(tr.to_chrome()) == []


def test_trace_off_clean_engine_and_control(monkeypatch):
    """The tier-1 gate on the zero-impact guarantee: one engine round
    loop traces clean with tracing off, and the trace=True control
    proves the jaxpr scan detects the tap (full catalog: `make lint`)."""
    monkeypatch.delenv("REPRO_TRACE", raising=False)
    from repro.analysis import waverace
    from repro.core import engine as E
    pts = [p for p in waverace.entry_points() if p[0] == "bfs/distributed"]
    (label, cap), = waverace.capture_algorithms(pts)

    def jx(spec):
        r = E._Runner(cap.alg, _mesh1(), cap.g, axis="data", capacity=64,
                      m=8, spec=spec, batch=cap.batch, max_subrounds=8)
        return str(jax.make_jaxpr(r._jfn)(
            *cap.alg.init(cap.g, r.layout), r.zero_carry(),
            jnp.asarray(1, jnp.int32), *r.arrays))

    assert "callback" not in jx(CommitSpec())
    assert "callback" in jx(CommitSpec(trace=True))


@pytest.mark.slow
def test_lint_trace_off_clean_cli():
    from repro.analysis import lint
    assert lint.main(["--skip-waverace", "--trace-off-clean"]) == 0


# -- serving spans ----------------------------------------------------------


def test_drain_span_reuses_clock_reads():
    """The pinned two-reads-per-drain contract survives tracing ON: the
    drain span is recorded from t0/dt the drain already read."""
    clk = CountingClock()
    tr = OT.Tracer(clock=clk, enabled=True)
    svc = GraphService(clock=clk, tracer=tr)
    svc.register_graph("g", erdos_renyi(20, 3.0, seed=0))
    svc.submit("g", BfsQuery(0))
    r0 = clk.reads
    svc.drain()
    # t0 + finally; wave spans add 2 more (begin/end of the one wave)
    assert clk.reads - r0 == 4
    names = [e["name"] for e in tr.events]
    assert "drain" in names and "wave" in names
    drain = next(e for e in tr.events if e["name"] == "drain")
    assert drain["args"]["done"] == 1
    assert tr.open_spans() == []


def test_submit_instants_record_cache_hits():
    tr = OT.Tracer(clock=FakeClock(), enabled=True)
    svc = GraphService(clock=FakeClock(), tracer=tr)
    svc.register_graph("g", erdos_renyi(20, 3.0, seed=0))
    svc.submit("g", BfsQuery(0))
    svc.drain()
    svc.submit("g", BfsQuery(0))        # cache hit
    subs = [e for e in tr.events if e["name"] == "submit"]
    assert [s["args"]["cache_hit"] for s in subs] == [False, True]


def test_crash_restore_redrain_single_trace():
    """Supervised crash -> restore -> re-drain is ONE well-formed trace:
    no orphan spans, restore + wal_replay instants present, every
    acknowledged ticket answered exactly once."""
    from repro.checkpoint.checkpointer import Checkpointer
    from repro.serve.durable import ServiceSupervisor

    clk = FakeClock()
    tr = OT.Tracer(clock=clk, enabled=True)
    svc = GraphService(clock=clk, tracer=tr, cache=False)
    g = erdos_renyi(24, 3.0, seed=5)
    svc.register_graph("g", g)
    ckdir = tempfile.mkdtemp(prefix="obs_ck_")
    try:
        sup = ServiceSupervisor(svc, Checkpointer(ckdir),
                                log=lambda *_: None)
        sup.save()
        tickets = [sup.submit("g", BfsQuery(s)) for s in range(3)]
        kill = svc._wave_i
        svc.fault_injector = (
            lambda where, i: (_ for _ in ()).throw(
                RuntimeError("host lost")) if i == kill else None)
        done = sup.drain()              # crash -> restore -> re-drain
        assert sorted(done) == tickets  # exactly-once: all, none doubled
        svc2 = sup.service
        assert svc2.tracer is tr        # ONE trace across the restore
        assert tr.open_spans() == []    # the faulted wave span closed
        names = [e["name"] for e in tr.events]
        assert names.count("drain") == 2    # faulted + re-drain
        inst = [e["name"] for e in tr.events if e["ph"] == "i"]
        assert "restore" in inst and "wal_replay" in inst
        wal = next(e for e in tr.events if e["name"] == "wal_replay")
        assert wal["args"]["replayed"] == 3
        assert OT.validate_trace(tr.to_chrome()) == []
        rows = [sup.result(t) for t in tickets]
        from repro.graphs.algorithms.bfs import bfs
        for s, row in zip(range(3), rows):
            np.testing.assert_array_equal(np.asarray(row),
                                          np.asarray(bfs(g, s).dist))
    finally:
        shutil.rmtree(ckdir, ignore_errors=True)


# -- continuous server: latency histogram + cache-hit drains ----------------


def test_continuous_latency_histogram_matches_bench_percentile():
    from repro.serve.continuous import ContinuousServer
    svc = GraphService(cache=False)
    svc.register_graph("g", kronecker(5, 6, seed=1))
    svc.register_graph("h", erdos_renyi(30, 4.0, seed=2))
    with ContinuousServer(svc, max_wait_s=0.005) as cs:
        tickets = [cs.submit("g", BfsQuery(s)) for s in range(4)]
        tickets += [cs.submit("h", BfsQuery(s)) for s in range(3)]
        cs.results(tickets, timeout=120)
        if cs.last_error is not None:
            raise cs.last_error
    lat = [cs.done_at[t] - cs.submit_at[t] for t in tickets]
    h = cs.svc.stats.registry.histogram("aam_submit_to_answer_seconds")
    assert h.count == len(tickets)
    assert h.sum == pytest.approx(sum(lat))
    for q in (0.5, 0.99):
        bench = float(np.percentile(lat, q * 100))
        assert abs(h.bucket_of(bench) - h.bucket_of(h.quantile(q))) <= 1


def test_cache_hit_only_cycle_updates_drain_stats():
    from repro.serve.continuous import ContinuousServer
    clk = FakeClock()
    svc = GraphService(clock=clk)
    svc.register_graph("g", erdos_renyi(20, 3.0, seed=0))
    svc.submit("g", BfsQuery(0))
    svc.drain()
    drains0 = svc.stats.drains
    svc.stats.last_drain_s = 7.5        # stale marker
    cs = ContinuousServer(svc)          # no loop needed for a cache hit
    t = cs.submit("g", BfsQuery(0))
    assert t in svc._results            # answered at submit
    assert svc.stats.drains == drains0 + 1
    assert svc.stats.last_drain_s == 0.0
    h = svc.stats.registry.histogram("aam_submit_to_answer_seconds")
    assert h.count == 1 and h.sum == 0.0


# -- bench-row trace fields -------------------------------------------------


def test_open_loop_rows_carry_trace_fields_schema():
    from benchmarks.serve_qps import _open_rows_to_json
    from repro.analysis import lint
    rows = [{"kind": "bfs", "mode": "product", "offered_qps": 20,
             "achieved_qps": 19.5, "p50_ms": 1.0, "p99_ms": 2.0,
             "mean_ms": 1.2, "n": 8, "product_waves": 2,
             "trace_rounds": 5, "trace_mean_density": 0.12,
             "trace_ladder_moves": 1}]
    d = tempfile.mkdtemp(prefix="obs_bench_")
    try:
        path = os.path.join(d, "BENCH_t.json")
        _open_rows_to_json(rows, path)
        assert lint.run_bench_schema(d) == []
        doc = json.loads(open(path).read())
        row = doc["rows"][0]
        for k in ("trace_rounds", "trace_mean_density",
                  "trace_ladder_moves"):
            assert isinstance(row[k], (int, float)), k
        assert "rounds=5" in row["derived"]
    finally:
        shutil.rmtree(d, ignore_errors=True)


def test_trace_probe_summary_fields():
    from benchmarks.serve_qps import _trace_probe
    gp = {"hot": kronecker(5, 6, seed=1),
          "t0": erdos_renyi(24, 3.0, seed=2)}
    p = _trace_probe("bfs", gp, None, True, 0)
    assert set(p) == {"rounds", "commits", "mean_density", "ladder_moves"}
    assert p["rounds"] > 0 and 0.0 <= p["mean_density"] <= 1.0
