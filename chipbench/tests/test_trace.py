"""The trace reduction: intervals, scope attribution and a recorded CPU
trace."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from chipbench.lib import hlo, layers, trace, work  # noqa: E402
from chipbench.harness import Context  # noqa: E402


def test_union_merges_overlaps_and_clips_to_window():
    iv = [(0, 10), (5, 20), (30, 40), (39, 45), (100, 200)]
    assert trace.union_ns(iv, 0, 1000) == 20 + 15 + 100
    assert trace.union_ns(iv, 8, 35) == 12 + 5
    assert trace.union_ns([], 0, 10) == 0


def test_gaps_are_the_complement_in_the_window():
    iv = [(2, 4), (3, 6), (8, 9)]
    assert trace.gaps(iv, 0, 10) == [(0, 2), (6, 8), (9, 10)]
    assert trace.gaps(iv, 2, 6) == []


def _op(s, e, name, opcode="fusion", module="jit_f"):
    return trace.Op(s, e, name, opcode, module)


def _synthetic():
    ops = {0: [_op(0, 40, "fusion.1"), _op(40, 70, "fusion.2"),
               _op(80, 90, "all-to-all.3", "all-to-all")],
           1: [_op(0, 20, "fusion.1"), _op(50, 60, "all-reduce.1",
                                           "all-reduce")]}
    host = [("chipbench.window", 0, 100), ("chipbench.unit", 0, 100),
            ("lower_sharding_computation", 70, 80)]
    return trace.Trace(ops, host, (0, 100))


def test_busy_idle_and_busiest_device():
    tr = _synthetic()
    assert tr.busy_s(0) == pytest.approx(80e-9)
    assert tr.idle_share(1) == pytest.approx(0.7)
    assert tr.busiest() == 0
    coll = tr.busy_s(0, lambda o: trace.is_collective(o.opcode))
    assert coll == pytest.approx(10e-9)
    assert trace.is_collective("all-reduce-start")
    assert not trace.is_collective("fusion")


def test_idle_gaps_are_named_by_the_innermost_host_span():
    bd = _synthetic().breakdown()
    assert bd["idle_gaps"][0][0] == "lower_sharding_computation"
    assert bd["idle_gaps"][0][1] == pytest.approx(10e-9)
    assert bd["device_ops"][0][0] == "jit_f/fusion.1"


HLO = """HloModule jit_f, entry_computation_layout={(s32[8]{0})->s32[8]{0}}

%fused_in (p0: s32[8]) -> s32[8] {
  %p0 = s32[8]{0} parameter(0)
  ROOT %add.1 = s32[8]{0} add(%p0, %p0), metadata={op_name="jit(f)/while/body/aam_commit/add"}
}

%fused_mixed (p0: s32[8]) -> s32[8] {
  %p0 = s32[8]{0} parameter(0)
  %mul.1 = s32[8]{0} multiply(%p0, %p0), metadata={op_name="jit(f)/while/body/mul"}
  ROOT %add.2 = s32[8]{0} add(%mul.1, %p0), metadata={op_name="jit(f)/while/body/aam_commit/add"}
}

ENTRY %main.1 (x: s32[8]) -> s32[8] {
  %x = s32[8]{0} parameter(0)
  %fusion.1 = s32[8]{0} fusion(%x), kind=kLoop, calls=%fused_in, metadata={op_name="jit(f)/while/body/aam_commit/add"}
  %fusion.2 = s32[8]{0} fusion(%fusion.1), kind=kLoop, calls=%fused_mixed, metadata={op_name="jit(f)/while/body/aam_commit/add"}
  %gather.3 = s32[8]{0} gather(%fusion.2, %x), offset_dims={}, metadata={op_name="jit(f)/while/body/gather"}
  %copy.4 = s32[8]{0} copy(%gather.3)
  ROOT %while.5 = s32[8]{0} while(%copy.4), condition=%c, body=%b, metadata={op_name="jit(f)/while"}
}
"""


def test_scope_classes_follow_every_op_name_of_a_fusion():
    c = hlo.classify(HLO, "aam_commit")
    assert c["fusion.1"][:2] == ("fusion", hlo.IN)
    assert c["fusion.2"][:2] == ("fusion", hlo.MIXED)
    assert c["gather.3"][:2] == ("gather", hlo.OUT)
    assert c["copy.4"][:2] == ("copy", hlo.UNNAMED)
    assert "while.5" not in c
    assert c["gather.3"][2] == "jit(f)/while/body/gather"


def _ctx(ops, rounds=2):
    tr = trace.Trace({0: ops}, [("chipbench.window", 0, 100)], (0, 100))
    return Context(trace=tr, counters={"rounds": rounds, "edges": 1000,
                                       "vertices": 100},
                   peaks={"hbm_bytes_per_s": 1e12}, hlo_texts=[HLO])


def test_breakdown_labels_name_each_ops_scope_class():
    ctx = _ctx([_op(0, 40, "fusion.1"), _op(40, 100, "fusion.2")])
    labels = layers.op_labels(ctx)
    assert labels[("jit_f", "fusion.1")] == \
        "in: jit(f)/while/body/aam_commit/add"
    assert labels[("jit_f", "fusion.2")].startswith("mixed: ")
    assert labels[("jit_f", "copy.4")] == "unnamed"
    bd = ctx.trace.breakdown(labels)
    assert bd["device_ops"][0][0] == \
        "jit_f/fusion.2 [mixed: jit(f)/while/body/aam_commit/add]"


def test_collective_time_per_round():
    tr = _synthetic()
    ctx = Context(trace=tr, counters={"rounds": 4}, peaks=None,
                  hlo_texts=[])
    worst = max(layers.collective_s(ctx, d) for d in tr.devices)
    assert worst == pytest.approx(10e-9)
    assert layers.per_round_ms(worst, ctx) == pytest.approx(2.5e-6)
    assert layers.per_round_ms(None, ctx) is None


def test_commit_bytes_come_from_shapes():
    assert work.commit_bytes(1000, 100) == 1000 * 9 + 100 * 8
    assert work.commit_bytes(31401498, 1 << 20) == \
        31401498 * 9 + (1 << 20) * 8


def test_recorded_cpu_trace_attributes_a_named_scope(tmp_path):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x, idx):
        y = x[idx] * 3                      # outside the scope
        with jax.named_scope("aam_commit"):
            return x.at[idx].min(y)

    x = jnp.arange(4096, dtype=jnp.int32)
    idx = (jnp.arange(65536, dtype=jnp.int32) * 7919) % 4096
    f(x, idx).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("chipbench.window"):
        for _ in range(20):
            f(x, idx).block_until_ready()
    jax.profiler.stop_trace()
    tr = trace.load_dir(str(tmp_path), "chipbench.window")
    assert tr.devices == [0]
    assert 0 < tr.busy_s(0) <= tr.window_s
    classes = hlo.classify(f.lower(x, idx).compile().as_text(), "aam_commit")
    seen = {classes[o.name][1] for o in tr.ops[0] if o.name in classes}
    assert hlo.IN in seen
