"""Phases of a round from the program's scopes: the root-op rule on
synthetic HLO, the new readers on empty traces, and idle gaps named by
the program's own ``aam.*`` spans (synthetic and in a recorded CPU
trace)."""
import importlib.util
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))       # the program, as run.py does

from chipbench.harness import Context  # noqa: E402
from chipbench.lib import phases, trace  # noqa: E402

NEW_READERS = ["message_ms.g500", "commit_ms.g500", "commit_stats_ms.g500",
               "message_ms.pr", "commit_ms.pr"]
TUNER_READERS = ["tuner_s.g500", "tuner_s.pr"]

BODY = "jit(f)/while/body"
HLO = f"""HloModule jit_f, entry_computation_layout={{(s32[8]{{0}})->s32[8]{{0}}}}

%fused_msgs (p0: s32[8]) -> s32[8] {{
  %p0 = s32[8]{{0}} parameter(0)
  ROOT %add.1 = s32[8]{{0}} add(%p0, %p0), metadata={{op_name="{BODY}/aam_messages/add"}}
}}

%fused_mixed (p0: s32[8]) -> s32[8] {{
  %p0 = s32[8]{{0}} parameter(0)
  %mul.1 = s32[8]{{0}} multiply(%p0, %p0), metadata={{op_name="{BODY}/aam_messages/mul"}}
  ROOT %min.2 = s32[8]{{0}} minimum(%mul.1, %p0), metadata={{op_name="{BODY}/aam_commit/min"}}
}}

ENTRY %main.1 (x: s32[8]) -> s32[8] {{
  %x = s32[8]{{0}} parameter(0)
  %fusion.1 = s32[8]{{0}} fusion(%x), kind=kLoop, calls=%fused_msgs, metadata={{op_name="{BODY}/aam_messages/add"}}
  %fusion.2 = s32[8]{{0}} fusion(%fusion.1), kind=kLoop, calls=%fused_mixed, metadata={{op_name="{BODY}/aam_commit/min"}}
  %scatter.3 = s32[8]{{0}} scatter(%fusion.2, %x, %x), metadata={{op_name="{BODY}/aam_commit/aam_commit_stats/scatter-min"}}
  %gather.4 = s32[8]{{0}} gather(%scatter.3, %x), metadata={{op_name="{BODY}/aam_commit_statsX/gather"}}
  %copy.5 = s32[8]{{0}} copy(%gather.4)
  ROOT %while.6 = s32[8]{{0}} while(%copy.5), condition=%c, body=%b, metadata={{op_name="jit(f)/while"}}
}}
"""


def _op(s, e, name, opcode="fusion", module="jit_f"):
    return trace.Op(s, e, name, opcode, module)


def _ctx(ops, rounds=2, hlo_texts=(HLO,), host=None):
    host = host or [("chipbench.window", 0, 100)]
    tr = trace.Trace({0: ops} if ops is not None else {}, host, (0, 100))
    return Context(trace=tr, counters={"rounds": rounds, "edges": 1000,
                                       "vertices": 100},
                   peaks=None, hlo_texts=list(hlo_texts))


def _reader(name):
    path = ROOT / "chipbench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "m_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_a_device_op_takes_its_roots_phase():
    table = phases.op_phases([HLO])
    assert table[("jit_f", "fusion.1")] == phases.MESSAGES
    # fused across the scope's edge: the root is the commit's
    assert table[("jit_f", "fusion.2")] == phases.COMMIT
    assert table[("jit_f", "scatter.3")] == phases.COMMIT_STATS
    # a longer name that only starts with a scope's name is no scope
    assert table[("jit_f", "gather.4")] == phases.OTHER
    assert table[("jit_f", "copy.5")] == phases.OTHER
    assert ("jit_f", "while.6") not in table
    assert phases.phase_of("jit(f)/jvp(aam_commit)/x") == phases.COMMIT


def test_nested_stats_count_toward_commit_and_stats():
    ops = [_op(0, 10, "fusion.1"), _op(10, 40, "fusion.2"),
           _op(40, 60, "scatter.3", "scatter"), _op(60, 70, "gather.4",
                                                    "gather"),
           _op(70, 75, "copy.5", "copy"), _op(80, 90, "unknown.9")]
    ctx = _ctx(ops)
    split = phases.split_s(ctx, 0)
    assert split == pytest.approx({"messages": 10e-9, "commit": 50e-9,
                                   "commit_stats": 20e-9,
                                   "other": 25e-9})
    # the phases add up to the busy time: commit holds its stats
    assert split["messages"] + split["commit"] + split["other"] == \
        pytest.approx(ctx.trace.busy_s(0))
    values = {n: _reader(n).read(ctx) for n in NEW_READERS}
    assert values["message_ms.g500"] == pytest.approx(10e-9 / 2 * 1e3)
    assert values["commit_ms.g500"] == pytest.approx(50e-9 / 2 * 1e3)
    assert values["commit_stats_ms.g500"] == pytest.approx(20e-9 / 2 * 1e3)
    assert values["message_ms.pr"] == values["message_ms.g500"]
    assert values["commit_ms.pr"] == values["commit_ms.g500"]


@pytest.mark.parametrize("name", NEW_READERS)
def test_new_readers_find_nothing_in_an_empty_trace(name):
    read = _reader(name).read
    assert read(Context(trace=None, counters={"rounds": 2}, peaks=None,
                        hlo_texts=[HLO])) is None
    assert read(_ctx(None)) is None                    # no device ops
    assert read(_ctx([_op(0, 10, "fusion.1")], hlo_texts=())) is None
    # a program without the scopes (the phase has no op): nothing
    assert read(_ctx([_op(0, 10, "copy.5", "copy")])) is None


@pytest.mark.parametrize("name", TUNER_READERS)
def test_tuner_readers_read_the_programs_counter(name, monkeypatch):
    from repro.core import autotune
    read = _reader(name).read
    monkeypatch.setattr(autotune.DEFAULT_TUNER, "tune_s", 1.25)
    assert read(_ctx(None)) == 1.25
    # a program whose tuner keeps no such counter: nothing to read
    monkeypatch.setattr(autotune, "DEFAULT_TUNER", object())
    assert read(_ctx(None)) is None


def test_an_idle_gap_inside_a_program_span_is_named_by_it():
    ops = [_op(0, 40, "fusion.1"), _op(80, 100, "fusion.2")]
    host = [("chipbench.window", 0, 100), ("chipbench.unit", 0, 100),
            ("aam.runner_build", 35, 85)]
    tr = _ctx(ops, host=host).trace
    assert tr.host_at(60) == "aam.runner_build"
    gap = tr.breakdown()["idle_gaps"][0]
    assert gap[0] == "aam.runner_build"
    assert gap[1] == pytest.approx(40e-9)


def test_recorded_trace_names_an_idle_gap_by_the_programs_span(tmp_path):
    import jax
    import jax.numpy as jnp
    from repro.obs import trace as obs_trace

    @jax.jit
    def f(x):
        return jnp.sort(x * 3)

    x = jnp.arange(1 << 14, dtype=jnp.int32)[::-1]
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("chipbench.window"):
        f(x).block_until_ready()
        with obs_trace.span("probe"):
            time.sleep(0.05)             # the chip waits on the host
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    tr = trace.load_dir(str(tmp_path), "chipbench.window")
    assert "aam.probe" in [n for n, _, _ in tr.host]
    name, seconds = max(tr.breakdown()["idle_gaps"], key=lambda g: g[1])
    assert name == "aam.probe" and seconds >= 0.04
