"""Reduction of a JAX profiler trace (``*.xplane.pb``) to device intervals.

What a TPU trace holds (read by hand on a v5e trace of ``bfs`` and
``pagerank``): one plane ``/device:TPU:<n>`` per chip with the lines

* ``XLA Modules`` — one event per program run, named ``jit_bfs(<id>)``;
* ``XLA Ops`` — one event per HLO instruction run, named by the
  instruction's text (``%fusion.42 = s32[...] fusion(...), kind=...``).
  Control flow (``while``, ``conditional``, ``call``) appears too, as an
  event spanning its whole body, so it is left out here;
* ``Async XLA Ops`` — copies and other asynchronous ops overlapping the
  others (not counted as busy).

Host threads sit on ``/host:CPU``, one line per thread; the line of the
thread that ran the window (named after the process, ``python3``) holds
the ``jax.profiler.TraceAnnotation`` spans.  Host and
device events share one clock (nanoseconds from the profile's start).

On the CPU backend (rehearsals and tests) there is no device plane: the
ops are host events carrying ``hlo_op`` and ``hlo_module`` stats, and
they are read as device 0.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

_DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:(\d+)$")
_TEXT_NAME = re.compile(r"^%([\w.\-]+)\s*=\s*(.*)$", re.S)
CONTAINERS = {"while", "conditional", "call"}
COLLECTIVES = {"all-to-all", "all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "ragged-all-to-all",
               "collective-broadcast"}


def _opcode_of_text(rhs: str) -> str:
    depth = 0
    for i, ch in enumerate(rhs):
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        elif ch == " " and depth == 0:
            m = re.match(r"\s*([\w\-]+)\(", rhs[i:])
            return m.group(1) if m else ""
    return ""


def _opcode_of_name(name: str) -> str:
    return re.sub(r"\.\d+$", "", name)


def is_collective(opcode: str) -> bool:
    base = re.sub(r"-(start|done)$", "", opcode)
    return base in COLLECTIVES


@dataclasses.dataclass(frozen=True)
class Op:
    start: float        # ns
    end: float          # ns
    name: str           # HLO instruction name
    opcode: str
    module: str | None  # e.g. "jit_bfs"


def union_ns(intervals, lo: float, hi: float) -> float:
    """Length of the union of ``(start, end)`` intervals clipped to
    ``[lo, hi]``."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals, lo: float, hi: float) -> list:
    """``(start, end)`` stretches of ``[lo, hi]`` that no interval
    covers."""
    out, cur = [], lo
    for s, e in sorted(intervals):
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(s, e) for s, e in out if e > s]


class Trace:
    """Device ops per device and the host's Python-thread spans, within
    the window the harness marked with a ``TraceAnnotation``."""

    def __init__(self, ops: dict, host: list, window: tuple):
        self.ops = ops              # {device: [Op]} (no control flow)
        self.host = host            # [(name, start, end)]
        self.window = window        # (start ns, end ns)

    @property
    def devices(self) -> list:
        return sorted(self.ops)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def busy_s(self, device: int, pred=None) -> float:
        """Seconds of the window in which an op (one matching ``pred``)
        ran on ``device``."""
        return union_ns([(o.start, o.end) for o in self.ops[device]
                         if pred is None or pred(o)], *self.window) / 1e9

    def idle_share(self, device: int) -> float:
        return 1.0 - self.busy_s(device) / self.window_s

    def busiest(self) -> int:
        return max(self.devices, key=self.busy_s)

    def host_at(self, t: float) -> str:
        """Innermost host span covering ``t`` (``idle`` if none)."""
        inside = [(e - s, n) for n, s, e in self.host if s <= t <= e]
        return min(inside)[1] if inside else "idle"

    def breakdown(self, labels=None, top: int = 10) -> dict:
        """The device ops that took most time (summed over devices, per
        chip) and the longest idle gaps of the busiest device, each named
        by what the host was doing in its middle.  ``labels`` maps
        ``(module, name)`` to a longer name (the scope an op ran in)."""
        lo, hi = self.window
        per = {}
        for ops in self.ops.values():
            for o in ops:
                s, e = max(o.start, lo), min(o.end, hi)
                if e > s:
                    key = (o.module, o.name)
                    per[key] = per.get(key, 0.0) + (e - s)
        n = max(len(self.ops), 1)
        named = []
        for (mod, name), ns in sorted(per.items(), key=lambda kv: -kv[1]):
            label = f"{mod}/{name}" if mod else name
            if labels and (mod, name) in labels:
                label += f" [{labels[(mod, name)]}]"
            named.append([label, ns / n / 1e9])
        dev = self.busiest() if self.ops else None
        idle = []
        if dev is not None:
            g = gaps([(o.start, o.end) for o in self.ops[dev]], lo, hi)
            for s, e in sorted(g, key=lambda se: se[0] - se[1])[:top]:
                idle.append([self.host_at((s + e) / 2), (e - s) / 1e9])
        return {"device_ops": named[:top], "idle_gaps": idle}


def _stats(ev) -> dict:
    return {k: v for k, v in ev.stats}


def from_profile(pd, window_span: str) -> Trace:
    """Build a :class:`Trace` from ``jax.profiler.ProfileData``."""
    ops: dict = {}
    host: list = []
    cpu_ops: list = []
    for plane in pd.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            lines = {ln.name: list(ln.events) for ln in plane.lines}
            modules = sorted((e.start_ns, e.start_ns + e.duration_ns,
                              e.name.split("(")[0])
                             for e in lines.get("XLA Modules", ()))
            out = ops.setdefault(dev, [])
            for ev in lines.get("XLA Ops", ()):
                tm = _TEXT_NAME.match(ev.name)
                name, opcode = ((tm.group(1), _opcode_of_text(tm.group(2)))
                                if tm else (ev.name, _opcode_of_name(ev.name)))
                if opcode in CONTAINERS:
                    continue
                s = ev.start_ns
                mod = next((mn for ms, me, mn in modules if ms <= s <= me),
                           None)
                out.append(Op(s, s + ev.duration_ns, name, opcode, mod))
            continue
        if not plane.name.startswith("/host:"):
            continue
        for ln in plane.lines:
            evs = list(ln.events)
            if any(ev.name == window_span for ev in evs):
                host = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                        for ev in evs]
                continue
            for ev in evs:
                st = _stats(ev)
                if "hlo_op" not in st:
                    continue
                name = str(st["hlo_op"])
                opcode = _opcode_of_name(name)
                if opcode not in CONTAINERS:
                    cpu_ops.append(Op(ev.start_ns,
                                      ev.start_ns + ev.duration_ns, name,
                                      opcode,
                                      str(st.get("hlo_module", "")) or None))
    if not ops and cpu_ops:
        ops[0] = cpu_ops
    spans = [(s, e) for n, s, e in host if n == window_span]
    if not spans:
        raise ValueError(f"no {window_span!r} span in the trace")
    return Trace(ops, host, spans[0])


def load_dir(directory: str, window_span: str) -> Trace:
    """Read the one ``*.xplane.pb`` the profiler wrote under
    ``directory``."""
    import jax
    files = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise ValueError(f"expected one xplane file under {directory}, "
                         f"found {files}")
    return from_profile(jax.profiler.ProfileData.from_file(files[0]),
                        window_span)
