"""Span tracer + Chrome/Perfetto export.

One :class:`Tracer` holds a flat event list; spans are "X" complete
events (begin/end read the tracer's clock), instants are "i" events
(restore, WAL replay, mesh shrink).  The tracer's clock defaults to
``time.perf_counter`` but a service constructed with an injected clock
binds its tracer to THE SAME clock, so fake-clock tests see
deterministic span timestamps.

Every span also opens a ``jax.profiler.TraceAnnotation`` named
``aam.<name>`` (:func:`annotate`), active tracer or not: while a
``jax.profiler`` trace is being recorded the span sits on its thread's
host line, on the clock of the device ops it dispatched, so an idle gap
of the chip can be named by what the program was doing.  With no
profile running the annotation is the profiler's own no-op, and it
reads none of the tracer's clock.  :func:`span` is the process-global
tracer's span, for code that has no tracer of its own (the tuner, the
ingest, ``run_distributed``).

Recording is inert unless the tracer is *active*: ``enabled=None``
(the default) follows the ``REPRO_TRACE`` environment variable, so the
zero-impact-when-off guarantee extends to the host side — an inactive
span performs no clock reads and records nothing.

``to_chrome()`` exports ``{"traceEvents": [...]}`` (Chrome tracing /
Perfetto JSON, microsecond timestamps); :func:`validate_trace` is the
schema smoke check the lint CLI and tier-1 tests run over every
exported document.
"""
from __future__ import annotations

import contextlib
import functools
import os
import re
import threading
import time
from pathlib import Path

import jax
from jax.profiler import TraceAnnotation

TRACE_SCHEMA = "aam-trace/v1"
# prefix of the program's spans on a jax.profiler trace's host lines
ANNOTATION_PREFIX = "aam."

# tid convention for the one-process serving stack: host-side serving
# spans vs device-side wavetap events render as two named rows
TID_SERVE = 0
TID_DEVICE = 1


def trace_enabled() -> bool:
    """The global toggle: ``REPRO_TRACE`` set to anything but ``0``."""
    return os.environ.get("REPRO_TRACE", "").strip() not in ("", "0")


def key_compile_cache_on_metadata() -> None:
    """Make JAX's persistent compile cache key on op metadata.

    A profile names each device op by the ``op_name`` its executable
    carries: the phase scopes of :mod:`repro.core.commit`.  By default
    the cache leaves metadata out of its key, so a cache shared with
    another version of this code serves that version's executable, and
    the profile shows that version's scopes.  The checkout's own
    directory is cut from source file names, so a checkout that moves
    still finds its entries."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    if jax.config.jax_hlo_source_file_canonicalization_regex is None:
        root = Path(__file__).resolve().parents[3]
        jax.config.update("jax_hlo_source_file_canonicalization_regex",
                          "^" + re.escape(str(root) + os.sep))


def annotate(name: str) -> TraceAnnotation:
    """``with annotate("drain"): ...`` — the profiler span
    ``aam.<name>`` alone, for a body whose :class:`Tracer` event is
    recorded afterwards from timestamps already read
    (:meth:`Tracer.complete`)."""
    return TraceAnnotation(ANNOTATION_PREFIX + name)


def annotated(name: str):
    """Decorator: every call of the function runs inside
    :func:`annotate` ``(name)``."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with annotate(name):
                return fn(*args, **kwargs)
        return inner
    return wrap


class Tracer:
    """Collects trace events; thread-safe (the continuous drain loop
    publishes from its own thread while clients submit).

    clock:   0-arg callable returning seconds.  Bind the service's
             injected clock so spans and ``ServiceStats`` timing agree.
    enabled: True/False pins the tracer on/off; None (default) follows
             ``REPRO_TRACE`` at each use site.
    """

    def __init__(self, clock=None, enabled: bool | None = None):
        self.clock = clock if clock is not None else time.perf_counter
        self.enabled = enabled
        self.events: list[dict] = []
        self._lock = threading.Lock()
        # per-thread stacks of open spans (orphan detection)
        self._open: dict[int, list[dict]] = {}
        # per-thread stacks of open profiler annotations (kept whether
        # or not the tracer is active)
        self._annotations = threading.local()

    @property
    def active(self) -> bool:
        return trace_enabled() if self.enabled is None else self.enabled

    # -- recording --------------------------------------------------------

    def begin(self, name: str, *, cat: str = "serve", tid: int = TID_SERVE,
              args: dict | None = None) -> None:
        """Open a span (reads the clock once when active) and its
        profiler annotation.  Prefer :meth:`span`."""
        ann = annotate(name)
        ann.__enter__()
        self._annotation_stack().append(ann)
        if not self.active:
            return
        ev = {"name": name, "cat": cat, "tid": tid, "ts": self.clock(),
              "args": dict(args or {})}
        with self._lock:
            self._open.setdefault(threading.get_ident(), []).append(ev)

    def end(self, args: dict | None = None) -> None:
        """Close the innermost open span of this thread (one clock
        read when active); no-op if none is open (e.g. tracing flipped
        mid-span)."""
        anns = self._annotation_stack()
        if anns:
            anns.pop().__exit__(None, None, None)
        if not self.active:
            return
        now = self.clock()
        with self._lock:
            stack = self._open.get(threading.get_ident())
            if not stack:
                return
            ev = stack.pop()
            ev["ph"] = "X"
            ev["dur"] = max(now - ev["ts"], 0.0)
            if args:
                ev["args"].update(args)
            self.events.append(ev)

    @contextlib.contextmanager
    def span(self, name: str, *, cat: str = "serve", tid: int = TID_SERVE,
             args: dict | None = None):
        """``with tracer.span("drain"): ...`` — the try/finally
        guarantees a fault inside the span still closes it, so a crash →
        restore run never leaves orphans."""
        self.begin(name, cat=cat, tid=tid, args=args)
        try:
            yield
        finally:
            self.end()

    def complete(self, name: str, ts: float, dur: float, *,
                 cat: str = "serve", tid: int = TID_SERVE,
                 args: dict | None = None) -> None:
        """Record a finished span from timestamps the caller ALREADY
        read — ``GraphService.drain`` reuses its own t0/dt so tracing
        adds zero clock reads there (a fake-clock test pins the exact
        read count)."""
        if not self.active:
            return
        ev = {"name": name, "cat": cat, "tid": tid, "ts": ts,
              "dur": max(dur, 0.0), "ph": "X", "args": dict(args or {})}
        with self._lock:
            self.events.append(ev)

    def instant(self, name: str, *, cat: str = "serve",
                tid: int = TID_SERVE, ts: float | None = None,
                args: dict | None = None) -> None:
        """Record an instant event (restore, WAL replay, mesh shrink)."""
        if not self.active:
            return
        ev = {"name": name, "cat": cat, "tid": tid,
              "ts": self.clock() if ts is None else ts, "ph": "i",
              "args": dict(args or {})}
        with self._lock:
            self.events.append(ev)

    def _annotation_stack(self) -> list:
        stack = getattr(self._annotations, "stack", None)
        if stack is None:
            stack = self._annotations.stack = []
        return stack

    # -- inspection / export ----------------------------------------------

    def open_spans(self) -> list[str]:
        """Names of spans begun but never ended — MUST be empty in a
        well-formed trace (the fault-path test asserts it)."""
        with self._lock:
            return [ev["name"] for stack in self._open.values()
                    for ev in stack]

    def clear(self) -> None:
        with self._lock:
            self.events.clear()
            self._open.clear()

    def to_chrome(self) -> dict:
        """Chrome tracing / Perfetto JSON: seconds -> microseconds."""
        with self._lock:
            events = [dict(e) for e in self.events]
        out = []
        for e in sorted(events, key=lambda e: e["ts"]):
            ev = {"name": e["name"], "cat": e["cat"], "ph": e["ph"],
                  "pid": 1, "tid": e["tid"],
                  "ts": round(e["ts"] * 1e6, 3), "args": e["args"]}
            if e["ph"] == "X":
                ev["dur"] = round(e["dur"] * 1e6, 3)
            else:
                ev["s"] = "p"        # process-scoped instant
            out.append(ev)
        return {"traceEvents": out, "displayTimeUnit": "ms",
                "otherData": {"schema": TRACE_SCHEMA}}


def validate_trace(doc) -> list[str]:
    """Schema smoke check over an exported trace document; returns
    findings (empty = valid).  Run by ``aamlint --trace-off-clean`` and
    the tier-1 tests over every trace this repo emits."""
    findings = []
    if not isinstance(doc, dict) or "traceEvents" not in doc:
        return ["trace: document has no traceEvents list"]
    events = doc["traceEvents"]
    if not isinstance(events, list):
        return ["trace: traceEvents is not a list"]
    for i, e in enumerate(events):
        missing = {"name", "ph", "ts", "pid", "tid"} - set(e)
        if missing:
            findings.append(f"trace: event {i} missing {sorted(missing)}")
            continue
        if not isinstance(e["ts"], (int, float)):
            findings.append(f"trace: event {i} ts not numeric")
        if e["ph"] == "X":
            if not isinstance(e.get("dur"), (int, float)) or e["dur"] < 0:
                findings.append(
                    f"trace: X event {i} ({e['name']}) bad dur")
        elif e["ph"] == "i":
            if e.get("s") not in ("g", "p", "t"):
                findings.append(
                    f"trace: instant {i} ({e['name']}) bad scope")
        elif e["ph"] not in ("B", "E", "M"):
            findings.append(f"trace: event {i} unknown phase {e['ph']!r}")
    return findings


# -- the process-global tracer ------------------------------------------
# Services share it by default (one continuous-batching run = one
# trace); engine instants (mesh shrink) land here too.  A test injects
# its own Tracer(clock=fake) either via set_tracer or per-service.

_TRACER: Tracer | None = None
_TRACER_LOCK = threading.Lock()


def get_tracer() -> Tracer:
    global _TRACER
    with _TRACER_LOCK:
        if _TRACER is None:
            _TRACER = Tracer()
        return _TRACER


def set_tracer(tracer: Tracer | None) -> None:
    global _TRACER
    with _TRACER_LOCK:
        _TRACER = tracer


def span(name: str, **kw):
    """``with span("tune"): ...`` — a span of the process-global tracer
    (:meth:`Tracer.span`): always the profiler annotation
    ``aam.<name>``, and a trace event when tracing is on."""
    return get_tracer().span(name, **kw)
