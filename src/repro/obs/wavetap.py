"""Per-round wave telemetry fed from INSIDE jitted round loops.

The paper's adaptive story runs on signals that only exist device-side
mid-loop: per-round conflicts, commit density, the ladder level M.
``wavetap`` streams them to the host with
``jax.experimental.io_callback``:

* :func:`tap_commit_step` wraps the ``step`` returned by
  ``repro.core.autotune.make_commit_step`` — one ordered callback per
  commit (all six single-shard loops and the ``ProductWave`` chunk
  bodies route through that one hook);
* :func:`round_recorder` is the engine ``_Runner`` tap — one unordered
  callback per round per shard (unordered: multi-device shard_map must
  not serialize on the host; the round index rides in the payload).

Records accumulate in a process-global :class:`Collector`;
:func:`flush_to` converts them into Chrome trace instants on the device
tid, each at the host time its callback arrived (the callback says
when a round's numbers reached the host, not how long the round ran on
the device: the device's own timeline is the ``jax.profiler`` trace),
and :func:`summary` reduces them to the per-row bench fields
(rounds, mean commit density, ladder moves).

The tap only enters a jaxpr when tracing was enabled AT TRACE TIME
(``CommitSpec(trace=True)`` or ``REPRO_TRACE=1``) — with tracing off
the wrapped step is returned untouched, and
``aamlint --trace-off-clean`` proves the shipped jaxprs are clean.
"""
from __future__ import annotations

import threading
import time

import jax.numpy as jnp
from jax.experimental import io_callback

from repro.obs import trace as _trace


class Collector:
    """Append-only record sink (io_callback may fire from runtime
    threads; everything is lock-guarded)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._records: list[dict] = []

    def add(self, rec: dict) -> None:
        with self._lock:
            self._records.append(rec)

    def records(self) -> list[dict]:
        with self._lock:
            return list(self._records)

    def drain(self) -> list[dict]:
        with self._lock:
            out, self._records = self._records, []
            return out

    def clear(self) -> None:
        with self._lock:
            self._records.clear()


_COLLECTOR = Collector()


def collector() -> Collector:
    return _COLLECTOR


def records() -> list[dict]:
    return _COLLECTOR.records()


def clear() -> None:
    _COLLECTOR.clear()


# -- device-side taps ---------------------------------------------------


def commit_recorder(label: str, op: str, backend: str):
    """Host callback for one commit stream."""
    def cb(conflicts, applied, messages, level):
        _COLLECTOR.add({
            "kind": "commit", "label": label, "op": op,
            "backend": backend, "t": time.perf_counter(),
            "conflicts": int(conflicts), "applied": int(applied),
            "messages": int(messages), "level": int(level)})
    return cb


def round_recorder(label: str):
    """Host callback for the engine's per-round stream."""
    def cb(it, conflicts, subrounds, messages, level, shard):
        _COLLECTOR.add({
            "kind": "round", "label": label, "t": time.perf_counter(),
            "round": int(it), "conflicts": int(conflicts),
            "subrounds": int(subrounds), "messages": int(messages),
            "level": int(level), "shard": int(shard)})
    return cb


def tap_commit_step(step, *, label: str, op: str, backend: str):
    """Wrap a ``make_commit_step`` step with the commit tap.

    Ordered: the single-shard loops run one commit stream, and ordering
    keeps the ladder-level sequence faithful."""
    cb = commit_recorder(label, op, backend)

    def traced_step(state, msgs, level):
        res, lvl = step(state, msgs, level)
        io_callback(cb, None, res.conflicts, res.applied,
                    jnp.sum(msgs.valid.astype(jnp.int32)), lvl,
                    ordered=True)
        return res, lvl

    return traced_step


# -- host-side reductions -----------------------------------------------


def summary(recs: list[dict] | None = None) -> dict:
    """Reduce records to the bench-row trace fields.

    rounds:       engine round records (shard 0) if any, else the
                  number of commits (one commit per round in the
                  single-shard loops);
    mean_density: mean conflicts/messages over commit+round records
                  with routed messages;
    ladder_moves: level changes between consecutive records of the
                  same stream (label);
    commits:      commit records seen.
    """
    recs = _COLLECTOR.records() if recs is None else recs
    rounds = sum(1 for r in recs
                 if r["kind"] == "round" and r.get("shard", 0) == 0)
    commits = sum(1 for r in recs if r["kind"] == "commit")
    dens = [r["conflicts"] / r["messages"] for r in recs
            if r.get("messages", 0) > 0]
    moves, last = 0, {}
    for r in recs:
        key = (r["kind"], r["label"])
        if key in last and r["level"] != last[key]:
            moves += 1
        last[key] = r["level"]
    return {"rounds": rounds if rounds else commits,
            "commits": commits,
            "mean_density": round(sum(dens) / len(dens), 4) if dens
            else 0.0,
            "ladder_moves": moves}


def flush_to(tracer, tid: int = _trace.TID_DEVICE) -> int:
    """Drain the collector into ``tracer`` as device-tid instants, one
    per record at its host arrival time ``t``, carrying the record's
    fields as args; returns the number of records flushed."""
    recs = _COLLECTOR.drain()
    if not tracer.active:
        return len(recs)
    for r in recs:
        args = {k: v for k, v in r.items()
                if k not in ("kind", "label", "t")}
        name = (f"round[{r['label']}]" if r["kind"] == "round"
                else f"commit[{r['label']}]")
        tracer.instant(name, cat=r["kind"], tid=tid, ts=r["t"], args=args)
    return len(recs)
