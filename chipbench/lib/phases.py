"""Device time of each phase of a round, named by the program's scopes.

The program names a round's phases with ``jax.named_scope``:
``aam_messages`` (building the round's messages), ``aam_commit`` (the
conflict-resolved write) and, nested in it, ``aam_commit_stats`` (the
success, conflict and applied bookkeeping).  A device op belongs to the
phase named in its root instruction's ``op_name`` in the optimized HLO
(the third field of :func:`chipbench.lib.hlo.classify`), so a fusion XLA
built across a scope's edge goes wholly to its root's phase.  In order:

* a root under ``aam_commit_stats`` is ``commit_stats``, and counts
  toward ``commit`` too;
* under ``aam_commit``, ``commit``;
* under ``aam_messages``, ``messages``;
* anything else, an op without ``op_name`` included, is ``other``.

A scope matches a whole path component of the ``op_name``, with
transform wrappers (``jvp(...)``) split off.  ``in``/``out``/``mixed``
(:mod:`chipbench.lib.hlo`) still label the breakdown.
"""
from __future__ import annotations

import re

from chipbench.lib import hlo, layers

MESSAGES_SCOPE = "aam_messages"
COMMIT_SCOPE = "aam_commit"
STATS_SCOPE = "aam_commit_stats"

MESSAGES, COMMIT, COMMIT_STATS, OTHER = (
    "messages", "commit", "commit_stats", "other")
# the phases each reported quantity sums
COMMIT_ALL = (COMMIT, COMMIT_STATS)


def components(op_name: str) -> list:
    return [c for c in re.split(r"[/()]", op_name) if c]


def phase_of(op_name: str) -> str:
    parts = components(op_name)
    if STATS_SCOPE in parts:
        return COMMIT_STATS
    if COMMIT_SCOPE in parts:
        return COMMIT
    if MESSAGES_SCOPE in parts:
        return MESSAGES
    return OTHER


def op_phases(hlo_texts) -> dict:
    """``{(module, instruction): phase}`` for every instruction of the
    programs that can run as a device op."""
    out = {}
    for text in hlo_texts:
        module = text.split(",", 1)[0].split()[1]
        for name, (_, _, op_name) in hlo.classify(text,
                                                  COMMIT_SCOPE).items():
            out[(module, name)] = phase_of(op_name)
    return out


def phase_s(ctx, device: int, phases, table: dict | None = None) -> float:
    """Seconds of the window in which an op of one of ``phases`` ran on
    ``device``; an op the programs' HLO does not name is ``other``."""
    table = op_phases(ctx.hlo_texts) if table is None else table
    return ctx.trace.busy_s(
        device, lambda o: table.get((o.module, o.name), OTHER) in phases)


def split_s(ctx, device: int) -> dict:
    """Seconds of each phase on ``device``: ``messages``, ``commit``
    (``commit_stats`` included), ``commit_stats`` and ``other``."""
    table = op_phases(ctx.hlo_texts)
    return {MESSAGES: phase_s(ctx, device, (MESSAGES,), table),
            COMMIT: phase_s(ctx, device, COMMIT_ALL, table),
            COMMIT_STATS: phase_s(ctx, device, (COMMIT_STATS,), table),
            OTHER: phase_s(ctx, device, (OTHER,), table)}


def per_round_ms(ctx, phases) -> float | None:
    """Device time of ``phases`` per round on the busiest chip, in ms;
    ``None`` when the window holds no op of them (a program without the
    scopes)."""
    tr = ctx.trace
    if tr is None or not tr.ops or not ctx.hlo_texts:
        return None
    seconds = phase_s(ctx, tr.busiest(), phases)
    return layers.per_round_ms(seconds, ctx) if seconds > 0 else None
