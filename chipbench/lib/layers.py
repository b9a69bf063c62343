"""Per-layer quantities of a traced window, shared by the metric readers."""
from __future__ import annotations

from chipbench.lib import hlo
from chipbench.lib.trace import is_collective

SCOPE = "aam_commit"


def per_round_ms(seconds: float | None, ctx) -> float | None:
    rounds = ctx.counters.get("rounds", 0)
    if seconds is None or rounds <= 0:
        return None
    return seconds / rounds * 1e3


def collective_s(ctx, device: int) -> float:
    return ctx.trace.busy_s(device, lambda o: is_collective(o.opcode))


def op_labels(ctx) -> dict:
    """``{(module, op): "<class>: <op_name>"}`` for the trace breakdown:
    whether each device op ran inside ``aam_commit`` (``in``), outside it
    (``out``) or fused across its boundary (``mixed``), and the scope it
    was traced under, from the optimized HLO of the programs that ran."""
    out = {}
    for text in ctx.hlo_texts:
        module = text.split(",", 1)[0].split()[1]
        for name, (_, cls, op_name) in hlo.classify(text, SCOPE).items():
            out[(module, name)] = f"{cls}: {op_name}" if op_name else cls
    return out
