"""Device time of collective ops (all-to-all, all-reduce, ...) per round
on the chip that spent most in them, in ms."""
from chipbench.lib import layers


def read(ctx):
    tr = ctx.trace
    if tr is None or not tr.ops:
        return None
    worst = max(layers.collective_s(ctx, d) for d in tr.devices)
    return layers.per_round_ms(worst, ctx) if worst > 0 else None
