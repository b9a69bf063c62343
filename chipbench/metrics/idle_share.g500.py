"""Share of the traced window in which no op ran on the chip, in %."""


def read(ctx):
    tr = ctx.trace
    return 100.0 * tr.idle_share(tr.busiest()) if tr and tr.ops else None
