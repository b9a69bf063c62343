"""Coalescing sub-rounds per round: ``DistributedResult.subrounds`` over
``rounds``, summed over the window's searches."""


def read(ctx):
    c = ctx.counters
    return c["subrounds"] / c["rounds"] if c.get("rounds") else None
