"""Messages that did work over messages built, in %: the program's
``BfsResult.messages`` (frontier edges) over rounds x edges (every round
builds one message per edge)."""


def read(ctx):
    c = ctx.counters
    built = c["rounds"] * c["edges"]
    return 100.0 * c["messages"] / built if built and "messages" in c \
        else None
