"""Device time per PageRank iteration of the ops whose root runs under the
program's ``aam_messages`` scope (building the round's messages), on
the busiest chip, in ms."""
from chipbench.lib import phases


def read(ctx):
    return phases.per_round_ms(ctx, (phases.MESSAGES,))
