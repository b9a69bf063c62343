"""Device time per round (BFS level) of the ops whose root runs under
the program's ``aam_commit_stats`` scope (the commit's success,
conflict and applied bookkeeping), on the busiest chip, in ms."""
from chipbench.lib import phases


def read(ctx):
    return phases.per_round_ms(ctx, (phases.COMMIT_STATS,))
