"""Device time per round (BFS level) of the ops whose root runs under the
program's ``aam_commit`` scope, its nested ``aam_commit_stats``
included, on the busiest chip, in ms."""
from chipbench.lib import phases


def read(ctx):
    return phases.per_round_ms(ctx, phases.COMMIT_ALL)
