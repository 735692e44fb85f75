"""Layer: dispatch + ladder.  Per solve, span ``stages.dispatch``."""


def read(ctx):
    return ctx.stats.median(
        [s.stage_ms(lambda k: k == "dispatch") for s in ctx.solves])
