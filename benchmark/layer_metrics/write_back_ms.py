"""Layer: write-back + read.  Per solve, span ``stages.write_back``."""


def read(ctx):
    return ctx.stats.median(
        [s.stage_ms(lambda k: k == "write_back") for s in ctx.solves])
