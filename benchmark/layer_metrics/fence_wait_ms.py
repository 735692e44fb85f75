"""Layer: fence.  Per solve, span ``stages.device_execute``: the HOST's wait
at the fence after each compiled call, not device time."""


def read(ctx):
    return ctx.stats.median(
        [s.stage_ms(lambda k: k == "device_execute") for s in ctx.solves])
