"""Layer: trace + prepare.  Per solve, the span ``stages`` before compile
and dispatch (trace, prepare, verify, admit, ...): host clock self times."""


def read(ctx):
    later = ("compile", "dispatch", "device_execute", "write_back")
    return ctx.stats.median(
        [s.stage_ms(lambda k: k not in later) for s in ctx.solves])
