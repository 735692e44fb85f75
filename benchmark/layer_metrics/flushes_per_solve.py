"""Layer: lazy DAG.  Flush spans per solve (median over the window), from
the event tap."""


def read(ctx):
    return ctx.stats.median([len(s.flushes) for s in ctx.solves])
