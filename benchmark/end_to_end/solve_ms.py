"""Median host-clock time of one solve in the window, ending in the
fetched value (which blocks)."""


def read(ctx):
    return ctx.stats.median([s.ms for s in ctx.solves])
