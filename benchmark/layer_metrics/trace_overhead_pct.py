"""Layer: harness.  Median solve under the profiler over the median solve
of the same process's untraced window, minus 1: how far to trust the
traced numbers."""


def read(ctx):
    if not ctx.traced or not ctx.solves:
        return None
    med = ctx.stats.median
    return 100.0 * (med([s.ms for s in ctx.traced])
                    / med([s.ms for s in ctx.solves]) - 1.0)
