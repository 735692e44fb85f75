"""Layer: lazy DAG.  Per solve, the host-clock time outside every flush
span (solve time minus the spans' ``wall_s``): building the DAG, slicing,
the conversion of the fetched value, the harness's own bookkeeping."""


def read(ctx):
    return ctx.stats.median([
        s.ms - 1e3 * sum(f.get("wall_s", 0.0) for f in s.flushes)
        for s in ctx.solves])
