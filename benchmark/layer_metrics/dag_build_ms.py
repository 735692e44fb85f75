"""Layer: lazy DAG.  Per solve, the host's time from a stream's first
pending node to the flush that collects it (annotation
``ramba.dag.build``): the script and the lazy layer under it, the
collector's pauses that fell there included.  The program's counter
``dag.build.ns``.  A node built with nothing pending inside a read
(``float(D[i])``) opens no phase.
Median over the window's solves of the solve's counter delta.  A program
without the counter has nothing to read."""

COUNTER = "dag.build.ns"


def read(ctx):
    if COUNTER not in ctx.program.rt.diagnostics.counters():
        return None
    return ctx.stats.median(
        [s.counters.get(COUNTER, 0) / 1e6 for s in ctx.solves])
