"""Layer: write-back + read.  Per solve, the time of the reads
(``ndarray.asarray``: device-to-host copy and NumPy's conversion, after the
flush under it has returned): the program's counter ``read.ns``.
Median over the window's solves of the solve's counter delta.  A program
without the counter has nothing to read."""

COUNTER = "read.ns"


def read(ctx):
    if COUNTER not in ctx.program.rt.diagnostics.counters():
        return None
    return ctx.stats.median(
        [s.counters.get(COUNTER, 0) / 1e6 for s in ctx.solves])
