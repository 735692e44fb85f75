"""Layer: host runtime.  Per solve, the pauses of Python's collector,
wherever in the solve they fell (annotation ``ramba.host.gc``, inside
whichever span was open): the program's counter ``host.gc.ns``, from one
``gc.callbacks`` entry.
Median over the window's solves of the solve's counter delta.  A program
without the counter has nothing to read."""

COUNTER = "host.gc.ns"


def read(ctx):
    if COUNTER not in ctx.program.rt.diagnostics.counters():
        return None
    return ctx.stats.median(
        [s.counters.get(COUNTER, 0) / 1e6 for s in ctx.solves])
