"""Layer: lazy DAG.  Per solve, the lazy nodes the script built
(``expr.Node``'s constructor, hit or miss of the inference memo): the
program's counter ``dag.node.n``.  A script that repeats itself reads the
same integer every solve.
Median over the window's solves of the solve's counter delta.  A program
without the counter has nothing to read."""

COUNTER = "dag.node.n"


def read(ctx):
    if COUNTER not in ctx.program.rt.diagnostics.counters():
        return None
    return ctx.stats.median(
        [s.counters.get(COUNTER, 0) for s in ctx.solves])
