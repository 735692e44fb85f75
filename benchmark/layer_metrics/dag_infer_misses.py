"""Layer: lazy DAG.  Per solve, the abstract evaluations made while the DAG
is built (misses of the aval memo and Python scalars): the program's
counter ``dag.infer.n``.
Median over the window's solves of the solve's counter delta.  A program
without the counter has nothing to read."""

COUNTER = "dag.infer.n"


def read(ctx):
    if COUNTER not in ctx.program.rt.diagnostics.counters():
        return None
    return ctx.stats.median(
        [s.counters.get(COUNTER, 0) for s in ctx.solves])
