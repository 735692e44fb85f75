"""Layer: lazy DAG.  Per solve, the time inside abstract evaluation at node
construction (``jax.eval_shape`` on a miss of the aval memo, and a Python
scalar's aval): the program's counter ``dag.infer.ns``, added up where the
work happens (``ramba_tpu/observe/profile.py`` ``span``).
Median over the window's solves of the solve's counter delta.  A program
without the counter has nothing to read."""

COUNTER = "dag.infer.ns"


def read(ctx):
    if COUNTER not in ctx.program.rt.diagnostics.counters():
        return None
    return ctx.stats.median(
        [s.counters.get(COUNTER, 0) / 1e6 for s in ctx.solves])
