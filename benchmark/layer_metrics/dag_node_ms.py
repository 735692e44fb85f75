"""Layer: lazy DAG.  Per solve, the time inside ``expr.Node``'s
constructor, which is ``infer_aval`` whole: the memo's key, the lookup,
and ``jax.eval_shape`` on a miss (``dag_infer_ms`` is that miss alone, so
the hit path is this less that): the program's counter ``dag.node.ns``.
Median over the window's solves of the solve's counter delta.  A program
without the counter has nothing to read."""

COUNTER = "dag.node.ns"


def read(ctx):
    if COUNTER not in ctx.program.rt.diagnostics.counters():
        return None
    return ctx.stats.median(
        [s.counters.get(COUNTER, 0) / 1e6 for s in ctx.solves])
