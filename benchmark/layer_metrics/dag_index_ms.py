"""Layer: lazy DAG.  Per solve, the time lowering the indexes of
``__getitem__`` and ``__setitem__`` (``ndarray._classify_index``: the
ellipsis, the bounds, ``expr.encode_index``): the program's counter
``dag.index.ns``.
Median over the window's solves of the solve's counter delta.  A program
without the counter has nothing to read."""

COUNTER = "dag.index.ns"


def read(ctx):
    if COUNTER not in ctx.program.rt.diagnostics.counters():
        return None
    return ctx.stats.median(
        [s.counters.get(COUNTER, 0) / 1e6 for s in ctx.solves])
