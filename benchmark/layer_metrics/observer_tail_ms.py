"""Layer: observers.  Per solve, the program's own bookkeeping after a flush
span's ``wall_s`` is stamped (``finalize_span``, ``events.emit``, the ledger, SLO
and progress observers): the program's counter ``observe.tail.ns``.
Median over the window's solves of the solve's counter delta.  A program
without the counter has nothing to read."""

COUNTER = "observe.tail.ns"


def read(ctx):
    if COUNTER not in ctx.program.rt.diagnostics.counters():
        return None
    return ctx.stats.median(
        [s.counters.get(COUNTER, 0) / 1e6 for s in ctx.solves])
