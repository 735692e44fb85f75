"""Layer: collectives.  What one device sends to another in the
transpose's swaps of one solve (the program's counter
``transpose.exchange_bytes``: an off-diagonal device's block, once a
swap), median over the window's solves.  A program without the counter
has nothing to read."""


def read(ctx):
    per = [s.counters.get("transpose.exchange_bytes") for s in ctx.solves]
    if not any(v is not None for v in per):
        return None
    return ctx.stats.median([(v or 0) / 1e9 for v in per])
