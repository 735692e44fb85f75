"""Layer: compile.  Flush spans with ``cache == "miss"`` inside the window
and the first traced stretch.  Expected 0."""


def read(ctx):
    return sum(1 for s in ctx.solves + ctx.traced for f in s.flushes
               if f.get("cache") == "miss")
