"""Layer: compile.  Cuts per solve (median over the window): the flush
span's ``segments``, its chained jit calls less one, summed over a solve's
flushes.  A flush whose program is longer than
``common.max_program_instrs`` runs as chained calls, each a launch the host
makes, and what crosses a cut is stored and read back, where XLA cannot
fuse; 0 for a flush that is one program.  A program without the span key
has nothing to read."""


def read(ctx):
    per_solve = [sum(f["segments"] for f in s.flushes if "segments" in f)
                 for s in ctx.solves
                 if any("segments" in f for f in s.flushes)]
    return ctx.stats.median(per_solve) if per_solve else None
