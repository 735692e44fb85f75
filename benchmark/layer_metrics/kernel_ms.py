"""Layer: kernels.  Device time per solve of every compute op in the
profiler trace (collectives apart), averaged over the chips."""


def read(ctx):
    t = ctx.trace
    return 1e3 * t["compute_s"] / t["solves"] if t else None
