"""Layer: kernels.  What a device hands to the group-by passes'
combination across chips in one solve (the program's counter
``segment.combine_bytes``: the partial sums of every group's slab where the
default layout splits the segment axis, and the scalar of the second
pass), median over the window's solves.  A program without the counter has
nothing to read."""


def read(ctx):
    per = [s.counters.get("segment.combine_bytes") for s in ctx.solves]
    if not any(v is not None for v in per):
        return None
    return ctx.stats.median([(v or 0) / 1e9 for v in per])
