"""Layer: kernels.  The two group-by passes against HBM bandwidth: they
have to read the cube twice and write and read the climatology once (the
program module's ``segment_bytes_per_solve``, the same whatever implements
the passes), over ``segment_ms``."""


def read(ctx):
    t, p = ctx.trace, ctx.peaks
    fn = getattr(ctx.program, "segment_bytes_per_solve", None)
    if not (t and p and fn and t["class_s"].get("segment")):
        return None
    least = fn() / p["hbm_bytes_per_s"] / ctx.chips
    return 100.0 * least / (t["class_s"]["segment"] / t["solves"])
