"""Layer: kernels.  The stencil kernel alone against HBM bandwidth: it has
to read A and write its output (the program module's
``stencil_bytes_per_solve``), over ``stencil_ms``."""


def read(ctx):
    t, p = ctx.trace, ctx.peaks
    fn = getattr(ctx.program, "stencil_bytes_per_solve", None)
    if not (t and p and fn and t["class_s"].get("stencil")):
        return None
    least = fn() / p["hbm_bytes_per_s"] / ctx.chips
    return 100.0 * least / (t["class_s"]["stencil"] / t["solves"])
