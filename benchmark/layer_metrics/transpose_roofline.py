"""Layer: kernels.  The transposition and the update it feeds against HBM
bandwidth: they have to read the received block and read and write B's
block once an iteration (the program module's
``transpose_bytes_per_solve``, the same whatever implements them), over
``transpose_ms``."""


def read(ctx):
    t, p = ctx.trace, ctx.peaks
    fn = getattr(ctx.program, "transpose_bytes_per_solve", None)
    if not (t and p and fn and t["class_s"].get("transpose")):
        return None
    least = fn() / p["hbm_bytes_per_s"] / ctx.chips
    return 100.0 * least / (t["class_s"]["transpose"] / t["solves"])
