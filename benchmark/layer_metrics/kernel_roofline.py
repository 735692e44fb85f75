"""Layer: kernels.  The least time the chip could take for one solve (the
larger of the algorithm's bytes over the HBM peak and its flops over the
flops peak, per chip; which one is on the line ``benchmark: roofline``)
over ``kernel_ms``."""


def read(ctx):
    t, p = ctx.trace, ctx.peaks
    nbytes = ctx.program.algo_bytes_per_solve()
    if not (t and p and nbytes and t["compute_s"]):
        return None
    flops = ctx.program.algo_flops_per_solve() or 0
    least = max(nbytes / p["hbm_bytes_per_s"], flops / p["flops_per_s"])
    return 100.0 * least / ctx.chips / (t["compute_s"] / t["solves"])
