"""Layer: device.  1 - (union of device op intervals over the traced
solves, averaged over the chips) / (the traced window)."""


def read(ctx):
    t = ctx.trace
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"]) if t else None
