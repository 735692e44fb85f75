"""Layer: kernels.  Device time per solve of the stencil kernel's ops (the
program module's ``stencil`` class of op names), averaged over the chips."""


def read(ctx):
    t = ctx.trace
    if not t or not t["class_s"].get("stencil"):
        return None
    return 1e3 * t["class_s"]["stencil"] / t["solves"]
