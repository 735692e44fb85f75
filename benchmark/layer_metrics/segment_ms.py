"""Layer: kernels.  Device time per solve of the two group-by passes (the
program module's ``segment`` class of op names), averaged over the chips."""


def read(ctx):
    t = ctx.trace
    if not t or not t["class_s"].get("segment"):
        return None
    return 1e3 * t["class_s"]["segment"] / t["solves"]
