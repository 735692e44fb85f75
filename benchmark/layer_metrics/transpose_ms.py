"""Layer: kernels.  Device time per solve of the update that reads the
exchanged block transposed (the program module's ``transpose`` class of op
names), averaged over the chips."""


def read(ctx):
    t = ctx.trace
    if not t or not t["class_s"].get("transpose"):
        return None
    return 1e3 * t["class_s"]["transpose"] / t["solves"]
