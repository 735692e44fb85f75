"""Layer: collectives.  Device time per solve of the collective ops
themselves in the trace, averaged over the chips.  The core runs its ops
in sequence, so this is time in which it computes nothing: issuing a
transfer, or waiting in a ``-done`` op for one that a kernel did not hide.
None on one chip."""


def read(ctx):
    t = ctx.trace
    if not t or ctx.chips < 2:
        return None
    return 1e3 * t["collective_s"] / t["solves"]
