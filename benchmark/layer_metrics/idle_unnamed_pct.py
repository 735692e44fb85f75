"""Layer: device.  Of the device's idle time inside the traced solves
(the window less the busy time, less ``(between solves)``), the share that
none of the program's own annotations carries: a gap counts as named when
its frame or that frame's child is a ``ramba.*`` name.  Read from the
stretch WITHOUT the Python tracer, whose host line holds the program's
annotations at the host's own speed; ``(no host frame)``, ``(shorter gaps,
not named)``, jax's own frames and what the list of ten cut off are
unnamed.  Nothing to read without a trace."""

BETWEEN = "(between solves)"


def read(ctx):
    t = ctx.trace
    if not t:
        return None
    gaps = dict(t["idle_gaps"])
    idle = t["window_s"] - t["busy_s"] - gaps.get(BETWEEN, 0.0)
    if idle <= 0:
        return 0.0
    named = sum(s for label, s in gaps.items()
                if any(part.startswith("ramba.")
                       for part in label.split(" > ")))
    return 100.0 * (1.0 - named / idle)
