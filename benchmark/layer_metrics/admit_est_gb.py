"""Layer: admit / memory.  The largest footprint admission estimated for a
program of the window (span ``mem_peak_est``: XLA's argument + output +
temporary bytes per device for the program lowered WITHOUT donation).  It
sees the temporaries inside a fused program, which the backend's
``peak_bytes_in_use`` (the line's ``device.memory_peak_bytes``) does not."""


def read(ctx):
    est = [f["mem_peak_est"] for s in ctx.solves for f in s.flushes
           if f.get("mem_peak_est")]
    return max(est) / 1e9 if est else None
