"""Bytes the algorithm has to move for the solves completed in the window
(the program module's convention, a function of the shapes) over the
window's seconds, over the chips.  Divided by the chip's HBM peak it is the
end-to-end share of the bandwidth roofline, and it puts cells of different
order or chip count side by side.  It is the window's mean rate: unlike
``solve_ms``, the median, it sees the time between solves and the slow
solves of the tail."""


def read(ctx):
    per_solve = ctx.program.algo_bytes_per_solve()
    if not per_solve or not ctx.solves:
        return None
    return per_solve * len(ctx.solves) / ctx.window_s / ctx.chips / 1e9
