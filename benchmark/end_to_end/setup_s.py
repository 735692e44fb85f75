"""Process start (the first statement of run.py) to the first timed
solve: imports, backend bring-up, resident arrays built on the device, and
the warm-up of this cell's own programs.  Host clock."""


def read(ctx):
    return ctx.setup_s
