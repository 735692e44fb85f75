"""Layer: compile.  The process's first solve: tracing, lowering (Mosaic
lowers before jax's cache has a key to look up), the admission estimate's
extra compile, and the executable from jax's persistent cache.  Part of
``setup_s``."""


def read(ctx):
    return ctx.warmup[0].ms if ctx.warmup else None
