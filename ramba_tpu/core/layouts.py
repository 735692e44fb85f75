"""Which layout a resident array has on the device: the system's choice,
not the compiler's, where it matters.

XLA:TPU lays out a program's results, and the arguments that carry no
layout of their own, to waste the least padding: it tiles (8, 128)
whichever two dimensions pad least and makes those the minor ones.  A
``(2922, 721, 1440)`` float32 cube comes out with TIME minor (721 and
1440 pad to 728 and 1536, 7.7 %; 1440 and 2922 pad by 0.75 %), and every
later program receives it so.  Nothing that walks an array along its
first axis survives that: a slab ``x[t]`` is one lane of every tile, and
XLA answers a loop of such slices with a transposed copy of the whole
operand (PERF.md section 6, PR 30: 25.08 GB asked of 15.75).  NumPy's
arrays are row-major, and this system's sharding, slicing and group-by
all treat axis 0 as the slow one; so a result of rank three or more is
kept row-major where the tiles of its last two dimensions waste under an
eighth, and left to the compiler where they would waste more (a
``(n, 3, 3)`` array tiled row-major is a hundred times its size).  jax
lowers a later program for the layout its argument has, so nothing else
needs to know.

One device only: pinning a layout takes a concrete sharding, and under a
mesh the results' shardings are GSPMD's to choose.  Arrays of rank one and
two are left alone (the compiler's choice is row-major for every shape the
benchmark's other cells hold).

A pinned program is compiled in this process, never loaded from a
persistent cache, jax's or ``compile/persist.py``'s: an executable
deserialized by jaxlib 0.9 does not say which layout its results have, the
arrays it makes report the default, and the next program is lowered for
the wrong one (PERF.md section 6, PR 30: "expected parameter of size
12226314240 ... but got buffer 13069615104").  So it is compiled once per
signature (``lower().compile()``, the executable kept and called), on the
compiling thread alone with jax's cache write threshold out of reach and
its key made from the module's metadata as well, which no entry written
without it can match; and ``compile/persist.py`` neither stores nor
serves a program ``pins`` says is pinned.

``RowMajorJit`` is the one place a flush's program becomes a jit: the
fuser's compile, admission's estimate (``resilience/memory.py``), the
memory report and the AOT lane's own compile and fallback all build it
here, so all of them see one layout decision.
"""

from __future__ import annotations

import jax
from jax._src import config as _jax_config

from ramba_tpu.parallel import mesh as _mesh


def keeps_row_major(aval) -> bool:
    shape = getattr(aval, "shape", ())
    if len(shape) < 3:
        return False
    rows = 8 * max(1, 4 // max(1, jax.numpy.dtype(aval.dtype).itemsize))
    tiled = (-(-shape[-2] // rows) * rows) * (-(-shape[-1] // 128) * 128)
    return 0 < tiled * 8 <= shape[-2] * shape[-1] * 9


def pins(results) -> bool:
    """Whether a program with these results (anything with a shape and a
    dtype) is a pinned one on the mesh as it stands."""
    return (_mesh.get_mesh().devices.size == 1
            and any(keeps_row_major(o) for o in results))


def result_formats(plain, args):
    """``out_shardings`` that keep ``plain``'s results row-major, or None
    where no result asks for it (or the mesh has several devices).
    ``plain`` is the jitted callable: its own ``eval_shape`` traces once
    and the lowering that follows reuses the trace."""
    if _mesh.get_mesh().devices.size != 1:
        return None  # before the trace: under a mesh nothing is asked
    outs = plain.eval_shape(*args)
    if not pins(outs):
        return None
    from jax.experimental.layout import Format, Layout
    from jax.sharding import NamedSharding, PartitionSpec

    here = NamedSharding(_mesh.get_mesh(), PartitionSpec())
    return tuple(
        Format(Layout(major_to_minor=tuple(range(len(o.shape)))), here)
        if keeps_row_major(o) else None for o in outs)


def _format(v):
    try:
        return v.format  # a jax array's layout and sharding
    except Exception:
        return None


class _Pinned:
    """A jit with row-major results as executables, one per layout of
    the arguments, each compiled once and outside jax's persistent
    cache (thread-local: a compile on another thread keeps its cache)."""

    def __init__(self, fun, donate, formats):
        self._jit = jax.jit(fun, donate_argnums=donate, out_shardings=formats)
        self._compiled = {}

    def __call__(self, *args):
        key = tuple(_format(v) for v in args)
        run = self._compiled.get(key)
        if run is None:
            with _jax_config.persistent_cache_min_compile_time_secs(
                    float("inf")), \
                    _jax_config.compilation_cache_include_metadata_in_key(
                        True):
                run = self._compiled[key] = self._jit.lower(*args).compile()
        return run(*args)

    def lower(self, *args):
        return self._jit.lower(*args)


class RowMajorJit:
    """``jax.jit(fun, donate_argnums=donate)`` whose results of rank three
    or more stay row-major on the device (the module's docstring says
    why and when).  A call costs one dictionary lookup more than the
    plain jit; a signature none of whose results is pinned is served by
    the plain jit itself."""

    def __init__(self, fun, donate=()):
        self._fun, self._donate = fun, tuple(donate)
        self._plain = jax.jit(fun, donate_argnums=self._donate)
        self._by_signature = {}

    def _jit_for(self, args):
        sig = tuple((v.shape, v.dtype, getattr(v, "weak_type", False))
                    if hasattr(v, "shape") and hasattr(v, "dtype")
                    else type(v) for v in args)
        fn = self._by_signature.get(sig)
        if fn is None:
            formats = result_formats(self._plain, args)
            fn = self._plain if formats is None else _Pinned(
                self._fun, self._donate, formats)
            self._by_signature[sig] = fn
        return fn

    def pins(self, *args) -> bool:
        return self._jit_for(args) is not self._plain

    def __call__(self, *args):
        return self._jit_for(args)(*args)

    def lower(self, *args):
        return self._jit_for(args).lower(*args)
