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

Pinning a layout takes a concrete sharding.  On one device there is one.
Under a mesh the system chooses it, by the same rule of rank: a flush puts
every result of rank three or more that is large enough to distribute in
its default layout (``mesh.held_spec``: the solver's split, or where that
does not divide the extents it splits the split that does; left to GSPMD a
cube of 1,462 days came out as two halves held twice and its climatology
whole on every device), and with that sharding known keeps it row-major
on every device as on one, where the tiles waste under an eighth.  Ranks
one and two stay GSPMD's and the compiler's, as on one device (there a
``distribution=`` argument or a sharding hint travels with the values
through GSPMD's propagation, which a pin would cut short, and the
compiler's layout is row-major for every shape the benchmark's other
cells hold); so does a result too small to distribute or of a shape no
split of the mesh divides (jax holds no such array).  A result of rank
three goes to the default layout whatever a hint upstream asked.

A program with a pinned LAYOUT is compiled in this process, never loaded
from a persistent cache, jax's or ``compile/persist.py``'s: an executable
deserialized by jaxlib 0.9 does not say which layout its results have, the
arrays it makes report the default, and the next program is lowered for
the wrong one (PERF.md section 6, PR 30: "expected parameter of size
12226314240 ... but got buffer 13069615104").  So it is compiled once per
signature (``lower().compile()``, the executable kept and called), on the
compiling thread alone with jax's cache write threshold out of reach and
its key made from the module's metadata as well, which no entry written
without it can match; and ``compile/persist.py`` neither stores nor
serves a program ``pins`` says is pinned.  A result whose sharding alone
is chosen (rank three under a mesh, tiles that would waste an eighth or
more) is an ordinary jit with ``out_shardings``: an executable says where
its results are, and the caches serve it.

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


def _pin(aval, mesh):
    """What a flush asks of the result ``aval`` (anything with a shape
    and a dtype) on ``mesh``: None (nothing: ranks one and two, and what
    is not distributed), a sharding (rank three or more on several
    devices: the default layout), or that sharding with the row-major
    layout (tiles that waste under an eighth, on one device and on
    several)."""
    from jax.sharding import NamedSharding, PartitionSpec

    shape = tuple(getattr(aval, "shape", ()))
    row_major = keeps_row_major(aval)
    if mesh.devices.size == 1:
        spec = PartitionSpec() if row_major else None
    else:
        spec = _mesh.held_spec(shape, mesh) if len(shape) >= 3 else None
    if spec is None:
        return None
    where = NamedSharding(mesh, spec)
    if not row_major:
        return where
    from jax.experimental.layout import Format, Layout

    return Format(Layout(major_to_minor=tuple(range(len(shape)))), where)


def _pins_layout(formats) -> bool:
    from jax.experimental.layout import Format

    return any(isinstance(f, Format) for f in formats)


def pins(results) -> bool:
    """Whether a program with these results (anything with a shape and a
    dtype) has a pinned layout on the mesh as it stands, and so is
    compiled in every process."""
    mesh = _mesh.get_mesh()
    return _pins_layout(_pin(o, mesh) for o in results)


def result_formats(plain, args):
    """``out_shardings`` that keep ``plain``'s results where the module's
    text says, or None where no result asks for anything.  ``plain`` is
    the jitted callable: its own ``eval_shape`` traces once and the
    lowering that follows reuses the trace."""
    mesh = _mesh.get_mesh()
    formats = tuple(_pin(o, mesh) for o in plain.eval_shape(*args))
    return formats if any(f is not None for f in formats) else None


def _format(v):
    try:
        return v.format  # a jax array's layout and sharding
    except Exception:
        return None


class _Pinned:
    """A jit with results of a pinned layout as executables, one per
    layout of the arguments, each compiled once and outside jax's
    persistent cache (thread-local: a compile on another thread keeps its
    cache)."""

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
    """``jax.jit(fun, donate_argnums=donate)`` whose results lie where the
    system wants them: rank three or more row-major on the device and,
    under a mesh, in the default layout (the module's docstring says why
    and when).  A call costs one dictionary lookup more than the plain jit; a
    signature none of whose results is pinned is served by the plain jit
    itself, one with shardings alone by a jit with ``out_shardings``."""

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
            if formats is None:
                fn = self._plain
            elif _pins_layout(formats):
                fn = _Pinned(self._fun, self._donate, formats)
            else:
                fn = jax.jit(self._fun, donate_argnums=self._donate,
                             out_shardings=formats)
            self._by_signature[sig] = fn
        return fn

    def pins(self, *args) -> bool:
        """Whether this signature's program has a pinned layout."""
        return isinstance(self._jit_for(args), _Pinned)

    def __call__(self, *args):
        return self._jit_for(args)(*args)

    def lower(self, *args):
        return self._jit_for(args).lower(*args)
