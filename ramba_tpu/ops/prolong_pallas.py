"""A Pallas TPU kernel for the trilinear prolongation of a structured-grid
solver (NPB MG's ``interp``) on one chip: the fine array written once.

The script is five writes onto zeros: ``f[0::2, 0::2, 0::2] = z[:-1, :-1,
:-1]``, then along each axis in turn ``f[1:-1] = f[1:-1] + 0.5 * (f[2:] +
f[:-2])`` (``core/rewrite.py`` ``fold_prolong`` makes them one node).  As
XLA lowers them each is a pass over the fine array, with pads, slices and
``dynamic_update_slice`` between: about 32 ms at 514^3 where writing the
array once takes 0.84 (PERF.md section 5).

Here a grid step reads coarse planes ``g`` and ``g + 1`` and writes the
fine planes ``2 g`` and ``2 g + 1``, a row tile of eight coarse rows at a
time: the four classes of a fine plane (a row on a coarse row or
between two, a lane likewise) are computed where the coarse values lie, and
laid between each other by ``tpu.dynamic_gather`` inside a vreg, first the
lanes and then the rows.  The arithmetic is the script's, in its order, so
every bit is the five writes' (``tests/test_prolong.py``): along an axis, a
point on a coarse point gains ``0.5 * (0 + 0)``, which turns -0 into +0 as
the script does, a point between two becomes ``0 + 0.5 * (up + down)``,
the first keeps its value and the last is the zero the script never writes
(the upper ghost layer, which ``comm3`` refreshes).  That zero is an
operand the kernel reads, as the script's is the array's: Mosaic folds
``x + 0.0`` into ``x`` (on the chip, -0 came out where the writes give +0:
``scripts/tpu_slicing_sweep.py prolong``, PR 38).

The blocks are whole tiles: the coarse operand's planes as (8, 128) tiles
cover them, and the fine result's rows as two per coarse row, so every read
and write is a whole vreg.  On the chip what lies past the array's edge in
a block is padding, and a block reaching past the array is written back up
to its edge; the interpreter refuses such blocks, so off the chip the
kernel is not offered and the suite's fixture (``tests/conftest.py``
``interpreting_prolong``) hands it a copy padded to whole blocks.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ramba_tpu.ops import pallas_backend as _pallas_backend
from ramba_tpu.ops import stencil_pallas as _stencil

#: the smallest fine extent the kernel takes: under it the five writes are
#: a few launches and a kernel is one more shape to trace, lower and compile
#: in every process (four such shapes of the ghost-layer walk: 4 s of set-up
#: for nothing that shows, PERF.md section 6, PR 35).  From the chip's
#: reading of both paths, ``scripts/tpu_slicing_sweep.py prolong``
MIN_EXTENT = 130
# The suite's switch (``interpreting_prolong``): offer the kernel off the
# chip and interpret it.
_INTERPRET = False


def _tiles(shape):
    """(row tiles, lane columns) of a coarse plane, and the fine block's
    rows and lanes: two fine rows a coarse row, whole lane tiles."""
    nt, ncol = -(-shape[1] // 8), -(-shape[2] // 128)
    return nt, ncol, 16 * nt, _stencil._round_up(2 * shape[2] - 2, 128)


def vmem_bytes(shape):
    """What a grid step asks of VMEM over a coarse ``(C0, C1, C2)`` array
    of four-byte elements: two coarse planes and two fine ones, each
    double-buffered.  A step of one coarse plane: five of them a step at
    258^3 read as one (1.198 ms against 1.209) and at 130^3 slower
    (0.313 against 0.283; ``scripts/tpu_slicing_sweep.py prolong``, PR
    38)."""
    nt, ncol, fr, fl = _tiles(shape)
    return 2 * 4 * (2 * 8 * nt * 128 * ncol + 2 * fr * fl) \
        + _stencil._VMEM_SLACK


def available(fine_shape, dtype) -> bool:
    """Whether the kernel takes a prolongation onto ``fine_shape``: Pallas
    enabled, a chip attached (or the suite's switch), rank 3, float32,
    every fine extent at least ``MIN_EXTENT``, and a grid step inside the
    VMEM a kernel may ask for."""
    if not _stencil._ENABLED:
        return False
    if _pallas_backend.interpret_mode() and not _INTERPRET:
        return False
    if (len(fine_shape) != 3 or jnp.dtype(dtype) != jnp.float32
            or min(fine_shape) < MIN_EXTENT):
        return False
    return vmem_bytes(tuple(n // 2 + 1 for n in fine_shape)) \
        <= _stencil._vmem_cap()


def interpreting():
    """What ``pallas_call`` is told: the chip compiles, anything else
    interprets."""
    return _INTERPRET or _pallas_backend.interpret_mode()


def prolong(z, interpret):
    """The fine ``(2 C0 - 2, 2 C1 - 2, 2 C2 - 2)`` array of the five writes
    from the coarse ``z``.  One jitted function per statics, as
    ``faces_pallas.wrap``: the twenty prolongations of a level trace and
    lower one kernel."""
    fine = tuple(2 * n - 2 for n in z.shape)
    return _prolong_jit(tuple(z.shape), fine, interpret)(z)


@functools.lru_cache(maxsize=64)
def _prolong_jit(*static):
    def ramba_prolong(z):
        return _prolong_call(*static, z)

    return jax.jit(ramba_prolong)


def _prolong_call(shape, fine, interpret, z):
    """``shape`` is the coarse array's and ``fine`` the result's; ``z``
    and ``fine`` may be larger (the suite's copy padded to whole blocks).
    Grid step ``g`` reads coarse planes ``g`` and ``g + 1`` and writes fine
    planes ``2 g`` and ``2 g + 1``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    c0, c1, c2 = shape
    nt, ncol, fr, fl = _tiles(shape)
    halves = [(c, h) for c in range(ncol) for h in (0, 1)
              if 256 * c + 128 * h < 2 * c2 - 2]

    def kernel(zero_ref, here_ref, next_ref, out_ref):
        sub = lax.broadcasted_iota(jnp.int32, (8, 128), 0)
        lane = lax.broadcasted_iota(jnp.int32, (8, 128), 1)
        zero = zero_ref[...]
        lanes_at = [lane // 2 + 64 * h for h in (0, 1)]
        rows_at = [sub // 2 + 4 * u for u in (0, 1)]
        g = pl.program_id(0)

        def spread(even, odd, half, axis):
            """``even`` in the even places of the axis, ``odd`` between:
            the values of the ``half``-th half of the vreg along it."""
            at, pos = (lanes_at, lane) if axis == 1 else (rows_at, sub)
            return jnp.where(pos % 2 == 1,
                             jnp.take_along_axis(odd, at[half], axis,
                                                 mode="promise_in_bounds"),
                             jnp.take_along_axis(even, at[half], axis,
                                                 mode="promise_in_bounds"))

        def one_axis(x, up, at, first, last):
            """The script's pass along one axis, at the coarse points
            ``at``: on a coarse point and between it and the next."""
            on = jnp.where(at == first, x, x + zero)
            between = jnp.where(at == last, zero, zero + 0.5 * (up + x))
            return on, between

        def planes(t):
            """Both fine planes of coarse plane g after the pass along the
            planes, at row tile t, column by column."""
            rows = pl.ds(pl.multiple_of(t * 8, 8), 8)
            return [one_axis(here_ref[0, rows, pl.ds(128 * c, 128)],
                             next_ref[0, rows, pl.ds(128 * c, 128)],
                             g, 0, c0 - 2) for c in range(ncol)]

        def row_tile(t, a):
            an = planes(jnp.minimum(t + 1, nt - 1))
            grow = 8 * t + sub
            for parity in (0, 1):
                rows = []  # (on a coarse row, between) per column
                for c in range(ncol):
                    x, xn = a[c][parity], an[c][parity]
                    up = jnp.where(sub == 7, pltpu.roll(xn, 7, 0),
                                   pltpu.roll(x, 7, 0))
                    rows.append(one_axis(x, up, grow, 0, c1 - 2))
                lanes = {}  # per column, per kind of row: on, between
                for c, _ in halves:
                    if c in lanes:
                        continue
                    lanes[c] = []
                    for kind in (0, 1):  # fine rows on, between
                        x = rows[c][kind]
                        xn = rows[min(c + 1, ncol - 1)][kind]
                        up = jnp.where(lane == 127, pltpu.roll(xn, 127, 1),
                                       pltpu.roll(x, 127, 1))
                        lanes[c].append(one_axis(x, up, 128 * c + lane, 0,
                                                 c2 - 2))
                for c, h in halves:
                    fine = [spread(*lanes[c][kind], h, 1) for kind in (0, 1)]
                    for u in (0, 1):
                        out_ref[parity,
                                pl.ds(pl.multiple_of(16 * t + 8 * u, 8), 8),
                                pl.ds(256 * c + 128 * h, 128)] = spread(
                                    fine[0], fine[1], u, 0)
            return an

        # 32-bit counters in the x64 regime too: Mosaic has no others
        lax.fori_loop(jnp.int32(0), jnp.int32(nt), row_tile,
                      planes(jnp.int32(0)))

    coarse = (8 * nt, 128 * ncol)
    return pl.pallas_call(
        kernel,
        grid=(c0 - 1,),
        out_shape=jax.ShapeDtypeStruct(fine, z.dtype),
        in_specs=[
            pl.BlockSpec((8, 128), lambda g: (0, 0)),
            pl.BlockSpec((1,) + coarse, lambda g: (g, 0, 0)),
            pl.BlockSpec((1,) + coarse, lambda g: (g + 1, 0, 0)),
        ],
        out_specs=pl.BlockSpec((2, fr, fl), lambda g: (g, 0, 0)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=vmem_bytes(shape)),
        interpret=interpret,
        name="ramba_prolong",
    )(jnp.zeros((8, 128), z.dtype), z, z)
