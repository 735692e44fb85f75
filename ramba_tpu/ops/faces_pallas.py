"""A Pallas TPU kernel that remaps whole rows and lanes of a rank-3 array
in place: the ghost-layer refresh of a structured-grid solver (NPB MG's
``comm3``, a reflecting boundary) on one chip.

``core/slicing.py`` ``remap`` is the caller: ``out[p, j, k] = x[p, w1(j),
w2(k)]``, ``w1`` and ``w2`` the identity but for a few rows and lanes,
every source a row or lane that is not itself remapped.  XLA:TPU writes a
lane face ``x[:, :, d] = x[:, :, s]`` by materialising ``f32[D, H, 1]`` in
(8, 128) tiles, one useful float in a row of 128: 137 MB at 514^3 for a
face of 1 MB, once by the ``slice`` and once by the
``dynamic-update-slice``, which then reads and writes the tile column it
lands in (PERF.md section 6, PR 35).

Here the result IS the operand (``input_output_aliases``, both left in
HBM), and the kernel visits only the blocks that hold a remapped lane or
row: first the lane-tile columns of the remapped lanes and of their
sources over every row (2 of 5 at 514^3), then the row tiles of the
remapped rows and of theirs over every lane, so that corners come from
both; what it does not visit stays as it lies.  Each walk is one of jax's
in-kernel pipelines (``pltpu.emit_pipeline``) over blocks of planes: every
tile column it needs is fetched once a step, under the step before, and
every destination written back under the step after.  Inside a block a
destination takes its source by a select on a lane (sublane) iota, the
source's tile rotated first where the two sit at different places of
their tiles: never where ``src - dst`` is a multiple of the tile, NPB's
periodic case at 2^k + 2.  Nothing a walk reads is written by it (no
source is a destination), so the order of its blocks is free.

Three facts shaped it (PERF.md section 6, PR 35): XLA copies an operand
that a custom call takes twice when one of the two is aliased to the
result (684 MB at 514^3), so the array is passed once and sliced inside;
Mosaic refuses a copy of a ragged tile by its logical size, and jax's
pipeline rounds it up to the tile, the padding being there in HBM; the
interpreter refuses that read and jax's pipeline asks the attached chip
its generation, so off the chip the kernel is not offered: the suite's
fixture (``tests/conftest.py`` ``interpreting_walk``) hands it a copy
padded to whole tiles and names a generation.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ramba_tpu.ops import pallas_backend as _pallas_backend
from ramba_tpu.ops import stencil_pallas as _stencil

# Bytes of one block: a lane-tile column of some planes (4 KB tiles, a
# plane's row tiles a stride apart in HBM).  Flat from a quarter to four
# times this on the chip (scripts/tpu_slicing_sweep.py; PERF.md section 6,
# PR 35).
_BLOCK_BYTES = 1 << 20
# Rows of a block handled at once: 64 rows of one lane tile are 8 vregs.
_CHUNK_ROWS = 64
# The suite's switch (``interpreting_walk``): offer the kernel off the chip
# and interpret it.  Not ``stencil_pallas._INTERPRET``, which the
# environment sets: nothing but that fixture makes this kernel interpret.
_INTERPRET = False


def _tiled(shape):
    """(Ho, Wo): a plane's rows and lanes as the chip tiles them."""
    return _stencil._round_up(shape[1], 8), _stencil._round_up(shape[2], 128)


def _sized(shape, bp):
    """``block`` with ``bp`` planes a lane block."""
    Ho, Wo = _tiled(shape)
    brp = bp * max(1, min(-(-shape[0] // bp),
                          _BLOCK_BYTES // (bp * Wo * 32)))
    need = 12 * max(bp * Ho * 512, brp * Wo * 32)
    return bp, brp, need + _stencil._VMEM_SLACK


def block(shape):
    """(planes a lane block, planes a row block, vmem_limit_bytes) over a
    ``(D, H, W)`` array of four-byte elements: ``_BLOCK_BYTES`` a block,
    the row walk's a whole number of the lane walk's; at most four tiles
    in and two out, each double-buffered, beside Mosaic's own scratch."""
    Ho, _ = _tiled(shape)
    return _sized(shape, max(1, min(shape[0], _BLOCK_BYTES // (Ho * 512))))


def available(shape, dtype) -> bool:
    """Whether the kernel takes this array: Pallas enabled, a chip
    attached (or the suite's interpreting switch), rank 3, a four-byte
    real element, a whole row tile, and blocks of one plane inside the
    VMEM a kernel may ask for.  No size from which it wins: on the chip
    the walk beat the six writes at every cube from 514^3 (0.96 ms
    against 3.69) down to 34^3 (0.011 against 0.022), and at 18^3 and
    10^3 both are a launch, 0.007 ms (``scripts/tpu_slicing_sweep.py
    faces``; PERF.md section 6, PR 35)."""
    if not _stencil._ENABLED:
        return False
    if _pallas_backend.interpret_mode() and not _INTERPRET:
        return False
    dtype = jnp.dtype(dtype)
    if (len(shape) != 3 or dtype.itemsize != 4
            or jnp.issubdtype(dtype, jnp.complexfloating) or shape[1] < 8):
        return False
    return _sized(shape, 1)[2] <= _stencil._vmem_cap()


def interpreting():
    """What ``pallas_call`` is told: the chip compiles, anything else
    interprets."""
    return _INTERPRET or _pallas_backend.interpret_mode()


def wrap(x, rows, lanes, interpret):
    """``x`` with row ``d`` of every plane taking row ``s`` for each
    ``(d, s)`` of ``rows`` and lane ``d`` taking lane ``s`` for each of
    ``lanes``, corners from both; no ``s`` is a ``d``.  One jitted
    function per statics, as ``stencil_pallas._padded_jit``: a flush that
    refreshes eighty arrays of one shape traces and lowers one kernel."""
    return _wrap_jit(tuple(rows), tuple(lanes), interpret,
                     *block(x.shape))(x)


@functools.lru_cache(maxsize=64)
def _wrap_jit(*static):
    def ramba_face_wrap(x):
        return _wrap_call(*static, x)

    return jax.jit(ramba_face_wrap)


def _by_tile(pairs, tile):
    """``{destination tile: [(place in it, source tile, place there)]}``."""
    out = {}
    for d, s in pairs:
        out.setdefault(d // tile, []).append((d % tile, s // tile, s % tile))
    return out


def _taking(v, takes, read, axis, tile):
    """The tile ``v`` with each place of ``takes`` along ``axis`` from its
    source tile (``read`` fetches one), rotated into place where the two
    places differ."""
    from jax.experimental.pallas import tpu as pltpu

    at = jax.lax.broadcasted_iota(jnp.int32, v.shape, axis)
    for place, src_tile, src_place in takes:
        src = read(src_tile)
        if place != src_place:
            src = pltpu.roll(src, (place - src_place) % tile, axis)
        v = jnp.where(at == place, src, v)
    return v


def _wrap_call(rows, lanes, interpret, bp, brp, vmem_limit, x):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    D = x.shape[0]
    Ho, Wo = _tiled(x.shape)

    def walk(hbm, pairs, tile, axis, planes, block, at):
        """One pipeline over blocks of ``planes`` planes: every tile that
        holds a destination of ``pairs`` is written, from itself and the
        tiles that hold its sources, each fetched once a step."""
        takes = _by_tile(pairs, tile)
        if not takes:
            return
        used = sorted(set(takes) | {t for ts in takes.values()
                                    for _, t, _ in ts})

        def body(*refs):
            ins = dict(zip(used, refs))
            outs = dict(zip(takes, refs[len(used):]))

            def plane(p):
                for r0 in range(0, block[0], _CHUNK_ROWS):
                    rws = pl.ds(r0, min(_CHUNK_ROWS, block[0] - r0))

                    def read(t, rws=rws):
                        return ins[t][p, rws, :]

                    for t, ts in takes.items():
                        outs[t][p, rws, :] = _taking(read(t), ts, read, axis,
                                                     tile)

            # 32-bit counters in the x64 regime too: Mosaic has no others
            jax.lax.fori_loop(jnp.int32(0), jnp.int32(planes),
                              lambda p, c: plane(p) or c, jnp.int32(0))

        def spec(t):
            return pl.BlockSpec((planes, *block), lambda i: (i, *at(t)))

        pltpu.emit_pipeline(
            body, grid=(-(-D // planes),),
            in_specs=[spec(t) for t in used],
            out_specs=[spec(t) for t in takes],
        )(*[hbm] * (len(used) + len(takes)))

    def kernel(in_hbm, out_hbm):
        # one buffer under two names: what is not visited stays as it lies
        del in_hbm
        walk(out_hbm, lanes, 128, 1, bp, (Ho, 128), lambda t: (0, t))
        walk(out_hbm, rows, 8, 0, brp, (8, Wo), lambda t: (t, 0))

    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        input_output_aliases={0: 0},
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem_limit),
        interpret=interpret,
        name="ramba_face_wrap",
    )(x)
