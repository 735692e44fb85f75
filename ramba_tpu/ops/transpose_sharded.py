"""The transpose of a rank-2 array laid over a square grid of devices:
``shard_map`` + ONE ``ppermute``.

The reference transposes a distributed array by remapping its shards'
axes and moving the elements each worker does not own
(``remap_axis``, shardview_array.py:1024-1042).  Here, where the default
layout (``parallel/mesh.py``) cuts both the operand and the result into
the same p x p grid of blocks, and every block is whole (8, 128) tiles
both ways round, block (r, c) of the operand, transposed, is block (c, r)
of the result.  So each device sends its block to the device that holds
that place, in one ``ppermute`` over the two mesh axes (the diagonal
devices to themselves: nothing crosses a chip).  Operand and result stay
in the default layout, so an array that is transposed every iteration
never leaves it.

``add_transposed`` is ``b + a.T`` (``rewrite.fold_add_transposed`` builds
it where the script writes ``B += A.T``).  Each device sends its block of
``a`` as it lies, and on the chip the received block is read transposed by
one Pallas kernel that adds it to ``b`` in place (``ramba_add_transposed``:
a grid of square tiles, the received tile (j, i) against ``b``'s (i, j)),
so the transposition is one pass of the update and not a copy of its own.
Left to itself, XLA lays the block out transposed before it is sent and
hoists that copy of ``a`` out of the iterations: the copies are then
rematerialised under the memory they hold, and a ten-iteration flush at
49,152^2 on four chips asks 16.9 GB a device.  Off the chip (or for
another dtype) the addition is XLA's.  The operands go through one
``optimization_barrier`` first, so the exchange starts only once ``b``
exists, and ``ndarray`` holds ``A``'s next value behind the updated ``B``
(node ``after``), so the block the next exchange sends is made after the
update: in a flush that repeats ``B += A.T; A += 1`` one exchange is in
flight and one block of A and one received block are live, not ten
(14.50 GB a device at 49,152^2 as admission estimates it).

Everywhere else (one device, rank 3 and above, a grid that is not
square, blocks that are not whole tiles) it is ``jnp.transpose``, which
GSPMD partitions.  Every transpose counts its path (``transpose.path.
swap``, ``.local`` on one device, ``.xla``) through
``registry.note_kernel``; the swap counts ``transpose.exchange_bytes``,
the bytes one off-diagonal device sends, and its note holds ``grid`` (p),
``block``, ``moved_blocks`` and, for the kernel, ``tile`` and
``vmem_limit_bytes``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ramba_tpu.observe import registry as _registry
from ramba_tpu.ops import pallas_backend as _pallas_backend
from ramba_tpu.ops import stencil_pallas as _stencil
from ramba_tpu.parallel import mesh as _mesh

#: a block's extents are whole lane tiles (and so row tiles) both ways
#: round: the transposed block is a block of the result as it lies
_LANES = 128
#: the kernel's square tile: the largest of these that divides both of a
#: block's extents
_TILES = (512, 256, 128)
# The suite's switch: offer the kernel off the chip and interpret it.
_INTERPRET = False


def plan(shape, mesh=None):
    """``(row axis, column axis, p)`` where a transpose of an array of
    ``shape`` takes the swap on ``mesh`` (the live one by default), else
    None."""
    mesh = mesh or _mesh.get_mesh()
    if len(shape) != 2 or mesh.devices.size == 1:
        return None
    spec = tuple(_mesh.default_spec(shape, mesh))
    if (len(spec) != 2 or tuple(_mesh.default_spec(shape[::-1], mesh)) != spec
            or not all(isinstance(e, str) for e in spec) or spec[0] == spec[1]):
        return None
    p = mesh.shape[spec[0]]
    if mesh.shape[spec[1]] != p or p * p != mesh.devices.size:
        return None
    if any(n % (p * _LANES) for n in shape):
        return None
    return spec[0], spec[1], p


def _tile(block, dtype):
    """The kernel's tile for ``b``'s blocks of extents ``block``, or None
    where the kernel is not offered: Pallas enabled, a chip attached (or
    the suite's switch), four-byte elements."""
    if not _stencil._ENABLED or jnp.dtype(dtype).itemsize != 4:
        return None
    if _pallas_backend.interpret_mode() and not _INTERPRET:
        return None
    return next(t for t in _TILES if not block[0] % t and not block[1] % t)


def _vmem_bytes(tile):
    """Three tiles (``b``'s, the received one, the result), each
    double-buffered."""
    return 3 * 2 * tile * tile * 4 + _stencil._VMEM_SLACK


def _note(x, how, interpret=False, **chose):
    """Count the path of a transpose of ``x``; with ``how`` (a plan),
    the swap's note."""
    if how is None:
        one = _mesh.get_mesh().devices.size == 1
        _registry.note_kernel("transpose", "local" if one else "xla")
        return
    p = how[2]
    block = [x.shape[0] // p, x.shape[1] // p]
    _registry.note_kernel(
        "transpose", "swap", interpret, grid=p, block=block,
        moved_blocks=p * (p - 1),
        exchange_bytes=block[0] * block[1] * x.dtype.itemsize, **chose)


def _swapped(block, how):
    """The block that lies at this device's transposed place, as it lies
    there: block (r, c) comes from device (c, r)."""
    rows, cols, p = how
    perm = [(r * p + c, c * p + r) for r in range(p) for c in range(p)]
    return jax.lax.ppermute(block, (rows, cols), perm)


def _on_blocks(fn, how, *arrs):
    rows, cols, _ = how
    spec = P(rows, cols)
    return jax.shard_map(fn, mesh=_mesh.get_mesh(), in_specs=spec,
                         out_specs=spec, check_vma=False)(*arrs)


def transpose(x, axes):
    """``jnp.transpose(x, axes)``; a rank-2 transpose on a square grid of
    whole-tile blocks by the swap."""
    how = plan(x.shape) if tuple(axes) == (1, 0) else None
    _note(x, how)
    if how is None:
        return jnp.transpose(x, axes)
    return _on_blocks(lambda a: _swapped(a, how).T, how, x)


def add_transposed(b, a):
    """``b + a.T``; on a square grid of whole-tile blocks the swap, read
    transposed by the update, after ``b`` exists."""
    how = plan(a.shape)
    if how is None:
        _note(a, how)
        return b + jnp.transpose(a)
    p = how[2]
    tile = _tile((b.shape[0] // p, b.shape[1] // p), b.dtype)
    interpret = _INTERPRET or _pallas_backend.interpret_mode()
    if tile is None:
        _note(a, how, update="xla")
    else:
        _note(a, how, interpret, update="pallas", tile=tile,
              vmem_limit_bytes=_vmem_bytes(tile))

    def local(bb, aa):
        bb, aa = jax.lax.optimization_barrier((bb, aa))
        got = _swapped(aa, how)
        if tile is None:
            return bb + got.T
        return _add_t_jit(tuple(bb.shape), str(bb.dtype), tile,
                          interpret)(bb, got)

    return _on_blocks(local, how, b, a)


@functools.lru_cache(maxsize=64)
def _add_t_jit(shape, dtype, tile, interpret):
    """``b + r.T`` of a ``shape`` block ``b`` and a transposed-shape
    ``r``, ``b`` updated in place: grid step (i, j) reads ``b``'s tile
    (i, j) and ``r``'s tile (j, i)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def kernel(b_ref, r_ref, out_ref):
        out_ref[...] = b_ref[...] + r_ref[...].T

    square = (tile, tile)

    def ramba_add_transposed(b, r):
        return pl.pallas_call(
            kernel,
            grid=(shape[0] // tile, shape[1] // tile),
            out_shape=jax.ShapeDtypeStruct(shape, dtype),
            in_specs=[pl.BlockSpec(square, lambda i, j: (i, j)),
                      pl.BlockSpec(square, lambda i, j: (j, i))],
            out_specs=pl.BlockSpec(square, lambda i, j: (i, j)),
            input_output_aliases={0: 0},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel"),
                vmem_limit_bytes=_vmem_bytes(tile)),
            interpret=interpret,
            name="ramba_add_transposed",
        )(b, r)

    return jax.jit(ramba_add_transposed)
