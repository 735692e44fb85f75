"""How a basic index (slices and integers) reads and writes a device array.

``x[idx]`` and ``x.at[idx].set(v)`` are right everywhere and slow or
wrong in four places this module answers (PERF.md section 6, PRs 32, 35):

* **A write.**  jax lowers ``x.at[idx].set(v)`` to a ``scatter``, also
  where the index is plain slices.  XLA's SPMD partitioner (jax 0.9.0)
  gets a chain of them wrong on a mesh: zeros sharded over two axes,
  ``x.at[1:-1, 1:-1, 1:-1].set(v)`` then ``x.at[0].set(x[8])`` returns
  other values than NumPy, each alone being right.  A unit-stride window
  is a ``dynamic_update_slice``, which the partitioner handles and every
  backend updates in place: a face of a cube costs the face, not the
  cube.  A strided window is the same after the values are spread over
  it (zeros between, ``lax.pad``: on the second-last axis too, 9.7 ms
  for every second point of 514^3 against 16.6 through the MXU) and
  merged with what lies there; only a stride over more than
  ``PAD_MAX_EXTENT`` elements that the MXU does not take stays jax's
  scatter, because that ``lax.pad`` takes XLA:TPU minutes to compile.
* **A stride on the last axis.**  XLA:TPU turns a strided ``slice`` of
  the lane axis into a gather and a strided ``scatter`` into a serial
  loop: on a 514^3 float32 array ``x[::2, ::2, ::2]`` took 296 ms, the
  same read inside a stencil's fusion 360 ms and eight
  ``u[p::2, q::2, r::2] = ...`` 3.4 s, against 2.8 ms for a pass over the
  array.  The chip's way to move lanes is the MXU: a product with a 0/1
  selection matrix.  To be exact for every bit pattern (NaN,
  infinities, -0, integers) the operand crosses as its bytes, each byte
  plane a bfloat16 array of integers under 256, accumulated in float32,
  and is put together again: no value is rounded, none meets another.
  A lane axis longer than ``LANE_WHOLE`` is cut into tiles of
  ``LANE_TILE``, all one product with one small matrix, so the work
  follows the array's size and not the square of its rows' length.  One
  device only, arrays of two- and four-byte elements, steps up to
  ``MXU_MAX_STEP``, windows of ``MXU_MIN_ELEMENTS`` and more
  (``scripts/tpu_slicing_sweep.py`` reads the constants on the chip);
  everything else takes jax's own lowering.
* **Strides on a major axis and on the second-last at once.**  As one
  ``slice`` (``x[::2, ::2]`` of that array) XLA:TPU's fusion halts the
  core; each alone is a plain copy (6.7 and 7.2 ms).  So every such read,
  at any size, on any mesh, slices the major axes first and the last two
  after an ``optimization_barrier``: two programs XLA cannot fuse back
  into the one that fails.
* **A whole face copied onto another of the same array.**  A ghost-layer
  refresh (NPB MG's ``comm3``: ``a[0] = a[m]; a[m + 1] = a[1]`` on every
  axis in turn) is six such writes.  XLA:TPU materialises a face of the
  LANE axis as ``f32[D, H, 1]`` in (8, 128) tiles, one useful float in a
  row of 128: 137 MB at 514^3 for 1 MB of face, written by a ``slice``
  and read back by the ``dynamic-update-slice``: 3.69 ms a refresh for
  0.4 % of the data.  ``core/rewrite.py`` folds a chain of such copies
  into one ``remap_faces`` node and ``remap`` lowers it: on one device a
  rank-3 array of four-byte elements that ``ops/faces_pallas.py`` takes
  is its own result, and the kernel visits, ONCE, the blocks that hold a
  ghost lane or row (0.96 ms); everything else is the six writes as they
  were.
* **A prolongation onto zeros.**  NPB MG's ``interp`` is five writes onto
  a fresh array (``f[0::2, 0::2, 0::2] = z[:-1, :-1, :-1]``, then ``f[1:-1]
  = f[1:-1] + 0.5 * (f[2:] + f[:-2])`` along each axis), each a pass over
  the fine array with pads and slices between (PERF.md section 5).
  ``core/rewrite.py`` folds them into one ``prolong`` node and ``prolong``
  lowers it: on one device a float32 array that ``ops/prolong_pallas.py``
  takes is written once, by the kernel; everything else is the five writes
  as they were.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ramba_tpu.observe import registry as _registry
from ramba_tpu.parallel import mesh as _mesh

#: below this a strided window stays with XLA's own slice: the launch, not
#: the gather, is what it costs.  Derived, not swept (PERF.md section 6, PR
#: 32): the gather read 8.7 ns an element kept (296 ms for 33.9 M of a
#: 514^3 array), so the 8,192 kept of such a window are 71 us, against the
#: product's three launches of 10 to 30
MXU_MIN_ELEMENTS = 1 << 16
#: above this step the lanes stay XLA's: the gather's cost follows the few
#: elements kept, the product's all that are read.  Where they cross has
#: no chip reading yet; 2 is what the benchmark runs
MXU_MAX_STEP = 8
#: a lane axis whose thinner side (a read's outputs, a write's inputs) has
#: at most ``LANE_WHOLE`` elements is one selection product as it stands;
#: a longer one is cut into tiles of ``LANE_TILE``, so the product's cost
#: follows the axis' length and not its square
LANE_WHOLE = 512
LANE_TILE = 256
#: a strided write spreads its values with ``lax.pad`` only over axes of
#: at most this extent: XLA:TPU's compile time for zeros laid BETWEEN the
#: elements of its minor dimension grows faster than the dimension (for a
#: described v5e, no chip attached: 1.2 s at 1,026 lanes of 64 rows, 2.8 at
#: 2,050, 33.8 at 8,194, 2,498 at 2^20; PERF.md section 6, PR 32)
PAD_MAX_EXTENT = 2048


def _axes(idx, shape):
    """``[(start, count, step, dropped)]`` per axis of ``shape`` for a
    basic index of slices with positive steps and in-range integers, or
    None for anything else (newaxis, negative steps, an index out of
    range: jax's own lowering says what NumPy says)."""
    idx = tuple(idx) if isinstance(idx, tuple) else (idx,)
    if any(i is None for i in idx):
        return None
    if any(i is Ellipsis for i in idx):
        k = [i is Ellipsis for i in idx].index(True)
        idx = (idx[:k] + (slice(None),) * (len(shape) - len(idx) + 1)
               + idx[k + 1:])
    if len(idx) > len(shape) or any(i is Ellipsis for i in idx):
        return None
    idx += (slice(None),) * (len(shape) - len(idx))
    out = []
    for it, n in zip(idx, shape):
        if isinstance(it, slice):
            start, stop, step = it.indices(n)
            if step < 1:
                return None
            out.append((start, len(range(start, stop, step)), step, False))
        else:
            i = int(it) + (n if int(it) < 0 else 0)
            if not 0 <= i < n:
                return None
            out.append((i, 1, 1, True))
    return out


def _lanes_through_mxu(x, axes) -> bool:
    """Whether a stride on the last axis takes the selection product."""
    if (x.ndim < 2 or not 1 < axes[-1][2] <= MXU_MAX_STEP
            or x.dtype.itemsize not in (2, 4)
            or jnp.issubdtype(x.dtype, jnp.complexfloating)
            or _mesh.get_mesh().devices.size != 1):
        return False
    window = int(np.prod([(c - 1) * st + 1 for _, c, st, _ in axes],
                         dtype=np.int64))
    return window >= MXU_MIN_ELEMENTS


def _select_last(y, n_out, step, spread):
    """The last axis of ``y`` thinned to every ``step``-th element
    (``n_out`` of them), or with ``spread`` its elements laid ``step``
    apart over ``n_out`` zeros between: a product with a 0/1 matrix on the
    MXU, byte plane by byte plane, so every bit pattern arrives as it
    left.  A long axis is filled up to whole tiles, ``LANE_TILE`` elements
    of the thinner side and ``step`` times as many of the other."""
    n_in = y.shape[-1]
    t_in, t_out = n_in, n_out
    if min(n_in, n_out) > LANE_WHOLE:
        t_in, t_out = ((LANE_TILE, LANE_TILE * step) if spread
                       else (LANE_TILE * step, LANE_TILE))
        tiles = -(-n_in // t_in)
        y = lax.pad(y, jnp.zeros((), y.dtype), [(0, 0, 0)] * (y.ndim - 1)
                    + [(0, tiles * t_in - n_in, 0)])
        y = y.reshape(y.shape[:-1] + (tiles, t_in))
    i = lax.broadcasted_iota(jnp.int32, (t_in, t_out), 0)
    j = lax.broadcasted_iota(jnp.int32, (t_in, t_out), 1)
    sel = ((j == step * i) if spread else (i == step * j)).astype(
        jnp.bfloat16)
    word = jnp.uint32 if y.dtype.itemsize == 4 else jnp.uint16
    bits = lax.bitcast_convert_type(y, word)
    dn = (((y.ndim - 1,), (0,)), ((), ()))
    out = None
    for k in range(y.dtype.itemsize):
        plane = ((bits >> (8 * k)) & 0xFF).astype(jnp.bfloat16)
        got = lax.dot_general(plane, sel, dn,
                              preferred_element_type=jnp.float32)
        got = got.astype(word) << (8 * k)
        out = got if out is None else out | got
    out = lax.bitcast_convert_type(out, y.dtype)
    if t_in != n_in:
        out = out.reshape(out.shape[:-2] + (-1,))[..., :n_out]
    return out


def take(x, idx):
    """``x[idx]``."""
    axes = _axes(idx, x.shape)
    if not axes:
        return x[idx]
    starts = [s for s, _, _, _ in axes]
    stops = [s + (c - 1) * st + 1 for s, c, st, _ in axes]
    steps = [st for _, _, st, _ in axes]
    mxu = _lanes_through_mxu(x, axes)
    major = max(x.ndim - 2, 0)  # axes before the last two
    halts = (x.ndim > 2 and steps[-2] > 1
             and any(st > 1 for st in steps[:major]))
    if not (mxu or halts):
        return x[idx]
    # for the product x is made once, whole: left to itself XLA fuses what
    # computes it into each byte plane's product and computes it four
    # times (a 27-point stencil read at stride two: 86 ms for 22)
    y = lax.optimization_barrier(x) if mxu else x
    y = lax.slice(y, starts, stops, steps[:major] + [1] * (x.ndim - major))
    rest = [1] * major + steps[major:]
    if mxu:
        rest[-1] = 1
    if any(st > 1 for st in rest):
        y = lax.slice(lax.optimization_barrier(y), [0] * y.ndim, y.shape,
                      rest)
    if mxu:
        y = _select_last(lax.optimization_barrier(y), axes[-1][1], steps[-1],
                         spread=False)
    return y.reshape(tuple(c for _, c, _, drop in axes if not drop))


def put(x, idx, v):
    """``x.at[idx].set(v)``."""
    axes = _axes(idx, x.shape)
    if axes is None:
        return x.at[idx].set(v)
    kept = tuple(c for _, c, _, drop in axes if not drop)
    if v.ndim > len(kept):  # NumPy drops leading axes of length one
        v = v.reshape(v.shape[v.ndim - len(kept):])
    v = jnp.broadcast_to(v, kept).reshape(tuple(c for _, c, _, _ in axes))
    if 0 in v.shape:
        return x
    starts = [s for s, _, _, _ in axes]
    steps = [st for _, _, st, _ in axes]
    if any(st > 1 for st in steps):
        extent = [(c - 1) * st + 1 for _, c, st, _ in axes]
        interior = [st - 1 for st in steps]
        mxu = _lanes_through_mxu(x, axes)
        if mxu:
            interior[-1] = 0
        if any(between and e > PAD_MAX_EXTENT
               for between, e in zip(interior, extent)):
            return x.at[idx].set(v.reshape(kept))  # jax's scatter
        if mxu:
            v = _select_last(v, extent[-1], steps[-1], spread=True)
        v = lax.pad(v, jnp.zeros((), v.dtype),
                    [(0, 0, between) for between in interior])
        mine = None
        for ax, st in enumerate(steps):
            if st > 1:
                on = lax.broadcasted_iota(jnp.int32, extent, ax) % st == 0
                mine = on if mine is None else mine & on
        v = jnp.where(mine, v, lax.slice(
            x, starts, [s + e for s, e in zip(starts, extent)]))
    return lax.dynamic_update_slice(x, v, starts)


def _composed(pairs):
    """``{d: s}`` of a chain of copies ``a[d] = a[s]`` along one axis, each
    reading the array as the copies before it left it: hyperplane ``d`` of
    the result is hyperplane ``s`` of the operand."""
    w = {}
    for d, s in pairs:
        w[d] = w.get(s, s)
    return {d: s for d, s in w.items() if d != s}


def _faces_through_kernel(x, composed) -> bool:
    """Whether a remap takes the in-place walk: one device, an array the
    kernel takes (``faces_pallas.available``: no size under which the six
    writes won on the chip), a row or lane remapped at all (a plane alone
    is XLA's in place), and no source that is itself a destination, so
    that what a block reads never depends on which blocks were written
    before it."""
    from ramba_tpu.ops import faces_pallas

    if (x.ndim != 3 or not (composed[1] or composed[2])
            or any(s in w for w in composed for s in w.values())
            or _mesh.get_mesh().devices.size != 1):
        return False
    return faces_pallas.available(x.shape, x.dtype)


def remap(x, maps):
    """``x`` after the copies ``x[.., d, ..] = x[.., s, ..]`` of ``maps``:
    per axis the ``(d, s)`` pairs in the order the script wrote them, whole
    hyperplanes.  The result is ``x[w0, w1, ...]`` with ``w`` the composed
    pairs of each axis, so copies on different axes commute.  Notes
    ``faces.path.wrap`` (the Pallas walk, its block on the note) or
    ``faces.path.dus`` (the writes one by one, the HLO they had)."""
    composed = [_composed(pairs) for pairs in maps]

    def face(ax, i):
        return (slice(None),) * ax + (i,)

    if _faces_through_kernel(x, composed):
        from ramba_tpu.ops import faces_pallas

        interpret = faces_pallas.interpreting()
        bp, brp, vmem_limit = faces_pallas.block(x.shape)
        _registry.note_kernel("faces", "wrap", interpret,
                              grid=-(-x.shape[0] // bp), block_planes=bp,
                              row_block_planes=brp,
                              vmem_limit_bytes=vmem_limit)
        x = faces_pallas.wrap(x, sorted(composed[1].items()),
                              sorted(composed[2].items()), interpret)
        for d, s in composed[0].items():  # no source is written: in place
            x = put(x, face(0, d), take(x, face(0, s)))
        return x
    _registry.note_kernel("faces", "dus")
    for ax, pairs in enumerate(maps):
        for d, s in pairs:
            x = put(x, face(ax, d), take(x, face(ax, s)))
    return x


def _prolong_through_kernel(f) -> bool:
    """Whether a prolongation onto ``f`` takes the kernel: one device and
    an array ``prolong_pallas.available`` takes."""
    from ramba_tpu.ops import prolong_pallas

    return (_mesh.get_mesh().devices.size == 1
            and prolong_pallas.available(f.shape, f.dtype))


def prolong(z, axes, f):
    """``f``, zeros of twice ``z``'s extents less two, after
    ``f[0::2, 0::2, 0::2] = z[:-1, :-1, :-1]`` and ``f[1:-1] = f[1:-1] +
    0.5 * (f[2:] + f[:-2])`` along the first ``axes`` axes in turn.  Notes
    ``prolong.path.pallas`` (the kernel, its block on the note) or
    ``prolong.path.xla`` (the writes one by one, the HLO they had)."""
    if axes == 3 and _prolong_through_kernel(f):
        from ramba_tpu.ops import prolong_pallas

        interpret = prolong_pallas.interpreting()
        _registry.note_kernel(
            "prolong", "pallas", interpret, grid=z.shape[0] - 1,
            block_planes=2,
            vmem_limit_bytes=prolong_pallas.vmem_bytes(z.shape))
        return prolong_pallas.prolong(z, interpret)
    _registry.note_kernel("prolong", "xla")
    f = put(f, (slice(0, None, 2),) * f.ndim,
            take(z, (slice(None, -1),) * z.ndim))
    for ax in range(axes):
        def on(start, stop):
            return (slice(None),) * ax + (slice(start, stop),)

        mid = on(1, -1)
        f = put(f, mid, take(f, mid) + 0.5 * (take(f, on(2, None))
                                              + take(f, on(None, -2))))
    return f
