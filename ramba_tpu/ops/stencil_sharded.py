"""Explicit multi-chip stencil path: shard_map + ppermute halo exchange.

The reference's distributed stencil hand-routes halo regions point-to-point
between workers (border tables /root/reference/ramba/shardview_array.py:
1069-1136, exchange /root/reference/ramba/ramba.py:1260-1322) and then runs
a per-worker numba.stencil over the halo-padded shard
(/root/reference/ramba/ramba.py:3315-3376).

TPU-native equivalent: a ``jax.shard_map`` over the live mesh in which each
shard

1. exchanges halo columns with its left/right neighbors via
   ``lax.ppermute`` (nearest-neighbor ICI traffic, width = the probed
   stencil radius — no full all-gather of the operand),
2. exchanges halo rows, the received column strips' corners joined to
   them (so corner halos ride along for free),
3. evaluates the stencil, producing every local output cell: on TPU (2-D)
   through the Pallas kernel (ops/stencil_pallas.py), which takes the
   local block as it lies and the four received strips as operands of
   their own, so nothing array-sized is made between the resident block
   and the kernel; elsewhere (any rank) through XLA shifted slices over
   the block with its halos joined on (``_exchange``), and
4. masks cells whose *global* neighborhood leaves the array (sstencil
   writes only fully-in-range indices; borders are zero).

Unlike the GSPMD fallback (XLA chooses the halo collectives), halo width
here is exactly the probed neighborhood and the exchange is explicit
nearest-neighbor ppermute.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ramba_tpu import common
from ramba_tpu.observe import registry as _registry
from ramba_tpu.parallel import mesh as _mesh

# Interior/halo overlap in the sharded path (off: single full-block eval)
_OVERLAP = __import__("os").environ.get(
    "RAMBA_TPU_STENCIL_OVERLAP", "1"
) not in ("0", "")


def _axis_entries(mesh, shape):
    """Mesh-axis assignment per array dim, mirroring the live default
    layout so the shard_map usually avoids a reshard on entry."""
    spec = _mesh.default_spec(shape, mesh)
    entries = list(spec) + [None] * (len(shape) - len(spec))

    def names(e):
        if e is None:
            return ()
        return (e,) if isinstance(e, str) else tuple(e)

    return [names(e) for e in entries]


def eligible(lo, hi, arrs) -> bool:
    """True when the explicit ppermute halo path applies (any rank)."""
    mesh = _mesh.get_mesh()
    n = mesh.devices.size
    if n <= 1:
        return False
    shapes = {a.shape for a in arrs}
    if len(shapes) != 1:
        return False
    (shape,) = shapes
    if len(shape) < 1 or len(shape) != len(lo):
        return False
    if math.prod(shape) < common.dist_threshold:
        return False  # replicated small arrays: local compute is free
    ents = _axis_entries(mesh, shape)
    if not any(ents):
        return False  # layout says replicate — nothing to exchange
    for d in range(len(shape)):
        nd = math.prod(mesh.shape[a] for a in ents[d]) if ents[d] else 1
        ld = -(-shape[d] // nd)
        # each halo must fit inside one neighbor shard
        if max(-lo[d], hi[d]) > ld:
            return False
    return True


def _from_neighbour(send, axes_names, nshards, step):
    """``send`` as the shard ``step`` (+1: the one below, -1: the one
    above) along the (possibly multi-name) mesh axis group sent it.  End
    shards receive zeros (masked out of the output downstream)."""
    if nshards <= 1:
        return jnp.zeros_like(send)
    perm = [(i, i + step) for i in range(nshards) if 0 <= i + step < nshards]
    # trace-time estimate: every non-end shard ships one halo slab
    _registry.inc(
        "stencil.halo_bytes_est",
        len(perm) * math.prod(send.shape) * send.dtype.itemsize,
    )
    return jax.lax.ppermute(send, axes_names, perm)


def _exchange(x, axis, axes_names, nshards, lo_amt, hi_amt):
    """Extend ``x`` along ``axis`` with halo slabs from the neighboring
    shards: a copy of ``x`` with the slabs joined on.  The XLA paths' and
    ``halo()``'s; the Pallas kernel takes the strips as they are
    (``_halo_strips``)."""
    parts = []
    if lo_amt:
        send = jax.lax.slice_in_dim(
            x, x.shape[axis] - lo_amt, x.shape[axis], axis=axis
        )
        parts.append(_from_neighbour(send, axes_names, nshards, 1))
    parts.append(x)
    if hi_amt:
        send = jax.lax.slice_in_dim(x, 0, hi_amt, axis=axis)
        parts.append(_from_neighbour(send, axes_names, nshards, -1))
    if len(parts) == 1:
        return x
    return jnp.concatenate(parts, axis=axis)


def _halo_strips(b, ents, counts, los, his):
    """The halo of the 2-D local block ``b`` as four strips, ``None`` where
    the stencil reaches nowhere: west ``(lh, left)``, east ``(lh, right)``,
    north ``(top, left + lw + right)`` and south ``(bottom, ...)``.  The
    edge columns go first; the edge rows travel with the received column
    strips' corners joined to them (three arrays a few rows high), so a
    stencil that reads its corners gets them.  No copy of ``b``."""
    lh, lw = b.shape
    (top, left), (bottom, right) = los, his
    west = east = north = south = None
    if left:
        west = _from_neighbour(b[:, lw - left:], ents[1], counts[1], 1)
    if right:
        east = _from_neighbour(b[:, :right], ents[1], counts[1], -1)

    def rows(r0, r1):
        return jnp.concatenate(
            [p[r0:r1] for p in (west, b, east) if p is not None], axis=1)

    if top:
        north = _from_neighbour(rows(lh - top, lh), ents[0], counts[0], 1)
    if bottom:
        south = _from_neighbour(rows(0, bottom), ents[0], counts[0], -1)
    return west, east, north, south


def run(func, lo, hi, slots, arrs, taps, epilogue=None):
    """Evaluate the stencil over the mesh with explicit halo exchange
    (any rank).  Returns the full-shape result with border cells zeroed.
    ``epilogue``: the update that follows it, named on the note."""
    mesh = _mesh.get_mesh()
    x = arrs[0]
    shape = x.shape
    nd = len(shape)
    los = tuple(-l for l in lo)  # halo widths below (per dim)
    his = tuple(hi)
    ents = _axis_entries(mesh, shape)
    counts = [
        math.prod(mesh.shape[a] for a in ents[d]) if ents[d] else 1
        for d in range(nd)
    ]

    # pad to shard-divisible global shape (garbage cells are masked): the
    # one array-sized copy this path still makes, so it is counted
    padded_shape = tuple(-(-shape[d] // counts[d]) * counts[d]
                         for d in range(nd))
    _registry.note_kernel(
        "stencil", "sharded",
        operand_copy=len(arrs) if padded_shape != shape else 0,
        epilogue=epilogue)
    if padded_shape != shape:
        pads = tuple((0, p - s) for p, s in zip(padded_shape, shape))
        arrs = [jnp.pad(a, pads) for a in arrs]
    local_shape = tuple(p // c for p, c in zip(padded_shape, counts))

    def local(*blocks):
        from ramba_tpu.ops import stencil_pallas
        from ramba_tpu.skeletons import _stencil_degrade, stencil_interior

        val = None
        if nd == 2 and stencil_pallas.available_local(blocks):
            # the kernel reads the block where it lies and its halo from
            # the strips as they were received: nothing array-sized is
            # made between the resident block and the kernel's VMEM
            try:
                val = stencil_pallas.run(
                    func, lo, hi, slots, blocks, taps,
                    halos=[_halo_strips(b, ents, counts, los, his)
                           for b in blocks])
            except Exception as e:  # trace-time kernel failure: XLA path
                _stencil_degrade("sharded pallas", "sharded xla", e)
        if val is None:
            # halo exchange dim by dim, last dim first; each later exchange
            # sends the already-extended block, so corner halos ride along
            exts = []
            for b in blocks:
                e = b
                for d in range(nd - 1, -1, -1):
                    e = _exchange(e, d, ents[d], counts[d], los[d], his[d])
                exts.append(e)
            _registry.note_kernel("stencil", "xla")
            inner = tuple(
                local_shape[d] - (los[d] + his[d]) for d in range(nd)
            )
            if (
                _OVERLAP
                and nd == 2
                and all(i > 0 for i in inner)
                and (any(los) or any(his))
            ):
                # overlapped schedule: the interior strip depends only on
                # the local block, so XLA runs it concurrently with the
                # (async) halo collective-permutes; border strips wait on
                # the halos.  The reference gets the analogous overlap
                # from Numba prange workers computing while ZMQ receives
                # land (ramba.py:3549-3780); here the latency-hiding
                # scheduler does it.
                val = _overlapped_val(func, lo, hi, slots, blocks, exts,
                                      local_shape)
            else:
                val = stencil_interior(func, lo, hi, slots, exts)
        valid = None
        for d in range(nd):
            off = (jax.lax.axis_index(ents[d]) if ents[d] else 0) \
                * local_shape[d]
            g = jax.lax.broadcasted_iota(jnp.int32, local_shape, d) + off
            ok = (g >= los[d]) & (g < shape[d] - his[d])
            valid = ok if valid is None else (valid & ok)
        return jnp.where(valid, val, jnp.zeros((), val.dtype))

    spec = P(*(
        (e[0] if len(e) == 1 else tuple(e)) if e else None for e in ents
    ))
    out = jax.shard_map(
        local, mesh=mesh, in_specs=spec, out_specs=spec, check_vma=False
    )(*arrs)
    if padded_shape != shape:
        out = out[tuple(slice(0, s) for s in shape)]
    return out


def _overlapped_val(func, lo, hi, slots, blocks, exts, shape):
    """Local (lh, lw) stencil values assembled from five pieces:

    * the interior — computed straight from the un-extended local blocks,
      with NO data dependency on the halo ppermutes, and
    * four border strips (top/bottom full-width, left/right between them)
      — computed from the halo-extended blocks.

    XLA's scheduler overlaps the halo transfer with the interior compute
    because the dependence graph allows it.  Strips and interior tile the
    block exactly (no cell computed twice)."""
    from ramba_tpu.skeletons import stencil_interior

    lh, lw = shape
    top, left = -lo[0], -lo[1]
    bottom, right = hi[0], hi[1]
    hr, hc = top + bottom, left + right  # neighborhood extents

    # interior: output rows [top, lh-bottom) x cols [left, lw-right)
    interior = stencil_interior(func, lo, hi, slots, blocks)

    def strip(r_lo, r_hi, c_lo, c_hi):
        """Stencil values for output rows [r_lo, r_hi) x cols [c_lo, c_hi),
        read from the ext blocks (output cell (r, c) needs ext rows
        [r, r+hr] and cols [c, c+hc])."""
        pieces = [
            jax.lax.slice(e, (r_lo, c_lo), (r_hi + hr, c_hi + hc))
            for e in exts
        ]
        return stencil_interior(func, lo, hi, slots, pieces)

    rows = []
    if top:
        rows.append(strip(0, top, 0, lw))
    mid = []
    if left:
        mid.append(strip(top, lh - bottom, 0, left))
    mid.append(interior)
    if right:
        mid.append(strip(top, lh - bottom, lw - right, lw))
    rows.append(mid[0] if len(mid) == 1 else jnp.concatenate(mid, axis=1))
    if bottom:
        rows.append(strip(lh - bottom, lh, 0, lw))
    return rows[0] if len(rows) == 1 else jnp.concatenate(rows, axis=0)
