"""Pallas TPU kernels for stencils of rank 2 and 3 on one chip.

The reference's stencil path (/root/reference/ramba/ramba.py:3315-3376)
compiles a ``numba.stencil`` per worker and runs it over halo-padded shards —
its PRK star-stencil benchmark hits ~50 GFlops/node (README.md:281-299).
The rebuild's default path lowers stencils to shifted-slice arithmetic that
XLA fuses (skeletons._eval_stencil); this module adds hand-tiled Pallas
kernels for the hot cases: float stencils on a single TPU chip, of rank 2
(any shape) and of rank 3 where the plane is wide enough for the kernel
to win (``_rank3_wins``: a predicate on rank, shape and dtype).

Design (pallas_guide.md patterns):

* The kernel grid walks blocks of the leading axis: row blocks at rank 2,
  blocks of planes at rank 3.  Each instance waits for its slab (the
  block plus its halo: a margin of one tile of rows above and below and
  one tile of lanes left and right; at rank 3 whole planes with those
  margins, and exactly the halo's planes above and below, the leading
  axis being untiled), whose DMAs from HBM into one of two VMEM scratch
  buffers the instance before it started, starts the next slab's, then
  evaluates the user's kernel function over *statically shifted* in-VMEM
  reads — the same trace-the-user-function approach as the XLA path, so
  arbitrary (including nonlinear) stencil bodies work.
* Every slab is fetched from the arrays that already hold the data: the
  operand as it lies in HBM, never a padded copy of it.  The general
  path (``_run_padded``; the name is the benchmark's, it pads nothing)
  copies the operand's whole tiles itself.  What is not tile-aligned, the
  ragged last lane and row tile, travels at rank 2 as tile-wide operands
  XLA lays out, with the halo strips a neighbouring shard sent
  (``_tail_operands``), and at rank 3 as blocks of the operand itself
  that Pallas's pipeline fetches (``_padded_call3``).  The path sizes its
  block from the VMEM it asks Mosaic for (``_padded_block``,
  ``_padded_block3``) and says what it chose on its kernel note:
  ``block_rows``, ``grid``, ``vmem_limit_bytes``, at rank 3
  ``block_planes``, and ``halo``: ``"edge"`` (the array's own edge:
  margins no copy wrote are masked) or ``"strips"`` (operands).
  ``stencil.operand_copy`` counts the operands that still travel in an
  array-sized XLA copy: one of rank 2 with no whole tile.
* Mosaic keeps a read's (row, lane) offset as the value's layout and
  rotates where two layouts meet: once a tap.  The rank-2 body reads
  slices of the slab's value and pays that (nine taps).  The rank-3 body
  (27 taps, 8 distinct (row, lane) shifts) stages each shift once a
  plane into VMEM and reads every tap aligned, a plane offset being
  another plane of the staged copy (``_stage_plan``).
* Output blocks are plain VMEM BlockSpecs; the stencil border is zeroed
  in the kernel by a select, to match sstencil's semantics (the reference
  writes only indices whose full neighborhood is in range).
* At rank 3 the store may write an elementwise update of the result
  (``v - s``, ``u + s``: a ``stencil_update`` node, which
  ``rewrite.fold_stencil_update`` makes where the script writes it), its
  other operand a block of the output's walk: one pass over HBM fewer
  than the kernel's result and XLA's separate subtraction.

Multi-chip stencils run through ops/stencil_sharded.py (shard_map +
explicit ppermute halo exchange), which calls back into this kernel on
each shard's local block of rank 2, its four received halo strips beside
it, via ``available_local``/``run(..., halos=)``.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np

from ramba_tpu.observe import registry as _registry
from ramba_tpu.ops import pallas_backend as _pallas_backend

_INTERPRET = os.environ.get("RAMBA_TPU_PALLAS_INTERPRET", "0") not in ("0", "")
_ENABLED = os.environ.get("RAMBA_TPU_PALLAS", "1") not in ("0", "")

# The fast path's VMEM working set (slabs + output block), inside the
# 16 MiB a kernel gets when it asks for no limit of its own.
_VMEM_BUDGET = 8 << 20

# The padded path asks for its own limit (_padded_block): at most
# _VMEM_SHARE of a core's VMEM, which jax names where a chip is attached
# (pltpu.get_tpu_info); the v5e's 128 MiB (the chip's own refusal says
# "would exceed memory (size=134217728)", tests/test_chip_smoke.py) stands
# in where none is.  _VMEM_SLACK is Mosaic's internal scratch beside what
# the estimate counts.
_V5E_VMEM = 128 << 20
_VMEM_SHARE = 0.75
_VMEM_SLACK = 2 << 20
# Rows the padded kernel's body evaluates at once, where VMEM allows: the
# knee of scripts/tpu_stencil_sweep.py on the chip (a 15000^2 f32 sweep:
# 16 rows 4.85 ms, 32 4.04, 64 3.66, 128 3.50, for 1.7, 3.1, 6.4 and 15 s
# of Mosaic; PERF.md section 6, PR 27).
_BLOCK_ROWS = 64
# Rank 3 (_padded_call3): the most rows of a plane staged at once (a
# staged copy of n rows reads n + 16: at 514^3 64 rows 4.22 ms a sweep,
# 128 3.91, 256 3.72; at 258^3 64 rows 0.72, the whole plane's 264 0.60),
# the most planes a block holds, where VMEM allows (the halo planes are
# fetched and staged again by every block, so a block of bp planes does
# (bp + halo) / bp of the work), and the most vregs of one value the body
# evaluates at once (a 27-point sum over 4 sublane tiles by 5 lane tiles
# stays in registers; at 514^3 10 vregs 3.93 ms, 20 3.85, 40 4.05):
# scripts/tpu_stencil_sweep.py on the chip, PERF.md section 6, PR 33.
_BLOCK_ROWS3 = 264
_BLOCK_PLANES = 16
_CHUNK_VREGS = 20
# The narrowest last axis a rank-3 array may have to take the kernel: set
# from the chip's reading of both paths at 130^3 and 258^3 (PERF.md
# section 6, PR 33).  Under one whole lane tile the operand would travel
# in an XLA-made copy; a small cube is launch-bound on either path.
_RANK3_MIN_LANES = 256


def _rank3_wins(shape, dtype, n_slabs) -> bool:
    """Whether the plane-walking kernel takes a rank-3 operand: float32,
    a last axis of at least _RANK3_MIN_LANES, a second-last of a whole row
    tile, and a plane small enough that a block of one, with the staged
    copies of a 27-point neighbourhood, fits the VMEM the kernel may ask
    for.  Everything else stays XLA's fusion of shifted slices."""
    if dtype != jnp.dtype(jnp.float32):
        return False
    _, H, W = shape
    if W < _RANK3_MIN_LANES or H < 8:
        return False
    rows = _part(_round_up(H, 8) // 8, _BLOCK_ROWS3 // 8) * 8
    need = _padded_vmem_bytes3(1, rows, H, W, 4, (1, 1), (8, 8, 128, 128),
                               [(2, 6)] * n_slabs, 27)
    return need <= _vmem_cap()


def available_local(arrs) -> bool:
    """Kernel eligibility for already-local (per-shard) blocks — used from
    inside stencil_sharded's shard_map, where the pallas_call sees purely
    local data."""
    if not _ENABLED:
        return False
    if _pallas_backend.interpret_mode() and not _INTERPRET:
        return False
    shapes = {a.shape for a in arrs}
    if len(shapes) != 1:
        return False
    (shape,) = shapes
    # one uniform dtype: scratch slabs are allocated with a single dtype
    dtypes = {jnp.dtype(a.dtype) for a in arrs}
    if len(dtypes) != 1:
        return False
    if len(shape) == 3:
        return _rank3_wins(shape, *dtypes, len(arrs))
    return len(shape) == 2 and dtypes <= {jnp.dtype(jnp.float32),
                                          jnp.dtype(jnp.bfloat16)}


def available(arrs) -> bool:
    """Pallas path eligibility for this op instance (global arrays)."""
    if len(jax.devices()) != 1 and not _INTERPRET:
        # sharded inputs would be all-gathered around the pallas_call;
        # multi-device goes through stencil_sharded (explicit ppermute
        # halos feeding the kernel on local blocks)
        return False
    return available_local(arrs)


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m

# Margins of the fast path's VMEM slabs.  RM rows / CM cols of each slab
# hold halo (or don't-care garbage at the array edges, masked out of the
# output); 8 and 128 are the TPU sublane/lane tile sizes, which keeps every
# DMA slice aligned.
_RM, _CM = 8, 128


def _fast_eligible(lo, hi, arrs) -> bool:
    H, W = arrs[0].shape
    top, left = -lo[0], -lo[1]
    bottom, right = hi[0], hi[1]
    return (
        W % 128 == 0
        and H % 8 == 0
        and H >= 32
        and max(top, bottom) <= _RM
        and max(left, right) <= _CM
    )


def run(func, lo, hi, slots, arrs, taps=8, *, halos=None, epilogue=None,
        base=None, _block_rows=None, _block_planes=None):
    """Evaluate the stencil with a Pallas kernel.  Returns the full-shape
    result with border cells zeroed (sstencil semantics); with ``halos``
    (per input the ``(west, east, north, south)`` strips its neighbours
    sent: ``_tail_operands``) every cell of the result, unmasked.  Off-TPU
    the kernel automatically falls back to ``interpret=True`` (rather
    than raising from an impossible Mosaic compile), so the CPU suite —
    and the autotune parity tests — exercise the same code path.  Rank 3
    takes the general path only.  ``epilogue`` is ``(fname, at)``, the
    elementwise update ``base fname s`` (``at`` 0) or ``s fname base``
    (``at`` 1) that follows a rank-3 result ``s``: with ``base`` the
    kernel's own store writes it and returns the update, without it the
    note only names it.  ``_block_rows`` and ``_block_planes``
    are scripts/tpu_stencil_sweep.py's: a candidate block in place of the
    derived one."""
    if base is not None and len(arrs[0].shape) != 3:
        raise NotImplementedError("the epilogue is the rank-3 kernel's")
    interpret = _INTERPRET or _pallas_backend.interpret_mode()
    if (len(arrs[0].shape) == 2 and halos is None
            and _fast_eligible(lo, hi, arrs)):
        _registry.note_kernel("stencil", "pallas_fast", interpret)
        return _run_fast(func, lo, hi, slots, arrs, taps, interpret,
                         _block_rows)
    return _run_padded(func, lo, hi, slots, arrs, taps, interpret,
                       _block_rows, halos, _block_planes, epilogue, base)


def _run_fast(func, lo, hi, slots, arrs, taps, interpret=_INTERPRET,
              block_rows=None):
    """Tiled kernel for aligned shapes: no host-visible padding pass and
    double-buffered HBM->VMEM slab DMA (compute on block i overlaps the
    fetch of block i+1 — the pipelining the reference gets from Numba's
    prange workers overlapping with ZMQ receives, ramba.py:3549-3780).

    Layout: each input gets two VMEM slabs of shape (bh + 2*RM, W + 2*CM).
    Slab row RM+r col CM+c holds input[i*bh - RM + (RM+r), c] — i.e. the
    block's rows with an RM-row halo above/below and a CM-col halo left/
    right.  Edge blocks DMA only the in-range rows; the out-of-range slab
    cells hold stale garbage that is read only by border output cells,
    which the final ``valid`` mask zeroes (sstencil writes only cells whose
    full neighborhood is in range)."""
    import jax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    x = arrs[0]
    H, W = x.shape
    dtype = x.dtype
    top, left = -lo[0], -lo[1]
    bottom, right = hi[0], hi[1]
    n_slabs = len(arrs)
    itemsize = np.dtype(dtype).itemsize

    Wi = W + 2 * _CM
    if block_rows:
        # clamp a candidate: blocks below _RM rows or off 8-row alignment
        # would put the mid-block DMA start (j*bh - _RM) out of bounds
        bh = max(_RM, _round_up(block_rows, 8))
    else:
        # VMEM: 2 slabs per input + pipelined out block + ~4 live tap temps.
        rowcost = itemsize * (n_slabs * 2 * Wi + 6 * W)
        bh = max(8, min(512, (_VMEM_BUDGET + (4 << 20)) // rowcost // 8 * 8))
    bh = min(bh, _round_up(H, 8))
    grid = -(-H // bh)
    slab_h = bh + 2 * _RM

    def kernel(*refs):
        ins = refs[:n_slabs]
        out_ref = refs[n_slabs]
        slabs = refs[n_slabs + 1: 2 * n_slabs + 1]  # each (2, slab_h, Wi)
        sems = refs[-1]  # (2, n_slabs) DMA semaphores
        i = pl.program_id(0)

        def dma(j, b, do_start):
            """Start (or wait on) the slab copies for block j into buf b.
            Every branch uses static copy shapes; wait must mirror start
            exactly so semaphore byte counts match."""
            for k in range(n_slabs):
                cds = pl.ds(_CM, W)  # input cols land in slab cols [CM, CM+W)

                def cases(which):
                    if which == "first":
                        # rows [0, slab_h - RM) -> slab rows [RM, slab_h)
                        L = min(H, slab_h - _RM)
                        return pltpu.make_async_copy(
                            ins[k].at[pl.ds(0, L)],
                            slabs[k].at[b, pl.ds(_RM, L), cds],
                            sems.at[b, k],
                        )
                    if which == "last":
                        rs = (grid - 1) * bh - _RM
                        L = H - rs
                        return pltpu.make_async_copy(
                            ins[k].at[pl.ds(rs, L)],
                            slabs[k].at[b, pl.ds(0, L), cds],
                            sems.at[b, k],
                        )
                    # bh ≡ 0 (mod 8) and _RM == 8, so j*bh - _RM is 8-aligned;
                    # phrase it as (…)*8 + pl.multiple_of so Mosaic's prover
                    # accepts the sublane-tiled HBM slice (an earlier
                    # Mosaic said: "tile index in dimension 0 … divisible by
                    # the tiling (8)" at bh=40 on the 8192x8192 shape).
                    rs_mid = pl.multiple_of((j * (bh // 8) - 1) * 8, 8)
                    return pltpu.make_async_copy(
                        ins[k].at[pl.ds(rs_mid, slab_h)],
                        slabs[k].at[b, pl.ds(0, slab_h), cds],
                        sems.at[b, k],
                    )

                def act(cp):
                    cp.start() if do_start else cp.wait()

                if grid == 1:
                    act(cases("first"))
                    continue

                @pl.when(j == 0)
                def _():
                    act(cases("first"))

                @pl.when(j == grid - 1)
                def _():
                    act(cases("last"))

                @pl.when((j > 0) & (j < grid - 1))
                def _():
                    act(cases("mid"))

        two = jnp.asarray(2, i.dtype)
        cur = jax.lax.rem(i, two)
        nxt = jax.lax.rem(i + jnp.asarray(1, i.dtype), two)

        @pl.when(i == 0)
        def _():
            dma(i, cur, True)

        @pl.when(i + 1 < grid)
        def _():
            dma(i + 1, nxt, True)

        dma(i, cur, False)  # wait for this block's slabs

        from ramba_tpu.skeletons import _KVal, _unwrap

        class _Shift:
            def __init__(self, k, wrap_vals):
                self.k = k
                self.wrap_vals = wrap_vals

            def __getitem__(self, off):
                if not isinstance(off, tuple):
                    off = (off,)
                di, dj = off
                piece = slabs[self.k][
                    cur, pl.ds(_RM + di, bh), pl.ds(_CM + dj, W)
                ]
                return _KVal(piece) if self.wrap_vals else piece

        def build(wrap):
            call_args = []
            ai = 0
            for kind, payload in slots:
                if kind == "arr":
                    call_args.append(_Shift(ai, wrap))
                    ai += 1
                else:
                    call_args.append(payload.v)
            return call_args

        from ramba_tpu.skeletons import call_stencil_body

        val = call_stencil_body(func, build).astype(dtype)
        gr = jax.lax.broadcasted_iota(jnp.int32, (bh, W), 0) + i * bh
        gc = jax.lax.broadcasted_iota(jnp.int32, (bh, W), 1)
        valid = (gr >= top) & (gr < H - bottom) & (gc >= left) & (gc < W - right)
        out_ref[:] = jnp.where(valid, val, jnp.zeros((), dtype))

    return pl.pallas_call(
        kernel,
        grid=(grid,),
        out_shape=jax.ShapeDtypeStruct((H, W), dtype),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * n_slabs,
        out_specs=pl.BlockSpec((bh, W), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        scratch_shapes=(
            [pltpu.VMEM((2, slab_h, Wi), dtype) for _ in range(n_slabs)]
            + [pltpu.SemaphoreType.DMA((2, n_slabs))]
        ),
        interpret=interpret,
        name="ramba_stencil_fast",
    )(*arrs)


def _vmem_cap() -> int:
    """The most VMEM the padded kernel asks for: a share of one
    TensorCore's, read from jax where a chip is attached."""
    if _pallas_backend.interpret_mode():
        return int(_V5E_VMEM * _VMEM_SHARE)
    from jax.experimental.pallas import tpu as pltpu

    return int(pltpu.get_tpu_info().vmem_capacity_bytes * _VMEM_SHARE)


def _margins(lo, hi, itemsize):
    """(top, bottom, left, right) margins of the padded kernel's slab: each
    halo rounded up to the tile (8 rows of 32 bits, 128 lanes), so that
    every copy into the slab starts and ends on a tile."""
    sub = 32 // itemsize
    return (_round_up(-lo[0], sub), _round_up(hi[0], sub),
            _round_up(-lo[1], 128), _round_up(hi[1], 128))


def _padded_vmem_bytes(bh, W, itemsize, n_slabs, taps, margins):
    """What a block of ``bh`` rows asks of VMEM: per input two slabs
    (the block and its margins) and the value of the one being read, the
    output block Pallas double-buffers, and on Mosaic's stack one (bh, Wo)
    temporary per shifted read plus three of the arithmetic (its refusals
    read 6 to 6.5 of them at 8 taps: PERF.md section 6, PR 27)."""
    mt, mb, ml, mr = margins
    Wo = _round_up(max(W, 128), 128)
    return itemsize * (3 * n_slabs * (bh + mt + mb) * (ml + Wo + mr)
                       + (max(taps, 1) + 5) * bh * Wo) + _VMEM_SLACK


def _padded_block(H, W, itemsize, n_slabs, taps, margins):
    """(rows per block, vmem_limit_bytes) of the padded kernel over an
    ``(H, W)`` array: ``n_slabs`` inputs, ``taps`` shifted reads,
    ``margins`` as ``_margins`` gives them.  The body's cost per row
    falls with the rows it evaluates at once (each unaligned read of bh
    rows touches bh/8 + 1 sublane tiles) and its temporaries and Mosaic's
    compile time rise with them: _BLOCK_ROWS where VMEM allows, fewer
    where the array is wide, never under the sublane tile."""
    cap = _vmem_cap()
    sub = 32 // itemsize

    def need(bh):
        return _padded_vmem_bytes(bh, W, itemsize, n_slabs, taps, margins)

    bh = min(_BLOCK_ROWS, _round_up(H, sub))
    while bh > sub and need(bh) > cap:
        bh -= sub
    return bh, min(cap, need(bh))


def _cat(parts, axis):
    parts = [p for p in parts if p is not None]
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis)


def _tail_operands(x, strips, lo, hi, margins, sub):
    """The small arrays the padded kernel reads beside ``x`` itself, each
    ``None`` or laid out so that its copy into the slab is tile-aligned:

    * west ``(H, left margin)``: the west halo in its last lanes;
    * east ``(H, k*128)``: ``x``'s ragged last lane tile, then the east
      halo, at the lanes they take in the slab after ``x``'s whole tiles;
    * north ``(top margin, Wi)``: the north halo, corners included, in its
      last rows, in the slab's lanes;
    * south ``(k*sub, Wi)``: ``x``'s ragged last row tile (with its west
      and east halo), then the south halo, in the slab's lanes.

    ``strips`` is ``None`` (no halo: the array's own edge) or the raw
    ``(west (H, left), east (H, right), north (top, left + W + right),
    south (bottom, left + W + right))``, ``None`` where the width is 0.
    XLA builds them: a tile-wide strip each, never a copy of ``x``."""
    H, W = x.shape
    mt, _, ml, mr = margins
    top, left = -lo[0], -lo[1]
    w, e, n, s = strips or (None,) * 4
    Hf, Wf = H // sub * sub, W // 128 * 128
    Wi = ml + _round_up(max(W, 128), 128) + mr

    def lanes(a, at):
        """``a`` at lane ``at`` of a slab-wide row strip."""
        return jnp.pad(a, ((0, 0), (at, Wi - at - a.shape[1])))

    west = None if w is None else jnp.pad(w, ((0, 0), (ml - left, 0)))
    east = None
    if W > Wf or e is not None:
        east = _cat([x[:, Wf:] if W > Wf else None, e], 1)
        east = jnp.pad(
            east, ((0, 0), (0, _round_up(east.shape[1], 128) - east.shape[1])))
    at = ml - left if strips else ml
    north = None
    if n is not None:
        north = jnp.pad(lanes(n, at), ((mt - top, 0), (0, 0)))
    south = None
    if H > Hf or s is not None:
        tail = None
        if H > Hf:
            tail = _cat([None if w is None else w[Hf:], x[Hf:],
                         None if e is None else e[Hf:]], 1)
        south = lanes(_cat([tail, s], 0), at)
        south = jnp.pad(
            south, ((0, _round_up(south.shape[0], sub) - south.shape[0]),
                    (0, 0)))
    return west, east, north, south


def _run_padded(func, lo, hi, slots, arrs, taps=8, interpret=_INTERPRET,
                block_rows=None, halos=None, block_planes=None,
                epilogue=None, base=None):
    """General-shape path: walk row blocks (rank 2) or blocks of planes
    (rank 3), the fetch of block i+1 under the compute of block i, every
    fetch straight from the arrays that hold the data: no padded copy of
    an operand is made (``_padded_call``, ``_padded_call3``).  Sizes the
    block, notes what it chose, and calls the kernel through one jitted
    function per (kernel function, neighbourhood, block, epilogue): a
    program that runs the same stencil ten times traces and lowers it
    once.  ``epilogue`` and ``base`` as ``run`` takes them."""
    x = arrs[0]
    H, W = x.shape[-2:]
    itemsize = np.dtype(x.dtype).itemsize
    sub = 32 // itemsize
    if x.ndim == 3:
        if halos:
            raise NotImplementedError(
                "the rank-3 kernel reads its halo from the array's own edge")
        fused = base is not None
        plan = _stage_plan(func, slots, sub)
        assert len(plan) == len(arrs), (len(plan), len(arrs))
        block, vmem_limit = _padded_block3(
            *x.shape, itemsize, (-lo[0], hi[0]),
            _margins(lo[1:], hi[1:], itemsize),
            [(len(lanes), len(subs)) for lanes, subs in plan], taps, fused)
        if block_rows or block_planes:
            # the sweep's candidate, under the cap itself
            rows = min(_round_up(block_rows or block[1], sub),
                       _round_up(x.shape[1], sub))
            block = block_planes or block[0], rows
            vmem_limit = _vmem_cap()
        _registry.note_kernel("stencil", "pallas_padded", interpret,
                              block_rows=block[1], grid=-(-x.shape[0]
                                                          // block[0]),
                              vmem_limit_bytes=vmem_limit, halo="edge",
                              block_planes=block[0],
                              epilogue=epilogue[0] if epilogue else "none",
                              epilogue_fused=fused)
        call = _padded_jit(func, tuple(lo), tuple(hi), tuple(slots),
                           interpret, block, vmem_limit, plan,
                           epilogue if fused else None)
        return call(list(arrs), base)
    if block_rows:
        # the sweep's candidate, under the cap itself
        bh, vmem_limit = _round_up(block_rows, sub), _vmem_cap()
    else:
        bh, vmem_limit = _padded_block(H, W, itemsize, len(arrs), taps,
                                       _margins(lo, hi, itemsize))
    # an operand with no whole tile reaches the kernel through its tails
    # alone: the one shape an XLA-made copy still serves
    _registry.note_kernel("stencil", "pallas_padded", interpret,
                          block_rows=bh, grid=-(-H // bh),
                          vmem_limit_bytes=vmem_limit,
                          halo="strips" if halos else "edge",
                          operand_copy=0 if H >= sub and W >= 128
                          else len(arrs))
    call = _padded_jit(func, tuple(lo), tuple(hi), tuple(slots), interpret,
                       bh, vmem_limit)
    return call(list(arrs), halos)


@functools.lru_cache(maxsize=64)
def _padded_jit(*static):
    """``_padded_call`` (rank 3: ``_padded_call3``) under these statics,
    jitted: jax traces it once per operand shapes and lowers it once per
    program, however many times the program calls it (PRK's ten
    iterations; PERF.md section 6, PR 29).  The second argument is the
    halo strips at rank 2 and the epilogue's base at rank 3."""
    def ramba_stencil(arrs, extra):
        if len(static[1]) == 3:
            return _padded_call3(*static, arrs, extra)
        return _padded_call(*static, arrs, extra)

    return jax.jit(ramba_stencil)


def _padded_call(func, lo, hi, slots, interpret, bh, vmem_limit, arrs, halos):
    """The padded kernel over ``arrs`` at ``bh`` rows a block.

    Layout: each input has two VMEM slabs of ``bh`` rows plus the row
    margins, ``W`` rounded up to the lane tile plus the lane margins
    (``_margins``: the halo rounded up to the tile, so operand row ``r``,
    column ``c`` of block ``i`` lies at slab row ``mt + r - i*bh``, lane
    ``ml + c``, and every copy is tile-aligned).  A middle block is one
    dynamic copy of ``slab_h`` rows of the operand's whole lane tiles; a
    block that reaches over the first or the last row (``edge``) has its
    own static, clipped copy shapes.  What is not tile-aligned in the
    operand itself, its ragged last lane tile and last row tile, comes
    from the tail operands (``_tail_operands``), and so do the halo
    strips where ``halos`` gives them (one ``(west, east, north, south)``
    per input: stencil_sharded's received strips).  Without ``halos`` the
    halo is the array's own edge: slab cells that no copy wrote hold stale
    VMEM and are read only by cells the ``valid`` select zeroes (sstencil
    writes only cells whose full neighbourhood is in range).  With them
    every cell of the output has its neighbourhood and the caller owns the
    masking."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    x = arrs[0]
    H, W = x.shape
    dtype = x.dtype
    itemsize = np.dtype(dtype).itemsize
    sub = 32 // itemsize
    top, left = -lo[0], -lo[1]
    bottom, right = hi[0], hi[1]
    n_slabs = len(arrs)
    margins = mt, mb, ml, _ = _margins(lo, hi, itemsize)

    Wo = _round_up(max(W, 128), 128)
    Wi = ml + Wo + margins[3]
    Hf, Wf = H // sub * sub, W // 128 * 128
    grid = -(-H // bh)
    slab_h = bh + mt + mb

    # operands: per input the array, then the tails it has
    tails = [_tail_operands(a, halos[k] if halos else None, lo, hi, margins,
                            sub) for k, a in enumerate(arrs)]
    operands, where = [], []
    for a, ts in zip(arrs, tails):
        idx = []
        for t in (a, *ts):
            idx.append(None if t is None else len(operands))
            if t is not None:
                operands.append(t)
        where.append(idx)
    _, east0, _, south0 = tails[0]
    ew = 0 if east0 is None else east0.shape[1]
    sh = 0 if south0 is None else south0.shape[0]

    # blocks whose slab reaches above row 0 or below the last whole row tile
    first_tail = max(0, (Hf - mb) // bh)
    n_head = min(grid, -(-mt // bh))
    edge = sorted(set(range(n_head)) | set(range(first_tail, grid)))
    n_ops = len(operands)

    def _kernel_body(*refs):
        # refs: the HBM operands, out_ref, n_slabs (2, slab_h, Wi) VMEM
        # slabs, one (2, 5 * n_slabs) array of DMA semaphores
        out_ref = refs[n_ops]
        slabs = refs[n_ops + 1: n_ops + 1 + n_slabs]
        sems = refs[-1]
        i = pl.program_id(0)
        cur = jax.lax.rem(i, jnp.asarray(2, i.dtype))

        def copies(j, b):
            """The copies that fill buffer ``b`` with block ``j``: ``j`` a
            Python int for an edge block, traced for a middle one.  The
            wait mirrors the start: the same list, so the semaphores' byte
            counts match."""
            if isinstance(j, int):
                start = j * bh - mt  # operand row of slab row 0
                r0 = max(0, start)
                L, d0 = min(Hf, start + slab_h) - r0, r0 - start
            else:
                # bh and mt are static multiples of the sublane tile:
                # expose that to Mosaic's divisibility prover (same class
                # of refusal as in _run_fast)
                start = None
                r0 = pl.multiple_of((j * (bh // sub) - mt // sub) * sub, sub)
                L, d0 = slab_h, 0
            cps = []
            for k in range(n_slabs):
                xi, wi, ei, ni, si = where[k]

                def cp(src, dst, c):
                    cps.append(pltpu.make_async_copy(
                        src, dst, sems.at[b, 5 * k + c]))

                slab = slabs[k]
                if L > 0:
                    rows, drows = pl.ds(r0, L), pl.ds(d0, L)
                    if Wf:
                        cp(refs[xi].at[rows, pl.ds(0, Wf)],
                           slab.at[b, drows, pl.ds(ml, Wf)], 0)
                    if wi is not None:
                        cp(refs[wi].at[rows], slab.at[b, drows, pl.ds(0, ml)],
                           1)
                    if ei is not None:
                        cp(refs[ei].at[rows],
                           slab.at[b, drows, pl.ds(ml + Wf, ew)], 2)
                if start is None:
                    continue
                if ni is not None and start < 0:
                    cp(refs[ni].at[pl.ds(mt + start, -start)],
                       slab.at[b, pl.ds(0, -start)], 3)
                n = min(sh, start + slab_h - Hf)
                if si is not None and n > 0:
                    cp(refs[si].at[pl.ds(0, n)],
                       slab.at[b, pl.ds(Hf - start, n)], 4)
            return cps

        def each_copy(j, b, act):
            """``act`` on the copies of block ``j`` (traced): one static
            branch per edge block, one for all the middle ones."""
            for e in edge:
                @pl.when(j == e)
                def _(e=e):
                    for c in copies(e, b):
                        act(c)

            if n_head < first_tail:
                @pl.when((j >= n_head) & (j < first_tail))
                def _():
                    for c in copies(j, b):
                        act(c)

        @pl.when(i == 0)
        def _():
            for c in copies(0, 0):
                c.start()

        if grid > 1:
            @pl.when(i + 1 < grid)
            def _():
                each_copy(i + 1, 1 - cur, lambda c: c.start())

        each_copy(i, cur, lambda c: c.wait())

        from ramba_tpu.skeletons import _KVal, call_stencil_body

        class _Shift:
            """Shifted reads as slices of the slab's value, loaded once:
            as fast on the chip as slicing the ref per read, and half
            the Mosaic compile (PERF.md section 6, PR 27)."""

            def __init__(self, ref, wrap_vals):
                self.ref = ref
                self.wrap_vals = wrap_vals
                self.slab = None

            def __getitem__(self, off):
                if not isinstance(off, tuple):
                    off = (off,)
                di, dj = off
                if self.slab is None:
                    self.slab = self.ref[cur]
                piece = self.slab[
                    mt + di: mt + di + bh, ml + dj: ml + dj + Wo
                ]
                return _KVal(piece) if self.wrap_vals else piece

        def build_args(wrap):
            call_args = []
            ai = 0
            for kind, payload in slots:
                if kind == "arr":
                    call_args.append(_Shift(slabs[ai], wrap))
                    ai += 1
                else:
                    call_args.append(payload.v)
            return call_args

        val = call_stencil_body(func, build_args).astype(dtype)
        if not halos:
            # zero the stencil border in-kernel (cells whose neighbourhood
            # leaves the array, which read the margins no copy wrote): a
            # select, never a multiply, because stale VMEM may hold NaN
            gr = jax.lax.broadcasted_iota(jnp.int32, (bh, Wo), 0) + i * bh
            gc = jax.lax.broadcasted_iota(jnp.int32, (bh, Wo), 1)
            valid = ((gr >= top) & (gr < H - bottom)
                     & (gc >= left) & (gc < W - right))
            val = jnp.where(valid, val, jnp.zeros((), dtype))
        out_ref[:] = val

    # out_shape is the exact result shape: pallas clips partial edge
    # blocks, and the kernel masks the stencil border itself (the array's
    # own; a shard's caller masks by global coordinates), so no
    # post-processing pass is needed.  The NumPy-ufunc retry and branch
    # auto-lowering happen inside the kernel body (call_stencil_body).
    return pl.pallas_call(
        _kernel_body,
        grid=(grid,),
        out_shape=jax.ShapeDtypeStruct((H, W), dtype),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * n_ops,
        out_specs=pl.BlockSpec((bh, Wo), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        scratch_shapes=(
            [pltpu.VMEM((2, slab_h, Wi), dtype)] * n_slabs
            + [pltpu.SemaphoreType.DMA((2, 5 * n_slabs))]
        ),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem_limit),
        interpret=interpret,
        name="ramba_stencil_padded",
    )(*operands)


def _stage_plan(func, slots, sub):
    """Per input, the shifted copies the rank-3 body stages before it
    evaluates: ``(lanes, subs)``, the lane offsets that are no whole tile
    and the ``(row, lane)`` offsets whose row offset is none.  A value
    read at such an offset is rotated into place by Mosaic wherever it
    meets a value read at another; staged once a plane, the rotation is
    shared by every plane offset that reads it (a 27-point operator has
    26 shifted reads and 8 distinct ``(row, lane)`` shifts)."""
    from ramba_tpu.skeletons import stencil_offsets

    plan = []
    for offs in stencil_offsets(func, slots):
        lanes = sorted({dj for _, _, dj in offs if dj % 128})
        subs = sorted({(di, dj) for _, di, dj in offs if di % sub})
        lanes += sorted({dj for _, dj in subs if dj % 128} - set(lanes))
        plan.append((tuple(lanes), tuple(subs)))
    return tuple(plan)


def _part(tiles, most):
    """The tiles a part when ``tiles`` are walked in equal parts of at most
    ``most``, the last part starting early where they do not divide (its
    first tiles are then done twice): the largest part that redoes under
    3 %, so that one traced body serves every part.  65 tiles in parts of
    at most 33 are two of 33 (66 for 65); 33 in parts of at most 4 are
    eleven of 3."""
    for t in range(min(most, tiles), 1, -1):
        if tiles >= 0.97 * -(-tiles // t) * t:
            return t
    return 1


def _chunk_rows(n, Wo, sub):
    """Rows of the ``n`` staged the rank-3 body evaluates at once: whole
    sublane tiles, at most _CHUNK_VREGS vregs a value."""
    return _part(n // sub, max(1, _CHUNK_VREGS // (Wo // 128))) * sub


def _padded_vmem_bytes3(bp, rows, H, W, itemsize, halo, margins, staged,
                        taps, base=False):
    """What a block of ``bp`` planes, staged ``rows`` rows at a time, asks
    of VMEM: per input two slabs of the block's planes and their halo (the
    whole plane and its margins) and two of its tail blocks, its
    lane-shifted copies (``rows`` and the row margins) and its row-shifted
    ones (``staged``: how many of each), the output block Pallas
    double-buffers and, with an epilogue's ``base``, its block likewise,
    and on Mosaic's stack the temporaries of one chunk and of one staged
    copy."""
    mt, mb, ml, mr = margins
    sub = 32 // itemsize
    Ho, Wo = _round_up(max(H, sub), sub), _round_up(max(W, 128), 128)
    planes = bp + sum(halo)
    words = (4 if base else 2) * bp * Ho * Wo
    for n_lane, n_sub in staged:
        words += planes * (2 * (mt + Ho + mb) * (ml + Wo + mr)
                           + 2 * (Ho * 128 + sub * Wo)
                           + n_lane * (mt + rows + mb) * Wo
                           + n_sub * rows * Wo)
    words += ((max(taps, 1) + 5) * _chunk_rows(rows, Wo, sub) * Wo
              + 2 * (mt + rows + mb) * (ml + Wo + mr))
    return itemsize * words + _VMEM_SLACK


def _padded_block3(D, H, W, itemsize, halo, margins, staged, taps,
                   base=False):
    """((planes per block, rows staged at once), vmem_limit_bytes) of the
    rank-3 kernel over a ``(D, H, W)`` array, with an epilogue's ``base``
    block or without.  The plane's rows in equal
    parts of at most _BLOCK_ROWS3 (``_part``; a shifted copy of n rows
    reads n and the row margins); as many planes as VMEM allows up to
    _BLOCK_PLANES, since every block fetches and stages its halo planes
    again.  A plane too large for a block of one is refused: the caller
    degrades to the XLA path."""
    cap = _vmem_cap()
    sub = 32 // itemsize
    rows = _part(_round_up(H, sub) // sub, _BLOCK_ROWS3 // sub) * sub

    def need(bp):
        return _padded_vmem_bytes3(bp, rows, H, W, itemsize, halo, margins,
                                   staged, taps, base)

    bp = min(_BLOCK_PLANES, D)
    while bp > 1 and need(bp) > cap:
        bp -= 1
    if need(bp) > cap:
        raise ValueError(
            f"one ({H}, {W}) plane and its staged copies ask {need(bp)} "
            f"bytes of VMEM, over {cap}")
    return (bp, rows), min(cap, need(bp))


def _padded_call3(func, lo, hi, slots, interpret, block, vmem_limit, plan,
                  epilogue, arrs, base):
    """The padded kernel over rank-3 ``arrs``, ``block`` = (planes a
    block, rows staged at once); with ``epilogue`` = ``(fname, at)`` the
    update ``base fname s`` (``at`` 0) or ``s fname base`` (``at`` 1) of
    its result ``s``.

    The grid walks blocks of planes.  The leading axis is untiled, so a
    slab holds exactly the block's planes and their halo, each plane
    whole: its rows and lanes with the margins ``_margins`` gives, operand
    plane ``q``, row ``r``, column ``c`` of block ``i`` at slab plane
    ``top + q - i*bp``, row ``mt + r``, lane ``ml + c``.  The slab of
    block i+1 is fetched while block i computes: the operand's whole tiles
    by the kernel's own copy, clipped and static for a block that reaches
    over the first or the last plane; what is not tile-aligned in the
    operand, the ragged last lane tile and row tile of every plane, as
    blocks of the same array that Pallas's pipeline fetches a step ahead
    and the body copies into the slab.  No XLA op stands between the
    resident array and the kernel, whatever the shape.

    The body never holds a block's temporaries.  ``rows`` rows at a time
    (equal parts of the plane, the last starting early where they do not
    divide it: ``_part``) it first stages, for every plane of the slab, the shifted copies
    ``plan`` names (``_stage_plan``): each lane offset once, then each
    (row, lane) offset from the lane-shifted copy; then it evaluates the
    user's function plane by plane, a sublane tile at a time, every read
    an aligned load of the slab or of a staged copy, a plane offset being
    another plane of the same buffer.  Cells whose neighbourhood leaves
    the array on any of the six faces are zeroed by the select: slab cells
    that no copy wrote hold stale VMEM and are read by no other cell.

    The epilogue's ``base`` arrives as blocks of the output's own walk,
    fetched ahead by Pallas, and the store writes ``fname`` of the base
    and the selected value, in the script's order: every cell the bits
    of the two passes it replaces, the border's too (``v - +0``)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from ramba_tpu.skeletons import _KVal, call_stencil_body

    bp, rows = block
    x = arrs[0]
    D, H, W = x.shape
    dtype = x.dtype
    itemsize = np.dtype(dtype).itemsize
    sub = 32 // itemsize
    top, bottom = -lo[0], hi[0]
    mt, mb, ml, mr = _margins(lo[1:], hi[1:], itemsize)
    Ho, Wo = _round_up(max(H, sub), sub), _round_up(max(W, 128), 128)
    Hi, Wi = mt + Ho + mb, ml + Wo + mr
    Hf, Wf = H // sub * sub, W // 128 * 128
    n_slabs = len(arrs)
    grid = -(-D // bp)
    slab_p = bp + top + bottom
    chunk = _chunk_rows(rows, Wo, sub)

    # operands: per input the array itself, for the copies of its whole
    # tiles, and the same array again for what is not tile-aligned in it:
    # the ragged last lane tile and row tile of every plane, as blocks
    # Pallas fetches ahead (it clips a block that leaves the array), the
    # block's planes in one and each halo plane in one of its own
    operands, in_specs, where = [], [], []

    def tail_specs(shape, at):
        """Blocks of ``shape`` (rows, lanes) at block index ``at`` of the
        last two axes: the planes of block i, then its halo planes."""
        def plane(off):
            return lambda i: (jnp.clip(i * bp + off, 0, D - 1), *at)

        return ([pl.BlockSpec((bp, *shape), lambda i: (i, *at),
                              memory_space=pltpu.VMEM)]
                + [pl.BlockSpec((1, *shape), plane(off),
                                memory_space=pltpu.VMEM)
                   for off in (*range(-top, 0), *range(bp, bp + bottom))])

    for a in arrs:
        idx = [len(operands)]
        operands.append(a)
        in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
        for ragged, shape, at in ((W > Wf, (Ho, 128), (0, Wf // 128)),
                                  (H > Hf, (sub, Wo), (Hf // sub, 0))):
            specs = tail_specs(shape, at) if ragged else []
            idx.append(range(len(operands), len(operands) + len(specs)))
            operands += [a] * len(specs)
            in_specs += specs
        where.append(idx)
    if epilogue:
        base_at = len(operands)
        operands.append(base)
        in_specs.append(pl.BlockSpec((bp, Ho, Wo), lambda i: (i, 0, 0),
                                     memory_space=pltpu.VMEM))
        update = {"subtract": jnp.subtract, "add": jnp.add}[epilogue[0]]
    n_ops = len(operands)

    # blocks whose slab reaches above plane 0 or below the last one
    n_head = min(grid, -(-top // bp))
    first_tail = max(n_head, (D - bottom) // bp)
    edge = [*range(n_head), *range(first_tail, grid)]

    # scratch: per input its slabs, then the staged copies it has
    scratch, slab_at, lane_at, sub_at = [], [], [], []
    for lanes, subs in plan:
        slab_at.append(len(scratch))
        scratch.append(pltpu.VMEM((2, slab_p, Hi, Wi), dtype))
        lane_at.append(len(scratch) if lanes else None)
        if lanes:
            scratch.append(pltpu.VMEM(
                (len(lanes), slab_p, mt + rows + mb, Wo), dtype))
        sub_at.append(len(scratch) if subs else None)
        if subs:
            scratch.append(pltpu.VMEM((len(subs), slab_p, rows, Wo), dtype))
    scratch.append(pltpu.SemaphoreType.DMA((2, n_slabs)))

    def _kernel_body(*refs):
        out_ref = refs[n_ops]
        bufs = refs[n_ops + 1:-1]
        sems = refs[-1]
        slabs = [bufs[k] for k in slab_at]
        i = pl.program_id(0)
        cur = jax.lax.rem(i, jnp.asarray(2, i.dtype))

        def copies(j, b):
            """The copies that fill buffer ``b`` with the whole tiles of
            block ``j``: ``j`` a Python int for an edge block, traced for
            a middle one; the wait mirrors the start."""
            if not (Hf and Wf):
                return []
            if isinstance(j, int):
                start = j * bp - top  # operand plane of slab plane 0
                p0 = max(0, start)
                L, d0 = min(D, start + slab_p) - p0, p0 - start
            else:
                p0, L, d0 = j * bp - top, slab_p, 0
            return [pltpu.make_async_copy(
                refs[where[k][0]].at[pl.ds(p0, L), pl.ds(0, Hf),
                                     pl.ds(0, Wf)],
                slabs[k].at[b, pl.ds(d0, L), pl.ds(mt, Hf), pl.ds(ml, Wf)],
                sems.at[b, k]) for k in range(n_slabs)]

        def each_copy(j, b, act):
            for e in edge:
                @pl.when(j == e)
                def _(e=e):
                    for c in copies(e, b):
                        act(c)

            if n_head < first_tail:
                @pl.when((j >= n_head) & (j < first_tail))
                def _():
                    for c in copies(j, b):
                        act(c)

        @pl.when(i == 0)
        def _():
            for c in copies(0, 0):
                c.start()

        if grid > 1:
            @pl.when(i + 1 < grid)
            def _():
                each_copy(i + 1, 1 - cur, lambda c: c.start())

        each_copy(i, cur, lambda c: c.wait())

        def loop(n, body):
            """``body(t)`` for t under ``n``: traced once where it
            repeats."""
            if n == 1:
                body(0)
            elif n:
                # 32-bit counters in the x64 regime too: Mosaic has no
                # others
                jax.lax.fori_loop(jnp.int32(0), jnp.int32(n),
                                  lambda t, c: body(t) or c, jnp.int32(0))

        # the tails of this block, fetched ahead by Pallas, into the slab
        for k in range(n_slabs):
            slab = slabs[k]
            for tails, rws, lns in (
                    (where[k][1], pl.ds(mt, Ho), pl.ds(ml + Wf, 128)),
                    (where[k][2], pl.ds(mt + Hf, sub), pl.ds(ml, Wo))):
                if not tails:
                    continue
                block, *halo = (refs[t] for t in tails)

                def plane(p, block=block, rws=rws, lns=lns, slab=slab):
                    slab[cur, top + p, rws, lns] = block[p]

                loop(bp, plane)
                for h, ref in enumerate(halo):
                    slab[cur, h if h < top else bp + h, rws, lns] = ref[0]

        def tile(r):
            """Row ``r``, a multiple of the sublane tile: said to Mosaic,
            whose loads and stores at a traced row want it proven."""
            return r if isinstance(r, int) else pl.multiple_of(r, sub)

        def stage(s, r0, n):
            """Slab plane ``s``, rows ``r0`` to ``r0 + n``: the shifted
            copies, each an aligned store."""
            for k, (lanes, subs) in enumerate(plan):
                slab = slabs[k]
                for at, dj in enumerate(lanes):
                    bufs[lane_at[k]][at, s, pl.ds(0, mt + n + mb), :] = slab[
                        cur, s, pl.ds(r0, mt + n + mb), pl.ds(ml + dj, Wo)]
                for at, (di, dj) in enumerate(subs):
                    if dj % 128:
                        v = bufs[lane_at[k]][lanes.index(dj), s,
                                             pl.ds(mt + di, n), :]
                    else:
                        # a traced row is read at its tile, the offset
                        # taken from the value
                        v = slab[cur, s, pl.ds(r0, mt + n + mb),
                                 pl.ds(ml + dj, Wo)][mt + di:mt + di + n]
                    bufs[sub_at[k]][at, s, pl.ds(0, n), :] = v

        class _Shift:
            """Input ``k`` read at a relative offset: ``m`` rows from row
            ``r0 + c0`` of the plane ``p`` of the block."""

            def __init__(self, k, p, r0, c0, m, wrap_vals):
                self.at = (k, p, r0, c0, m)
                self.wrap_vals = wrap_vals

            def __getitem__(self, off):
                k, p, r0, c0, m = self.at
                dp, di, dj = off
                lanes, subs = plan[k]
                s = p + top + dp
                if di % sub:
                    piece = bufs[sub_at[k]][subs.index((di, dj)), s,
                                            pl.ds(c0, m), :]
                elif dj % 128:
                    piece = bufs[lane_at[k]][lanes.index(dj), s,
                                             pl.ds(tile(mt + di + c0), m), :]
                else:
                    piece = slabs[k][cur, s,
                                     pl.ds(tile(mt + di + r0 + c0), m),
                                     pl.ds(ml + dj, Wo)]
                return _KVal(piece) if self.wrap_vals else piece

        def evaluate(p, r0, c0, m):
            """``m`` rows from row ``r0 + c0`` of plane ``p`` of the
            block."""
            def build_args(wrap):
                call_args, ai = [], 0
                for kind, payload in slots:
                    if kind == "arr":
                        call_args.append(_Shift(ai, p, r0, c0, m, wrap))
                        ai += 1
                    else:
                        call_args.append(payload.v)
                return call_args

            val = call_stencil_body(func, build_args).astype(dtype)
            # zero the stencil border in-kernel: a select, never a
            # multiply, because stale VMEM may hold NaN
            g = i * bp + p
            gr = jax.lax.broadcasted_iota(jnp.int32, (m, Wo), 0) + (r0 + c0)
            gc = jax.lax.broadcasted_iota(jnp.int32, (m, Wo), 1)
            valid = ((gr >= -lo[1]) & (gr < H - hi[1])
                     & (gc >= -lo[2]) & (gc < W - hi[2])
                     & (g >= top) & (g < D - bottom))
            rws = pl.ds(tile(r0 + c0), m)
            val = jnp.where(valid, val, jnp.zeros((), dtype))
            if epilogue:
                b = refs[base_at][p, rws, :]
                val = update(*((b, val) if epilogue[1] == 0 else (val, b)))
            out_ref[p, rws, :] = val

        staged = any(lanes or subs for lanes, subs in plan)

        def parts(n, m, body):
            """``body(start)`` over ``n`` rows in parts of ``m``: the last
            part starts early where ``m`` does not divide ``n`` and does
            its first rows again, so that every part is the same traced
            body."""
            loop(-(-n // m), lambda t: body(
                t * m if isinstance(t, int) and (t + 1) * m <= n
                else tile(jnp.minimum(t * m, n - m))))

        def do_rows(r0):
            if staged:
                loop(slab_p, lambda s: stage(s, r0, rows))
            loop(bp, lambda p: parts(
                rows, chunk, lambda c0: evaluate(p, r0, c0, chunk)))

        parts(Ho, rows, do_rows)

    return pl.pallas_call(
        _kernel_body,
        grid=(grid,),
        out_shape=jax.ShapeDtypeStruct((D, H, W), dtype),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bp, Ho, Wo), lambda i: (i, 0, 0),
                               memory_space=pltpu.VMEM),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem_limit),
        interpret=interpret,
        name="ramba_stencil_padded",
    )(*operands)


# Registered kernel family: skeletons._eval_stencil (and anything else)
# reaches this kernel through the backend registry rather than importing
# this module's entry points ad hoc.
_pallas_backend.register_family("stencil", available=available, run=run)
