"""Pallas TPU kernel for 2-D stencils.

The reference's stencil path (/root/reference/ramba/ramba.py:3315-3376)
compiles a ``numba.stencil`` per worker and runs it over halo-padded shards —
its PRK star-stencil benchmark hits ~50 GFlops/node (README.md:281-299).
The rebuild's default path lowers stencils to shifted-slice arithmetic that
XLA fuses (skeletons._eval_stencil); this module adds a hand-tiled Pallas
kernel for the hot case: 2-D float stencils on a single TPU chip.

Design (pallas_guide.md patterns):

* The input is zero-padded by the stencil halo and the lane dimension is
  rounded up to 128.  The kernel grid walks row slabs; each instance waits
  for its slab (rows + halo), whose DMA from HBM into one of two VMEM
  scratch buffers the instance before it started, starts the next slab's,
  then evaluates the user's kernel function over *statically shifted*
  in-VMEM slices — the same trace-the-user-function approach as the XLA
  path, so arbitrary (including nonlinear) stencil bodies work.
* The padded path sizes its row block from the VMEM it asks Mosaic for
  (``_padded_block``), and says what it chose on its kernel note.
* Output blocks are plain VMEM BlockSpecs; borders are zeroed afterwards to
  match sstencil's semantics (the reference writes only indices whose full
  neighborhood is in range).

Multi-chip stencils run through ops/stencil_sharded.py (shard_map +
explicit ppermute halo exchange), which calls back into this kernel on
each shard's halo-extended local block via ``available_local``/``run``.
"""

from __future__ import annotations

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


def available_local(arrs) -> bool:
    """Kernel eligibility for already-local (per-shard) blocks — used from
    inside stencil_sharded's shard_map, where halo exchange has happened
    and the pallas_call sees purely local data."""
    if not _ENABLED:
        return False
    if _pallas_backend.interpret_mode() and not _INTERPRET:
        return False
    shapes = {a.shape for a in arrs}
    if len(shapes) != 1:
        return False
    (shape,) = shapes
    if len(shape) != 2:
        return False
    # one uniform dtype: scratch slabs are allocated with a single dtype
    dtypes = {a.dtype for a in arrs}
    return len(dtypes) == 1 and dtypes <= {jnp.dtype(jnp.float32),
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


def run(func, lo, hi, slots, arrs, taps=8, *, _block_rows=None):
    """Evaluate the stencil with a Pallas kernel.  Returns the full-shape
    result with border cells zeroed (sstencil semantics).  Off-TPU the
    kernel automatically falls back to ``interpret=True`` (rather than
    raising from an impossible Mosaic compile), so the CPU suite — and
    the autotune parity tests — exercise the same code path.
    ``_block_rows`` is scripts/tpu_stencil_sweep.py's: a candidate block
    height in place of the derived one."""
    interpret = _INTERPRET or _pallas_backend.interpret_mode()
    if _fast_eligible(lo, hi, arrs):
        _registry.note_kernel("stencil", "pallas_fast", interpret)
        return _run_fast(func, lo, hi, slots, arrs, taps, interpret,
                         _block_rows)
    return _run_padded(func, lo, hi, slots, arrs, taps, interpret,
                       _block_rows)


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


def _padded_widths(W, halo_c):
    """(Wo, Wi): lane widths of the output block and of the padded input."""
    Wo = _round_up(max(W, 128), 128)
    return Wo, _round_up(Wo + halo_c, 128)


def _padded_vmem_bytes(bh, W, itemsize, n_slabs, taps, halo):
    """What a block of ``bh`` rows asks of VMEM: per input two slabs and
    the value of the one being read, the output block Pallas
    double-buffers, and on Mosaic's stack one (bh, Wo) temporary per
    shifted read plus three of the arithmetic (its refusals read 6 to
    6.5 of them at 8 taps: PERF.md section 6, PR 27)."""
    halo_r, halo_c = halo
    Wo, Wi = _padded_widths(W, halo_c)
    slab_h = _round_up(bh + halo_r, 8)
    return itemsize * (3 * n_slabs * slab_h * Wi
                       + (max(taps, 1) + 5) * bh * Wo) + _VMEM_SLACK


def _padded_block(H, W, itemsize, n_slabs, taps, halo):
    """(rows per block, vmem_limit_bytes) of the padded kernel over an
    ``(H, W)`` array: ``n_slabs`` inputs, ``taps`` shifted reads,
    ``halo = (top + bottom, left + right)``.  The body's cost per row
    falls with the rows it evaluates at once (each unaligned read of bh
    rows touches bh/8 + 1 sublane tiles) and its temporaries and Mosaic's
    compile time rise with them: _BLOCK_ROWS where VMEM allows, fewer
    where the array is wide, never under the 8-row tile."""
    cap = _vmem_cap()

    def need(bh):
        return _padded_vmem_bytes(bh, W, itemsize, n_slabs, taps, halo)

    bh = min(_BLOCK_ROWS, _round_up(H, 8))
    while bh > 8 and need(bh) > cap:
        bh -= 8
    return bh, min(cap, need(bh))


def _run_padded(func, lo, hi, slots, arrs, taps=8, interpret=_INTERPRET,
                block_rows=None):
    """General-shape path: halo-pad the input and walk row slabs, the
    fetch of slab i+1 under the compute of slab i."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    x = arrs[0]
    H, W = x.shape
    dtype = x.dtype
    top, left = -lo[0], -lo[1]
    bottom, right = hi[0], hi[1]
    halo_r = top + bottom
    n_slabs = len(arrs)

    Wo, Wi = _padded_widths(W, left + right)
    if block_rows:
        # the sweep's candidate, under the cap itself
        bh, vmem_limit = _round_up(block_rows, 8), _vmem_cap()
    else:
        bh, vmem_limit = _padded_block(
            H, W, np.dtype(dtype).itemsize, n_slabs, taps,
            (halo_r, left + right))
    grid = -(-H // bh)
    Ho = grid * bh
    _registry.note_kernel("stencil", "pallas_padded", interpret,
                          block_rows=bh, grid=grid,
                          vmem_limit_bytes=vmem_limit)

    # Mosaic requires HBM slices 8-aligned in the sublane dim: round the
    # slab height up and pad the input tail to cover the extra rows read.
    # The input stays padded to Ho + halo + extra rows, so every block's
    # copy is the one static (slab_h, Wi) shape at row i*bh.
    slab_h = _round_up(bh + halo_r, 8)
    extra = slab_h - (bh + halo_r)

    def pad(a):
        return jnp.pad(
            a, ((top, Ho - H + bottom + extra), (left, Wi - W - left)),
        )

    padded = [pad(a) for a in arrs]

    def _kernel_body(*refs):
        # refs: n_slabs HBM inputs, out_ref, n_slabs (2, slab_h, Wi) VMEM
        # slabs, one (2, n_slabs) array of DMA semaphores
        ins = refs[:n_slabs]
        out_ref = refs[n_slabs]
        slabs = refs[n_slabs + 1: 2 * n_slabs + 1]
        sems = refs[-1]
        i = pl.program_id(0)
        cur = jax.lax.rem(i, jnp.asarray(2, i.dtype))

        def copies(j, b):
            # bh is a static multiple of 8: expose that to Mosaic's
            # divisibility prover (same class of refusal as in _run_fast)
            rs = pl.multiple_of(j * (bh // 8) * 8, 8)
            return [
                pltpu.make_async_copy(
                    ins[k].at[pl.ds(rs, slab_h), :], slabs[k].at[b],
                    sems.at[b, k])
                for k in range(n_slabs)
            ]

        @pl.when(i == 0)
        def _():
            for cp in copies(i, cur):
                cp.start()

        if grid > 1:
            @pl.when(i + 1 < grid)
            def _():
                for cp in copies(i + 1, 1 - cur):
                    cp.start()

        for cp in copies(i, cur):
            cp.wait()

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
                    top + di: top + di + bh, left + dj: left + dj + Wo
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
        # zero the stencil border in-kernel (cells whose neighborhood
        # leaves the valid array) — saves a full masking pass afterwards
        gr = jax.lax.broadcasted_iota(jnp.int32, (bh, Wo), 0) + i * bh
        gc = jax.lax.broadcasted_iota(jnp.int32, (bh, Wo), 1)
        valid = (gr >= top) & (gr < H - bottom) & (gc >= left) & (gc < W - right)
        out_ref[:] = jnp.where(valid, val, jnp.zeros((), dtype))

    # out_shape is the exact result shape: pallas clips partial edge
    # blocks, and the kernel masks the stencil border itself, so no
    # post-processing pass is needed.  The NumPy-ufunc retry and branch
    # auto-lowering happen inside the kernel body (call_stencil_body).
    return pl.pallas_call(
        _kernel_body,
        grid=(grid,),
        out_shape=jax.ShapeDtypeStruct((H, W), dtype),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * n_slabs,
        out_specs=pl.BlockSpec((bh, Wo), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        scratch_shapes=(
            [pltpu.VMEM((2, slab_h, Wi), dtype)] * n_slabs
            + [pltpu.SemaphoreType.DMA((2, n_slabs))]
        ),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem_limit),
        interpret=interpret,
        name="ramba_stencil_padded",
    )(*padded)


# Registered kernel family: skeletons._eval_stencil (and anything else)
# reaches this kernel through the backend registry rather than importing
# this module's entry points ad hoc.
_pallas_backend.register_family("stencil", available=available, run=run)
