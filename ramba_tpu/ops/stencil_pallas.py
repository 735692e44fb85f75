"""Pallas TPU kernel for 2-D stencils.

The reference's stencil path (/root/reference/ramba/ramba.py:3315-3376)
compiles a ``numba.stencil`` per worker and runs it over halo-padded shards —
its PRK star-stencil benchmark hits ~50 GFlops/node (README.md:281-299).
The rebuild's default path lowers stencils to shifted-slice arithmetic that
XLA fuses (skeletons._eval_stencil); this module adds a hand-tiled Pallas
kernel for the hot case: 2-D float stencils on a single TPU chip.

Design (pallas_guide.md patterns):

* The input is zero-padded by the stencil halo and the lane dimension is
  rounded up to 128.  The kernel grid walks row slabs; each instance DMAs
  its slab (rows + halo) from HBM into a VMEM scratch buffer, then evaluates
  the user's kernel function over *statically shifted* in-VMEM slices — the
  same trace-the-user-function approach as the XLA path, so arbitrary
  (including nonlinear) stencil bodies work.
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

# VMEM working-set budget for slabs + output block (bytes); a v5e core has
# ~16 MB of VMEM and the runtime needs headroom for double-buffered output.
_VMEM_BUDGET = 8 << 20


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


# Fast-path block height (rows per grid step); sweepable for tuning.
_BH = int(os.environ.get("RAMBA_TPU_STENCIL_BH", "0") or 0)

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


def run(func, lo, hi, slots, arrs, taps=8):
    """Evaluate the stencil with a Pallas kernel.  Returns the full-shape
    result with border cells zeroed (sstencil semantics).  Off-TPU the
    kernel automatically falls back to ``interpret=True`` (rather than
    raising from an impossible Mosaic compile), so the CPU suite — and
    the autotune parity tests — exercise the same code path."""
    interpret = _INTERPRET or _pallas_backend.interpret_mode()
    if _fast_eligible(lo, hi, arrs):
        _registry.note_kernel("stencil", "pallas_fast", interpret)
        return _run_fast(func, lo, hi, slots, arrs, taps, interpret)
    _registry.note_kernel("stencil", "pallas_padded", interpret)
    return _run_padded(func, lo, hi, slots, arrs, taps, interpret)


def _run_fast(func, lo, hi, slots, arrs, taps, interpret=_INTERPRET):
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
    if _BH:
        # clamp the override: blocks below _RM rows or off 8-row alignment
        # would put the mid-block DMA start (j*bh - _RM) out of bounds
        bh = max(_RM, _round_up(_BH, 8))
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


def _run_padded(func, lo, hi, slots, arrs, taps=8, interpret=_INTERPRET):
    """General-shape path: halo-pad the input and walk row slabs."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    x = arrs[0]
    H, W = x.shape
    dtype = x.dtype
    top, left = -lo[0], -lo[1]
    bottom, right = hi[0], hi[1]
    halo_r = top + bottom

    Wo = _round_up(max(W, 128), 128)
    Wi = _round_up(Wo + left + right, 128)

    # Rows per output block within the VMEM budget.  Mosaic materializes
    # one (bh, Wo) temporary per shifted-slice read on its VMEM stack, so
    # the working set is ~ (taps + double-buffered out) output-width blocks
    # plus the input slabs.
    itemsize = np.dtype(dtype).itemsize
    n_slabs = len(arrs)
    denom = itemsize * (n_slabs * Wi + (max(taps, 1) + 3) * Wo)
    bh = max(8, min(512, (_VMEM_BUDGET // denom - halo_r) // 8 * 8))
    grid = -(-H // bh)
    Ho = grid * bh

    # Mosaic requires HBM slices 8-aligned in the sublane dim: round the
    # slab height up and pad the input tail to cover the extra rows read.
    slab_h = _round_up(bh + halo_r, 8)
    extra = slab_h - (bh + halo_r)

    def pad(a):
        return jnp.pad(
            a, ((top, Ho - H + bottom + extra), (left, Wi - W - left)),
        )

    padded = [pad(a) for a in arrs]

    def _kernel_body(*refs):
        # refs: n_slabs HBM inputs, out_ref, n_slabs VMEM scratch, 1 sem
        ins = refs[:n_slabs]
        out_ref = refs[n_slabs]
        slabs = refs[n_slabs + 1: 2 * n_slabs + 1]
        sem = refs[-1]
        i = pl.program_id(0)
        for k in range(n_slabs):
            # bh is a static multiple of 8: expose that to Mosaic's
            # divisibility prover (same class of refusal as in _run_fast)
            rs = pl.multiple_of(i * (bh // 8) * 8, 8)
            cp = pltpu.make_async_copy(
                ins[k].at[pl.ds(rs, slab_h), :], slabs[k], sem
            )
            cp.start()
            cp.wait()

        from ramba_tpu.skeletons import _KVal, call_stencil_body

        class _Shift:
            def __init__(self, ref, wrap_vals):
                self.ref = ref
                self.wrap_vals = wrap_vals

            def __getitem__(self, off):
                if not isinstance(off, tuple):
                    off = (off,)
                di, dj = off
                piece = self.ref[
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
            [pltpu.VMEM((slab_h, Wi), dtype)] * n_slabs
            + [pltpu.SemaphoreType.DMA]
        ),
        interpret=interpret,
        name="ramba_stencil_padded",
    )(*padded)


# Registered kernel family: skeletons._eval_stencil (and anything else)
# reaches this kernel through the backend registry rather than importing
# this module's entry points ad hoc.
_pallas_backend.register_family("stencil", available=available, run=run)
