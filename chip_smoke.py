"""Does the flush path run on the chip?  ``python chip_smoke.py``.

One process, no children, no retries.  It refuses to start unless jax's
first device is a TPU, then drives ``import ramba_tpu as np`` -> lazy DAG
-> flush -> compile -> execute -> write-back -> value read through the
public API only, over the default mesh of every device jax shows (the same
file is the one-chip and the four-chip run): the reference's five
configurations (BASELINE.json ``configs``) at the sizes the reference
publishes, the small semantics flows, and the distributed surface of
``__graft_entry__``.  Every phase is checked against a plain NumPy
computation of the same thing (on slices or bands of rows read back where
a full host copy would cost more than the run), and against the repo's own
record of what happened: flush spans, degradation events, registry
counters, kernel paths, shard placement.  A phase that fell back, retried,
interpreted a kernel or left an array whole on one device FAILS; the
process then exits non-zero.

Timings printed here are smoke timings on the named device (first call
includes tracing and compilation), not benchmark results.

The phases are plain functions of a size so that tests/test_chip_smoke.py
can run them at toy sizes on the CPU mesh; ``main()`` has no CPU mode.
"""

from __future__ import annotations

import json
import math
import os
import sys
import time

import numpy

SEED = 21
#: rows (or elements) per window read back for a NumPy comparison
BAND = 16
SLICE = 1 << 16


class SmokeFailure(AssertionError):
    """A phase's check did not hold."""


def _require(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


# ---------------------------------------------------------------------------
# the repo's own record of what a block of work did
# ---------------------------------------------------------------------------


#: counters that move only when work left the path it claims to be on
_HIDING_COUNTERS = ("skeletons.host_fallback", "stencil.degraded",
                    "resilience.retries", "resilience.degrade",
                    "resilience.host_committed", "memory.admission_rejects")


class Recorder:
    """Collects every event the repo emits while the block runs (an
    ``observe.events`` tap) and the registry counters it moved."""

    def __init__(self, rt):
        self.rt = rt
        self.events = []
        self.counters = {}

    def __enter__(self):
        self._c0 = self.rt.diagnostics.counters()
        self.rt.observe.events.add_tap(self.events.append)
        return self

    def __exit__(self, *exc):
        self.rt.observe.events.remove_tap(self.events.append)
        c1 = self.rt.diagnostics.counters()
        self.counters = {k: v - self._c0.get(k, 0) for k, v in c1.items()
                         if v != self._c0.get(k, 0)}
        return False

    @property
    def flushes(self):
        return [e for e in self.events if e.get("type") == "flush"]

    def kernel_paths(self, kernel="stencil"):
        return [k["path"] for f in self.flushes
                for k in f.get("kernels", ()) if k["kernel"] == kernel]

    def rungs(self):
        return sorted({f.get("degraded", "fused") for f in self.flushes})

    def require_clean(self, *, interpret_ok=False):
        """No flush below the fused rung, no degrade/retry/fault event, no
        host fallback, and (on the chip) no interpreted Pallas kernel."""
        bad = [e for e in self.events
               if e.get("type") in ("degrade", "fault", "flush_error")]
        _require(not bad, f"degrade/fault events: {bad[:3]}")
        _require(self.rungs() in ([], ["fused"]),
                 f"flush ran below the fused rung: {self.rungs()}")
        moved = {n: d for n, d in self.counters.items() if d > 0 and (
            n.startswith(_HIDING_COUNTERS)
            or (n.endswith(".interpret") and not interpret_ok))}
        _require(not moved, f"counters moved: {moved}")
        if not interpret_ok:
            interp = [k for f in self.flushes for k in f.get("kernels", ())
                      if k.get("interpret")]
            _require(not interp, f"interpreted kernels: {interp}")


def _require_sharded(rt, arr, what, default_layout=True):
    """``arr`` is laid out over every device of the live mesh: a
    NamedSharding on that mesh (the default layout for its shape, unless
    the layout is GSPMD's to choose), shards on ``len(jax.devices())``
    distinct devices, each holding 1/ndev of it: nothing replicated,
    nothing whole on device 0.  Returns the PartitionSpec."""
    import jax
    from jax.sharding import NamedSharding

    from ramba_tpu.parallel import mesh as _mesh

    v = arr._value()
    ndev = len(jax.devices())
    _require(isinstance(v.sharding, NamedSharding),
             f"{what}: sharding is {type(v.sharding).__name__}")
    _require(v.sharding.mesh.devices.size == ndev,
             f"{what}: sharded over a mesh of {v.sharding.mesh.devices.size}")
    if default_layout:
        expected = NamedSharding(rt.get_mesh(), _mesh.default_spec(v.shape))
        _require(v.sharding.is_equivalent_to(expected, v.ndim),
                 f"{what}: sharding {v.sharding.spec} != default "
                 f"{expected.spec} for shape {v.shape}")
    shards = v.addressable_shards
    devices = {s.device for s in shards}
    _require(len(devices) == ndev,
             f"{what}: shards on {len(devices)} devices, want {ndev}")
    for s in shards:
        _require(s.data.size * ndev <= v.size + ndev * max(v.shape),
                 f"{what}: a shard holds {s.data.size} of {v.size} "
                 f"elements on {s.device}")
    return v.sharding.spec


def _row_bands(rt, arr):
    """Bands of BAND rows to check: top, bottom, the middle, and one
    straddling every shard boundary along the rows."""
    H = arr.shape[0]
    starts = {0, max(0, H - BAND), max(0, H // 2 - BAND // 2)}
    for s in arr._value().addressable_shards:
        r0 = s.index[0].start or 0
        if 0 < r0 < H:
            starts.add(max(0, min(H - BAND, r0 - BAND // 2)))
    return sorted((a, min(H, a + BAND)) for a in starts)


def _elem_windows(arr, width=SLICE):
    """Windows of a 1-D array to check: start, end, and one straddling
    every shard boundary."""
    n = arr.shape[0]
    width = min(width, n)
    starts = {0, n - width}
    for s in arr._value().addressable_shards:
        b = s.index[0].start or 0
        if 0 < b < n:
            starts.add(max(0, min(n - width, b - width // 2)))
    return sorted((a, a + width) for a in starts)


def _host(x):
    return numpy.asarray(x)


def _timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def _tol(dtype):
    """Elementwise tolerance for a handful of roundings in ``dtype``."""
    return 64 * float(numpy.finfo(dtype).eps)


# ---------------------------------------------------------------------------
# NumPy references
# ---------------------------------------------------------------------------


def star2_np(a):
    """PRK star stencil r=2 with sstencil's zero border, plain NumPy."""
    o = numpy.zeros_like(a)
    if a.shape[0] < 5 or a.shape[1] < 5:
        return o
    o[2:-2, 2:-2] = (
        0.25 * (a[2:-2, 3:-1] + a[2:-2, 1:-3] + a[3:-1, 2:-2] + a[1:-3, 2:-2])
        + 0.125 * (a[2:-2, 4:] + a[2:-2, :-4] + a[4:, 2:-2] + a[:-4, 2:-2])
    )
    return o


def jacobi_np(a):
    """5-point Jacobi sweep (examples/stencil_jacobi.py), plain NumPy."""
    o = numpy.zeros_like(a)
    if a.shape[0] < 3 or a.shape[1] < 3:
        return o
    o[1:-1, 1:-1] = 0.25 * (a[1:-1, 2:] + a[1:-1, :-2]
                            + a[2:, 1:-1] + a[:-2, 1:-1])
    return o


#: NAS MG's A by distance class (centre, face, edge, corner)
_A27 = (-8 / 3, 0.0, 1 / 6, 1 / 12)


def a27_np(a):
    """The 27-point operator of weights ``_A27`` with sstencil's zero
    border, plain NumPy."""
    import itertools

    o = numpy.zeros_like(a)
    if min(a.shape) < 3:
        return o
    inner = 0
    for d in itertools.product((-1, 0, 1), repeat=3):
        c = a.dtype.type(_A27[sum(abs(v) for v in d)])
        inner = inner + c * a[tuple(slice(1 + v, n - 1 + v)
                                    for v, n in zip(d, a.shape))]
    o[1:-1, 1:-1, 1:-1] = inner
    return o


def _check_stencil_bands(rt, x, y, sweep_np, radius, sweeps, what):
    """Compare rows of ``y`` (device) with ``sweeps`` NumPy sweeps over the
    rows of ``x`` (device) they depend on.  A band of output rows [a, b)
    depends on input rows [a - reach, b + reach), reach = radius*sweeps;
    the band is cut only at true array edges, where the NumPy sweep zeroes
    the border exactly as sstencil does."""
    H = x.shape[0]
    reach = radius * sweeps
    worst = 0.0
    for a, b in _row_bands(rt, y):
        lo, hi = max(0, a - reach), min(H, b + reach)
        ref = _host(x[lo:hi])
        # a band cut inside the array gets garbage within `reach` of the
        # cut; those rows are outside [a, b) by construction
        for _ in range(sweeps):
            ref = sweep_np(ref)
        got = _host(y[a:b])
        want = ref[a - lo: b - lo]
        _require(got.shape == want.shape, f"{what}: band shape {got.shape}")
        _require(numpy.isfinite(got).all(), f"{what}: non-finite rows {a}:{b}")
        err = float(numpy.max(numpy.abs(got - want))) if got.size else 0.0
        worst = max(worst, err)
        _require(err <= _tol(got.dtype) * sweeps,
                 f"{what}: rows {a}:{b} differ from NumPy by {err:.3e}")
    return worst


def expected_stencil_paths(n, ndev):
    """The path the code's shape predicates name for an n x n f32 stencil
    of radius <= 8: the sharded ppermute path feeding the padded kernel on
    halo-extended (no longer 128-aligned) local blocks on several devices;
    on one device the fast kernel when n is lane/sublane aligned, else the
    padded one."""
    if ndev > 1:
        return ("sharded", "pallas_padded")
    if n % 128 == 0 and n >= 32:
        return ("pallas_fast",)
    return ("pallas_padded",)


def expected_stencil_paths3(n, ndev):
    """The path an n^3 f32 stencil takes: on one device the general
    Pallas kernel, walking blocks of planes, where the last axis has two
    whole lane tiles (``stencil_pallas._rank3_wins``), else XLA's fusion
    of shifted slices; on several devices the sharded path, whose local
    blocks of rank 3 are XLA's."""
    if ndev > 1:
        return ("sharded", "xla")
    return ("pallas_padded",) if n >= 256 else ("xla",)


def expected_face_walks(n, iters, ndev):
    """How many of the ``(4 lt - 2) iters + 1`` ghost-layer refreshes of
    ``phase_mg``'s float32 solve over an n^3 grid take the in-place walk
    (``faces.path.wrap``): on one chip every refresh of a level whose
    (2^k + 2)^3 array has a whole row tile of 8, four an iteration and
    the first ``resid``'s at the finest; on several devices none."""
    if ndev > 1:
        return 0
    lt = n.bit_length() - 1
    return sum(4 * iters + (k == lt) for k in range(2, lt + 1)
               if 2 ** k + 2 >= 8)


def expected_prolong_path(n, ndev):
    """The path a prolongation onto an n^3 float32 array takes: on one
    device the kernel (``ops/prolong_pallas.py``) from ``MIN_EXTENT`` up,
    else the five writes through XLA."""
    from ramba_tpu.ops import prolong_pallas

    return "pallas" if ndev == 1 and n >= prolong_pallas.MIN_EXTENT else "xla"


# ---------------------------------------------------------------------------
# phases: plain functions of a size; return a dict of facts, raise on failure
# ---------------------------------------------------------------------------


def phase_semantics(rt, n, interpret_ok=False):
    """View aliasing, masked in-place update, and ten fused ``a += 1`` in
    ONE flush with the input buffer donated (peak device memory)."""
    import jax

    with Recorder(rt) as rec:
        base = numpy.arange(64 * 48, dtype=numpy.float32).reshape(64, 48)
        a = rt.fromarray(base.copy())
        t = a.T
        t += 1
        _require(numpy.array_equal(_host(a), base + 1),
                 "t = a.T; t += 1 did not mutate a")
        m = numpy.linspace(-1.0, 1.0, 4096).astype(numpy.float32)
        b = rt.fromarray(m.copy())
        b[b > 0] += 1
        _require(numpy.array_equal(_host(b), numpy.where(m > 0, m + 1, m)),
                 "a[a > 0] += 1 differs from NumPy")

        c = rt.ones(n, dtype=numpy.float32)
        rt.sync()
        _require_sharded(rt, c, "ones(n)")
        nbytes = n * 4
        devs = jax.local_devices()
        before = [d.memory_stats() for d in devs]
        with Recorder(rt) as inner:
            def ten():
                nonlocal c
                for _ in range(10):
                    c += 1
                rt.sync()
            _, first = _timed(ten)
            _, second = _timed(ten)
        _require(len(inner.flushes) == 2,
                 f"ten fused a += 1 took {len(inner.flushes)} flushes for "
                 f"two rounds, want one each")
        after = [d.memory_stats() for d in devs]
        grew = None
        shard = nbytes / len(devs)
        if all(before) and all(after) and all(
                b["peak_bytes_in_use"] <= b["bytes_in_use"] + 0.5 * shard
                for b in before):
            # the peak so far is below what a second copy of the shard
            # would reach, so the peak can tell: with the input donated the
            # output reuses its buffer; without, a second copy is live
            grew = max(a_["peak_bytes_in_use"] - b_["bytes_in_use"]
                       for a_, b_ in zip(after, before))
            _require(grew <= 0.5 * shard,
                     f"peak device memory grew by {grew} bytes over the "
                     f"resident {int(shard)}-byte shard: donation did not "
                     f"hold")
        win = _elem_windows(c, 1024)[-1]
        _require(numpy.array_equal(_host(c[win[0]:win[1]]),
                                   numpy.full(win[1] - win[0], 21.0,
                                              numpy.float32)),
                 "ten fused a += 1, twice, did not give 21")
        _require_sharded(rt, c, "a += 1 result")
        del c
    rec.require_clean(interpret_ok=interpret_ok)
    return {"n": n, "flushes": len(rec.flushes), "rungs": rec.rungs(),
            "peak_growth_bytes": grew, "first_s": first, "second_s": second}


def phase_distributed(rt, interpret_ok=False):
    """The distributed surface of ``__graft_entry__`` on the live mesh:
    matmul, stencil halo exchange, scan carry, group-by, smap branches,
    spmd and halo, through the chip's compiler at small size."""
    import jax

    from __graft_entry__ import distributed_surface

    # XLA's default matmul precision on the TPU is one bf16 MXU pass
    # (8 mantissa bits): products of O(n) values summed over n terms
    on_tpu = jax.devices()[0].platform == "tpu"
    with Recorder(rt) as rec:
        _, first = _timed(lambda: distributed_surface(
            matmul_rtol=2e-2 if on_tpu else 2e-4))
    rec.require_clean(interpret_ok=interpret_ok)
    return {"flushes": len(rec.flushes), "rungs": rec.rungs(),
            "stencil_paths": sorted(set(rec.kernel_paths())),
            "first_s": first}


def phase_chain(rt, n, interpret_ok=False):
    """BASELINE configs 1 and 2: the headline chain with A, B, C dropped,
    read through ``float(sum(D))`` in ONE flush, then mean/min/max of the
    same array."""
    import jax

    if not jax.config.jax_enable_x64:
        # arange is an int64 iota truncated to int32 in the x32 regime
        # the chip runs in: exact below 2**31, wrong above (BASELINE's
        # 4e9 needs an extent this regime cannot index)
        _require(n < 2 ** 31, f"n={n} does not fit the x32 regime's iota")

    def chain():
        A = rt.arange(n) / 1000.0
        B = rt.sin(A)
        C = rt.cos(A)
        D = B * B + C ** 2
        del A, B, C
        return D, float(rt.sum(D))

    with Recorder(rt) as rec:
        with Recorder(rt) as r1:
            (D, s), first = _timed(chain)
        _require(len(r1.flushes) == 1,
                 f"the chain took {len(r1.flushes)} flushes, want ONE")
        del D
        with Recorder(rt) as r2:
            (D, s2), second = _timed(chain)
        _require(len(r2.flushes) == 1 and r2.flushes[0]["cache"] == "hit",
                 f"second chain call: {len(r2.flushes)} flushes, cache "
                 f"{[f['cache'] for f in r2.flushes]}")
        _require(s == s2, f"two runs of the chain summed to {s} and {s2}")
        eps = float(numpy.finfo(D.dtype).eps)
        # every element is 1 within a few roundings; a pairwise or blocked
        # sum of n of them errs by at most ~eps*log2(n) relative
        sum_rtol = eps * (8 + numpy.log2(n))
        _require(abs(s - n) <= sum_rtol * n,
                 f"sum(D) = {s!r}, want {n} within {sum_rtol:.1e}")
        _require_sharded(rt, D, "chain D")
        worst = 0.0
        for a, b in _elem_windows(D):
            x = (numpy.arange(a, b, dtype=numpy.int64).astype(D.dtype)
                 / D.dtype.type(1000.0))
            sb, cb = numpy.sin(x), numpy.cos(x)
            ref = sb * sb + cb ** 2
            got = _host(D[a:b])
            _require(numpy.isfinite(got).all(), f"D[{a}:{b}] not finite")
            err = float(numpy.max(numpy.abs(got - ref)))
            worst = max(worst, err)
            _require(err <= _tol(D.dtype), f"D[{a}:{b}] off NumPy by {err:.2e}")
        with Recorder(rt) as r3:
            def reductions():
                m, lo, hi = rt.mean(D), rt.min(D), rt.max(D)
                return float(m), float(lo), float(hi)
            (mean, lo, hi), red_s = _timed(reductions)
        _require(abs(mean - 1.0) <= sum_rtol, f"mean(D) = {mean!r}")
        _require(1.0 - _tol(D.dtype) <= lo <= 1.0 <= hi <= 1.0 + _tol(D.dtype),
                 f"min(D), max(D) = {lo!r}, {hi!r}")
        dtype = str(D.dtype)
        del D
    rec.require_clean(interpret_ok=interpret_ok)
    return {"n": n, "dtype": dtype, "chain_flushes": len(r1.flushes),
            "reduction_flushes": len(r3.flushes), "rungs": rec.rungs(),
            "sum": s, "mean": mean, "min": lo, "max": hi,
            "max_abs_err": worst, "first_s": first, "second_s": second,
            "reductions_s": red_s}


def _random_f32(rt, shape):
    """Input made on the device from SEED (loading is set-up, not work)."""
    x = rt.random.random(shape).astype(numpy.float32)
    rt.sync()
    return x


def phase_groupby(rt, days, grid, groups=366, interpret_ok=False):
    """The Xarray day-of-year pattern over a small cube on the live
    mesh, against NumPy: the mean and the max of every day of the year
    (the sorted chunked walk: on several devices each walks its own block
    inside ``shard_map`` and the partials are combined, which is where the
    scatter it replaced miscompiled), and the RMS of the anomalies (the
    walk again, nothing stored, on one device and on several).  The cube
    is a flush's result on a ragged grid, so on one chip it lies
    row-major where the compiler would have put time last
    (``core/layouts.py``); with ``days`` that the devices do not divide
    (2922 on four) the default layout is the split that does divide, time
    2 x longitude 2, and every device still holds its share.  Then the
    two corners of the walk no cell
    stands on: twelve groups of thousands of narrow rows (each chunk one
    gather), and the minimum on the eager rung, where every op meets the
    pinned cube without a jit around it."""
    from ramba_tpu.resilience import faults

    labels = (numpy.arange(days) % 365 + (numpy.arange(days) // 1461)
              ).astype(numpy.int32) % groups
    rng = numpy.random.default_rng(SEED)
    x = rt.fromarray(rng.random((days,) + tuple(grid),
                                dtype=numpy.float32)) * 1.0
    narrow = rt.fromarray(rng.random((days * 16, 8), dtype=numpy.float32))
    months = (numpy.arange(days * 16) // 61 % 12).astype(numpy.int32)
    rt.sync()
    _require_sharded(rt, x, "groupby operand")
    with Recorder(rt) as rec:
        def run():
            g = x.groupby(0, labels, groups)
            clim = g.mean()
            rms = float((((g - clim) ** 2).mean()) ** 0.5)
            return clim, g.max(), rms

        (clim, top, rms), first = _timed(run)
        _require_sharded(rt, clim, "groupby climatology")
        got, got_top = _host(clim), _host(top)
        (_, _, again), second = _timed(run)
        by_month = _host(narrow.groupby(0, months, 12).sum())
    rec.require_clean(interpret_ok=interpret_ok)
    fetches = sorted({k["fetch"] for f in rec.flushes
                      for k in f.get("kernels", ()) if k["kernel"] == "segment"})
    _require(fetches == ["gather", "slices"], f"segment fetches {fetches}")
    nn = _host(narrow).astype(numpy.float64)
    want_month = numpy.stack([nn[months == m].sum(0) for m in range(12)])
    _require(numpy.allclose(by_month, want_month, rtol=2e-5, atol=0),
             "the monthly sums differ from NumPy")
    with Recorder(rt) as low, faults.inject("compile", "always"):
        bottom = _host(x.groupby(0, labels, groups).min())
    _require(low.rungs() == ["eager"], f"forced off the jit: {low.rungs()}")
    xn = _host(x).astype(numpy.float64)
    want = numpy.full((groups,) + tuple(grid), numpy.nan)
    want_top = numpy.full(want.shape, -numpy.inf)
    for g in range(groups):
        members = xn[labels == g]
        if len(members):
            want[g], want_top[g] = members.mean(0), members.max(0)
    want_rms = float(numpy.sqrt(numpy.mean((xn - want[labels]) ** 2)))
    full = ~numpy.isnan(want[:, 0, 0])
    err = float(numpy.max(numpy.abs(got[full] - want[full])))
    _require(numpy.isnan(got[~full]).all(), "an empty day has a mean")
    _require(err <= _tol(numpy.float32), f"clim off NumPy by {err:.3e}")
    _require(numpy.array_equal(got_top, want_top.astype(numpy.float32)),
             "the maxima differ from NumPy")
    want_bottom = numpy.stack([xn[labels == g].min(0, initial=numpy.inf)
                               for g in range(groups)])
    _require(numpy.array_equal(bottom, want_bottom.astype(numpy.float32)),
             "the minima on the eager rung differ from NumPy")
    _require(abs(rms - want_rms) <= 1e-5 * want_rms and again == rms,
             f"rms {rms!r}, {again!r}, NumPy {want_rms!r}")
    paths = sorted(k[len("segment.path."):] for k, v in rec.counters.items()
                   if k.startswith("segment.path.") and v > 0)
    _require(paths == ["walk_broadcast", "walk_reduce"],
             f"segment paths {paths}")
    return {"flushes": len(rec.flushes), "rungs": rec.rungs(),
            "segment_paths": paths, "fetches": fetches, "max_abs_err": err,
            "rms": rms, "first_s": first, "second_s": second,
            "spec": str(x._value().sharding.spec),
            "layout": str(getattr(x._value(), "format", None))}


def _star2(rt):
    """The PRK star stencil r=2 (13 flops per interior point)."""
    @rt.stencil
    def star2(a):
        return (
            0.25 * (a[0, 1] + a[0, -1] + a[1, 0] + a[-1, 0])
            + 0.125 * (a[0, 2] + a[0, -2] + a[2, 0] + a[-2, 0])
        )

    return star2


def phase_stencil(rt, n, expect, interpret_ok=False):
    """BASELINE config 3, PRK flavour: one star r=2 sweep over n x n f32,
    on the path the shape predicates name, checked on bands of rows."""
    star2 = _star2(rt)
    with Recorder(rt) as rec:
        x = _random_f32(rt, (n, n))

        def sweep():
            y = rt.sstencil(star2, x)
            rt.sync()
            return y

        with Recorder(rt) as r1:
            y, first = _timed(sweep)
        paths = tuple(dict.fromkeys(r1.kernel_paths()))
        _require(paths == tuple(expect),
                 f"stencil {n}^2 took path {paths}, want {tuple(expect)}")
        del y
        with Recorder(rt) as r2:
            y, second = _timed(sweep)
        _require([f["cache"] for f in r2.flushes] == ["hit"],
                 f"second sweep: {[f['cache'] for f in r2.flushes]}")
        _require_sharded(rt, y, f"stencil {n}^2 output")
        worst = _check_stencil_bands(rt, x, y, star2_np, 2, 1,
                                     f"star2 {n}^2")
        del x, y
    rec.require_clean(interpret_ok=interpret_ok)
    return {"n": n, "path": "+".join(paths), "rungs": rec.rungs(),
            "max_abs_err": worst, "first_s": first, "second_s": second}


def phase_stencil3(rt, n, expect, interpret_ok=False):
    """NAS MG's 27-point A (``benchmark/programs/nas_mg.py``) over an n^3
    f32 array: one sweep on the path ``expect`` names, checked on bands of
    planes against NumPy and everywhere against XLA's fusion of shifted
    slices over the same device array."""
    import jax
    import jax.numpy as jnp

    from benchmark.programs import nas_mg
    from ramba_tpu import skeletons

    op = nas_mg.stencil27(rt, _A27)
    with Recorder(rt) as rec:
        x = _random_f32(rt, (n, n, n))

        def sweep():
            y = rt.sstencil(op, x)
            rt.sync()
            return y

        with Recorder(rt) as r1:
            y, first = _timed(sweep)
        paths = tuple(dict.fromkeys(r1.kernel_paths()))
        _require(paths == tuple(expect),
                 f"stencil {n}^3 took path {paths}, want {tuple(expect)}")
        copies = r1.counters.get("stencil.operand_copy", 0)
        _require(not copies,
                 f"stencil {n}^3: {copies} operands travelled in a copy")
        del y
        with Recorder(rt) as r2:
            y, second = _timed(sweep)
        _require([f["cache"] for f in r2.flushes] == ["hit"],
                 f"second sweep: {[f['cache'] for f in r2.flushes]}")
        _require_sharded(rt, y, f"stencil {n}^3 output")
        worst = _check_stencil_bands(rt, x, y, a27_np, 1, 1, f"A27 {n}^3")
        slots = (("arr", 0),)
        lo, hi, _ = op.neighborhood(slots)

        @jax.jit
        def off_xla(xv, yv):
            ref = skeletons.stencil_interior(op.func, lo, hi, slots, [xv])
            ref = jnp.zeros_like(xv).at[1:-1, 1:-1, 1:-1].set(ref)
            return jnp.max(jnp.abs(yv - ref))

        vs_xla = float(off_xla(x._value(), y._value()))
        _require(vs_xla <= _tol(numpy.float32),
                 f"A27 {n}^3 differs from the XLA path by {vs_xla:.3e}")
        del x, y
    rec.require_clean(interpret_ok=interpret_ok)
    return {"n": n, "path": "+".join(paths), "rungs": rec.rungs(),
            "max_abs_err": worst, "max_abs_vs_xla": vs_xla,
            "first_s": first, "second_s": second}


def phase_stencil_sweeps(rt, n, sweeps, jacobi_iters, expect,
                         interpret_ok=False):
    """``sstencil_iterate`` (sweeps of star r=2 in one on-device loop) and
    the chained 5-point Jacobi of examples/stencil_jacobi.py."""
    star2 = _star2(rt)

    @rt.stencil
    def jacobi(a):
        return 0.25 * (a[0, 1] + a[0, -1] + a[1, 0] + a[-1, 0])

    with Recorder(rt) as rec:
        x = _random_f32(rt, (n, n))

        def iterate():
            y = rt.sstencil_iterate(star2, x, sweeps)
            rt.sync()
            return y

        with Recorder(rt) as r1:
            y, first = _timed(iterate)
        paths = tuple(r1.kernel_paths())
        _require(len(r1.flushes) == 1, f"{len(r1.flushes)} flushes")
        # the sweep body is traced twice: once for its output type
        _require(set(paths) == set(expect) and paths,
                 f"sstencil_iterate took {paths}, want {tuple(expect)}")
        worst_it = _check_stencil_bands(rt, x, y, star2_np, 2, sweeps,
                                        f"sstencil_iterate x{sweeps}")
        del y

        def chained():
            y = x
            for _ in range(jacobi_iters):
                y = rt.sstencil(jacobi, y)
            s = float(rt.sum(y))
            return y, s

        with Recorder(rt) as r2:
            (y, s), second = _timed(chained)
        _require(len(r2.flushes) == 1,
                 f"chained Jacobi took {len(r2.flushes)} flushes")
        jpaths = tuple(r2.kernel_paths())
        _require(set(jpaths) == set(expect) and jpaths,
                 f"Jacobi took {jpaths}, want {tuple(expect)}")
        _require(numpy.isfinite(s), f"sum after Jacobi = {s!r}")
        worst_j = _check_stencil_bands(rt, x, y, jacobi_np, 1, jacobi_iters,
                                       f"Jacobi x{jacobi_iters}")
        _require_sharded(rt, y, "Jacobi output")
        del x, y
    rec.require_clean(interpret_ok=interpret_ok)
    return {"n": n, "sweeps": sweeps, "jacobi_iters": jacobi_iters,
            "path": "+".join(dict.fromkeys(paths)), "rungs": rec.rungs(),
            "max_abs_err": max(worst_it, worst_j),
            "iterate_first_s": first, "jacobi_first_s": second}


def phase_prk_scalars(rt, n, iters, expect, interpret_ok=False):
    """Two PRK solves (``B += stencil(A); A += 1.0``, ``iters`` times in
    one flush, then the norm): in the second no scalar operand crosses to
    the device.  Each ``1.0`` is found resident (``dispatch.scalar.hit``)
    and none is put (``dispatch.scalar.put``); a solve as slow as the
    first would say the executable copies the resident arrays instead of
    taking them where they lie."""
    @rt.stencil
    def star(a):  # PRK's weights, r = 2: one sweep of i + j adds 2
        return (0.25 * (a[0, 1] - a[0, -1] + a[1, 0] - a[-1, 0])
                + 0.125 * (a[0, 2] - a[0, -2] + a[2, 0] - a[-2, 0]))

    with Recorder(rt) as rec:
        i = rt.arange(n, dtype=numpy.float32)
        A = i[:, None] + i[None, :]
        B = rt.zeros((n, n), dtype=numpy.float32)
        rt.sync()

        def solve():
            nonlocal A, B
            for _ in range(iters):
                B += rt.sstencil(star, A)
                A += 1.0
            return float(rt.sum(abs(B))) / (n - 4) ** 2

        with Recorder(rt) as r1:
            norm1, first = _timed(solve)
        with Recorder(rt) as r2:
            norm2, second = _timed(solve)
        for T, norm in ((iters, norm1), (2 * iters, norm2)):
            _require(abs(norm - 2.0 * T) <= 1e-4 * 2.0 * T,
                     f"PRK norm after {T} iterations = {norm!r}")
        _require(len(r2.flushes) == 1 and r2.flushes[0]["cache"] == "hit",
                 f"second solve: {[f['cache'] for f in r2.flushes]}")
        _require(set(r1.kernel_paths()) == set(expect),
                 f"PRK took {r1.kernel_paths()}, want {tuple(expect)}")
        hits = r2.counters.get("dispatch.scalar.hit", 0)
        puts = r2.counters.get("dispatch.scalar.put", 0)
        _require(puts == 0 and hits >= iters,
                 f"second solve: {hits} scalar operands found resident, "
                 f"{puts} put; want at least {iters} and 0")
        _require_sharded(rt, B, "PRK B")
        del A, B
    rec.require_clean(interpret_ok=interpret_ok)
    return {"n": n, "iters": iters, "rungs": rec.rungs(),
            "scalar_puts_first": r1.counters.get("dispatch.scalar.put", 0),
            "scalar_hits_second": hits, "scalar_puts_second": puts,
            "first_s": first, "second_s": second}


def phase_mg(rt, n, want=None, iters=1, interpret_ok=False):
    """NAS MG's four operators over the pyramid of an n^3 grid
    (``benchmark/programs/nas_mg.py``: ``iters`` V-cycles and the
    residual, twice over): ONE flush on the fused rung, the second time a
    hit; one too long for one program (a V-cycle at 512^3 is 110
    instructions, a program at most 768) runs as chained segments, the
    second time from the executables of the first.  The second solve's
    DAG is the first's served from the lazy layer's memos: its indexes
    from ``ndarray._index_memo`` (``dag.index.hit``), its inferences by
    keys found unwalked (``dag.key.hit``), each at least 95 %.
    ``want`` is the norm to meet; the NumPy reference gives it where it is
    not given (toy sizes: at 512^3 it takes minutes)."""
    from benchmark.programs import nas_mg

    cfg = {"n": n, "iterations": iters, "dtype": "float32",
           "smoother": "B+", "norm": want,
           "as_published": {"n": n, "iterations": iters},
           "assumed": {"norm_rtol": 1e-4, "window_rtol": 2e-5}}
    if want is None:
        cfg["norm"] = nas_mg.mg_np(n, iters, numpy.float64, "B+")[0][-1]
    prog = nas_mg.Program(rt, cfg, {"solve": [{"op": "mg"}]},
                          numpy.random.default_rng(SEED), 1)
    with Recorder(rt) as rec:
        prog.setup()
        with Recorder(rt) as r1:
            out1, first = _timed(prog.solve)
        with Recorder(rt) as r2:
            out2, second = _timed(prog.solve)
        for out in (out1, out2):
            bad = prog.check(out)
            _require(bad is None, bad)
        _require(out1 == out2, f"two solves read {out1} and {out2}")
        calls = r2.counters.get("fuser.segments", 0)
        hits = r2.counters.get("fuser.segment.hit", 0)
        long = r2.flushes[0]["instrs"] > rt.common.max_program_instrs
        _require(calls == hits and (calls >= 2) == long
                 and not r2.counters.get("fuser.segment.miss", 0),
                 f"second solve: {calls} segment calls, {hits} hits")
        _require([f["cache"] for f in r2.flushes] == ["hit"],
                 f"second solve: {[f['cache'] for f in r2.flushes]}")
        c = r2.counters
        index_hits = c.get("dag.index.hit", 0) / max(c.get("dag.index.n", 0), 1)
        key_hits = c.get("dag.key.hit", 0) / max(c.get("dag.infer.hit", 0), 1)
        _require(index_hits >= 0.95 and key_hits >= 0.95,
                 f"second solve: {index_hits:.3f} of the indexes and "
                 f"{key_hits:.3f} of the inference hits from the memos")
        paths = sorted(k[len("stencil.path."):] for k, v in
                       r2.counters.items()
                       if k.startswith("stencil.path.") and v > 0)
        # every ``comm3`` is ONE node (4 lt - 2 an iteration and the first
        # ``resid``'s); on one chip the levels with a whole row tile take
        # the in-place walk (off the chip the kernel is not offered)
        wraps = r2.counters.get("faces.path.wrap", 0)
        writes = r2.counters.get("faces.path.dus", 0)
        _require(wraps + writes == (4 * prog.lt - 2) * iters + 1,
                 f"second solve: {wraps} + {writes} ghost-layer refreshes")
        walks = 0 if interpret_ok else expected_face_walks(
            n, iters, rt.get_mesh().devices.size)
        _require(wraps == walks,
                 f"faces.path.wrap moved by {wraps}, expected {walks}")
        if rt.get_mesh().devices.size == 1:
            # on a mesh the layouts of the pyramid's nine sizes are
            # GSPMD's to choose: nothing to hold them to
            _require_sharded(rt, prog.u, "MG u")
        prog.u = prog.r = prog.v = None
    rec.require_clean(interpret_ok=interpret_ok)
    return {"n": n, "norm": out2[0], "want": cfg["norm"],
            "path": "+".join(paths), "faces": f"wrap:{wraps}+dus:{writes}",
            "rungs": rec.rungs(),
            "instrs": r2.flushes[0]["instrs"], "segments": calls,
            "segment_hits_second": hits,
            "segment_misses_first": r1.counters.get("fuser.segment.miss", 0),
            "index_hit_share_second": index_hits,
            "key_hit_share_second": key_hits,
            "first_s": first, "second_s": second}


def phase_prolong(rt, n, expect, interpret_ok=False):
    """NAS MG's ``interp`` onto a fresh n^3 float32 array
    (``benchmark/programs/nas_mg.py`` ``prolong``): five writes folded into
    ONE node (``rewrite.rewrite_prolong`` four times) on the path
    ``expect`` names, counted once by a flush that hits (one that compiles
    also counts admission's lowering), and every bit the five writes' as
    the fold-off script makes them over the same device array."""
    from benchmark.programs import nas_mg

    with Recorder(rt) as rec:
        z = _random_f32(rt, (n // 2 + 1,) * 3)

        def interp():
            f = nas_mg.prolong(z, rt.zeros((n,) * 3, dtype=numpy.float32))
            rt.sync()
            return f

        with Recorder(rt) as r1:
            f, first = _timed(interp)
        folded = r1.counters.get("rewrite.rewrite_prolong", 0)
        paths = tuple(dict.fromkeys(r1.kernel_paths("prolong")))
        _require(folded == 4 and paths == (expect,),
                 f"prolong {n}^3: {folded} writes folded, took {paths}, "
                 f"want 4 and ({expect!r},)")
        del f
        with Recorder(rt) as r2:
            f, second = _timed(interp)
        counted = r2.counters.get(f"prolong.path.{expect}", 0)
        caches = [fl["cache"] for fl in r2.flushes]
        _require(counted == 1 and caches == ["hit"],
                 f"prolong {n}^3 again: prolong.path.{expect} moved by "
                 f"{counted}, flushes {caches}")
        rt.common.rewrite_enabled = False
        try:
            plain = interp()
        finally:
            rt.common.rewrite_enabled = True
        got, want = _host(f), _host(plain)
        _require(numpy.array_equal(got.view(numpy.uint32),
                                   want.view(numpy.uint32)),
                 f"prolong {n}^3: {int((got != want).sum())} elements "
                 f"differ from the five writes")
        del z, f, plain
    rec.require_clean(interpret_ok=interpret_ok)
    return {"n": n, "path": expect, "rungs": rec.rungs(), "first_s": first,
            "second_s": second}


def expected_transpose_path(n, ndev):
    """The path ``ops/transpose_sharded.py`` names for an n x n float32
    transpose under the default mesh of ``ndev`` devices: the swap of
    blocks on a square grid whose blocks are whole lane tiles, the local
    transpose on one device, GSPMD's otherwise."""
    p = math.isqrt(ndev)
    if ndev == 1:
        return "local"
    return "swap" if p * p == ndev and n % (128 * p) == 0 else "xla"


def phase_transpose(rt, n, expect, iters=3, interpret_ok=False):
    """PRK Transpose's ``B += A.T; A += 1`` (``benchmark/programs/
    prk_transpose.py``) on an n x n float32 matrix made as PRK makes it:
    ``iters`` iterations a flush, each folded into ONE node
    (``rewrite.rewrite_add_transposed``), on the path ``expect`` names,
    counted once an iteration by a flush that hits (one that compiles
    also counts admission's lowering), ``A`` and ``B`` in the default
    layout where they swap and every bit NumPy float32's."""
    f32 = numpy.float32
    with Recorder(rt) as rec:
        i = rt.arange(n, dtype=f32)
        A = i[:, None] * n + i[None, :]
        B = rt.zeros((n, n), dtype=f32)
        rt.sync()
        An = numpy.arange(n, dtype=f32)[:, None] * f32(n) + numpy.arange(
            n, dtype=f32)[None, :]
        Bn = numpy.zeros((n, n), f32)

        def iterate():
            nonlocal A, B, An, Bn
            for _ in range(iters):
                B += A.T
                A += 1.0
                Bn += An.T
                An += f32(1)
            rt.sync()

        with Recorder(rt) as r1:
            _, first = _timed(iterate)
        folded = r1.counters.get("rewrite.rewrite_add_transposed", 0)
        paths = tuple(dict.fromkeys(r1.kernel_paths("transpose")))
        _require(folded == iters, f"transpose {n}^2: {folded} of {iters} "
                                  f"updates folded")
        with Recorder(rt) as r2:
            _, second = _timed(iterate)
        counted = r2.counters.get(f"transpose.path.{expect}", 0)
        caches = [fl["cache"] for fl in r2.flushes]
        _require(counted == iters and paths == (expect,) and caches == ["hit"],
                 f"transpose {n}^2 again: took {paths}, transpose.path."
                 f"{expect} moved by {counted}, flushes {caches}")
        for name, x, want in (("A", A, An), ("B", B, Bn)):
            # GSPMD's transpose leaves the layout GSPMD's
            _require_sharded(rt, x, f"transpose {name}",
                             default_layout=expect == "swap")
            _require(numpy.array_equal(_host(x), want),
                     f"transpose {n}^2: {name} differs from NumPy")
        sent = r2.counters.get("transpose.exchange_bytes", 0)
        del A, B
    rec.require_clean(interpret_ok=interpret_ok)
    return {"n": n, "path": expect, "rungs": rec.rungs(),
            "exchange_bytes": sent, "first_s": first, "second_s": second}


def phase_axpy(rt, n_total, interpret_ok=False):
    """BASELINE config 4: ``random.normal`` fill, then ``Y += a*X`` in
    place, ``n_total`` elements in X and Y together."""
    n = n_total // 2
    alpha = 2.5
    with Recorder(rt) as rec:
        rt.random.seed(SEED)
        X = rt.random.normal(size=n).astype(numpy.float32)
        Y = rt.random.normal(size=n).astype(numpy.float32)
        rt.sync()
        _require_sharded(rt, X, "normal X")
        wins = _elem_windows(Y)
        xs = [_host(X[a:b]) for a, b in wins]
        ys = [_host(Y[a:b]) for a, b in wins]
        # the fill is standard normal: mean 0, std 1 (5 sigma of the mean)
        mx, sx = float(rt.mean(X)), float(rt.std(X))
        _require(abs(mx) <= 5.0 / numpy.sqrt(n) + 1e-6 and abs(sx - 1) < 1e-2,
                 f"normal fill has mean {mx!r}, std {sx!r}")

        def axpy():
            nonlocal Y
            Y += alpha * X
            rt.sync()

        with Recorder(rt) as r1:
            _, first = _timed(axpy)
        _require(len(r1.flushes) == 1, f"axpy took {len(r1.flushes)} flushes")
        worst = 0.0
        for (a, b), xw, yw in zip(wins, xs, ys):
            got = _host(Y[a:b])
            ref = numpy.float32(alpha) * xw + yw
            _require(numpy.isfinite(got).all(), f"Y[{a}:{b}] not finite")
            # a fused multiply-add rounds once where NumPy rounds twice
            err = float(numpy.max(numpy.abs(got - ref)))
            worst = max(worst, err)
            _require(err <= _tol(got.dtype) * 8,
                     f"Y[{a}:{b}] off NumPy by {err:.2e}")
        with Recorder(rt) as r2:
            _, second = _timed(axpy)
        _require([f["cache"] for f in r2.flushes] == ["hit"],
                 f"second axpy: {[f['cache'] for f in r2.flushes]}")
        _require_sharded(rt, Y, "axpy Y")
        del X, Y
    rec.require_clean(interpret_ok=interpret_ok)
    return {"n_total": 2 * n, "rungs": rec.rungs(), "max_abs_err": worst,
            "first_s": first, "second_s": second}


def phase_broadcast(rt, n, interpret_ok=False):
    """BASELINE config 5: ``A[:, None] + B[None, :]`` materialized at
    n x n, summed, and checked on bands of rows."""
    with Recorder(rt) as rec:
        rt.random.seed(SEED + 1)
        A = rt.random.uniform(size=n).astype(numpy.float32)
        B = rt.random.uniform(size=n).astype(numpy.float32)
        rt.sync()
        a_np, b_np = _host(A), _host(B)

        def outer():
            C = A[:, None] + B[None, :]
            return C, float(rt.sum(C))

        with Recorder(rt) as r1:
            (C, s), first = _timed(outer)
        _require(len(r1.flushes) == 1, f"{len(r1.flushes)} flushes")
        del C
        with Recorder(rt) as r2:
            (C, s2), second = _timed(outer)
        _require([f["cache"] for f in r2.flushes] == ["hit"],
                 f"second call: {[f['cache'] for f in r2.flushes]}")
        _require(C.shape == (n, n), f"shape {C.shape}")
        # computed from two 1-D operands: the layout is GSPMD's choice
        spec = _require_sharded(rt, C, "broadcast C", default_layout=False)
        want = float(n) * (a_np.sum(dtype=numpy.float64)
                           + b_np.sum(dtype=numpy.float64))
        eps = float(numpy.finfo(C.dtype).eps)
        _require(s == s2 and abs(s - want) <= want * eps * (8 + 2 * numpy.log2(n)),
                 f"sum(C) = {s!r}, {s2!r}; NumPy says {want!r}")
        for a, b in _row_bands(rt, C):
            got = _host(C[a:b])
            ref = a_np[a:b, None] + b_np[None, :]
            _require(numpy.array_equal(got, ref),
                     f"C[{a}:{b}] differs from NumPy")
        del A, B, C
    rec.require_clean(interpret_ok=interpret_ok)
    return {"n": n, "rungs": rec.rungs(), "layout": str(spec), "sum": s,
            "first_s": first, "second_s": second}


# ---------------------------------------------------------------------------
# main: the chip, or nothing
# ---------------------------------------------------------------------------


def _need(facts, key):
    _require(facts[key] is not None, f"{key} could not be established")
    return facts


def _count_files(path):
    return sum(len(files) for _, _, files in os.walk(path))


def _fmt(v):
    if not isinstance(v, float):
        return str(v).replace(" ", "")
    return f"{v:.4g}" if abs(v) < 1e4 else repr(v)


def result_line(ok, dev, ndev):
    """The last line of standard output, read by the driver: one JSON
    object with exactly the keys ``ok`` and ``device``, the device as jax
    reports it.  Anything else the run has to say goes on the lines above."""
    return json.dumps({
        "ok": bool(ok),
        "device": {"platform": str(dev.platform),
                   "kind": str(dev.device_kind), "count": int(ndev)},
    })


def main() -> int:
    t_start = time.perf_counter()
    import jax

    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU: jax's first device is "
              f"{dev.platform}:{dev.device_kind} (of {len(devs)}); this "
              f"script only runs on the chip", file=sys.stderr)
        return 1

    import importlib.metadata as md

    import jaxlib

    import ramba_tpu as rt

    cache = rt.common.compile_cache_dir()
    files0 = _count_files(cache)
    ndev = len(devs)
    print(f"chip_smoke: platform={dev.platform} device_kind={dev.device_kind!r}"
          f" devices={ndev} default_backend={jax.default_backend()}")
    print(f"chip_smoke: jax={jax.__version__} jaxlib={jaxlib.__version__} "
          f"libtpu={md.version('libtpu')} x64={jax.config.jax_enable_x64}")
    print(f"chip_smoke: compile cache dir={cache} "
          f"(jax: {jax.config.jax_compilation_cache_dir}) "
          f"files_at_start={files0}")
    mesh = rt.get_mesh()
    print(f"chip_smoke: mesh={dict(mesh.shape)} bring_up_s="
          f"{time.perf_counter() - t_start:.2f}")
    _require(jax.config.jax_compilation_cache_dir == cache,
             "jax's compile cache is not where common.compile_cache_dir says")
    _require(mesh.devices.size == ndev, "default mesh leaves devices out")
    rt.random.seed(SEED)

    phases = [
        # first, while the process's peak is still low, and with an array
        # big enough to set it: the donation check must be decidable here
        ("semantics", lambda: _need(phase_semantics(rt, 1 << 28),
                                    "peak_growth_bytes")),
        ("distributed", lambda: phase_distributed(rt)),
        ("groupby 366 days", lambda: phase_groupby(rt, 2928, (60, 380))),
        # eight years whose days four chips do not divide: the layout that
        # does divide, the partial sums combined across chips
        ("groupby 2922 days", lambda: phase_groupby(rt, 2922, (60, 380))),
        ("chain+reductions", lambda: phase_chain(rt, 1_000_000_000)),
        ("stencil 8192^2", lambda: phase_stencil(
            rt, 8192, expected_stencil_paths(8192, ndev))),
        ("stencil sweeps 8192^2", lambda: phase_stencil_sweeps(
            rt, 8192, 5, 10, expected_stencil_paths(8192, ndev))),
        ("prk scalars 8192^2", lambda: phase_prk_scalars(
            rt, 8192, 10, expected_stencil_paths(8192, ndev))),
        # level 8 of mg-C's pyramid: the smallest the rank-3 kernel takes
        ("stencil 258^3", lambda: phase_stencil3(
            rt, 258, expected_stencil_paths3(258, ndev))),
        # NumPy float32 reads 1.2746751486658546e-04 after two iterations at
        # class C (float64 1.274675290838857e-04: PERF.md, PR 32) and
        # 8.398651024955054e-05 after three (``mg_np``, off the chip, PR
        # 35); two are one program since a refresh is one instruction
        ("mg 512^3", lambda: phase_mg(rt, 512, 8.398651024955054e-05,
                                      iters=3)),
        # a prolongation over the kernel's bound and one under it
        ("prolong 258^3", lambda: phase_prolong(
            rt, 258, expected_prolong_path(258, ndev))),
        ("prolong 10^3", lambda: phase_prolong(
            rt, 10, expected_prolong_path(10, ndev))),
        # on four chips the swap of the off-diagonal blocks
        ("transpose 4096^2", lambda: phase_transpose(
            rt, 4096, expected_transpose_path(4096, ndev))),
        ("axpy 1e9", lambda: phase_axpy(rt, 1_000_000_000)),
        ("broadcast 32768^2", lambda: phase_broadcast(rt, 32768)),
        ("stencil 30000^2", lambda: phase_stencil(
            rt, 30000, expected_stencil_paths(30000, ndev))),
    ]
    failed = []
    for name, fn in phases:
        t0 = time.perf_counter()
        try:
            facts = fn()
        except Exception as e:  # a failed phase is reported, and fails the run
            failed.append(name)
            print(f"chip_smoke: FAIL {name}: {type(e).__name__}: "
                  f"{str(e)[:2000]}")
        else:
            print(f"chip_smoke: ok   {name}: "
                  + " ".join(f"{k}={_fmt(v)}" for k, v in facts.items())
                  + f" (smoke timings on {dev.device_kind}, "
                    f"phase {time.perf_counter() - t0:.1f}s)")
        sys.stdout.flush()

    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    print("chip_smoke: peak_bytes_in_use per device = "
          f"{[s.get('peak_bytes_in_use') for s in stats]}")
    print(f"chip_smoke: compile cache dir={cache} files_at_start={files0} "
          f"files_at_end={_count_files(cache)} total_s="
          f"{time.perf_counter() - t_start:.1f}")
    print(f"chip_smoke: failed phases = {failed}")
    print(result_line(not failed, dev, ndev))
    sys.stdout.flush()
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
