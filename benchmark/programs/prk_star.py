"""Parallel Research Kernels, Stencil: star shape, radius r, single
precision, as PRK iterates it (not the repo's averaging kernel).

    W[0][+-j] = W[+-j][0] = +-1 / (2 j r),  j = 1..r
    A[i, j] = i + j   (made on the device),  B = 0
    each iteration:  B += stencil(W, A);  A += 1.0
    value read:      sum(|B|) / (n - 2r)^2,  which is 2 T after T iterations

State carries over from solve to solve.  The traffic file lists what one
solve does:

``iterate``  ``count`` iterations, written as the PRK loop through the
             public API with no sync() inside (one ``sstencil_iterate``
             per statement would be a different program: a later mix)
``norm``     read the value above
"""

from __future__ import annotations

import numpy

from benchmark.record import BenchFailure, require, require_sharded, \
    window_starts

BAND, WIDTH = 16, 2048


def star_np(a, r):
    """One application of the PRK star weights to ``a`` in plain NumPy,
    with sstencil's zero border of width r."""
    o = numpy.zeros_like(a)
    H, W = a.shape
    if H <= 2 * r or W <= 2 * r:
        return o
    acc = numpy.zeros((H - 2 * r, W - 2 * r), a.dtype)
    for j in range(1, r + 1):
        w = a.dtype.type(1.0 / (2 * j * r))
        acc = acc + w * (a[r:H - r, r + j:W - r + j]
                         - a[r:H - r, r - j:W - r - j]
                         + a[r + j:H - r + j, r:W - r]
                         - a[r - j:H - r - j, r:W - r])
    o[r:H - r, r:W - r] = acc
    return o


class Program:
    def __init__(self, rt, cfg, traffic, rng, ndev):
        self.rt, self.cfg, self.traffic, self.rng = rt, cfg, traffic, rng
        self.n = int(cfg["n"])
        self.r = int(cfg["radius"])
        self.dtype = numpy.dtype(cfg["dtype"])
        self.ops = traffic["solve"]
        self.iterations = 0
        r = self.r

        @rt.stencil
        def star(a):
            acc = None
            for j in range(1, r + 1):
                term = (1.0 / (2 * j * r)) * (a[0, j] - a[0, -j]
                                              + a[j, 0] - a[-j, 0])
                acc = term if acc is None else acc + term
            return acc

        self.star = star

    def setup(self):
        rt, n = self.rt, self.n
        i = rt.arange(n, dtype=self.dtype)
        self.A = i[:, None] + i[None, :]
        self.B = rt.zeros((n, n), dtype=self.dtype)
        rt.sync()

    def solve(self):
        rt = self.rt
        out = []
        for op in self.ops:
            kind = op["op"]
            if kind == "iterate":
                for _ in range(int(op["count"])):
                    self.B += rt.sstencil(self.star, self.A)
                    self.A += 1.0
                self.iterations += int(op["count"])
            elif kind == "norm":
                v = float(rt.sum(abs(self.B))) / (self.n - 2 * self.r) ** 2
                out.append((kind, self.iterations, v))
            else:
                raise BenchFailure(f"prk_star: unknown op {kind!r}")
        return out

    def check(self, out):
        """PRK's own verification, on every solve: the norm is 2 T."""
        rtol = float(self.cfg["assumed"]["norm_rtol"])
        for _, T, v in out:
            if not abs(v - 2.0 * T) <= rtol * 2.0 * T:
                return f"norm after {T} iterations = {v!r}, want {2.0 * T}"
        return None

    def verify(self):
        """Windows of B and A against T iterations of the NumPy
        reference on the same window (the stencil reads A, which only
        the +1 changes, so a window needs r more rows and columns and no
        more): corners, every shard boundary, and windows drawn from the
        seed.  Outside the window."""
        rt, n, r, T = self.rt, self.n, self.r, self.iterations
        rt.sync()
        specs = [str(require_sharded(rt, x, f"star {name}"))
                 for name, x in (("A", self.A), ("B", self.B))]
        rows = window_starts(self.B, 0, BAND, self.rng, 2)
        cols = window_starts(self.B, 1, WIDTH, self.rng, 2)
        # both corners, then every start of either axis at least once
        pairs = {(rows[0], cols[0]), (rows[-1], cols[-1])}
        pairs.update((rows[i % len(rows)], cols[-1 - i % len(cols)])
                     for i in range(max(len(rows), len(cols))))
        worst = 0.0
        for a, c in sorted(pairs):
            b, d = min(n, a + BAND), min(n, c + WIDTH)
            lo, hi = max(0, a - r), min(n, b + r)
            le, ri = max(0, c - r), min(n, d + r)
            i = numpy.arange(lo, hi, dtype=self.dtype)[:, None]
            j = numpy.arange(le, ri, dtype=self.dtype)[None, :]
            refA = i + j
            refB = numpy.zeros_like(refA)
            for _ in range(T):
                # a cut inside the array gets a false zero border within
                # r of the cut, outside [a, b) x [c, d) by design; at a
                # true edge it is sstencil's own zero border
                refB += star_np(refA, r)
                refA += self.dtype.type(1.0)
            want = refB[a - lo:b - lo, c - le:d - le]
            got = numpy.asarray(self.B[a:b, c:d])
            require(got.shape == want.shape, f"B window shape {got.shape}")
            err = float(numpy.max(numpy.abs(got - want))) if got.size else 0.
            worst = max(worst, err)
            require(err <= 1e-6 * max(1.0, 2.0 * T),
                    f"B[{a}:{b},{c}:{d}] off NumPy by {err:.3e} after {T}")
            gotA = numpy.asarray(self.A[a:b, c:d])
            require(numpy.array_equal(
                gotA, refA[a - lo:b - lo, c - le:d - le]),
                f"A[{a}:{b},{c}:{d}] differs from NumPy after {T}")
        return {"max_abs_err": worst, "layout": specs[1],
                "iterations": T, "windows": len(pairs)}

    def expected_paths(self, ndev):
        """The path the code's shape predicates name for an n x n f32
        stencil: ppermute halos feeding the padded kernel on several
        devices; on one the fast kernel when n is lane/sublane aligned,
        else the padded one."""
        if ndev > 1:
            return ("pallas_padded", "sharded")
        if self.n % 128 == 0 and self.n >= 32:
            return ("pallas_fast",)
        return ("pallas_padded",)

    # -- what the algorithm has to move and compute -----------------------
    def _counts(self):
        its = sum(int(op["count"]) for op in self.ops
                  if op["op"] == "iterate")
        reads = sum(1 for op in self.ops if op["op"] == "norm")
        return its, reads

    def algo_bytes_per_solve(self):
        """Convention: an iteration reads A and reads and writes B
        (3 n^2) and reads and writes A (2 n^2); a norm reads B once
        more.  That is the least the algorithm allows; every pass the
        program adds (a stencil output stored and read back) counts
        against it."""
        its, reads = self._counts()
        return (5 * its + reads) * self.n ** 2 * self.dtype.itemsize

    def algo_flops_per_solve(self):
        """PRK's own count: (2 (4r + 1) + 1) per interior point and
        iteration, 19 at r = 2 (a multiply and an add per weight, centre
        included, and the +1)."""
        its, _ = self._counts()
        return its * (2 * (4 * self.r + 1) + 1) * (self.n - 2 * self.r) ** 2

    def stencil_bytes_per_solve(self):
        """The stencil kernel alone: read A, write its output."""
        its, _ = self._counts()
        return 2 * its * self.n ** 2 * self.dtype.itemsize

    def kernels(self):
        """Classes of device op, matched in order against
        ``<kind> <label>``: the Pallas kernel is a ``tpu_custom_call``
        (its name is the unhelpful ``run``: the kernels carry no stable
        name yet); pads, updates and the norm are XLA's."""
        return {"stencil": r"^custom-call", "fusion": r"."}
