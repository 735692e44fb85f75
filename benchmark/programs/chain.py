"""The reference's headline chain (Python-for-HPC/ramba README.md:16-65,
sample/test-ramba.py) and reads of its result.

    A = arange(n) / 1000;  B = sin(A);  C = cos(A);  D = B*B + C**2

The traffic file lists what one solve does, from this vocabulary:

``chain_sum``   the whole chain and ``float(sum(D))`` (BASELINE config 2)
``elem``        ``count`` reads ``float(D[i])`` of the resident D
``slice``       ``count`` reads ``asarray(D[a:a+width])``
``slice_sum``   ``count`` reads ``float(sum(D[a:a+width]))``

Every new offset is a new program today (slice offsets are static in the
program's key), so the offsets of a mix's reads are drawn once from the
traffic file's own ``offsets_seed``: the same in every run, so every
program is in jax's cache after a checkout's first run, and warmed in
set-up.
"""

from __future__ import annotations

import numpy

from benchmark.record import BenchFailure, require, require_sharded, tol, \
    window_starts

VERIFY_WIDTH = 1 << 16


def chain_np(a, b, dtype):
    """Elements [a, b) of D in plain NumPy, in ``dtype`` as the x32
    regime computes it (the iota is exact below 2**31)."""
    x = (numpy.arange(a, b, dtype=numpy.int64).astype(dtype)
         / dtype.type(1000.0))
    s, c = numpy.sin(x), numpy.cos(x)
    return s * s + c ** 2


def _width_tol(dtype, width):
    """Relative error of a blocked sum of ``width`` values near 1."""
    return float(numpy.finfo(dtype).eps) * (8 + numpy.log2(max(width, 2)))


class Program:
    def __init__(self, rt, cfg, traffic, rng, ndev):
        self.rt, self.cfg, self.traffic, self.rng = rt, cfg, traffic, rng
        self.n = int(cfg["n"])
        self.dtype = numpy.dtype(cfg["dtype"])
        require(self.n < 2 ** 31, f"n={self.n} does not fit the x32 iota")
        self.ops = traffic["solve"]
        self.D = None
        self._offsets = [() for _ in self.ops]

    # -- the program ------------------------------------------------------
    def _chain(self):
        rt = self.rt
        A = rt.arange(self.n) / 1000.0
        B = rt.sin(A)
        C = rt.cos(A)
        D = B * B + C ** 2
        del A, B, C
        return D

    def setup(self):
        if not self.traffic["resident"]:
            return
        self.D = self._chain()
        self.rt.sync()
        rng = numpy.random.default_rng(int(self.traffic["offsets_seed"]))
        self._offsets = [
            rng.integers(0, self.n - op.get("width", 1), op["count"])
            if "count" in op else () for op in self.ops]

    def solve(self):
        """One solve: every statement of the traffic's list, in order,
        each ending in a value on the host.  Returns (op, offset, width,
        value) for the closed-form check, which is not timed."""
        rt = self.rt
        out = []
        for op, offs in zip(self.ops, self._offsets):
            kind, w = op["op"], op.get("width", 1)
            if kind == "chain_sum":
                D = self._chain()
                out.append((kind, 0, self.n, float(rt.sum(D))))
                del D
                continue
            for a in offs:
                a = int(a)
                if kind == "elem":
                    v = float(self.D[a])
                elif kind == "slice":
                    v = numpy.asarray(self.D[a:a + w])
                elif kind == "slice_sum":
                    v = float(rt.sum(self.D[a:a + w]))
                else:
                    raise BenchFailure(f"chain: unknown op {kind!r}")
                out.append((kind, a, w, v))
        return out

    # -- the guarantee: sin^2 + cos^2 = 1 ---------------------------------
    def check(self, out):
        """Closed form, on every solve.  Returns why it missed, or None."""
        for kind, a, w, v in out:
            if kind in ("chain_sum", "slice_sum"):
                rtol = _width_tol(self.dtype, w)
                if not abs(v - w) <= rtol * w:
                    return (f"sum(D[{a}:{a + w}]) = {v!r}, want {w} within "
                            f"{rtol:.1e}")
            elif kind == "elem":
                if not abs(v - 1.0) <= tol(self.dtype):
                    return f"D[{a}] = {v!r}, want 1"
            elif kind == "slice":
                if not (v.dtype == self.dtype and numpy.all(
                        numpy.abs(v - 1.0) <= tol(self.dtype))):
                    return f"D[{a}:{a + w}] is not 1 everywhere"
        return None

    def verify(self):
        """Windows of D against the NumPy reference, outside the window:
        both ends, every shard boundary, and windows drawn from the
        seed; and D laid out 1/ndev per device."""
        rt = self.rt
        D = self.D if self.D is not None else self._chain()
        rt.sync()
        require(D.dtype == self.dtype, f"D is {D.dtype}, want {self.dtype}")
        spec = require_sharded(rt, D, "chain D")
        worst = 0.0
        width = min(VERIFY_WIDTH, self.n)
        for a in window_starts(D, 0, width, self.rng, 3):
            got = numpy.asarray(D[a:a + width])
            ref = chain_np(a, a + width, self.dtype)
            require(numpy.isfinite(got).all(), f"D[{a}:] not finite")
            err = float(numpy.max(numpy.abs(got - ref)))
            worst = max(worst, err)
            require(err <= tol(self.dtype),
                    f"D[{a}:{a + width}] off NumPy by {err:.2e}")
        return {"max_abs_err": worst, "layout": str(spec)}

    def expected_paths(self, ndev):
        return ()  # no stencil kernel anywhere in this program

    # -- what the algorithm has to move and compute -----------------------
    def _chains_per_solve(self):
        return sum(1 for op in self.ops if op["op"] == "chain_sum")

    def algo_bytes_per_solve(self):
        """Convention: D is written once (n * itemsize); arange is
        generated, A, B, C are never stored, and the sum rides the same
        pass.  Small reads of a resident D move nothing worth counting,
        so a traffic mix without ``chain_sum`` reports None."""
        k = self._chains_per_solve()
        return k * self.n * self.dtype.itemsize or None

    def algo_flops_per_solve(self):
        """Convention: per element one divide, one sin, one cos, two
        multiplies, one add for D and one add for the sum = 7, counting
        a transcendental as one operation (the VPU spends many more)."""
        k = self._chains_per_solve()
        return k * self.n * 7 or None

    def kernels(self):
        """Device ops of this program by class, for the trace reduction:
        the chain is XLA fusions (and whatever else XLA emits), no
        custom call."""
        return {"fusion": r"."}
