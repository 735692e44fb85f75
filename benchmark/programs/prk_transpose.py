"""Parallel Research Kernels, Transpose, single precision, as PRK iterates
it, on a matrix laid over a square grid of chips.

    A[i, j] = i * n + j   (float32, made on the device),  B = 0
    each iteration:  B += A.T;  A += 1.0
    value read:      sum(|B|) / n^2,  which is T (n^2 + T - 2) / 2 after
                     T iterations in all

State carries over from solve to solve.  The traffic file lists what one
solve does (``iterate``: ``count`` iterations written as the PRK loop
through the public API, no sync() inside; ``norm``: read the value
above), as for ``prk_star``.  ``A`` is made as ``prk_star`` makes it,
added to ``zeros`` of the default layout in the same flush: the outer
product alone leaves it rows four ways, and the first solve's flush would
then move 9.66 GB between layouts while it swaps blocks (22.5 GB a device
at 49,152^2, compiled for the chip).

Every solve is held to the closed form and to ONE swap of blocks an
iteration (``ops/transpose_sharded.py``: counters ``transpose.path.swap``
and ``transpose.exchange_bytes``); ``verify`` holds windows of ``B`` and
``A`` to ``transpose_np`` bit for bit.  The probe, in set-up, refuses a
program whose transpose does not take the swap on the live mesh: one that
does not would be killed compiling 9.66 GB moved ten ways at once.
"""

from __future__ import annotations

import math

import numpy

from benchmark.record import BenchFailure, require, require_sharded, \
    window_starts

BAND, WIDTH = 16, 2048
#: the probe's order, in blocks of whole lane tiles a side of the grid
PROBE_TILES = 2


def initial_np(rows, cols, n, dtype):
    """``A[rows, cols]`` as made: ``i * n + j`` in ``dtype``, the
    multiplication and the addition each rounded, in that order."""
    i = numpy.asarray(rows, dtype=dtype)[:, None]
    j = numpy.asarray(cols, dtype=dtype)[None, :]
    return i * dtype.type(n) + j


def transpose_np(a, b, iterations):
    """``iterations`` of PRK's ``B += A.T; A += 1`` in plain NumPy, in
    ``a``'s dtype: the updated ``(a, b)``; ``b`` has ``a.T``'s shape."""
    a, b = a.copy(), b.copy()
    one = a.dtype.type(1)
    for _ in range(iterations):
        b += a.T
        a += one
    return a, b


class Program:
    def __init__(self, rt, cfg, traffic, rng, ndev):
        self.rt, self.cfg, self.traffic, self.rng = rt, cfg, traffic, rng
        self.n = int(cfg["n"])
        self.dtype = numpy.dtype(cfg["dtype"])
        self.ops = traffic["solve"]
        self.ndev = int(ndev)
        self.iterations = 0
        self._counted = {}  # counter -> its value after the last check

    # -- set-up ------------------------------------------------------------
    def _probe(self):
        """``B += A.T`` once on the live mesh at a small order whose blocks
        are whole tiles, through the ordinary entry points, before
        anything is built at size: it has to take the swap and agree with
        NumPy."""
        rt, reg = self.rt, self.rt.observe.registry
        p = math.isqrt(self.ndev)
        n = p * PROBE_TILES * 128
        before = reg.get("transpose.path.swap")
        i = rt.arange(n, dtype=self.dtype)
        a = i[:, None] * n + i[None, :]
        b = rt.zeros((n, n), dtype=self.dtype)
        b += a.T
        got = numpy.asarray(b)
        try:
            require(p * p == self.ndev, f"{self.ndev} devices are no square "
                                        f"grid")
            require(reg.get("transpose.path.swap") > before,
                    "its transpose does not take the swap")
            a0 = initial_np(range(n), range(n), n, self.dtype)
            require(numpy.array_equal(got, a0.T), "B += A.T differs from "
                                                  "NumPy")
            require_sharded(rt, b, f"a {b.shape} B")
        except BenchFailure as e:
            raise SystemExit(
                f"prk_transpose: on {self.ndev} devices {e}: this program "
                f"cannot run this configuration")

    def setup(self):
        self._probe()
        rt, n = self.rt, self.n
        i = rt.arange(n, dtype=self.dtype)
        self.A = rt.zeros((n, n), dtype=self.dtype) + (i[:, None] * n
                                                       + i[None, :])
        self.B = rt.zeros((n, n), dtype=self.dtype)
        rt.sync()
        self._counters()

    def _counters(self):
        """What the swap's counters moved by since the last call."""
        reg = self.rt.observe.registry
        moved = {}
        for name in ("transpose.path.swap", "transpose.exchange_bytes"):
            now = reg.get(name)
            moved[name] = now - self._counted.get(name, 0)
            self._counted[name] = now
        return moved

    # -- every solve -------------------------------------------------------
    def solve(self):
        rt = self.rt
        out = []
        for op in self.ops:
            kind = op["op"]
            if kind == "iterate":
                for _ in range(int(op["count"])):
                    self.B += self.A.T
                    self.A += 1.0
                self.iterations += int(op["count"])
            elif kind == "norm":
                v = float(rt.sum(abs(self.B))) / self.n ** 2
                out.append((kind, self.iterations, v))
            else:
                raise BenchFailure(f"prk_transpose: unknown op {kind!r}")
        return out

    def block_bytes(self):
        """What one off-diagonal device sends in a swap: its block."""
        p = math.isqrt(self.ndev)
        return (self.n // p) ** 2 * self.dtype.itemsize

    def check(self, out):
        """PRK's verification on every solve, and one swap an iteration:
        the norm is the closed form within ``norm_rtol``; the counters
        moved once an iteration, exactly where the flush ran a compiled
        program (a flush that compiles traces the transpose again for
        admission's estimate, so it counts more)."""
        rtol = float(self.cfg["assumed"]["norm_rtol"])
        n2 = self.n ** 2
        for _, T, v in out:
            want = T * (n2 + T - 2) / 2
            if not abs(v - want) <= rtol * want:
                return f"norm after {T} iterations = {v!r}, want {want!r}"
        moved = self._counters()
        its = self._its()
        swaps, sent = moved["transpose.path.swap"], \
            moved["transpose.exchange_bytes"]
        hit = [f.get("cache") for f in self.rt.diagnostics.last_flushes(1)] \
            == ["hit"]
        if (swaps, sent) == (its, its * self.block_bytes()) or (
                not hit and swaps >= its
                and sent >= its * self.block_bytes()):
            return None
        return (f"{swaps} swaps moving {sent} bytes a device for {its} "
                f"iterations (want one of {self.block_bytes()} each)")

    # -- correct: NumPy outside the window ---------------------------------
    def verify(self):
        """Windows of B and A against T iterations of ``transpose_np`` on
        the transposed window of the initial A: corners, every block
        boundary, and windows drawn from the seed, bit for bit; what the
        same reference gives in bfloat16 has to miss both the windows and
        the norm's limit.  Outside the window."""
        import ml_dtypes

        rt, n, T = self.rt, self.n, self.iterations
        rt.sync()
        specs = [str(require_sharded(rt, x, f"transpose {name}"))
                 for name, x in (("A", self.A), ("B", self.B))]
        rows = window_starts(self.B, 0, BAND, self.rng, 2)
        cols = window_starts(self.B, 1, WIDTH, self.rng, 2)
        pairs = {(rows[0], cols[0]), (rows[-1], cols[-1])}
        pairs.update((rows[i % len(rows)], cols[-1 - i % len(cols)])
                     for i in range(max(len(rows), len(cols))))
        low = numpy.dtype(ml_dtypes.bfloat16)
        low_err = 0.0
        for a, c in sorted(pairs):
            b, d = min(n, a + BAND), min(n, c + WIDTH)
            # B's window is the sum of the transposed windows of A's
            # iterates; A's is its own
            src = initial_np(range(c, d), range(a, b), n, self.dtype)
            _, wantB = transpose_np(src, numpy.zeros((b - a, d - c),
                                                     self.dtype), T)
            wantA, _ = transpose_np(
                initial_np(range(a, b), range(c, d), n, self.dtype),
                numpy.zeros((d - c, b - a), self.dtype), T)
            gotB = numpy.asarray(self.B[a:b, c:d])
            gotA = numpy.asarray(self.A[a:b, c:d])
            require(numpy.array_equal(gotB, wantB),
                    f"B[{a}:{b},{c}:{d}] differs from NumPy after {T}: "
                    f"{float(numpy.max(numpy.abs(gotB - wantB))):.3e}")
            require(numpy.array_equal(gotA, wantA),
                    f"A[{a}:{b},{c}:{d}] differs from NumPy after {T}")
            _, lowB = transpose_np(src.astype(low), numpy.zeros(
                (b - a, d - c), low), T)
            require(not numpy.array_equal(lowB.astype(self.dtype), wantB),
                    f"B[{a}:{b},{c}:{d}] in bfloat16 equals float32's")
            low_err = max(low_err, abs(float(numpy.mean(lowB, dtype=float))
                                       / float(numpy.mean(wantB, dtype=float))
                                       - 1.0))
        rtol = float(self.cfg["assumed"]["norm_rtol"])
        require(not low_err <= rtol,
                f"the norm's limit {rtol:.1e} would pass bfloat16 "
                f"({low_err:.3e})")
        return {"layout": specs[1], "A_layout": specs[0], "iterations": T,
                "windows": len(pairs), "bfloat16_mean_rel_err": low_err}

    def expected_paths(self, ndev):
        """No stencil runs: the path this program is held to is the
        transpose's, in ``check``."""
        return ()

    # -- what the algorithm has to move and compute -----------------------
    def _its(self):
        return sum(int(op["count"]) for op in self.ops
                   if op["op"] == "iterate")

    def algo_bytes_per_solve(self):
        """``prk_star``'s convention: an iteration reads A and reads and
        writes B (3 n^2) and reads and writes A (2 n^2); a norm reads B
        once more."""
        reads = sum(1 for op in self.ops if op["op"] == "norm")
        return (5 * self._its() + reads) * self.n ** 2 * self.dtype.itemsize

    def algo_flops_per_solve(self):
        """Two additions a point and an iteration (B's and A's); PRK's
        rate counts words, not flops."""
        return 2 * self._its() * self.n ** 2

    def transpose_bytes_per_solve(self):
        """The transposition and the update it feeds, whatever implements
        them: read the source block, read and write B's block, 3 n^2 an
        iteration.  ``A += 1`` is not in the class (``kernels``)."""
        return 3 * self._its() * self.n ** 2 * self.dtype.itemsize

    def kernels(self):
        """Classes of device op, matched in order against ``<kind>
        <label>``: the update that reads the received block transposed
        is the Pallas call ``ramba_add_transposed``; ``A += 1``, the norm
        and the rest are XLA's."""
        return {"transpose": r"ramba_add_transposed", "fusion": r"."}
