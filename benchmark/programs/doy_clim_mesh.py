"""``doy_clim``'s solve, word for word, on a cube no one chip holds: the
day-of-year climatology over the whole WMO normal 1991-2020 (30 years,
10,958 days of the ERA5 grid, 45.5 GB of float32) laid over the four chips
of one host by the program's own default layout.  The program, the solve,
the counters it is held to and the plain reference (``doy_clim_np``) are
``doy_clim``'s; three things differ, all of them because of the size:

* the probe runs on the live mesh, on a small cube whose time extent the
  devices do not divide, and refuses at once a program that does not hold
  it 1/ndev a device in its default layout or whose second pass leaves the
  walk: such a program would be killed building 45.5 GB, or holding the
  anomaly cube;
* ``verify`` never holds ``X`` on the host: it pulls latitude bands and
  holds ALL of ``clim`` and the RMS to the float64 reference band by band,
  then reads the reference in float16 on a window drawn from ``--seed``;
* ``check`` also holds both passes to ``sharded`` on their notes.
"""

from __future__ import annotations

import os
import time
from concurrent.futures import ThreadPoolExecutor

import numpy

from benchmark.programs import doy_clim
from benchmark.programs.doy_clim import day_of_year_labels, doy_clim_np
from benchmark.record import BenchFailure, require, require_sharded

__all__ = ["Program", "doy_clim_np", "day_of_year_labels"]

#: the window of the float16 reading (rows, columns), as ``doy_clim``'s
WINDOW = (32, 64)


class Program(doy_clim.Program):
    def __init__(self, rt, cfg, traffic, rng, ndev):
        super().__init__(rt, cfg, traffic, rng, ndev)
        self.ndev = int(ndev)
        self.band = int(cfg["assumed"]["band_rows"])
        self._sharded = {}  # segment path -> its newest note's ``sharded``
        self._pull_wait = 0.0  # seconds ``verify`` waited for a band

    # -- set-up ------------------------------------------------------------
    def _probe(self):
        """The solve on ``8 ndev - 2`` days of a 2 x ndev grid in 15
        groups (cube and climatology both large enough to distribute),
        through the ordinary entry points, before anything is built at
        size."""
        rt, n = self.rt, self.ndev
        days = 8 * n - 2  # the largest axis, and ndev does not divide it
        before = self._segment_paths()
        x = rt.fromarray(numpy.arange(days * 2 * n, dtype=self.dtype)
                         .reshape(days, 2, n)) * self.dtype.type(1)
        g = x.groupby(0, numpy.arange(days, dtype=numpy.int32) % 15, 15)
        clim = g.mean()
        float(((g - clim) ** 2).mean())
        now = self._segment_paths()
        moved = sorted(k for k, v in now.items() if v > before.get(k, 0))
        want = sorted(self.cfg["assumed"]["segment_paths"])
        try:
            require(moved == want, f"its group-by took the segment paths "
                                   f"{moved}, not {want}")
            for name, a in (("cube", x), ("climatology", clim)):
                require_sharded(rt, a, f"a {a.shape} {name}")
        except BenchFailure as e:
            raise SystemExit(
                f"doy_clim_mesh: on {n} devices {e}: this program cannot "
                f"run this configuration")

    # -- every solve -------------------------------------------------------
    def check(self, out):
        """``doy_clim``'s check, and both passes inside ``shard_map``: the
        notes of a flush that traced say so (a flush that replays its
        trace counts the same notes again)."""
        error = super().check(out)
        for f in self.rt.diagnostics.last_flushes(len(self.ops)):
            for k in f.get("kernels", ()):
                if k.get("kernel") == "segment":
                    self._sharded[k["path"]] = bool(k.get("sharded"))
        want = sorted(self.cfg["assumed"]["segment_paths"])
        if not error and (sorted(self._sharded) != want
                          or not all(self._sharded.values())):
            error = f"segment notes {self._sharded}: not sharded"
        return error

    # -- correct: the reference band by band, outside the window ----------
    def _bands(self):
        """``(first row, band of X on the host)`` over all latitudes, the
        next band on its way while this one is read; the seconds spent
        waiting for one are kept."""
        import jax

        xv = self.X._value()
        cuts = {}

        def pull(r0):
            n = min(self.band, self.H - r0)
            if n not in cuts:  # two programs: the bands, and the last one
                cuts[n] = jax.jit(lambda a, r: jax.lax.dynamic_slice_in_dim(
                    a, r, n, axis=1))
            return numpy.asarray(cuts[n](xv, r0))

        starts = list(range(0, self.H, self.band))
        with ThreadPoolExecutor(1) as pool:
            nxt = pool.submit(pull, starts[0])
            for i, r0 in enumerate(starts):
                t0 = time.perf_counter()
                band = nxt.result()
                self._pull_wait += time.perf_counter() - t0
                if i + 1 < len(starts):
                    nxt = pool.submit(pull, starts[i + 1])
                yield r0, band

    def verify(self):
        """All of ``clim`` and the RMS against the reference on the
        system's own ``X``, a band of latitudes at a time (a day of the
        year's members summed in float64, then their anomalies), and what
        the reference reads when it accumulates in float16, which has to
        miss both limits: the tolerances lie between."""
        rt, G, lab = self.rt, self.G, self.labels
        rt.sync()
        require(self.clim is not None and self.rms, "no solve ran")
        specs = [str(require_sharded(rt, a, f"doy_clim_mesh {name}"))
                 for name, a in (("X", self.X), ("clim", self.clim))]
        got = numpy.asarray(self.clim)
        require(got.shape == (G, self.H, self.W) and got.dtype == self.dtype,
                f"clim is {got.dtype}{got.shape}")
        counts = numpy.bincount(lab, minlength=G)
        full = counts > 0
        # the days in runs along which the label rises by one (a year of
        # days of the year is one): a run's days are consecutive slabs of
        # X and consecutive slabs of the climatology, so the reference adds
        # and subtracts whole slices and copies nothing
        edges = [0, *(numpy.flatnonzero(numpy.diff(lab) != 1) + 1), self.T]
        runs = [(t0, t1, int(lab[t0])) for t0, t1 in zip(edges, edges[1:])]
        wh, ww = min(WINDOW[0], self.H), min(WINDOW[1], self.W)
        a = int(self.rng.integers(0, max(self.H - wh, 1)))
        c = int(self.rng.integers(0, max(self.W - ww, 1)))
        w = numpy.empty((self.T, wh, ww), self.dtype)
        ref_w = numpy.full((G, wh, ww), numpy.nan)

        def part(band, r0, i0, i1):
            """Rows ``i0:i1`` of one band (``r0 + i0`` of the grid): the
            largest miss of ``clim`` there and the sum of the squared
            anomalies.  A sum in float64 is finite only if every member
            is."""
            rows = slice(r0 + i0, r0 + i1)
            ref = numpy.zeros((G, i1 - i0, self.W), numpy.float64)
            for t0, t1, g0 in runs:
                ref[g0:g0 + t1 - t0] += band[t0:t1, i0:i1]
            require(numpy.isfinite(ref).all(), "X is not finite")
            require(numpy.isnan(got[~full, rows]).all(),
                    "a day with no member has a mean")
            ref[full] /= counts[full, None, None]
            lo, hi = max(a, rows.start), min(a + wh, rows.stop)
            if lo < hi:
                ref_w[full, lo - a:hi - a] = ref[
                    full, lo - rows.start:hi - rows.start, c:c + ww]
            sq, room = 0.0, numpy.empty_like(ref)
            for t0, t1, g0 in runs:
                d = numpy.subtract(band[t0:t1, i0:i1], ref[g0:g0 + t1 - t0],
                                   out=room[:t1 - t0]).ravel()
                sq += float(d @ d)
            return float(numpy.max(numpy.abs(got[full, rows] - ref[full]),
                                   initial=0.0)), sq

        clim_err, sq, t0 = 0.0, 0.0, time.perf_counter()
        workers = min(16, os.cpu_count() or 1)
        with ThreadPoolExecutor(workers) as pool:
            for r0, band in self._bands():
                n = band.shape[1]
                lo, hi = max(a, r0), min(a + wh, r0 + n)
                if lo < hi:
                    w[:, lo - a:hi - a] = band[:, lo - r0:hi - r0, c:c + ww]
                step = -(-n // workers)
                for err, s in pool.map(
                        lambda i0: part(band, r0, i0, min(i0 + step, n)),
                        range(0, n, step)):
                    clim_err, sq = max(clim_err, err), sq + s
                del band
        reference_s = time.perf_counter() - t0
        ref_rms = float(numpy.sqrt(sq / (self.T * self.H * self.W)))
        require(clim_err <= self.clim_atol,
                f"clim off the reference by {clim_err:.3e} "
                f"(limit {self.clim_atol:.1e})")
        rms_err = max(abs(v - ref_rms) for v in self.rms) / ref_rms
        require(rms_err <= self.rms_rtol,
                f"rms {self.rms[0]!r} off the reference's {ref_rms!r} by "
                f"{rms_err:.3e} (limit {self.rms_rtol:.1e})")
        # the same reference in the next precision down, on the window: it
        # has to miss both limits
        want, want_rms = doy_clim_np(w, lab, G)
        require(numpy.allclose(want[full], ref_w[full], rtol=0, atol=1e-9),
                "doy_clim_np and the banded reference disagree")
        low = numpy.zeros(want.shape, numpy.float16)
        for t in range(self.T):
            low[lab[t]] += w[t].astype(numpy.float16)
        with numpy.errstate(invalid="ignore", divide="ignore", over="ignore"):
            low /= counts[:, None, None].astype(numpy.float16)
            low_rms = numpy.sqrt(numpy.mean(
                (w.astype(numpy.float16) - low[lab]) ** 2,
                dtype=numpy.float16))
        low_err = float(numpy.max(numpy.abs(
            low[full].astype(numpy.float64) - want[full])))
        low_rms_err = abs(float(low_rms) - want_rms) / want_rms
        require(not low_err <= self.clim_atol
                and not low_rms_err <= self.rms_rtol,  # a NaN misses too
                f"the limits {self.clim_atol:.1e}, {self.rms_rtol:.1e} would "
                f"pass float16 ({low_err:.3e}, {low_rms_err:.3e})")
        return {"clim_max_abs_err": clim_err, "rms_rel_err": rms_err,
                "rms": self.rms[0], "ref_rms": ref_rms,
                "float16_clim_max_abs_err": low_err,
                "float16_rms_rel_err": low_rms_err,
                "layout": specs[0], "clim_layout": specs[1], "T": self.T,
                "sharded": dict(self._sharded),
                "reference_s": reference_s, "reference_pull_wait_s": self._pull_wait,
                "solves_checked": len(self.rms)}
