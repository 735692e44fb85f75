"""The Xarray group-by pattern the reference's documentation names
(Python-for-HPC/ramba docs/index.md:53-58; ``RambaGroupby``,
ramba.py:10290-10643): a day-of-year climatology of a daily field and the
RMS of its anomalies,

    clim = ds.groupby("time.dayofyear").mean("time")
    anom = ds.groupby("time.dayofyear") - clim

on one float32 variable on a (time, lat, lon) grid.  One solve, on the
resident cube ``X``:

    g = X.groupby(0, doy, G)
    clim = g.mean()                                  # (G, lat, lon), kept
    rms = float((((g - clim) ** 2).mean()) ** 0.5)   # anomalies not stored

``X`` is made on the device in set-up, from ``--seed``, by one fused
program: a latitude profile, an annual cycle whose amplitude follows the
latitude, a wave in longitude, and normal noise (Box-Muller over two
hashes of the position and the seed).  The NumPy reference below never
makes ``X``: it is given the system's own ``X``, pulled to the host.
"""

from __future__ import annotations

import datetime

import numpy

from benchmark.record import BenchFailure, require, require_sharded

#: time steps pulled to the host at once (64 days of the ERA5 grid: 266 MB)
PULL_DAYS = 64


def doy_clim_np(x, labels, G):
    """The plain reference: (clim, rms) in float64.  ``clim[g]`` is the
    mean over the members of group ``g`` (NaN for a group with none), the
    anomalies are ``x - clim[labels]``, ``rms`` their root mean square."""
    x = numpy.asarray(x, numpy.float64)
    clim = numpy.full((G,) + x.shape[1:], numpy.nan)
    for g in range(G):
        members = x[labels == g]
        if len(members):
            clim[g] = members.mean(0)
    anom = x - clim[labels]
    return clim, float(numpy.sqrt(numpy.mean(anom ** 2)))


def day_of_year_labels(first_year, years):
    """Day of year - 1 of every day of ``years`` whole years from 1
    January ``first_year`` (proleptic Gregorian calendar)."""
    days = [(datetime.date(y + 1, 1, 1) - datetime.date(y, 1, 1)).days
            for y in range(first_year, first_year + years)]
    return numpy.concatenate([numpy.arange(d) for d in days]).astype(
        numpy.int32)


class Program:
    def __init__(self, rt, cfg, traffic, rng, ndev):
        self.rt, self.cfg, self.traffic, self.rng = rt, cfg, traffic, rng
        self.H, self.W = (int(v) for v in cfg["grid"])
        self.G = int(cfg["groups"])
        self.dtype = numpy.dtype(cfg["dtype"])
        self.labels = day_of_year_labels(int(cfg["first_year"]),
                                         int(cfg["years"]))
        self.T = len(self.labels)
        require(int(self.labels.max()) < self.G,
                f"day {self.labels.max() + 1} of the year, {self.G} groups")
        self.ops = traffic["solve"]
        self.X = self.clim = None
        self.rms = []          # every solve's value, in order
        self._paths = {}       # segment.path.* as the last check saw them
        assumed = cfg["assumed"]
        self.rms_rtol = float(assumed["rms_rtol"])
        self.clim_atol = float(assumed["clim_atol"])

    # -- set-up: the resident cube ----------------------------------------
    def _probe(self):
        """The solve on three days of a 2 x 4 grid, before anything is
        built at size.  A program whose group-by does not take the walk
        does work of groups x data and stores the broadcast operand: at
        this cell's size its flush is refused by the device and then
        interpreted on the host, where the process is killed at the
        machine's 40 GiB (PERF.md section 6, PR 30).  Such a program cannot
        run this configuration, and is told so here, at once."""
        rt = self.rt
        before = self._segment_paths()
        x = rt.fromarray(numpy.arange(24, dtype=self.dtype).reshape(3, 2, 4))
        g = x.groupby(0, numpy.array([1, 0, 1], numpy.int32), 2)
        float(((g - g.mean()) ** 2).mean())
        now = self._segment_paths()
        moved = sorted(k for k, v in now.items() if v > before.get(k, 0))
        want = sorted(self.cfg["assumed"]["segment_paths"])
        if moved != want:
            raise SystemExit(
                f"doy_clim: this program's group-by took the segment paths "
                f"{moved}, not {want}: it cannot run this configuration")

    def setup(self):
        rt, f = self.rt, self.dtype.type
        self._probe()
        seed = int(self.rng.integers(0, 2 ** 31))
        s1, s2 = f(seed % 9973 * 0.6180339), f(seed // 9973 % 9973 * 0.4142135)
        t = rt.arange(self.T, dtype=self.dtype)[:, None, None]
        i = rt.arange(self.H, dtype=self.dtype)[None, :, None]
        j = rt.arange(self.W, dtype=self.dtype)[None, None, :]
        day = rt.fromarray(self.labels.astype(self.dtype))[:, None, None]
        lat = (f(90.0) - f(180.0 / max(self.H - 1, 1)) * i) * f(numpy.pi / 180)
        field = (f(250.0) + f(50.0) * rt.cos(lat)
                 + f(15.0) * rt.sin(lat)
                 * rt.cos(f(2 * numpy.pi / 365.25) * (day - f(200.0)))
                 + f(2.0) * rt.cos(f(6 * numpy.pi / self.W) * j))

        def hash01(a, b, c, s):
            v = rt.sin(t * f(a) + i * f(b) + j * f(c) + s) * f(43758.5453)
            return v - rt.floor(v)

        u1 = f(1.0) - hash01(12.9898, 78.233, 37.719, s1)       # (0, 1]
        u2 = hash01(93.9898, 67.345, 11.135, s2)
        noise = rt.sqrt(f(-2.0) * rt.log(u1)) * rt.cos(f(2 * numpy.pi) * u2)
        self.X = field + f(float(self.cfg["assumed"]["noise_sigma"])) * noise
        del t, i, j, day, lat, field, u1, u2, noise
        rt.sync()
        self._paths = self._segment_paths()

    # -- one solve ---------------------------------------------------------
    def solve(self):
        out = []
        for op in self.ops:
            if op["op"] != "clim_anom":
                raise BenchFailure(f"doy_clim: unknown op {op['op']!r}")
            g = self.X.groupby(0, self.labels, self.G)
            self.clim = g.mean()
            rms = float((((g - self.clim) ** 2).mean()) ** 0.5)
            out.append(rms)
        return out

    def _segment_paths(self):
        prefix = "segment.path."
        return {k[len(prefix):]: v
                for k, v in self.rt.diagnostics.counters().items()
                if k.startswith(prefix)}

    def check(self, out):
        """Every solve: both passes took the walk (the counters moved
        since the last check: a flush moves them, hit or miss), and the
        RMS is the first solve's, which ``verify`` holds to the reference
        (the program is deterministic, so the first stands for all)."""
        now = self._segment_paths()
        moved = sorted(k for k, v in now.items()
                       if v > self._paths.get(k, 0))
        self._paths = now
        want = sorted(self.cfg["assumed"]["segment_paths"])
        if moved != want:
            return f"segment passes took {moved}, want {want}"
        for rms in out:
            self.rms.append(rms)
            first = self.rms[0]
            if not abs(rms - first) <= self.rms_rtol * abs(first):
                return f"rms {rms!r} is not the first solve's {first!r}"
        return None

    # -- correct: the reference, outside the window -----------------------
    def _pull(self):
        """``X`` on the host, pulled in blocks of ``PULL_DAYS`` by one
        compiled slice (the start is an argument)."""
        import jax

        xv = self.X._value()
        step = min(PULL_DAYS, self.T)
        cut = jax.jit(lambda a, t0: jax.lax.dynamic_slice_in_dim(
            a, t0, step, axis=0))
        host = numpy.empty((self.T, self.H, self.W), self.dtype)
        for t0 in range(0, self.T, step):
            t0 = min(t0, self.T - step)
            host[t0:t0 + step] = numpy.asarray(cut(xv, t0))
        return host

    def verify(self):
        """The whole of ``clim`` and the RMS against the reference on
        the system's own ``X`` (in blocks: the sums of a day of the year
        in float64, then the anomalies), and what the reference reads
        when it accumulates in float16, which has to miss both limits:
        the tolerances lie between."""
        rt, G, lab = self.rt, self.G, self.labels
        rt.sync()
        require(self.clim is not None and self.rms, "no solve ran")
        specs = [str(require_sharded(rt, a, f"doy_clim {name}"))
                 for name, a in (("X", self.X), ("clim", self.clim))]
        x = self._pull()
        counts = numpy.bincount(lab, minlength=G)
        ref = numpy.zeros((G, self.H, self.W), numpy.float64)
        for t in range(self.T):
            require(numpy.isfinite(x[t]).all(), f"X[{t}] is not finite")
            ref[lab[t]] += x[t]
        with numpy.errstate(invalid="ignore", divide="ignore"):
            ref /= counts[:, None, None]
        sq = 0.0
        for t in range(self.T):
            d = (x[t] - ref[lab[t]]).ravel()
            sq += float(d @ d)
        ref_rms = float(numpy.sqrt(sq / x.size))
        got = numpy.asarray(self.clim)
        require(got.shape == ref.shape and got.dtype == self.dtype,
                f"clim is {got.dtype}{got.shape}")
        full = counts > 0
        clim_err = 0.0
        for g in range(G):  # a day at a time: the arrays are gigabytes
            if full[g]:
                clim_err = max(clim_err, float(numpy.max(numpy.abs(
                    got[g] - ref[g]))))
            else:
                require(numpy.isnan(got[g]).all(),
                        f"day {g + 1} has no member and has a mean")
        require(clim_err <= self.clim_atol,
                f"clim off the reference by {clim_err:.3e} "
                f"(limit {self.clim_atol:.1e})")
        rms_err = max(abs(v - ref_rms) for v in self.rms) / ref_rms
        require(rms_err <= self.rms_rtol,
                f"rms {self.rms[0]!r} off the reference's {ref_rms!r} by "
                f"{rms_err:.3e} (limit {self.rms_rtol:.1e})")
        # the same reference in the next precision down, on one window
        # drawn from the seed: it has to miss both limits
        a = int(self.rng.integers(0, max(self.H - 32, 1)))
        c = int(self.rng.integers(0, max(self.W - 64, 1)))
        w = x[:, a:a + 32, c:c + 64]
        want, want_rms = doy_clim_np(w, lab, G)
        low = numpy.zeros(want.shape, numpy.float16)
        for t in range(self.T):
            low[lab[t]] += w[t].astype(numpy.float16)
        with numpy.errstate(invalid="ignore", divide="ignore"):
            low /= counts[:, None, None].astype(numpy.float16)
        low_rms = numpy.sqrt(numpy.mean(
            (w.astype(numpy.float16) - low[lab]) ** 2, dtype=numpy.float16))
        require(numpy.allclose(want[full], ref[full, a:a + 32, c:c + 64],
                               rtol=0, atol=1e-9),
                "doy_clim_np and the blocked reference disagree")
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
                "layout": specs[0], "T": self.T,
                "solves_checked": len(self.rms)}

    def expected_paths(self, ndev):
        return ()  # no stencil kernel; the segment paths are check()'s

    # -- what the algorithm has to move and compute -----------------------
    def segment_bytes_per_solve(self):
        """Convention: each pass reads ``X`` once; the climatology is
        written once and read once: 2 |X| + 2 |clim|, the same whatever
        implements the passes."""
        per = self.H * self.W * self.dtype.itemsize
        return (2 * self.T + 2 * self.G) * per * len(self.ops)

    def algo_bytes_per_solve(self):
        return self.segment_bytes_per_solve()

    def algo_flops_per_solve(self):
        """An add a value for the sums; a subtract, a multiply and an add
        a value for the anomalies."""
        return 4 * self.T * self.H * self.W * len(self.ops)

    def kernels(self):
        """Classes of device op, matched in order against ``<kind>
        <label>``: both passes are XLA ``while`` loops whose bodies are
        fusions; the ``while`` op's own event spans its body's and is
        kept out of the sum."""
        return {"loop": r"^while ", "segment": r"."}
