"""NAS Parallel Benchmarks, kernel MG (NPB 3.x ``MG/mg.f``; Bailey et al.,
"The NAS Parallel Benchmarks", RNR-94-007, section 2.2.2): V-cycle
multigrid for a periodic Poisson problem on an n^3 grid, four 27-point
operators over a pyramid of log2(n) grids, verified by the L2 norm of the
final residual.

    resid(u, v) = v - A u        A = (-8/3, 0, 1/6, 1/12)
    psinv(r, u) = u + S r        S = (-3/17, 1/33, -1/61, 0)   classes B and up
                                     (-3/8, 1/32, -1/64, 0)    classes S, W, A
    rprj3(r)    = (1/2, 1/4, 1/8, 1/16) r at every second point
    interp(z, u) = u + the trilinear prolongation of z

a 27-point operator being four weights by distance class (centre, 6 faces,
12 edges, 8 corners).  One solve is NPB's timed section:

    u = 0; r = resid(u, v)
    nit times:  mg3P(u, v, r);  r = resid(u, v)
    value read: sqrt(sum(r^2) / n^3)

The ``ramba_tpu`` program is NPB's own port: (m + 2)^3 arrays with one
ghost layer a side at every level, the operators through ``rt.sstencil``
(27 relative reads; it writes the points whose whole neighbourhood is in
range), ``comm3`` (six face assignments, axis by axis) after each, and
``interp`` as the specification writes it: z placed at every second point
of a zero grid, then each axis in turn gaining half its neighbours.  No
sync() inside a solve.  The NumPy reference below has no ghost layers
(``numpy.roll`` for the periodic neighbours) and imports no ``ramba_tpu``.
"""

from __future__ import annotations

import importlib.util
import itertools

import numpy

from benchmark.record import BenchFailure, require, require_sharded

A_WEIGHTS = (-8 / 3, 0.0, 1 / 6, 1 / 12)
SMOOTHERS = {"SWA": (-3 / 8, 1 / 32, -1 / 64, 0.0),
             "B+": (-3 / 17, 1 / 33, -1 / 61, 0.0)}
P_WEIGHTS = (0.5, 0.25, 0.125, 0.0625)
#: NPB counts 58 flops a finest point and iteration
NPB_FLOPS_PER_POINT = 58
#: the side of a window ``verify`` compares
WINDOW = 8


# -- the plain reference: NumPy, periodic by numpy.roll --------------------
def _s(a, ax):
    return numpy.roll(a, 1, ax) + numpy.roll(a, -1, ax)


def op27(a, w):
    """faces = (Sx + Sy + Sz) a, edges = (SxSy + SxSz + SySz) a,
    corners = SxSySz a."""
    ax, ay, az = _s(a, 2), _s(a, 1), _s(a, 0)
    axy = _s(ax, 1)
    t = a.dtype.type
    return (t(w[0]) * a + t(w[1]) * (ax + ay + az)
            + t(w[2]) * (axy + _s(ax + ay, 0)) + t(w[3]) * _s(axy, 0))


def resid_np(u, v):
    return v - op27(u, A_WEIGHTS)


def psinv_np(r, u, c):
    return u + op27(r, c)


def rprj3_np(r):
    # coarse point j lies on fine point 2 j + 1
    return numpy.ascontiguousarray(op27(r, P_WEIGHTS)[1::2, 1::2, 1::2])


def interp_np(z, u):
    f = numpy.zeros_like(u)
    f[1::2, 1::2, 1::2] = z
    for ax in range(3):
        f = f + u.dtype.type(.5) * _s(f, ax)
    return u + f


def _mulmod46(a, x):
    """a x mod 2^46 on uint64 arrays: a 46-bit product overflows 64 bits,
    so multiply in 23-bit halves."""
    a, x = numpy.uint64(a), numpy.asarray(x, numpy.uint64)
    m23, s = numpy.uint64((1 << 23) - 1), numpy.uint64(23)
    a1, a0, x1, x0 = a >> s, a & m23, x >> s, x & m23
    return ((a0 * x0 + (((a1 * x0 + a0 * x1) & m23) << s))
            & numpy.uint64((1 << 46) - 1))


def randlc_stream(count, seed=314159265, a=5 ** 13):
    """NPB's generator x <- 5^13 x mod 2^46, ``count`` numbers as 46-bit
    integers, the first being the first product.  The stream doubles:
    x[m:2m] = 5^(13 m) x[:m]."""
    x = numpy.empty(count, numpy.uint64)
    x[0] = _mulmod46(a, seed)
    m, am = 1, a
    while m < count:
        k = min(m, count - m)
        x[m:m + k] = _mulmod46(am, x[:k])
        am = int(_mulmod46(am, am))
        m += k
    return x


#: numbers drawn at a time: the stream is never held whole
ZRAN_BLOCK = 1 << 20


def zran3(n, dtype):
    """NPB's right-hand side: the stream laid in C order over (i3, i2,
    i1); +1 at its ten largest, -1 at its ten smallest, 0 elsewhere.
    Returns v and the flat positions of the twenty charges (the largest
    first).  Block b of the stream is 5^(13 b B) times block 0, so only
    each block's ten smallest and largest are kept."""
    count, keep, mod = n ** 3, 10, 1 << 46
    first = randlc_stream(min(count, ZRAN_BLOCK))
    jump, mult = pow(5 ** 13, len(first), mod), 1
    where, what = [], []
    for start in range(0, count, len(first)):
        x = first if mult == 1 else _mulmod46(mult, first)
        x = x[:count - start]
        ends = numpy.arange(len(x))
        if len(x) > 2 * keep:
            ends = numpy.argpartition(x, (keep - 1, len(x) - keep))
            ends = numpy.concatenate([ends[:keep], ends[-keep:]])
        where.append(start + ends)
        what.append(x[ends])
        mult = mult * jump % mod
    where, what = numpy.concatenate(where), numpy.concatenate(what)
    order = where[numpy.argsort(what)]
    charges = numpy.concatenate([order[-keep:], order[:keep]])
    v = numpy.zeros(count, dtype)
    v[charges[:keep]] = 1
    v[charges[keep:]] = -1
    return v.reshape(n, n, n), charges


def mg3p_np(u, r, v, c, lt):
    for k in range(lt, 1, -1):
        r[k - 1] = rprj3_np(r[k])
    u[1] = psinv_np(r[1], numpy.zeros_like(r[1]), c)
    for k in range(2, lt):
        u[k] = interp_np(u[k - 1], numpy.zeros_like(r[k]))
        r[k] = resid_np(u[k], r[k])
        u[k] = psinv_np(r[k], u[k], c)
    u[lt] = interp_np(u[lt - 1], u[lt])
    r[lt] = resid_np(u[lt], v)
    u[lt] = psinv_np(r[lt], u[lt], c)


def mg_np(n, nit, dtype, smoother):
    """NPB MG's timed section in plain NumPy: (the norm after every
    iteration, u, r).  The squares of the norm are summed in float64."""
    dtype = numpy.dtype(dtype).type
    lt, c = n.bit_length() - 1, SMOOTHERS[smoother]
    v, _ = zran3(n, dtype)
    u = {lt: numpy.zeros_like(v)}
    r = {lt: resid_np(u[lt], v)}
    norms = []
    for _ in range(nit):
        mg3p_np(u, r, v, c, lt)
        r[lt] = resid_np(u[lt], v)
        norms.append(float(numpy.sqrt(
            numpy.sum(r[lt].astype(numpy.float64) ** 2) / n ** 3)))
    return norms, u[lt], r[lt]


# -- NPB's port with ghost layers, on NumPy arrays or ``ramba_tpu``'s ------
def wrap_ghosts(a):
    """The n^3 periodic array with one ghost layer a side (host)."""
    return numpy.pad(a, 1, mode="wrap")


def comm3(a):
    """Refresh the ghost layers: each axis in turn, whole faces, so that
    edges and corners come right."""
    m = a.shape[0] - 2
    for ax in range(3):
        lo, hi, first, last = ([slice(None)] * 3 for _ in range(4))
        lo[ax], hi[ax], first[ax], last[ax] = 0, m + 1, 1, m
        a[tuple(lo)] = a[tuple(last)]
        a[tuple(hi)] = a[tuple(first)]
    return a


def prolong(z, f):
    """The trilinear prolongation of z onto the zero grid ``f``: coarse
    point J lies on fine point 2 J (ghost layers counted, so z's lower
    ghost lands on f's), then along each axis in turn every point gains
    half its two neighbours: a point between two coarse ones becomes
    their mean, one on a coarse point gains nothing.  Right at every
    point but f's upper ghost layer, which ``comm3`` refreshes."""
    f[0::2, 0::2, 0::2] = z[:-1, :-1, :-1]
    for ax in range(3):
        mid, up, dn = ([slice(None)] * 3 for _ in range(3))
        mid[ax], up[ax], dn[ax] = slice(1, -1), slice(2, None), slice(None, -2)
        mid, up, dn = tuple(mid), tuple(up), tuple(dn)
        f[mid] = f[mid] + 0.5 * (f[up] + f[dn])
    return f


def stencil27(rt, w):
    """The 27-point operator of weights ``w`` as a ``rt.stencil`` kernel:
    relative reads, zero weights left out."""
    w = tuple(float(x) for x in w)

    def op(a):
        acc = None
        for d in itertools.product((-1, 0, 1), repeat=3):
            c = w[sum(abs(x) for x in d)]
            if c:
                term = c * a[d]
                acc = term if acc is None else acc + term
        return acc

    return rt.stencil(op)


class Program:
    def __init__(self, rt, cfg, traffic, rng, ndev):
        self.rt, self.cfg, self.traffic, self.rng = rt, cfg, traffic, rng
        self.n = int(cfg["n"])
        self.nit = int(cfg["iterations"])
        self.dtype = numpy.dtype(cfg["dtype"])
        self.lt = self.n.bit_length() - 1
        require(self.n == 1 << self.lt and self.lt >= 2,
                f"n = {self.n} is not a power of two of at least 4")
        self.smoother = SMOOTHERS[cfg["smoother"]]
        self.ops = traffic["solve"]
        assumed = cfg["assumed"]
        self.norm_rtol = float(assumed["norm_rtol"])
        self.window_rtol = float(assumed["window_rtol"])
        self.A = stencil27(rt, A_WEIGHTS)
        self.S = stencil27(rt, self.smoother)
        self.P = stencil27(rt, P_WEIGHTS)
        self.v = self.u = self.r = None
        self.norms = []        # every solve's value, in order

    # -- set-up: the right-hand side, resident ----------------------------
    def setup(self):
        # The parent of PR 32 RAN this configuration (set-up 410 s, a solve
        # 14.2 s, one a window; PERF.md section 6): without
        # ``core/slicing.py`` the script's strided reads and writes are
        # gathers and serial scatters, and every segment compiles anew.
        # That is more time than a run of the harness has, so such a
        # program is told at once, before anything is built.
        if importlib.util.find_spec("ramba_tpu.core.slicing") is None:
            raise SystemExit("nas_mg: this program lowers a strided index to "
                             "a gather or a scatter: it cannot run this "
                             "configuration inside a run's time")
        pub = self.cfg["as_published"]
        if (self.n, self.nit) == (int(pub["n"]), int(pub["iterations"])):
            self.want = float(self.cfg["norm"])
        else:  # a rehearsal at another size: the reference gives the norm
            self.want = mg_np(self.n, self.nit, numpy.float64,
                              self.cfg["smoother"])[0][-1]
        v, self.charges = zran3(self.n, self.dtype)
        self.v = self.rt.fromarray(wrap_ghosts(v))
        self.rt.sync()

    # -- the four operators -------------------------------------------------
    def resid(self, u, v):
        return comm3(v - self.rt.sstencil(self.A, u))

    def psinv(self, r, u):
        s = self.rt.sstencil(self.S, r)
        return comm3(s if u is None else u + s)

    def rprj3(self, r):
        mc = (r.shape[0] - 2) // 2
        c = self.rt.zeros((mc + 2,) * 3, dtype=self.dtype)
        c[1:-1, 1:-1, 1:-1] = self.rt.sstencil(self.P, r)[2::2, 2::2, 2::2]
        return comm3(c)

    def interp(self, z, u):
        f = prolong(z, self.rt.zeros((2 * (z.shape[0] - 2) + 2,) * 3,
                                     dtype=self.dtype))
        return comm3(f if u is None else u + f)

    def mg3p(self, u, v, r):
        lt = self.lt
        for k in range(lt, 1, -1):
            r[k - 1] = self.rprj3(r[k])
        u[1] = self.psinv(r[1], None)
        for k in range(2, lt):
            u[k] = self.interp(u[k - 1], None)
            r[k] = self.resid(u[k], r[k])
            u[k] = self.psinv(r[k], u[k])
        u[lt] = self.interp(u[lt - 1], u[lt])
        r[lt] = self.resid(u[lt], v)
        u[lt] = self.psinv(r[lt], u[lt])

    def solve(self):
        rt, lt, out = self.rt, self.lt, []
        for op in self.ops:
            if op["op"] != "mg":
                raise BenchFailure(f"nas_mg: unknown op {op['op']!r}")
            u = {lt: rt.zeros((self.n + 2,) * 3, dtype=self.dtype)}
            r = {lt: self.resid(u[lt], self.v)}
            for _ in range(self.nit):
                self.mg3p(u, self.v, r)
                r[lt] = self.resid(u[lt], self.v)
            ri = r[lt][1:-1, 1:-1, 1:-1]
            norm = float(rt.sqrt(rt.sum(ri * ri) / float(self.n) ** 3))
            self.u, self.r = u[lt], r[lt]
            self.norms.append(norm)
            out.append(norm)
        return out

    def check(self, out):
        """NPB's own verification, on every solve, and every solve equal
        to the first within the same limit."""
        for v in out:
            for what, want in (("the configuration's", self.want),
                               ("the first solve's", self.norms[0])):
                if not abs(v - want) <= self.norm_rtol * want:
                    return (f"norm {v!r} off {what} {want!r} by "
                            f"{abs(v - want) / want:.3e} "
                            f"(limit {self.norm_rtol:.1e})")
        return None

    # -- correct: the residual identity and each operator, on windows -----
    def _windows(self, m):
        """Corners of the windows on a periodic grid of side m, whose
        side is ``w``: both ends of every axis (the periodic seam), round
        two of the twenty charges, and four drawn from the seed."""
        w = min(WINDOW, m)
        e = m - w
        starts = {(0, 0, 0), (e, e, e), (0, e, 0), (e, 0, e), (0, 0, e),
                  (e, e, 0)}
        for flat in self.charges[[0, 10]]:
            at = numpy.unravel_index(int(flat), (self.n,) * 3)
            starts.add(tuple(int(min(max(x * m // self.n - w // 2, 0), e))
                             for x in at))
        for _ in range(4):
            starts.add(tuple(int(x) for x in self.rng.integers(0, e + 1, 3)))
        return sorted(starts), w

    @staticmethod
    def _block(x, at, w, halo=0):
        """The periodic grid ``x`` (no ghost layers) on [at - halo, at + w
        + halo) of every axis, by periodic index."""
        return x[numpy.ix_(*[numpy.arange(a - halo, a + w + halo)
                             % x.shape[0] for a in at])]

    def _compare(self, what, got, ref, worst):
        """Windows of the system's ``got`` against ``ref(at, w)``, which
        returns NumPy's values there and the operand blocks it read: the
        error is held against the largest operand, since an operator sums
        27 products of them."""
        starts, w = self._windows(got.shape[0])
        for at in starts:
            want, operands = ref(at, w)
            scale = max(float(numpy.max(numpy.abs(o))) for o in operands)
            err = float(numpy.max(numpy.abs(
                self._block(got, at, w) - want))) / (scale or 1.0)
            worst[what] = max(worst.get(what, 0.0), err)
            require(err <= self.window_rtol,
                    f"{what} at {at} off NumPy by {err:.3e} of its largest "
                    f"operand {scale:.3e} (limit {self.window_rtol:.1e})")
        return len(starts)

    def verify(self):
        """Outside the window: the final r against v - A u from the
        system's own u; then ONE more application of psinv, rprj3 and
        interp by the system on its own arrays, each against NumPy
        float32 on the same windows; the ghost layers of every array
        against the faces they copy; and the residual identity through an
        operand rounded to bfloat16, which has to miss the limit."""
        rt = self.rt
        rt.sync()
        require(self.u is not None and self.norms, "no solve ran")
        specs = [str(require_sharded(rt, a, f"nas_mg {name}"))
                 for name, a in (("u", self.u), ("r", self.r))]
        smoothed = self.psinv(self.r, self.u)
        coarse = self.rprj3(self.r)
        fine = self.interp(coarse, smoothed.copy())
        host = {}
        for name, a in (("v", self.v), ("u", self.u), ("r", self.r),
                        ("psinv", smoothed), ("rprj3", coarse),
                        ("interp", fine)):
            x = numpy.asarray(a)
            require(x.dtype == self.dtype and numpy.isfinite(x).all(),
                    f"{name} is {x.dtype}, or not finite")
            require(numpy.array_equal(x, comm3(x.copy())),
                    f"the ghost layers of {name} are stale")
            host[name] = x[1:-1, 1:-1, 1:-1]
        del smoothed, coarse, fine
        v, u, r = host["v"], host["u"], host["r"]
        worst = {}

        def resid_ref(at, w, u=u):
            ub, vb = self._block(u, at, w, 1), self._block(v, at, w, 1)
            return (vb - op27(ub, A_WEIGHTS))[1:-1, 1:-1, 1:-1], (ub, vb)

        def psinv_ref(at, w):
            ub, rb = self._block(u, at, w, 1), self._block(r, at, w, 1)
            return (ub + op27(rb, self.smoother))[1:-1, 1:-1, 1:-1], (ub, rb)

        def rprj3_ref(at, w):
            # coarse point j lies on fine point 2 j + 1
            rb = self._block(r, [2 * a for a in at], 2 * w, 1)
            return op27(rb, P_WEIGHTS)[2:-1:2, 2:-1:2, 2:-1:2], (rb,)

        def interp_ref(at, w):
            # index i of ``full`` is fine point 2 lo + i; its first and
            # last planes wrap round the block and are not read
            lo = [a // 2 - 1 for a in at]
            z = self._block(host["rprj3"], lo, w // 2 + 2)
            full = interp_np(z, numpy.zeros(tuple(2 * n for n in z.shape),
                                            z.dtype))
            base = self._block(host["psinv"], at, w)
            add = full[tuple(slice(a - 2 * l, a - 2 * l + w)
                             for a, l in zip(at, lo))]
            return base + add, (base, z)

        windows = self._compare("resid", r, resid_ref, worst)
        self._compare("psinv", host["psinv"], psinv_ref, worst)
        self._compare("rprj3", host["rprj3"], rprj3_ref, worst)
        self._compare("interp", host["interp"], interp_ref, worst)
        # the control: u rounded to bfloat16 (8 bits of mantissa kept)
        low_u = (u.view(numpy.uint32) & numpy.uint32(0xFFFF0000)).view(
            numpy.float32)
        low = {}
        try:
            self._compare("resid", r, lambda at, w: resid_ref(at, w, low_u),
                          low)
        except BenchFailure:
            low = None
        require(low is None, f"the limit {self.window_rtol:.1e} would pass "
                f"an operand in bfloat16 ({low})")
        first = self.norms[0]
        return {"norm": first, "want": self.want,
                "norm_rel_err": abs(first - self.want) / self.want,
                "norms_equal": len(set(self.norms)) == 1,
                "window_rel_err": worst, "layout": specs[0],
                "solves_checked": len(self.norms),
                "windows": windows}

    def expected_paths(self, ndev):
        """The configuration's ``stencil_paths`` for what the library's own
        predicate says of an operator's operand at each level of the
        pyramid: the Pallas family's path where it takes a ((2^k + 2)^3,
        dtype) array, else XLA's fusion of shifted slices.  Today it takes
        none of rank 3, so every operator is ``xla``; a kernel that takes
        some moves the expectation with it, level by level, and a solve
        that falls off it fails."""
        import jax

        from ramba_tpu.ops import stencil_pallas

        names = self.cfg["stencil_paths"]
        return tuple(sorted({
            names["kernel" if stencil_pallas.available(
                (jax.ShapeDtypeStruct(((1 << k) + 2,) * 3, self.dtype),))
                else "fusion"]
            for k in range(1, self.lt + 1)}))

    # -- what the algorithm has to move and compute -----------------------
    def _passes(self):
        """Array passes of one iteration by operator and level, in points
        of that level: ``resid`` and ``psinv`` read two arrays and write
        one (``psinv`` onto a zeroed level reads one); ``rprj3`` reads the
        fine array and writes an eighth; ``interp`` reads and writes the
        fine array and reads an eighth (onto a zeroed level it reads no
        fine array).  The norm can fuse into the last ``resid``; the fill
        of a zeroed level into its first writer."""
        lt = self.lt
        per = {op: dict.fromkeys(range(1, lt + 1), 0.0)
               for op in ("resid", "psinv", "rprj3", "interp")}
        per["psinv"][1] = 2
        for k in range(2, lt + 1):
            per["rprj3"][k] += 1              # level k -> k - 1
            per["rprj3"][k - 1] += 1
            per["interp"][k - 1] += 1         # level k - 1 -> k: read z
            per["interp"][k] += 1 if k < lt else 2
            per["resid"][k] = per["psinv"][k] = 3
        per["resid"][lt] += 3                 # the iteration's last resid
        return per

    def _bytes(self, operators):
        """The passes of ``operators`` over a solve, and the first
        ``resid`` (u = 0: read v, write r), in bytes."""
        per = self._passes()
        points = 2 * self.n ** 3 + self.nit * sum(
            passes * (1 << k) ** 3
            for op in operators for k, passes in per[op].items())
        return int(points * self.dtype.itemsize) * len(self.ops)

    def algo_bytes_per_solve(self):
        return self._bytes(("resid", "psinv", "rprj3", "interp"))

    def algo_flops_per_solve(self):
        return (NPB_FLOPS_PER_POINT * self.n ** 3 * self.nit
                * len(self.ops))

    def stencil_bytes_per_solve(self):
        """``resid``, ``psinv`` and ``rprj3`` alone, at every level."""
        return self._bytes(("resid", "psinv", "rprj3"))

    def kernels(self):
        """Classes of device op, matched in order against ``<kind>
        <label>``: the configuration's ``kernel_classes``.  On the XLA
        path an operator is a fusion like any other, the names cannot tell
        ``resid`` from ``interp`` and the class ``stencil`` (a custom
        call) matches nothing."""
        return dict(self.cfg["kernel_classes"])
