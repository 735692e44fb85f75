"""Pallas stencil kernel, run in interpreter mode on the CPU mesh (the
real-TPU lowering of the same kernel is exercised by bench.py on hardware).
"""

import itertools

import numpy as np
import pytest

import ramba_tpu as rt
from ramba_tpu.ops import stencil_pallas


@pytest.fixture
def interpret_mode(monkeypatch):
    monkeypatch.setattr(stencil_pallas, "_INTERPRET", True)
    monkeypatch.setattr(stencil_pallas, "_ENABLED", True)
    # pin dispatch to the single-chip kernel: the multi-device composed
    # path (shard_map + ppermute + local kernel) has its own test file
    from ramba_tpu.ops import stencil_sharded

    monkeypatch.setattr(stencil_sharded, "eligible", lambda *a, **k: False)


def _prk_star2(w=None):
    @rt.stencil
    def star2(a):
        return (
            0.25 * (a[0, 1] + a[0, -1] + a[1, 0] + a[-1, 0])
            + 0.125 * (a[0, 2] + a[0, -2] + a[2, 0] + a[-2, 0])
        )

    return star2


def _star2_numpy(x):
    out = np.zeros_like(x)
    out[2:-2, 2:-2] = (
        0.25 * (x[2:-2, 3:-1] + x[2:-2, 1:-3] + x[3:-1, 2:-2] + x[1:-3, 2:-2])
        + 0.125 * (x[2:-2, 4:] + x[2:-2, :-4] + x[4:, 2:-2] + x[:-4, 2:-2])
    )
    return out


class TestPallasStencil:
    def test_star2_matches_numpy(self, interpret_mode):
        x = np.arange(40 * 36, dtype=np.float32).reshape(40, 36) / 7.0
        out = rt.sstencil(_prk_star2(), rt.fromarray(x)).asarray()
        np.testing.assert_allclose(out, _star2_numpy(x), rtol=1e-5)

    def test_flush_span_says_what_the_padded_kernel_chose(self, interpret_mode):
        x = np.arange(40 * 36, dtype=np.float32).reshape(40, 36)
        rt.sstencil(_prk_star2(), rt.fromarray(x)).asarray()
        (note,) = [k for f in rt.diagnostics.last_flushes(4)
                   for k in f.get("kernels", ())
                   if k["path"] == "pallas_padded"][-1:]
        assert (note["block_rows"], note["grid"]) == (40, 1)
        assert 0 < note["vmem_limit_bytes"] <= stencil_pallas._vmem_cap()

    def test_available_gating(self):
        import jax
        import jax.numpy as jnp

        a = jnp.zeros((16, 16), jnp.float32)
        # off the TPU without interpret mode: not available; on one chip
        # (RAMBA_TEST_TPU=1) the kernel compiles and is
        on_one_chip = (jax.default_backend() == "tpu"
                       and len(jax.devices()) == 1)
        assert stencil_pallas.available([a]) == on_one_chip

    def test_odd_sizes(self, interpret_mode):
        # non-multiple-of-128 width, non-multiple-of-block height
        x = np.random.RandomState(0).rand(37, 131).astype(np.float32)
        out = rt.sstencil(_prk_star2(), rt.fromarray(x)).asarray()
        np.testing.assert_allclose(out, _star2_numpy(x), rtol=1e-4, atol=1e-5)

    def test_asymmetric_offsets(self, interpret_mode):
        @rt.stencil
        def shifted(a):
            return a[-1, 0] + a[0, 2]

        x = np.arange(64, dtype=np.float32).reshape(8, 8)
        out = rt.sstencil(shifted, rt.fromarray(x)).asarray()
        e = np.zeros_like(x)
        e[1:, :-2] = x[:-1, :-2] + x[1:, 2:]
        np.testing.assert_allclose(out, e)

    def test_two_input_arrays(self, interpret_mode):
        @rt.stencil
        def mix(a, b):
            return a[0, 0] + 0.5 * (b[-1, 0] + b[1, 0])

        x = np.random.RandomState(1).rand(24, 40).astype(np.float32)
        y = np.random.RandomState(2).rand(24, 40).astype(np.float32)
        out = rt.sstencil(mix, rt.fromarray(x), rt.fromarray(y)).asarray()
        e = np.zeros_like(x)
        e[1:-1, :] = x[1:-1, :] + 0.5 * (y[:-2, :] + y[2:, :])
        np.testing.assert_allclose(out, e, rtol=1e-6)

    def test_numpy_kernel_body(self, interpret_mode):
        @rt.stencil
        def npk(a):
            return np.maximum(a[0, -1], a[0, 1])

        x = np.random.RandomState(3).rand(16, 20).astype(np.float32)
        out = rt.sstencil(npk, rt.fromarray(x)).asarray()
        e = np.zeros_like(x)
        e[:, 1:-1] = np.maximum(x[:, :-2], x[:, 2:])
        np.testing.assert_allclose(out, e)


def _mix(a, b):
    return a[0, 0] + 0.5 * (b[-1, 0] + b[1, 0])


def _skew(a):
    return a[-3, 0] + a[0, 5] - a[1, -1]


def _far(a):
    # halos wider than one tile: 9 rows up, 130 lanes right
    return a[-9, 0] - a[0, 130] + a[2, -1]


def _box(a):
    # reads its corners
    return (a[-1, -1] + 2 * a[-1, 0] + 3 * a[-1, 1] + 4 * a[0, -1]
            + 5 * a[0, 0] + 6 * a[0, 1] + 7 * a[1, -1] + 8 * a[1, 0]
            + 9 * a[1, 1])


def _op27(a):
    # NPB MG's P: four distinct weights by distance class, all dyadic, so
    # that the 27 products of whole numbers sum exactly in any order
    acc = None
    for d in itertools.product((-1, 0, 1), repeat=3):
        term = (0.5, 0.25, 0.125, 0.0625)[sum(abs(v) for v in d)] * a[d]
        acc = term if acc is None else acc + term
    return acc


def _skew3(a):
    # another reach on each side of each axis
    return a[-2, 0, 1] + 2 * a[0, 3, 0] - a[1, -1, -5]


def _mix3(a, b):
    return a[0, 0, 0] + 0.5 * (b[-1, 0, 0] + b[1, 0, 0]) + b[0, 1, -1]


def _branch3(a):
    if a[0, 0, 0] > 8:
        return a[1, 0, 0] + a[0, -1, 0]
    return 2 * a[0, 0, 1]


_KERNELS = {"mix": _mix, "skew": _skew, "far": _far, "box": _box,
            "op27": _op27, "skew3": _skew3, "mix3": _mix3,
            "branch3": _branch3}
#: kernels whose operand is whole numbers: their sums are exact
_WHOLE = ("box", "op27", "branch3")


def _kernel(which):
    return _prk_star2() if which == "star2" else rt.stencil(_KERNELS[which])


def _data(rs, shape, which):
    """Random operand; whole numbers for the nine-tap box and the 27-point
    operator, whose sums are then exact in any order (XLA and the
    interpreter contract differently)."""
    return rs.randint(0, 16, shape) if which in _WHOLE else rs.rand(*shape)


def _bordered(st, lo, hi, slots, arrs):
    """sstencil's result by the XLA shifted-slice path: zero border."""
    from ramba_tpu import skeletons

    shape = arrs[0].shape
    interior = np.asarray(
        skeletons.stencil_interior(st.func, lo, hi, slots, arrs))
    want = np.zeros(shape, dtype=interior.dtype)
    want[tuple(slice(-l, n - h) for l, h, n in zip(lo, hi, shape))] = interior
    return want


def _padded(st, lo, hi, slots, arrs, taps, interpret, block):
    """``_run_padded`` at ``block``: rows a block at rank 2, (planes a
    block, rows staged at once) at rank 3, ``None`` for the derived."""
    planes = None
    if block is not None and arrs[0].ndim == 3:
        planes, block = block
    return stencil_pallas._run_padded(st.func, lo, hi, slots, arrs, taps,
                                      interpret, block, None, planes)


#: (shape, dtype, kernel, candidate rows or None for the derived height,
#: grid expected)
_PADDED_CASES = {
    "grid1-derived": ((40, 36), "float32", "star2", None, 1),
    "grid1-odd": ((37, 131), "float32", "star2", 40, 1),
    "grid2-ragged": ((37, 131), "float32", "star2", 24, 2),
    "grid5-ragged": ((37, 131), "float32", "star2", 8, 5),
    "grid3-two-inputs": ((50, 200), "float32", "mix", 24, 3),
    "grid4-bf16": ((52, 130), "bfloat16", "star2", 16, 4),
    "grid3-asymmetric": ((45, 150), "float32", "skew", 16, 3),
    "grid2-derived-tall": ((100, 140), "float32", "skew", None, 2),
    # toy images of the benchmark's 13500^2 (H % 8 = 4, W % 128 = 60) and
    # 15000^2 (W % 128 = 24): ragged in rows and lanes together
    "toy-13500": ((108, 188), "float32", "star2", 16, 7),
    "toy-15000": ((120, 152), "float32", "star2", 16, 8),
    "toy-13500-bf16": ((108, 188), "bfloat16", "star2", 32, 4),
    "last-block-shorter-than-halo": ((33, 140), "float32", "star2", 16, 3),
    "last-block-one-row-grid6": ((41, 140), "float32", "star2", 8, 6),
    "halo-wider-than-a-tile": ((60, 400), "float32", "far", 8, 8),
    "no-whole-row-tile": ((5, 300), "float32", "box", None, 1),
    "box-two-blocks": ((44, 260), "float32", "box", 24, 2),
    # rank 3: (planes a block, rows staged at once); the grid walks planes
    "cube-grid1-derived": ((10, 66, 130), "float32", "op27", None, 1),
    "cube-grid2": ((16, 24, 256), "float32", "op27", (8, 16), 2),
    "cube-no-whole-lane-tile": ((18, 20, 34), "float32", "op27", (4, 8), 5),
    "cube-short-last-block": ((34, 258, 130), "float32", "op27", (5, 64), 7),
    "cube-one-plane-blocks": ((6, 40, 140), "float32", "op27", (1, 16), 6),
    "cube-asymmetric": ((13, 45, 150), "float32", "skew3", (4, 16), 4),
    "cube-two-inputs": ((9, 50, 200), "float32", "mix3", (3, 24), 3),
    "cube-branching": ((7, 24, 136), "float32", "branch3", (4, 8), 2),
}
#: inputs a kernel takes
_N_IN = {"mix": 2, "mix3": 2}


class TestPaddedKernel:
    """``_run_padded`` itself, interpreting: the double-buffered slab
    fetch at every grid length, against the XLA shifted-slice path."""

    @pytest.mark.parametrize("case", sorted(_PADDED_CASES))
    def test_matches_stencil_interior_exactly(self, case):
        import jax.numpy as jnp

        from ramba_tpu.observe import registry

        shape, dtype, which, rows, want_grid = _PADDED_CASES[case]
        st = _kernel(which)
        rs = np.random.RandomState(len(case))
        n_in = _N_IN.get(which, 1)
        arrs = [jnp.asarray(_data(rs, shape, which), dtype=dtype)
                for _ in range(n_in)]
        slots = tuple(("arr", k) for k in range(n_in))
        lo, hi, taps = st.neighborhood(slots)
        with registry.collect_kernel_notes() as notes:
            got = _padded(st, lo, hi, slots, arrs, taps, True, rows)
        (note,) = notes
        assert note["path"] == "pallas_padded" and note["interpret"]
        # the blocked axis: rows at rank 2, planes at rank 3
        walked = note["block_planes" if len(shape) == 3 else "block_rows"]
        assert note["grid"] == want_grid == -(-shape[0] // walked)
        assert rows is None or rows in (
            note["block_rows"], (note.get("block_planes"), note["block_rows"]))
        assert 0 < note["vmem_limit_bytes"] <= stencil_pallas._vmem_cap()
        assert note["halo"] == "edge"
        # at rank 2 an operand with no whole tile travels in an XLA-made
        # copy; at rank 3 none does, the kernel fetches the ragged tiles
        whole = len(shape) == 3 or (
            shape[0] >= 32 // arrs[0].dtype.itemsize and shape[1] >= 128)
        assert note.get("operand_copy", 0) == (0 if whole else n_in)
        assert got.dtype == arrs[0].dtype and got.shape == shape
        got = np.asarray(got)
        np.testing.assert_array_equal(
            got, _bordered(st, lo, hi, slots, arrs))
        # the faces: every cell whose neighbourhood leaves the array
        inner = tuple(slice(-l, n - h) for l, h, n in zip(lo, hi, shape))
        face = np.ones(shape, bool)
        face[inner] = False
        assert (got[face] == 0).all() and got[inner].any()

    @pytest.mark.parametrize("case", [
        "toy-13500", "toy-15000", "last-block-shorter-than-halo",
        "last-block-one-row-grid6", "grid3-two-inputs",
        "halo-wider-than-a-tile", "cube-grid2", "cube-no-whole-lane-tile",
        "cube-short-last-block", "cube-asymmetric", "cube-two-inputs"])
    def test_stale_slab_never_reaches_the_result(self, case):
        """The TPU interpreter with every scratch buffer NaN to begin with
        (and reads out of bounds refused): slab cells that no copy wrote
        are read only by cells the select zeroes."""
        import jax.numpy as jnp
        from jax.experimental.pallas import tpu as pltpu

        shape, dtype, which, rows, _ = _PADDED_CASES[case]
        st = _kernel(which)
        n_in = _N_IN.get(which, 1)
        rs = np.random.RandomState(7)
        arrs = [jnp.asarray(_data(rs, shape, which), dtype=dtype)
                for _ in range(n_in)]
        slots = tuple(("arr", k) for k in range(n_in))
        lo, hi, taps = st.neighborhood(slots)
        got = np.asarray(_padded(
            st, lo, hi, slots, arrs, taps,
            pltpu.InterpretParams(uninitialized_memory="nan"), rows))
        assert not np.isnan(got).any()
        np.testing.assert_array_equal(
            got, _bordered(st, lo, hi, slots, arrs))

    def test_nan_at_the_arrays_edge_leaves_the_border_zero(self):
        import jax.numpy as jnp

        st = _prk_star2()
        x = np.random.RandomState(3).rand(108, 188).astype(np.float32)
        x[:2] = x[-2:] = np.nan
        x[:, :2] = x[:, -2:] = np.nan
        slots = (("arr", 0),)
        lo, hi, taps = st.neighborhood(slots)
        got = np.asarray(stencil_pallas._run_padded(
            st.func, lo, hi, slots, [jnp.asarray(x)], taps, True, 16))
        border = np.ones(x.shape, bool)
        border[2:-2, 2:-2] = False
        assert (got[border] == 0).all()
        np.testing.assert_array_equal(
            got, _bordered(st, lo, hi, slots, [jnp.asarray(x)]))

    def test_no_operand_sized_pad_or_concatenate_is_traced(self):
        """The program of one PRK sweep holds no ``pad`` and no
        ``concatenate`` as large as the operand: the tails are a tile wide."""
        import jax
        import jax.numpy as jnp

        st = _prk_star2()
        slots = (("arr", 0),)
        lo, hi, taps = st.neighborhood(slots)
        shape = (1080, 1880)
        jaxpr = jax.make_jaxpr(lambda a: stencil_pallas._run_padded(
            st.func, lo, hi, slots, [a], taps, True))(
                jnp.zeros(shape, jnp.float32))
        sizes = _copy_sizes(jaxpr.jaxpr)
        assert sizes and max(sizes) <= shape[0] * shape[1] // 8, sizes


def test_ten_calls_of_one_stencil_trace_the_kernel_once(monkeypatch):
    """A program that runs the same stencil ten times (PRK's solve) builds
    the kernel once: the calls share one jitted function, so jax lowers
    one program for the ten."""
    import jax
    import jax.numpy as jnp

    built = []
    real = stencil_pallas._padded_call
    monkeypatch.setattr(stencil_pallas, "_padded_call",
                        lambda *a: built.append(a[5]) or real(*a))
    stencil_pallas._padded_jit.cache_clear()
    st = _prk_star2()
    slots = (("arr", 0),)
    lo, hi, taps = st.neighborhood(slots)

    def solve(a, b):
        for _ in range(10):
            b = b + stencil_pallas._run_padded(st.func, lo, hi, slots, [a],
                                               taps, True, 16)
            a = a + 1
        return a, b

    z = jnp.zeros((108, 188), jnp.float32)
    try:
        jaxpr = jax.make_jaxpr(solve)(z, z)
    finally:
        stencil_pallas._padded_jit.cache_clear()
    assert built == [16]
    calls = [e for e in jaxpr.jaxpr.eqns if e.primitive.name in ("pjit", "jit")
             and e.params.get("name") == "ramba_stencil"]
    assert len(calls) == 10
    assert len({id(e.params["jaxpr"]) for e in calls}) == 1


def _copy_sizes(jaxpr):
    """Elements of every ``pad`` / ``concatenate`` output in ``jaxpr`` and
    the programs nested in it (a kernel's own body aside)."""
    import jax

    sizes = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in ("pad", "concatenate"):
            sizes.append(int(np.prod(eqn.outvars[0].aval.shape)))
        if eqn.primitive.name == "pallas_call":
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            sizes.extend(_copy_sizes(sub))
    return sizes


# what the benchmark's star cells hand the kernel per chip: the arrays as
# they lie, no longer the halo-extended 13504^2 and 15004^2
_STAR_SHAPES = [(15000, 15000), (13500, 13500), (30000, 30000)]
_STAR_MARGINS = (8, 8, 128, 128)


class TestPaddedBlock:
    """The height helper: a formula over what the kernel can observe."""

    @pytest.mark.parametrize("shape", _STAR_SHAPES, ids=str)
    def test_benchmark_shapes(self, shape):
        args = (*shape, 4, 1, 8, _STAR_MARGINS)
        assert stencil_pallas._margins((-2, -2), (2, 2), 4) == _STAR_MARGINS
        bh, limit = stencil_pallas._padded_block(*args)
        # 64 rows but on the one-chip 30000^2 of PERF.md section 7
        assert bh == (64 if shape[1] < 30000 else 48)
        assert stencil_pallas._padded_vmem_bytes(bh, *args[1:]) <= limit
        assert limit <= stencil_pallas._vmem_cap()

    @pytest.mark.parametrize("vary,values", [
        ("W", [100, 1000, 15000, 30000, 60000, 100000, 170000, 10 ** 6]),
        ("taps", [1, 4, 8, 16, 32, 64, 128]),
        ("n_slabs", [1, 2, 3, 4, 8, 16]),
        ("itemsize", [2, 4]),
        ("H", [1, 8, 37, 64, 1000, 15000]),
    ])
    def test_height_is_monotone_and_bounded(self, vary, values):
        base = {"H": 15000, "W": 30000, "itemsize": 4, "n_slabs": 1,
                "taps": 8}
        cap = stencil_pallas._vmem_cap()
        heights = []
        for v in values:
            a = dict(base, **{vary: v})
            bh, limit = stencil_pallas._padded_block(
                a["H"], a["W"], a["itemsize"], a["n_slabs"], a["taps"],
                _STAR_MARGINS)
            assert bh % 8 == 0 and 8 <= bh <= max(8, -(-a["H"] // 8) * 8)
            assert 0 < limit <= cap
            heights.append(bh)
        if vary == "H":
            assert heights == sorted(heights)
        else:
            assert heights == sorted(heights, reverse=True)
            assert heights[0] > heights[-1] or vary == "itemsize"

    def test_cap_is_a_share_of_the_cores_vmem(self):
        assert (stencil_pallas._vmem_cap()
                == int(stencil_pallas._V5E_VMEM * stencil_pallas._VMEM_SHARE)
                < stencil_pallas._V5E_VMEM)


def test_note_kernel_keeps_what_the_kernel_chose_and_replay_ignores_it():
    from ramba_tpu.observe import registry

    before = registry.get("stencil.path.pallas_padded")
    with registry.collect_kernel_notes() as notes:
        registry.note_kernel("stencil", "pallas_padded", False,
                             block_rows=64, grid=235,
                             vmem_limit_bytes=65523712)
        registry.note_kernel("stencil", "xla")
    assert notes == [
        {"kernel": "stencil", "path": "pallas_padded", "interpret": False,
         "block_rows": 64, "grid": 235, "vmem_limit_bytes": 65523712},
        {"kernel": "stencil", "path": "xla", "interpret": False},
    ]
    registry.replay_kernel_notes(notes)
    assert registry.get("stencil.path.pallas_padded") == before + 2


@pytest.fixture
def no_fallback(monkeypatch):
    """Make any silent fall-back to the XLA or padded path a hard failure."""
    import ramba_tpu.skeletons as sk

    def boom(*a, **k):
        raise AssertionError("padded path used, fast path expected")

    monkeypatch.setattr(stencil_pallas, "_run_padded", boom)
    monkeypatch.setattr(sk, "_pallas_fallback_warned", False)
    import warnings as _w

    real_warn = _w.warn

    def strict_warn(msg, *a, **k):
        if "pallas stencil" in str(msg):
            raise AssertionError(f"fallback: {msg}")
        return real_warn(msg, *a, **k)

    monkeypatch.setattr("warnings.warn", strict_warn)


def _force_fast_rows(monkeypatch, rows):
    """Give the fast kernel a candidate block height, as the sweep script
    does: through its private keyword."""
    real = stencil_pallas._run_fast
    monkeypatch.setattr(
        stencil_pallas, "_run_fast",
        lambda *args: real(*args[:7], block_rows=rows))


class TestPallasFastPath:
    """The aligned-shape kernel: no pad pass, double-buffered slab DMA."""

    def test_eligibility(self):
        import jax.numpy as jnp

        a = jnp.zeros((40, 128), jnp.float32)
        b = jnp.zeros((40, 130), jnp.float32)  # W not 128-aligned
        c = jnp.zeros((37, 128), jnp.float32)  # H not 8-aligned
        assert stencil_pallas._fast_eligible((-2, -2), (2, 2), [a])
        assert not stencil_pallas._fast_eligible((-2, -2), (2, 2), [b])
        assert not stencil_pallas._fast_eligible((-2, -2), (2, 2), [c])

    def test_fast_star2_matches_numpy(self, interpret_mode, no_fallback):
        x = np.random.RandomState(0).rand(40, 128).astype(np.float32)
        out = rt.sstencil(_prk_star2(), rt.fromarray(x)).asarray()
        np.testing.assert_allclose(out, _star2_numpy(x), rtol=1e-5, atol=1e-6)

    def test_fast_multiblock(self, interpret_mode, no_fallback, monkeypatch):
        # force several grid steps so the double-buffer rotation is exercised
        _force_fast_rows(monkeypatch, 8)
        x = np.random.RandomState(1).rand(64, 256).astype(np.float32)
        out = rt.sstencil(_prk_star2(), rt.fromarray(x)).asarray()
        np.testing.assert_allclose(out, _star2_numpy(x), rtol=1e-5, atol=1e-6)

    def test_fast_single_block(self, interpret_mode, no_fallback, monkeypatch):
        _force_fast_rows(monkeypatch, 64)
        x = np.random.RandomState(2).rand(32, 128).astype(np.float32)
        out = rt.sstencil(_prk_star2(), rt.fromarray(x)).asarray()
        np.testing.assert_allclose(out, _star2_numpy(x), rtol=1e-5, atol=1e-6)

    def test_fast_two_inputs(self, interpret_mode, no_fallback, monkeypatch):
        _force_fast_rows(monkeypatch, 16)

        @rt.stencil
        def mix(a, b):
            return a[0, 0] + 0.5 * (b[-1, 0] + b[1, 0])

        x = np.random.RandomState(3).rand(48, 128).astype(np.float32)
        y = np.random.RandomState(4).rand(48, 128).astype(np.float32)
        out = rt.sstencil(mix, rt.fromarray(x), rt.fromarray(y)).asarray()
        e = np.zeros_like(x)
        e[1:-1, :] = x[1:-1, :] + 0.5 * (y[:-2, :] + y[2:, :])
        np.testing.assert_allclose(out, e, rtol=1e-6)

    def test_fast_asymmetric(self, interpret_mode, no_fallback, monkeypatch):
        _force_fast_rows(monkeypatch, 8)

        @rt.stencil
        def shifted(a):
            return a[-3, 0] + a[0, 5]

        x = np.random.RandomState(5).rand(40, 128).astype(np.float32)
        out = rt.sstencil(shifted, rt.fromarray(x)).asarray()
        e = np.zeros_like(x)
        e[3:, :-5] = x[:-3, :-5] + x[3:, 5:]
        np.testing.assert_allclose(out, e)


# -- rank 3: the plane-walking kernel ----------------------------------------
_A27 = (-8 / 3, 0.0, 1 / 6, 1 / 12)


def _a27(a):
    # NPB MG's A: the face weight is zero and left out, as the cell does
    acc = None
    for d in itertools.product((-1, 0, 1), repeat=3):
        c = _A27[sum(abs(v) for v in d)]
        if c:
            term = c * a[d]
            acc = term if acc is None else acc + term
    return acc


def _a27_numpy(x):
    out = np.zeros_like(x)
    inner = 0
    for d in itertools.product((-1, 0, 1), repeat=3):
        c = np.float32(_A27[sum(abs(v) for v in d)])
        inner = inner + c * x[tuple(slice(1 + v, n - 1 + v)
                                    for v, n in zip(d, x.shape))]
    out[1:-1, 1:-1, 1:-1] = inner
    return out


class TestRank3:
    @pytest.mark.parametrize("shape", [(18, 20, 34), (10, 66, 130),
                                       (12, 24, 258)], ids=str)
    def test_27_points_through_sstencil_against_numpy(
            self, shape, interpret_mode, monkeypatch):
        """The cell's own operator on cubes and non-cubes with ragged last
        lanes and rows, through ``rt.sstencil``: the predicate lets the
        shape through once its threshold is low enough, and the counters
        say which path ran."""
        from ramba_tpu import diagnostics

        monkeypatch.setattr(stencil_pallas, "_RANK3_MIN_LANES", 34)
        x = np.random.RandomState(5).rand(*shape).astype(np.float32)
        before = diagnostics.counters()
        out = rt.sstencil(rt.stencil(_a27), rt.fromarray(x)).asarray()
        after = diagnostics.counters()
        assert after.get("stencil.path.pallas_padded", 0) > before.get(
            "stencil.path.pallas_padded", 0)
        assert after.get("stencil.path.xla", 0) == before.get(
            "stencil.path.xla", 0)
        np.testing.assert_allclose(out, _a27_numpy(x), rtol=0, atol=2e-6)
        face = np.ones(shape, bool)
        face[1:-1, 1:-1, 1:-1] = False
        assert (out[face] == 0).all()

    def test_a_small_cube_stays_on_the_xla_path(self, interpret_mode):
        from ramba_tpu import diagnostics

        # (a shape of its own: the fuser keeps a program by its function
        # and shapes, and the test above lowered the threshold)
        x = np.random.RandomState(6).rand(11, 66, 130).astype(np.float32)
        before = diagnostics.counters()
        out = rt.sstencil(rt.stencil(_a27), rt.fromarray(x)).asarray()
        after = diagnostics.counters()
        assert after.get("stencil.path.xla", 0) > before.get(
            "stencil.path.xla", 0)
        assert after.get("stencil.path.pallas_padded", 0) == before.get(
            "stencil.path.pallas_padded", 0)
        np.testing.assert_allclose(out, _a27_numpy(x), rtol=0, atol=2e-6)

    @pytest.mark.parametrize("shape,dtype,n_in,takes", [
        ((514, 514, 514), "float32", 1, True),
        ((258, 258, 258), "float32", 1, True),
        ((258, 258, 258), "float32", 2, True),
        ((66, 66, 66), "float32", 1, False),     # no whole lane tile
        ((34, 34, 34), "float32", 1, False),
        ((4, 4, 4), "float32", 1, False),
        ((64, 4, 512), "float32", 1, False),     # no whole row tile
        ((514, 514, 514), "bfloat16", 1, False),
        ((4, 15000, 15000), "float32", 1, False),  # a plane over VMEM
        ((15000, 15000), "float32", 1, True),    # rank 2 as before
        ((16, 16), "bfloat16", 1, True),
        ((4, 4, 4, 512), "float32", 1, False),
    ])
    def test_the_predicate_reads_rank_shape_and_dtype(
            self, shape, dtype, n_in, takes, monkeypatch):
        import jax

        monkeypatch.setattr(stencil_pallas, "_INTERPRET", True)
        monkeypatch.setattr(stencil_pallas, "_ENABLED", True)
        arrs = [jax.ShapeDtypeStruct(shape, dtype)] * n_in
        assert stencil_pallas.available(arrs) == takes
        assert stencil_pallas.available_local(arrs) == takes

    def test_the_threshold_is_the_last_axis(self):
        import jax.numpy as jnp

        f32 = jnp.dtype("float32")
        lanes = stencil_pallas._RANK3_MIN_LANES
        assert stencil_pallas._rank3_wins((8, 8, lanes), f32, 1)
        assert not stencil_pallas._rank3_wins((8, 8, lanes - 1), f32, 1)
        assert 128 <= lanes <= 258  # mg-C's 258^3 and 514^3 take the kernel

    @pytest.mark.parametrize("shape,taps", [
        ((514, 514, 514), 27), ((514, 514, 514), 21), ((258, 258, 258), 27),
        ((130, 130, 130), 27), ((1026, 1026, 1026), 27), ((8, 8, 128), 1)],
        ids=str)
    def test_block_never_asks_more_than_the_cap(self, shape, taps):
        halo, margins = (1, 1), (8, 8, 128, 128)
        (bp, rows), limit = stencil_pallas._padded_block3(
            *shape, 4, halo, margins, [(2, 6)], taps)
        assert 1 <= bp <= min(stencil_pallas._BLOCK_PLANES, shape[0])
        assert rows % 8 == 0 and 8 <= rows <= stencil_pallas._BLOCK_ROWS3
        assert stencil_pallas._padded_vmem_bytes3(
            bp, rows, *shape[1:], 4, halo, margins, [(2, 6)],
            taps) <= limit <= stencil_pallas._vmem_cap()
        # a plane more would not fit, or the block is as tall as it may be
        assert bp == min(stencil_pallas._BLOCK_PLANES, shape[0]) or (
            stencil_pallas._padded_vmem_bytes3(
                bp + 1, rows, *shape[1:], 4, halo, margins, [(2, 6)], taps)
            > stencil_pallas._vmem_cap())

    @pytest.mark.parametrize("tiles,most,want", [
        (65, 33, 33),   # 514 rows: two parts of 264, eight rows done twice
        (33, 33, 33),   # 258 rows: the plane whole
        (33, 4, 3),     # its evaluation: eleven parts of three tiles
        (17, 33, 17), (1, 4, 1), (64, 4, 4), (7, 4, 1), (5, 4, 1)])
    def test_a_plane_is_walked_in_equal_parts(self, tiles, most, want):
        t = stencil_pallas._part(tiles, most)
        assert t == want <= max(1, min(tiles, most))
        done = -(-tiles // t) * t
        assert tiles <= done and tiles >= 0.97 * done

    def test_a_plane_too_large_is_refused(self):
        with pytest.raises(ValueError, match="VMEM"):
            stencil_pallas._padded_block3(
                4, 15000, 15000, 4, (1, 1), (8, 8, 128, 128), [(2, 6)], 27)

    def test_the_stage_plan_shares_the_shifts_of_27_points(self):
        st = rt.stencil(_op27)
        slots = (("arr", 0),)
        (plan,) = stencil_pallas._stage_plan(st.func, slots, 8)
        lanes, subs = plan
        assert lanes == (-1, 1)
        assert sorted(subs) == [(di, dj) for di in (-1, 1)
                                for dj in (-1, 0, 1)]
        # offsets that are whole tiles stage nothing
        whole = rt.stencil(lambda a: a[1, 8, 0] + a[0, 0, 128] + a[-1, 0, 0])
        assert stencil_pallas._stage_plan(whole.func, slots, 8) == (
            ((), ()),)

    @pytest.mark.parametrize("shape", [(12, 72, 1200), (9, 20, 34)], ids=str)
    def test_the_operand_is_the_kernels_only_input(self, shape):
        """No XLA op stands between the array and the kernel: the program
        of one sweep is the ``pallas_call`` on the operand itself, the
        ragged tiles being blocks of it."""
        import jax
        import jax.numpy as jnp

        st = rt.stencil(_op27)
        slots = (("arr", 0),)
        lo, hi, taps = st.neighborhood(slots)
        jaxpr = jax.make_jaxpr(lambda a: stencil_pallas._run_padded(
            st.func, lo, hi, slots, [a], taps, True))(
                jnp.zeros(shape, jnp.float32))
        (outer,) = jaxpr.jaxpr.eqns
        assert outer.params["name"] == "ramba_stencil"
        (call,) = outer.params["jaxpr"].jaxpr.eqns
        assert call.primitive.name == "pallas_call"
        assert {v.aval.shape for v in call.invars} == {shape}

    def test_halo_strips_are_rank_2_only(self):
        import jax.numpy as jnp

        st = rt.stencil(_op27)
        slots = (("arr", 0),)
        lo, hi, taps = st.neighborhood(slots)
        x = jnp.zeros((8, 16, 256), jnp.float32)
        with pytest.raises(NotImplementedError):
            stencil_pallas.run(st.func, lo, hi, slots, [x], taps,
                               halos=[(x, x, x, x)])


# -- rank 3: the update the kernel's own store writes -------------------------
_UPDATES = {"sub": lambda v, s: v - s, "add": lambda v, s: v + s,
            "radd": lambda v, s: s + v}


class TestEpilogue:
    """``v - sstencil(A, u)`` and ``u + sstencil(S, r)`` folded into one
    ``stencil_update`` node against the script's two nodes (rewrites
    off), bit for bit: where the kernel takes the operand its store
    writes the update, under 256 lanes XLA's stencil and map do."""

    @pytest.mark.parametrize("which", sorted(_UPDATES))
    @pytest.mark.parametrize("shape,fused", [
        ((6, 13, 260), True),     # ragged lanes and rows, one block
        ((20, 16, 256), True),    # two blocks, the last a short one
        ((9, 24, 300), True),
        ((6, 16, 20), False),     # under a lane tile: the XLA fallback
    ], ids=str)
    def test_folded_equals_unfolded_bit_for_bit(self, shape, fused, which,
                                                interpret_mode, monkeypatch):
        from ramba_tpu import common, diagnostics

        rs = np.random.RandomState(sum(shape))
        u = rs.standard_normal(shape).astype(np.float32)
        v = rs.standard_normal(shape).astype(np.float32)
        # signed zeros on the border cells, where the stencil reads +0
        v[0] = -0.0
        v[:, :, -1] = -0.0
        v[:, 0, ::2] = 0.0
        st = rt.stencil(_a27)
        got = {}
        for rewrite in (False, True):
            monkeypatch.setattr(common, "rewrite_enabled", rewrite)
            before = diagnostics.counters()
            out = _UPDATES[which](rt.fromarray(v),
                                  rt.sstencil(st, rt.fromarray(u)))
            assert out.read_expr().op == (
                "stencil_update" if rewrite else "map")
            got[rewrite] = np.asarray(out)
            after = diagnostics.counters()
            moved = {k: after.get(k, 0) - before.get(k, 0) for k in (
                "stencil.epilogue.fused", "stencil.epilogue.unfused",
                "rewrite.rewrite_stencil_update")}
            assert moved == {
                "stencil.epilogue.fused": int(rewrite and fused),
                "stencil.epilogue.unfused": int(rewrite and not fused),
                "rewrite.rewrite_stencil_update": int(rewrite)}
        assert got[True].dtype == np.float32
        np.testing.assert_array_equal(got[True].view(np.uint32),
                                      got[False].view(np.uint32))
        # the border is the base's own, -0 included, but where +0 is added
        face = np.ones(shape, bool)
        face[1:-1, 1:-1, 1:-1] = False
        want = v + np.float32(0) if which != "sub" else v
        np.testing.assert_array_equal(got[True][face].view(np.uint32),
                                      want[face].view(np.uint32))

    @pytest.mark.parametrize("which", ["sub", "radd"])
    def test_stale_slab_never_reaches_the_update(self, which):
        """Blocks of two planes (the last a short one), every scratch
        buffer NaN to begin with: the base is read where the output is
        written and nowhere else."""
        import jax.numpy as jnp
        from jax.experimental.pallas import tpu as pltpu

        st = rt.stencil(_a27)
        slots = (("arr", 0),)
        lo, hi, taps = st.neighborhood(slots)
        rs = np.random.RandomState(9)
        shape = (7, 13, 260)
        u, v = (jnp.asarray(rs.standard_normal(shape), jnp.float32)
                for _ in range(2))
        fname, at = {"sub": ("subtract", 0), "radd": ("add", 1)}[which]
        got = np.asarray(stencil_pallas._run_padded(
            st.func, lo, hi, slots, [u], taps,
            pltpu.InterpretParams(uninitialized_memory="nan"), 8, None, 2,
            (fname, at), v))
        s = np.asarray(stencil_pallas._run_padded(
            st.func, lo, hi, slots, [u], taps, True, 8, None, 2))
        want = _UPDATES[which](np.asarray(v), s)
        np.testing.assert_array_equal(got.view(np.uint32),
                                      want.view(np.uint32))

    def test_the_base_block_is_counted_in_vmem(self):
        """At 514^3 the base's double-buffered block costs a plane of the
        block: five planes without it, four with it (258^3: 14, 12)."""
        got = {}
        for n in (514, 258):
            for base in (False, True):
                (bp, _), limit = stencil_pallas._padded_block3(
                    n, n, n, 4, (1, 1), (8, 8, 128, 128), [(2, 6)], 27,
                    base)
                assert limit <= stencil_pallas._vmem_cap()
                got[n, base] = bp
        assert got == {(514, False): 5, (514, True): 4,
                       (258, False): 14, (258, True): 12}
