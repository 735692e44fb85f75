"""Pallas stencil kernel, run in interpreter mode on the CPU mesh (the
real-TPU lowering of the same kernel is exercised by bench.py on hardware).
"""

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


_KERNELS = {"mix": _mix, "skew": _skew, "far": _far, "box": _box}


def _kernel(which):
    return _prk_star2() if which == "star2" else rt.stencil(_KERNELS[which])


def _data(rs, shape, which):
    """Random operand; whole numbers for the nine-tap box, whose sum is
    then exact in any order (XLA and the interpreter contract differently)."""
    return rs.randint(0, 16, shape) if which == "box" else rs.rand(*shape)


def _bordered(st, lo, hi, slots, arrs):
    """sstencil's result by the XLA shifted-slice path: zero border."""
    from ramba_tpu import skeletons

    shape = arrs[0].shape
    interior = np.asarray(
        skeletons.stencil_interior(st.func, lo, hi, slots, arrs))
    want = np.zeros(shape, dtype=interior.dtype)
    want[-lo[0]:shape[0] - hi[0], -lo[1]:shape[1] - hi[1]] = interior
    return want


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
}


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
        n_in = 2 if which == "mix" else 1
        arrs = [jnp.asarray(_data(rs, shape, which), dtype=dtype)
                for _ in range(n_in)]
        slots = tuple(("arr", k) for k in range(n_in))
        lo, hi, taps = st.neighborhood(slots)
        with registry.collect_kernel_notes() as notes:
            got = stencil_pallas._run_padded(
                st.func, lo, hi, slots, arrs, taps, True, rows)
        (note,) = notes
        assert note["path"] == "pallas_padded" and note["interpret"]
        assert note["grid"] == want_grid == -(-shape[0] // note["block_rows"])
        assert rows is None or note["block_rows"] == rows
        assert 0 < note["vmem_limit_bytes"] <= stencil_pallas._vmem_cap()
        assert note["halo"] == "edge"
        # only an operand with no whole tile travels in an XLA-made copy
        whole = shape[0] >= 32 // arrs[0].dtype.itemsize and shape[1] >= 128
        assert note.get("operand_copy", 0) == (0 if whole else n_in)
        assert got.dtype == arrs[0].dtype and got.shape == shape
        np.testing.assert_array_equal(
            np.asarray(got), _bordered(st, lo, hi, slots, arrs))

    @pytest.mark.parametrize("case", [
        "toy-13500", "toy-15000", "last-block-shorter-than-halo",
        "last-block-one-row-grid6", "grid3-two-inputs",
        "halo-wider-than-a-tile"])
    def test_stale_slab_never_reaches_the_result(self, case):
        """The TPU interpreter with every scratch buffer NaN to begin with
        (and reads out of bounds refused): slab cells that no copy wrote
        are read only by cells the select zeroes."""
        import jax.numpy as jnp
        from jax.experimental.pallas import tpu as pltpu

        shape, dtype, which, rows, _ = _PADDED_CASES[case]
        st = _kernel(which)
        n_in = 2 if which == "mix" else 1
        rs = np.random.RandomState(7)
        arrs = [jnp.asarray(rs.rand(*shape), dtype=dtype)
                for _ in range(n_in)]
        slots = tuple(("arr", k) for k in range(n_in))
        lo, hi, taps = st.neighborhood(slots)
        got = np.asarray(stencil_pallas._run_padded(
            st.func, lo, hi, slots, arrs, taps,
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
