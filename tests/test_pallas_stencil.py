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
}


class TestPaddedKernel:
    """``_run_padded`` itself, interpreting: the double-buffered slab
    fetch at every grid length, against the XLA shifted-slice path."""

    @pytest.mark.parametrize("case", sorted(_PADDED_CASES))
    def test_matches_stencil_interior_exactly(self, case):
        import jax.numpy as jnp

        from ramba_tpu import skeletons
        from ramba_tpu.observe import registry

        shape, dtype, which, rows, want_grid = _PADDED_CASES[case]
        st = {"star2": _prk_star2(), "mix": rt.stencil(_mix),
              "skew": rt.stencil(_skew)}[which]
        rs = np.random.RandomState(len(case))
        n_in = 2 if which == "mix" else 1
        arrs = [jnp.asarray(rs.rand(*shape), dtype=dtype)
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
        want = np.zeros(shape, dtype=got.dtype)
        interior = np.asarray(
            skeletons.stencil_interior(st.func, lo, hi, slots, arrs))
        want[-lo[0]:shape[0] - hi[0], -lo[1]:shape[1] - hi[1]] = interior
        assert got.dtype == arrs[0].dtype and got.shape == shape
        np.testing.assert_array_equal(np.asarray(got), want)


_STAR_SHAPES = [(15000, 15000), (13504, 13504), (15004, 15004)]


class TestPaddedBlock:
    """The height helper: a formula over what the kernel can observe."""

    @pytest.mark.parametrize("shape", _STAR_SHAPES, ids=str)
    def test_benchmark_shapes(self, shape):
        args = (*shape, 4, 1, 8, (4, 4))
        bh, limit = stencil_pallas._padded_block(*args)
        assert bh >= 32 and bh % 8 == 0
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
                (4, 4))
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
