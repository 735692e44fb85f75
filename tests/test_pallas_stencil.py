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
        monkeypatch.setattr(stencil_pallas, "_BH", 8)
        x = np.random.RandomState(1).rand(64, 256).astype(np.float32)
        out = rt.sstencil(_prk_star2(), rt.fromarray(x)).asarray()
        np.testing.assert_allclose(out, _star2_numpy(x), rtol=1e-5, atol=1e-6)

    def test_fast_single_block(self, interpret_mode, no_fallback, monkeypatch):
        monkeypatch.setattr(stencil_pallas, "_BH", 64)
        x = np.random.RandomState(2).rand(32, 128).astype(np.float32)
        out = rt.sstencil(_prk_star2(), rt.fromarray(x)).asarray()
        np.testing.assert_allclose(out, _star2_numpy(x), rtol=1e-5, atol=1e-6)

    def test_fast_two_inputs(self, interpret_mode, no_fallback, monkeypatch):
        monkeypatch.setattr(stencil_pallas, "_BH", 16)

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
        monkeypatch.setattr(stencil_pallas, "_BH", 8)

        @rt.stencil
        def shifted(a):
            return a[-3, 0] + a[0, 5]

        x = np.random.RandomState(5).rand(40, 128).astype(np.float32)
        out = rt.sstencil(shifted, rt.fromarray(x)).asarray()
        e = np.zeros_like(x)
        e[3:, :-5] = x[:-3, :-5] + x[3:, 5:]
        np.testing.assert_allclose(out, e)
