"""Tests for the auxiliary subsystems: timing, debug artifacts, pattern
rewrites, constraints, jit/remote, distributed bring-up.

Reference test model: everything end-to-end differential vs NumPy
(/root/reference/ramba/tests/test_distributed_array.py:240-260 run_both).
"""

import os

import numpy as np
import pytest

import ramba_tpu as rt
from tests.helpers import default_rtol, map_dtype, oracle
from ramba_tpu.core import fuser
from ramba_tpu.core.expr import Node
from ramba_tpu.core.rewrite import rewrite_roots


# ---------------------------------------------------------------------------
# timing
# ---------------------------------------------------------------------------


class TestTiming:
    def test_counters_accumulate(self):
        from ramba_tpu.utils import timing

        timing.add_time("unit_test", 1.5)
        timing.add_time("unit_test", 0.5)
        timing.add_sub_time("unit_test", "sub", 0.25)
        snap = timing.get_timing()
        assert snap["timers"]["unit_test"] == (2.0, 2)
        assert snap["sub_timers"][("unit_test", "sub")] == (0.25, 1)

    def test_flush_records_exec_and_per_func(self, monkeypatch):
        from ramba_tpu import common
        from ramba_tpu.utils import timing

        monkeypatch.setattr(common, "timing_level", 1)  # per_func is gated
        timing.reset()
        for _ in range(2):  # 2nd run is a guaranteed compile-cache hit
            a = rt.arange(1000) * 2.0
            rt.sync()
        snap = timing.get_timing()
        assert snap["timers"].get("flush_execute", (0, 0))[1] >= 1
        assert len(snap["per_func"]) >= 1

    def test_summary_prints(self, capsys):
        import io

        from ramba_tpu.utils import timing

        timing.add_time("printable", 0.1)
        buf = io.StringIO()
        timing.timing_summary(file=buf)
        assert "printable" in buf.getvalue()

    def test_timer_context(self):
        from ramba_tpu.utils import timing

        timing.reset()
        with timing.timer("ctx"):
            pass
        assert timing.time_dict["ctx"][1] == 1


# ---------------------------------------------------------------------------
# debug artifacts
# ---------------------------------------------------------------------------


class TestDebug:
    def test_output_dot(self, tmp_path):
        from ramba_tpu.utils import debug

        a = rt.arange(100) + 1.0
        b = rt.sin(a)
        path = tmp_path / "g.dot"
        text = debug.output_dot(str(path))
        assert "digraph" in text
        assert "map" in text
        assert path.exists()
        rt.sync()

    def test_report_pending(self):
        import io

        from ramba_tpu.utils import debug

        rt.sync()
        a = rt.arange(50) * 3
        buf = io.StringIO()
        n = debug.report_pending(file=buf)
        assert n >= 1
        assert "pending" in buf.getvalue()
        rt.sync()
        buf2 = io.StringIO()
        assert debug.report_pending(file=buf2) == 0


# ---------------------------------------------------------------------------
# pattern rewrites (reference: ramba.py:4567-4789)
# ---------------------------------------------------------------------------


class TestRewrites:
    def test_arange_reshape_values(self):
        a = rt.arange(24).reshape(4, 6) + 0
        np.testing.assert_array_equal(a.asarray(),
                                      np.arange(24).reshape(4, 6))

    def test_arange_reshape_rewrites_to_fill(self):
        a = rt.arange(24, dtype=np.float64)
        r = Node("reshape", ((4, 6),), [a.read_expr()])
        (out,) = rewrite_roots([r])
        assert out.op == "fromfunction"
        rt.sync()

    def test_stack_mean_advindex_values(self):
        # the xarray groupby().mean() expansion (docs/index.md:53-58)
        x = np.arange(48, dtype=np.float64).reshape(4, 12)
        labels = np.arange(12) % 3
        X = rt.fromarray(x)
        cols = [np.where(labels == g)[0] for g in range(3)]
        stacked = rt.stack([rt.mean(X[:, idx], axis=1) for idx in cols],
                           axis=1)
        expect = np.stack([x[:, idx].mean(axis=1) for idx in cols], axis=1)
        np.testing.assert_allclose(stacked.asarray(), expect)

    def test_stack_mean_advindex_rewrites_to_segment_reduce(self):
        x = np.arange(48, dtype=np.float64).reshape(4, 12)
        labels = np.arange(12) % 3
        X = rt.fromarray(x)
        cols = [np.where(labels == g)[0] for g in range(3)]
        stacked = rt.stack([rt.mean(X[:, idx], axis=1) for idx in cols],
                           axis=1)
        (out,) = rewrite_roots([stacked.read_expr()])
        ops = _collect_ops(out)
        assert "segment_reduce" in ops
        assert "stack" not in ops
        rt.sync()

    def test_concat_binop_getitem_values(self):
        # the xarray anomaly pattern: x[:, idx_g] - m[g], concatenated
        x = np.arange(60, dtype=np.float64).reshape(5, 12)
        labels = np.arange(12) % 3
        m = np.stack([x[:, labels == g].mean(axis=1) for g in range(3)], 0)
        X, M = rt.fromarray(x), rt.fromarray(m)
        cols = [np.where(labels == g)[0] for g in range(3)]
        parts = [X[:, idx] - M[g][:, None] for g, idx in enumerate(cols)]
        out = rt.concatenate(parts, axis=1)
        # the [:, None] climatology idiom must fire the rewrite
        (r,) = rewrite_roots([out.read_expr()])
        ops = _collect_ops(r)
        assert "concatenate" not in ops
        assert "take" in ops
        expect = np.concatenate(
            [x[:, idx] - m[g][:, None] for g, idx in enumerate(cols)], axis=1
        )
        np.testing.assert_allclose(out.asarray(), expect)

    def test_stack_reduce_duplicate_in_group_no_rewrite(self):
        # duplicates within one group: original counts twice, segment_reduce
        # would count once -> the rewrite must not fire, values must match
        x = np.arange(24, dtype=np.float64).reshape(4, 6)
        X = rt.fromarray(x)
        groups = [np.array([0, 0, 1]), np.array([2, 3, 4, 5])]
        stacked = rt.stack([rt.sum(X[:, i], axis=1) for i in groups], axis=1)
        (r,) = rewrite_roots([stacked.read_expr()])
        assert "segment_reduce" not in _collect_ops(r)
        expect = np.stack([x[:, i].sum(axis=1) for i in groups], axis=1)
        np.testing.assert_allclose(stacked.asarray(), expect)

    def test_concat_binop_misaligned_no_rewrite(self):
        # 1-D-per-group operand against rows grouped on axis 0: trailing
        # broadcast alignment differs before/after -> must not fire
        x = np.arange(60, dtype=np.float64).reshape(12, 5)
        labels = np.arange(12) % 3
        m = np.array([10.0, 20.0, 30.0])
        X, M = rt.fromarray(x), rt.fromarray(m)
        rows = [np.where(labels == g)[0] for g in range(3)]
        parts = [X[idx] - M[g] for g, idx in enumerate(rows)]
        out = rt.concatenate(parts, axis=0)
        expect = np.concatenate(
            [x[idx] - m[g] for g, idx in enumerate(rows)], axis=0
        )
        np.testing.assert_allclose(out.asarray(), expect)

    def test_concat_binop_scalar_groups_rewrites(self):
        # 1-D x grouped on axis 0 with scalar-per-group operand: aligned,
        # fires and stays correct
        x = np.arange(12, dtype=np.float64)
        labels = np.arange(12) % 3
        m = np.array([10.0, 20.0, 30.0])
        X, M = rt.fromarray(x), rt.fromarray(m)
        pos = [np.where(labels == g)[0] for g in range(3)]
        parts = [X[idx] * M[g] for g, idx in enumerate(pos)]
        out = rt.concatenate(parts, axis=0)
        (r,) = rewrite_roots([out.read_expr()])
        assert "concatenate" not in _collect_ops(r)
        expect = np.concatenate(
            [x[idx] * m[g] for g, idx in enumerate(pos)]
        )
        np.testing.assert_allclose(out.asarray(), expect)

    def test_rewrite_disabled_flag(self, monkeypatch):
        from ramba_tpu import common

        monkeypatch.setattr(common, "rewrite_enabled", False)
        a = rt.arange(24).reshape(4, 6) + 0
        np.testing.assert_array_equal(a.asarray(),
                                      np.arange(24).reshape(4, 6))


def _collect_ops(root):
    ops = []
    stack = [root]
    seen = set()
    while stack:
        e = stack.pop()
        if id(e) in seen:
            continue
        seen.add(id(e))
        if isinstance(e, Node):
            ops.append(e.op)
            stack.extend(e.args)
    return ops


# ---------------------------------------------------------------------------
# constraints (reference: ramba.py:5296-5315,9915-9922)
# ---------------------------------------------------------------------------


class TestConstraints:
    def test_smap_axis_constraint(self):
        from ramba_tpu.parallel import constraints

        constraints.clear_constraints()
        a = rt.arange(1024).astype(np.float64)
        b = rt.ones(1024)
        out = rt.smap(lambda x, y: x + y, a, b, axis=0)
        assert len(constraints.get_constraints()) == 1
        np.testing.assert_allclose(out.asarray(),
                                   np.arange(1024) + 1.0)

    def test_add_constraint_2d(self):
        from ramba_tpu.parallel import constraints

        constraints.clear_constraints()
        a = rt.fromarray(np.arange(64, dtype=np.float64).reshape(8, 8))
        b = rt.fromarray(np.ones((8, 8)))
        con = rt.add_constraint([a, b], axis=1)
        assert con.axis == 1
        np.testing.assert_allclose((a * b).asarray(),
                                   np.arange(64).reshape(8, 8))


# ---------------------------------------------------------------------------
# jit / remote (reference: ramba.py:549-874)
# ---------------------------------------------------------------------------


class TestJitRemote:
    def test_jit_on_ndarray(self):
        @rt.jit
        def f(x, y):
            return x * 2 + y

        a = rt.arange(100).astype(np.float64)
        out = f(a, 3.0)
        assert isinstance(out, rt.ndarray)
        np.testing.assert_allclose(out.asarray(), np.arange(100) * 2 + 3)

    def test_jit_plain_args(self):
        @rt.jit
        def f(x):
            return x + 1

        assert int(f(np.int64(1))) == 2

    def test_remote_function(self):
        @rt.remote
        def work(x):
            return x * x

        fut = work.remote(7)
        assert rt.get(fut) == 49
        assert work(3) == 9

    def test_remote_class(self):
        @rt.remote
        class Counter:
            def __init__(self, start):
                self.n = start

            def incr(self, k):
                self.n += k
                return self.n

        c = Counter.remote(10)
        assert rt.get(c.incr.remote(5)) == 15
        assert rt.get([c.incr.remote(1), c.incr.remote(1)]) == [16, 17]


# ---------------------------------------------------------------------------
# distributed bring-up (reference: common.py:49-100, ramba.py:10650-10724)
# ---------------------------------------------------------------------------


class TestDistributed:
    def test_in_driver_and_process_identity(self):
        import jax

        # single host: the one process IS the driver; cross-process leg:
        # exactly rank 0 is (the reference's MPI in_driver gating)
        assert rt.distributed.in_driver() == (jax.process_index() == 0)
        assert rt.distributed.process_count() == jax.process_count()
        assert rt.distributed.process_index() == jax.process_index()

    def test_initialize_noop_without_coordinator(self):
        rt.distributed.initialize()  # must not raise when already up/solo

    def test_global_mesh(self):
        import jax

        m = rt.distributed.global_mesh()
        assert m.devices.size == len(jax.devices())

    def test_local_devices(self):
        import jax

        assert (len(rt.distributed.local_devices())
                == len(jax.devices()) // jax.process_count())


class TestPersistentCache:
    """Reference: RAMBA_CACHE Numba disk cache (ramba.py:177-246) — here
    jax's persistent compilation cache, in ONE directory placed from
    outside (a compile writing there is checked in fresh interpreters by
    tests/test_compile_cache_dir.py; this suite runs with the cache off,
    see conftest.py)."""

    def test_one_directory_placed_by_the_environment(self, tmp_path,
                                                     monkeypatch):
        from ramba_tpu import common

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert common.compile_cache_dir() == os.path.join(repo, ".jax_cache")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "x"))
        assert common.compile_cache_dir() == str(tmp_path / "x")
        # RAMBA_CACHE never moves it
        monkeypatch.setenv("RAMBA_CACHE", str(tmp_path / "aot"))
        assert common.compile_cache_dir() == str(tmp_path / "x")

    def test_ramba_cache_arms_only_the_aot_lane(self, tmp_path, monkeypatch):
        from ramba_tpu import common

        monkeypatch.delenv("RAMBA_CACHE", raising=False)
        assert common.persistent_cache_path() is None
        monkeypatch.setenv("RAMBA_CACHE", "0")
        assert common.persistent_cache_path() is None
        monkeypatch.setenv("RAMBA_CACHE", str(tmp_path / "aot"))
        assert common.persistent_cache_path() == str(tmp_path / "aot")
        monkeypatch.setenv("RAMBA_CACHE", "1")
        assert common.persistent_cache_path() == os.path.join(
            common.compile_cache_dir(), "ramba_aot")


class TestApiParity:
    """Module-level names from the reference public surface
    (ramba.py:8546-9857) added for completeness."""

    def test_isscalar(self):
        assert rt.isscalar(3) and rt.isscalar(2.5)
        assert not rt.isscalar(np.zeros(3))
        assert rt.isscalar(rt.fromarray(np.float64(2.0)))
        assert not rt.isscalar(rt.arange(4))

    def test_result_type(self):
        a = rt.arange(4).astype(np.int32)
        assert rt.result_type(a, np.float64) == np.result_type(np.int32, np.float64)

    def test_implements_extension(self):
        from ramba_tpu.core.interop import HANDLED_FUNCTIONS

        fn = np.trapezoid if hasattr(np, "trapezoid") else np.trapz
        try:
            @rt.implements(fn)
            def my_trap(y, *args, **kwargs):
                return "custom"

            assert fn(rt.arange(5.0)) == "custom"
        finally:
            HANDLED_FUNCTIONS.pop(fn, None)

    def test_apply_index(self):
        shape = (10, 8, 6)
        dim_shapes, (cindex, axismap) = rt.apply_index(
            shape, (slice(1, 9, 2), 3, slice(None)))
        assert dim_shapes == (4, 6)
        assert axismap == [0, 2]
        assert cindex[1] == slice(3, 4, 1)

    def test_reshape_copy(self):
        a = rt.arange(12.0)
        b = rt.reshape_copy(a, (3, 4))
        b[0, 0] = 99.0
        assert float(a[0]) == 0.0  # copy, not a view
        c = a.reshape_copy(4, 3)
        assert c.shape == (4, 3)

    def test_create_array_with_divisions(self):
        # split-count form
        a = rt.create_array_with_divisions((16, 8), (4, 1), dtype=np.float64)
        assert a.shape == (16, 8) and a.dtype == map_dtype(np.float64)
        # reference (nworkers, 2, ndim) start/end ranges form: 4 row blocks
        div = np.array([[[i * 4, 0], [(i + 1) * 4, 8]] for i in range(4)])
        b = rt.create_array_with_divisions((16, 8), div)
        assert b.shape == (16, 8)
        b[:] = 1.0
        assert float(b.sum()) == 128.0

    def test_fromarray_distribution_forms(self):
        from jax.sharding import PartitionSpec as P

        x = np.arange(64.0).reshape(8, 8)
        for dist in (None, (4, 1), P("d0"), ):
            a = rt.fromarray(x, distribution=dist)
            np.testing.assert_allclose(a.asarray(), x)

    def test_comm_stats(self, capsys):
        rt.reset_timing()
        a = rt.fromarray(np.arange(1000.0))
        a.asarray()
        st = rt.timing.comm_stats
        nbytes = 1000 * np.dtype(map_dtype(np.float64)).itemsize
        assert st["host_to_device_bytes"] >= nbytes
        assert st["device_to_host_bytes"] >= nbytes
        rt.print_comm_stats(file=None)  # prints to stderr

    def test_timing_str_and_passthroughs(self):
        # reference surface: module-level add_time/add_sub_time/time_dict/
        # get_timing_str (ramba.py:985-1019); orphan sub-timers must be
        # visible in reports (review r4)
        rt.reset_timing()
        rt.add_time("flush", 0.25)
        rt.add_sub_time("flush", "compile", 0.1)
        rt.add_sub_time("orphan", "x", 0.1)
        s = rt.get_timing_str(details=True)
        assert "flush: 0.25s(1)" in s and "compile: 0.1s(1)" in s, s
        assert "orphan" in s and "x: 0.1s(1)" in s, s
        assert "flush" in rt.time_dict
        rt.reset_timing()

    def test_numpy_alias_reexports(self):
        # /root/reference/ramba/__init__.py:20 re-exports numpy C-named
        # aliases; drop-in users reference them as ramba.double etc.
        for name in ("byte", "short", "intc", "uint", "half", "single",
                     "double", "longdouble", "csingle", "cdouble"):
            assert getattr(rt, name) is getattr(np, name), name
        assert rt.iinfo(rt.int32).max == 2 ** 31 - 1
        assert rt.finfo(np.float32).eps == np.finfo(np.float32).eps

    def test_reset_timing(self):
        rt.timing.add_time("x", 1.0)
        rt.reset_timing()
        assert "x" not in rt.timing.time_dict


class TestApiParityReviewFixes:
    def test_apply_index_bounds_and_ellipsis(self):
        with pytest.raises(IndexError):
            rt.apply_index((10,), (15,))
        ds, (ci, am) = rt.apply_index((3, 4), (Ellipsis, 2))
        assert ds == (3,) and am == [0] and ci[1] == slice(2, 3, 1)
        ds, _ = rt.apply_index((3, 4), (None, slice(None), 1))
        assert ds == (1, 3)
        ds, (ci, _) = rt.apply_index((5,), (-2,))
        assert ci[0] == slice(3, 4, 1)

    def test_spec_from_splits_subset(self):
        import jax
        from jax.sharding import Mesh

        from ramba_tpu.parallel.mesh import spec_from_splits

        if len(jax.devices()) < 8:
            pytest.skip("needs 8 devices for the (2,2,2) mesh")
        devs = np.array(jax.devices()[:8]).reshape(2, 2, 2)
        mesh = Mesh(devs, axis_names=("a", "b", "c"))
        spec = spec_from_splits((4,), mesh)
        # 4 needs two of the 2-sized axes
        assert spec and isinstance(spec[0], tuple) and len(spec[0]) == 2

    def test_fromarray_distribution_counts_transfer(self):
        rt.reset_timing()
        rt.fromarray(np.arange(4096.0), distribution=(8,))
        assert rt.timing.comm_stats["host_to_device_bytes"] >= 4096 * 8


class TestApplyIndexCanonical:
    def test_negative_step_slice_reusable(self):
        ds, (ci, _) = rt.apply_index((5,), (slice(None, None, -1),))
        assert ds == (5,)
        x = np.arange(5)
        np.testing.assert_array_equal(x[ci[0]], x[::-1])
        ds2, (ci2, _) = rt.apply_index((10,), (slice(8, 2, -2),))
        assert ds2 == (3,)
        np.testing.assert_array_equal(np.arange(10)[ci2[0]],
                                      np.arange(10)[8:2:-2])

class TestAdviceBacklogR2:
    """Regression tests for the round-1 ADVICE items (VERDICT r2 #10)."""

    def test_min_out_positional(self):
        # a.min(0, out) must WRITE out (numpy positional order is
        # (axis, out) for min/max/any/all — no dtype slot)
        a = rt.fromarray(np.arange(12.0).reshape(3, 4))
        out = rt.zeros(4)
        r = a.min(0, out)
        assert r is out
        np.testing.assert_allclose(out.asarray(), [0.0, 1.0, 2.0, 3.0])
        out2 = rt.zeros(3)
        a.max(1, out2)
        np.testing.assert_allclose(out2.asarray(), [3.0, 7.0, 11.0])

    def test_module_level_out_positional(self):
        a = rt.fromarray(np.arange(12.0).reshape(3, 4))
        out = rt.zeros(4)
        assert rt.min(a, 0, out) is out
        np.testing.assert_allclose(out.asarray(), [0.0, 1.0, 2.0, 3.0])
        # sum keeps numpy's (a, axis, dtype, out) order
        out3 = rt.zeros(4)
        assert rt.sum(a, 0, None, out3) is out3
        np.testing.assert_allclose(out3.asarray(), [12.0, 15.0, 18.0, 21.0])

    def test_any_all_out(self):
        a = rt.fromarray(np.array([[True, False], [True, True]]))
        out = rt.zeros(2, dtype=bool)
        assert a.all(0, out) is out
        np.testing.assert_array_equal(out.asarray(), [True, False])

    def test_double_ellipsis_raises(self):
        a = rt.fromarray(np.arange(12.0).reshape(3, 4))
        with pytest.raises(IndexError, match="single ellipsis"):
            a[..., ...]

    def test_pre_freeze_view_stays_writeable(self):
        # numpy: a view taken before the base is frozen keeps its own
        # writeable flag and writes through
        a = rt.fromarray(np.zeros(6))
        v = a[2:5]
        a.flags.writeable = False
        assert v.flags.writeable
        v[0] = 7.0
        np.testing.assert_allclose(a.asarray(), [0, 0, 7.0, 0, 0, 0])
        # but a NEW view of the frozen base is read-only
        w = a[1:3]
        assert not w.flags.writeable
        with pytest.raises(ValueError):
            w[0] = 1.0

    def test_divisions_covers_all_shards(self):
        from ramba_tpu.parallel.shardview import divisions

        a = rt.zeros((64, 64))
        rt.sync()
        d = divisions(a)
        import jax

        assert d.shape[0] == len(jax.devices())
        # the union of shard boxes covers the full array exactly
        total = sum(
            int(np.prod(np.maximum(0, d[i, 1] - d[i, 0])))
            for i in range(d.shape[0])
        )
        assert total == 64 * 64

class TestMultiProcess:
    """The reference CI's mpiexec -n 2 leg (python-package.yml:40-46), as
    jax multi-controller SPMD.  Spawns two fresh processes, so it is gated
    behind RAMBA_TPU_MULTIPROC_TEST=1 to keep the default suite fast.
    The FULL-suite version of this leg is scripts/two_process_suite.py,
    which runs every test cross-process (round-4 verdict #4)."""

    @pytest.mark.skipif(
        not os.environ.get("RAMBA_TPU_MULTIPROC_TEST"),
        reason="2-process smoke spawns fresh processes; run via "
               "RAMBA_TPU_MULTIPROC_TEST=1, or use the full cross-process "
               "leg: scripts/two_process_suite.py",
    )
    def test_two_process_smoke(self):
        import subprocess
        import sys

        script = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "scripts", "two_process_smoke.py",
        )
        r = subprocess.run(
            [sys.executable, "-u", script], capture_output=True, text=True,
            timeout=300,
        )
        assert r.returncode == 0, r.stdout + r.stderr

class TestTraceND:
    def test_trace_matches_numpy(self):
        a = np.arange(24.0).reshape(2, 3, 4)
        for kw in ({}, {"offset": 1}, {"axis1": 1, "axis2": 2},
                   {"offset": -1, "axis1": 0, "axis2": 2}):
            got = rt.trace(rt.fromarray(a), **kw).asarray()
            np.testing.assert_allclose(got, np.trace(a, **{
                "offset": kw.get("offset", 0),
                "axis1": kw.get("axis1", 0),
                "axis2": kw.get("axis2", 1),
            }))
        m = np.arange(16.0).reshape(4, 4)
        assert float(rt.trace(rt.fromarray(m))) == np.trace(m)

class TestDtypePromotionParity:
    """NumPy NEP-50 promotion parity (the reference computes with
    numpy/Numba and inherits these semantics; here numpy's own
    ufunc.resolve_dtypes supplies the loop dtypes under x64)."""

    DTYPES = [np.int8, np.uint8, np.int32, np.int64, np.float32,
              np.float64, np.bool_]

    def test_binop_matrix(self):
        import warnings

        for d1 in self.DTYPES:
            for d2 in self.DTYPES:
                a = np.ones(4, dtype=d1)
                b = np.full(4, 2, dtype=d2)
                for op in ("add", "multiply", "true_divide", "maximum"):
                    with warnings.catch_warnings():
                        warnings.simplefilter("ignore")
                        want = np.asarray(getattr(oracle(), op)(a, b))
                        got = getattr(np, op)(
                            rt.fromarray(a), rt.fromarray(b)
                        ).asarray()
                    assert got.dtype == want.dtype, (op, d1, d2, got.dtype)
                    np.testing.assert_allclose(got, want)

    def test_weak_scalar_promotion(self):
        # NEP 50: int32_arr + python_float -> float64; f32_arr + float -> f32
        # (x32 regime: jax lattice -> f32 for the first case, via oracle)
        orc = oracle()
        x = rt.fromarray(np.ones(4, np.int32))
        assert (x + 2.0).asarray().dtype == np.asarray(
            orc.add(np.ones(4, np.int32), 2.0)).dtype
        y = rt.fromarray(np.ones(4, np.float32))
        assert (y + 2.0).asarray().dtype == np.float32
        assert (x + 2).asarray().dtype == np.int32

    def test_int_division_is_float64(self):
        # (float32 under the x32 regime's jax lattice)
        a = rt.fromarray(np.array([1, 2, 7], np.int32))
        r = (a / rt.fromarray(np.array([2, 4, 2], np.int32))).asarray()
        assert r.dtype == np.asarray(
            oracle().true_divide(np.ones(1, np.int32), np.ones(1, np.int32))
        ).dtype
        np.testing.assert_allclose(r, [0.5, 0.5, 3.5])

class TestViewAliasingEdges:
    """Write-through across gnarly view chains (reference: views share a
    gid and all writes land in the base shards, ramba.py:5545-5565)."""

    @pytest.mark.parametrize("name,mutate", [
        ("neg step write",
         lambda a: a[::-1].__setitem__((0, slice(None)), 99.0)),
        ("reshape view write",
         lambda a: a.reshape(6, 4).__setitem__((2, slice(None)), -1.0)),
        ("chained view write", lambda a: a[1:][1:].__setitem__(0, 5.0)),
        ("transpose slice iadd", lambda a: a.T[2:4].__iadd__(10.0)),
        ("ravel write",
         lambda a: a.reshape(-1).__setitem__(slice(3, 9), 0.0)),
        ("col neg step imul", lambda a: a[:, ::-2].__imul__(2.0)),
        ("newaxis write",
         lambda a: a[:, None, :].__setitem__((1, 0, slice(None)), 7.0)),
    ])
    def test_write_through(self, name, mutate):
        w = np.arange(24.0).reshape(4, 6)
        g = rt.fromarray(w.copy())
        mutate(w)
        mutate(g)
        np.testing.assert_allclose(np.asarray(g), w, err_msg=name)


class TestCumulativePromotion:
    def test_small_int_scans_widen(self):
        # numpy: cumsum/cumprod of sub-word ints promote to int64/uint64
        for dt in (np.int8, np.int16, np.int32, np.uint8, np.bool_):
            a = np.ones(10, dtype=dt)
            for op in ("cumsum", "cumprod"):
                w = np.asarray(getattr(oracle(), op)(a))
                g = getattr(rt, op)(rt.fromarray(a)).asarray()
                assert g.dtype == w.dtype, (op, dt, g.dtype, w.dtype)
                np.testing.assert_array_equal(g, w)

class TestJoinPromotionParity:
    def test_concat_stack_where_mixed_dtypes(self):
        i = np.ones(4, np.int32)
        f = np.ones(4, np.float32)
        for name, fn in [
            ("concat", lambda ap: ap.concatenate(
                [ap.asarray(i), ap.asarray(f)])),
            ("stack", lambda ap: ap.stack(
                [ap.asarray(i), ap.asarray(f)])),
            ("where", lambda ap: ap.where(
                ap.asarray(i) > 0, ap.asarray(i), ap.asarray(f))),
        ]:
            w = np.asarray(fn(oracle()))
            g = np.asarray(fn(rt))
            assert g.dtype == w.dtype, (name, g.dtype, w.dtype)
            np.testing.assert_allclose(g, w)
        # weak scalar in where keeps the array dtype (NEP 50)
        r = rt.where(rt.fromarray(f) > 0, rt.fromarray(f), 0.0).asarray()
        assert r.dtype == np.float32

class TestModfDivmod:
    def test_modf(self):
        v = np.array([1.7, -2.3, 0.5, -0.0])
        wf, wi = np.modf(v)
        gf, gi = rt.modf(rt.fromarray(v))
        np.testing.assert_allclose(gf.asarray(), wf,
                                   rtol=default_rtol(1e-7))
        np.testing.assert_allclose(gi.asarray(), wi)

    def test_divmod(self):
        a = np.array([7, -7, 9])
        b = np.array([3, 3, -4])
        wq, wr = np.divmod(a, b)
        gq, gr = rt.divmod(rt.fromarray(a), rt.fromarray(b))
        np.testing.assert_array_equal(gq.asarray(), wq)
        np.testing.assert_array_equal(gr.asarray(), wr)
