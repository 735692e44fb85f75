"""Fusion and memory-behavior invariants.

The reference enforces fusion as CI-tested performance behavior
(/root/reference/ramba/tests/test_distributed_array.py:112-199): 10 fused
``a += 1`` must cost <2x one, unfusable slices >5x, and 500M-2B element
expressions must fit a 7 GB VM only if no temporaries materialize.  Timing
asserts are flaky on shared CI, so the rebuild expresses the SAME invariants
structurally: compile/flush counts (one fused program per batch, cache hits
on repeats) and XLA's own memory analysis (no materialized temporaries).
This is what SURVEY §4 prescribes: "re-express the fusion tests as
HLO-module-count / peak-HBM assertions".
"""

import numpy as np

import ramba_tpu as rt
from ramba_tpu.core import fuser


def _reset_point():
    rt.sync()
    return dict(fuser.stats)


class TestFusion:
    def test_chain_fuses_into_one_flush(self):
        before = _reset_point()
        a = rt.arange(10_000) / 1000.0
        b = rt.sin(a)
        c = rt.cos(a)
        d = b * b + c ** 2
        rt.sync()
        after = dict(fuser.stats)
        assert after["flushes"] - before["flushes"] == 1
        assert np.allclose(d.asarray(), 1.0)

    def test_inplace_loop_single_flush(self):
        # reference test_fuse: 10 fused a+=1 iterations (~cost of 1)
        before = _reset_point()
        a = rt.zeros(10_000)
        for _ in range(10):
            a += 1
        rt.sync()
        after = dict(fuser.stats)
        assert after["flushes"] - before["flushes"] == 1
        assert np.allclose(a.asarray(), 10.0)

    def test_repeat_program_hits_compile_cache(self):
        def run():
            x = rt.arange(5_000) / 7.0
            y = rt.sin(x) * rt.cos(x)
            rt.sync()
            return y

        run()
        before = _reset_point()
        run()
        run()
        after = dict(fuser.stats)
        # same structure, same shapes -> zero new XLA executables
        assert after["compiles"] == before["compiles"]

    def test_scalar_change_does_not_recompile(self):
        def run(k):
            x = rt.arange(5_000) * k
            rt.sync()
            return x

        run(1.5)
        before = _reset_point()
        run(2.5)
        run(3.5)
        after = dict(fuser.stats)
        assert after["compiles"] == before["compiles"]

    def test_fusion_eliminates_temporaries(self):
        # reference test_fuse2: a += (7a-3)+(4a+5a) on 500M float64 must not
        # materialize intermediates.  Structural version: XLA's memory
        # analysis of the fused program shows temp usage far below the
        # 3 intermediate buffers the unfused program would need.
        rt.sync()
        n = 1_000_000
        a = rt.ones(n)
        a += (7 * a - 3) + (4 * a + 5 * a)
        info = fuser.analyze_pending()
        assert info is not None
        nbytes = n * 8
        temp = info["temp_size_in_bytes"]
        if temp is not None and temp > 0:
            assert temp < 1.5 * nbytes, info
        rt.sync()
        assert np.allclose(a.asarray(), 1 + (7 - 3) + (4 + 5))

    def test_pi_integration_fused(self):
        # reference test_pi_integration_fused (2e9 elems in 7GB); scaled-down
        # numeric check + structural no-temporaries assertion.
        rt.sync()
        n = 2_000_000
        h = 1.0 / n
        x = (rt.arange(n) + 0.5) * h
        pi = rt.sum(4.0 / (1.0 + x * x)) * h
        info = fuser.analyze_pending()
        assert info is not None
        # the only large buffers are the output of the iota chain; reduction
        # must not materialize extra copies of x
        temp = info["temp_size_in_bytes"]
        if temp is not None and temp > 0:
            assert temp < 3 * n * 8, info
        assert abs(float(pi) - np.pi) < 1e-6

    def test_nofuse_slices_flush_separately(self):
        # reference test_nofuse: data-dependent slice writes can't fuse; here
        # each materialization point is its own flush when interleaved with
        # reads, and results stay correct.
        a = rt.zeros(1000)
        for i in range(5):
            a[i:] += 1
            assert float(a[i]) == i + 1  # read forces the flush
        np.testing.assert_allclose(
            a.asarray(), np.minimum(np.arange(1000) + 1, 5)[::1] * 0 +
            np.array([1, 2, 3, 4, 5] + [5] * 995)
        )


class TestSegmentation:
    """Oversized programs run as chained bounded jits (round-4 verdict #3:
    a 3000-op chain in one XLA program took minutes to compile)."""

    def test_long_chain_is_segmented_and_exact(self):
        before = _reset_point()
        n_ops = 1000
        x = rt.zeros(2_000, dtype="float32")
        for _ in range(n_ops):
            x = x + 1
        rt.sync()
        after = dict(fuser.stats)
        import math

        from ramba_tpu import common

        # a call packs the fewest repetitions that reach an eighth of a
        # segment, or a divisor of the count from half of that up
        expect = math.ceil(n_ops / (common.max_program_instrs // 8))
        segs = after["segments"] - before["segments"]
        # segment count scales with chain length (rewrite may shrink the
        # program slightly, hence >=); one flush, not one per segment
        assert expect - 1 <= segs <= 2 * expect, (segs, expect)
        assert after["flushes"] - before["flushes"] == 1
        np.testing.assert_allclose(x.asarray(), n_ops)

    def test_segment_count_scales_with_chain_length(self):
        counts = []
        for n_ops in (500, 1500):
            before = _reset_point()
            x = rt.zeros(512, dtype="float32")
            for _ in range(n_ops):
                x = rt.sqrt(x * x + 1.0) - rt.sqrt(x * x) + x
            rt.sync()
            counts.append(fuser.stats["segments"] - before["segments"])
        assert counts[1] > counts[0] >= 1, counts

    def test_segmented_dag_with_shared_subexprs_matches_numpy(self):
        # not a pure chain: shared subexpressions + several roots crossing
        # segment boundaries, checked differentially at a tiny segment size
        from ramba_tpu import common

        old = common.max_program_instrs
        common.max_program_instrs = 8
        try:
            rng = np.random.default_rng(0)
            an = rng.standard_normal(3_000).astype(np.float32)
            a = rt.array(an)
            b = a
            ref = an.copy()
            for i in range(40):
                s = b * 0.5 + i
                b = s + rt.sin(s) * 0.1
                sr = ref * 0.5 + i
                ref = sr + np.sin(sr) * 0.1
            c = b - a  # 'a' (an original leaf) used again in the last segment
            rt.sync()
            np.testing.assert_allclose(b.asarray(), ref, rtol=2e-5)
            np.testing.assert_allclose(c.asarray(), ref - an, rtol=2e-4, atol=2e-4)
        finally:
            common.max_program_instrs = old

    def test_segmentation_disabled_by_zero(self):
        from ramba_tpu import common

        old = common.max_program_instrs
        common.max_program_instrs = 0
        try:
            before = _reset_point()
            x = rt.zeros(256, dtype="float32")
            for _ in range(600):
                x = x + 1
            rt.sync()
            assert fuser.stats["segments"] == before["segments"]
            np.testing.assert_allclose(x.asarray(), 600)
        finally:
            common.max_program_instrs = old


class TestAnalyzePending:
    def test_none_when_empty(self):
        rt.sync()
        assert fuser.analyze_pending() is None

    def test_instruction_count(self):
        rt.sync()
        a = rt.arange(1000) + 1
        b = a * 2
        info = fuser.analyze_pending()
        assert info["instructions"] >= 2
        rt.sync()
