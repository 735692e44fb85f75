"""Compile classes, persistent AOT cache, and warm pool (PR 14:
``ramba_tpu/compile/``, RAMBA_COMPILE_CLASSES / RAMBA_CACHE / RAMBA_AOT).

The contract under test, in order of importance:

* **Byte identity** — a bucketed execution (pad to the compile class,
  run at the bucket shape, slice back) must produce byte-identical
  results to the exact-shape execution of the same program, proven by a
  seeded fuzz oracle with RAMBA_VERIFY=strict and memoization on.
* **Safety discipline** — only elementwise programs may bucket; a
  shape-sensitive instruction (flip, reduce, cumulative, ...) bails out
  to an exact-shape compile (``compile.bucket_bailout``), and a forged
  bucket claim (fault site ``compile:bucket``) is caught by the
  ``compile-class`` verify rule *before* any data is touched.
* **Warm start** — a second process sharing a persist cache answers
  from deserialized AOT executables: zero compiles, zero compile
  seconds in its ledger.  Corrupt entries evict and recompile
  (``compile:persist``), never raise.
* **Executable sharing** — a randomized-leading-dim soak under pow2
  keeps the compile-cache hit rate above 95%: many request extents,
  a handful of executables.

The SPMD analog (identical bucket decisions on both ranks, warm phase
answering from the shared cache) is ``scripts/two_process_suite.py
--warmstart-leg``.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax as _jax

import ramba_tpu as rt
from ramba_tpu import common
from ramba_tpu.analyze.findings import ProgramVerificationError
from ramba_tpu.compile import classes, persist, warmpool
from ramba_tpu.core import fuser
from ramba_tpu.observe import events, ledger, registry
from ramba_tpu.resilience import faults

_MULTIPROC = _jax.process_count() > 1
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _isolate():
    """Empty pending set, pow2 classes armed, persist disarmed, no
    faults; env restored manually (not via monkeypatch) so the final
    ``classes.reset()`` re-reads the *restored* environment and nothing
    leaks into other test modules."""
    saved = {k: os.environ.get(k)
             for k in ("RAMBA_COMPILE_CLASSES", "RAMBA_CACHE", "RAMBA_AOT")}
    fuser.flush()
    faults.configure(None)
    os.environ["RAMBA_COMPILE_CLASSES"] = "pow2"
    os.environ.pop("RAMBA_CACHE", None)
    os.environ.pop("RAMBA_AOT", None)
    classes.reset()
    persist.reset()
    yield
    faults.reset()
    for k, v in saved.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v
    classes.reset()
    persist.reset()


def _findings(fs, rule, severity=None):
    return [f for f in fs if f.rule == rule
            and (severity is None or f.severity == severity)]


# ---------------------------------------------------------------------------
# bucket math
# ---------------------------------------------------------------------------


class TestBucketMath:
    def test_pow2(self):
        pol = ("pow2",)
        assert [classes.bucket_for(n, pol) for n in
                (1, 2, 3, 4, 5, 8, 9, 255, 256, 300)] == \
            [1, 2, 4, 4, 8, 8, 16, 256, 256, 512]

    def test_linear(self):
        pol = ("linear", 5)
        assert [classes.bucket_for(n, pol) for n in
                (1, 4, 5, 6, 11, 300)] == [5, 5, 5, 10, 15, 300]

    def test_degenerate_extents_pass_through(self):
        assert classes.bucket_for(0, ("pow2",)) == 0
        assert classes.bucket_for(-3, ("linear", 4)) == -3

    def test_policy_parse(self):
        assert classes._parse("") == ("off",)
        assert classes._parse("off") == ("off",)
        assert classes._parse("0") == ("off",)
        assert classes._parse("pow2") == ("pow2",)
        assert classes._parse("1") == ("pow2",)
        assert classes._parse("linear:16") == ("linear", 16)
        # malformed policies fail safe to exact shapes, never crash
        assert classes._parse("linear:zero") == ("off",)
        assert classes._parse("linear:0") == ("off",)
        assert classes._parse("cubic") == ("off",)


# ---------------------------------------------------------------------------
# planning: who buckets, who bails
# ---------------------------------------------------------------------------


class TestPlanning:
    def test_elementwise_flush_buckets_and_lands_on_span(self):
        base = np.arange(40, dtype=np.float32).reshape(5, 8)
        a = rt.array(base)
        out = np.asarray(a * 2.0 + 1.0)
        np.testing.assert_array_equal(out, base * 2.0 + 1.0)
        snap = classes.snapshot()
        assert snap["planned"] >= 1 and snap["padded"] >= 1, snap
        assert snap["pad_bytes"] > 0 and snap["pad_waste_frac"] > 0
        span = events.last(1, type="flush")[-1]
        assert span.get("compile_class") == ["pow2", 8], span
        assert span.get("pad_waste_bytes", 0) > 0

    def test_class_charged_to_ledger(self):
        a = rt.array(np.ones((5, 8), np.float32))
        np.asarray(rt.expm1(a) * 0.5)
        ks = ledger.snapshot()["kernels"]
        tagged = [k for k in ks.values()
                  if k.get("compile_class") == ["pow2", 8]]
        assert tagged, "no ledger entry carries the compile class"
        assert any(k.get("pad_waste", 0) > 0 for k in tagged)

    def test_decision_recorded_per_fingerprint(self):
        a = rt.array(np.ones((6, 8), np.float32))
        np.asarray(a + 2.5)
        dec = classes.decisions()
        assert ("pow2", 8) in dec.values(), dec

    def test_exact_power_of_two_pads_nothing(self):
        base = np.arange(32, dtype=np.float32).reshape(4, 8)
        p0 = classes.snapshot()["padded"]
        out = np.asarray(rt.array(base) * 3.0)
        np.testing.assert_array_equal(out, base * 3.0)
        snap = classes.snapshot()
        assert snap["planned"] >= 1
        assert snap["padded"] == p0  # bucket == n: plan, but no pad

    def test_shape_sensitive_program_bails_out(self):
        base = np.arange(40, dtype=np.float32).reshape(5, 8)
        b0 = classes.snapshot()["bailouts"]
        r0 = registry.get("compile.bucket_bailout")
        got = float(rt.sum(rt.array(base) * 2.0))
        assert got == pytest.approx(float(np.sum(base * 2.0)))
        assert classes.snapshot()["bailouts"] > b0
        assert registry.get("compile.bucket_bailout") > r0

    def test_broadcast_leaf_not_padded(self):
        x = np.arange(40, dtype=np.float32).reshape(5, 8)
        row = np.arange(8, dtype=np.float32).reshape(1, 8)
        out = np.asarray(rt.array(x) + rt.array(row))
        np.testing.assert_array_equal(out, x + row)
        assert classes.snapshot()["planned"] >= 1

    def test_linear_policy_token(self, monkeypatch):
        monkeypatch.setenv("RAMBA_COMPILE_CLASSES", "linear:4")
        classes.reset()
        base = np.ones((6, 8), np.float32)
        np.asarray(rt.array(base) * 4.0)
        span = events.last(1, type="flush")[-1]
        assert span.get("compile_class") == ["linear:4", 8], span

    def test_off_plans_nothing(self, monkeypatch):
        monkeypatch.setenv("RAMBA_COMPILE_CLASSES", "off")
        classes.reset()
        np.asarray(rt.array(np.ones((5, 8), np.float32)) * 2.0)
        snap = classes.snapshot()
        assert snap["planned"] == 0 and snap["bailouts"] == 0
        span = events.last(1, type="flush")[-1]
        assert "compile_class" not in span


# ---------------------------------------------------------------------------
# byte identity: bucketed vs exact-shape oracle (fuzz)
# ---------------------------------------------------------------------------


_UNARY = [rt.tanh, rt.sin, rt.exp, lambda t: t * 1.5 - 0.25]
_BINARY = [lambda t, u: t + u, lambda t, u: t * u,
           lambda t, u: t - 0.5 * u, rt.maximum]


class TestByteIdentity:
    def test_fuzz_bucketed_matches_exact(self, monkeypatch):
        """Seeded random map chains over random (n, k) leaves, each run
        twice — classes off (oracle) and pow2 (bucketed) — with the
        strict verifier and memoization on.  assert_array_equal is byte
        identity: elementwise rows are computed independently, so the
        pad/slice wrapper must be exact, not approximately right."""
        from ramba_tpu.core import memo

        monkeypatch.setenv("RAMBA_VERIFY", "strict")
        monkeypatch.setenv("RAMBA_MEMO", "1")
        memo.reset()
        rng = np.random.default_rng(1414)
        try:
            for _trial in range(10):
                n = int(rng.integers(1, 34))
                k = int(rng.integers(1, 10))
                base = rng.standard_normal((n, k)).astype(np.float32)
                other = rng.standard_normal((n, k)).astype(np.float32)
                steps = [(int(rng.integers(len(_UNARY))),
                          int(rng.integers(len(_BINARY))))
                         for _ in range(int(rng.integers(1, 4)))]

                def compute():
                    x, y = rt.array(base), rt.array(other)
                    z = x
                    for ui, bi in steps:
                        z = _BINARY[bi](_UNARY[ui](z), y)
                    return np.asarray(z)

                monkeypatch.setenv("RAMBA_COMPILE_CLASSES", "off")
                classes.reset()
                exact = compute()
                monkeypatch.setenv("RAMBA_COMPILE_CLASSES", "pow2")
                classes.reset()
                bucketed = compute()
                np.testing.assert_array_equal(exact, bucketed)
            assert classes.snapshot()["planned"] >= 1
        finally:
            memo.reset()


# ---------------------------------------------------------------------------
# the compile-class verify rule vs a forged bucket claim
# ---------------------------------------------------------------------------


class TestVerifyRule:
    def test_forged_claim_raises_in_strict(self, monkeypatch):
        monkeypatch.setenv("RAMBA_VERIFY", "strict")
        base = np.arange(48, dtype=np.float32).reshape(6, 8)
        a = rt.array(base)
        b = rt.flip(a * 2.0, axis=0)  # flip would read the pad rows
        with faults.inject("compile:bucket", "once"):
            with pytest.raises(ProgramVerificationError) as ei:
                fuser.flush()
        errs = _findings(ei.value.findings, "compile-class", "error")
        assert errs, ei.value.findings
        assert "shape-sensitive" in errs[0].message
        # nothing executed on the forged plan; the retry (fault consumed)
        # bails out to exact shapes and computes the right answer
        monkeypatch.setenv("RAMBA_VERIFY", "0")
        np.testing.assert_array_equal(np.asarray(b),
                                      np.flip(base * 2.0, axis=0))

    def test_forged_claim_routes_down_ladder_in_warn(self, monkeypatch):
        monkeypatch.setenv("RAMBA_VERIFY", "warn")
        base = np.arange(48, dtype=np.float32).reshape(6, 8)
        b = rt.flip(rt.array(base) * 2.0, axis=0)
        with faults.inject("compile:bucket", "once"):
            fuser.flush()
        ev = events.last(8, type="finding")
        assert any(e["rule"] == "compile-class" for e in ev), ev
        # the distrusted flush dropped the plan: exact-shape fallback,
        # correct bytes
        np.testing.assert_array_equal(np.asarray(b),
                                      np.flip(base * 2.0, axis=0))

    def test_honest_bucketed_flush_is_clean_in_strict(self, monkeypatch):
        monkeypatch.setenv("RAMBA_VERIFY", "strict")
        base = np.arange(24, dtype=np.float32).reshape(3, 8)
        out = np.asarray(rt.array(base) * 2.0 + 1.0)  # must not raise
        np.testing.assert_array_equal(out, base * 2.0 + 1.0)
        assert classes.snapshot()["planned"] >= 1


# ---------------------------------------------------------------------------
# persistent AOT cache
# ---------------------------------------------------------------------------


class TestPersistCache:
    def test_disarmed_without_cache_dir(self):
        assert not persist.armed()
        assert persist.snapshot()["dir"] is None

    def test_ramba_aot_zero_disarms(self, tmp_path, monkeypatch):
        monkeypatch.setenv("RAMBA_CACHE", str(tmp_path / "c"))
        monkeypatch.setenv("RAMBA_AOT", "0")
        persist.reconfigure()
        assert not persist.armed()

    def test_aot_roundtrip_serves_without_recompiling(
            self, tmp_path, monkeypatch):
        monkeypatch.setenv("RAMBA_CACHE", str(tmp_path / "cache"))
        persist.reconfigure()
        assert persist.armed(), persist.snapshot()
        # forget executables compiled before the lane was armed — only a
        # fresh compile registers an AOT candidate
        with fuser._cache_lock:
            fuser._compile_cache.clear()
        base = np.arange(40, dtype=np.float32).reshape(5, 8)
        np.asarray(rt.array(base) * 3.0 + 1.0)
        rep = persist.save_topk(4)
        assert rep["stored"] >= 1, rep
        assert persist.snapshot()["bytes_written"] > 0
        # a fresh in-memory cache must answer from disk: is_new stays
        # False, so the ledger sees near-zero compile wall
        with fuser._cache_lock:
            fuser._compile_cache.clear()
        h0 = persist.snapshot()["hits"]
        out = np.asarray(rt.array(base) * 3.0 + 1.0)
        np.testing.assert_array_equal(out, base * 3.0 + 1.0)
        snap = persist.snapshot()
        assert snap["hits"] == h0 + 1, snap
        assert snap["bytes_read"] > 0

    def test_corrupt_entry_evicts_and_recompiles(
            self, tmp_path, monkeypatch):
        monkeypatch.setenv("RAMBA_CACHE", str(tmp_path / "cache"))
        persist.reconfigure()
        with fuser._cache_lock:
            fuser._compile_cache.clear()
        base = np.arange(40, dtype=np.float32).reshape(5, 8)
        np.asarray(rt.array(base) * 7.0)
        assert persist.save_topk(4)["stored"] >= 1
        with fuser._cache_lock:
            fuser._compile_cache.clear()
        c0 = persist.snapshot()["corrupt"]
        with faults.inject("compile:persist", "once"):
            out = np.asarray(rt.array(base) * 7.0)  # must NOT raise
        np.testing.assert_array_equal(out, base * 7.0)
        snap = persist.snapshot()
        assert snap["corrupt"] == c0 + 1, snap
        assert registry.get("compile.persist_corrupt") >= 1
        # the bad entry was evicted from disk; the recompile re-registered
        # the fingerprint as a fresh AOT candidate
        assert snap["candidates"] >= 1


class TestAotStoresOnlyFreshCompiles:
    def test_jax_cache_hit_is_not_serialized(self, tmp_path, monkeypatch):
        """An executable jax loaded from its own persistent cache does not
        serialize to a blob another process can load, so the AOT lane
        skips it — observed through jax.monitoring, without switching
        jax's cache off or moving it."""
        import jax

        monkeypatch.setenv("RAMBA_CACHE", str(tmp_path / "cache"))
        persist.reconfigure()
        with fuser._cache_lock:
            fuser._compile_cache.clear()
        base = np.arange(56, dtype=np.float32).reshape(7, 8)
        np.asarray(rt.array(base) * 11.0 - 2.0)
        real_compile = jax.stages.Lowered.compile

        def compile_as_cache_hit(self, *a, **k):
            jax.monitoring.record_event("/jax/compilation_cache/cache_hits")
            return real_compile(self, *a, **k)

        monkeypatch.setattr(jax.stages.Lowered, "compile",
                            compile_as_cache_hit)
        before = jax.config.jax_enable_compilation_cache
        rep = persist.save_topk(4)
        assert rep.get("stored", 0) == 0 and rep["skipped"] >= 1, rep
        assert registry.get("compile.persist_store_skipped_jax_cache") >= 1
        assert jax.config.jax_enable_compilation_cache == before


class TestPersistInit:
    def test_cache_status_fields_and_event(self, tmp_path, monkeypatch):
        """setup_compile_cache reports where jax's cache lives and says so
        on the event stream; with the variable set it leaves the placing
        to jax (which reads the variable itself at start-up)."""
        import jax

        before = jax.config.jax_compilation_cache_dir
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "xc"))
        st = common.setup_compile_cache()
        assert st.ok and st.source == "env", st
        assert st.path == str(tmp_path / "xc") and os.path.isdir(st.path)
        assert jax.config.jax_compilation_cache_dir == before
        ev = events.last(3, type="compile.persist_init")
        assert ev and ev[-1]["path"] == st.path and ev[-1]["ok"]

    def test_unset_env_is_the_checkout(self, monkeypatch):
        import jax

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        st = common.setup_compile_cache()
        assert st.ok and st.source == "checkout", st
        assert st.path == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == st.path

    def test_ramba_cache_does_not_place_jax_cache(self, tmp_path,
                                                  monkeypatch):
        import jax

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        monkeypatch.setenv("RAMBA_CACHE", str(tmp_path / "aot"))
        st = common.setup_compile_cache()
        assert st.path == os.path.join(REPO, ".jax_cache"), st
        assert jax.config.jax_compilation_cache_dir == st.path
        assert common.persistent_cache_path() == str(tmp_path / "aot")


# ---------------------------------------------------------------------------
# warm-compile observability + trace-replay warm pool
# ---------------------------------------------------------------------------


class TestWarmObservability:
    def test_warm_scope_tags_ledger_and_perf_report(self):
        from ramba_tpu import diagnostics

        with fuser._cache_lock:
            fuser._compile_cache.clear()
        with ledger.compile_source("warm"):
            a = rt.array(np.arange(24, dtype=np.float32).reshape(3, 8))
            np.asarray(rt.sinh(a) * 1.25)
        ks = ledger.snapshot()["kernels"]
        warm = [k for k in ks.values() if k.get("warm_compiles")]
        assert warm, "no ledger entry tagged source=warm"
        rep = diagnostics.perf_report()
        comp = rep.get("compile")
        assert comp and comp["compiles"]["warm"] >= 1, comp
        assert comp["compiles"]["warm_s"] >= 0.0
        assert comp["classes"]["mode"] == "pow2"

    @pytest.mark.skipif(_MULTIPROC, reason="single-process pipeline test")
    def test_warmpool_replays_trace_through_pipeline(
            self, tmp_path, monkeypatch):
        monkeypatch.setenv("RAMBA_CACHE", str(tmp_path / "cache"))
        persist.reconfigure()
        trace = str(tmp_path / "trace.jsonl")
        saved_path = events._trace_path
        events.configure(trace)
        try:
            a = rt.array(np.arange(32, dtype=np.float32).reshape(4, 8))
            np.asarray(rt.exp(a * 0.125))
        finally:
            events.configure(saved_path)
        assert persist.saved_fingerprints(), "program skeleton not saved"
        # forget the executable; the warm pool must rebuild it from the
        # trace + skeleton, through submit_warm (tagged source=warm)
        with fuser._cache_lock:
            fuser._compile_cache.clear()
        w0 = registry.get("compile.warmpool_submit")
        report = warmpool.warm(trace, top_k=4)
        assert report["submitted"] >= 1, report
        assert report["warmed"] >= 1 and report["failed"] == 0, report
        assert registry.get("compile.warmpool_submit") > w0
        ks = ledger.snapshot()["kernels"]
        assert any(k.get("warm_compiles") for k in ks.values())
        from ramba_tpu import serve

        serve.shutdown()

    def test_trace_report_prints_warm_demand_split(self, tmp_path):
        trace = str(tmp_path / "t.jsonl")
        saved_path = events._trace_path
        events.configure(trace)
        try:
            with fuser._cache_lock:
                fuser._compile_cache.clear()
            a = rt.array(np.arange(16, dtype=np.float32).reshape(2, 8))
            np.asarray(a * 5.0 - 2.0)
        finally:
            events.configure(saved_path)
        r = subprocess.run(
            [sys.executable,
             os.path.join(REPO, "scripts", "trace_report.py"), trace],
            capture_output=True, text=True, cwd=REPO, timeout=120)
        assert r.returncode == 0, r.stderr[-1000:]
        assert "compiles:" in r.stdout and "demand" in r.stdout, r.stdout
        assert "bucketed flushes:" in r.stdout, r.stdout


# ---------------------------------------------------------------------------
# second-process warm start (the acceptance criterion)
# ---------------------------------------------------------------------------


# argv: <phase>.  cold compiles + stores AOT entries; warm (same
# RAMBA_CACHE) must answer from them with zero compiles in its ledger.
# jax's own persistent cache is armed in both, where the environment says.
_WARMSTART_CHILD = """
import json
import sys
import numpy as np
import ramba_tpu as rt
from ramba_tpu import common
from ramba_tpu.compile import classes, persist
from ramba_tpu.observe import ledger
assert classes.enabled(), 'RAMBA_COMPILE_CLASSES not armed'
import jax
assert jax.config.jax_compilation_cache_dir == common.compile_cache_dir()
persist.reconfigure()
assert persist.armed(), persist.snapshot()
base = np.arange(48, dtype=np.float32).reshape(6, 8)
got = np.asarray((rt.array(base) * 2.0 + 1.0).asarray())
assert np.array_equal(got, base * 2.0 + 1.0), got
if sys.argv[1] == 'cold':
    rep = persist.save_topk(8)
    assert rep['stored'] + rep['skipped'] >= 1, rep
ks = ledger.snapshot()['kernels'].values()
print(json.dumps({
    'compiles': sum(k['compiles'] for k in ks),
    'compile_s': sum(k['compile_s'] for k in ks),
    'hits': persist.snapshot()['hits'],
    'call_fallbacks': persist.snapshot()['call_fallbacks'],
}))
"""


class TestWarmStart:
    def test_second_process_pays_zero_compiles(self, tmp_path):
        # jax's own cache is placed (by the environment) in a directory
        # that starts empty, so the cold process compiles fresh: the AOT
        # lane stores only fresh compiles (persist.store_entry)
        env = dict(os.environ)
        env.update(JAX_PLATFORMS="cpu", RAMBA_COMPILE_CLASSES="pow2",
                   RAMBA_CACHE=str(tmp_path / "cache"), PYTHONPATH=REPO,
                   JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
        for k in ("RAMBA_AOT", "RAMBA_FAULTS", "RAMBA_TRACE", "RAMBA_MEMO",
                  "RAMBA_VERIFY", "RAMBA_PERF", "RAMBA_TEST_PROCS"):
            env.pop(k, None)
        reports = {}
        for phase in ("cold", "warm"):
            r = subprocess.run(
                [sys.executable, "-c", _WARMSTART_CHILD, phase],
                capture_output=True, text=True, timeout=240,
                cwd=REPO, env=env)
            assert r.returncode == 0, (phase, r.stderr[-2000:])
            reports[phase] = json.loads(r.stdout.strip().splitlines()[-1])
        assert reports["cold"]["compiles"] >= 1, reports
        # the acceptance criterion: near-zero compile wall in the warm
        # process's ledger — here exactly zero, served from AOT entries
        assert reports["warm"]["compiles"] == 0, reports
        assert reports["warm"]["compile_s"] == 0.0, reports
        assert reports["warm"]["hits"] >= 1, reports
        assert reports["warm"]["call_fallbacks"] == 0, reports


# ---------------------------------------------------------------------------
# randomized-shape soak: many extents, a handful of executables
# ---------------------------------------------------------------------------


class TestShapeSoak:
    def test_soak_holds_95_percent_hit_rate(self):
        rng = np.random.default_rng(99)
        h0 = registry.get("fuser.cache_hit")
        m0 = registry.get("fuser.cache_miss")
        p0 = classes.snapshot()["planned"]
        for i in range(240):
            n = int(rng.integers(1, 301))
            base = np.full((n, 4), float(i % 7), np.float32)
            out = np.asarray(rt.array(base) * 2.0 + 1.0)
            assert out.shape == (n, 4)
            if i % 40 == 0:  # spot-check values, not just shapes
                np.testing.assert_array_equal(out, base * 2.0 + 1.0)
        hits = registry.get("fuser.cache_hit") - h0
        misses = registry.get("fuser.cache_miss") - m0
        assert hits + misses >= 240
        rate = hits / (hits + misses)
        # pow2 folds extents 1..300 onto <= 10 buckets: at most ~10
        # compiles across 240 flushes
        assert rate > 0.95, (hits, misses, rate)
        assert classes.snapshot()["planned"] - p0 >= 240
