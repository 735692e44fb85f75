"""Kernel cost ledger and trace tooling (ramba-perf).

Covers ``ramba_tpu.observe.ledger`` + the fuser hooks + the offline CLIs:

* rolling-window p50/p95 math and full-history count/total/min/max,
* stable kernel fingerprints (equal cache keys fingerprint equally;
  donation mask and semantic regime separate them),
* ledger accumulation through real flushes (compile vs execute
  attribution, cache hit/miss, rung counts, bytes),
* true-LRU compile cache with ``fuser.cache_evict`` counter + event,
* the ``delay:ms=<n>`` RAMBA_FAULTS grammar,
* ``scripts/trace_report.py --merge-ranks`` over hand-built multi-rank
  JSONL (including a truncated final line),
* ``observe.events`` rank re-probing (no permanent ``(0, 1)`` cache
  before distributed bring-up).
"""

import io
import json
import os
import subprocess
import sys
import time

import pytest

import jax as _jax
import ramba_tpu as rt
from ramba_tpu import diagnostics
from ramba_tpu.core import fuser
from ramba_tpu.core.expr import Const
from ramba_tpu.observe import events, ledger
from ramba_tpu.resilience import faults

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_MULTIPROC = _jax.process_count() > 1


def _chain():
    a = rt.arange(512) * 3.0 + 1.0
    return float(rt.sum(a))


# ---------------------------------------------------------------------------
# rolling stats + fingerprints (pure units)
# ---------------------------------------------------------------------------


def test_rolling_window_quantile_math():
    r = ledger._Rolling(window=128)
    for i in range(1, 101):
        r.add(float(i))
    assert r.count == 100
    assert r.min == 1.0 and r.max == 100.0
    assert abs(r.total - 5050.0) < 1e-9
    assert r.quantile(0.50) == 50.0
    assert r.quantile(0.95) == 95.0
    assert r.quantile(1.0) == 100.0
    s = r.summary()
    assert s["p50_s"] == 50.0 and s["p95_s"] == 95.0

    # quantiles are over the bounded window; count/total keep full history
    r2 = ledger._Rolling(window=4)
    for v in (1.0, 2.0, 3.0, 4.0, 100.0):
        r2.add(v)
    assert r2.count == 5
    assert r2.quantile(0.5) == 3.0  # window is [2, 3, 4, 100]

    assert ledger._Rolling(window=4).quantile(0.5) is None


def test_fingerprint_stable_and_distinct():
    prog_key = ((("mul", None, (0,)),), 1, ("C",), (1,))
    key_a = (prog_key, (), (False,))
    # a separately-constructed equal tuple must fingerprint identically
    key_b = (((("mul", None, (0,)),), 1, ("C",), (1,)), (), (False,))
    fp = ledger.fingerprint(key_a)
    assert fp == ledger.fingerprint(key_b)
    assert len(fp) == 12
    # donation mask and semantic regime are part of the kernel identity
    assert ledger.fingerprint((prog_key, (0,), (False,))) != fp
    assert ledger.fingerprint((prog_key, (), (True,))) != fp
    # objects whose repr embeds addresses degrade to type/qualname tokens:
    # two distinct-but-equal-shaped closures must not split the fingerprint
    key_c = (prog_key, (), (False,), (lambda x: x,))
    key_d = (prog_key, (), (False,), (lambda x: x,))
    assert ledger.fingerprint(key_c) == ledger.fingerprint(key_d)


# ---------------------------------------------------------------------------
# ledger accumulation through real flushes
# ---------------------------------------------------------------------------


def test_ledger_accumulates_compile_and_exec():
    fuser.flush()
    diagnostics.reset()
    fuser._compile_cache.clear()
    v1 = _chain()
    v2 = _chain()
    assert v1 == v2
    rep = diagnostics.perf_report()
    fused = [k for k in rep["kernels"].values() if k["rungs"].get("fused")]
    assert fused, rep["kernels"]
    k = max(fused, key=lambda e: e["cache"]["misses"])
    assert k["label"].startswith("prog_")
    assert k["compiles"] >= 1
    assert k["compile_s"] > 0.0
    assert k["exec"]["count"] >= 1
    assert k["exec"]["p50_s"] is not None and k["exec"]["p50_s"] > 0.0
    assert k["exec"]["min_s"] <= k["exec"]["p50_s"] <= k["exec"]["max_s"]
    assert k["cache"]["misses"] >= 1 and k["cache"]["hits"] >= 1
    assert k["bytes_out"] > 0
    assert k["rungs"]["fused"] >= 2
    # per-program flush wall windows (the hedge threshold reads them)
    assert rep["flushes"]
    win = list(rep["flushes"].values())[0]
    assert win["count"] >= 2 and win["p50_s"] > 0.0


def test_sync_mode_records_synchronized_window():
    fuser.flush()
    ledger.reconfigure(mode="sync")
    try:
        diagnostics.reset()
        fuser._compile_cache.clear()
        _chain()
        _chain()
        rep = diagnostics.perf_report()
        assert rep["mode"] == "sync"
        synced = [k for k in rep["kernels"].values() if k.get("sync")]
        assert synced, rep["kernels"]
        s = synced[0]["sync"]
        assert s["count"] >= 1 and s["p50_s"] > 0.0
        if not _MULTIPROC:
            # sync mode implies cost capture; CPU XLA supplies flops
            assert any(k.get("flops") is not None
                       for k in rep["kernels"].values())
    finally:
        ledger.reconfigure()  # back to env-driven config


def test_ledger_records_eager_rung():
    fuser.flush()
    diagnostics.reset()
    a = rt.arange(64) * 2.0
    program, leaves, _ = fuser._prepare_program([a._expr])
    leaf_vals = [fuser.leaf_value(lf) if isinstance(lf, Const) else lf.value
                 for lf in leaves]
    outs = fuser._run_eager(program, leaf_vals, None)
    assert len(outs) == 1
    rep = diagnostics.perf_report()
    rungs = {}
    for k in rep["kernels"].values():
        for name, n in k["rungs"].items():
            rungs[name] = rungs.get(name, 0) + n
    assert rungs.get("eager", 0) >= 1, rungs


def test_diagnostics_report_includes_kernel_table():
    _chain()
    buf = io.StringIO()
    diagnostics.report(file=buf)
    out = buf.getvalue()
    assert "-- kernels" in out
    assert "hit/miss/evict" in out


# ---------------------------------------------------------------------------
# true-LRU compile cache + evict accounting
# ---------------------------------------------------------------------------


def test_compile_cache_true_lru_with_evict_counter(monkeypatch):
    from ramba_tpu.parallel import mesh as _mesh

    fuser.flush()
    monkeypatch.setattr(fuser, "_COMPILE_CACHE_MAX", 2)
    saved = dict(fuser._compile_cache)
    fuser._compile_cache.clear()
    fuser._cache_epoch = _mesh.mesh_epoch
    try:
        # jax.jit traces lazily, so programs with fake op names are safe
        # in _get_compiled as long as the returned fn is never called
        progs = [
            fuser._Program((((f"fakeop{i}", None, (0,)),)), 1, ("C",), (1,))
            for i in range(3)
        ]
        keys = [fuser._cache_key(p, ()) for p in progs]
        before = diagnostics.counters().get("fuser.cache_evict", 0)

        _fn, new0, fp0, _b = fuser._get_compiled(progs[0], ())
        assert new0
        _fn, new1, _, _b = fuser._get_compiled(progs[1], ())
        assert new1
        _fn, hit0, fp0b, _b = fuser._get_compiled(progs[0], ())  # refresh
        assert not hit0 and fp0b == fp0
        _fn, new2, _, _b = fuser._get_compiled(progs[2], ())  # evicts prog1
        assert new2

        # FIFO would have evicted prog0 (oldest insert); true LRU keeps it
        # because the hit refreshed its recency, and evicts prog1 instead
        assert keys[0] in fuser._compile_cache
        assert keys[1] not in fuser._compile_cache
        assert keys[2] in fuser._compile_cache

        after = diagnostics.counters().get("fuser.cache_evict", 0)
        assert after == before + 1
        evs = events.last(5, type="cache_evict")
        assert evs and evs[-1]["key"] == ledger.fingerprint(keys[1])
        # the ledger distinguishes capacity churn from cold misses
        entry = diagnostics.perf_report()["kernels"][
            ledger.fingerprint(keys[1])]
        assert entry["cache"]["evicts"] >= 1
    finally:
        fuser._compile_cache.clear()
        fuser._compile_cache.update(saved)


def test_program_fix_point_construction():
    # sanity: the hand-built _Program above matches what _get_compiled
    # expects (instrs tuple-of-tuples, out slot past the leaves)
    p = fuser._Program((("fakeop", None, (0,)),), 1, ("C",), (1,))
    assert p.key[0] == (("fakeop", None, (0,)),)
    assert p.n_leaves == 1 and p.out_slots == (1,)


# ---------------------------------------------------------------------------
# delay fault grammar
# ---------------------------------------------------------------------------


def test_delay_fault_grammar():
    sp = faults._parse_one("execute:delay:ms=50")
    assert sp.mode == "delay" and sp.kind == "delay"
    assert sp.delay_ms == 50.0
    with pytest.raises(ValueError):
        faults._parse_one("execute:delay")  # ms= payload required
    with pytest.raises(ValueError):
        faults._parse_one("execute:once:ms=50")  # ms= only with delay
    with pytest.raises(ValueError):
        faults._parse_one("execute:delay:ms=-5")
    with pytest.raises(ValueError):
        faults._parse_one("execute:delay:fatal:ms=5")  # delay takes no kind
    with pytest.raises(ValueError):
        faults._parse_one("execute:delay:ms=5:ms=6")


def test_delay_fault_sleeps_without_raising():
    with faults.active("mysite:delay:ms=40"):
        t0 = time.perf_counter()
        faults.check("mysite")  # must NOT raise
        dt = time.perf_counter() - t0
    assert dt >= 0.03, dt
    ev = events.last(3, type="fault")[-1]
    assert ev["site"] == "mysite"
    assert ev["kind"] == "delay" and ev["ms"] == 40.0


# ---------------------------------------------------------------------------
# trace_report: --merge-ranks
# ---------------------------------------------------------------------------


def _write_rank_file(path, evs, trailing_garbage: bool = False):
    with open(path, "w") as f:
        for e in evs:
            f.write(json.dumps(e) + "\n")
        if trailing_garbage:
            # a crashed writer leaves a truncated final line
            f.write('{"type":"flush","label":"prog_tail","ts":1.0')


def test_trace_report_merge_ranks(tmp_path):
    base = tmp_path / "t.jsonl"
    r0 = [
        {"type": "health", "source": "distributed_init", "outcome": "ok",
         "ts": 100.0, "seq": 1, "rank": 0},
        {"type": "flush", "label": "prog_a", "ts": 100.1, "seq": 2,
         "rank": 0, "wall_s": 0.01, "cache": "miss"},
        {"type": "flush", "label": "prog_b", "ts": 100.2, "seq": 3,
         "rank": 0, "wall_s": 0.01, "cache": "hit"},
    ]
    r1 = [
        {"type": "health", "source": "distributed_init", "outcome": "ok",
         "ts": 200.0, "seq": 1, "rank": 1},
        {"type": "flush", "label": "prog_a", "ts": 200.1, "seq": 2,
         "rank": 1, "wall_s": 0.01, "cache": "miss"},
        {"type": "flush", "label": "prog_b", "ts": 200.25, "seq": 3,
         "rank": 1, "wall_s": 0.3, "degraded": "chunked", "cache": "hit"},
        {"type": "degrade", "site": "flush", "action": "degrade",
         "from": "fused", "to": "chunked",
         "ts": 200.26, "seq": 4, "rank": 1},
    ]
    _write_rank_file(f"{base}.rank0", r0)
    _write_rank_file(f"{base}.rank1", r1, trailing_garbage=True)
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "trace_report.py"),
         str(base), "--merge-ranks"],
        capture_output=True, text=True,
    )
    assert r.returncode == 0, r.stdout + r.stderr
    assert "2 rank(s)" in r.stdout
    # the 100 s clock skew is measured off the bring-up anchors...
    assert "r1=+100.0000s" in r.stdout
    # ...so the two bring-up events land at the same adjusted instant
    assert r.stdout.count("+   0.000s") >= 2
    # rank 1 degraded to chunked while rank 0 stayed fused at flush #1
    assert "rank divergence at flush #1" in r.stdout
    assert "r0=prog_b/fused" in r.stdout and "r1=prog_b/chunked" in r.stdout
    assert "degrade   flush degrade fused->chunked" in r.stdout
    # the truncated final line warns to stderr without crashing the merge
    assert "unparseable" in r.stderr


def test_trace_report_merge_ranks_lockstep(tmp_path):
    base = tmp_path / "ok.jsonl"
    for rank in range(2):
        _write_rank_file(f"{base}.rank{rank}", [
            {"type": "health", "source": "distributed_init", "outcome": "ok",
             "ts": 10.0 + rank, "seq": 1, "rank": rank},
            {"type": "flush", "label": "prog_a", "ts": 10.1 + rank, "seq": 2,
             "rank": rank, "wall_s": 0.01, "cache": "miss"},
        ])
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "trace_report.py"),
         str(base), "--merge-ranks"],
        capture_output=True, text=True,
    )
    assert r.returncode == 0, r.stdout + r.stderr
    assert "rank divergence: none" in r.stdout


# ---------------------------------------------------------------------------
# events rank re-probing
# ---------------------------------------------------------------------------


def test_rank_info_not_cached_until_authoritative(monkeypatch):
    monkeypatch.setattr(events, "_rank", None)
    calls = []

    def fake_probe_pre():
        calls.append(1)
        return (0, 1, False)

    monkeypatch.setattr(events, "_probe_rank", fake_probe_pre)
    assert events._rank_info() == (0, 1)
    assert events._rank_info() == (0, 1)
    assert len(calls) == 2  # non-authoritative answers are NOT cached

    monkeypatch.setattr(events, "_probe_rank", lambda: (1, 2, True))
    assert events._rank_info() == (1, 2)
    # once authoritative, the cache holds even if the probe changes
    monkeypatch.setattr(events, "_probe_rank", fake_probe_pre)
    assert events._rank_info() == (1, 2)

    # invalidate_rank (called by distributed.initialize) forces a re-probe
    events.invalidate_rank()
    assert events._rank_info() == (0, 1)


def test_probe_rank_authoritative_with_live_backend():
    # the suite has computed by now, so a backend exists: the probe must
    # be authoritative and agree with jax
    r, n, authoritative = events._probe_rank()
    assert authoritative
    assert (r, n) == (_jax.process_index(), _jax.process_count())
