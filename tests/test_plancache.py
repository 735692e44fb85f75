"""Proof-carrying plan certificates (``analyze/plancert.py`` +
``core/plancache.py``, RAMBA_PLANCERT).

The contract under test, in order of importance:

* **Soundness of redemption** — a hit must be provably equivalent to a
  fresh analysis: byte-identical results, the certified verdicts
  (findings, effect class, compile class) re-stamped on the span, and
  the verifier/certifier pipeline actually SKIPPED (counted via a
  wrapped ``_verify_if_enabled``).
* **Sound invalidation** — every ambient input a signature field reads
  (rule set, governor budget band, mesh epoch, …) must flip the
  certificate stale when it changes, with the changed field named in
  ``stale_causes``; the re-analysis then re-certifies.
* **Strict-mode rejection** — a ``plan:stale``-forged staleness verdict
  raises ``ProgramVerificationError`` under strict and silently
  re-analyzes (byte-identical) under warn.
* **Batched coherence** — hit agreement runs per batch, and a divergent
  round clears the local store.

The SPMD analog (lockstep hit/miss decisions on both ranks) is
``scripts/two_process_suite.py --plancache-leg``; the randomized
byte-identity oracle is the plan-cache leg in test_fuzz.py.
"""

import io
import json

import numpy as np
import pytest

import ramba_tpu as rt
from ramba_tpu.analyze import lint as alint
from ramba_tpu.analyze import plancert
from ramba_tpu.analyze.findings import ProgramVerificationError
from ramba_tpu.core import fuser, plancache
from ramba_tpu.observe import events
from ramba_tpu.resilience import faults


@pytest.fixture(autouse=True)
def _isolate(monkeypatch):
    """Armed plan cache under strict verify, empty store, no faults; the
    ambient env the signatures read is scoped per-test."""
    from ramba_tpu.core import memo

    fuser.flush()
    faults.configure(None)
    monkeypatch.setenv("RAMBA_PLANCERT", "1")
    monkeypatch.setenv("RAMBA_VERIFY", "strict")
    for k in ("RAMBA_VERIFY_RULES", "RAMBA_VERIFY_SKIP",
              "RAMBA_HBM_BUDGET", "RAMBA_MEMO"):
        monkeypatch.delenv(k, raising=False)
    plancache.reset()
    plancert.reset_caches()
    memo.reset()
    yield
    faults.reset()
    plancache.reset()
    plancert.reset_caches()
    memo.reset()


def _workload():
    a = rt.fromarray(np.arange(256.0).reshape(16, 16))
    b = rt.fromarray(np.ones((16, 16)))
    return np.asarray((a + b) * 2.0 - 0.5)


def _counting_verifier(monkeypatch):
    """Wrap the fuser's verifier entry point so tests can prove a hit
    skipped the analysis pipeline rather than merely matching output."""
    calls = []
    inner = fuser._verify_if_enabled

    def wrapper(*args, **kwargs):
        calls.append(1)
        return inner(*args, **kwargs)

    monkeypatch.setattr(fuser, "_verify_if_enabled", wrapper)
    return calls


def test_off_by_default(monkeypatch):
    monkeypatch.setenv("RAMBA_PLANCERT", "0")
    _workload()
    _workload()
    snap = plancache.snapshot()
    assert not snap["enabled"]
    assert snap["entries"] == 0 and snap.get("lookups") is None


def test_repeat_hits_and_skips_analysis(monkeypatch):
    calls = _counting_verifier(monkeypatch)
    first = _workload()
    n_miss = len(calls)
    assert n_miss >= 1
    assert plancache.snapshot().get("stores", 0) >= 1
    second = _workload()
    snap = plancache.snapshot()
    assert snap.get("hits", 0) >= 1 and not snap.get("stale")
    # the hit redeemed the certificate: no fresh verifier run
    assert len(calls) == n_miss
    assert first.tobytes() == second.tobytes()
    span = events.last(1, type="flush")[-1]
    assert span.get("plan_cache") == "hit"
    # the stage ledger splits trace from prepare so the waterfall shows
    # what the fast path saves; both must be stamped on a hit
    stages = span.get("stages") or {}
    assert "trace" in stages and "prepare" in stages


def test_ruleset_change_invalidates(monkeypatch):
    _workload()
    _workload()
    assert plancache.snapshot().get("hits", 0) >= 1
    monkeypatch.setenv("RAMBA_VERIFY_RULES", "shape-dtype")
    _workload()
    snap = plancache.snapshot()
    assert snap.get("stale", 0) >= 1
    assert snap["stale_causes"].get("ruleset", 0) >= 1
    # the re-analysis re-certified under the new rule set: repeats hit
    h0 = snap.get("hits", 0)
    _workload()
    assert plancache.snapshot().get("hits", 0) == h0 + 1


def test_budget_band_change_invalidates(monkeypatch):
    _workload()
    _workload()
    assert plancache.snapshot().get("hits", 0) >= 1
    monkeypatch.setenv("RAMBA_HBM_BUDGET", str(1 << 30))
    _workload()
    snap = plancache.snapshot()
    assert snap.get("stale", 0) >= 1
    assert snap["stale_causes"].get("budget_band", 0) >= 1


def test_mesh_epoch_change_invalidates():
    from ramba_tpu.parallel import mesh as pmesh

    _workload()
    _workload()
    assert plancache.snapshot().get("hits", 0) >= 1
    pmesh.mesh_epoch += 1
    try:
        _workload()
        snap = plancache.snapshot()
        assert snap.get("stale", 0) >= 1
        assert snap["stale_causes"].get("mesh_epoch", 0) >= 1
    finally:
        pmesh.mesh_epoch -= 1


def test_forged_stale_strict_raises():
    first = _workload()
    _workload()
    with faults.active("plan:stale:always"):
        with pytest.raises(ProgramVerificationError, match="plan-stale"):
            _workload()
    fuser.flush()
    # the forged verdict never corrupted the cache: repeats still hit
    h0 = plancache.snapshot().get("hits", 0)
    again = _workload()
    assert plancache.snapshot().get("hits", 0) == h0 + 1
    assert again.tobytes() == first.tobytes()


def test_forged_stale_warn_reanalyzes(monkeypatch):
    monkeypatch.setenv("RAMBA_VERIFY", "warn")
    calls = _counting_verifier(monkeypatch)
    first = _workload()
    n_miss = len(calls)
    with faults.active("plan:stale:always"):
        second = _workload()
    # warn mode silently re-ran the full analysis instead of trusting
    # (or raising on) the forged verdict — byte-identical either way
    assert len(calls) > n_miss
    assert second.tobytes() == first.tobytes()
    snap = plancache.snapshot()
    assert snap.get("forged_stale", 0) >= 1
    assert not snap.get("stale")    # forged, not genuine


def test_forging_fault_sites_stand_down():
    # while an analysis-corrupting fault is armed the cache must neither
    # serve nor store — a forged verdict certified once would outlive
    # the fault plan
    _workload()
    s0 = plancache.snapshot().get("stores", 0)
    with faults.active("memo:insert:always"):
        _workload()
    snap = plancache.snapshot()
    assert snap.get("stores", 0) == s0
    assert snap.get("hits") is None


def test_certificate_roundtrips_through_payload():
    _workload()
    entry = next(iter(plancache._store.values()))
    cert = entry.cert
    back = plancert.from_payload(
        json.loads(json.dumps(plancert.to_payload(cert))))
    assert back is not None
    assert back.signature == cert.signature
    assert back.sig_fields == cert.sig_fields
    assert back.findings_digest == cert.findings_digest
    assert back.aval_sig == cert.aval_sig


def test_batched_agree_divergence_clears(monkeypatch):
    class _Stub:
        def engaged(self):
            return True

        def agree(self, name, n, reduce="min"):
            return n - 1    # a peer saw fewer hits: divergence

    monkeypatch.setattr(plancache, "_coherence", _Stub())
    monkeypatch.setenv("RAMBA_PLANCERT_AGREE", "2")
    _workload()
    _workload()     # hit 1: below batch, no agree round yet
    snap = plancache.snapshot()
    assert snap.get("agree_rounds") is None
    assert snap["pending_agree_hits"] == 1
    _workload()     # hit 2 completes the batch: divergent round
    snap = plancache.snapshot()
    assert snap.get("agree_rounds", 0) == 1
    assert snap.get("divergences", 0) == 1
    assert snap["entries"] == 0 and snap["pending_agree_hits"] == 0
    ev = events.last(1, type="plan_divergence")
    assert ev and ev[-1]["agreed"] == ev[-1]["proposed"] - 1


def test_eviction_cap(monkeypatch):
    monkeypatch.setenv("RAMBA_PLANCERT_MAX", "1")
    a = rt.fromarray(np.arange(16.0))
    np.asarray(a + 1.0)
    np.asarray(a * 3.0)
    snap = plancache.snapshot()
    assert snap["entries"] == 1
    assert snap.get("evictions", 0) >= 1
    del a


def test_plan_audit_over_live_trace(tmp_path, capsys):
    path = str(tmp_path / "plan.jsonl")
    events.configure(path)
    try:
        for _ in range(3):
            _workload()
    finally:
        events.configure(None)
    evs = alint.load_events(alint.discover(path)[0])
    assert any(e.get("type") == "plan_cert" for e in evs)
    assert alint.main(["--plan-audit", path]) == 0
    out = capsys.readouterr().out
    assert "plan audit" in out
    assert "proof re-derives" in out
    assert "PROOF BROKEN" not in out
    rec = alint.plan_audit(evs, file=io.StringIO())
    assert rec["certificates"] >= 1
    assert rec["would_hits"] >= rec["live_hits"] >= 1
    assert rec["proof_broken"] == {}


def test_plan_audit_flags_broken_proof(tmp_path, capsys):
    path = str(tmp_path / "plan.jsonl")
    events.configure(path)
    try:
        _workload()
        _workload()
    finally:
        events.configure(None)
    evs = alint.load_events(alint.discover(path)[0])
    for e in evs:
        if e.get("type") == "plan_cert":
            # corrupt the stored effect verdict: the offline replay must
            # catch a certificate whose proof no longer re-derives
            e["effect"][2] = "host-effecting"
    rec = alint.plan_audit(evs, file=io.StringIO())
    assert rec["proof_broken"]
