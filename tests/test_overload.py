"""The overload-control plane (serve/overload.py).

Single-process coverage of the four tentpole pieces — deadline
propagation, admission control + shedding, per-tenant circuit breakers,
hedged dispatch — plus the satellite fixes (bounded fairness queue,
ticket abandonment).  Every shed must surface as a *classified* error
(never a bare TimeoutError the retry layer would happily re-attempt),
fail fast, and leave the shed arrays able to self-heal on next touch.

The coherent (epoch-agreed, rank-identical) shedding story is SPMD-only
and lives in ``two_process_suite.py --overload-leg``.
"""

import threading
import time

import numpy as np
import pytest

import jax as _jax
import ramba_tpu as rt
from ramba_tpu import serve
from ramba_tpu.core import fuser
from ramba_tpu.core.expr import Const
from ramba_tpu.observe import events, ledger, registry
from ramba_tpu.resilience import faults, retry
from ramba_tpu.serve import overload
from ramba_tpu.serve.fairness import RoundRobin
from ramba_tpu.serve.pipeline import CompilePipeline

_MULTIPROC = _jax.process_count() > 1

spmd_skip = pytest.mark.skipif(
    _MULTIPROC,
    reason="threaded serving is single-controller; SPMD uses --overload-leg",
)


@pytest.fixture(autouse=True)
def _clean_overload(monkeypatch):
    """Fast retries, clean breakers/brownout/faults, no leaked pipeline
    worker, and no half-open streams bleeding into the next test."""
    monkeypatch.setenv("RAMBA_RETRY_BASE_S", "0.001")
    faults.configure(None)
    overload.reset()
    yield
    serve.shutdown()  # also resets overload state
    faults.reset()
    fuser.sync()
    ledger.reconfigure()


def _manual_pipeline(**kw) -> CompilePipeline:
    """A pipeline whose worker never starts — tests drive dispatch
    inline with ``_drive`` for deterministic timing."""
    pipe = CompilePipeline(**kw)
    pipe._ensure_worker = lambda: None
    return pipe


def _drive(pipe: CompilePipeline, max_group: int = 8) -> int:
    """Dispatch everything queued; returns the number of groups run."""
    n = 0
    while True:
        group = pipe.queue.pop_group(
            max_group, fingerprint_of=lambda t: t.work.fingerprint,
            timeout=0)
        if not group:
            return n
        pipe._dispatch_group(group)
        n += 1


# -- deadlines ---------------------------------------------------------------


def test_deadline_clock():
    d = overload.Deadline(50.0)
    assert not d.expired()
    assert 0.0 < d.remaining_s() <= 0.05
    late = overload.Deadline(50.0, now=time.monotonic() - 1.0)
    assert late.expired() and late.remaining_s() < 0
    assert late.elapsed_ms() >= 1000.0


def test_mint_deadline_opt_in(monkeypatch):
    assert overload.mint_deadline(None) is None
    assert overload.mint_deadline(10.0).budget_ms == 10.0
    monkeypatch.setenv("RAMBA_DEADLINE_MS", "250")
    assert overload.mint_deadline(None).budget_ms == 250.0
    monkeypatch.setenv("RAMBA_DEADLINE_MS", "0")
    assert overload.mint_deadline(None) is None


def test_clamp_watchdog():
    d = overload.Deadline(10_000.0)
    # remaining dominates a larger watchdog; watchdog dominates a larger
    # remaining; no deadline leaves the watchdog untouched (incl. None)
    assert overload.clamp_watchdog(30.0, d) < 10.0
    assert overload.clamp_watchdog(1.0, d) == 1.0
    assert overload.clamp_watchdog(None, d) <= 10.0
    assert overload.clamp_watchdog(5.0, None) == 5.0
    assert overload.clamp_watchdog(None, None) is None
    expired = overload.Deadline(10.0, now=time.monotonic() - 1.0)
    # floored so an expired budget still arms (0 would mean "unarmed")
    assert overload.clamp_watchdog(30.0, expired) == pytest.approx(0.001)


@spmd_skip
def test_expired_deadline_sheds_before_dispatch():
    """A queued flush whose budget expired is shed in O(ms) with a
    classified DeadlineExceededError — before compile/dispatch — and the
    shed array self-heals on next touch."""
    pipe = _manual_pipeline()
    with serve.Session(tenant="dl", pipeline=pipe, deadline_ms=20) as s:
        a = rt.ones((16, 16)) * 3.0
        ticket = s.flush()
        assert ticket.deadline is not None
        time.sleep(0.05)  # budget spent while queued
        t0 = time.perf_counter()
        _drive(pipe)
        shed_wall = time.perf_counter() - t0
        with pytest.raises(overload.DeadlineExceededError) as ei:
            ticket.wait(5)
        assert ei.value.shed_classification == "deadline"
        assert ei.value.stage == "dispatch"
        assert shed_wall < 0.25  # no compile happened behind the shed
        assert registry.get("serve.shed.deadline") >= 1
        sheds = events.last(5, type="shed")
        assert any(e["reason"] == "deadline" for e in sheds)
    # self-heal OUTSIDE the session: inside it every re-flush inherits
    # the stream's 20ms budget (compile alone blows that), which is the
    # deadline doing its job — the undeadlined default stream heals it
    np.testing.assert_allclose(a.asarray(), 3.0)


@spmd_skip
def test_fresh_deadline_admits():
    pipe = _manual_pipeline()
    with serve.Session(tenant="dl2", pipeline=pipe, deadline_ms=60_000) as s:
        a = rt.ones((8, 8)) + 1.0
        ticket = s.flush()
        _drive(pipe)
        assert ticket.wait(5) == []
        np.testing.assert_allclose(a.asarray(), 2.0)


def test_deadline_rung_pruning_and_exhaustion():
    """Rungs whose rolling p50 cannot fit the remaining budget are
    skipped; when nothing fits the ladder sheds with stage='ladder'."""
    ledger.reconfigure(min_samples=3)
    for _ in range(4):
        ledger.record_flush_wall({"label": "L", "wall_s": 10.0})
        ledger.record_flush_wall({"label": "L", "degraded": "split",
                              "wall_s": 0.001})
    assert ledger.rung_quantile("L", "fused", 0.5) == 10.0
    assert ledger.rung_quantile("L", "split", 0.5) == 0.001
    assert ledger.rung_quantile("L", "chunked", 0.5) is None  # no history
    d = overload.Deadline(100.0)
    rungs = [("fused", lambda: 1), ("split", lambda: 2),
             ("chunked", lambda: 3)]
    kept = overload.prune_rungs(rungs, d, "L")
    # fused (p50=10s) cannot fit 100ms; split can; chunked has no
    # history so it gets the benefit of the doubt
    assert [n for n, _ in kept] == ["split", "chunked"]
    assert registry.get("serve.deadline_rung_skips") >= 1
    # all rungs over budget -> classified shed at the ladder stage
    with pytest.raises(overload.DeadlineExceededError) as ei:
        overload.prune_rungs([("fused", lambda: 1)], d, "L")
    assert ei.value.stage == "ladder"
    # no deadline -> untouched
    assert overload.prune_rungs(rungs, None, "L") is rungs


# -- CoDel sojourn control ---------------------------------------------------


def test_codel_tolerates_spikes_drops_standing_queue():
    c = overload._CoDel()
    t = 100.0
    # below target: never drops, resets the above-clock
    assert not c.should_drop(0.01, target_s=0.05, interval_s=0.2, now=t)
    # a transient spike above target survives the interval grace
    assert not c.should_drop(0.06, target_s=0.05, interval_s=0.2, now=t)
    assert not c.should_drop(0.07, target_s=0.05, interval_s=0.2, now=t + 0.1)
    # dipping below target resets — no drop even after the interval
    assert not c.should_drop(0.01, target_s=0.05, interval_s=0.2, now=t + 0.15)
    assert not c.should_drop(0.08, target_s=0.05, interval_s=0.2, now=t + 0.2)
    # standing above target for the whole interval: drop-from-front
    assert c.should_drop(0.08, target_s=0.05, interval_s=0.2, now=t + 0.45)
    assert c.drops == 1


def test_sojourn_shed_via_dispatch_verdict(monkeypatch):
    monkeypatch.setenv("RAMBA_SERVE_SOJOURN_MS", "5")
    monkeypatch.setenv("RAMBA_SERVE_SOJOURN_INTERVAL_MS", "1")
    old = time.perf_counter() - 1.0  # 1s sojourn >> 5ms target
    # first verdict arms the CoDel above-clock, second (past the 1ms
    # interval) drops
    overload.dispatch_verdict(deadline=None, enqueued_at=old,
                              tenant="sj", priority=False, label="L")
    time.sleep(0.005)
    with pytest.raises(overload.ShedError) as ei:
        overload.dispatch_verdict(deadline=None, enqueued_at=old,
                                  tenant="sj", priority=False, label="L")
    assert ei.value.reason == "sojourn"
    assert registry.get("serve.shed.sojourn") >= 1


# -- brownout state machine --------------------------------------------------


def test_brownout_transitions_and_events():
    b = overload._Brownout()
    assert b.state == "green"
    # one hot signal -> yellow
    assert b.update(queue_ratio=0.6, memory_frac=0.0,
                    breached=False) == "yellow"
    # two hot signals (or one extreme) -> red
    assert b.update(queue_ratio=0.6, memory_frac=0.9,
                    breached=False) == "red"
    assert b.update(queue_ratio=0.96, memory_frac=0.0,
                    breached=False) == "red"
    # cool signals recover
    assert b.update(queue_ratio=0.0, memory_frac=0.0,
                    breached=False) == "green"
    assert b.transitions["green->yellow"] == 1
    assert b.transitions["yellow->red"] == 1
    evs = events.last(10, type="brownout")
    assert any(e["from"] == "yellow" and e["to"] == "red" for e in evs)


def test_brownout_gates_speculative_and_red_sheds():
    assert overload.allow_speculative()
    overload._brownout.update(queue_ratio=0.6, memory_frac=0.0,
                              breached=False)
    assert not overload.allow_speculative()
    # admit_submit recomputes from live signals: a backlog at the full
    # depth cap is the queue signal that forces red
    cap = overload.queue_depth_cap()
    with pytest.raises(overload.ShedError) as ei:
        overload.admit_submit(tenant="t", priority=False, queue_depth=cap)
    assert ei.value.reason == "brownout"
    assert overload.brownout_state() == "red"
    # priority tenants ride through red
    overload.admit_submit(tenant="t", priority=True, queue_depth=cap)


@spmd_skip
def test_warm_work_shed_under_brownout():
    pipe = _manual_pipeline()
    overload._brownout.state = "yellow"
    ran = []
    t = pipe.submit_warm(lambda: ran.append(1), label="warm-test")
    assert t.done and t.wait(1) == []
    assert ran == []  # the thunk never ran — and never queued
    assert len(pipe.queue) == 0
    assert registry.get("serve.warm_shed") >= 1


# -- circuit breakers --------------------------------------------------------


def test_breaker_full_cycle(monkeypatch):
    monkeypatch.setenv("RAMBA_BREAKER_THRESHOLD", "3")
    monkeypatch.setenv("RAMBA_BREAKER_COOLDOWN_S", "0.05")
    b = overload.CircuitBreaker("acme")
    b.admit()
    b.record(False)
    b.record(False)
    assert b.state == "closed"  # under threshold
    b.record(False)
    assert b.state == "open" and b.trips == 1
    # open fails fast — O(ms), carries retry_after
    t0 = time.perf_counter()
    with pytest.raises(overload.CircuitOpenError) as ei:
        b.admit()
    assert (time.perf_counter() - t0) < 0.005
    assert ei.value.shed_classification == "breaker"
    assert ei.value.retry_after_s is not None
    # cooldown -> half-open, exactly one probe
    time.sleep(0.06)
    b.admit()
    assert b.state == "half_open"
    with pytest.raises(overload.CircuitOpenError):
        b.admit()  # second concurrent probe refused
    # probe success closes and clears the failure window
    b.record(True)
    assert b.state == "closed"
    b.record(False)
    assert b.state == "closed"  # window was cleared on close
    evs = events.last(10, type="breaker")
    assert any(e["action"] == "open" for e in evs)
    assert any(e["action"] == "closed" for e in evs)


def test_breaker_probe_failure_reopens(monkeypatch):
    monkeypatch.setenv("RAMBA_BREAKER_THRESHOLD", "1")
    monkeypatch.setenv("RAMBA_BREAKER_COOLDOWN_S", "0.02")
    b = overload.CircuitBreaker("x")
    b.record(False)
    assert b.state == "open"
    time.sleep(0.03)
    b.admit()  # the probe
    b.record(False)
    assert b.state == "open" and b.trips == 2


@spmd_skip
def test_breaker_trips_on_flush_errors_and_fails_fast(monkeypatch):
    """Repeated flush errors open the tenant's breaker; the next submit
    fails in O(ms) with no prepare work and the pending graph intact."""
    monkeypatch.setenv("RAMBA_BREAKER_THRESHOLD", "2")
    monkeypatch.setenv("RAMBA_BREAKER_COOLDOWN_S", "30")
    pipe = _manual_pipeline()
    with serve.Session(tenant="flaky", pipeline=pipe) as s:
        faults.configure("compile:always:fatal")
        doomed = []
        for _ in range(2):
            fuser._compile_cache.clear()  # cached compiles skip the site
            doomed.append(rt.ones((8, 8)) * 2.0)
            t = s.flush()
            _drive(pipe)
            with pytest.raises(faults.InjectedFault):
                t.wait(5)
        faults.configure(None)
        assert overload.breaker_for("flaky").state == "open"
        a = rt.ones((8, 8)) * 5.0
        t0 = time.perf_counter()
        with pytest.raises(overload.CircuitOpenError):
            s.flush()
        assert (time.perf_counter() - t0) < 0.05
        # the rejected submit detached nothing: the array still flushes
        np.testing.assert_allclose(a.asarray(), 5.0)
        # sheds must not feed the breaker's failure window back
        assert len(overload.breaker_for("flaky").failures) == 2
        s.close(drain=False)


# -- bounded fairness queue --------------------------------------------------


def test_queue_depth_cap_rejects_with_classified_error():
    q = RoundRobin(depth_cap=2)
    q.push("a", 1)
    q.push("a", 2)
    before = registry.get("serve.shed.queue_full")
    with pytest.raises(overload.QueueFullError) as ei:
        q.push("a", 3)
    assert ei.value.tenant == "a" and ei.value.cap == 2
    assert registry.get("serve.shed.queue_full") == before + 1
    assert any(e["reason"] == "queue_full"
               for e in events.last(5, type="shed"))
    # other tenants are unaffected; popping frees capacity
    q.push("b", 1)
    assert q.pop_group(1, timeout=0) == [1]
    q.push("a", 3)
    assert q.depth("a") == 2


def test_queue_depth_env_default(monkeypatch):
    monkeypatch.setenv("RAMBA_SERVE_QUEUE_DEPTH", "1")
    q = RoundRobin()
    q.push("a", 1)
    with pytest.raises(overload.QueueFullError):
        q.push("a", 2)
    monkeypatch.setenv("RAMBA_SERVE_QUEUE_DEPTH", "0")  # 0 disables
    for i in range(100):
        q.push("a", i)


@spmd_skip
def test_submit_unwinds_on_queue_full(monkeypatch):
    """The depth cap is the last-resort backstop: a backlog at the cap
    already reads as red brownout, so non-priority submits shed *before*
    the push — only priority traffic (which rides through red) can reach
    QueueFullError.  A rejection after prepare must release the work's
    pins so the arrays self-heal."""
    monkeypatch.setenv("RAMBA_SERVE_QUEUE_DEPTH", "1")
    pipe = _manual_pipeline()
    with serve.Session(tenant="qf", pipeline=pipe, priority=True) as s:
        a = rt.ones((8, 8)) * 2.0
        t1 = s.flush()
        b = rt.ones((8, 8)) * 7.0
        with pytest.raises(overload.QueueFullError):
            s.flush()
        assert len(s.stream.inflight) == 1  # the rejected ticket unwound
        _drive(pipe)
        assert t1.wait(5) == []
        np.testing.assert_allclose(a.asarray(), 2.0)
        np.testing.assert_allclose(b.asarray(), 7.0)  # self-healed


# -- ticket abandonment (regression) -----------------------------------------


@spmd_skip
def test_abandoned_ticket_discarded_not_written_back():
    """wait(timeout) expiry abandons the ticket: the classified
    TicketAbandoned (still a TimeoutError for caller compat) replaces
    the bare TimeoutError, the queued dispatch is dropped instead of
    executing for nobody, and the arrays self-heal on next touch."""
    pipe = _manual_pipeline()
    with serve.Session(tenant="ab", pipeline=pipe) as s:
        a = rt.ones((8, 8)) * 4.0
        ticket = s.flush()
        with pytest.raises(TimeoutError) as ei:  # caller-compatible type
            ticket.wait(0.01)  # worker disabled: guaranteed to expire
        assert isinstance(ei.value, overload.TicketAbandoned)
        assert ticket.abandoned and not ticket.done
        before = registry.get("serve.abandoned_drop")
        _drive(pipe)
        assert registry.get("serve.abandoned_drop") == before + 1
        with pytest.raises(overload.TicketAbandoned):
            ticket.wait(5)
        assert any(e["reason"] == "abandoned"
                   for e in events.last(5, type="shed"))
        # nothing was executed for the abandoned ticket...
        assert not isinstance(a._expr, Const)
        # ...and the array still self-heals to the right bytes
        np.testing.assert_allclose(a.asarray(), 4.0)
        s.close(drain=False)


@spmd_skip
def test_late_completion_skips_write_back():
    """A ticket abandoned mid-dispatch must not write results back into
    the stream the caller walked away from."""
    pipe = _manual_pipeline()
    with serve.Session(tenant="late", pipeline=pipe) as s:
        a = rt.ones((8, 8)) * 9.0
        ticket = s.flush()
        work = ticket.work
        # simulate "abandoned after dispatch started": the pipeline's
        # pre-dispatch drop check has passed, the probe flips later
        work.is_abandoned = lambda: True
        result = fuser._flush_dispatch(work)
        assert registry.get("serve.abandoned_late") >= 1
        assert not isinstance(a._expr, Const)  # no write-back
        # resolve before touching: materialization drains the stream,
        # which would otherwise wait forever on the undone ticket
        ticket._resolve(result)
        np.testing.assert_allclose(a.asarray(), 9.0)  # self-heals
        s.close(drain=False)


# -- shed classification -----------------------------------------------------


def test_sheds_classify_fatal_never_retryable():
    """Every overload error must classify 'fatal' in retry.classify —
    re-attempting a shed defeats the shed.  TicketAbandoned is the sharp
    case: it IS a TimeoutError, which classifies retryable by default."""
    assert retry.classify(TimeoutError("bare")) == "retryable"  # baseline
    for exc in (
        overload.DeadlineExceededError("d"),
        overload.QueueFullError("t", 5, 5),
        overload.ShedError("brownout"),
        overload.CircuitOpenError("t", "open"),
        overload.TicketAbandoned("gone"),
        overload.OverloadError("generic"),
    ):
        assert retry.classify(exc) == "fatal", type(exc).__name__


# -- hedged dispatch ---------------------------------------------------------


def test_hedge_threshold_gates(monkeypatch):
    class _P:
        instrs = [("mul", None, (0, 1))]
        n_leaves = 2
        out_slots = (2,)

    class _Host:
        instrs = [("apply", "<function f at 0x7f>", (0,))]
        n_leaves = 1
        out_slots = (1,)

    # factor unset -> off even for pure programs
    monkeypatch.delenv("RAMBA_HEDGE_FACTOR", raising=False)
    assert overload.hedge_threshold("L", _P(), ()) is None
    monkeypatch.setenv("RAMBA_HEDGE_FACTOR", "2.0")
    # pure + history -> threshold = factor * p95
    ledger.reconfigure(min_samples=3)
    for _ in range(4):
        ledger.record_flush_wall({"label": "HL", "wall_s": 0.1})
    assert overload.hedge_threshold("HL", _P(), ()) == pytest.approx(0.2)
    # no history -> off
    assert overload.hedge_threshold("nohist", _P(), ()) is None
    # host-effecting program -> never hedged
    assert overload.hedge_threshold("HL", _Host(), ()) is None
    # donation -> never hedged (the loser would read consumed buffers)
    assert overload.hedge_threshold("HL", _P(), (0,)) is None


def test_run_hedged_primary_wins_no_hedge():
    span = {"calls": []}
    out = overload.run_hedged(lambda sp: ("ok", "fused"), 5.0,
                              span=span, label="L")
    assert out == ("ok", "fused")
    assert registry.get("serve.hedge.fired") == 0


def test_run_hedged_hedge_wins_and_loser_cancelled():
    from ramba_tpu.resilience import elastic

    release = threading.Event()
    primary_cancelled = threading.Event()
    calls = []

    def execute(sp):
        calls.append(1)
        if len(calls) == 1:  # primary: stall until released, then check
            release.wait(10)
            if elastic.cancelled():
                primary_cancelled.set()
                raise RuntimeError("cancelled loser")
            return ("primary", "fused")
        return ("hedge", "fused")

    span = {"calls": []}
    out = overload.run_hedged(execute, 0.02, span=span, label="L")
    assert out == ("hedge", "fused")
    assert registry.get("serve.hedge.fired") == 1
    assert registry.get("serve.hedge.won_hedge") == 1
    release.set()
    assert primary_cancelled.wait(5)  # loser saw its cancel flag
    evs = events.last(10, type="hedge")
    assert any(e["action"] == "fired" for e in evs)
    assert any(e.get("winner") == "hedge" for e in evs)


def test_run_hedged_propagates_winner_error():
    def execute(sp):
        raise ValueError("boom")

    with pytest.raises(ValueError, match="boom"):
        overload.run_hedged(execute, 5.0, span={"calls": []}, label="L")


@spmd_skip
def test_hedge_byte_identity_on_vs_off(monkeypatch):
    """End-to-end: a seeded serve:hedge delay makes the primary slow,
    the hedge fires and wins, and the winner's bytes are identical to
    the unhedged run — that is what the purity certificate buys."""
    pipe = _manual_pipeline()

    def run_once(session_tenant):
        with serve.Session(tenant=session_tenant, pipeline=pipe) as s:
            a = (rt.ones((16, 16)) * 3.0) + 1.0
            t = s.flush()
            _drive(pipe)
            t.wait(10)
            return np.asarray(a.asarray()).copy()

    # unhedged baseline + rolling history for the program's label
    ledger.reconfigure(min_samples=3)
    baseline = run_once("h0")
    for i in range(4):
        np.testing.assert_array_equal(run_once(f"warm{i}"), baseline)
    # arm hedging: tiny threshold so the seeded 150ms primary delay
    # always loses the race to the un-delayed hedge attempt
    monkeypatch.setenv("RAMBA_HEDGE_FACTOR", "0.5")
    faults.configure("serve:hedge:delay:ms=150")
    fired_before = registry.get("serve.hedge.fired")
    hedged = run_once("hedged")
    faults.configure(None)
    assert registry.get("serve.hedge.fired") == fired_before + 1
    assert registry.get("serve.hedge.won_hedge") >= 1
    np.testing.assert_array_equal(hedged, baseline)


# -- fault sites -------------------------------------------------------------


def test_serve_admit_fault_becomes_shed(monkeypatch):
    """An injected serve:admit fault is converted into a shed verdict
    (reason=fault) — the hook the rank-skewed chaos leg drives."""
    faults.configure("serve:admit:2")
    with pytest.raises(overload.ShedError) as ei:
        overload.dispatch_verdict(deadline=None, enqueued_at=None,
                                  tenant="f", priority=False, label="L")
    assert ei.value.reason == "fault"
    with pytest.raises(overload.ShedError):
        overload.dispatch_verdict(deadline=None, enqueued_at=None,
                                  tenant="f", priority=False, label="L")
    # spec exhausted (mode "2" = first two checks): admitted now
    overload.dispatch_verdict(deadline=None, enqueued_at=None,
                              tenant="f", priority=False, label="L")
    assert registry.get("serve.shed.fault") >= 2


def test_verdict_inactive_is_free():
    """No deadline, no sojourn target, no serve:admit fault: the verdict
    decides nothing and must not emit, count, or agree."""
    before = registry.get("serve.shed")
    overload.dispatch_verdict(deadline=None, enqueued_at=time.perf_counter(),
                              tenant="idle", priority=False, label="L")
    assert registry.get("serve.shed") == before


# -- observability -----------------------------------------------------------


def test_overload_report_and_diagnostics():
    overload._brownout.update(queue_ratio=0.6, memory_frac=0.0,
                              breached=False)
    overload.breaker_for("rep").record(False)
    rep = overload.report()
    assert rep["brownout"]["state"] == "yellow"
    assert rep["breakers"]["rep"]["recent_failures"] == 1
    assert "queue_depth_cap" in rep
    from ramba_tpu import diagnostics
    import io

    buf = io.StringIO()
    diagnostics.report(file=buf)
    # the section renders once there is overload activity
    assert "brownout=yellow" in buf.getvalue()


def test_breaker_trip_is_flight_incident():
    from ramba_tpu.observe import telemetry

    assert telemetry.is_incident({"type": "breaker", "action": "open"})
    assert not telemetry.is_incident({"type": "breaker",
                                      "action": "closed"})
    assert telemetry.is_incident({"type": "slo_breach"})
