"""Serving subsystem: session-scoped flush streams + async compile pipeline.

Covers ``ramba_tpu.serve`` and the fuser's stream refactor:

* ``FlushStream`` isolation — one stream's pending work, threshold
  counter, and quarantine scope never leak into another stream (or the
  default stream),
* the per-stream ``max_pending_ops`` auto-flush (and the ``on_threshold``
  hook serving sessions use to route threshold flushes async),
* ``RoundRobin`` fairness: FIFO within a tenant, rotation between
  tenants, head-only fingerprint coalescing,
* the async pipeline: ticket resolution, error propagation (an enqueued
  flush fails exactly like a synchronous one, just later), coalesced
  batch dispatch,
* per-tenant quota admission routing an over-quota flush to the chunked
  rung without touching other tenants,
* thread-safety regression hammers for the counters registry, event
  ring, and kernel cost ledger (8 writer threads, exact final counts),
* the acceptance soak: >= 8 concurrent sessions with mixed shapes under
  seeded fault injection produce byte-identical results vs single-stream
  execution, with zero cross-tenant quarantine bleed.

Threaded tests are single-controller only: concurrent flush ordering is
nondeterministic across threads, which SPMD collectives cannot tolerate
(the deterministic SPMD story is ``two_process_suite.py --serving-leg``).
"""

import io
import threading

import numpy as np
import pytest

import jax as _jax
import ramba_tpu as rt
from ramba_tpu import diagnostics, serve
from ramba_tpu.core import fuser
from ramba_tpu.core.expr import Const
from ramba_tpu.observe import events, ledger, registry
from ramba_tpu.resilience import faults
from ramba_tpu.serve.fairness import RoundRobin
from ramba_tpu.serve.pipeline import CompilePipeline

_MULTIPROC = _jax.process_count() > 1

spmd_skip = pytest.mark.skipif(
    _MULTIPROC,
    reason="threaded serving is single-controller; SPMD uses --serving-leg",
)


@pytest.fixture(autouse=True)
def _clean_serving(monkeypatch):
    """Fast retries, no leaked faults, no leaked pipeline worker, and no
    half-open streams bleeding pending work into the next test."""
    monkeypatch.setenv("RAMBA_RETRY_BASE_S", "0.001")
    faults.configure(None)
    yield
    serve.shutdown()
    faults.reset()
    fuser.sync()


# -- RoundRobin --------------------------------------------------------------


def test_roundrobin_fifo_within_tenant():
    q = RoundRobin()
    for i in range(5):
        q.push("a", ("a", i))
    got = [q.pop_group(1, timeout=0)[0] for _ in range(5)]
    assert got == [("a", i) for i in range(5)]
    assert q.pop_group(1, timeout=0) == []


def test_roundrobin_rotates_between_tenants():
    q = RoundRobin()
    for i in range(3):
        q.push("a", ("a", i))
    q.push("b", ("b", 0))
    q.push("c", ("c", 0))
    order = [q.pop_group(1, timeout=0)[0] for _ in range(5)]
    # b and c each wait at most one rotation despite a's backlog
    assert order == [("a", 0), ("b", 0), ("c", 0), ("a", 1), ("a", 2)]


def test_roundrobin_coalesces_matching_heads_only():
    q = RoundRobin()
    fp = {("a", 0): "X", ("a", 1): "X", ("a", 2): "Y", ("a", 3): "X",
          ("b", 0): "X"}
    for item in [("a", 0), ("a", 1), ("a", 2), ("a", 3)]:
        q.push("a", item)
    q.push("b", ("b", 0))
    g1 = q.pop_group(8, fingerprint_of=fp.get, timeout=0)
    # a's two consecutive X heads coalesce, plus b's matching head; a's
    # trailing X is BEHIND Y so taking it would break a's FIFO order
    assert g1 == [("a", 0), ("a", 1), ("b", 0)]
    g2 = q.pop_group(8, fingerprint_of=fp.get, timeout=0)
    assert g2 == [("a", 2)]
    assert q.pop_group(8, fingerprint_of=fp.get, timeout=0) == [("a", 3)]


def test_roundrobin_coalesce_cap_and_close():
    q = RoundRobin()
    for i in range(6):
        q.push("a", ("a", i))
    g = q.pop_group(4, fingerprint_of=lambda _: "same", timeout=0)
    assert g == [("a", i) for i in range(4)]
    q.close()
    # close drains remaining work, then returns [] forever
    assert q.pop_group(4, fingerprint_of=lambda _: "same") == \
        [("a", 4), ("a", 5)]
    assert q.pop_group(4) == []


def test_roundrobin_close_wakes_blocked_pop():
    q = RoundRobin()
    out = []

    def waiter():
        out.append(q.pop_group(1, timeout=30))

    t = threading.Thread(target=waiter)
    t.start()
    q.close()
    t.join(timeout=10)
    assert not t.is_alive() and out == [[]]


# -- FlushStream isolation ---------------------------------------------------


def test_stream_isolation_pending_and_flush():
    fuser.flush()
    s1 = fuser.FlushStream(name="iso1")
    s2 = fuser.FlushStream(name="iso2")
    with fuser.stream_scope(s1):
        a = rt.arange(32) * 2.0
    with fuser.stream_scope(s2):
        b = rt.arange(32) + 7.0

    def _has(stream, arr):
        return any(x is arr for x in stream.pending_roots())

    assert _has(s1, a) and not _has(s2, a)
    assert _has(s2, b) and not _has(s1, b)
    assert not _has(fuser.default_stream(), a)
    s1.flush()
    # s1's flush materialized only s1's work
    assert isinstance(a._expr, Const)
    assert not isinstance(b._expr, Const)
    assert any(x is b for x in s2.pending_roots())
    np.testing.assert_array_equal(np.asarray(a), np.arange(32) * 2.0)
    np.testing.assert_array_equal(np.asarray(b), np.arange(32) + 7.0)


def test_materialization_chases_owning_stream():
    # Touching an array outside its stream's scope must still flush the
    # stream that owns the work (cross-thread handoff of results).
    s = fuser.FlushStream(name="owner")
    with fuser.stream_scope(s):
        a = rt.arange(16) * 3.0
    # current stream is back to default here
    np.testing.assert_array_equal(np.asarray(a), np.arange(16) * 3.0)
    assert s.stats["flushes"] == 1


def test_per_stream_threshold_autoflush():
    fuser.flush()
    s = fuser.FlushStream(name="cap", max_pending_ops=4)
    before_default = fuser.default_stream().nodes_since_flush
    with fuser.stream_scope(s):
        arrs = [rt.arange(8) + float(i) for i in range(6)]
    assert s.stats["flushes"] >= 1  # the cap fired mid-build
    # a session's burst never advances the default stream's counter
    assert fuser.default_stream().nodes_since_flush == before_default
    for i, a in enumerate(arrs):
        np.testing.assert_array_equal(np.asarray(a), np.arange(8) + i)


def test_threshold_hook_routes_instead_of_flushing():
    fired = []
    s = fuser.FlushStream(name="hook", max_pending_ops=3)
    s.on_threshold = fired.append
    with fuser.stream_scope(s):
        a = rt.arange(8) * 1.0
        b = rt.arange(8) * 2.0
    assert fired and all(x is s for x in fired)
    assert s.stats["flushes"] == 0  # the hook replaced the sync flush
    np.testing.assert_array_equal(np.asarray(b), np.arange(8) * 2.0)
    np.testing.assert_array_equal(np.asarray(a), np.arange(8) * 1.0)


def test_default_stream_spans_carry_no_serving_fields():
    fuser.flush()
    a = rt.arange(64) * 1.5
    np.asarray(a)
    span = diagnostics.last_flushes(1)[0]
    assert "stream" not in span and "tenant" not in span


# -- async pipeline ----------------------------------------------------------


@spmd_skip
def test_session_async_flush_ticket():
    with serve.Session(tenant="async1") as s:
        a = rt.arange(128) * 2.0 + 1.0
        t = s.flush()
        assert t.wait(timeout=60) == []
        assert t.done
    np.testing.assert_array_equal(np.asarray(a), np.arange(128) * 2.0 + 1.0)
    assert s.stats["enqueued"] >= 1 and s.stats["flushes"] >= 1


@spmd_skip
def test_an_idle_dispatch_worker_holds_nothing_of_its_last_flush():
    """Once a ticket is resolved and its owner has dropped the arrays, the
    ledger is back where it was: the worker's frame does not keep the
    last group (tickets, their work, its leaf buffers and results) while
    it waits for the next one.  It did, for the half second of its poll,
    and bytes of one test died in the middle of another that was reading
    the ledger (tests/test_memory.py, PR 36)."""
    import gc
    import time

    from ramba_tpu.resilience import memory

    rt.sync()
    gc.collect()
    base = memory.ledger.live_bytes
    with serve.Session(tenant="idle") as s:
        a = rt.fromarray(np.ones(64, np.float32))
        b = a + 1.0
        t = s.flush()
        assert t.wait(timeout=60) == []
        assert memory.ledger.live_bytes == base + 512
        del a, b, t
    del s
    # the ticket and its work are a cycle; the worker needs a moment to
    # return from the dispatch, far less than its poll of 0.5 s
    until = time.monotonic() + 0.3
    gc.collect()
    while memory.ledger.live_bytes != base and time.monotonic() < until:
        time.sleep(0.01)
        gc.collect()
    assert memory.ledger.live_bytes == base


@spmd_skip
def test_empty_flush_returns_finished_ticket():
    with serve.Session(tenant="empty") as s:
        t = s.flush()
        assert t.done and t.wait() == []


@spmd_skip
def test_ticket_propagates_flush_error_and_quarantines():
    fuser._compile_cache.clear()
    with serve.Session(tenant="doomed") as s:
        a = rt.arange(48) * 5.0
        with faults.inject("compile", "once", kind="fatal"):
            t = s.flush()
            with pytest.raises(faults.InjectedFault):
                t.wait(timeout=60)
        assert s.stats["quarantined"] >= 1
        # the quarantined array self-heals when touched (fault was one-shot)
        np.testing.assert_array_equal(np.asarray(a), np.arange(48) * 5.0)


@spmd_skip
def test_quarantine_never_bleeds_across_tenants():
    fuser.flush()
    fuser._compile_cache.clear()
    pipe = CompilePipeline()
    bad = serve.Session(tenant="bleed-bad", pipeline=pipe)
    good = serve.Session(tenant="bleed-good", pipeline=pipe)
    with good:
        h = rt.arange(64) * 0.5
        with bad:
            b = rt.arange(64) * 9.0
            with faults.inject("compile", "once", kind="fatal"):
                t = bad.flush()
                with pytest.raises(faults.InjectedFault):
                    t.wait(timeout=60)
            assert bad.stream.stats["quarantined"] >= 1
        # bad quarantined its own roots; good's pending work is intact
        assert good.stream.stats["quarantined"] == 0
        assert any(x is h for x in good.stream.pending_roots())
        np.testing.assert_array_equal(np.asarray(h), np.arange(64) * 0.5)
    assert good.stream.stats["quarantined"] == 0
    # the quarantined array self-heals when touched (fault was one-shot)
    np.testing.assert_array_equal(np.asarray(b), np.arange(64) * 9.0)
    pipe.stop()


@spmd_skip
def test_coalescing_dispatches_matching_fingerprints_together():
    fuser.flush()
    pipe = CompilePipeline(coalesce=8)
    pipe._ensure_worker = lambda: None  # drive the dispatch loop by hand
    before = registry.get("serve.coalesced")
    with serve.Session(tenant="co", pipeline=pipe) as s:
        arrs, tickets = [], []
        for i in range(3):
            arrs.append(rt.arange(64) * 2.0)  # identical structure each time
            tickets.append(s.flush())
        group = pipe.queue.pop_group(
            8, fingerprint_of=lambda t: t.work.fingerprint, timeout=0)
        assert len(group) == 3
        pipe._dispatch_group(group)
        for t in tickets:
            assert t.wait(timeout=60) == [] and t.coalesced == 3
        for a in arrs:
            np.testing.assert_array_equal(np.asarray(a), np.arange(64) * 2.0)
    assert registry.get("serve.coalesced") - before == 3
    ev = events.last(5, type="serve_coalesce")
    assert ev and ev[-1]["n"] == 3 and ev[-1]["tenants"] == ["co"]
    pipe.stop()


@spmd_skip
def test_abandoned_session_work_self_heals():
    s = serve.Session(tenant="abandon")
    tok = fuser.activate_stream(s.stream)
    try:
        a = rt.arange(32) + 4.0
    finally:
        fuser.deactivate_stream(tok)
    s.close(drain=False)  # nothing dispatched; the array keeps its graph
    np.testing.assert_array_equal(np.asarray(a), np.arange(32) + 4.0)


# -- tenant quotas & attribution ---------------------------------------------


@spmd_skip
def test_tenant_quota_routes_over_quota_flush_chunked():
    fuser.flush()
    before = registry.get("serve.quota_rejects")
    with serve.Session(tenant="quota-t", quota="16k") as s:
        a = rt.arange(16384) * 2.0 + 1.0  # ~64KB f32 / 128KB f64, >> 16KB
        s.flush(wait=True)
    np.testing.assert_allclose(np.asarray(a), np.arange(16384) * 2.0 + 1.0)
    assert registry.get("serve.quota_rejects") - before >= 1
    spans = [f for f in diagnostics.last_flushes(10)
             if f.get("tenant") == "quota-t"]
    assert spans and spans[-1].get("tenant_admission") == "chunked"
    assert spans[-1].get("degraded") == "chunked"
    rep = serve.tenant_report()
    assert rep["quota-t"]["quota_rejects"] >= 1


@spmd_skip
def test_tenant_attribution_in_reports():
    fuser.flush()
    with serve.Session(tenant="acct") as s:
        a = rt.arange(96) * 3.0
        s.flush(wait=True)
    np.asarray(a)
    rep = serve.tenant_report()
    assert rep["acct"]["flushes"] >= 1 and rep["acct"]["nodes"] >= 1
    assert rep["acct"]["executes"] >= 1
    # the kernel cost ledger carries the per-tenant execution split
    snap = ledger.snapshot()
    assert any("acct" in (k.get("tenants") or {})
               for k in snap["kernels"].values())
    # diagnostics surfaces the rollup in both machine and human form
    assert diagnostics.snapshot()["serving"]["acct"]["flushes"] >= 1
    buf = io.StringIO()
    diagnostics.report(file=buf)
    assert "serving (per tenant)" in buf.getvalue()
    assert "acct" in buf.getvalue()


# -- thread-safety hammers ---------------------------------------------------


def _hammer(n_threads, fn):
    errs = []

    def run():
        try:
            fn()
        except BaseException as e:  # noqa: BLE001
            errs.append(e)

    ts = [threading.Thread(target=run) for _ in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=120)
    assert not errs, errs


def test_counter_registry_hammer():
    # Regression for the registry lock: unlocked read-modify-write
    # increments lose updates under contention.
    key = "test.serving.hammer"
    registry.counters.pop(key, None)
    N = 20000
    _hammer(8, lambda: [registry.inc(key) for _ in range(N)])
    assert registry.get(key) == 8 * N
    registry.counters.pop(key, None)


def test_event_ring_hammer():
    # Concurrent emit must neither raise nor duplicate sequence numbers.
    N = 2000
    _hammer(8, lambda: [events.emit({"type": "test_hammer"})
                        for _ in range(N)])
    seqs = [e["seq"] for e in events.ring if e.get("type") == "test_hammer"]
    assert len(seqs) == len(set(seqs))
    events.ring.clear()


def test_kernel_ledger_hammer():
    # Concurrent record_execute on ONE fingerprint: the rolling window
    # and per-tenant counts must add up exactly.
    fp = "hammerfp"
    N = 2000
    _hammer(8, lambda: [
        ledger.record_execute(fp, "hammer", 1, "fused", 0.001, False,
                              tenant="ht")
        for _ in range(N)
    ])
    snap = ledger.snapshot()["kernels"].get(fp)
    assert snap is not None
    assert snap["exec"]["count"] == 8 * N
    assert snap["tenants"]["ht"] == 8 * N
    ledger.reset()


# -- the acceptance soak -----------------------------------------------------


_SOAK_SHAPES = [(257,), (64, 3), (31,), (8, 8, 2), (500,), (129,), (16, 17),
                (77,)]


def _soak_build(i):
    """Session ``i``'s workload: a few dependent elementwise programs over
    a shape from the mixed pool.  Elementwise-only so results are
    bitwise-deterministic regardless of flush/fusion boundaries."""
    shape = _SOAK_SHAPES[i % len(_SOAK_SHAPES)]
    n = int(np.prod(shape))
    a = rt.reshape(rt.arange(n), shape) * (i + 1.0)
    b = rt.sqrt(a + 1.0) + i
    c = b * 2.0 - rt.reshape(rt.arange(n), shape) * 0.25
    d = rt.abs(c) + b
    return a, d


@spmd_skip
def test_threaded_soak_eight_sessions_byte_identical():
    fuser.sync()
    n_sessions = 8
    # single-stream baseline first: the exact bytes each session must get
    expected = {}
    for i in range(n_sessions):
        a, d = _soak_build(i)
        expected[i] = (np.asarray(a).tobytes(), np.asarray(d).tobytes(),
                       np.asarray(a).shape)
    fuser.sync()

    results = {}
    barrier = threading.Barrier(n_sessions)

    def session_worker(i):
        with serve.Session(tenant=f"soak{i % 4}") as s:
            barrier.wait(timeout=60)  # maximize interleaving
            a, d = _soak_build(i)
            s.flush()  # async mid-build flush races the builds below
            e = d * 1.0 + 0.0  # more work enqueued behind the async flush
            s.flush(wait=True)
            results[i] = (np.asarray(a).tobytes(), np.asarray(d).tobytes(),
                          np.asarray(a).shape, np.asarray(e).tobytes(),
                          s.stream)

    # seeded deterministic faults: retry must absorb them invisibly
    faults.configure("execute:2,compile:2", seed=7)
    try:
        threads = [threading.Thread(target=session_worker, args=(i,))
                   for i in range(n_sessions)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        assert not any(t.is_alive() for t in threads)
    finally:
        faults.configure(None)

    assert len(results) == n_sessions
    for i in range(n_sessions):
        a_b, d_b, shp, e_b, stream = results[i]
        assert shp == expected[i][2]
        assert a_b == expected[i][0], f"session {i}: a diverged"
        assert d_b == expected[i][1], f"session {i}: d diverged"
        assert e_b == expected[i][1], f"session {i}: e diverged"
        # no cross-tenant interference: nothing quarantined anywhere
        assert stream.stats["quarantined"] == 0, (i, stream.stats)
    # every tenant shows up in the serving rollup with clean accounting
    rep = serve.tenant_report()
    for t in range(4):
        assert rep[f"soak{t}"]["flushes"] >= 1
        assert rep[f"soak{t}"]["quota_rejects"] == 0
