"""Self-metering observability plane (PR 20).

Covers ``ramba_tpu.observe.observer`` (the observer-tax ledger),
tail-based trace retention (``RAMBA_TRACE_SAMPLE``), the buffered JSONL
writer, and the incident explainer:

* the file lane head-samples 1-in-N traces by a deterministic trace-id
  hash; an incident retroactively latches the chain (tail latch), a
  rotated buffer leaves a ``trace_gap`` marker,
* writer overflow/failure is counted (``events.write_dropped`` /
  ``events.write_errors``), never raised; ring overwrites count
  ``events.ring_dropped``,
* the explainer names the dominant divergent stage with an
  operator-facing verdict for >= 3 distinct dominant-stage scenarios,
* ``scripts/trace_report.py`` renders the explainer's verdicts and
  sampled-out gaps instead of ORPHANED.
"""

import json
import os
import subprocess
import sys

import ramba_tpu as rt
from ramba_tpu import diagnostics
from ramba_tpu.observe import attrib, events, observer, registry, telemetry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _chain(n=2711):
    a = rt.arange(n) * 2.0 + 1.0
    return float(rt.sum(a))


def _counter(name):
    return registry.snapshot()["counters"].get(name, 0)


# ---------------------------------------------------------------------------
# tail-based trace retention + buffered writer
# ---------------------------------------------------------------------------


def _pick_tid(sampled_in, start=0):
    """First trace id (deterministic hash) with the wanted verdict."""
    i = start
    while True:
        tid = f"t-{i:04d}"
        if events.trace_sampled_in(tid) == sampled_in:
            return tid
        i += 1


def _read_jsonl(path):
    with open(path) as f:
        return [json.loads(ln) for ln in f if ln.strip()]


def test_tail_latch_replays_buffered_chain(tmp_path):
    path = str(tmp_path / "t.jsonl")
    events.configure(path, sample=4)
    try:
        tid_out = _pick_tid(False)
        tid_in = _pick_tid(True)
        b0 = _counter("events.tail_buffered")
        l0 = _counter("events.tail_latched")
        for i in range(3):
            events.emit({"type": "flush", "label": "prog_t", "i": i,
                         "trace_id": tid_out})
        events.emit({"type": "flush", "label": "prog_t", "i": 99,
                     "trace_id": tid_in})
        events.sync()
        evs = _read_jsonl(path)
        # steady state: the sampled-out chain is buffered, not written;
        # the sampled-in chain writes through
        assert [e.get("trace_id") for e in evs] == [tid_in]
        assert _counter("events.tail_buffered") == b0 + 3
        # incident: the chain is latched and replayed IN ORDER ahead of
        # the incident line
        events.emit({"type": "slo_breach", "label": "prog_t",
                     "trace_id": tid_out})
        events.sync()
        chain = [e for e in _read_jsonl(path)
                 if e.get("trace_id") == tid_out]
        assert [e.get("i") for e in chain[:3]] == [0, 1, 2]
        assert chain[3]["type"] == "slo_breach"
        assert _counter("events.tail_latched") == l0 + 1
        # later events of a latched trace write through unsampled
        events.emit({"type": "flush", "label": "prog_t", "i": 7,
                     "trace_id": tid_out})
        events.sync()
        chain = [e for e in _read_jsonl(path)
                 if e.get("trace_id") == tid_out]
        assert chain[-1].get("i") == 7
        # events with NO trace id always write through
        events.emit({"type": "health", "source": "x", "outcome": "ok"})
        events.sync()
        assert any(e.get("type") == "health" for e in _read_jsonl(path))
    finally:
        events.configure(None)


def test_tail_buffer_rotation_leaves_gap_marker(tmp_path):
    path = str(tmp_path / "t.jsonl")
    events.configure(path, sample=4)
    try:
        tid = _pick_tid(False)
        n = 70  # > the 64-event per-trace buffer: 6 oldest rotate out
        for i in range(n):
            events.emit({"type": "flush", "label": "prog_g", "i": i,
                         "trace_id": tid})
        events.emit({"type": "slo_breach", "label": "prog_g",
                     "trace_id": tid})
        events.sync()
        evs = [e for e in _read_jsonl(path) if e.get("trace_id") == tid]
        gaps = [e for e in evs if e.get("type") == "trace_gap"]
        assert len(gaps) == 1 and gaps[0]["dropped"] == n - 64, gaps
        kept = [e.get("i") for e in evs if e.get("type") == "flush"]
        assert kept == list(range(n - 64, n))  # newest 64 survive
    finally:
        events.configure(None)


def test_buffered_writer_overflow_drops_counted(tmp_path):
    path = str(tmp_path / "t.jsonl")
    events.configure(path, buffer_max=4)
    try:
        d0 = _counter("events.write_dropped")
        # hold the writer lock: drains can't run, the pending buffer
        # fills to buffer_max and further lines drop (counted, no raise,
        # no blocking — the writer must never backpressure the flush)
        with events._write_lock:
            for i in range(10):
                events.emit({"type": "bench_tick", "i": i})
        events.sync()
        assert _counter("events.write_dropped") >= d0 + 6
        assert len(_read_jsonl(path)) <= 4
    finally:
        events.configure(None)


def test_write_errors_counted_not_raised(tmp_path, monkeypatch):
    path = str(tmp_path / "t.jsonl")
    events.configure(path)
    try:
        class _Bad:
            def write(self, s):
                raise OSError("disk full")

        monkeypatch.setattr(events, "_file", lambda: _Bad())
        e0 = _counter("events.write_errors")
        events.emit({"type": "bench_tick", "i": 0})
        events.sync()  # must not raise
        assert _counter("events.write_errors") >= e0 + 1
    finally:
        monkeypatch.undo()
        events.configure(None)


def test_ring_dropped_counter():
    events.configure(None)
    r0 = _counter("events.ring_dropped")
    n = events.ring.maxlen + 10
    for i in range(n):
        events.emit({"type": "bench_tick", "i": i})
    assert _counter("events.ring_dropped") >= r0 + 10


def test_trace_sampled_in_deterministic():
    events.configure(None, sample=4)
    try:
        tids = [f"t-{i:04d}" for i in range(64)]
        verdicts = [events.trace_sampled_in(t) for t in tids]
        assert any(verdicts) and not all(verdicts)
        # pure hash: same answer on every call (and on every rank)
        assert [events.trace_sampled_in(t) for t in tids] == verdicts
        # no trace id -> always in; sample 1 -> everything in
        assert events.trace_sampled_in(None)
    finally:
        events.configure(None)
    assert all(events.trace_sampled_in(t) for t in ("a", "b", "c"))


# ---------------------------------------------------------------------------
# observer-tax ledger
# ---------------------------------------------------------------------------


def test_observer_ledger_accounting():
    observer.reset()
    observer.add("events", 0.002)
    observer.add("events", 0.001)
    observer.add("fence", 0.004)
    observer.add("fence", -1.0)  # negative clock skew: ignored
    with observer.taxed("telemetry"):
        pass
    snap = observer.snapshot()
    comps = snap["components"]
    assert comps["events"]["count"] == 2
    assert abs(comps["events"]["seconds"] - 0.003) < 1e-9
    assert comps["fence"]["count"] == 1
    assert comps["telemetry"]["count"] == 1
    assert snap["total_s"] >= 0.007
    observer.reset()
    assert observer.snapshot()["components"] == {}


def test_observer_tax_frac_denominator_is_flush_wall():
    observer.reset()
    attrib.reset()
    assert observer.tax_frac() is None  # no attributed wall yet
    _chain(3307)  # one real flush: attrib totals + emit/ledger billing
    frac = observer.tax_frac()
    assert frac is not None and 0.0 < frac
    snap = observer.snapshot()
    assert snap.get("tax_frac") == frac
    # the flush itself billed the plane's components
    assert "events" in snap["components"]
    assert "ledger" in snap["components"]
    attrib.reset()
    observer.reset()


def test_observer_surfaces_in_diagnostics_and_telemetry():
    observer.reset()
    observer.add("fleet", 0.001)
    rep = diagnostics.observer_report()
    assert rep["components"]["fleet"]["seconds"] > 0
    assert "observer" in diagnostics.snapshot()
    import io
    buf = io.StringIO()
    diagnostics.report(file=buf)
    assert "observer tax" in buf.getvalue()
    prom = telemetry.render()
    line = next(ln for ln in prom.splitlines()
                if ln.startswith("ramba_observer_seconds_total{"))
    assert 'component="fleet"' in line  # (rank label rides along)
    observer.reset()


# ---------------------------------------------------------------------------
# incident explainer
# ---------------------------------------------------------------------------


def _seed_baseline(fp, n=5):
    """Five steady spans -> per-stage rolling baselines for ``fp``."""
    for _ in range(n):
        span = {"stages": {"prepare": 0.001, "queue_wait": 0.001,
                           "dispatch": 0.004, "device_execute": 0.004},
                "wall_s": 0.011}
        attrib.finalize_span(span, fp=fp)


def test_explainer_verdicts_three_dominant_stages():
    attrib.reset()
    try:
        fp = "fe" * 6
        _seed_baseline(fp)
        # 1: queue_wait 12x baseline -> overload
        why = attrib.explain(
            {"stages": {"prepare": 0.001, "queue_wait": 0.012,
                        "dispatch": 0.004, "device_execute": 0.004},
             "wall_s": 0.022, "fingerprint": fp})
        assert why["stage"] == "queue_wait"
        assert why["verdict"] == "overload"
        assert 11.0 <= why["ratio"] <= 13.0
        assert "12.0x baseline -> overload" in why["text"]
        # 2: compile appearing on a steady-state fingerprint (no
        # baseline window at all) -> cache miss, divergent by existence
        why = attrib.explain(
            {"stages": {"prepare": 0.001, "queue_wait": 0.001,
                        "compile": 0.050, "dispatch": 0.004,
                        "device_execute": 0.004},
             "wall_s": 0.061, "fingerprint": fp})
        assert why["stage"] == "compile"
        assert why["verdict"] == "cache miss"
        assert why["ratio"] is None and "compile -> cache miss" in why["text"]
        # 3: device_execute dominates -> device regression (explicit fp
        # argument wins over the span stamp)
        why = attrib.explain(
            {"stages": {"prepare": 0.001, "queue_wait": 0.001,
                        "dispatch": 0.004, "device_execute": 0.040},
             "wall_s": 0.047}, fp=fp)
        assert why["stage"] == "device_execute"
        assert why["verdict"] == "device regression"
        # 4: unattributed residual blowing up -> untracked interference
        why = attrib.explain(
            {"stages": {"prepare": 0.001, "queue_wait": 0.001,
                        "dispatch": 0.004, "device_execute": 0.004},
             "unattributed_s": 0.030, "wall_s": 0.041, "fingerprint": fp})
        assert why["stage"] == "unattributed"
        assert "untracked interference" in why["verdict"]
    finally:
        attrib.reset()


def test_explainer_silent_without_divergence_or_history():
    attrib.reset()
    try:
        fp = "fd" * 6
        # no baselines at all -> None (nothing to diff against)
        assert attrib.explain(
            {"stages": {"prepare": 0.001}, "wall_s": 0.001,
             "fingerprint": fp}) is None
        _seed_baseline(fp)
        # a span AT baseline -> None (no stage exceeds 1.5x its p50)
        assert attrib.explain(
            {"stages": {"prepare": 0.001, "queue_wait": 0.001,
                        "dispatch": 0.004, "device_execute": 0.004},
             "wall_s": 0.011, "fingerprint": fp}) is None
        # no fingerprint -> None
        assert attrib.explain(
            {"stages": {"prepare": 0.9}, "wall_s": 1.0}) is None
    finally:
        attrib.reset()


# ---------------------------------------------------------------------------
# trace_report: explainer verdicts + sampled-out gaps
# ---------------------------------------------------------------------------


def _write_jsonl(path, events_):
    with open(path, "w") as f:
        for e in events_:
            f.write(json.dumps(e) + "\n")


def _trace_report(*args):
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "trace_report.py"),
         *args],
        capture_output=True, text=True,
    )


def test_attrib_report_renders_explainer_verdicts(tmp_path):
    path = tmp_path / "t.jsonl"
    _write_jsonl(path, [
        {"type": "flush", "label": "prog_a", "ts": 1.0, "seq": 1,
         "wall_s": 0.01, "unattributed_s": 0.001,
         "stages": {"prepare": 0.002, "dispatch": 0.003,
                    "device_execute": 0.004}},
        {"type": "slo_breach", "label": "prog_a", "ts": 1.2, "seq": 2,
         "why": "queue_wait 12.0x baseline -> overload",
         "why_stage": "queue_wait", "why_verdict": "overload"},
    ])
    r = _trace_report(str(path), "--attrib")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "incident explainer verdicts" in r.stdout
    assert "queue_wait 12.0x baseline -> overload" in r.stdout


def test_trace_chain_gap_classified_not_orphaned(tmp_path):
    path = tmp_path / "t.jsonl"
    # chain whose early spans rotated out of the tail buffer: the child
    # event's parent is gone, but the trace_gap marker explains why
    _write_jsonl(path, [
        {"type": "trace_gap", "trace_id": "req-1", "dropped": 6,
         "reason": "tail_buffer_rotation", "ts": 1.0, "seq": 1},
        {"type": "flush", "label": "prog_a", "ts": 1.1, "seq": 2,
         "trace_id": "req-1", "span_id": "s2", "wall_s": 0.01},
        {"type": "degrade", "action": "rung", "ts": 1.2, "seq": 3,
         "trace_id": "req-1", "parent_span": "s-rotated-out"},
    ])
    r = _trace_report(str(path), "--trace", "req-1")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "sampling gap: 6 event(s)" in r.stdout
    assert "sampled-out events (1)" in r.stdout
    assert "ORPHANED" not in r.stdout
    # without a gap marker the same shape is a genuine orphan
    _write_jsonl(path, [
        {"type": "flush", "label": "prog_a", "ts": 1.1, "seq": 1,
         "trace_id": "req-2", "span_id": "s2", "wall_s": 0.01},
        {"type": "degrade", "action": "rung", "ts": 1.2, "seq": 2,
         "trace_id": "req-2", "parent_span": "s-missing-rank"},
    ])
    r2 = _trace_report(str(path), "--trace", "req-2")
    assert r2.returncode == 0, r2.stdout + r2.stderr
    assert "ORPHANED events (1)" in r2.stdout
    assert "sampling gap" not in r2.stdout
