"""Critical-path attribution plane: the flush span's stage waterfall.

Covers ``ramba_tpu.observe.attrib`` + the fuser/pipeline stage stamps +
the offline CLI:

* every flush span carries a monotonically-ordered stage ledger whose
  durations plus the ``unattributed_s`` residual reconcile with span
  wall time (a seeded execute delay lands in a stage, not the residual),
* ``RAMBA_ATTRIB=off`` drops the fence and keeps the stages,
* profiler annotations that carry the span's trace id (no variable set),
* Prometheus series: stage totals, and the compile-class/AOT satellite
  counters,
* ``scripts/trace_report.py --attrib`` on synthetic inputs.
"""

import contextlib
import json
import os
import subprocess
import sys

import ramba_tpu as rt
from ramba_tpu import diagnostics
from ramba_tpu.observe import attrib, events, profile, telemetry
from ramba_tpu.resilience import faults

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _chain(n=2711):
    a = rt.arange(n) * 2.0 + 1.0
    return float(rt.sum(a))


@contextlib.contextmanager
def _env(**kv):
    saved = {k: os.environ.get(k) for k in kv}
    for k, v in kv.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v
    try:
        yield
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


# ---------------------------------------------------------------------------
# stage ledger: ordering + wall reconciliation
# ---------------------------------------------------------------------------


def test_span_stages_ordered_and_reconcile_with_wall():
    _chain()
    span = diagnostics.last_flushes(1)[0]
    st = span["stages"]
    assert st, span
    # only canonical stages, in canonical (monotonic critical-path) order
    assert set(st) <= set(attrib.STAGES)
    order = [k for k in attrib.STAGES if k in st]
    assert list(st) == order or sorted(st, key=attrib.STAGES.index) == order
    # identity: stages + residual == wall (finalize_span construction)
    total = sum(st.values()) + span["unattributed_s"]
    assert abs(total - span["wall_s"]) <= 2e-5 * (len(st) + 2), span


def test_seeded_execute_delay_lands_in_a_stage_not_the_residual():
    _chain(2717)  # compile outside the measurement
    with faults.active("execute:delay:ms=150"):
        for _ in range(3):
            _chain(2717)
    spans = diagnostics.last_flushes(3)
    assert len({s["label"] for s in spans}) == 1, spans
    # the stage clock starts before the fault hook, so the 150 ms are
    # dispatch's: the residual's noise is microseconds beside them
    assert all(s["stages"]["dispatch"] >= 0.15 for s in spans), spans
    fracs = sorted(s["unattributed_s"] / s["wall_s"] for s in spans)
    assert fracs[1] <= 0.05, fracs


def test_attribution_report_aggregates():
    _chain()
    rep = attrib.attribution_report()
    assert rep["flushes"] >= 1
    assert rep["stage_seconds"].get("prepare", 0.0) > 0.0
    assert rep["unattributed_s"] >= 0.0
    assert 0.0 <= rep["unattributed_frac"] <= 1.0
    assert set(rep) == {"flushes", "stage_seconds", "unattributed_s",
                        "unattributed_frac"}
    assert rep == diagnostics.perf_report()["attribution"]


def test_attrib_off_disables_fence_but_keeps_stages():
    with _env(RAMBA_ATTRIB="off"):
        attrib.reconfigure()
        try:
            assert not attrib.fence_enabled()
            _chain(2713)
            st = diagnostics.last_flushes(1)[0]["stages"]
            assert "device_execute" not in st
            assert "dispatch" in st or "compile" in st
        finally:
            pass
    attrib.reconfigure()
    assert attrib.fence_enabled()


# ---------------------------------------------------------------------------
# profiler annotations carry the span's trace id
# ---------------------------------------------------------------------------


def test_flush_annotation_carries_trace_id(tmp_path):
    """With no variable set, a profiler session sees each flush stage of a
    traced session under its stable name with the span's trace id and
    label as arguments: a timeline row joins back to its RAMBA_TRACE
    span."""
    import jax.profiler as _prof

    from ramba_tpu import serve
    from tests.helpers import profiled_host_lines

    assert not os.environ.get("RAMBA_PROFILE")
    ctx = profile.flush_annotation("run", {"label": "prog_x",
                                           "trace_id": "tr-0042"})
    assert isinstance(ctx, _prof.TraceAnnotation)
    assert isinstance(profile.flush_annotation("fence"),
                      _prof.TraceAnnotation)

    def body():
        with serve.Session(tenant="acme", trace_id="cafe000000000042") as s:
            a = rt.arange(2711) * 2.0 + 1.0
            s.flush(wait=True)
            a.asarray()

    lines = profiled_host_lines(tmp_path, body)
    span = [e for e in events.ring if e.get("type") == "flush"
            and e.get("trace_id") == "cafe000000000042"][-1]
    for stage in ("prepare", "run", "fence"):
        got = [stats for evs in lines.values() for name, _, _, stats in evs
               if name == "ramba.flush." + stage
               and stats.get("trace_id") == "cafe000000000042"]
        assert got, f"no ramba.flush.{stage} with the session's trace id"
        assert got[-1]["label"] == span["label"]


# ---------------------------------------------------------------------------
# Prometheus exposition
# ---------------------------------------------------------------------------


def test_prometheus_attrib_series():
    _chain()
    text = telemetry.render()
    assert "ramba_flushes_attributed_total" in text
    assert 'ramba_stage_seconds_total{' in text
    assert 'stage="prepare"' in text
    assert "ramba_stage_unattributed_seconds_total" in text
    # satellite: jit-cache hit rate reaches the exporter
    assert "ramba_compile_hit_rate" in text


def test_prometheus_compile_class_satellite_counters(monkeypatch):
    from ramba_tpu.compile import classes, persist

    monkeypatch.setattr(classes, "snapshot", lambda: {
        "mode": "pow2", "planned": 3, "padded": 2, "bailouts": 0,
        "pad_bytes": 4096, "pad_waste_frac": 0.25,
    })
    monkeypatch.setattr(persist, "snapshot", lambda: {
        "armed": True, "hits": 1, "misses": 2, "corrupt": 0, "stores": 1,
        "bytes_read": 10, "bytes_written": 20, "call_fallbacks": 7,
    })
    fams = telemetry._Families({"rank": 0})
    telemetry._compile_series(fams)
    text = fams.render()
    fallback = [l for l in text.splitlines()
                if l.startswith("ramba_compile_call_fallbacks_total")]
    assert fallback and fallback[0].endswith(" 7"), text
    waste = [l for l in text.splitlines()
             if l.startswith("ramba_compile_bucket_pad_waste_bytes")]
    assert waste and waste[0].endswith(" 4096"), text


# ---------------------------------------------------------------------------
# offline CLIs
# ---------------------------------------------------------------------------


def _write_jsonl(path, events_):
    with open(path, "w") as f:
        for e in events_:
            f.write(json.dumps(e) + "\n")


def test_trace_report_attrib_waterfall_cli(tmp_path):
    path = tmp_path / "t.jsonl"
    _write_jsonl(path, [
        {"type": "flush", "label": "prog_a", "ts": 1.0, "seq": 1,
         "wall_s": 0.1, "unattributed_s": 0.01,
         "stages": {"prepare": 0.01, "compile": 0.07, "dispatch": 0.005,
                    "device_execute": 0.004, "write_back": 0.001}},
        {"type": "flush", "label": "prog_b", "ts": 1.1, "seq": 2,
         "wall_s": 0.05, "unattributed_s": 0.03,
         "stages": {"prepare": 0.005, "dispatch": 0.01,
                    "device_execute": 0.005}},
    ])
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "trace_report.py"),
         str(path), "--attrib"],
        capture_output=True, text=True,
    )
    assert r.returncode == 0, r.stdout + r.stderr
    assert "stage waterfall" in r.stdout
    assert "prog_a" in r.stdout and "prog_b" in r.stdout
    assert "unattributed gap" in r.stdout
    # prog_b carries the bigger unexplained gap => listed first
    gap_block = r.stdout.split("unattributed gap")[1]
    assert gap_block.index("prog_b") < gap_block.index("prog_a")
    # a trace with no stage ledgers reports rather than crashes
    bare = tmp_path / "bare.jsonl"
    _write_jsonl(bare, [{"type": "flush", "label": "prog_c", "ts": 1.0,
                         "seq": 1, "wall_s": 0.1}])
    r2 = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "trace_report.py"),
         str(bare), "--attrib"],
        capture_output=True, text=True,
    )
    assert r2.returncode == 1
    assert "no stage-attributed" in r2.stdout


def test_trace_report_merge_ranks_stage_columns(tmp_path):
    base = tmp_path / "m.jsonl"
    for rank in range(2):
        _write_jsonl(f"{base}.rank{rank}", [
            {"type": "health", "source": "distributed_init", "outcome": "ok",
             "ts": 10.0, "seq": 1, "rank": rank},
            {"type": "flush", "label": "prog_a", "ts": 10.1, "seq": 2,
             "rank": rank, "wall_s": 0.01, "cache": "miss",
             "unattributed_s": 0.001,
             "stages": {"prepare": 0.002, "compile": 0.007}},
        ])
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "trace_report.py"),
         str(base), "--merge-ranks"],
        capture_output=True, text=True,
    )
    assert r.returncode == 0, r.stdout + r.stderr
    assert "rank divergence: none" in r.stdout
    assert "stage seconds per rank:" in r.stdout
    assert "prepare" in r.stdout and "unattributed" in r.stdout
    # a rank stamping a different stage signature at the same flush
    # index is flagged as divergence
    _write_jsonl(f"{base}.rank1", [
        {"type": "health", "source": "distributed_init", "outcome": "ok",
         "ts": 10.0, "seq": 1, "rank": 1},
        {"type": "flush", "label": "prog_a", "ts": 10.1, "seq": 2,
         "rank": 1, "wall_s": 0.01, "cache": "miss",
         "unattributed_s": 0.001,
         "stages": {"prepare": 0.002, "compile": 0.005,
                    "device_execute": 0.002}},
    ])
    r2 = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "trace_report.py"),
         str(base), "--merge-ranks"],
        capture_output=True, text=True,
    )
    assert r2.returncode == 0, r2.stdout + r2.stderr
    assert "rank divergence at flush #0" in r2.stdout
    assert "stages" in r2.stdout
