"""Critical-path attribution plane: waterfalls, rooflines, sentinel.

Covers ``ramba_tpu.observe.attrib`` + the fuser/pipeline stage stamps +
the offline CLIs:

* every flush span carries a monotonically-ordered stage ledger whose
  durations plus the ``unattributed_s`` residual reconcile with span
  wall time (within 5 % for benched kernels),
* roofline math (``classify``) on a fake peak table — achieved rates,
  fraction of peak, bandwidth-vs-compute boundedness at the ridge point,
* ``RAMBA_PEAKS_JSON`` override resolution (inline JSON and file path,
  device_kind substring match, default fallback),
* live roofline rows built from fenced device windows + ledger cost
  models under ``RAMBA_PERF=1``,
* the perf-regression sentinel: exactly one ``perf_regression`` event +
  flight-recorder incident under ``RAMBA_FAULTS=execute:delay:ms=150``,
  silence on a clean soak, baselines persisted/restored across
  processes via ``RAMBA_BASELINE_DIR``,
* profiler annotations that carry the span's trace id (no variable set),
* Prometheus series: stage totals + rooflines + regressions, and the
  compile-class/AOT satellite counters,
* ``scripts/trace_report.py --attrib`` and ``scripts/roofline_report.py``
  on synthetic inputs, ``scripts/perf_diff.py`` device-kind warning.
"""

import contextlib
import glob
import json
import os
import subprocess
import sys

import ramba_tpu as rt
from ramba_tpu import diagnostics
from ramba_tpu.observe import attrib, events, ledger, profile, telemetry
from ramba_tpu.resilience import faults

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _chain(n=2711):
    a = rt.arange(n) * 2.0 + 1.0
    return float(rt.sum(a))


def _big_chain():
    a = rt.arange(1_500_000) * 1.000001 + 0.5
    b = rt.sqrt(a * a + 1.0)
    return float(rt.sum(b))


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


def test_benched_kernel_stage_sum_within_5pct_of_wall():
    _big_chain()  # compile outside the measurement
    for _ in range(5):
        _big_chain()
    spans = diagnostics.last_flushes(5)
    label = spans[-1]["label"]
    fracs = sorted(
        s["unattributed_s"] / s["wall_s"]
        for s in spans if s["label"] == label and s["wall_s"] > 0
    )
    assert fracs, spans
    # acceptance: stage durations explain >= 95 % of span wall for a
    # benched (ms-scale) kernel; median shields one scheduler hiccup
    assert fracs[len(fracs) // 2] <= 0.05, fracs


def test_attribution_report_aggregates():
    _chain()
    rep = attrib.attribution_report()
    assert rep["flushes"] >= 1
    assert rep["stage_seconds"].get("prepare", 0.0) > 0.0
    assert rep["unattributed_s"] >= 0.0
    assert 0.0 <= rep["unattributed_frac"] <= 1.0
    # the CPU backend is not in the peak table: stages are attributed,
    # no roofline is drawn against invented peaks
    assert rep["device_kind"] == "cpu"
    assert rep["peaks"] is None and rep["rooflines"] == {}
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
# roofline math + peak tables (pure units)
# ---------------------------------------------------------------------------


def test_classify_bandwidth_vs_compute_bound():
    peaks = {"peak_gbps": 100.0, "peak_tflops": 1.0}  # ridge = 10 fl/B
    r = attrib.classify(flops=1e6, bytes_accessed=1e8, device_s=1e-3,
                        peaks=peaks)
    assert r["bound"] == "bandwidth"
    assert r["achieved_gb_per_s"] == 100.0       # at peak bandwidth
    assert r["bandwidth_frac"] == 1.0
    assert r["frac_of_peak"] == 1.0
    assert r["intensity"] == 0.01 and r["ridge"] == 10.0
    c = attrib.classify(flops=1e10, bytes_accessed=1e6, device_s=1e-2,
                        peaks=peaks)
    assert c["bound"] == "compute"
    assert c["achieved_tflops"] == 1.0
    assert c["compute_frac"] == 1.0
    # degenerate inputs refuse to classify rather than divide by zero
    assert attrib.classify(0, 0, 1e-3, peaks) is None
    assert attrib.classify(1e6, 1e6, 0.0, peaks) is None


def test_peak_table_override_inline_and_file(tmp_path):
    table = {"zz99": {"peak_gbps": 123.0, "peak_tflops": 4.5}}
    with _env(RAMBA_PEAKS_JSON=json.dumps(table)):
        attrib.reconfigure()
        hit = attrib.peak_table("Super ZZ99 Chip")
        assert hit["peak_gbps"] == 123.0 and hit["peak_tflops"] == 4.5
        assert hit["source"] == "RAMBA_PEAKS_JSON"
        # an unknown device has no peaks, not default ones
        assert attrib.peak_table("unknown-part") is None
    p = tmp_path / "peaks.json"
    p.write_text(json.dumps(table))
    with _env(RAMBA_PEAKS_JSON=str(p)):
        attrib.reconfigure()
        assert attrib.peak_table("zz99 rev2")["peak_tflops"] == 4.5
    attrib.reconfigure()
    # builtin table survives a bogus override
    assert attrib.peak_table("TPU v4")["peak_gbps"] == 1228.0
    # what a v5e chip reports as device_kind (chip run, PR 21)
    assert attrib.peak_table("TPU v5 lite")["peak_gbps"] == 819.0
    assert attrib.peak_table("cpu") is None


def test_live_roofline_rows_from_fenced_windows():
    ledger.reconfigure(mode="on")  # arm cost_analysis capture
    # the CPU mesh has no builtin peaks: give it some, or no row is drawn
    peaks = json.dumps({"cpu": {"peak_gbps": 50.0, "peak_tflops": 1.0}})
    try:
        with _env(RAMBA_PEAKS_JSON=peaks):
            attrib.reconfigure()
            for _ in range(4):
                _chain(3217)  # unique shape => fresh kernel => cost captured
            rep = attrib.attribution_report()
        attrib.reconfigure()
        rows = [r for r in rep["rooflines"].values()
                if r["device_time_source"] == "fence"]
        assert rows, rep["rooflines"]
        r = rows[0]
        assert r["bound"] in ("bandwidth", "compute")
        assert r["frac_of_peak"] >= 0.0
        assert r["device_p50_s"] > 0.0
        assert r["achieved_gb_per_s"] >= 0.0
    finally:
        ledger.reconfigure()


# ---------------------------------------------------------------------------
# perf-regression sentinel
# ---------------------------------------------------------------------------


def test_sentinel_fires_exactly_once_with_flight_incident(tmp_path):
    fdir = tmp_path / "flight"
    with _env(RAMBA_FLIGHT_DIR=str(fdir)):
        telemetry.flight_reset()
        attrib.reset()
        attrib.reconfigure(baseline_dir=str(tmp_path / "base"),
                           drift_min_samples=3)
        try:
            for _ in range(5):
                _chain(4099)
            assert attrib.save_baselines()
            # simulate a fresh run against the saved baseline
            attrib.reset()
            attrib.reconfigure(baseline_dir=str(tmp_path / "base"),
                               drift_min_samples=3)
            base = len(events.last(0, type="perf_regression"))
            with faults.active("execute:delay:ms=150"):
                for _ in range(4):
                    _chain(4099)
            evs = events.last(0, type="perf_regression")
            assert len(evs) == base + 1, evs
            ev = evs[-1]
            for k in ("fingerprint", "label", "p50_s", "baseline_p50_s",
                      "drift", "factor", "samples"):
                assert k in ev, f"perf_regression missing {k!r}"
            assert ev["p50_s"] > ev["baseline_p50_s"] * 2.0
            assert ev["drift"] > 2.0
            # exactly one flight-recorder incident for the regression
            recs = [json.load(open(p))
                    for p in glob.glob(str(fdir / "flight_*.json"))]
            perf_recs = [r for r in recs
                         if r["incident"]["type"] == "perf_regression"]
            assert len(perf_recs) == 1, [r["incident"]["type"] for r in recs]
            # further offending flushes do NOT re-fire for the same kernel
            with faults.active("execute:delay:ms=150"):
                _chain(4099)
            assert len(events.last(0, type="perf_regression")) == base + 1
            sen = diagnostics.perf_report()["attribution"]["sentinel"]
            assert sen["regressions"] == 1
            assert ev["fingerprint"] in sen["regressed"]
        finally:
            telemetry.flight_reset()
            attrib.reset()
            attrib.reconfigure()


def test_sentinel_silent_on_clean_soak(tmp_path):
    attrib.reset()
    attrib.reconfigure(baseline_dir=str(tmp_path), drift_min_samples=3)
    try:
        for _ in range(5):
            _chain(4111)
        assert attrib.save_baselines()
        attrib.reset()
        attrib.reconfigure(baseline_dir=str(tmp_path), drift_min_samples=3)
        base = len(events.last(0, type="perf_regression"))
        for _ in range(8):
            _chain(4111)
        assert len(events.last(0, type="perf_regression")) == base
        # drift_factor <= 0 disables the sentinel even for glacial calls
        attrib.reset()
        attrib.reconfigure(baseline_dir=str(tmp_path), drift_factor=0.0,
                           drift_min_samples=3)
        with faults.active("execute:delay:ms=150"):
            for _ in range(4):
                _chain(4111)
        assert len(events.last(0, type="perf_regression")) == base
    finally:
        attrib.reset()
        attrib.reconfigure()


def test_baseline_only_ratchets_down(tmp_path):
    attrib.reset()
    attrib.reconfigure(baseline_dir=str(tmp_path), drift_min_samples=1)
    try:
        attrib.record_device("aa" * 6, "prog_x", 0.010)
        attrib.save_baselines()
        first = attrib.load_baselines()["aa" * 6]["p50_s"]
        assert first == 0.010
        # a slower run must not raise the bar...
        attrib.reset()
        attrib.reconfigure(baseline_dir=str(tmp_path), drift_min_samples=1)
        attrib.record_device("aa" * 6, "prog_x", 0.500)
        attrib.save_baselines()
        assert attrib.load_baselines()["aa" * 6]["p50_s"] == first
        # ...while a faster run lowers it
        attrib.reset()
        attrib.reconfigure(baseline_dir=str(tmp_path), drift_min_samples=1)
        attrib.record_device("aa" * 6, "prog_x", 0.002)
        attrib.save_baselines()
        assert attrib.load_baselines()["aa" * 6]["p50_s"] == 0.002
    finally:
        attrib.reset()
        attrib.reconfigure()


def test_baseline_persist_restore_across_processes(tmp_path):
    """Process 1 records baselines; process 2 restores them from
    RAMBA_BASELINE_DIR and its seeded delay trips the sentinel exactly
    once — fingerprints are process-stable, so the baseline file is the
    only state shared."""
    record = (
        "import ramba_tpu as rt\n"
        "from ramba_tpu.observe import attrib\n"
        "for _ in range(5):\n"
        "    a = rt.arange(2711) * 2.0 + 1.0\n"
        "    float(rt.sum(a))\n"
        "p = attrib.save_baselines()\n"
        "assert p, 'no baseline written'\n"
        "print('SAVED', len(attrib.load_baselines()))\n"
    )
    check = (
        "import ramba_tpu as rt\n"
        "from ramba_tpu.observe import attrib, events\n"
        "assert attrib.load_baselines(), 'baseline file not restored'\n"
        "for _ in range(5):\n"
        "    a = rt.arange(2711) * 2.0 + 1.0\n"
        "    float(rt.sum(a))\n"
        "print('REGRESSIONS', len(events.last(0, type='perf_regression')))\n"
    )
    env = dict(os.environ)
    env.pop("RAMBA_FAULTS", None)
    env.pop("RAMBA_TRACE", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["RAMBA_BASELINE_DIR"] = str(tmp_path)
    env["RAMBA_PERF_DRIFT_MIN_SAMPLES"] = "3"
    r1 = subprocess.run([sys.executable, "-c", record], env=env,
                        capture_output=True, text=True, cwd=REPO)
    assert r1.returncode == 0, r1.stdout + r1.stderr
    assert "SAVED" in r1.stdout
    assert os.path.exists(tmp_path / "perf_baseline.json")
    env2 = dict(env)
    env2["RAMBA_FAULTS"] = "execute:delay:ms=150"
    r2 = subprocess.run([sys.executable, "-c", check], env=env2,
                        capture_output=True, text=True, cwd=REPO)
    assert r2.returncode == 0, r2.stdout + r2.stderr
    assert "REGRESSIONS 1" in r2.stdout, r2.stdout


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
    assert "ramba_perf_regressions_total" in text
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


def test_roofline_report_cli(tmp_path):
    cap = tmp_path / "cap.json"
    cap.write_text(json.dumps({
        "device_kind": "FakeChip",
        "kernels": {
            "aabbccdd0011": {
                "label": "prog_bw",
                "exec": {"count": 5, "p50_s": 0.001, "total_s": 0.005},
                "sync": {"count": 5, "p50_s": 0.001},
                "flops": 1e6, "bytes_accessed": 1e8,
            },
            "ddccbbaa1100": {
                "label": "prog_fl",
                "exec": {"count": 5, "p50_s": 0.01, "total_s": 0.05},
                "flops": 1e10, "bytes_accessed": 1e6,
            },
            "deadbeef0000": {  # no cost model => skipped
                "label": "prog_na",
                "exec": {"count": 5, "p50_s": 0.01, "total_s": 0.05},
            },
        },
    }))
    peaks = json.dumps({"peak_gbps": 100.0, "peak_tflops": 1.0})
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "roofline_report.py"),
         str(cap), "--peaks", peaks],
        capture_output=True, text=True,
    )
    assert r.returncode == 0, r.stdout + r.stderr
    assert "prog_bw" in r.stdout and "bandwidth" in r.stdout
    assert "prog_fl" in r.stdout and "compute" in r.stdout
    assert "1 skipped" in r.stdout
    assert "RAMBA_PERF=sync" in r.stdout  # dispatch-window caveat
    rj = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "roofline_report.py"),
         str(cap), "--peaks", peaks, "--json"],
        capture_output=True, text=True,
    )
    assert rj.returncode == 0, rj.stdout + rj.stderr
    obj = json.loads(rj.stdout)
    assert obj["device_kind"] == "FakeChip"
    by_label = {k["label"]: k for k in obj["kernels"]}
    assert by_label["prog_bw"]["bound"] == "bandwidth"
    assert by_label["prog_bw"]["frac_of_peak"] == 1.0
    assert by_label["prog_bw"]["device_time_source"] == "sync"
    assert by_label["prog_fl"]["device_time_source"] == "dispatch"
    # no usable kernels => usage error
    empty = tmp_path / "empty.json"
    empty.write_text(json.dumps({"kernels": {}}))
    r3 = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "roofline_report.py"),
         str(empty)],
        capture_output=True, text=True,
    )
    assert r3.returncode == 2


def test_perf_diff_warns_on_device_kind_mismatch(tmp_path):
    old = tmp_path / "old.json"
    new = tmp_path / "new.json"
    kernels = {"aa00": {"label": "prog_a",
                        "exec": {"count": 5, "p50_s": 0.01,
                                 "total_s": 0.05}}}
    old.write_text(json.dumps({"device_kind": "TPU v4",
                               "kernels": kernels, "hbm_gb_per_s": 100.0}))
    new.write_text(json.dumps({"device_kind": "TPU v5e",
                               "kernels": kernels, "hbm_gb_per_s": 101.0}))
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "perf_diff.py"),
         str(old), str(new)],
        capture_output=True, text=True,
    )
    # warns (stderr) but does NOT gate: identical kernels => exit 0
    assert r.returncode == 0, r.stdout + r.stderr
    assert "device_kind mismatch" in r.stderr
    # same kind => no warning
    new.write_text(json.dumps({"device_kind": "TPU v4",
                               "kernels": kernels, "hbm_gb_per_s": 101.0}))
    r2 = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "perf_diff.py"),
         str(old), str(new)],
        capture_output=True, text=True,
    )
    assert r2.returncode == 0
    assert "device_kind mismatch" not in r2.stderr
