"""Fleet observability federation: snapshot spool, collector, health model.

Covers ``ramba_tpu/observe/fleet.py`` and its seams:

* spool publishing: atomic versioned documents named by replica id, the
  identity block, monotone publish_seq, env-driven autostart off the
  flush path,
* the collector's edge cases — the ones a real fleet throws at it:
  stale snapshots, torn/truncated JSON (classified, NEVER a crash),
  mismatched schema_version, and the healthy -> stale -> dead
  transition as a snapshot ages past the RAMBA_FLEET_STALE_X /
  RAMBA_FLEET_DEAD_X thresholds,
* degraded classification from the published signals block (brownout,
  open breakers, latched SLO breaches),
* fleet rollups: goodput reconciliation against per-replica documents,
  exact merged SLO histograms, dead replicas excluded from aggregation,
* Prometheus federation rendering with ``replica`` labels, and
* cross-process trace stitching: ``trace_report.py --trace`` over a
  directory of per-replica JSONL files, including orphan-half flagging.

The live multi-process soak (3 publishers, SIGKILL mid-soak, collector
CLI) is scripts/two_process_suite.py --fleet-leg; these tests pin the
library logic with hand-built spool directories and injected clocks.
"""

import json
import os
import subprocess
import sys
import time

import pytest

from ramba_tpu import diagnostics
from ramba_tpu.observe import fleet, registry, slo

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_fleet(monkeypatch):
    monkeypatch.delenv("RAMBA_FLEET_DIR", raising=False)
    monkeypatch.delenv("RAMBA_FLEET_INTERVAL_S", raising=False)
    monkeypatch.delenv("RAMBA_FLEET_STALE_X", raising=False)
    monkeypatch.delenv("RAMBA_FLEET_DEAD_X", raising=False)
    fleet.reset()
    yield
    fleet.reset()


def _doc(tmp_path, replica="h-1-0", age_s=0.0, interval_s=5.0,
         schema_version=None, signals=None, counters=None,
         diagnostics_extra=None, now=1_000_000.0):
    """Hand-build one spool document the way a publisher would."""
    ident = {"schema_version": diagnostics.SCHEMA_VERSION,
             "host": replica.rsplit("-", 2)[0],
             "pid": int(replica.rsplit("-", 2)[1]),
             "rank": int(replica.rsplit("-", 2)[2]),
             "nprocs": 1, "device_kind": "cpu",
             "start_time_wall": now - 3600.0,
             "start_time_mono": 1.0}
    sig = {"brownout": "green", "open_breakers": [], "breaker_trips": 0,
           "shed_total": 0, "slo_breached": [], "heartbeat_running": False,
           "heartbeat_age_s": None, "heartbeat_interval_s": None}
    sig.update(signals or {})
    diag = {"counters": counters or {}}
    diag.update(diagnostics_extra or {})
    doc = {"schema_version": (diagnostics.SCHEMA_VERSION
                              if schema_version is None else schema_version),
           "identity": ident, "replica": replica,
           "interval_s": interval_s,
           "published_at": now - age_s,
           "published_mono": 100.0 - age_s,
           "publish_seq": 7, "signals": sig, "diagnostics": diag}
    path = os.path.join(tmp_path, f"{replica}.json")
    with open(path, "w") as f:
        json.dump(doc, f)
    return doc


NOW = 1_000_000.0


# -- publisher ---------------------------------------------------------------


def test_publish_writes_versioned_identity_document(tmp_path):
    path = fleet.publish(str(tmp_path))
    assert path and os.path.exists(path)
    doc = json.load(open(path))
    assert doc["schema_version"] == diagnostics.SCHEMA_VERSION
    ident = doc["identity"]
    assert ident["pid"] == os.getpid()
    assert doc["replica"] == fleet.replica_id(ident)
    assert os.path.basename(path) == doc["replica"] + ".json"
    assert doc["publish_seq"] >= 1
    assert doc["signals"]["brownout"] in ("green", "yellow", "red")
    assert "counters" in doc["diagnostics"]
    assert not [p for p in os.listdir(tmp_path) if p.endswith(".tmp")]


def test_publish_seq_monotone_and_single_file(tmp_path):
    p1 = fleet.publish(str(tmp_path))
    s1 = json.load(open(p1))["publish_seq"]
    p2 = fleet.publish(str(tmp_path))
    s2 = json.load(open(p2))["publish_seq"]
    assert p1 == p2, "one replica republishes in place"
    assert s2 == s1 + 1
    assert registry.get("fleet.publishes") >= 2


def test_publish_noop_without_fleet_dir():
    assert fleet.fleet_dir() is None
    assert fleet.publish() is None
    assert not fleet.started()


def test_ensure_started_spins_up_publisher_thread(tmp_path, monkeypatch):
    monkeypatch.setenv("RAMBA_FLEET_DIR", str(tmp_path))
    monkeypatch.setenv("RAMBA_FLEET_INTERVAL_S", "0.05")
    fleet.reset()
    fleet.ensure_started()
    assert fleet.started()
    def _docs():
        # poll for the final document, not the transient .tmp sibling
        return [p for p in os.listdir(str(tmp_path)) if p.endswith(".json")]

    deadline = time.time() + 10
    while time.time() < deadline and not _docs():
        time.sleep(0.02)
    assert _docs(), "spool thread publishes without any explicit call"
    fleet.stop()
    assert not fleet.started()


# -- classification ----------------------------------------------------------


def test_fresh_green_snapshot_is_healthy(tmp_path):
    _doc(str(tmp_path), age_s=0.5, now=NOW)
    h = fleet.health(str(tmp_path), now=NOW)
    row = h["replicas"]["h-1-0"]
    assert row["state"] == fleet.HEALTHY
    assert h["fleet_state"] == fleet.HEALTHY
    assert h["counts"][fleet.HEALTHY] == 1
    assert row["age_s"] == pytest.approx(0.5)


def test_healthy_to_stale_to_dead_as_snapshot_ages(tmp_path):
    """The replica-death transition, driven purely by the injected
    clock: fresh -> stale past 1.5x interval -> dead past 2x."""
    _doc(str(tmp_path), interval_s=5.0, age_s=0.0, now=NOW)
    assert fleet.health(str(tmp_path),
                        now=NOW)["fleet_state"] == fleet.HEALTHY
    # age 7.5s == 1.5 x 5s is NOT yet stale (strict >); 7.6s is
    assert fleet.health(str(tmp_path),
                        now=NOW + 7.6)["fleet_state"] == fleet.STALE
    assert fleet.health(str(tmp_path),
                        now=NOW + 10.1)["fleet_state"] == fleet.DEAD
    row = fleet.health(str(tmp_path), now=NOW + 10.1)["replicas"]["h-1-0"]
    assert row["state"] == fleet.DEAD
    assert "2x interval" in row["reason"]


def test_stale_and_dead_factors_from_env(tmp_path, monkeypatch):
    monkeypatch.setenv("RAMBA_FLEET_STALE_X", "3")
    monkeypatch.setenv("RAMBA_FLEET_DEAD_X", "6")
    _doc(str(tmp_path), interval_s=1.0, age_s=2.0, now=NOW)
    assert fleet.health(str(tmp_path),
                        now=NOW)["fleet_state"] == fleet.HEALTHY
    assert fleet.health(str(tmp_path),
                        now=NOW + 2.0)["fleet_state"] == fleet.STALE
    assert fleet.health(str(tmp_path),
                        now=NOW + 5.0)["fleet_state"] == fleet.DEAD


def test_torn_document_classified_stale_never_crashes(tmp_path):
    """A truncated write from a dying process is DATA, not an error:
    the collector classifies it and moves on."""
    _doc(str(tmp_path), replica="ok-1-0", now=NOW)
    with open(tmp_path / "torn-2-0.json", "w") as f:
        f.write('{"schema_version": 1, "identity": {"pid": 2, "ho')
    with open(tmp_path / "empty-3-0.json", "w") as f:
        f.write("")
    h = fleet.health(str(tmp_path), now=NOW)
    assert h["replicas"]["ok-1-0"]["state"] == fleet.HEALTHY
    assert h["replicas"]["torn-2-0"]["state"] == fleet.STALE
    assert "Error" in h["replicas"]["torn-2-0"]["reason"]
    assert h["replicas"]["empty-3-0"]["state"] == fleet.STALE
    assert h["fleet_state"] == fleet.STALE


def test_mismatched_schema_version_skipped_as_stale(tmp_path):
    _doc(str(tmp_path), replica="old-1-0",
         schema_version=diagnostics.SCHEMA_VERSION + 1, now=NOW)
    row = fleet.health(str(tmp_path), now=NOW)["replicas"]["old-1-0"]
    assert row["state"] == fleet.STALE
    assert "schema_version" in row["reason"]


def test_degraded_from_signals(tmp_path):
    _doc(str(tmp_path), replica="brown-1-0",
         signals={"brownout": "red"}, now=NOW)
    _doc(str(tmp_path), replica="breaker-2-0",
         signals={"open_breakers": ["acme"]}, now=NOW)
    _doc(str(tmp_path), replica="slo-3-0",
         signals={"slo_breached": ["acme"]}, now=NOW)
    _doc(str(tmp_path), replica="wedged-4-0",
         signals={"heartbeat_running": True, "heartbeat_age_s": 9.0,
                  "heartbeat_interval_s": 1.0}, now=NOW)
    h = fleet.health(str(tmp_path), now=NOW)
    states = {r: row["state"] for r, row in h["replicas"].items()}
    assert states == {r: fleet.DEGRADED for r in states}
    assert "brownout red" in h["replicas"]["brown-1-0"]["reason"]
    assert "acme" in h["replicas"]["breaker-2-0"]["reason"]
    assert "SLO" in h["replicas"]["slo-3-0"]["reason"]
    assert "heartbeat" in h["replicas"]["wedged-4-0"]["reason"]
    assert h["fleet_state"] == fleet.DEGRADED


def test_empty_or_missing_dir_is_vacuously_healthy(tmp_path):
    h = fleet.health(str(tmp_path / "nope"))
    assert h["replicas"] == {} and h["fleet_state"] == fleet.HEALTHY


# -- rollup ------------------------------------------------------------------


def test_rollup_goodput_reconciles_and_excludes_dead(tmp_path):
    _doc(str(tmp_path), replica="a-1-0", now=NOW,
         counters={"fuser.flushes": 10, "fuser.nodes_flushed": 30,
                   "serve.flushes": 10, "serve.shed": 1})
    _doc(str(tmp_path), replica="b-2-0", now=NOW,
         counters={"fuser.flushes": 7, "fuser.nodes_flushed": 21,
                   "serve.flushes": 7})
    # a corpse: counted by health, EXCLUDED from aggregation
    _doc(str(tmp_path), replica="dead-3-0", age_s=60.0, now=NOW,
         counters={"fuser.flushes": 1000})
    roll = fleet.rollup(str(tmp_path), now=NOW)
    assert roll["replicas"] == ["a-1-0", "b-2-0"]
    gp = roll["goodput"]
    assert gp["flushes"] == 17 and gp["nodes_flushed"] == 51
    assert gp["shed_total"] == 1
    assert gp["flushes"] == sum(
        r["flushes"] for r in gp["replicas"].values())
    assert gp["replicas"]["a-1-0"]["uptime_s"] == pytest.approx(3600.0)


def test_rollup_merges_slo_histograms_exactly(tmp_path):
    """Fixed-bucket summaries merge by cumulative-count addition — the
    merged percentile must equal a single histogram fed both streams."""
    h1, h2, ref = slo.Histogram(), slo.Histogram(), slo.Histogram()
    for v in (0.001, 0.004, 0.004, 0.02):
        h1.observe(v)
        ref.observe(v)
    for v in (0.08, 0.3, 1.2):
        h2.observe(v)
        ref.observe(v)
    _doc(str(tmp_path), replica="a-1-0", now=NOW, diagnostics_extra={
        "slo": {"histograms": {"e2e": {"acme": h1.summary()}}}})
    _doc(str(tmp_path), replica="b-2-0", now=NOW, diagnostics_extra={
        "slo": {"histograms": {"e2e": {"acme": h2.summary()}}}})
    merged = fleet.rollup(str(tmp_path), now=NOW)["slo"]["e2e"]["acme"]
    want = ref.summary()
    assert merged["count"] == want["count"] == 7
    for q in ("p50_ms", "p95_ms", "p99_ms"):
        assert merged[q] == pytest.approx(want[q])
    assert merged["sum_s"] == pytest.approx(want["sum_s"])


def test_rollup_cache_comparison(tmp_path):
    _doc(str(tmp_path), replica="warm-1-0", now=NOW,
         counters={"fuser.cache_hit": 9, "fuser.cache_miss": 1},
         diagnostics_extra={"perf": {
             "compile": {"persist": {"hits": 5, "misses": 0}}}})
    _doc(str(tmp_path), replica="cold-2-0", now=NOW,
         counters={"fuser.cache_hit": 1, "fuser.cache_miss": 9},
         diagnostics_extra={"perf": {
             "compile": {"persist": {"hits": 0, "misses": 5}}}})
    roll = fleet.rollup(str(tmp_path), now=NOW)
    assert roll["caches"]["warm-1-0"]["jit_hit_rate"] == pytest.approx(0.9)
    assert roll["caches"]["cold-2-0"]["jit_hit_rate"] == pytest.approx(0.1)
    assert roll["caches"]["warm-1-0"]["aot_hits"] == 5
    assert roll["caches"]["cold-2-0"]["aot_misses"] == 5


# -- Prometheus federation ---------------------------------------------------


def test_render_fleet_exposition_with_replica_labels(tmp_path):
    _doc(str(tmp_path), replica="a-1-0", now=NOW,
         counters={"fuser.flushes": 4})
    _doc(str(tmp_path), replica="b-2-0", age_s=60.0, now=NOW)
    body = fleet.render(str(tmp_path), now=NOW)
    assert ('ramba_fleet_replica_state{replica="a-1-0",state="healthy"} 1'
            in body)
    assert ('ramba_fleet_replica_state{replica="b-2-0",state="dead"} 1'
            in body)
    assert 'ramba_fleet_replicas{state="healthy"} 1' in body
    assert 'ramba_fleet_replicas{state="dead"} 1' in body
    assert 'ramba_fleet_flushes_total{replica="a-1-0"} 4' in body
    assert "ramba_fleet_goodput_flushes_total 4" in body
    assert 'ramba_process_info{' in body and 'pid="1"' in body


def test_write_textfile_atomic(tmp_path):
    spool = tmp_path / "spool"
    spool.mkdir()
    _doc(str(spool), now=time.time())
    out = tmp_path / "fleet.prom"
    fleet.write_textfile(str(out), str(spool))
    assert "ramba_fleet_replicas" in out.read_text()
    assert not list(tmp_path.glob("*.tmp"))


# -- stitched traces ---------------------------------------------------------


def _run_report(*args):
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "trace_report.py"),
         *args],
        capture_output=True, text=True,
    )


def test_trace_stitching_across_replica_dirs_flags_orphans(tmp_path):
    """Two replicas' JSONL files under one directory: the --trace chain
    must stitch spans sharing the trace_id across the process boundary
    and flag the half whose parent span was never collected."""
    (tmp_path / "replica0").mkdir()
    (tmp_path / "replica1").mkdir()
    r0 = [
        {"type": "serve_session", "trace_id": "T1", "span_id": "R",
         "stream": "session:acme", "tenant": "acme", "ts": 1.0, "seq": 1},
        {"type": "flush", "label": "prog_a", "trace_id": "T1",
         "span_id": "S1", "parent_span": "R", "ts": 1.1, "seq": 2,
         "wall_s": 0.01, "cache": "miss"},
    ]
    r1 = [
        # stitched: replica1's flush parented by replica0's session root
        {"type": "flush", "label": "prog_b", "trace_id": "T1",
         "span_id": "S2", "parent_span": "R", "ts": 1.2, "seq": 1,
         "wall_s": 0.02, "cache": "hit"},
        {"type": "degrade", "site": "flush", "action": "rung",
         "from": "fused", "to": "split", "trace_id": "T1",
         "parent_span": "S2", "ts": 1.25, "seq": 2},
        # orphaned half: its parent ran in a process we did not collect
        {"type": "stall", "site": "flush", "waited_s": 1.0,
         "classification": "wedge", "trace_id": "T1",
         "parent_span": "LOST", "ts": 1.4, "seq": 3},
    ]
    for name, evs in (("replica0", r0), ("replica1", r1)):
        with open(tmp_path / name / "trace.jsonl", "w") as f:
            for e in evs:
                f.write(json.dumps(e) + "\n")
    r = _run_report(str(tmp_path), "--trace", "T1")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "2 process(es)" in r.stdout
    assert "replica0/trace" in r.stdout and "replica1/trace" in r.stdout
    # both flush spans in ONE chain, in time order
    assert r.stdout.index("prog_a") < r.stdout.index("prog_b")
    assert "fused->split" in r.stdout
    assert "ORPHANED" in r.stdout
    assert "parent_span=LOST" in r.stdout
    # the merged timeline walks the same directory
    m = _run_report(str(tmp_path), "--merge-ranks")
    assert m.returncode == 0, m.stdout + m.stderr
    assert "2 rank(s)" in m.stdout
