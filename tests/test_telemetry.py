"""Live telemetry plane: exporter, SLO histograms, tracing, flight recorder.

Covers ``ramba_tpu/observe/telemetry.py`` + ``observe/slo.py`` and their
integration seams:

* Prometheus text-format correctness — TYPE lines, rank/tenant labels,
  escaped label values, cumulative histogram buckets that are monotone
  non-decreasing and end at the +Inf total,
* fixed-bucket histogram math (quantile interpolation, saturation at the
  last finite bucket) and the slo_breach latch (one event per episode,
  re-armed on recovery),
* causal trace propagation: serve.Session mints trace_id/root_span, the
  flush span chains to it, the ticket carries it, degrade-rung and
  slow-flush events inside the dispatch scope inherit it — including
  coalesced tickets where N traces share one dispatch batch,
* the HTTP exporter end-to-end on an ephemeral port (scrape, 404, and a
  consistent scrape while flushes run),
* atomic textfile export (no partial file visible),
* flight recorder: exactly-once dump per incident under a seeded
  RAMBA_FAULTS stall, dump contents (incident + identity + ring +
  diagnostics with one capture stamp), RAMBA_FLIGHT_MAX oldest-first
  retention GC,
* the ``ramba_process_info`` identity series and multi-rank textfile
  ``.rank<i>`` suffixing,
* monotonic ``mono`` stamps on events, ``snapshot_ring`` consistency,
  and trace_report.py: ``--trace`` chain reconstruction and merge-ranks
  tolerance of an anchorless rank file.
"""

import glob
import json
import os
import subprocess
import sys
import threading
import time
import urllib.request

import pytest

import jax as _jax
import ramba_tpu as rt
from ramba_tpu import diagnostics, serve
from ramba_tpu.core import fuser
from ramba_tpu.observe import events, registry, slo, telemetry
from ramba_tpu.resilience import faults

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_MULTIPROC = _jax.process_count() > 1

spmd_skip = pytest.mark.skipif(
    _MULTIPROC,
    reason="threaded serving is single-controller; SPMD uses --telemetry-leg",
)


@pytest.fixture(autouse=True)
def _clean_telemetry(monkeypatch):
    """No leaked exporter threads, faults, breach latches, or flight
    budget between tests."""
    monkeypatch.setenv("RAMBA_RETRY_BASE_S", "0.001")
    faults.configure(None)
    slo.reconfigure(objective_ms=-1)
    yield
    telemetry.reset()
    serve.shutdown()
    faults.reset()
    fuser.sync()
    slo.reset()
    slo.reconfigure(objective_ms=-1)


# -- histogram math ----------------------------------------------------------


def test_histogram_buckets_cumulative_monotone():
    h = slo.Histogram()
    for v in (0.0005, 0.003, 0.003, 0.07, 0.2, 42.0):
        h.observe(v)
    cum = h.cumulative()
    counts = [c for _, c in cum]
    assert counts == sorted(counts), "cumulative counts must be monotone"
    assert cum[-1][0] == float("inf")
    assert cum[-1][1] == h.count == 6
    # the 42 s outlier lands in +Inf only
    assert cum[-2][1] == 5


def test_histogram_quantile_interpolation_and_saturation():
    h = slo.Histogram()
    for _ in range(100):
        h.observe(0.004)  # lands in (0.0025, 0.005]
    q = h.quantile(0.5)
    assert 0.0025 <= q <= 0.005
    h2 = slo.Histogram()
    h2.observe(99.0)  # beyond the last finite bucket
    assert h2.quantile(0.99) == slo.BUCKETS_S[-1]
    assert slo.Histogram().quantile(0.5) is None


def test_observe_span_routes_prepare_and_dispatch():
    slo.reset()
    slo.observe_span({"tenant": "t1", "linearize_s": 0.002, "wall_s": 0.03})
    snap = slo.snapshot()["histograms"]
    assert snap["prepare"]["t1"]["count"] == 1
    assert snap["dispatch"]["t1"]["count"] == 1
    assert snap["e2e"] == {}


def test_slo_breach_latch_fires_once_then_rearms():
    slo.reset()
    slo.reconfigure(objective_ms=10.0, min_samples=5)
    breaches = []
    for _ in range(10):  # p95 ~ 50ms >> 10ms objective
        ev = slo.observe_e2e(0.05, tenant="hot", trace_id="tr1")
        if ev is not None:
            breaches.append(ev)
    assert len(breaches) == 1, "latched: one event per episode"
    ev = breaches[0]
    assert ev["type"] == "slo_breach" and ev["tenant"] == "hot"
    assert ev["trace_id"] == "tr1"
    assert ev["p95_ms"] > ev["objective_ms"]
    assert registry.get("serve.tenant.hot.slo_breach") == 1
    assert "hot" in slo.breached_tenants()
    # recovery: flood with fast samples until p95 drops below 0.8x, then
    # breach again -> second event
    for _ in range(2000):
        slo.observe_e2e(0.0001, tenant="hot")
    assert "hot" not in slo.breached_tenants()
    for _ in range(3000):
        ev = slo.observe_e2e(5.0, tenant="hot")
        if ev is not None:
            break
    assert ev is not None, "re-armed latch fires on the second episode"


# -- exporter text format ----------------------------------------------------


def test_render_counter_and_gauge_typing():
    registry.inc("probe.typing_hits", 3)
    registry.gauge("probe.typing_level", 1234)
    body = telemetry.render()
    assert "# TYPE ramba_probe_typing_hits_total counter" in body
    assert 'ramba_probe_typing_hits_total{rank="0"} 3' in body
    # gauge() names are typed gauge, no _total suffix
    assert "# TYPE ramba_probe_typing_level gauge" in body
    assert 'ramba_probe_typing_level{rank="0"} 1234' in body


def test_render_tenant_counters_get_labels():
    registry.inc("serve.tenant.acme.flushes", 7)
    body = telemetry.render()
    assert 'ramba_serve_tenant_flushes_total{rank="0",tenant="acme"} 7' \
        in body


def test_render_histogram_bucket_monotonicity_and_inf():
    slo.reset()
    for v in (0.0004, 0.002, 0.03, 0.4, 20.0):
        slo.observe("e2e", v, tenant="t")
    body = telemetry.render()
    buckets = []
    for line in body.splitlines():
        if line.startswith("ramba_flush_e2e_seconds_bucket") \
                and 'tenant="t"' in line:
            le = line.split('le="')[1].split('"')[0]
            buckets.append((le, float(line.rsplit(" ", 1)[1])))
    assert buckets, "histogram series must render"
    assert buckets[-1][0] == "+Inf"
    counts = [c for _, c in buckets]
    assert counts == sorted(counts)
    assert counts[-1] == 5
    # _sum/_count close the family
    assert "ramba_flush_e2e_seconds_count" in body
    assert "ramba_flush_e2e_seconds_sum" in body


def test_render_every_sample_has_rank_label():
    registry.inc("fuser.flushes")
    for line in telemetry.render().splitlines():
        if line.startswith("#") or not line.strip():
            continue
        assert 'rank="' in line, f"unlabeled sample: {line}"


def test_render_label_escaping():
    registry.inc('serve.tenant.we"ird.flushes')
    body = telemetry.render()
    assert 'tenant="we\\"ird"' in body


# -- http + textfile exporters ----------------------------------------------


def test_http_exporter_serves_metrics_on_ephemeral_port():
    registry.inc("fuser.flushes", 2)
    port = telemetry.start(port=0)
    assert port and port > 0
    assert telemetry.port() == port
    body = urllib.request.urlopen(
        f"http://127.0.0.1:{port}/metrics", timeout=5).read().decode()
    assert "ramba_fuser_flushes_total" in body
    with pytest.raises(urllib.error.HTTPError):
        urllib.request.urlopen(
            f"http://127.0.0.1:{port}/nope", timeout=5)
    telemetry.stop()
    assert not telemetry.started()


@spmd_skip
def test_http_scrape_consistent_during_flushes():
    """A scrape taken while flushes are running parses clean: histogram
    families complete, buckets monotone — the atomic-snapshot guarantee
    the exporter exists to provide."""
    port = telemetry.start(port=0)
    stop = threading.Event()
    errs = []

    def hammer():
        try:
            with serve.Session(tenant="soak") as s:
                i = 0
                while not stop.is_set() and i < 50:
                    a = rt.ones((64,)) + float(i)
                    s.flush(wait=True)
                    a.asarray()
                    i += 1
        except Exception as e:  # noqa: BLE001
            errs.append(repr(e))

    t = threading.Thread(target=hammer)
    t.start()
    try:
        for _ in range(5):
            body = urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=5
            ).read().decode()
            per_series: dict = {}
            for line in body.splitlines():
                if "_bucket{" in line:
                    fam = line.split("{")[0]
                    key = (fam, line.split('tenant="')[1].split('"')[0]
                           if 'tenant="' in line else "")
                    per_series.setdefault(key, []).append(
                        float(line.rsplit(" ", 1)[1]))
            for key, counts in per_series.items():
                assert counts == sorted(counts), (key, counts)
    finally:
        stop.set()
        t.join()
    assert not errs, errs


def test_textfile_export_atomic(tmp_path):
    registry.inc("textfile.probe")
    path = tmp_path / "metrics.prom"
    telemetry.write_textfile(str(path))
    body = path.read_text()
    assert 'ramba_textfile_probe_total{rank="0"} 1' in body
    assert not list(tmp_path.glob("*.tmp")), "no torn temp files left"
    # periodic writer refreshes the file
    registry.inc("textfile.probe", 41)
    telemetry.start(path=str(path), interval_s=0.05)
    want = 'ramba_textfile_probe_total{rank="0"} 42'
    deadline = time.time() + 5
    while time.time() < deadline:
        if want in path.read_text():
            break
        time.sleep(0.02)
    assert want in path.read_text()


def test_process_info_identity_series():
    """The ``*_info`` convention: value 1, identity in the labels — the
    series federated fleet scrapes join/dedup replicas on."""
    body = telemetry.render()
    lines = [ln for ln in body.splitlines()
             if ln.startswith("ramba_process_info{")]
    assert len(lines) == 1, body[:400]
    line = lines[0]
    assert f'pid="{os.getpid()}"' in line
    assert f'schema_version="{diagnostics.SCHEMA_VERSION}"' in line
    assert 'host="' in line and 'start_time="' in line
    assert line.endswith(" 1")


@spmd_skip
def test_textfile_path_multirank_suffix(tmp_path, monkeypatch):
    """Two ranks handed the same textfile path must not clobber each
    other's atomic rewrites: nprocs>1 auto-suffixes ``.rank<i>``."""
    p = str(tmp_path / "m.prom")
    assert telemetry.textfile_path(p) == p  # single process: unchanged
    monkeypatch.setattr(events, "_rank", (1, 2))
    try:
        assert telemetry.textfile_path(p) == f"{p}.rank1"
        telemetry.write_textfile(p)
        assert os.path.exists(f"{p}.rank1") and not os.path.exists(p)
        assert 'rank="1"' in open(f"{p}.rank1").read()
    finally:
        events.invalidate_rank()


# -- trace propagation -------------------------------------------------------


@spmd_skip
def test_session_mints_trace_and_span_chains_to_root():
    with serve.Session(tenant="acme") as s:
        assert s.trace_id and s.root_span
        assert s.stream.trace_id == s.trace_id
        a = rt.ones((32,)) * 2.0
        t = s.flush(wait=True)
        a.asarray()
    assert t.trace_id == s.trace_id
    spans = [e for e in events.ring if e.get("type") == "flush"
             and e.get("trace_id") == s.trace_id]
    assert spans, "flush span carries the session's trace_id"
    span = spans[-1]
    assert span["parent_span"] == s.root_span
    assert span["span_id"] != s.root_span
    sess_evs = [e for e in events.ring if e.get("type") == "serve_session"
                and e.get("trace_id") == s.trace_id]
    assert sess_evs and sess_evs[0]["span_id"] == s.root_span


@spmd_skip
def test_explicit_trace_id_joins_existing_trace():
    with serve.Session(tenant="acme", trace_id="cafe000000000001") as s:
        assert s.trace_id == "cafe000000000001"
        rt.ones((16,)).asarray()


@spmd_skip
def test_child_events_inherit_trace_via_dispatch_scope():
    """Events emitted inside the dispatch (an injected fault's record
    here, same mechanism as degrade/stall/memory) are auto-stamped with
    the flush span's trace context — no per-site wiring."""
    faults.configure("dispatch:delay:ms=5:after=3")
    with serve.Session(tenant="acme") as s:
        for i in range(5):
            a = rt.ones((32,)) + float(i)
            s.flush(wait=True)
            a.asarray()
    seeded = [e for e in events.ring if e.get("type") == "fault"
              and e.get("site") == "dispatch"]
    assert seeded, "the seeded delay must leave its fault record"
    assert seeded[-1].get("trace_id") == s.trace_id
    # parent is the flush span, not the session root
    spans = {e.get("span_id") for e in events.ring
             if e.get("type") == "flush"}
    assert seeded[-1].get("parent_span") in spans


@spmd_skip
def test_coalesced_tickets_keep_distinct_traces():
    """N same-fingerprint flushes coalesce into one dispatch batch; each
    ticket still resolves its own trace_id and the serve_coalesce event
    lists all of them."""
    fuser.flush()
    pipe = serve.CompilePipeline(coalesce=8)
    pipe._ensure_worker = lambda: None  # hold dispatch: force coalescing
    sessions, tickets, arrs = [], [], []
    try:
        for i in range(3):
            s = serve.Session(tenant=f"t{i}", pipeline=pipe)
            tok = fuser.activate_stream(s.stream)
            try:
                arrs.append(rt.arange(64) * 2.0)  # same fingerprint each
                tickets.append(s.flush())
            finally:
                fuser.deactivate_stream(tok)
            sessions.append(s)
        group = pipe.queue.pop_group(
            8, fingerprint_of=lambda t: t.work.fingerprint, timeout=0)
        assert len(group) >= 2, "same-fingerprint tickets must coalesce"
        pipe._dispatch_group(group)
        ids = {t.trace_id for t in group}
        assert len(ids) == len(group), "each ticket keeps its own trace"
        ce = [e for e in events.ring if e.get("type") == "serve_coalesce"]
        assert ce and set(ce[-1]["trace_ids"]) == ids
        for t in group:
            span = t.work.span
            assert span.get("trace_id") == t.trace_id
    finally:
        for s in sessions:
            s.close(drain=False)
        pipe.stop()


@spmd_skip
def test_e2e_slo_observed_per_ticket():
    slo.reset()
    with serve.Session(tenant="lat") as s:
        arrs = []
        for i in range(3):
            arrs.append(rt.ones((16,)) + float(i))
            s.flush(wait=True)
    rep = serve.tenant_report()
    assert rep["lat"]["e2e_samples"] >= 3
    assert rep["lat"]["e2e_p95_ms"] is not None
    assert rep["lat"]["e2e_p50_ms"] <= rep["lat"]["e2e_p99_ms"]


# -- flight recorder ---------------------------------------------------------


@spmd_skip
def test_flight_recorder_exactly_once_per_incident(tmp_path, monkeypatch):
    """A seeded one-shot fatal fault produces exactly ONE incident
    event (the quarantine's ``flush_error``) and exactly ONE dump — the
    recorder maps incidents 1:1 to files."""
    fd = tmp_path / "flight"
    monkeypatch.setenv("RAMBA_FLIGHT_DIR", str(fd))
    telemetry.flight_reset()
    fuser.flush()
    for i in range(3):
        (rt.ones((32,)) + float(i)).asarray()
    fuser._compile_cache.clear()
    with faults.inject("compile", "once", kind="fatal"):
        with pytest.raises(faults.InjectedFault):
            (rt.ones((32,)) + 7.0).asarray()
    for i in range(2):
        (rt.ones((32,)) + float(i)).asarray()
    dumps = sorted(glob.glob(str(fd / "flight_*.json")))
    assert len(dumps) == 1, dumps
    rec = json.loads(open(dumps[0]).read())
    assert rec["incident"]["type"] == "flush_error"
    assert rec["events"], "ring included"
    assert "captured_at" in rec["diagnostics"]
    assert rec["identity"]["pid"] == os.getpid()
    assert rec["identity"]["schema_version"] == diagnostics.SCHEMA_VERSION
    assert os.path.basename(dumps[0]).startswith(
        f"flight_{rec['incident']['seq']:06d}_")
    assert registry.get("telemetry.flight_dumps") == 1


@spmd_skip
def test_flight_recorder_cap_is_retention_gc(tmp_path, monkeypatch):
    """RAMBA_FLIGHT_MAX is disk retention, not an incident budget: every
    incident dumps, then the OLDEST of this process's files are evicted
    past the cap — a week-long soak keeps the freshest incidents instead
    of going blind after the first N."""
    monkeypatch.setenv("RAMBA_FLIGHT_DIR", str(tmp_path))
    monkeypatch.setenv("RAMBA_FLIGHT_MAX", "2")
    telemetry.flight_reset()
    gc0 = registry.get("telemetry.flight_gc")
    dumps0 = registry.get("telemetry.flight_dumps")
    for i in range(5):
        events.emit({"type": "slo_breach", "tenant": "x", "n": i})
    dumps = sorted(glob.glob(str(tmp_path / "flight_*.json")))
    assert len(dumps) == 2
    assert registry.get("telemetry.flight_dumps") - dumps0 == 5
    assert registry.get("telemetry.flight_gc") - gc0 == 3
    # the two survivors are the NEWEST incidents (oldest-first eviction)
    ns = sorted(json.loads(open(p).read())["incident"]["n"] for p in dumps)
    assert ns == [3, 4]


def test_flight_recorder_off_without_dir(tmp_path):
    assert "RAMBA_FLIGHT_DIR" not in os.environ
    events.emit({"type": "slo_breach", "tenant": "x"})
    assert telemetry.dump_flight({"type": "stall", "seq": 1}) is None


def test_stall_event_is_incident():
    assert telemetry.is_incident({"type": "stall", "site": "dispatch"})
    assert telemetry.is_incident({"type": "flush_error"})
    assert telemetry.is_incident({"type": "memory", "action": "oom_evict"})
    assert not telemetry.is_incident({"type": "memory", "action": "admit"})
    assert not telemetry.is_incident({"type": "flush"})


# -- events: mono stamps, ring snapshot --------------------------------------


def test_events_carry_monotonic_stamp():
    e = events.emit({"type": "bench_tick"})
    assert isinstance(e["mono"], float) and isinstance(e["ts"], float)
    e2 = events.emit({"type": "bench_tick"})
    assert e2["mono"] >= e["mono"]


def test_snapshot_ring_is_a_copy():
    events.emit({"type": "bench_tick"})
    snap = events.snapshot_ring()
    n = len(snap)
    events.emit({"type": "bench_tick"})
    assert len(snap) == n


def test_diagnostics_snapshot_stamped_once():
    snap = diagnostics.snapshot()
    assert isinstance(snap["captured_at"], float)
    assert isinstance(snap["captured_mono"], float)
    json.dumps(snap, default=str)  # serializable whole


# -- trace_report integration ------------------------------------------------


def _run_report(*args):
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "trace_report.py"),
         *args],
        capture_output=True, text=True,
    )


def test_trace_report_trace_chain(tmp_path):
    path = tmp_path / "t.jsonl"
    evs = [
        {"type": "serve_session", "trace_id": "T1", "span_id": "R",
         "stream": "session:acme", "tenant": "acme", "ts": 1.0, "seq": 1},
        {"type": "flush", "label": "prog_a", "trace_id": "T1",
         "span_id": "S1", "parent_span": "R", "ts": 1.1, "seq": 2,
         "wall_s": 0.01, "cache": "miss", "queue_s": 0.002},
        {"type": "degrade", "site": "flush", "action": "rung",
         "from": "fused", "to": "split", "trace_id": "T1",
         "parent_span": "S1", "ts": 1.15, "seq": 3},
        {"type": "slo_breach", "tenant": "acme", "p95_ms": 50.0,
         "objective_ms": 10.0, "samples": 20, "trace_id": "T1",
         "parent_span": "R", "ts": 1.2, "seq": 4},
        # unrelated noise that must NOT appear
        {"type": "flush", "label": "prog_zzz", "trace_id": "T2",
         "span_id": "S9", "ts": 1.3, "seq": 5, "wall_s": 0.01},
    ]
    with open(path, "w") as f:
        for e in evs:
            f.write(json.dumps(e) + "\n")
    r = _run_report(str(path), "--trace", "T1")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "trace T1: 4 events" in r.stdout
    assert "session" in r.stdout and "tenant=acme" in r.stdout
    assert "flush #0" in r.stdout and "prog_a" in r.stdout
    assert "fused->split" in r.stdout
    assert "SLO-BREACH" in r.stdout
    assert "prog_zzz" not in r.stdout
    # unknown id: nonzero exit
    assert _run_report(str(path), "--trace", "NOPE").returncode == 1


def test_merge_ranks_tolerates_anchorless_rank(tmp_path):
    """A rank file with no health anchor (crashed pre-init) must get
    skew 0 and a visible warning — NOT be aligned off its first event."""
    base = tmp_path / "t.jsonl"
    r0 = [
        {"type": "health", "source": "distributed_init", "outcome": "ok",
         "ts": 100.0, "seq": 1, "rank": 0},
        {"type": "flush", "label": "prog_a", "ts": 100.1, "seq": 2,
         "rank": 0, "wall_s": 0.01, "cache": "miss"},
    ]
    r1 = [  # no health event at all
        {"type": "flush", "label": "prog_a", "ts": 500.0, "seq": 1,
         "rank": 1, "wall_s": 0.01, "cache": "miss", "degraded": "chunked"},
    ]
    for i, evs in enumerate((r0, r1)):
        with open(f"{base}.rank{i}", "w") as f:
            for e in evs:
                f.write(json.dumps(e) + "\n")
    r = _run_report(str(base), "--merge-ranks")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "UNANCHORED" in r.stdout
    assert "r1=+0.0000s" in r.stdout


def test_merge_ranks_uses_mono_for_alignment(tmp_path):
    """When anchor and events carry ``mono``, a wall-clock step between
    bring-up and later events cannot warp the merged ordering."""
    base = tmp_path / "m.jsonl"
    r0 = [
        {"type": "health", "source": "distributed_init", "outcome": "ok",
         "ts": 100.0, "mono": 10.0, "seq": 1, "rank": 0},
        # wall clock stepped +1000s mid-run; mono says +0.5s after anchor
        {"type": "flush", "label": "prog_a", "ts": 1100.5, "mono": 10.5,
         "seq": 2, "rank": 0, "wall_s": 0.01, "degraded": "eager"},
    ]
    with open(f"{base}.rank0", "w") as f:
        for e in r0:
            f.write(json.dumps(e) + "\n")
    r = _run_report(str(base), "--merge-ranks")
    assert r.returncode == 0, r.stdout + r.stderr
    # adjusted offset is mono-derived (+0.5s), not the wall-clock +1000s
    assert "+   0.500s" in r.stdout


def test_heartbeat_gap_math_uses_mono(tmp_path):
    """An NTP step between beats must not fabricate a gap when mono
    stamps are present."""
    path = tmp_path / "hb.jsonl"
    evs = [
        {"type": "heartbeat", "n": 1, "interval_s": 1.0,
         "ts": 100.0, "mono": 50.0, "seq": 1},
        # wall clock jumped 500 s; mono shows a healthy 1 s beat
        {"type": "heartbeat", "n": 2, "interval_s": 1.0,
         "ts": 600.0, "mono": 51.0, "seq": 2},
    ]
    with open(path, "w") as f:
        for e in evs:
            f.write(json.dumps(e) + "\n")
    r = _run_report(str(path))
    assert r.returncode == 0, r.stdout + r.stderr
    assert "GAP" not in r.stdout
    assert "no gaps over 2x interval" in r.stdout


# -- registry atomicity ------------------------------------------------------


def test_gauge_names_tracked_and_reset():
    registry.gauge("memory.live_bytes", 5)
    assert "memory.live_bytes" in registry.gauge_names()
    registry.inc("fuser.flushes")
    assert "fuser.flushes" not in registry.gauge_names()
    registry.reset_counters()
    assert "memory.live_bytes" not in registry.gauge_names()
