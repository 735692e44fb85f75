#!/usr/bin/env python
"""Summarize a RAMBA_TRACE JSONL file.

Usage:
    python scripts/trace_report.py /tmp/t.jsonl [more.jsonl ...]

Accepts the path passed to RAMBA_TRACE directly; when the run was
multi-controller the per-rank files (``<path>.rank0``, ``<path>.rank1``, ...)
are discovered automatically.  Stdlib only — runs anywhere the trace file
can be copied to, no jax required.

Prints, per input:
  * health records (platform, device count, init time, fallback reasons),
  * flush totals: count, wall time, compile vs execute split, cache hit
    rate, instructions, bytes in (leaves) and out (roots),
  * rewrite-rule fire totals,
  * the degradation timeline (injected faults, retries, ladder rung
    transitions fused→split→chunked→eager→host, recoveries — newest
    last),
  * the memory timeline (admission checks, watermark crossings, spills,
    restores, oom evictions) with a peak-live column in the flush
    totals,
  * the elastic lifecycle timeline (watchdog stalls, drains,
    checkpoints, resumes, heartbeat misses) plus a per-rank heartbeat
    liveness summary that flags gaps wider than 2x the beacon interval
    — the offline signature of a wedged rank, and
  * the top programs by cumulative wall time.

``--merge-ranks`` switches to a cross-rank view: per-rank files are
aligned by their distributed bring-up anchor (clock skew subtracted),
interleaved into one timeline, and the per-rank flush streams are
compared in lockstep order to flag rank divergence (e.g. one rank
degraded to ``chunked`` while another stayed ``fused``, or the two
stamped different stage signatures for the same flush index).

``--attrib`` switches to the stage-waterfall view of the attribution
plane (observe/attrib.py): per-program stage decomposition of flush
wall time (prepare / verify / queue_wait / coalesce / compile / admit /
dispatch / device_execute / write_back), recent per-flush waterfalls,
and the top programs by unattributed gap — the wall-clock the stage
ledger could NOT explain, which is where to dig first.
"""

from __future__ import annotations

import argparse
import glob
import json
import sys
from collections import defaultdict

# canonical stage order (mirrors ramba_tpu.observe.attrib.STAGES —
# duplicated so this script stays stdlib-only / copyable off-host)
STAGE_ORDER = ("trace", "prepare", "verify", "queue_wait", "coalesce",
               "compile",
               "admit", "dispatch", "device_execute", "write_back")


def _stage_sig(flush: dict) -> str:
    """Order-stable stage signature of one flush span ('' when the span
    predates the stage ledger)."""
    st = flush.get("stages") or {}
    return ",".join(k for k in STAGE_ORDER if k in st)


def _discover(path: str) -> list:
    """The file itself, or its .rank* siblings (multi-controller runs).
    A DIRECTORY discovers every trace JSONL beneath it, one stream per
    process that wrote its own trace dir/file."""
    import os

    if os.path.isdir(path):
        return _walk_trace_dir(path)
    files = []
    if os.path.exists(path):
        files.append(path)
    files += sorted(glob.glob(glob.escape(path) + ".rank*"))
    return files


def _walk_trace_dir(root: str) -> list:
    """Every ``*.jsonl`` / ``*.jsonl.rank<i>`` file under ``root``,
    sorted — one entry per per-process trace stream."""
    import os

    out = []
    for dirpath, _dirs, names in os.walk(root):
        for name in sorted(names):
            if ".jsonl" in name and not name.endswith(".tmp"):
                out.append(os.path.join(dirpath, name))
    return sorted(out)


def _rname(r) -> str:
    """Display name of one stream key: SPMD ranks are ints (``r0``),
    the processes of a directory are path-derived string labels used
    verbatim."""
    return f"r{r}" if isinstance(r, int) else str(r)


def _load_streams(path: str):
    """``{stream_key: [events]}`` for one input.  A plain file keys its
    ``.rank<i>`` siblings by integer rank; a directory keys each
    discovered file by its relative path (the process label), so two
    processes that each called themselves rank 0 stay distinct streams.
    Returns None when nothing was found."""
    import os

    if os.path.isdir(path):
        streams: dict = {}
        for f in _walk_trace_dir(path):
            label = os.path.relpath(f, path).replace(os.sep, "/")
            label = label.replace(".jsonl", "") or label
            streams.setdefault(label, []).extend(_load(f))
        return streams or None
    found = _discover(path)
    if not found:
        return None
    streams = {}
    for f in found:
        evs = _load(f)
        streams.setdefault(_file_rank(f, evs), []).extend(evs)
    return streams


def _load(path: str) -> list:
    events = []
    with open(path) as f:
        for ln, line in enumerate(f, 1):
            line = line.strip()
            if not line:
                continue
            try:
                events.append(json.loads(line))
            except json.JSONDecodeError as e:
                print(f"{path}:{ln}: unparseable line ({e})", file=sys.stderr)
    return events


def _fmt_bytes(n: float) -> str:
    for unit in ("B", "KB", "MB", "GB", "TB"):
        if abs(n) < 1024 or unit == "TB":
            return f"{n:,.1f} {unit}" if unit != "B" else f"{int(n)} B"
        n /= 1024
    return f"{n:,.1f} TB"


def report(path: str, events: list, top: int = 10, file=None) -> None:
    file = file or sys.stdout
    print(f"== {path} ({len(events)} events) ==", file=file)

    health = [e for e in events if e.get("type") == "health"]
    for h in health:
        bits = [f"{k}={h[k]}" for k in
                ("platform", "device_count", "outcome", "init_seconds",
                 "source") if k in h]
        print("health: " + " ".join(bits), file=file)
        if h.get("error"):
            print(f"  error: {h['error']}", file=file)

    _degradation_timeline(events, file=file)
    _memory_timeline(events, file=file)
    _lifecycle_timeline(events, file=file)
    _findings_summary(events, file=file)

    flushes = [e for e in events if e.get("type") == "flush"]
    if not flushes:
        print("no flush spans", file=file)
        return

    wall = sum(f.get("wall_s", 0.0) for f in flushes)
    compile_s = sum(f.get("compile_s", 0.0) for f in flushes)
    execute_s = sum(f.get("execute_s", 0.0) for f in flushes)
    linearize_s = sum(f.get("linearize_s", 0.0) for f in flushes)
    hits = sum(1 for f in flushes if f.get("cache") == "hit")
    memo_hits = sum(1 for f in flushes if f.get("cache") == "memo")
    instrs = sum(f.get("instrs", 0) for f in flushes)
    leaf_b = sum(f.get("leaf_bytes", 0) for f in flushes)
    out_b = sum(f.get("out_bytes", 0) for f in flushes)
    donated = sum(f.get("donated", 0) for f in flushes)
    segs = sum(f.get("segments", 0) for f in flushes)

    print(
        f"flushes: {len(flushes)}  wall {wall:.4f}s  "
        f"(linearize {linearize_s:.4f}s, compile {compile_s:.4f}s, "
        f"execute-cached {execute_s:.4f}s)",
        file=file,
    )
    line = (
        f"cache: {hits}/{len(flushes)} hit "
        f"({100.0 * hits / len(flushes):.0f}%)  "
        f"instrs: {instrs}  segments: {segs}  donated bufs: {donated}"
    )
    if memo_hits:
        line += f"  memo hits: {memo_hits}"
    print(line, file=file)
    # compile-class + warm-pool attribution (PR-14): `compile` events are
    # source-tagged by the ledger; bucketed spans carry compile_class
    compiles = [e for e in events if e.get("type") == "compile"]
    bucketed = [f for f in flushes if f.get("compile_class")]
    if compiles or bucketed:
        warm = [e for e in compiles if e.get("source") == "warm"]
        warm_s = sum(e.get("seconds", 0.0) for e in warm)
        all_s = sum(e.get("seconds", 0.0) for e in compiles)
        line = (f"compiles: {len(compiles)} "
                f"({len(warm)} warm {warm_s:.4f}s / "
                f"{len(compiles) - len(warm)} demand "
                f"{all_s - warm_s:.4f}s)")
        if bucketed:
            waste = sum(f.get("pad_waste_bytes", 0) for f in bucketed)
            classes = sorted({tuple(f["compile_class"]) for f in bucketed})
            line += (f"  bucketed flushes: {len(bucketed)}"
                     f" classes: {len(classes)}"
                     f" pad waste: {_fmt_bytes(waste)}")
        print(line, file=file)
    # plan-certificate cache (PR-18): hits skip the prepare-side
    # analysis pipeline; stale events name the invalidation causes
    plan_hits = sum(1 for f in flushes if f.get("plan_cache"))
    plan_stale = [e for e in events if e.get("type") == "plan_stale"]
    if plan_hits or plan_stale:
        line = (f"plan cache: {plan_hits}/{len(flushes)} flushes on the "
                f"fast path ({100.0 * plan_hits / len(flushes):.0f}%)")
        if plan_stale:
            causes = defaultdict(int)
            forged = 0
            for e in plan_stale:
                if e.get("forged"):
                    forged += 1
                for c in e.get("causes", ()):
                    causes[str(c)] += 1
            cs = "  ".join(f"{c}={n}"
                           for c, n in sorted(causes.items()))
            line += f"  stale: {len(plan_stale)}"
            if forged:
                line += f" (forged: {forged})"
            if cs:
                line += f" causes: {cs}"
        print(line, file=file)
    cse = [e for e in events if e.get("type") == "cse_merge"]
    if memo_hits or cse:
        rejected = sum(1 for e in events
                       if e.get("type") == "memo_insert_rejected")
        line = (f"result memo: {memo_hits}/{len(flushes)} flushes served "
                f"from cache ({100.0 * memo_hits / len(flushes):.0f}%)")
        if cse:
            line += f"  cse merges: {len(cse)}"
        if rejected:
            line += f"  uncertified inserts rejected: {rejected}"
        print(line, file=file)
    peak_live = max((f.get("mem_live_bytes", 0) or 0) for f in flushes)
    peak_est = max((f.get("mem_peak_est", 0) or 0) for f in flushes)
    line = f"bytes: in {_fmt_bytes(leaf_b)}  out {_fmt_bytes(out_b)}"
    if peak_live or peak_est:
        line += f"  peak live {_fmt_bytes(peak_live)}"
        if peak_est:
            line += f"  peak est {_fmt_bytes(peak_est)}"
    print(line, file=file)

    fires = defaultdict(int)
    for f in flushes:
        for rule, n in (f.get("rewrite_fires") or {}).items():
            fires[rule] += n
    if fires:
        print("rewrite fires: " + "  ".join(
            f"{r}={n}" for r, n in sorted(fires.items())), file=file)

    # serving runs tag spans with the tenant; the table grows a tenant
    # column (and a per-tenant totals block) only when one is present, so
    # single-stream traces render exactly as before
    tenanted = any("tenant" in f for f in flushes)
    if tenanted:
        per_tenant = defaultdict(lambda: [0.0, 0, 0])  # [wall, count, queued]
        for f in flushes:
            ent = per_tenant[f.get("tenant", "-")]
            ent[0] += f.get("wall_s", 0.0)
            ent[1] += 1
            ent[2] += 1 if "queue_s" in f else 0
        coalesced = [e for e in events if e.get("type") == "serve_coalesce"]
        print("per-tenant flush totals:", file=file)
        for t, (w, cnt, quo) in sorted(per_tenant.items(),
                                       key=lambda kv: -kv[1][0]):
            print(f"  {t:<18s} {w:10.4f}s  x{cnt:<5d} async {quo}",
                  file=file)
        if coalesced:
            n = sum(e.get("n", 0) for e in coalesced)
            print(f"coalesced batches: {len(coalesced)} "
                  f"({n} flushes merged)", file=file)

    # label -> [wall, count, compile, tenants]
    per = defaultdict(lambda: [0.0, 0, 0.0, set()])
    for f in flushes:
        ent = per[f.get("label", "?")]
        ent[0] += f.get("wall_s", 0.0)
        ent[1] += 1
        ent[2] += f.get("compile_s", 0.0)
        if "tenant" in f:
            ent[3].add(f["tenant"])
    print(f"top {min(top, len(per))} programs by wall time:", file=file)
    ranked = sorted(per.items(), key=lambda kv: -kv[1][0])[:top]
    for label, (w, cnt, comp, tenants) in ranked:
        line = f"  {label:<18s} {w:10.4f}s  x{cnt:<5d} compile {comp:.4f}s"
        if tenanted:
            line += f"  tenant {','.join(sorted(tenants)) or '-'}"
        print(line, file=file)


def _findings_summary(events: list, file=None) -> None:
    """Static-analysis findings (RAMBA_VERIFY / ramba-lint) by rule and
    severity, with a sample message per bucket."""
    file = file or sys.stdout
    findings = [e for e in events if e.get("type") == "finding"]
    if not findings:
        return
    per = defaultdict(lambda: [0, ""])  # (rule, severity) -> [count, sample]
    for e in findings:
        ent = per[(e.get("rule", "?"), e.get("severity", "?"))]
        ent[0] += 1
        if not ent[1]:
            ent[1] = str(e.get("message", ""))[:60]
    print(f"verifier findings ({len(findings)}):", file=file)
    print(f"  {'rule':<20s} {'severity':<9s} {'count':>5s}  sample",
          file=file)
    sev_rank = {"error": 0, "warning": 1, "info": 2}
    for (rule, sev), (n, sample) in sorted(
        per.items(), key=lambda kv: (sev_rank.get(kv[0][1], 3), kv[0][0])
    ):
        print(f"  {rule:<20s} {sev:<9s} {n:>5d}  {sample}", file=file)


def _degradation_timeline(events: list, file=None, cap: int = 50) -> None:
    """Chronological fault/retry/degradation lines, timestamped relative to
    the first event in the trace."""
    file = file or sys.stdout
    degr = [e for e in events if e.get("type") in ("fault", "degrade")]
    if not degr:
        return
    stamps = [e["ts"] for e in events if isinstance(e.get("ts"), (int, float))]
    t0 = min(stamps) if stamps else None
    print(f"degradation timeline ({len(degr)} events):", file=file)
    for e in degr[:cap]:
        rel = (f"+{e['ts'] - t0:8.3f}s"
               if t0 is not None and isinstance(e.get("ts"), (int, float))
               else " " * 10)
        if e["type"] == "fault":
            line = (f"fault     {e.get('site', '?')} "
                    f"call={e.get('call', '?')} mode={e.get('mode', '?')}")
        else:
            action = e.get("action", "?")
            site = e.get("site", "?")
            if action == "retry":
                line = (f"retry     {site} attempt={e.get('attempt', '?')} "
                        f"delay={e.get('delay_s', 0)}s")
            elif action == "exhausted":
                line = (f"exhausted {site} "
                        f"attempts={e.get('attempts', '?')}")
            elif action == "rung":
                line = (f"degrade   {site} "
                        f"{e.get('from', '?')} -> {e.get('to', '?')}")
            elif action == "recovered":
                line = f"recovered {site} rung={e.get('rung', '?')}"
            else:
                line = f"{action} {site}"
            if e.get("error"):
                line += f"  ({str(e['error'])[:80]})"
        print(f"  {rel}  {line}", file=file)
    if len(degr) > cap:
        print(f"  ... and {len(degr) - cap} more", file=file)
    retries = sum(1 for e in degr
                  if e.get("type") == "degrade" and e.get("action") == "retry")
    rungs = sum(1 for e in degr
                if e.get("type") == "degrade" and e.get("action") == "rung")
    faults = sum(1 for e in degr if e.get("type") == "fault")
    print(f"degradation totals: faults={faults} retries={retries} "
          f"rung-steps={rungs}", file=file)


def _memory_timeline(events: list, file=None, cap: int = 50) -> None:
    """Chronological memory-governor lines (admission checks that crossed
    the watermark, spills, restores, oom evictions), timestamped relative
    to the first event in the trace.  Plain in-budget admits are elided —
    they would drown the interesting lines one-per-flush."""
    file = file or sys.stdout
    mem = [e for e in events if e.get("type") == "memory"]
    if not mem:
        return
    shown = [e for e in mem if not (e.get("action") == "admit" and e.get("ok"))]
    stamps = [e["ts"] for e in events if isinstance(e.get("ts"), (int, float))]
    t0 = min(stamps) if stamps else None
    admits = sum(1 for e in mem if e.get("action") == "admit")
    print(f"memory timeline ({len(mem)} events, {admits} admission checks):",
          file=file)
    for e in shown[:cap]:
        rel = (f"+{e['ts'] - t0:8.3f}s"
               if t0 is not None and isinstance(e.get("ts"), (int, float))
               else " " * 10)
        action = e.get("action", "?")
        if action == "admit":
            line = (f"admit     projected="
                    f"{_fmt_bytes(e.get('projected_bytes', 0))} "
                    f"est={_fmt_bytes(e.get('est_bytes', 0))} over budget")
        elif action == "watermark":
            line = (f"watermark over={_fmt_bytes(e.get('over_bytes', 0))} "
                    f"wm={_fmt_bytes(e.get('watermark_bytes', 0))}")
        elif action == "spill":
            line = (f"spill     {_fmt_bytes(e.get('bytes', 0))} "
                    f"-> host (live {_fmt_bytes(e.get('live_bytes', 0))})")
        elif action == "restore":
            line = (f"restore   {_fmt_bytes(e.get('bytes', 0))} "
                    f"-> device (live {_fmt_bytes(e.get('live_bytes', 0))})")
        elif action == "oom_evict":
            line = (f"oom-evict need={_fmt_bytes(e.get('need_bytes', 0))} "
                    f"freed={_fmt_bytes(e.get('freed_bytes', 0))}")
        elif action == "reject":
            line = (f"reject    over={_fmt_bytes(e.get('over_bytes', 0))} "
                    f"freed={_fmt_bytes(e.get('freed_bytes', 0))} "
                    f"route={e.get('route', '?')}")
        else:
            line = action
        print(f"  {rel}  {line}", file=file)
    if len(shown) > cap:
        print(f"  ... and {len(shown) - cap} more", file=file)
    spills = sum(1 for e in mem if e.get("action") == "spill")
    restores = sum(1 for e in mem if e.get("action") == "restore")
    rejects = sum(1 for e in mem if e.get("action") == "reject")
    print(f"memory totals: spills={spills} restores={restores} "
          f"rejects={rejects}", file=file)


def _lifecycle_timeline(events: list, file=None, cap: int = 40) -> None:
    """Elastic job-lifecycle lines (watchdog stalls, drain / checkpoint /
    resume phases, heartbeat misses) plus a heartbeat liveness summary.

    Heartbeats themselves are volume (one per RAMBA_HEARTBEAT_S), so
    they are rolled up rather than listed: beat count, observed beacon
    span, and every inter-beat gap wider than 2x the interval — a rank
    that went silent mid-run shows up here as a flagged gap even though
    no single event says so."""
    file = file or sys.stdout
    beats = [e for e in events if e.get("type") == "heartbeat"]
    life = [e for e in events if e.get("type") in ("stall", "lifecycle")]
    if not beats and not life:
        return
    stamps = [e["ts"] for e in events if isinstance(e.get("ts"), (int, float))]
    t0 = min(stamps) if stamps else None

    def rel(e):
        return (f"+{e['ts'] - t0:8.3f}s"
                if t0 is not None and isinstance(e.get("ts"), (int, float))
                else " " * 10)

    if life:
        print(f"lifecycle timeline ({len(life)} events):", file=file)
        for e in life[:cap]:
            if e["type"] == "stall":
                line = (f"STALL     {e.get('site', '?')} "
                        f"waited={e.get('waited_s', '?')}s "
                        f"deadline={e.get('deadline_s', '?')}s "
                        f"class={e.get('classification', '?')}")
            else:
                phase = e.get("phase", "?")
                line = f"{phase:<9s}"
                for k in ("step", "streams", "age_s", "limit_s",
                          "deleted_steps", "from_processes", "to_processes",
                          "freed_bytes", "wall_s"):
                    if e.get(k) is not None:
                        line += f" {k}={e[k]}"
            print(f"  {rel(e)}  {line}", file=file)
        if len(life) > cap:
            print(f"  ... and {len(life) - cap} more", file=file)
        stalls = sum(1 for e in life if e["type"] == "stall")
        misses = sum(1 for e in life if e.get("phase") == "heartbeat_missed")
        saves = sum(1 for e in life if e.get("phase") == "checkpoint_saved")
        resumes = sum(1 for e in life if e.get("phase") == "resume_complete")
        print(f"lifecycle totals: stalls={stalls} heartbeat-misses={misses} "
              f"checkpoints={saves} resumes={resumes}", file=file)

    if beats:
        interval = beats[-1].get("interval_s") or 0.0
        # Inter-beat gaps use the monotonic clock when every beat carries
        # one (events gained ``mono`` alongside ``ts``): an NTP step
        # between two beats would otherwise fabricate — or hide — a gap.
        # Wall clock only for older traces.
        if all(isinstance(e.get("mono"), (int, float)) for e in beats):
            stamped = [(e["mono"], e.get("ts")) for e in beats]
        else:
            stamped = [(e["ts"], e["ts"]) for e in beats
                       if isinstance(e.get("ts"), (int, float))]
        span = (stamped[-1][0] - stamped[0][0]) if len(stamped) > 1 else 0.0
        print(f"heartbeat: {len(beats)} beats over {span:.3f}s "
              f"(interval {interval}s)", file=file)
        limit = 2.0 * interval if interval else None
        flagged = 0
        for (a, a_ts), (b, _b_ts) in zip(stamped, stamped[1:]):
            gap = b - a
            if limit is not None and gap > limit:
                flagged += 1
                r = (f"+{a_ts - t0:8.3f}s"
                     if t0 is not None and isinstance(a_ts, (int, float))
                     else " " * 10)
                print(f"  {r}  GAP {gap:.3f}s > 2x interval "
                      f"({limit:.3f}s) — rank silent", file=file)
        if limit is not None and not flagged:
            print(f"  no gaps over 2x interval ({limit:.3f}s)", file=file)


def _file_rank(path: str, events: list) -> int:
    """Rank of one trace file: the ``.rank<i>`` filename suffix wins,
    else the first event carrying a ``rank`` field, else 0."""
    import re

    m = re.search(r"\.rank(\d+)$", path)
    if m:
        return int(m.group(1))
    for e in events:
        r = e.get("rank")
        if isinstance(r, int):
            return r
    return 0


def _anchor(events: list):
    """Per-rank alignment anchor ``(ts, mono)``: the distributed bring-up
    health record is the one event every rank emits at (nearly) the same
    real moment — the group barrier inside jax.distributed.initialize.
    Fallback: any health record (mesh bring-up).  Returns None when the
    rank has NO health event at all; the caller must then treat the rank
    as unanchored (skew 0) rather than misalign it off its first event,
    whose real-world moment is arbitrary.  ``mono`` rides along so later
    per-rank deltas can use the monotonic clock (immune to NTP steps);
    it is None for traces written before events carried ``mono``."""
    for pred in (
        lambda e: e.get("type") == "health"
        and e.get("source") == "distributed_init",
        lambda e: e.get("type") == "health",
    ):
        for e in events:
            if pred(e) and isinstance(e.get("ts"), (int, float)):
                mono = e.get("mono")
                return (e["ts"],
                        mono if isinstance(mono, (int, float)) else None)
    return None


def _anchor_ts(events: list):
    """Back-compat shim: the wall-clock half of :func:`_anchor`."""
    a = _anchor(events)
    return a[0] if a is not None else None


def _merge_line(e: dict) -> str:
    """One compact description for the merged timeline."""
    t = e.get("type", "?")
    if t == "health":
        return (f"health    {e.get('source', '?')}"
                f" outcome={e.get('outcome', '?')}")
    if t == "fault":
        return (f"fault     {e.get('site', '?')} mode={e.get('mode', '?')}"
                f" call={e.get('call', '?')}")
    if t == "degrade":
        return (f"degrade   {e.get('site', '?')} {e.get('action', '?')}"
                f" {e.get('from', '')}->{e.get('to', '')}")
    if t == "cache_evict":
        return f"cache_evict {e.get('key', '?')}"
    if t == "flush_error":
        line = f"flush_err {e.get('label', '?')}"
        if e.get("tenant"):
            line += f" tenant={e['tenant']}"
        return line + f" {str(e.get('error', ''))[:60]}"
    if t == "serve_coalesce":
        return (f"coalesce  fp={e.get('fingerprint', '?')}"
                f" n={e.get('n', '?')}"
                f" tenants={','.join(e.get('tenants') or [])}")
    if t == "serve_session":
        line = f"session   stream={e.get('stream', '?')}"
        if e.get("tenant"):
            line += f" tenant={e['tenant']}"
        return line
    if t == "slo_breach":
        return (f"SLO-BREACH tenant={e.get('tenant', '-')}"
                f" p95={e.get('p95_ms', '?')}ms"
                f" objective={e.get('objective_ms', '?')}ms"
                f" samples={e.get('samples', '?')}")
    if t == "program":
        instrs = e.get("instrs")
        n = len(instrs) if isinstance(instrs, list) else instrs
        return f"program   {e.get('label', '?')} instrs={n}"
    if t == "plan_stale":
        causes = ",".join(e.get("causes") or []) or "?"
        tag = " FORGED" if e.get("forged") else ""
        return (f"plan_stale {e.get('label', '?')}"
                f" causes={causes}{tag}")
    if t == "plan_divergence":
        return (f"plan_diverge proposed={e.get('proposed', '?')}"
                f" agreed={e.get('agreed', '?')} (cache cleared)")
    if t == "memory":
        return (f"memory    {e.get('action', '?')}"
                f" {_fmt_bytes(e.get('bytes', e.get('over_bytes', 0)) or 0)}")
    if t == "stall":
        return (f"STALL     {e.get('site', '?')}"
                f" waited={e.get('waited_s', '?')}s"
                f" class={e.get('classification', '?')}")
    if t == "coherence":
        line = (f"coherence {e.get('site', '?')}"
                f" epoch={e.get('epoch', '?')}"
                f" {e.get('proposal', '?')}->{e.get('decision', '?')}")
        if e.get("outcome") == "local":
            line += " LOCAL-FALLBACK"
        return line
    if t == "lifecycle":
        line = f"lifecycle {e.get('phase', '?')}"
        if e.get("step") is not None:
            line += f" step={e['step']}"
        return line
    if t == "reshard":
        a = e.get("action", "?")
        line = f"reshard   {a} epoch={e.get('epoch', '?')}"
        if a == "plan":
            line += (f" stages={e.get('stages', '?')}"
                     f" {_fmt_bytes(e.get('bytes', 0) or 0)}"
                     f" peak<={_fmt_bytes(e.get('peak_bound_bytes', 0) or 0)}")
        elif a == "stage":
            line += (f" stage={e.get('stage', '?')}"
                     f" {_fmt_bytes(e.get('bytes', 0) or 0)}")
        elif a == "rollback":
            line += f" ROLLBACK {str(e.get('error', ''))[:60]}"
        else:
            line += f" {_fmt_bytes(e.get('bytes', 0) or 0)}"
        return line
    if t == "flush":
        return (f"flush     {e.get('label', '?')}"
                f" rung={e.get('degraded', 'fused')}"
                f" wall={e.get('wall_s', 0):.4f}s")
    if t == "shed":
        line = (f"shed      {e.get('reason', '?')}"
                f" stage={e.get('stage', '?')}")
        if e.get("label"):
            line += f" {e['label']}"
        if e.get("tenant"):
            line += f" tenant={e['tenant']}"
        if e.get("epoch") is not None:
            line += f" epoch={e['epoch']}"
        return line
    if t == "breaker":
        line = (f"breaker   tenant={e.get('tenant', '?')}"
                f" {e.get('from', '?')}->{e.get('to', '?')}"
                f" failures={e.get('failures', '?')}")
        if e.get("to") == "open":
            line += " TRIPPED"
        return line
    if t == "hedge":
        line = f"hedge     {e.get('action', '?')} {e.get('label', '?')}"
        if e.get("action") == "fired":
            line += (f" threshold={e.get('threshold_ms', '?')}ms"
                     f" waited={e.get('waited_ms', '?')}ms")
        elif e.get("action") == "resolved":
            line += (f" winner={e.get('winner', '?')}"
                     f" wall={e.get('wall_ms', '?')}ms")
        return line
    if t == "brownout":
        return (f"brownout  {e.get('from', '?')}->{e.get('to', '?')}"
                f" queue={e.get('queue_ratio', '?')}"
                f" mem={e.get('memory_frac', '?')}"
                f" slo_breached={e.get('slo_breached', '?')}")
    return t


def merge_report(path: str, per_rank: dict, file=None, cap: int = 80) -> None:
    """Cross-rank merged timeline + rank-divergence analysis.

    ``per_rank`` maps rank -> event list; keys are integer SPMD ranks
    for file inputs and process path labels for directory inputs — the
    analysis is identical.  Per-rank clock skew is
    estimated from the bring-up anchor (see ``_anchor_ts``) and
    subtracted, then all ranks' noteworthy events are interleaved by
    adjusted timestamp (seq breaks ties within a rank).  Divergence
    check: walking each rank's flush stream in lockstep order, every
    position where ranks disagree on program label or degradation rung
    is flagged — one rank degrading to ``chunked`` while another stayed
    ``fused`` is how SPMD runs deadlock in collectives, and it is
    invisible in any single-rank view."""
    file = file or sys.stdout
    ranks = sorted(per_rank)
    total = sum(len(v) for v in per_rank.values())
    print(f"== merged timeline: {path} ({len(ranks)} rank(s), "
          f"{total} events) ==", file=file)
    anchors = {r: _anchor(per_rank[r]) for r in ranks}
    known = [a[0] for a in anchors.values() if a is not None]
    base = min(known) if known else 0.0
    skew = {}
    for r in ranks:
        if anchors[r] is None:
            # No bring-up anchor in this rank's file (e.g. it crashed
            # before initialize, or the file is a fragment).  Skew 0 is
            # honest — any other offset would be invented — but the
            # timeline reader must know this rank floats.
            skew[r] = 0.0
            print(f"rank {_rname(r)}: no bring-up anchor event — UNANCHORED "
                  "(skew 0 assumed, cross-rank ordering approximate)",
                  file=file)
        else:
            skew[r] = anchors[r][0] - base
    print("rank skew (vs earliest anchor): " + "  ".join(
        f"{_rname(r)}={skew[r]:+.4f}s" for r in ranks), file=file)

    def _adjusted(r: int, e: dict):
        """Event time on the common (earliest-anchor) axis.  When both
        the rank's anchor and the event carry ``mono``, the offset from
        the anchor uses the monotonic clock — an NTP step between
        bring-up and the event cannot warp the timeline.  Wall-clock
        minus skew otherwise."""
        a = anchors[r]
        mono = e.get("mono")
        if (a is not None and a[1] is not None
                and isinstance(mono, (int, float))):
            return base + (mono - a[1])
        ts = e.get("ts")
        if not isinstance(ts, (int, float)):
            return None
        return ts - skew[r]

    merged = []
    for r in ranks:
        for e in per_rank[r]:
            adj = _adjusted(r, e)
            if adj is None:
                continue
            merged.append((adj, e.get("seq", 0), r, e))
    merged.sort(key=lambda t: (t[0], t[1], t[2]))
    t0 = merged[0][0] if merged else 0.0

    def noteworthy(e: dict) -> bool:
        t = e.get("type")
        if t in ("fault", "degrade", "cache_evict",
                 "flush_error", "health", "serve_coalesce", "stall",
                 "lifecycle", "coherence", "reshard", "shed", "breaker",
                 "hedge", "brownout", "plan_stale", "plan_divergence"):
            return True
        if t == "memory":
            return not (e.get("action") == "admit" and e.get("ok"))
        if t == "flush":
            return "degraded" in e
        return False

    shown = [m for m in merged if noteworthy(m[3])]
    print(f"noteworthy events ({len(shown)} of {len(merged)} stamped):",
          file=file)
    for adj, _seq, r, e in shown[:cap]:
        print(f"  +{adj - t0:8.3f}s {_rname(r)}  {_merge_line(e)}", file=file)
    if len(shown) > cap:
        print(f"  ... and {len(shown) - cap} more", file=file)

    # --- rank divergence over the lockstep flush streams ---
    streams = {
        r: [e for e in per_rank[r] if e.get("type") == "flush"]
        for r in ranks
    }
    counts = {r: len(streams[r]) for r in ranks}
    if len(ranks) < 2:
        print("rank divergence: single rank, nothing to compare", file=file)
        return
    diverged = []
    depth = min(counts.values())
    for i in range(depth):
        labels = {r: streams[r][i].get("label", "?") for r in ranks}
        rungs = {r: streams[r][i].get("degraded", "fused") for r in ranks}
        sigs = {r: _stage_sig(streams[r][i]) for r in ranks}
        if (len(set(labels.values())) > 1 or len(set(rungs.values())) > 1
                or len(set(sigs.values())) > 1):
            diverged.append((i, labels, rungs, sigs))
    if len(set(counts.values())) > 1:
        print("rank divergence: flush-count mismatch " + "  ".join(
            f"{_rname(r)}={counts[r]}" for r in ranks), file=file)
    for i, labels, rungs, sigs in diverged[:20]:
        line = f"rank divergence at flush #{i}: " + "  ".join(
            f"{_rname(r)}={labels[r]}/{rungs[r]}" for r in ranks)
        if len(set(sigs.values())) > 1:
            line += "  stages " + "  ".join(
                f"{_rname(r)}=[{sigs[r]}]" for r in ranks)
        print(line, file=file)
    if len(diverged) > 20:
        print(f"  ... and {len(diverged) - 20} more", file=file)
    if not diverged and len(set(counts.values())) == 1:
        print(f"rank divergence: none ({depth} lockstep flushes, "
              "labels, rungs and stage signatures agree)", file=file)
    # per-rank stage-seconds columns: a rank burning its wall in a
    # different stage than its peers is the cross-rank perf smell the
    # lockstep labels above can't show
    totals = {r: defaultdict(float) for r in ranks}
    unatt = {r: 0.0 for r in ranks}
    for r in ranks:
        for e in streams[r]:
            for k, v in (e.get("stages") or {}).items():
                if isinstance(v, (int, float)):
                    totals[r][k] += v
            u = e.get("unattributed_s")
            if isinstance(u, (int, float)):
                unatt[r] += u
    stages_seen = [k for k in STAGE_ORDER
                   if any(totals[r].get(k) for r in ranks)]
    if stages_seen:
        print("stage seconds per rank:", file=file)
        for k in stages_seen:
            print(f"  {k:<15s} " + "  ".join(
                f"{_rname(r)}={totals[r].get(k, 0.0):.4f}s" for r in ranks),
                file=file)
        print("  unattributed    " + "  ".join(
            f"{_rname(r)}={unatt[r]:.4f}s" for r in ranks), file=file)


def attrib_report(path: str, events: list, top: int = 10,
                  file=None) -> int:
    """Stage-waterfall view of one trace file (see observe/attrib.py).

    Three blocks: per-program stage decomposition (where each program's
    cumulative wall went), the most recent per-flush waterfalls, and the
    top programs by unattributed gap — wall time none of the stage
    stamps explain (fault injection, GC pauses, lock convoys, ...)."""
    file = file or sys.stdout
    flushes = [e for e in events
               if e.get("type") == "flush" and e.get("stages")]
    print(f"{path}:", file=file)
    if not flushes:
        print("  no stage-attributed flush spans "
              "(trace predates the attribution plane?)", file=file)
        return 1
    per_label: dict = {}
    for e in flushes:
        agg = per_label.setdefault(e.get("label", "?"), {
            "n": 0, "wall": 0.0, "unattributed": 0.0,
            "stages": defaultdict(float),
        })
        agg["n"] += 1
        agg["wall"] += e.get("wall_s") or 0.0
        u = e.get("unattributed_s")
        agg["unattributed"] += u if isinstance(u, (int, float)) else 0.0
        for k, v in e["stages"].items():
            if isinstance(v, (int, float)):
                agg["stages"][k] += v

    def _waterfall(stages: dict, wall: float, unattributed: float) -> str:
        parts = []
        for k in STAGE_ORDER:
            v = stages.get(k)
            if not v:
                continue
            pct = f" {v / wall:.0%}" if wall > 0 else ""
            parts.append(f"{k}={v:.4f}s{pct}")
        if unattributed:
            pct = f" {unattributed / wall:.0%}" if wall > 0 else ""
            parts.append(f"unattributed={unattributed:.4f}s{pct}")
        return "  ".join(parts)

    print(f"stage waterfall ({len(flushes)} attributed flush(es), "
          f"{len(per_label)} program(s)):", file=file)
    ranked = sorted(per_label.items(), key=lambda kv: kv[1]["wall"],
                    reverse=True)
    for label, agg in ranked[:top]:
        print(f"  {label} x{agg['n']} wall={agg['wall']:.4f}s", file=file)
        print("    " + _waterfall(agg["stages"], agg["wall"],
                                  agg["unattributed"]), file=file)
    # plan-cache fast path (PR-18): a hit skips the prepare-side
    # analysis pipeline, so its prepare+verify collapses to the
    # version-vector check — quantify the drop against the miss path
    def _pv(e: dict) -> float:
        s = e["stages"]
        return ((s.get("prepare") or 0.0) + (s.get("verify") or 0.0))

    plan_hits = [e for e in flushes if e.get("plan_cache")]
    if plan_hits:
        plan_misses = [e for e in flushes if not e.get("plan_cache")]
        hs = sorted(_pv(e) for e in plan_hits)
        h50 = hs[len(hs) // 2]
        line = (f"plan-cache fast path: {len(plan_hits)} hit(s)  "
                f"prepare+verify p50 {h50 * 1e6:.0f}us")
        if plan_misses:
            ms = sorted(_pv(e) for e in plan_misses)
            m50 = ms[len(ms) // 2]
            line += f" vs {m50 * 1e6:.0f}us on the miss path"
            if h50 > 0:
                line += f" ({m50 / h50:.1f}x)"
        print(line, file=file)
    recent = flushes[-8:]
    print(f"recent flushes (last {len(recent)}):", file=file)
    for e in recent:
        wall = e.get("wall_s") or 0.0
        u = e.get("unattributed_s")
        u = u if isinstance(u, (int, float)) else 0.0
        rung = e.get("degraded", "fused")
        plan = f" plan={e['plan_cache']}" if e.get("plan_cache") else ""
        print(f"  {e.get('label', '?')} [{rung}]{plan} wall={wall:.4f}s  "
              + _waterfall(e["stages"], wall, u), file=file)
    # incident explainer verdicts (stamped by observe/slo.py — see
    # observe/attrib.py explain()): why each incident's flush diverged
    whys = [e for e in events if e.get("why")]
    if whys:
        print(f"incident explainer verdicts ({len(whys)}):", file=file)
        for e in whys[-8:]:
            who = e.get("label") or e.get("fingerprint") or ""
            print(f"  {e.get('type', '?'):<16s} {who:<22s} {e['why']}",
                  file=file)
    gaps = sorted(per_label.items(), key=lambda kv: kv[1]["unattributed"],
                  reverse=True)
    gaps = [(lb, a) for lb, a in gaps if a["unattributed"] > 0][:top]
    if gaps:
        print(f"top {len(gaps)} program(s) by unattributed gap:",
              file=file)
        for label, agg in gaps:
            share = (agg["unattributed"] / agg["wall"]
                     if agg["wall"] > 0 else 0.0)
            print(f"  {label:<22s} gap={agg['unattributed']:.4f}s "
                  f"({share:.1%} of {agg['wall']:.4f}s, x{agg['n']})",
                  file=file)
    return 0


def trace_chain(trace_id: str, per_rank: dict, file=None) -> int:
    """Reconstruct ONE request's causal chain across processes.

    Every event stamped with ``trace_id`` (directly, or via the
    ``trace_ids`` list on a coalesced-batch event) is collected from all
    input streams (SPMD ranks, or processes when the input was a
    directory) and re-threaded by span parentage: the ``serve_session``
    root, then each flush span in time order, with that span's child
    events (degrade rungs, stalls, memory admissions, barrier spans)
    indented beneath it — the end-to-end story
    of one request, even when its pieces executed on different processes
    and interleaved with thousands of unrelated events.  A child whose
    ``parent_span`` resolves to NO span in the inputs is an orphaned
    half: its other side ran in a process whose trace was not collected
    (or was lost) — flagged explicitly instead of silently filed as
    session-level."""
    file = file or sys.stdout
    evs = []
    for r in sorted(per_rank):
        for e in per_rank[r]:
            if (e.get("trace_id") == trace_id
                    or trace_id in (e.get("trace_ids") or [])):
                evs.append((r, e))
    if not evs:
        print(f"trace {trace_id}: no events found", file=file)
        return 1

    def _key(pair):
        _r, e = pair
        ts = e.get("ts")
        return (ts if isinstance(ts, (int, float)) else 0.0,
                e.get("seq", 0))

    evs.sort(key=_key)
    ranks = sorted({r for r, _ in evs})
    stamps = [e.get("ts") for _, e in evs
              if isinstance(e.get("ts"), (int, float))]
    t0 = min(stamps) if stamps else None

    def rel(e):
        ts = e.get("ts")
        return (f"+{ts - t0:8.3f}s"
                if t0 is not None and isinstance(ts, (int, float))
                else " " * 10)

    roots = [(r, e) for r, e in evs if e.get("type") == "serve_session"]
    spans = [(r, e) for r, e in evs if e.get("type") == "flush"]
    span_ids = {e.get("span_id") for _, e in spans if e.get("span_id")}
    root_ids = {e.get("span_id") for _, e in roots if e.get("span_id")}
    children = defaultdict(list)
    for r, e in evs:
        if e.get("type") in ("serve_session", "flush"):
            continue
        children[e.get("parent_span")].append((r, e))

    names = [_rname(r) for r in ranks]
    print(f"== trace {trace_id}: {len(evs)} events across "
          f"{len(ranks)} process(es) {names} ==", file=file)
    for r, e in roots:
        line = f"session   stream={e.get('stream', '?')}"
        if e.get("tenant"):
            line += f" tenant={e['tenant']}"
        print(f"{rel(e)} {_rname(r)}  {line}", file=file)
    for i, (r, e) in enumerate(spans):
        line = (f"flush #{i}  {e.get('label', '?')}"
                f" rung={e.get('degraded', 'fused')}"
                f" cache={e.get('cache', '?')}")
        if e.get("queue_s") is not None:
            line += f" queue={e['queue_s']}s"
        line += f" wall={e.get('wall_s', 0):.4f}s"
        if e.get("coalesced"):
            line += f" coalesced={e['coalesced']}"
        print(f"{rel(e)} {_rname(r)}  {line}", file=file)
        for cr, c in sorted(children.get(e.get("span_id"), []),
                            key=lambda p: p[1].get("seq", 0)):
            print(f"{rel(c)} {_rname(cr)}    └ {_merge_line(c)}", file=file)
    # events parented by the session root (or nothing resolvable): the
    # slo_breach verdict, coalesce joins, pre-span stalls.  Split by
    # whether the parent actually resolves: parent_span == a session
    # root (or unset) is normal session-level fan-in; a parent id that
    # matches NOTHING in the inputs means the other half of this trace
    # lives in a process we did not collect — an orphaned half.
    session_level = []
    orphaned = []
    # trace_gap markers: the tail-latch buffer (RAMBA_TRACE_SAMPLE)
    # rotated before this trace latched in — events are missing by
    # sampling policy, not by collection failure
    gaps = [(r, e) for r, e in evs if e.get("type") == "trace_gap"]
    gap_dropped = sum(e.get("dropped") or 0 for _, e in gaps)
    for pid, kids in children.items():
        if pid in span_ids:
            continue
        if pid is None or pid in root_ids:
            session_level.extend(
                (cr, c) for cr, c in kids if c.get("type") != "trace_gap")
        else:
            orphaned.extend((pid, cr, c) for cr, c in kids
                            if c.get("type") != "trace_gap")
    if session_level:
        print("session-level events:", file=file)
        for cr, c in sorted(session_level, key=_key):
            print(f"{rel(c)} {_rname(cr)}  {_merge_line(c)}", file=file)
    if gaps:
        print(f"sampling gap: {gap_dropped} event(s) dropped by the "
              "tail-latch buffer before this trace latched in "
              "(RAMBA_TRACE_SAMPLE head sampling — raise "
              "RAMBA_TRACE_SAMPLE fidelity or the buffer bound to keep "
              "longer pre-incident chains)", file=file)
    if orphaned:
        if gaps:
            print(f"sampled-out events ({len(orphaned)}) — parent span "
                  "fell out of the tail-latch buffer (see sampling gap "
                  "above), NOT a missing rank:", file=file)
        else:
            print(f"ORPHANED events ({len(orphaned)}) — parent span not "
                  "in any collected stream (other half of the trace "
                  "missing):", file=file)
        for pid, cr, c in sorted(orphaned, key=lambda t: _key(t[1:])):
            print(f"{rel(c)} {_rname(cr)}  {_merge_line(c)}"
                  f"  [parent_span={pid}]", file=file)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Summarize RAMBA_TRACE JSONL trace files."
    )
    ap.add_argument("paths", nargs="+",
                    help="trace file(s); .rank* siblings auto-discovered")
    ap.add_argument("--top", type=int, default=10,
                    help="programs to list (default 10)")
    ap.add_argument("--merge-ranks", action="store_true",
                    help="interleave per-rank files into one skew-adjusted"
                         " timeline and flag rank divergence")
    ap.add_argument("--merge-cap", type=int, default=80,
                    help="max merged timeline lines (default 80)")
    ap.add_argument("--attrib", action="store_true",
                    help="stage-waterfall view: per-program stage"
                         " decomposition, recent per-flush waterfalls,"
                         " top programs by unattributed gap")
    ap.add_argument("--trace", metavar="ID", default=None,
                    help="reconstruct one request's causal chain: every"
                         " event carrying this trace_id, across ranks,"
                         " threaded session -> flush spans -> rung/stall"
                         "/memory children")
    args = ap.parse_args(argv)

    if args.trace:
        rc = 0
        for p in args.paths:
            per_rank = _load_streams(p)
            if per_rank is None:
                print(f"{p}: no trace file found", file=sys.stderr)
                return 2
            rc = max(rc, trace_chain(args.trace, per_rank))
        return rc

    if args.attrib:
        rc = 0
        files = []
        for p in args.paths:
            found = _discover(p)
            if not found:
                print(f"{p}: no trace file found", file=sys.stderr)
                return 2
            files += [f for f in found if f not in files]
        for f in files:
            rc = max(rc, attrib_report(f, _load(f), top=args.top))
        return rc

    if args.merge_ranks:
        for p in args.paths:
            per_rank = _load_streams(p)
            if per_rank is None:
                print(f"{p}: no trace file found", file=sys.stderr)
                return 2
            merge_report(p, per_rank, cap=args.merge_cap)
        return 0

    files = []
    for p in args.paths:
        found = _discover(p)
        if not found:
            print(f"{p}: no trace file found", file=sys.stderr)
            return 2
        files += [f for f in found if f not in files]

    for f in files:
        report(f, _load(f), top=args.top)
    return 0


if __name__ == "__main__":
    sys.exit(main())
