"""Critical-path attribution: the flush span's stage waterfall.

The kernel ledger (observe/ledger.py) answers "how long did kernel X
take"; this module answers the two questions the ledger cannot:

* **Where does a flush's wall-clock actually go?**  Every flush span
  carries a ``stages`` dict stamped along the critical path —
  ``prepare / verify / queue_wait / coalesce / compile / admit /
  dispatch / device_execute / write_back`` — and :func:`finalize_span`
  folds the residual into ``unattributed_s`` so the stage durations plus
  the residual always reconcile with ``wall_s``.  ``device_execute`` is
  the host's wait in a ``jax.block_until_ready`` fence after each
  compiled call (opt out with ``RAMBA_ATTRIB=off``), not device time:
  that comes from a profiler trace, to which the same spans are joined
  via the ``jax.profiler.TraceAnnotation``s of ``observe/profile.py``,
  which carry the span's trace id.

* **Why was THIS flush slow?**  :func:`finalize_span` also maintains
  per-fingerprint per-stage rolling baselines; when an ``slo_breach``
  fires, observe/slo.py calls :func:`explain` to diff the span's
  waterfall against those baselines and stamp a ``why`` verdict naming
  the dominant divergent stage ("queue_wait 12.0x baseline ->
  overload").

Everything here is lock-guarded dict math on the host — no jax import,
so offline consumers (trace_report.py) stay cheap.
"""

from __future__ import annotations

import os
import threading
from typing import Optional

from ramba_tpu.observe import ledger as _ledger

# Canonical stage order: a span's stages, iterated in this order, read as
# the flush's waterfall.  Keep in sync with the glossary in docs/index.md.
STAGES = (
    "trace",           # caller thread: linearize + fuse + leaf plumbing
                       # (graph capture — unavoidable per flush)
    "prepare",         # caller thread: the analysis pipeline — class
                       # proof, fingerprint, memo certification, plan
                       # cache (skippable via a plan certificate)
    "verify",          # RAMBA_VERIFY eager shadow evaluation
    "queue_wait",      # async pipeline: submit -> group pop
    "coalesce",        # async pipeline: group pop -> this ticket's dispatch
    "compile",         # cache-miss call: trace + XLA compile (+ cost probe)
    "admit",           # memory-ledger admission sizing
    "dispatch",        # steady-state call: host dispatch until handles return
    "device_execute",  # block_until_ready fence: on-device tail
    "write_back",      # ladder return -> results pinned + span finalized
)

_lock = threading.Lock()

# config (reread by reconfigure())
_enabled = True

# state
_stage_totals: "dict[str, float]" = {}
_unattributed_total = 0.0
_flushes = 0
# incident-explainer baselines: fp -> {stage|"unattributed": _Rolling}
_stage_base: "dict[str, dict]" = {}


def reconfigure(*, enabled: Optional[bool] = None) -> None:
    """(Re)read env config; kwargs override env (tests)."""
    global _enabled
    if enabled is None:
        raw = os.environ.get("RAMBA_ATTRIB", "1").strip().lower()
        _enabled = raw not in ("0", "off", "false", "no")
    else:
        _enabled = bool(enabled)


def fence_enabled() -> bool:
    """Does every compiled call end in a ``block_until_ready`` fence?"""
    return _enabled


def flush_wall_total() -> float:
    """Total attributed flush wall (stages + residual) — the observer
    tax's denominator (observe/observer.py)."""
    with _lock:
        return sum(_stage_totals.values()) + _unattributed_total


# ---------------------------------------------------------------------------
# stage ledger
# ---------------------------------------------------------------------------


def add_stage(span: Optional[dict], stage: str, seconds: float) -> None:
    """Accumulate ``seconds`` into ``span['stages'][stage]``."""
    if span is None or seconds < 0:
        return
    st = span.setdefault("stages", {})
    st[stage] = st.get(stage, 0.0) + seconds


def finalize_span(span: dict, fp: Optional[str] = None) -> None:
    """Round the span's stage ledger, fold the residual into
    ``unattributed_s``, and roll both into the global/per-fp totals
    (including the incident explainer's per-stage baselines).
    Called once per flush just before the span event is emitted."""
    st = span.get("stages")
    if st is None:
        return
    wall = float(span.get("wall_s") or 0.0)
    total = 0.0
    for k in list(st):
        v = float(st[k])
        total += v
        st[k] = round(v, 6)
    un = max(0.0, wall - total)
    span["unattributed_s"] = round(un, 6)
    global _unattributed_total, _flushes
    with _lock:
        _flushes += 1
        _unattributed_total += un
        for k, v in st.items():
            _stage_totals[k] = _stage_totals.get(k, 0.0) + v
        if fp:
            base = _stage_base.get(fp)
            if base is None:
                base = _stage_base[fp] = {}
            for k, v in st.items():
                win = base.get(k)
                if win is None:
                    win = base[k] = _ledger._Rolling()
                win.add(v)
            uwin = base.get("unattributed")
            if uwin is None:
                uwin = base["unattributed"] = _ledger._Rolling()
            uwin.add(un)


def _ordered(stages: dict) -> dict:
    out = {k: stages[k] for k in STAGES if k in stages}
    for k in stages:  # future stages survive the reorder
        out.setdefault(k, stages[k])
    return out


# ---------------------------------------------------------------------------
# incident explainer
# ---------------------------------------------------------------------------

# dominant divergent stage -> operator-facing verdict
_EXPLAIN_VERDICTS = {
    "queue_wait": "overload",
    "coalesce": "overload",
    "compile": "cache miss",
    "admit": "memory pressure",
    "device_execute": "device regression",
    "dispatch": "host dispatch slowdown",
    "write_back": "host dispatch slowdown",
    "trace": "host analysis slowdown",
    "prepare": "host analysis slowdown",
    "verify": "host analysis slowdown",
    "unattributed": "untracked interference (GC / lock convoy?)",
}
_EXPLAIN_MIN_SAMPLES = 3   # baseline window floor before a ratio is trusted
_EXPLAIN_FACTOR = 1.5      # a stage must exceed 1.5x its p50 to diverge
_EXPLAIN_NOVEL_FRAC = 0.25  # baseline-less stage must eat >=25% of wall


def explain(span: dict, fp: Optional[str] = None) -> Optional[dict]:
    """Diff one span's stage waterfall against its fingerprint's rolling
    per-stage baselines and name the dominant divergent stage.

    Returns ``{"stage", "verdict", "text", "ratio", "stage_s",
    "baseline_p50_s"}`` or None when nothing diverges (or no baseline
    history exists yet).  Dominance is by absolute excess over the
    baseline p50 — the stage that actually ate the wall, not the one
    with the flashiest ratio on a microsecond base.  A stage with no
    baseline at all (e.g. ``compile`` appearing on a steady-state
    fingerprint) is divergent by existence when it claims a meaningful
    share of the wall — that IS the cache-miss signature."""
    if fp is None:
        fp = span.get("fingerprint")
    st = dict(span.get("stages") or {})
    un = span.get("unattributed_s")
    if isinstance(un, (int, float)) and un > 0:
        st["unattributed"] = float(un)
    if not fp or not st:
        return None
    wall = float(span.get("wall_s") or 0.0)
    best = None  # (excess, stage, baseline_p50, value)
    with _lock:
        base = _stage_base.get(fp)
        if not base:
            return None
        for k, v in st.items():
            if not isinstance(v, (int, float)) or v <= 0:
                continue
            win = base.get(k)
            p50 = (win.quantile(0.50)
                   if win is not None and win.count >= _EXPLAIN_MIN_SAMPLES
                   else None)
            if p50 is None or p50 <= 0:
                if wall > 0 and v >= _EXPLAIN_NOVEL_FRAC * wall:
                    cand = (float(v), k, None, float(v))
                else:
                    continue
            else:
                if v <= p50 * _EXPLAIN_FACTOR:
                    continue
                cand = (float(v) - p50, k, p50, float(v))
            if best is None or cand[0] > best[0]:
                best = cand
    if best is None:
        return None
    _excess, stage, p50, value = best
    verdict = _EXPLAIN_VERDICTS.get(stage, "stage regression")
    if p50:
        ratio = value / p50
        text = f"{stage} {ratio:.1f}x baseline -> {verdict}"
    else:
        ratio = None
        text = f"{stage} -> {verdict}"
    return {
        "stage": stage,
        "verdict": verdict,
        "text": text,
        "ratio": round(ratio, 2) if ratio is not None else None,
        "stage_s": round(value, 6),
        "baseline_p50_s": round(p50, 6) if p50 else None,
    }


# ---------------------------------------------------------------------------
# reports
# ---------------------------------------------------------------------------


def attribution_report() -> dict:
    """The stage ledger's totals in one dict (diagnostics/CLI).
    Empty dict when no flush has been attributed yet."""
    with _lock:
        flushes = _flushes
        stage_totals = {k: round(v, 6) for k, v in _stage_totals.items()}
        un = round(_unattributed_total, 6)
    if not flushes:
        return {}
    out = {
        "flushes": flushes,
        "stage_seconds": _ordered(stage_totals),
        "unattributed_s": un,
    }
    denom = sum(stage_totals.values()) + un
    out["unattributed_frac"] = round(un / denom, 4) if denom > 0 else 0.0
    return out


def snapshot() -> dict:
    return attribution_report()


def reset() -> None:
    """Forget every total and baseline (tests)."""
    global _unattributed_total, _flushes
    with _lock:
        _stage_totals.clear()
        _unattributed_total = 0.0
        _flushes = 0
        _stage_base.clear()


reconfigure()
