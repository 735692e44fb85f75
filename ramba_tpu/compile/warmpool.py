"""Trace-replay warm pool: pre-compile tomorrow's executables from
yesterday's traffic.

A ``RAMBA_TRACE`` capture records one ``program`` event per flush (now
carrying the kernel fingerprint and compile class).  This module ranks
the (fingerprint, compile_class) pairs by how often they appeared —
re-weighted by the live ledger's exec counts when available — loads the
matching program skeletons from the persist cache
(``compile/persist.py``), and submits compile thunks through
``CompilePipeline.submit_warm``.  The pipeline applies the PR-13
overload policy for free: under yellow/red brownout speculative warm
work is the first load shed (``serve.warm_shed``), and warm thunks take
round-robin turns with real traffic instead of starving it.

The result: a process that replays last shift's trace before opening to
traffic serves its first requests from warm executables instead of
paying cold XLA compiles.  ``scripts/warm_pool.py`` is the operational
CLI wrapper.
"""

from __future__ import annotations

import json
import time
from typing import Optional

from ramba_tpu.compile import persist as _persist
from ramba_tpu.observe import registry as _registry


def rank_trace(trace_path: str) -> list:
    """Rank (fingerprint, compile_class) pairs from a trace by arrival
    count, most frequent first.  Events without a fingerprint (pre-PR-14
    traces) are skipped."""
    counts: dict = {}
    with open(trace_path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                ev = json.loads(line)
            except json.JSONDecodeError:
                continue
            if ev.get("type") != "program":
                continue
            fp = ev.get("fingerprint")
            if not fp:
                continue
            key = (fp, _token(ev.get("compile_class")))
            counts[key] = counts.get(key, 0) + 1
    ranked = sorted(counts.items(), key=lambda it: (-it[1], it[0]))
    return [(fp, cls, n) for (fp, cls), n in ranked]


def _token(cls):
    if isinstance(cls, list):
        return tuple(cls)
    return cls


def _ledger_weight(fp: str) -> int:
    try:
        from ramba_tpu.observe import ledger as _ledger

        k = _ledger.snapshot().get("kernels", {}).get(fp)
        if k:
            return int(k.get("exec", {}).get("count", 0))
    except Exception:  # noqa: BLE001
        pass
    return 0


def _make_thunk(fp: str, rec: dict):
    """A warm thunk: rebuild the program skeleton, compile through the
    fuser's own cache (so the hot path later hits it), and execute once
    on zero-filled examples to populate jit's per-shape cache — the same
    shape of warm-up the autotuner uses."""

    def thunk():
        import jax

        from ramba_tpu.core import fuser as _fuser

        program = _fuser._Program(rec["instrs"], rec["n_leaves"],
                                  rec["leaf_kinds"], rec["out_slots"],
                                  rec.get("live_cuts", ()))
        vals = _persist._example_vals(rec["sig"])
        fn, _is_new, _fp, _backend = _fuser._get_compiled(
            program, tuple(rec["donate"]), leaf_vals=vals,
            compile_class=rec.get("compile_class"))
        out = fn(*vals)
        jax.block_until_ready(out)

    return thunk


def warm(trace_path: str, top_k: int = 8,
         budget_s: Optional[float] = None, pipeline=None,
         wait: bool = True, timeout: float = 120.0) -> dict:
    """Replay a trace's top-K programs through ``submit_warm``.

    Budget-capped (``top_k`` entries, optionally ``budget_s`` seconds of
    submission wall) and brownout-gated by the pipeline itself.  Returns
    a report dict; never raises on individual warm failures — a failed
    warm-up is a lost opportunity, not an error."""
    report = {
        "considered": 0, "submitted": 0, "warmed": 0, "failed": 0,
        "shed": 0, "unresolved": 0, "budget_stop": 0, "seconds": 0.0,
    }
    ranked = rank_trace(trace_path)
    # prefer what the live ledger has actually been executing
    ranked.sort(key=lambda it: (-(_ledger_weight(it[0]) + it[2]), it[0]))
    if pipeline is None:
        from ramba_tpu.serve import pipeline as _pipeline

        pipeline = _pipeline.get_pipeline()
    t0 = time.monotonic()
    shed_before = _registry.get("serve.warm_shed")
    tickets = []
    for fp, _cls, _n in ranked[: max(0, int(top_k))]:
        report["considered"] += 1
        if budget_s is not None and time.monotonic() - t0 > budget_s:
            report["budget_stop"] += 1
            break
        rec = _persist.load_program(fp)
        if rec is None:
            report["unresolved"] += 1
            continue
        tickets.append(pipeline.submit_warm(
            _make_thunk(fp, rec), label=f"warmpool:{fp}"))
        report["submitted"] += 1
        _registry.inc("compile.warmpool_submit")
    if wait:
        for t in tickets:
            try:
                t.wait(timeout=timeout)
            except BaseException:  # noqa: BLE001 — count, don't raise
                report["failed"] += 1
            else:
                report["warmed"] += 1
    report["shed"] = _registry.get("serve.warm_shed") - shed_before
    report["seconds"] = round(time.monotonic() - t0, 4)
    return report
