"""Ledger-driven backend autotuner (`ramba-autotune`).

``core/fuser.py::_get_compiled`` asks this module which lowering backend —
``xla`` (the default jit lowering) or ``pallas``
(``ops/pallas_backend.py``) — should serve a kernel fingerprint.  The
decision is *measured*, not modeled:

* ``RAMBA_AUTOTUNE`` unset/``off`` — every fingerprint takes ``xla``
  (selection ``default``); zero overhead, historical behavior.
* ``RAMBA_AUTOTUNE=race`` (or ``1``/``on``) — the first executions of a
  lowerable fingerprint alternate backends, each sample landing in that
  backend's slice of the kernel cost ledger (``observe/ledger.py``).
  Once every candidate holds ``RAMBA_AUTOTUNE_K`` (default 3) steady-state
  samples, the backend with the lower exec p50 is **latched** for the
  fingerprint and the loser's executable ages out of the fuser's LRU
  compile cache naturally.
* ``RAMBA_AUTOTUNE=force:<backend>`` — pin every lowerable fingerprint to
  one backend (measurement and A/B harnesses).

Latched decisions persist to ``RAMBA_AUTOTUNE_CACHE`` (a JSON decision
table, written atomically) so a later process skips the race entirely:
its selections come straight from the table (counted under the
``autotune.race_skipped`` registry counter; fresh races count under
``autotune.race_started``).

A Pallas failure at compile or first execution calls :func:`note_failure`,
which latches ``xla`` for the fingerprint and records the fallback on the
ledger's backend slice — degradation, never an error.

Race compiles must not block the serving hot path: when the async compile
pipeline (``serve/pipeline.py``) is live, :func:`maybe_prewarm` ships the
challenger's first (compile-paying) execution through it as a warm task,
so the race's steady-state samples start from an already-jitted callable.
"""

from __future__ import annotations

import json
import os
import tempfile
import threading
from typing import Optional

from ramba_tpu.observe import ledger as _ledger
from ramba_tpu.observe import registry as _registry

XLA = "xla"
PALLAS = "pallas"

_lock = threading.RLock()

# fingerprint -> {"backend": str, "via": str}; "via" in
# default|autotune|persisted|forced|fallback
_decisions: "dict[str, dict]" = {}
# fingerprint -> True once a prewarm task has been submitted
_prewarmed: "dict[str, bool]" = {}
# fingerprints whose pallas lowering failed (never re-raced this process)
_failed: "set[str]" = set()

_mode = "off"        # off | race | force
_forced: Optional[str] = None
_k = 3
_cache_path: Optional[str] = None
_table_loaded = False
# monotone counter bumped on every decision-table mutation (latch, table
# load, fallback, reset/reconfigure).  The plan-certificate validity
# analysis (analyze/plancert.py) folds this into its invalidation
# signature: a cached prepare verdict is only as fresh as the autotune
# table it read.
_generation = 0


def generation() -> int:
    """Decision-table generation: increments whenever any fingerprint's
    backend decision could have changed."""
    return _generation


def _bump_generation_locked() -> None:
    global _generation
    _generation += 1


def reconfigure(*, mode: Optional[str] = None,
                cache_path: Optional[str] = None,
                k: Optional[int] = None) -> None:
    """Reload configuration from the environment (keyword overrides for
    tests).  Clears in-memory decisions so mode changes take effect; the
    persisted table (if any) is lazily re-read."""
    global _mode, _forced, _k, _cache_path, _table_loaded
    with _lock:
        raw = os.environ.get("RAMBA_AUTOTUNE", "") if mode is None else mode
        raw = (raw or "").strip().lower()
        if raw in ("", "0", "off", "false", "no"):
            _mode, _forced = "off", None
        elif raw.startswith("force:"):
            b = raw.split(":", 1)[1]
            _mode, _forced = "force", (b if b in (XLA, PALLAS) else XLA)
        elif raw in ("race", "1", "on", "true", "yes"):
            _mode, _forced = "race", None
        else:
            _mode, _forced = "off", None
        try:
            _k = max(1, int(os.environ.get("RAMBA_AUTOTUNE_K", "3") or 3)
                     if k is None else int(k))
        except ValueError:
            _k = 3
        _cache_path = os.environ.get("RAMBA_AUTOTUNE_CACHE") \
            if cache_path is None else cache_path
        _decisions.clear()
        _prewarmed.clear()
        _failed.clear()
        _table_loaded = False
        _bump_generation_locked()


def reset() -> None:
    """Drop all decisions/race state (tests); keeps configuration."""
    with _lock:
        _decisions.clear()
        _prewarmed.clear()
        _failed.clear()
        _table_loaded = False
        _bump_generation_locked()


def mode() -> str:
    return _mode


def active() -> bool:
    return _mode != "off"


# ---------------------------------------------------------------------------
# persisted decision table
# ---------------------------------------------------------------------------


def _load_table_locked() -> None:
    global _table_loaded
    if _table_loaded:
        return
    _table_loaded = True
    if not _cache_path:
        return
    try:
        with open(_cache_path) as f:
            table = json.load(f)
    except (OSError, ValueError):
        return
    if not isinstance(table, dict):
        return
    n = 0
    for fp, row in table.get("decisions", {}).items():
        b = row.get("backend") if isinstance(row, dict) else None
        if b in (XLA, PALLAS) and fp not in _decisions:
            _decisions[fp] = {"backend": b, "via": "persisted"}
            n += 1
    if n:
        _registry.inc("autotune.table_loaded_decisions", n)
        _bump_generation_locked()


def _persist_table_locked() -> None:
    if not _cache_path:
        return
    table = {
        "version": 1,
        "decisions": {
            fp: {"backend": d["backend"], "via": d["via"]}
            for fp, d in _decisions.items()
            if d["via"] in ("autotune", "persisted", "fallback")
        },
    }
    try:
        d = os.path.dirname(os.path.abspath(_cache_path)) or "."
        fd, tmp = tempfile.mkstemp(prefix=".autotune-", dir=d)
        with os.fdopen(fd, "w") as f:
            json.dump(table, f, indent=0, sort_keys=True)
        os.replace(tmp, _cache_path)
    except OSError:
        pass


# ---------------------------------------------------------------------------
# selection
# ---------------------------------------------------------------------------


def _agree_winner(winner: str) -> str:
    """Cross-rank agreement on the latched backend.  In a multi-controller
    job every rank MUST latch the same backend per fingerprint (divergent
    lowerings would desync the SPMD program streams).  Race counts are
    ledger-driven and advance in lockstep, so all ranks reach the latch on
    the same dispatch; rank 0's measured winner becomes the decision —
    local p50s can disagree across ranks when the backends are close.

    Rides the resilience coherence layer (``coherence.agree`` with
    ``reduce="bcast"``), which does the transfer-ledger accounting and
    emits the ``coherence`` event itself — control-plane traffic is never
    silently swallowed.  A failed round falls back to the local winner
    with an ``outcome=local`` event, preserving the old best-effort
    semantics without the old bare ``except: pass``."""
    from ramba_tpu.resilience import coherence as _coherence

    if not _coherence.engaged():
        return winner
    v = _coherence.agree("autotune:winner",
                         1 if winner == PALLAS else 0, reduce="bcast")
    return PALLAS if v else XLA


def select(fp: str, program, leaf_vals) -> tuple:
    """Backend for this dispatch: ``(backend, via)``.

    ``via`` is ``default`` (autotune off or program not Pallas-lowerable),
    ``forced``, ``racing`` (still alternating, not yet latched),
    ``autotune`` (latched by a race this process), ``persisted`` (latched
    by the decision table), or ``fallback`` (Pallas failed earlier)."""
    if _mode == "off":
        return XLA, "default"
    from ramba_tpu.ops import pallas_backend as _pallas

    with _lock:
        _load_table_locked()
        d = _decisions.get(fp)
        if d is not None:
            return d["backend"], d["via"]
        if fp in _failed:
            return XLA, "fallback"
    if not _pallas.supports(program, leaf_vals):
        return XLA, "default"
    if _mode == "force":
        return _forced, "forced"

    # race: alternate backends until each holds K steady-state samples,
    # then latch the lower p50
    stats = _ledger.backend_stats(fp)
    counts = {b: (stats.get(b) or {}).get("count", 0) for b in (XLA, PALLAS)}
    with _lock:
        d = _decisions.get(fp)  # latched concurrently?
        if d is not None:
            return d["backend"], d["via"]
        if counts[XLA] == 0 and counts[PALLAS] == 0 \
                and fp not in _prewarmed:
            _prewarmed[fp] = False  # race begins now
            _registry.inc("autotune.race_started")
        if counts[XLA] >= _k and counts[PALLAS] >= _k:
            p50 = {b: (stats.get(b) or {}).get("p50_s") for b in (XLA, PALLAS)}
            winner = PALLAS if (p50[PALLAS] or float("inf")) < \
                (p50[XLA] or float("inf")) else XLA
            winner = _agree_winner(winner)
            _decisions[fp] = {"backend": winner, "via": "autotune"}
            _bump_generation_locked()
            _registry.inc("autotune.latched")
            _registry.gauge("autotune.decisions", float(len(_decisions)))
            _persist_table_locked()
            return winner, "autotune"
    # alternate toward whichever backend has fewer samples (pallas first,
    # so its compile cost is paid while xla is still warm in the jit cache)
    return (PALLAS, "racing") if counts[PALLAS] <= counts[XLA] \
        else (XLA, "racing")


def note_failure(fp: str, backend: str, err) -> None:
    """A backend failed to lower/compile/execute for this fingerprint:
    latch the other backend and record the fallback."""
    with _lock:
        _failed.add(fp)
        _decisions[fp] = {"backend": XLA, "via": "fallback"}
        _bump_generation_locked()
        _persist_table_locked()
    _ledger.record_backend_fallback(fp, backend, str(err))


def decision(fp: str) -> Optional[dict]:
    with _lock:
        d = _decisions.get(fp)
        return dict(d) if d is not None else None


def latched_via_autotune() -> bool:
    """True when at least one fingerprint's backend was latched by a
    measured race or the persisted table (bench.py's
    ``backend_selected_via`` flips to ``autotune`` on this)."""
    with _lock:
        return any(d["via"] in ("autotune", "persisted")
                   for d in _decisions.values())


def report() -> dict:
    """The ``autotune`` section of ``diagnostics.perf_report()``: mode,
    per-fingerprint decisions, and the measured race overhead (total
    steady-state seconds + compile seconds sunk into each loser)."""
    with _lock:
        decisions = {fp: dict(d) for fp, d in _decisions.items()}
        failed = sorted(_failed)
    overhead_s = 0.0
    races = 0
    for fp, d in decisions.items():
        if d["via"] != "autotune":
            continue
        races += 1
        stats = _ledger.backend_stats(fp)
        loser = PALLAS if d["backend"] == XLA else XLA
        ls = stats.get(loser) or {}
        overhead_s += float(ls.get("total_s") or 0.0)
        overhead_s += float(ls.get("compile_s") or 0.0)
    return {
        "mode": _mode if _mode != "force" else f"force:{_forced}",
        "k": _k,
        "cache_path": _cache_path,
        "decisions": decisions,
        "failed": failed,
        "races_latched": races,
        "race_overhead_s": round(overhead_s, 6),
    }


# ---------------------------------------------------------------------------
# pipeline prewarm: challenger compiles off the hot path
# ---------------------------------------------------------------------------


def maybe_prewarm(fp: str, program, leaf_vals, donate_key: tuple) -> None:
    """Submit the challenger backend's first (compile-paying) execution
    through the async compile pipeline, once per fingerprint, so race
    compiles never block a serving flush.  No-op when no pipeline is live
    (the synchronous path just pays the compile inline, as it always has
    for fresh XLA kernels)."""
    if _mode != "race":
        return
    with _lock:
        if _prewarmed.get(fp):
            return
        _prewarmed[fp] = True
    try:
        from ramba_tpu.serve import pipeline as _pipeline
        pipe = _pipeline.current_pipeline()
    except Exception:
        return
    if pipe is None or not hasattr(pipe, "submit_warm"):
        return
    import jax

    avals = []
    for v in leaf_vals:
        if getattr(v, "shape", ()) and hasattr(v, "dtype"):
            avals.append(jax.ShapeDtypeStruct(v.shape, v.dtype))
        else:
            # a scalar operand, a number or its resident array: pass
            # through by value, weak type and all
            avals.append(v)

    def warm():
        import jax.numpy as jnp
        from ramba_tpu.core import fuser as _fuser

        fn, _is_new, _fp, backend = _fuser._get_compiled(
            program, donate_key,
            leaf_vals=[
                jnp.zeros(a.shape, a.dtype)
                if isinstance(a, jax.ShapeDtypeStruct) else a
                for a in avals
            ],
            force_backend=PALLAS,
        )
        if backend != PALLAS:
            return
        args = [jnp.zeros(a.shape, a.dtype)
                if isinstance(a, jax.ShapeDtypeStruct) else a
                for a in avals]
        jax.block_until_ready(fn(*args))
        _registry.inc("autotune.prewarm_done")

    try:
        pipe.submit_warm(warm, label=f"autotune:{fp}")
        _registry.inc("autotune.prewarm_submitted")
    except Exception:
        pass


reconfigure()
