"""Graceful-degradation ladder for kernel execution.

When the fused path keeps failing — repeated compile faults, or a device
OOM where re-attempting the identical program is pointless — execution
walks down a ladder of progressively cheaper-to-satisfy strategies
instead of crashing the program:

    fused  →  split  →  chunked  →  eager  →  host

* **fused**: the normal path — one jit-compiled program (possibly
  auto-segmented by ``RAMBA_TPU_MAX_PROGRAM_INSTRS``).  Where admission
  estimates the program over the HBM watermark as it stands and under it
  with its live set bounded, this rung runs that form: the same one
  program, reordered and cut into live groups with the values live at
  each cut behind an ``optimization_barrier`` (``memory._fit_live_groups``,
  ``fuser._live_grouped``).  Nothing has failed and nothing is degraded:
  one executable, one dispatch, the same donation.
* **split**: the same program re-run through the segmented executor with
  a halved segment size and no leaf donation — smaller XLA programs,
  smaller peak live set.
* **chunked**: the segmented executor bounded by estimated live *bytes*
  per segment (``fuser._run_chunked`` / ``resilience.memory``) — the
  memory-pressure rung.  Admission control can also start the ladder
  here directly, before anything has failed: for a program that is over
  the watermark even in live groups and after eviction.
* **eager**: per-op dispatch with no jit at all.
* **host**: the whole program interpreted on the CPU backend (device →
  host fallback as a first-class path; only offered single-controller).

``oom``-class failures (real or injected ``RESOURCE_EXHAUSTED``) get an
extra recovery step before the ladder moves: the memory governor evicts
spill candidates (``memory.evict_for_oom``), so the next rung starts
with more free HBM — "evict → drop one rung → retry", not blind backoff.

Each rung transition is emitted as a ``degrade`` event and counter so
``scripts/trace_report.py`` can show the degradation timeline; each rung
itself runs under the retry engine, so transient failures are retried in
place before the ladder moves at all.

The ladder never hides programming errors: anything :func:`retry.classify`
calls ``fatal`` (TypeError, KernelTraceError, ...) propagates unchanged
from whichever rung hit it.
"""

from __future__ import annotations

from typing import Callable, List, Optional, Tuple

from ramba_tpu.observe import events as _events
from ramba_tpu.observe import registry as _registry
from ramba_tpu.resilience import coherence as _coherence
from ramba_tpu.resilience import retry as _retry

#: Canonical rung order for the flush ladder.
LADDER = ("fused", "split", "chunked", "eager", "host")


def run_ladder(site: str, rungs: List[Tuple[str, Callable]], *,
               leaf_check: Optional[Callable[[], bool]] = None,
               tags: Optional[dict] = None):
    """Try ``rungs`` (ordered ``(name, thunk)`` pairs) until one succeeds.

    Each rung runs under ``retry.call(site, thunk)``.  Returns
    ``(result, rung_name)``.  Moves down a rung only for degrade-class
    failures (OOM, exhausted retry budgets); fatal errors raise from the
    rung that hit them.  ``leaf_check`` (if given) must return True for
    the ladder to continue — it guards against re-running a program whose
    donated input buffers were already consumed by a failed attempt.
    ``tags`` (e.g. ``{"tenant": ...}`` from a serving session) ride on
    every degrade event so the degradation timeline attributes to a
    tenant; None adds nothing, keeping historical events byte-identical.

    Under multi-controller execution with the coherence layer engaged,
    every rung outcome runs through a ``flush:rung`` agreement round
    (severity-max — the worst rung proposed by any rank wins): a rank
    whose attempt succeeded still drops with the fleet when a peer
    failed, so the ranks' collective schedules never diverge; a fatal
    (or donation-exhausted) outcome anywhere aborts everywhere with the
    same classification instead of one error and one hang.
    Single-controller the agreement is a byte-exact no-op.
    """
    coh = _coherence.engaged()
    rsite = f"{site}:rung"
    n = len(rungs)
    last: Optional[Exception] = None
    prev_name: Optional[str] = None
    i = 0
    while i < n:
        name, thunk = rungs[i]
        if i > 0:
            _registry.inc("resilience.degrade_steps")
            _registry.inc(f"resilience.degrade.{name}")
            _events.emit({"type": "degrade", "site": site, "action": "rung",
                          "from": prev_name, "to": name,
                          "error": _retry._errstr(last) if last else None,
                          **(tags or {})})
        out = None
        err: Optional[Exception] = None
        my = _coherence.P_OK
        if coh and i > 0 and leaf_check is not None and not leaf_check():
            # A locally-successful earlier attempt consumed this rank's
            # donated inputs, but the fleet agreed to drop anyway (a peer
            # failed).  This rank cannot run the lower rung — propose a
            # coherent abort so every rank surfaces the same terminal
            # error instead of one error and one hang.
            err = last if last is not None else RuntimeError(
                f"{site}: donated inputs consumed before rung {name!r}")
            my = _coherence.P_FATAL
        else:
            try:
                out = _retry.call(site, thunk, coherent=coh)
            except Exception as e:
                err = e
                cls = _retry.classify(e)
                if cls == "fatal":
                    if not coh:
                        raise
                    my = _coherence.P_FATAL
                elif leaf_check is not None and not leaf_check():
                    # Donated inputs are gone; a lower rung would recompute
                    # from deleted buffers.  Surface the real failure.
                    if not coh:
                        raise
                    my = _coherence.P_FATAL
                else:
                    my = _coherence.P_OOM if cls == "oom" \
                        else _coherence.P_DROP
        decision = _coherence.decide(rsite, my) if coh else my
        if decision == _coherence.P_OK:
            if i > 0:
                _registry.inc("resilience.degrade_recovered")
                _events.emit({"type": "degrade", "site": site,
                              "action": "recovered", "rung": name,
                              **(tags or {})})
            return out, name
        if decision == _coherence.P_OOM:
            # Device memory exhaustion: free HBM before the next rung
            # runs — eviction is the recovery, the rung drop is the
            # insurance.  Coherent: every rank evicts, not just the one
            # that observed the OOM.
            try:
                from ramba_tpu.resilience import memory as _memory

                _memory.evict_for_oom(
                    err if err is not None
                    else _coherence.CoherentAbort(rsite, decision))
            except Exception:
                pass
        if decision >= _coherence.P_FATAL or i + 1 >= n:
            # The raised class must match the agreed decision on every
            # rank (coherent terminal = identical classification fleet-
            # wide); the local error surfaces directly when it already
            # is that class, otherwise it rides as the abort's cause.
            if err is not None and (not coh or _retry.classify(err) ==
                                    _coherence.decision_class(decision)):
                raise err
            raise _coherence.CoherentAbort(
                rsite, decision,
                cause=_retry._errstr(err) if err is not None else None)
        last = err if err is not None \
            else _coherence.CoherentAbort(rsite, decision)
        prev_name = name
        i += 1
    raise last if last is not None else RuntimeError(
        f"{site}: empty ladder")
