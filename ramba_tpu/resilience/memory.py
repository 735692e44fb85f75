"""Memory-pressure governor: HBM budget, live-bytes ledger, spill, admission.

The degradation ladder (PR 2) can only *react* to ``RESOURCE_EXHAUSTED``;
this module exists so a flush that will not fit never reaches XLA in the
first place — the peak-memory-aware scheduling discipline of
"Memory-efficient array redistribution through portable collective
communication" (arXiv:2112.01075) applied to the fuser:

* **Budget** — per-device HBM capacity: ``RAMBA_HBM_BUDGET`` when set
  (``common.parse_bytes`` grammar, e.g. ``4g``), else the device's own
  ``memory_stats()["bytes_limit"]`` when the backend reports one (TPU/GPU
  do, CPU does not), else *no budget* — the documented CPU-test default in
  which the governor is fully disabled and the fused fast path runs with
  zero overhead beyond ledger dict upkeep.
* **Ledger** — live-bytes accounting for every realized ``Const`` leaf,
  driven by the fuser's existing owner census (``owner_incref`` /
  ``owner_decref``): entries are keyed by buffer identity and hold only
  *weak* references to the owning Const nodes, so the ledger can never
  itself pin HBM.
* **Spill** — an LRU list of cold, non-pinned, fully-addressable arrays
  that can be ``jax.device_get`` to host (``resilience.spill``) and are
  transparently re-``device_put`` on next touch.  Never spilled: donated
  leaves (owners == 0 means they are not in the ledger at all), pinned
  in-flight flush leaves, and non-fully-addressable (multi-host) shards.
* **Admission** — before a flush executes, its peak footprint is
  estimated (XLA's own ``compiled.memory_analysis()`` via an AOT lowering
  when it reports real numbers, else the analytic live-set walk in
  ``analyze.rules.estimate_peak_bytes``; ``RAMBA_HBM_ESTIMATE=analytic``
  forces the latter).  If ``live + peak`` crosses the watermark
  (``RAMBA_HBM_WATERMARK``, default 0.9 of budget) the governor first
  asks whether the same program fits with its live set bounded
  (:func:`_fit_live_groups`: ``fuser._live_grouped`` reorders it to keep
  few values live and cuts it with the byte segmenter into as few live
  groups as bring its estimate under the watermark less what is resident
  and not an argument; the values live at each cut go through an
  ``optimization_barrier``).  That program is estimated the same way and,
  when it fits, is what :func:`admit` hands back: still ONE jitted
  program on the ``fused`` rung with the same donation, admitted with
  ``ok: true`` and no ``watermark`` event.  Only a program that is still
  over when grouped is evicted for, and then — if still over — routed to
  the ``chunked`` rung (byte-bounded segments, one executable each, see
  ``fuser._run_chunked``) instead of letting it OOM.
* **OOM recovery** — ``retry.classify`` marks real and injected
  ``RESOURCE_EXHAUSTED`` as the distinct ``oom`` class; the ladder calls
  :func:`evict_for_oom` before dropping a rung, so recovery is
  "evict → drop one rung → retry", not blind backoff.

Everything observable lands on the observe stream: ``memory``-type
watermark/evict/spill/restore/admit events and the gauges
``memory.live_bytes``, ``memory.spilled_bytes``, ``memory.evictions``,
``memory.admission_rejects``, and the counter ``memory.live_grouped``
(one per flush admitted in live groups; the ``admit`` event and the
flush span carry the count as ``live_groups``).

Implementation note: expression nodes are normally immutable; the one
sanctioned mutation in the codebase is the governor swapping a
``Const.value`` between a device array and its :class:`~ramba_tpu.
resilience.spill.SpilledArray` stand-in.  Both directions go through
``fuser.owner_rekey`` so the donation census follows the buffer.
"""

from __future__ import annotations

import itertools
import os
import re
import threading
import weakref
from typing import Optional

from ramba_tpu import common as _common
from ramba_tpu.observe import events as _events
from ramba_tpu.observe import registry as _registry
from ramba_tpu.resilience import coherence as _coherence
from ramba_tpu.resilience import spill as _spill


def _nbytes(v) -> int:
    try:
        return int(v.nbytes)
    except Exception:
        return 0


def _is_device_array(v) -> bool:
    import jax

    return isinstance(v, jax.Array)


def _current_tenant() -> Optional[str]:
    """Tenant of the active flush stream (serving sessions), None outside
    one.  Lazy import: the fuser imports this module at its own import."""
    try:
        from ramba_tpu.core import fuser as _fuser

        return _fuser.current_tenant()
    except Exception:
        return None


# ---------------------------------------------------------------------------
# budget / watermark
# ---------------------------------------------------------------------------

# memory_stats() probe result: unset | int | None (backend reports nothing).
_device_budget: object = "unset"


def device_budget_bytes() -> Optional[int]:
    """The backend-reported per-device HBM capacity, probed once."""
    global _device_budget
    if _device_budget == "unset":
        limit = None
        try:
            import jax

            stats = jax.local_devices()[0].memory_stats()
            if stats:
                limit = int(stats.get("bytes_limit") or 0) or None
        except Exception:
            limit = None
        _device_budget = limit
    return _device_budget  # type: ignore[return-value]


def budget_bytes() -> Optional[int]:
    """Effective per-device budget; None disables the governor entirely
    (the documented default on CPU test backends, which report no
    ``bytes_limit``)."""
    raw = os.environ.get("RAMBA_HBM_BUDGET")
    if raw:
        try:
            return max(1, _common.parse_bytes(raw))
        except ValueError:
            pass
    return device_budget_bytes()


def watermark_bytes(budget: Optional[int] = None) -> Optional[int]:
    """Admission threshold: ``RAMBA_HBM_WATERMARK`` as a fraction of the
    budget when ≤ 1.0, an absolute byte count otherwise; default 0.9."""
    if budget is None:
        budget = budget_bytes()
    if budget is None:
        return None
    raw = os.environ.get("RAMBA_HBM_WATERMARK")
    if raw:
        try:
            v = float(raw)
            if 0.0 < v <= 1.0:
                return int(budget * v)
        except ValueError:
            pass
        try:
            return max(1, _common.parse_bytes(raw))
        except ValueError:
            pass
    return int(budget * 0.9)


def chunk_target_bytes() -> int:
    """Per-segment live-byte target for the ``chunked`` rung.  Derived
    from the watermark when a budget is known; otherwise
    ``RAMBA_CHUNK_BYTES`` (default 256 MiB) so the rung still works as a
    plain ladder fallback on budgetless backends.

    The chunk budget determines segment boundaries — program structure —
    so under coherent multi-controller execution it is min-agreed across
    ranks (tightest budget wins) before anyone cuts a segment."""
    raw = os.environ.get("RAMBA_CHUNK_BYTES")
    target = None
    if raw:
        try:
            target = max(1, _common.parse_bytes(raw))
        except ValueError:
            pass
    if target is None:
        b = budget_bytes()
        if b:
            target = max(1 << 16, (watermark_bytes(b) or b) // 4)
        else:
            target = 256 << 20
    if _coherence.engaged():
        # 64 KiB granularity keeps byte counts inside the int32 transport.
        target = max(1 << 16, _coherence.agree(
            "memory:chunk_bytes", target >> 16, reduce="min") << 16)
    return target


# ---------------------------------------------------------------------------
# the ledger
# ---------------------------------------------------------------------------


class _Entry:
    __slots__ = ("key", "nbytes", "consts", "seq", "pins", "spilled",
                 "tenant")

    def __init__(self, key: int, nbytes: int, seq: int, spilled: bool,
                 tenant: Optional[str] = None):
        self.key = key          # id() of the current value object
        self.nbytes = nbytes    # HBM footprint when resident
        self.consts: list = []  # weakrefs to the owning Const nodes
        self.seq = seq          # LRU clock: higher = touched more recently
        self.pins = 0           # >0 while a flush holds this as a leaf
        self.spilled = spilled
        self.tenant = tenant    # serving tenant that materialized it


class Ledger:
    """Live-bytes accounting over every realized leaf buffer.

    Holds no strong references to buffers or Consts — entries die with
    the owner census (``on_release``) or when every owning Const is
    garbage-collected, so the ledger can never leak HBM.
    """

    def __init__(self):
        self.entries: dict = {}
        self.live_bytes = 0
        self.spilled_bytes = 0
        self.peak_live_bytes = 0
        self.evictions = 0
        self.restores = 0
        # bytes placed through governed_device_put that are still alive
        # but not (yet) census-owned — padded stencil operands, reshard
        # stage buffers.  Counted into peak_live_bytes so transient
        # device traffic cannot hide from the bookkeeping.
        self.transient_bytes = 0
        # tenant -> resident (non-spilled) bytes, for serving quotas.
        # Keys appear on first materialization under a serve.Session.
        self.tenant_live: dict = {}
        self._clock = itertools.count(1)
        # RLock: public methods lock, and evict_until -> _spill_entry
        # re-enters.  Lock order is memory -> fuser census (owner_rekey);
        # the fuser never calls into the ledger while holding its census
        # lock, so the pair cannot deadlock.
        self._lock = threading.RLock()

    def _tenant_add(self, e: "_Entry", sign: int) -> None:
        if e.tenant is None:
            return
        n = self.tenant_live.get(e.tenant, 0) + sign * e.nbytes
        self.tenant_live[e.tenant] = max(0, n)

    # -- census hooks (called from fuser.owner_incref/owner_decref) --------

    def on_incref(self, const) -> None:
        v = const.value
        k = id(v)
        with self._lock:
            e = self.entries.get(k)
            if e is None:
                spilled = isinstance(v, _spill.SpilledArray)
                if not spilled and not _is_device_array(v):
                    return
                e = _Entry(k, _nbytes(v), next(self._clock), spilled,
                           tenant=_current_tenant())
                self.entries[k] = e
                if spilled:
                    self.spilled_bytes += e.nbytes
                else:
                    self.live_bytes += e.nbytes
                    self._tenant_add(e, +1)
                    if self.live_bytes > self.peak_live_bytes:
                        self.peak_live_bytes = self.live_bytes
            else:
                e.seq = next(self._clock)
            for r in e.consts:
                if r() is const:
                    return
            e.consts.append(weakref.ref(const))

    def on_release(self, value) -> None:
        with self._lock:
            e = self.entries.pop(id(value), None)
            if e is None:
                return
            if e.spilled:
                self.spilled_bytes -= e.nbytes
            else:
                self.live_bytes -= e.nbytes
                self._tenant_add(e, -1)

    def _drop(self, e: "_Entry") -> None:
        """Remove an entry whose owners all died without a decref."""
        with self._lock:
            if self.entries.pop(e.key, None) is None:
                return
            if e.spilled:
                self.spilled_bytes -= e.nbytes
            else:
                self.live_bytes -= e.nbytes
                self._tenant_add(e, -1)

    # -- pinning (in-flight flush leaves are never spill candidates) -------

    def pin_values(self, vals) -> list:
        keys = []
        with self._lock:
            for v in vals:
                e = self.entries.get(id(v))
                if e is not None:
                    e.pins += 1
                    e.seq = next(self._clock)
                    keys.append(e.key)
        return keys

    def unpin(self, keys) -> None:
        with self._lock:
            for k in keys:
                e = self.entries.get(k)
                if e is not None and e.pins > 0:
                    e.pins -= 1

    def touch(self, value) -> None:
        with self._lock:
            e = self.entries.get(id(value))
            if e is not None:
                e.seq = next(self._clock)

    # -- spill / restore ----------------------------------------------------

    def _live_consts(self, e: "_Entry") -> list:
        return [c for c in (r() for r in e.consts) if c is not None]

    def _spill_entry(self, e: "_Entry") -> int:
        """Spill one resident entry to host.  Returns HBM bytes freed.
        Caller must hold ``self._lock``."""
        if e.spilled or e.pins:
            return 0
        consts = self._live_consts(e)
        if not consts:
            self._drop(e)
            return 0
        v = consts[0].value
        if not _is_device_array(v):
            return 0
        try:
            if v.is_deleted() or not v.is_fully_addressable:
                return 0
        except Exception:
            return 0
        if e.nbytes <= 0:
            return 0
        wrapper = _spill.spill_to_host(v)
        for c in consts:
            c.value = wrapper
        from ramba_tpu.core import fuser as _fuser

        _fuser.owner_rekey(v, wrapper)
        del self.entries[e.key]
        e.key = id(wrapper)
        e.consts = [weakref.ref(c) for c in consts]
        e.spilled = True
        self.entries[e.key] = e
        self.live_bytes -= e.nbytes
        self._tenant_add(e, -1)
        self.spilled_bytes += e.nbytes
        self.evictions += 1
        _registry.inc("memory.evictions")
        _update_gauges(self)
        _events.emit({
            "type": "memory", "action": "spill", "bytes": e.nbytes,
            "shape": list(wrapper.shape), "dtype": str(wrapper.dtype),
            "live_bytes": self.live_bytes,
            "spilled_bytes": self.spilled_bytes,
        })
        return e.nbytes

    def restore(self, const):
        """Bring a spilled Const back onto the device (all sibling Consts
        sharing the buffer are updated) and return the jax.Array."""
        with self._lock:
            wrapper = const.value
            if not isinstance(wrapper, _spill.SpilledArray):
                return wrapper
            e = self.entries.get(id(wrapper))
            arr = _spill.restore_to_device(wrapper)
            consts = self._live_consts(e) if e is not None else []
            if not any(c is const for c in consts):
                consts.append(const)
            for c in consts:
                c.value = arr
            from ramba_tpu.core import fuser as _fuser

            _fuser.owner_rekey(wrapper, arr)
            nbytes = _nbytes(arr) or wrapper.device_nbytes
            if e is not None:
                del self.entries[e.key]
                e.key = id(arr)
                e.consts = [weakref.ref(c) for c in consts]
                e.spilled = False
                e.seq = next(self._clock)
                self.entries[e.key] = e
                self.spilled_bytes -= e.nbytes
                e.nbytes = nbytes
                self.live_bytes += e.nbytes
                self._tenant_add(e, +1)
                if self.live_bytes > self.peak_live_bytes:
                    self.peak_live_bytes = self.live_bytes
            self.restores += 1
        _registry.inc("memory.restores")
        _update_gauges(self)
        _events.emit({
            "type": "memory", "action": "restore", "bytes": nbytes,
            "live_bytes": self.live_bytes,
            "spilled_bytes": self.spilled_bytes,
        })
        return arr

    def swap_value(self, old, new) -> bool:
        """Replace a resident buffer with ``new`` in place: every live
        Const owning ``old`` is repointed, the fuser census is rekeyed,
        and the ledger entry follows the buffer (nbytes delta included).
        The sanctioned commit path for reshard/live-reshape, mirroring
        ``_spill_entry``'s rekey discipline.  Returns False when ``old``
        is not tracked (caller keeps both values alive; nothing swapped).
        """
        if old is new:
            return True
        with self._lock:
            e = self.entries.get(id(old))
            if e is None:
                return False
            consts = self._live_consts(e)
            for c in consts:
                c.value = new
            from ramba_tpu.core import fuser as _fuser

            _fuser.owner_rekey(old, new)
            del self.entries[e.key]
            e.key = id(new)
            e.consts = [weakref.ref(c) for c in consts]
            e.seq = next(self._clock)
            self.entries[e.key] = e
            new_nbytes = _nbytes(new)
            if not e.spilled:
                self.live_bytes += new_nbytes - e.nbytes
                self._tenant_add(e, -1)
                e.nbytes = new_nbytes
                self._tenant_add(e, +1)
                if self.live_bytes > self.peak_live_bytes:
                    self.peak_live_bytes = self.live_bytes
            else:
                self.spilled_bytes += new_nbytes - e.nbytes
                e.nbytes = new_nbytes
        _update_gauges(self)
        return True

    # -- transient (non-census) placements ---------------------------------

    def _begin_transient(self, nbytes: int) -> None:
        with self._lock:
            self.transient_bytes += nbytes
            peak = self.live_bytes + self.transient_bytes
            if peak > self.peak_live_bytes:
                self.peak_live_bytes = peak

    def _end_transient(self, nbytes: int) -> None:
        with self._lock:
            self.transient_bytes = max(0, self.transient_bytes - nbytes)

    def evict_until(self, need: int, tenant: Optional[str] = None) -> int:
        """Spill LRU-coldest candidates until ``need`` bytes are freed (or
        candidates run out).  Returns bytes actually freed.  ``tenant``
        restricts candidates to that tenant's own entries — quota
        enforcement must reclaim from the over-quota tenant, never evict
        a neighbor to make room for it."""
        with self._lock:
            freed = 0
            cands = [e for e in list(self.entries.values())
                     if not e.spilled and not e.pins
                     and (tenant is None or e.tenant == tenant)]
            cands.sort(key=lambda e: e.seq)
            for e in cands:
                if freed >= need:
                    break
                freed += self._spill_entry(e)
            return freed

    # -- reporting ----------------------------------------------------------

    def tenant_snapshot(self) -> dict:
        """Copy of the nonzero per-tenant resident byte counts, taken
        under the ledger lock — the public read serve.tenant_report()
        and the metrics exporter use instead of reaching into _lock."""
        with self._lock:
            return {t: b for t, b in self.tenant_live.items() if b}

    def snapshot(self, top: int = 5) -> dict:
        with self._lock:
            rows = []
            pinned = 0
            for e in list(self.entries.values()):
                consts = self._live_consts(e)
                if not consts:
                    self._drop(e)
                    continue
                if e.pins and not e.spilled:
                    pinned += e.nbytes
                v = consts[0].value
                rows.append({
                    "nbytes": e.nbytes,
                    "shape": list(getattr(v, "shape", ())),
                    "dtype": str(getattr(v, "dtype", "?")),
                    "spilled": e.spilled,
                    "pinned": e.pins,
                    "owners": len(consts),
                    **({"tenant": e.tenant} if e.tenant else {}),
                })
            rows.sort(key=lambda r: r["nbytes"], reverse=True)
            _update_gauges(self)
            out = {
                "budget_bytes": budget_bytes(),
                "watermark_bytes": watermark_bytes(),
                "live_bytes": self.live_bytes,
                "spilled_bytes": self.spilled_bytes,
                "pinned_bytes": pinned,
                "transient_bytes": self.transient_bytes,
                "peak_live_bytes": self.peak_live_bytes,
                "evictions": self.evictions,
                "restores": self.restores,
                "arrays": len(rows),
                "top": rows[:top],
            }
            if any(self.tenant_live.values()):
                out["tenant_live_bytes"] = {
                    t: b for t, b in sorted(self.tenant_live.items()) if b
                }
            return out


def _update_gauges(led: "Ledger") -> None:
    _registry.gauge("memory.live_bytes", led.live_bytes)
    _registry.gauge("memory.spilled_bytes", led.spilled_bytes)


#: Process-wide ledger singleton (the fuser census hooks feed this).
ledger = Ledger()


def reset() -> None:
    """Forget all accounting (tests).  Does NOT restore spilled arrays."""
    global ledger, _device_budget
    ledger = Ledger()
    _device_budget = "unset"
    _est_memo.clear()


# ---------------------------------------------------------------------------
# footprint estimation
# ---------------------------------------------------------------------------

_est_memo: dict = {}
_EST_MEMO_MAX = 256


def _leaf_avals(leaf_vals) -> list:
    import jax
    import numpy as np

    avals = []
    for v in leaf_vals:
        if _is_device_array(v):
            # with the array's own layout where the backend has layouts:
            # the estimate is of the program a call would run
            for place in ("format", "sharding"):
                try:
                    avals.append(jax.ShapeDtypeStruct(
                        v.shape, v.dtype, sharding=getattr(v, place)))
                    break
                except Exception:
                    continue
            else:
                avals.append(jax.ShapeDtypeStruct(v.shape, v.dtype))
            continue
        a = np.asarray(v)
        avals.append(jax.ShapeDtypeStruct(a.shape, a.dtype))
    return avals


def _xla_estimate(program, avals) -> Optional[int]:
    """XLA's own numbers via an AOT lowering (the ``analyze_pending``
    pattern): argument + output + temp sizes, of the callable the fused
    rung would jit for ``program`` (barriers and all, when it carries
    ``live_cuts``).  Returns None when the backend reports nothing usable
    (CPU typically reports zeros)."""
    import jax

    from ramba_tpu.core import fuser as _fuser

    from ramba_tpu.core import layouts as _layouts

    compiled = _layouts.RowMajorJit(
        _fuser._build_callable(program)).lower(*avals).compile()
    ma = compiled.memory_analysis()
    total = 0
    for name in ("argument_size_in_bytes", "output_size_in_bytes",
                 "temp_size_in_bytes"):
        v = getattr(ma, name, None)
        if v:
            total += int(v)
    return total if total > 0 else None


def _segmented_estimate(program, avals, donate) -> Optional[int]:
    """The same for a program the fused rung runs as chained segments
    (``fuser._run_segmented``): the worst segment's own numbers plus the
    values that are live past it and not its arguments (leaves the caller
    keeps, values carried to a later segment).  Each distinct segment is
    lowered once; the whole program never is, which is what segmenting
    is for.  Values between segments carry shapes only, as in
    ``fuser.analyze_pending``."""
    import jax

    from ramba_tpu.analyze.rules import _aval_nbytes
    from ramba_tpu.core import fuser as _fuser

    last_use = _fuser._last_use_map(program)
    donated = set(donate)
    live = dict(enumerate(avals))
    nbytes = {s: _aval_nbytes(a) for s, a in live.items()}
    seen: dict = {}
    peak = 0
    for seg, in_slots, out_here, top in _fuser._iter_segments(program,
                                                              last_use):
        seg_avals = [live[s] for s in in_slots]
        key = _est_key(seg, seg_avals, ())
        if key not in seen:
            est = _xla_estimate(seg, seg_avals)
            if est is None:
                return None
            seen[key] = est, jax.eval_shape(_fuser._build_callable(seg),
                                            *seg_avals)
        est, outs = seen[key]
        peak = max(peak, est + sum(nbytes.values())
                   - sum(nbytes[s] for s in in_slots))
        for s in in_slots:
            if last_use.get(s, 0) < top and (s >= program.n_leaves
                                             or s in donated):
                del live[s], nbytes[s]
        for s, a in zip(out_here, outs):
            live[s], nbytes[s] = a, _aval_nbytes(a)
    return peak


def _est_key(program, avals, donate) -> tuple:
    return (program.key, tuple(donate),
            tuple((tuple(a.shape), str(a.dtype)) for a in avals))


def estimate_program_bytes(program, leaf_vals, donate=()) -> int:
    """Peak device footprint estimate for one linearized program.

    Prefers ``compiled.memory_analysis()`` (memoized per structure+avals —
    the AOT compile is paid once per program shape, and jax's own
    executable cache makes the later ``jax.jit`` call cheap); falls back
    to the analytic live-set walk in ``analyze.rules`` when XLA reports
    nothing (CPU) or ``RAMBA_HBM_ESTIMATE=analytic`` forces determinism.
    """
    avals = _leaf_avals(leaf_vals)
    fp = _est_key(program, avals, donate)
    cached = _est_memo.get(fp)
    if cached is not None:
        return cached
    est: Optional[int] = None
    if os.environ.get("RAMBA_HBM_ESTIMATE", "") != "analytic":
        cap = _common.max_program_instrs
        try:
            if cap and len(program.instrs) > cap:
                est = _segmented_estimate(program, avals, donate)
            else:
                est = _xla_estimate(program, avals)
        except Exception:
            est = None
    if est is None:
        from ramba_tpu.analyze import rules as _rules

        est = _rules.estimate_peak_bytes(program, avals, donate)
    if len(_est_memo) >= _EST_MEMO_MAX:
        _est_memo.clear()
    _est_memo[fp] = est
    return est


# ---------------------------------------------------------------------------
# admission control + oom recovery
# ---------------------------------------------------------------------------


def _resident_overlap(leaf_vals, tenant: Optional[str] = None) -> int:
    """Resident bytes among ``leaf_vals`` already counted by the ledger
    (optionally only entries belonging to ``tenant``): the program
    estimate counts its arguments too, so they must not be double-billed.
    Caller need not hold the ledger lock."""
    resident = 0
    seen: set = set()
    with ledger._lock:
        for v in leaf_vals:
            k = id(v)
            if k in seen:
                continue
            seen.add(k)
            e = ledger.entries.get(k)
            if e is not None and not e.spilled and (
                tenant is None or e.tenant == tenant
            ):
                resident += e.nbytes
    return resident


#: Lowerings one admission may spend looking for a grouping that fits:
#: each is a whole compile of the program.
_GROUPED_LOWERINGS = 3


def _fit_live_groups(program, leaf_vals, donate_key, est: int, room: int):
    """The same program with its live set bounded (``fuser._live_grouped``)
    in as few groups as bring its estimate under ``room``, given that as
    it stands it needs ``est``.  Returns ``(grouped program, its
    estimate)``, or None when no grouping found fits; memoized beside
    the estimates, so a steady flush pays a lookup.

    The first count is the share of the room the program overflows by;
    after a grouping that is still over, the two readings give the part
    of the estimate that grouping does not shrink (arguments, outputs,
    the largest single step) and the part that falls as 1/groups, and so
    the next count, or the verdict that none can fit."""
    from ramba_tpu.core import fuser as _fuser

    if room <= 0 or len(program.instrs) < 2:
        return None
    avals = _leaf_avals(leaf_vals)
    memo_key = ("live_groups", room) + _est_key(program, avals, donate_key)
    if memo_key in _est_memo:
        return _est_memo[memo_key]
    groups = max(2, -(-est // room))
    found, n = None, 1
    for _ in range(_GROUPED_LOWERINGS):
        grouped = _fuser._live_grouped(program, avals, groups)
        if grouped is None or grouped.live_groups <= n:
            break  # no cut left to make
        n = grouped.live_groups
        est_n = estimate_program_bytes(grouped, leaf_vals, donate_key)
        if est_n <= room:
            found = (grouped, est_n)
            break
        shrinks = (est - est_n) * n // (n - 1)
        fixed = est - shrinks
        if shrinks <= 0 or fixed >= room:
            break
        groups = max(n + 1, -(-shrinks // (room - fixed)))
    if len(_est_memo) >= _EST_MEMO_MAX:
        _est_memo.clear()
    _est_memo[memo_key] = found
    return found


def _admit_budget(program, leaf_vals, donate_key,
                  span: Optional[dict] = None):
    """The global-budget admission leg (historical ``admit`` body).
    Returns ``(route chunked, the program the fused rung runs)``: the
    program as it stands when its estimate is under the watermark (and
    when no budget is known: a no-op), else the same program with its
    live set grouped when that fits, else evict, then route chunked."""
    budget = budget_bytes()
    if budget is None:
        return False, program
    wm = watermark_bytes(budget) or budget
    est = estimate_program_bytes(program, leaf_vals, donate_key)
    # ledger.live already counts this flush's resident leaves; the program
    # estimate counts its arguments too — subtract the overlap so leaves
    # are not double-billed.
    resident = _resident_overlap(leaf_vals)
    other = max(0, ledger.live_bytes - resident)
    if other + est > wm:
        # before anything is evicted: grouping costs a few array passes,
        # a spill costs a trip to the host
        fit = _fit_live_groups(program, leaf_vals, donate_key, est,
                               wm - other)
        if fit is not None:
            if span is not None:
                span["mem_peak_est_ungrouped"] = est
            program, est = fit
            _registry.inc("memory.live_grouped")
    projected = other + est
    if span is not None:
        span["mem_live_bytes"] = ledger.live_bytes
        span["mem_peak_est"] = est
    _update_gauges(ledger)
    _events.emit({
        "type": "memory", "action": "admit", "est_bytes": est,
        "live_bytes": ledger.live_bytes, "projected_bytes": projected,
        "watermark_bytes": wm, "budget_bytes": budget,
        "ok": projected <= wm, "live_groups": program.live_groups,
    })
    if projected <= wm:
        return False, program
    _events.emit({
        "type": "memory", "action": "watermark",
        "over_bytes": projected - wm, "watermark_bytes": wm,
    })
    freed = ledger.evict_until(projected - wm)
    if projected - freed <= wm:
        if span is not None:
            span["admission"] = "evicted"
        return False, program
    _registry.inc("memory.admission_rejects")
    _registry.gauge("memory.admission_rejects.last_over_bytes",
                    projected - freed - wm)
    _events.emit({
        "type": "memory", "action": "reject", "route": "chunked",
        "est_bytes": est, "freed_bytes": freed,
        "over_bytes": projected - freed - wm,
    })
    if span is not None:
        span["admission"] = "chunked"
    return True, program


def _admit_tenant(program, leaf_vals, donate_key, span: Optional[dict],
                  tenant: str, quota: int) -> bool:
    """Per-tenant quota admission (serving sessions).  Independent of the
    global budget — quotas must work on budgetless backends (CPU tests)
    — and reclaims only from the over-quota tenant's OWN entries before
    routing its flush chunked: a tenant blowing its quota degrades that
    tenant, never a neighbor."""
    est = estimate_program_bytes(program, leaf_vals, donate_key)
    with ledger._lock:
        tenant_resident = ledger.tenant_live.get(tenant, 0)
    other = max(0, tenant_resident - _resident_overlap(leaf_vals, tenant))
    projected = other + est
    if projected <= quota:
        return False
    freed = ledger.evict_until(projected - quota, tenant=tenant)
    if projected - freed <= quota:
        if span is not None:
            span["tenant_admission"] = "evicted"
        return False
    _registry.inc("serve.quota_rejects")
    _registry.inc(f"serve.tenant.{tenant}.quota_rejects")
    _events.emit({
        "type": "memory", "action": "reject", "route": "chunked",
        "tenant": tenant, "quota_bytes": quota,
        "est_bytes": est, "freed_bytes": freed,
        "over_bytes": projected - freed - quota,
    })
    if span is not None:
        span["tenant_admission"] = "chunked"
    return True


def admit(program, leaf_vals, donate_key, span: Optional[dict] = None, *,
          tenant: Optional[str] = None,
          quota: Optional[int] = None):
    """Pre-flush admission check.  Returns ``(route, program)``.
    ``route`` is True when the flush should be routed to the ``chunked``
    rung — it does not fit under the global watermark, grouped or after
    eviction, OR it would push ``tenant`` past its serving ``quota`` even
    after evicting that tenant's own cold arrays; False admits the fused
    path, for ``program``: the one handed in, or its live-grouped form
    where only that fits under the watermark.  The global leg is a no-op
    when no budget is known; the tenant leg runs whenever a quota is
    given."""
    asked = program
    route, program = _admit_budget(program, leaf_vals, donate_key, span)
    if tenant is not None and quota:
        if _admit_tenant(program, leaf_vals, donate_key, span, tenant,
                         int(quota)):
            route = True
    if _coherence.engaged() and (budget_bytes() is not None
                                 or (tenant is not None and quota)):
        # Routing to chunked changes program structure; when any rank's
        # governor is armed, all ranks agree (chunked anywhere → chunked
        # everywhere).  Budgetless, quota-less flushes skip the round so
        # the healthy CPU path stays collective-free.
        agreed = bool(_coherence.agree("memory:admit",
                                       1 if route else 0, reduce="max"))
        if agreed and not route and span is not None:
            span["admission"] = "coherent"
        route = agreed
        # the cuts are program structure too: every rank runs as many
        # groups as the rank that needs the most
        groups = _coherence.agree("memory:live_groups",
                                  program.live_groups, reduce="max")
        if groups > program.live_groups:
            from ramba_tpu.core import fuser as _fuser

            program = _fuser._live_grouped(
                asked, _leaf_avals(leaf_vals), groups) or program
    return route, program


_OOM_BYTES_RE = re.compile(r"(\d{4,})\s*bytes|[Aa]llocating\s+(\d+)")


def evict_for_oom(exc: BaseException) -> int:
    """Ladder hook for oom-class failures: free at least the amount the
    error asked for (injected faults carry ``.bytes``; real XLA messages
    usually name the allocation size), or everything unpinned when the
    size is unknown.  Returns bytes freed."""
    need = getattr(exc, "bytes", None)
    if not need:
        m = _OOM_BYTES_RE.search(str(exc))
        if m:
            need = int(m.group(1) or m.group(2))
    if not need:
        need = ledger.live_bytes or 1
    if _coherence.engaged():
        # Evictions change which buffers are resident — structure the
        # next rung depends on — so the need is max-agreed: every rank
        # frees at least what the worst-off rank asked for (ceil to the
        # 64 KiB transport granularity so small needs never round to 0).
        need = max(1, _coherence.agree(
            "memory:oom_evict", (int(need) + 0xFFFF) >> 16,
            reduce="max") << 16)
    freed = ledger.evict_until(int(need))
    _events.emit({
        "type": "memory", "action": "oom_evict", "need_bytes": int(need),
        "freed_bytes": freed, "live_bytes": ledger.live_bytes,
    })
    return freed


# ---------------------------------------------------------------------------
# governor-accounted placement
# ---------------------------------------------------------------------------


def reserve_headroom(nbytes: int, *, site: str = "transient") -> int:
    """Make room for an ``nbytes`` placement: when a budget is known and
    ``live + transient + nbytes`` crosses the watermark, spill LRU
    victims until it fits (or candidates run out).  Returns bytes freed;
    0 when no budget is armed or the placement already fits.  This is
    the admission check for non-census device traffic — reshard stage
    buffers, padded operand copies."""
    budget = budget_bytes()
    if budget is None or nbytes <= 0:
        return 0
    wm = watermark_bytes(budget) or budget
    with ledger._lock:
        projected = ledger.live_bytes + ledger.transient_bytes + int(nbytes)
    if projected <= wm:
        return 0
    _events.emit({
        "type": "memory", "action": "watermark", "site": site,
        "over_bytes": projected - wm, "watermark_bytes": wm,
    })
    return ledger.evict_until(projected - wm)


def governed_device_put(value, sharding=None, *, site: str = "device_put"):
    """``jax.device_put`` with admission through the HBM governor.

    Device placements outside the fuser's owner census — padded stencil
    operands in ``skeletons.spmd``, reshard stage buffers — used to be
    invisible to the ledger: no admission check, no peak-live
    accounting.  This is their sanctioned path:

    1. admission: when a budget is known and ``live + transient +
       nbytes`` crosses the watermark, LRU victims are spilled first
       (``evict_until``) — a near-budget placement spills instead of
       OOMing;
    2. placement: plain ``jax.device_put``;
    3. accounting: the buffer's bytes ride in
       ``ledger.transient_bytes`` (and therefore ``peak_live_bytes``)
       until the returned array is garbage-collected, via a weakref
       finalizer — no caller-side release protocol.

    Zero-cost when the value has no measurable size; budgetless
    backends skip admission but still account the transient peak.
    """
    import jax

    nbytes = _nbytes(value)
    reserve_headroom(nbytes, site=site)
    out = jax.device_put(value, sharding) if sharding is not None \
        else jax.device_put(value)
    placed = _nbytes(out) or nbytes
    if placed > 0:
        ledger._begin_transient(placed)
        weakref.finalize(out, ledger._end_transient, placed)
        _registry.inc("memory.governed_puts")
        _events.emit({
            "type": "memory", "action": "governed_put", "site": site,
            "bytes": placed, "live_bytes": ledger.live_bytes,
            "transient_bytes": ledger.transient_bytes,
        })
    return out


# ---------------------------------------------------------------------------
# module-level conveniences used by the fuser hot path
# ---------------------------------------------------------------------------


def on_incref(const) -> None:
    ledger.on_incref(const)


def on_release(value) -> None:
    ledger.on_release(value)


def restore(const):
    return ledger.restore(const)


def is_spilled(value) -> bool:
    return isinstance(value, _spill.SpilledArray)
