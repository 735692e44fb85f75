"""Per-compiled-kernel cost ledger (`ramba-perf`).

The flush span stream (observe/events.py) records *that* a flush happened
and what it cost in aggregate; this module attributes cost to the unit
users actually pay for — the compiled kernel:

* **Ledger.**  Every compile-cache interaction and every execution in
  ``core/fuser.py`` (all rungs: fused/split/chunked/eager/host) lands in
  one entry per kernel, keyed by a *stable fingerprint* of the fuser's
  full ``_cache_key`` (structure + donation mask + semantic regime).
  Entries carry compile wall time, rolling execution stats
  (count/total/min/max/p50/p95 over the last ``RAMBA_PERF_WINDOW``
  samples), bytes in/out, cache hit/miss/evict counts, per-rung
  execution counts, and — when XLA's AOT ``cost_analysis()`` is
  available and ``RAMBA_PERF`` is on — analytic flops / bytes-accessed.
  Accumulation is ALWAYS on: it is a few dict operations per dispatch,
  cheap against the dispatch itself.
* **Timing regimes.**  Execution samples are dispatch-time by default
  (the async-dispatch wall the rest of the span machinery already
  measures, so the hot path is unperturbed).  ``RAMBA_PERF=sync``
  additionally records ``block_until_ready``-synchronized samples in a
  separate rolling window — device time, at the cost of serializing
  dispatch.
* **Flush walls.**  Each flush's wall time feeds a rolling window per
  flush program and per (program, rung): the history the hedged
  dispatch and the deadline-aware ladder (serve/overload.py) size their
  thresholds from.  Nothing here compares a flush with that history.

Environment:

* ``RAMBA_PERF`` — unset/0: ledger on, cost_analysis off (default);
  ``1``/``on``: + capture XLA cost_analysis per new kernel; ``sync``:
  all of that + synchronized execution timing.
* ``RAMBA_PERF_WINDOW`` — rolling-window length (default 64).

Read APIs: ``snapshot()`` here and
``ramba_tpu.diagnostics.perf_report()``.
"""

from __future__ import annotations

import hashlib
import os
import threading
import time as _time
from collections import deque
from contextlib import contextmanager
from typing import Optional

from ramba_tpu.observe import events as _events
from ramba_tpu.observe import observer as _observer
from ramba_tpu.observe import registry as _registry

# Guards every mutable store below (_kernels, _flush_walls, _fp_memo, the
# per-entry rolling windows): concurrent serving streams record into the
# ledger from many threads.  RLock so snapshot() can call entry.summary()
# which reads the same state.
_lock = threading.RLock()


# ---------------------------------------------------------------------------
# configuration (re-readable for tests via reconfigure())
# ---------------------------------------------------------------------------


def _parse_mode(v: Optional[str]) -> str:
    if not v or v in ("0", "off", "false", "no"):
        return ""
    if v.strip().lower() == "sync":
        return "sync"
    return "on"


_mode = ""
# flush walls a program needs before flush_quantile/rung_quantile answer
_min_samples = 5
_window = 64


def reconfigure(*, mode: Optional[str] = None,
                min_samples: Optional[int] = None,
                window: Optional[int] = None) -> None:
    """Reload configuration from the environment, with explicit keyword
    overrides (tests).  Existing rolling windows keep their old length;
    only windows created after a ``window`` change use the new one."""
    global _mode, _min_samples, _window
    _mode = _parse_mode(os.environ.get("RAMBA_PERF")) if mode is None \
        else _parse_mode(mode)
    _min_samples = 5 if min_samples is None else int(min_samples)
    try:
        _window = max(4, int(
            os.environ.get("RAMBA_PERF_WINDOW", "64") or 64
        ) if window is None else int(window))
    except ValueError:
        _window = 64


def mode() -> str:
    return _mode


def sync_timing() -> bool:
    return _mode == "sync"


def cost_enabled() -> bool:
    return _mode in ("on", "sync")


# ---------------------------------------------------------------------------
# rolling statistics
# ---------------------------------------------------------------------------


class _Rolling:
    """Count/total/min/max over the full history + quantiles over a
    bounded window of the most recent samples."""

    __slots__ = ("count", "total", "min", "max", "window")

    def __init__(self, window: Optional[int] = None):
        self.count = 0
        self.total = 0.0
        self.min: Optional[float] = None
        self.max: Optional[float] = None
        self.window: "deque[float]" = deque(maxlen=window or _window)

    def add(self, s: float) -> None:
        self.count += 1
        self.total += s
        if self.min is None or s < self.min:
            self.min = s
        if self.max is None or s > self.max:
            self.max = s
        self.window.append(s)

    def quantile(self, q: float) -> Optional[float]:
        """Nearest-rank quantile over the rolling window (None when
        empty)."""
        if not self.window:
            return None
        srt = sorted(self.window)
        idx = max(0, min(len(srt) - 1,
                         int(-(-q * len(srt) // 1)) - 1))  # ceil - 1
        return srt[idx]

    def summary(self) -> dict:
        out = {
            "count": self.count,
            "total_s": round(self.total, 6),
            "min_s": round(self.min, 6) if self.min is not None else None,
            "max_s": round(self.max, 6) if self.max is not None else None,
        }
        p50, p95 = self.quantile(0.50), self.quantile(0.95)
        out["p50_s"] = round(p50, 6) if p50 is not None else None
        out["p95_s"] = round(p95, 6) if p95 is not None else None
        return out


# ---------------------------------------------------------------------------
# stable kernel fingerprints
# ---------------------------------------------------------------------------


def _token(x) -> str:
    """Canonical serialization of one cache-key element: stable across
    processes (no ``id()``-bearing reprs), so two SPMD ranks fingerprint
    the same program identically.  Plain values serialize by repr;
    anything that could embed a memory address (closures in statics,
    array objects) degrades to its type/qualname."""
    if x is None or isinstance(x, (bool, int, float, str, bytes)):
        return repr(x)
    if isinstance(x, (tuple, list)):
        return "(" + ",".join(_token(i) for i in x) + ")"
    if isinstance(x, dict):
        items = sorted(x.items(), key=lambda kv: repr(kv[0]))
        return "{" + ",".join(_token(k) + ":" + _token(v)
                              for k, v in items) + "}"
    name = getattr(x, "__qualname__", None) or getattr(x, "__name__", None)
    if name:
        return f"<{type(x).__name__}:{name}>"
    return f"<{type(x).__module__}.{type(x).__name__}>"


_fp_memo: dict = {}
_FP_MEMO_MAX = 4096


def fingerprint(cache_key) -> str:
    """12-hex stable fingerprint of a fuser ``_cache_key`` tuple.
    Memoized on the (hashable) key tuple itself so the hot path pays one
    dict lookup per flush, not a re-serialization."""
    try:
        fp = _fp_memo.get(cache_key)
    except TypeError:  # unhashable element snuck in: serialize every time
        return hashlib.sha256(_token(cache_key).encode()).hexdigest()[:12]
    if fp is None:
        fp = hashlib.sha256(_token(cache_key).encode()).hexdigest()[:12]
        with _lock:
            if len(_fp_memo) >= _FP_MEMO_MAX:
                _fp_memo.clear()
            _fp_memo[cache_key] = fp
    return fp


# ---------------------------------------------------------------------------
# the ledger proper
# ---------------------------------------------------------------------------


class BackendStats:
    """Per-lowering-backend cost slice of one kernel entry (the
    autotuner's evidence: ``xla`` vs ``pallas`` execution percentiles,
    compile cost, analytic flops/bytes, and fallback count)."""

    __slots__ = ("exec", "compiles", "compile_s", "flops",
                 "bytes_accessed", "fallbacks", "_cost_tried")

    def __init__(self):
        self.exec = _Rolling()
        self.compiles = 0
        self.compile_s = 0.0
        self.flops: Optional[float] = None
        self.bytes_accessed: Optional[float] = None
        self.fallbacks = 0
        self._cost_tried = False

    def summary(self) -> dict:
        out = {
            "compiles": self.compiles,
            "compile_s": round(self.compile_s, 6),
            "exec": self.exec.summary(),
        }
        if self.flops is not None:
            out["flops"] = self.flops
        if self.bytes_accessed is not None:
            out["bytes_accessed"] = self.bytes_accessed
        if self.fallbacks:
            out["fallbacks"] = self.fallbacks
        return out


class KernelEntry:
    """All accumulated cost knowledge about one compiled kernel."""

    __slots__ = (
        "label", "instrs", "donated", "compiles", "compile_s",
        "warm_compiles", "warm_compile_s", "compile_class", "pad_waste",
        "exec", "sync", "bytes_in", "bytes_out",
        "hits", "misses", "evicts", "rungs", "tenants",
        "flops", "bytes_accessed", "_cost_tried", "backends",
    )

    def __init__(self, label: str = "?", instrs: int = 0, donated: int = 0):
        self.label = label
        self.instrs = instrs
        self.donated = donated
        self.compiles = 0
        self.compile_s = 0.0
        # warm-pool attribution: compiles paid proactively (trace replay
        # through submit_warm) vs. on the demand path.  Zero outside the
        # warm pool so historical summaries keep their shape.
        self.warm_compiles = 0
        self.warm_compile_s = 0.0
        # compile-class decision for this kernel (token like
        # ("pow2", 64)) and cumulative pad-waste bytes charged to it
        self.compile_class = None
        self.pad_waste = 0
        self.exec = _Rolling()
        self.sync: Optional[_Rolling] = None
        self.bytes_in = 0
        self.bytes_out = 0
        self.hits = 0
        self.misses = 0
        self.evicts = 0
        self.rungs: dict = {}
        # tenant -> execution count (serving attribution; empty outside
        # serve.Session so historical summaries are unchanged)
        self.tenants: dict = {}
        self.flops: Optional[float] = None
        self.bytes_accessed: Optional[float] = None
        self._cost_tried = False
        # backend name ("xla"/"pallas") -> BackendStats; empty until a
        # dispatch carries an explicit backend label, so pre-autotune
        # summaries are byte-identical to the historical shape
        self.backends: dict = {}

    def backend(self, name: str) -> BackendStats:
        b = self.backends.get(name)
        if b is None:
            b = self.backends[name] = BackendStats()
        return b

    def summary(self) -> dict:
        out = {
            "label": self.label,
            "instrs": self.instrs,
            "donated": self.donated,
            "compiles": self.compiles,
            "compile_s": round(self.compile_s, 6),
            "exec": self.exec.summary(),
            "bytes_in": self.bytes_in,
            "bytes_out": self.bytes_out,
            "cache": {"hits": self.hits, "misses": self.misses,
                      "evicts": self.evicts},
            "rungs": dict(self.rungs),
        }
        if self.tenants:
            out["tenants"] = dict(self.tenants)
        if self.sync is not None:
            out["sync"] = self.sync.summary()
        if self.flops is not None:
            out["flops"] = self.flops
        if self.bytes_accessed is not None:
            out["bytes_accessed"] = self.bytes_accessed
        if self.warm_compiles:
            out["warm_compiles"] = self.warm_compiles
            out["warm_compile_s"] = round(self.warm_compile_s, 6)
        if self.compile_class is not None:
            out["compile_class"] = list(self.compile_class)
            out["pad_waste"] = self.pad_waste
        if self.backends:
            out["backends"] = {name: b.summary()
                               for name, b in self.backends.items()}
        return out


_kernels: "dict[str, KernelEntry]" = {}

# flush-program label -> rolling wall-time window (hedge threshold)
_flush_walls: "dict[str, _Rolling]" = {}

# per-(label, rung) flush walls: the overload plane's deadline-aware
# ladder asks "can the chunked rung of THIS program fit the remaining
# budget" — a question the label-level window cannot answer once a
# program has degraded even once (its window then mixes rung costs)
_rung_walls: "dict[tuple, _Rolling]" = {}


def _entry(fp: str, label: Optional[str] = None, instrs: int = 0,
           donated: int = 0) -> KernelEntry:
    e = _kernels.get(fp)
    if e is None:
        e = KernelEntry(label or "?", instrs, donated)
        _kernels[fp] = e
    elif label is not None and e.label == "?":
        e.label = label
    return e


# Compile-source attribution (thread-local): the serve pipeline wraps
# warm-ticket thunks in compile_source("warm") so every compile they
# trigger — however deep in the fuser — lands on the warm side of the
# warm-vs-demand split without threading a parameter through the stack.
_compile_source = threading.local()


@contextmanager
def compile_source(source: str):
    """Scope within which compiles are attributed to ``source``
    ("warm" for warm-pool pre-compiles; the default is "demand")."""
    prev = getattr(_compile_source, "value", None)
    _compile_source.value = source
    try:
        yield
    finally:
        _compile_source.value = prev


def current_compile_source() -> str:
    return getattr(_compile_source, "value", None) or "demand"


def record_compile(fp: str, seconds: float, label: Optional[str] = None,
                   source: Optional[str] = None,
                   compile_class=None) -> None:
    """One compile (jit trace + lower + XLA compile wall) for a kernel.

    ``source`` defaults to the ambient :func:`compile_source` scope;
    ``"warm"`` compiles are additionally split out so diagnostics can
    show how much compile wall the warm pool pre-paid.  Emits a
    ``compile`` trace event (source-tagged) when tracing is on so
    ``scripts/trace_report.py`` can report the split offline."""
    src = source or current_compile_source()
    with _lock:
        e = _entry(fp, label)
        e.compiles += 1
        e.compile_s += seconds
        if src == "warm":
            e.warm_compiles += 1
            e.warm_compile_s += seconds
        if compile_class is not None:
            e.compile_class = tuple(compile_class)
    if _events.trace_enabled():
        _events.emit({
            "type": "compile",
            "fingerprint": fp,
            "seconds": round(seconds, 6),
            "source": src,
        })


def record_class(fp: str, compile_class, pad_waste: int,
                 label: Optional[str] = None) -> None:
    """Record a flush's compile-class decision on its kernel entry
    (token + cumulative pad-waste bytes, the cost side of bucketing)."""
    with _lock:
        e = _entry(fp, label)
        e.compile_class = tuple(compile_class)
        e.pad_waste += int(pad_waste)


def record_cache(fp: str, kind: str, label: Optional[str] = None) -> None:
    """One compile-cache interaction: ``kind`` in hit|miss|evict."""
    with _lock:
        e = _entry(fp, label)
        if kind == "hit":
            e.hits += 1
        elif kind == "miss":
            e.misses += 1
        elif kind == "evict":
            e.evicts += 1


def record_execute(fp: str, label: str, instrs: int, rung: str,
                   seconds: float, is_new: bool,
                   bytes_in: int = 0, bytes_out: int = 0,
                   donated: int = 0,
                   sync_seconds: Optional[float] = None,
                   tenant: Optional[str] = None,
                   backend: Optional[str] = None) -> None:
    """One execution of a compiled (or interpreted) kernel.

    First calls (``is_new``) pay jit trace + lower + XLA compile and are
    accounted as compile wall time, NOT as execution samples — mixing
    them in would poison the steady-state percentiles.  ``tenant`` (a
    serving session's identity) accumulates a per-tenant execution
    count on the entry.  ``backend``
    (a lowering backend name, ``xla``/``pallas``) additionally records
    the sample in that backend's slice — the per-fingerprint evidence
    ``core/autotune.py`` races on.  Compiles inherit the ambient
    :func:`compile_source` scope ("warm" inside warm-pool thunks)."""
    src = current_compile_source() if is_new else None
    t_obs = _time.perf_counter()
    with _lock:
        e = _entry(fp, label, instrs, donated)
        e.instrs = instrs or e.instrs
        e.donated = max(e.donated, donated)
        e.bytes_in += int(bytes_in)
        e.bytes_out += int(bytes_out)
        e.rungs[rung] = e.rungs.get(rung, 0) + 1
        if tenant is not None:
            e.tenants[tenant] = e.tenants.get(tenant, 0) + 1
        if is_new:
            e.compiles += 1
            e.compile_s += seconds
            if src == "warm":
                e.warm_compiles += 1
                e.warm_compile_s += seconds
        else:
            e.exec.add(seconds)
            if sync_seconds is not None:
                if e.sync is None:
                    e.sync = _Rolling()
                e.sync.add(sync_seconds)
        if backend is not None:
            b = e.backend(backend)
            if is_new:
                b.compiles += 1
                b.compile_s += seconds
            else:
                b.exec.add(seconds)
    _observer.add("ledger", _time.perf_counter() - t_obs)
    if is_new and _events.trace_enabled():
        _events.emit({
            "type": "compile",
            "fingerprint": fp,
            "seconds": round(seconds, 6),
            "source": src,
        })


def record_backend_fallback(fp: str, backend: str, err: str,
                            label: Optional[str] = None) -> None:
    """One failed attempt to run ``backend`` for this kernel (e.g. a
    Pallas Mosaic compile error): counted on the backend slice, mirrored
    on the observability stream so post-mortems see the degradation."""
    with _lock:
        e = _entry(fp, label)
        e.backend(backend).fallbacks += 1
    _registry.inc("autotune.backend_fallback")
    _events.emit({
        "type": "backend_fallback",
        "fingerprint": fp,
        "backend": backend,
        "error": str(err)[:200],
    })


def backend_stats(fp: str) -> dict:
    """Autotuner read API: backend name -> (exec samples, exec p50,
    total exec seconds, compile seconds, fallbacks) for one kernel.
    Returns {} for unknown fingerprints."""
    with _lock:
        e = _kernels.get(fp)
        if e is None:
            return {}
        out = {}
        for name, b in e.backends.items():
            out[name] = {
                "count": b.exec.count,
                "p50_s": b.exec.quantile(0.50),
                "total_s": b.exec.total,
                "compile_s": b.compile_s,
                "fallbacks": b.fallbacks,
            }
        return out


def capture_cost(fp: str, fn, leaf_vals,
                 backend: Optional[str] = None) -> None:
    """Attach XLA AOT ``cost_analysis()`` flops / bytes-accessed to the
    kernel entry, once, when ``RAMBA_PERF`` is on.  The AOT
    lower+compile is a second compilation of the same program — strictly
    opt-in and once per kernel; any failure (backend without
    cost_analysis, extended dtypes) just leaves the fields absent.
    With ``backend`` the capture lands on that backend's slice (once per
    backend), on top of the entry-level once-only capture."""
    if not cost_enabled():
        return
    with _lock:
        e = _entry(fp)
        b = e.backend(backend) if backend is not None else None
        if b is not None:
            if b._cost_tried and e._cost_tried:
                return
            b._cost_tried = True
            e._cost_tried = True
        else:
            if e._cost_tried:
                return
            e._cost_tried = True
    try:
        compiled = fn.lower(*leaf_vals).compile()
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else None
        if not ca:
            return
        flops = ca.get("flops")
        ba = ca.get("bytes accessed")
        with _lock:
            if flops is not None:
                if e.flops is None:
                    e.flops = float(flops)
                if b is not None:
                    b.flops = float(flops)
            if ba is not None:
                if e.bytes_accessed is None:
                    e.bytes_accessed = float(ba)
                if b is not None:
                    b.bytes_accessed = float(ba)
    except Exception:
        pass


def record_flush_wall(span: dict) -> None:
    """File one finished flush span's ``wall_s`` under its program label
    and under its (label, rung): the rolling windows
    :func:`flush_quantile` (hedge threshold) and :func:`rung_quantile`
    (deadline-aware ladder) answer from."""
    label = span.get("label", "?")
    wall = float(span.get("wall_s", 0.0) or 0.0)
    t_obs = _time.perf_counter()
    with _lock:
        win = _flush_walls.get(label)
        if win is None:
            win = _flush_walls[label] = _Rolling()
        win.add(wall)
        rkey = (label, span.get("degraded") or "fused")
        rwin = _rung_walls.get(rkey)
        if rwin is None:
            rwin = _rung_walls[rkey] = _Rolling()
        rwin.add(wall)
    _observer.add("ledger", _time.perf_counter() - t_obs)


def flush_quantile(label: str, q: float) -> Optional[float]:
    """Rolling flush-wall quantile for ``label``, or None below the
    sample floor — the hedged-dispatch trigger reads p95
    here, so hedging stays off until real history exists."""
    with _lock:
        win = _flush_walls.get(label)
        if win is None or win.count < _min_samples:
            return None
        return win.quantile(q)


def rung_quantile(label: str, rung: str, q: float) -> Optional[float]:
    """Rolling flush-wall quantile for one (label, rung) pair, or None
    below the sample floor — the deadline-aware ladder skips rungs
    whose p50 cannot fit the remaining budget."""
    with _lock:
        win = _rung_walls.get((label, rung))
        if win is None or win.count < _min_samples:
            return None
        return win.quantile(q)


def snapshot() -> dict:
    """JSON-serializable ledger dump — the payload behind
    ``diagnostics.perf_report()``."""
    with _lock:
        return {
            "mode": _mode or "off",
            "window": _window,
            "kernels": {fp: e.summary() for fp, e in _kernels.items()},
            "flushes": {label: w.summary()
                        for label, w in _flush_walls.items()},
        }


def kernel_keys() -> list:
    """Sorted kernel fingerprints — SPMD ranks running in lockstep must
    report identical sets (asserted by two_process_suite --perf-leg)."""
    with _lock:
        return sorted(_kernels)


def reset() -> None:
    """Drop all accumulated state (tests/benchmarks)."""
    with _lock:
        _kernels.clear()
        _flush_walls.clear()
        _rung_walls.clear()
        _fp_memo.clear()


reconfigure()
