"""jax.profiler integration: the program's names on the profiler's clock.

Every flush runs inside ``TraceAnnotation``s with stable names
(``ramba.flush.prepare``, ``ramba.flush.run``, ``ramba.flush.fence``),
each carrying the program's label and the span's trace id as arguments,
and the work outside the flush span that :func:`span` wraps shows as
``ramba.<name>``: ``ramba.dag.build`` from a stream's first pending
node to its flush (core/fuser.py ``FlushStream``), ``ramba.dag.infer``,
``ramba.read``, ``ramba.observe.tail``, and ``ramba.host.gc`` for each
pause of Python's collector (counted below, in whichever of the others
it fell).  They engage under ANY profiler session: one the
caller starts (``jax.profiler.start_trace``) or the whole-process one of
``RAMBA_PROFILE_DIR=<dir>``, which the first flush starts and atexit
stops.  With no session a ``TraceAnnotation`` is one atomic load, so
nothing gates them.  The annotations carry no number the flush span
lacks: the stage ledger (observe/attrib.py) stays the source of every
stage metric, and a timeline row is matched back to its span by the
``trace_id`` argument.
"""

from __future__ import annotations

import atexit
import gc
import os
import threading
import time

from jax.profiler import TraceAnnotation, start_trace, stop_trace

from ramba_tpu.observe import registry

_DIR = os.environ.get("RAMBA_PROFILE_DIR") or None
_started = False


def ensure_started() -> None:
    """Start the profiler trace once (no-op unless RAMBA_PROFILE_DIR)."""
    global _started
    if _DIR is None or _started:
        return
    _started = True
    os.makedirs(_DIR, exist_ok=True)
    start_trace(_DIR)
    atexit.register(_stop)


def _stop() -> None:
    global _started
    if not _started:
        return
    _started = False
    try:
        stop_trace()
    except Exception:  # interpreter teardown: best-effort
        pass


def span_args(span) -> dict:
    """The arguments a flush's annotations carry: the program's label and,
    where the stream has one, the span's trace id."""
    args = {}
    if span is not None:
        if span.get("label") is not None:
            args["label"] = span["label"]
        if span.get("trace_id") is not None:
            args["trace_id"] = span["trace_id"]
    return args


def flush_annotation(stage: str, span=None):
    """``ramba.flush.<stage>`` on the profiler's host line for the flush
    that ``span`` records, with :func:`span_args` as arguments (the name
    stays one per stage, so gaps add up over programs).  Free of any
    gate: safe on the per-flush hot path."""
    return TraceAnnotation("ramba.flush." + stage, **span_args(span))


class span:
    """Host work outside the flush span, counted where it happens: for
    its duration ``ramba.<name>`` is open on the profiler's host line,
    and on exit the elapsed nanoseconds go to the registry counter
    ``<name>.ns`` and 1 to ``<name>.n``.  No event is emitted.  One
    entered and left by hand (a stream's build phase) may be left on
    another thread than it was entered on: the profiler keeps a line a
    thread, so the annotation is then dropped and the counters kept."""

    __slots__ = ("name", "_ann", "_t0", "_tid")

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        self._tid = threading.get_ident()
        self._ann = TraceAnnotation("ramba." + self.name)
        self._ann.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        ns = time.perf_counter_ns() - self._t0
        if threading.get_ident() == self._tid:
            self._ann.__exit__(*exc)
        registry.inc(self.name + ".ns", ns)
        registry.inc(self.name + ".n")
        return False


# Python's collector: counters ``host.gc.n`` (collections), ``host.gc.ns``
# (their pauses) and ``host.gc.gen2.n`` (the full ones), and
# ``ramba.host.gc`` open for each pause.  One collection runs at a time
# in a process, on the thread that tripped it, so plain integers do; the
# registry folds them in when it is read.  Nothing runs between
# collections, and no threshold is touched.
_gc_n = _gc_ns = _gc_gen2_n = 0
_gc_t0 = 0
_gc_ann = None


def _on_gc(phase, info):
    global _gc_n, _gc_ns, _gc_gen2_n, _gc_t0, _gc_ann
    if phase == "start":
        _gc_ann = TraceAnnotation("ramba.host.gc",
                                  generation=info["generation"])
        _gc_ann.__enter__()
        _gc_t0 = time.perf_counter_ns()
    elif _gc_ann is not None:
        _gc_ns += time.perf_counter_ns() - _gc_t0
        _gc_n += 1
        _gc_gen2_n += info["generation"] == 2
        _gc_ann.__exit__(None, None, None)
        _gc_ann = None


gc.callbacks.append(_on_gc)
registry.add_source(lambda: {"host.gc.n": _gc_n, "host.gc.ns": _gc_ns,
                             "host.gc.gen2.n": _gc_gen2_n})
