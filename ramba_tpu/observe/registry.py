"""Single process-wide metrics store: counters + timers + comm gauges.

The reference scatters its instrumentation over private module dicts
(``time_dict``/``sub_time_dict``/``per_func`` in ramba.py:923-1019, per-queue
byte stats in ramba_queue_zmq.py:127-135).  Here every store lives in ONE
module so ``ramba_tpu.diagnostics`` can snapshot the whole system at once;
``utils/timing.py`` keeps its public surface by aliasing these same objects
(the dicts below ARE ``timing.time_dict`` etc. — one store, two names).

Counter naming convention: ``<subsystem>.<event>`` — e.g.
``fuser.cache_miss``, ``rewrite.rewrite_arange_reshape``,
``skeletons.host_fallback``, ``stencil.halo_bytes_est``,
``distributed.allgather_bytes``.  ``*_bytes``/``*_bytes_est`` counters
accumulate byte totals; everything else counts occurrences.  ``*_est``
byte counters for collectives are computed from static shapes at jax trace
time, so they count bytes per *compiled structure*, not per execution —
XLA's profiler owns exact per-execution collective traffic.
"""

from __future__ import annotations

import contextlib
import threading
import time
from collections import defaultdict

# Process birth stamps, frozen at first import of the observability plane
# (one pair per process lifetime).  The diagnostics identity block and
# the ``ramba_process_info`` exporter series use these to distinguish
# "same pid, new incarnation" — a restarted process publishes a NEW
# start_wall, so a collector never merges two lives of one pid into one
# counter history.
START_WALL: float = round(time.time(), 6)
START_MONO: float = round(time.monotonic(), 6)

# One lock for the whole store: the stores are touched together (snapshot,
# reset) and individual updates are tiny, so finer grain buys nothing.
# RLock because utils/timing.py wrappers alias these dicts and may be
# called from code already holding it.  Concurrent serving sessions hammer
# inc() from many threads — unguarded ``d[k] += n`` is a read-modify-write
# that loses increments under contention.
lock = threading.RLock()

# occurrence / byte counters: name -> int
counters: dict = defaultdict(int)

# names that were last written via gauge() — the metrics exporter types
# these as Prometheus gauges instead of counters
_gauge_names: set = set()

# name -> [total_seconds, call_count]  (aliased as timing.time_dict)
timers: dict = defaultdict(lambda: [0.0, 0])
# (parent, name) -> [total_seconds, call_count]  (timing.sub_time_dict)
sub_timers: dict = defaultdict(lambda: [0.0, 0])
# program label -> [total_seconds, call_count]  (timing.per_func)
per_func: dict = defaultdict(lambda: [0.0, 0])

# host<->device boundary traffic (timing.comm_stats)
comm: dict = {
    "host_to_device_bytes": 0, "host_to_device_count": 0,
    "device_to_host_bytes": 0, "device_to_host_count": 0,
}


def inc(name: str, n: int = 1) -> None:
    """Increment a named counter (hot-path safe: one dict add)."""
    with lock:
        counters[name] += n


# Counts made thousands of times a flush (a lazy node, an index lowered)
# do not take the lock: their owner adds to plain module integers and
# registers a function that returns {name: total since import}.  fold()
# brings the store up to those totals; every read of the store below, and
# diagnostics.counters(), folds first.  ``counters`` read directly is as
# of the last fold.
_sources: list = []
_folded: dict = {}


def add_source(fn) -> None:
    """Register ``fn() -> {name: total}`` as the owner of those names."""
    with lock:
        _sources.append(fn)


def fold() -> None:
    """Add to the store what each source has counted since the last
    fold."""
    with lock:
        for fn in _sources:
            for name, total in fn().items():
                counters[name] += total - _folded.get(name, 0)
                _folded[name] = total


# Which lowering a hand-written kernel took is decided while jax traces
# the program (from shapes, dtypes, mesh and backend), so it is recorded
# then.  Two records with two lifetimes:
# - the counters ``<kernel>.path.<path>`` (and ``<kernel>.interpret``)
#   move once per flush that runs the kernel, cache hit or not: when the
#   flush's compiled call traces, through note_kernel; when it does not,
#   through replay_kernel_notes with the notes its trace left
#   (fuser._count_kernel_paths).  They also move when node inference
#   traces a kernel (a miss of expr.infer_aval: first sight of a function
#   over given avals), which no flush span sees.
# - the flush span's ``kernels`` list holds the notes only when the
#   flush itself traced (fuser._execute_compiled collects them).
_kernel_notes = threading.local()

#: what a note counts besides its path, as ``<kernel>.<name>``; kept on
#: the note where it is not 0, so that a replay counts it again
_TALLIES = ("operand_copy", "combine_bytes", "exchange_bytes")


def _count_kernel(kernel: str, path: str, interpret: bool,
                  tallies: dict, epilogue=None, epilogue_fused=False) -> None:
    inc(f"{kernel}.path.{path}")
    if interpret:
        inc(f"{kernel}.interpret")
    for name, n in tallies.items():
        if n:
            inc(f"{kernel}.{name}", n)
    if epilogue not in (None, "none"):
        inc(f"{kernel}.epilogue.{'fused' if epilogue_fused else 'unfused'}")


def note_kernel(kernel: str, path: str, interpret: bool = False, *,
                block_rows=None, grid=None, vmem_limit_bytes=None,
                halo=None, operand_copy: int = 0, combine_bytes: int = 0,
                exchange_bytes: int = 0, epilogue=None,
                epilogue_fused: bool = False, **chose) -> None:
    """Record that ``kernel`` (e.g. ``"stencil"``) lowered through
    ``path`` (``sharded`` / ``pallas_fast`` / ``pallas_padded`` / ``xla``
    / a Pallas family name), and whether a Pallas kernel on that path
    interprets instead of compiling for the chip.  Called while jax
    traces: counts the path, and leaves a note with the enclosing
    :func:`collect_kernel_notes`, if any.  A kernel that sizes its own
    blocks says what it chose (``block_rows``, ``grid``,
    ``vmem_limit_bytes``, and ``halo``: where it reads its halo from):
    kept on the note, not counted.  ``operand_copy`` is how many of its
    operands reach the kernel through an array-sized copy XLA makes:
    counted as ``<kernel>.operand_copy`` and kept on the note where it is
    not 0, so that a replay counts it again.  ``combine_bytes`` is what a
    device hands to the kernel's combination across chips (the segment
    walk's partial sums): counted as ``<kernel>.combine_bytes`` and kept
    on the note likewise; ``exchange_bytes`` the most bytes one device
    sends to another (the transpose's swap), likewise.  ``epilogue`` is
    the elementwise update that follows the kernel's result (the stencil's
    ``subtract`` or ``add``, ``none``) and ``epilogue_fused`` whether the
    kernel's own store wrote it: an update counts
    ``<kernel>.epilogue.fused`` or ``.unfused``, both kept on the note so
    that a replay counts it again.  Any further keyword is a
    plain value a lowering chose for itself (the segment walk's ``groups``,
    ``chunk_rows``, ``chunks``, ``fetch``, ``sharded``, ``split``,
    ``local_rows``, ``combine``): kept on the note as given."""
    tallies = dict(zip(_TALLIES, (operand_copy, combine_bytes,
                                  exchange_bytes)))
    epilogue_fused = bool(epilogue_fused) and epilogue not in (None, "none")
    _count_kernel(kernel, path, interpret, tallies, epilogue, epilogue_fused)
    notes = getattr(_kernel_notes, "active", None)
    if notes is not None:
        note = {"kernel": kernel, "path": path, "interpret": bool(interpret)}
        sized = {"block_rows": block_rows, "grid": grid,
                 "vmem_limit_bytes": vmem_limit_bytes,
                 **{k: n or None for k, n in tallies.items()}}
        note.update((k, int(v)) for k, v in sized.items() if v is not None)
        if halo is not None:
            note["halo"] = halo
        if epilogue is not None:
            note["epilogue"] = epilogue
            if epilogue != "none":
                note["epilogue_fused"] = epilogue_fused
        note.update(chose)
        notes.append(note)


def replay_kernel_notes(notes) -> None:
    """Count the kernels of ``notes`` (as :func:`collect_kernel_notes`
    yielded them when the program was traced) for a call of the compiled
    program that traced nothing: counters only, never a new note."""
    for note in notes:
        _count_kernel(note["kernel"], note["path"], note["interpret"],
                      {k: note.get(k, 0) for k in _TALLIES},
                      note.get("epilogue"), note.get("epilogue_fused", False))


@contextlib.contextmanager
def collect_kernel_notes():
    """Collect this thread's :func:`note_kernel` records made inside the
    block (jax traces in the calling thread); yields the list.  Empty
    after a call that hit jax's trace cache: the notes are per trace, the
    counters per flush."""
    prev = getattr(_kernel_notes, "active", None)
    notes: list = []
    _kernel_notes.active = notes
    try:
        yield notes
    finally:
        _kernel_notes.active = prev


def gauge(name: str, value) -> None:
    """Set a counter to an absolute level (e.g. ``memory.live_bytes``) —
    same store and naming convention as :func:`inc`, but last-write-wins
    semantics for quantities that go down as well as up."""
    with lock:
        counters[name] = int(value)
        _gauge_names.add(name)


def gauge_names() -> set:
    """Copy of the names with gauge (last-write-wins) semantics."""
    with lock:
        return set(_gauge_names)


def get(name: str) -> int:
    fold()
    return counters.get(name, 0)


def prefixed(prefix: str) -> dict:
    """Counters under one subsystem prefix (e.g. ``prefixed("resilience.")``
    → every fault/retry/degradation counter)."""
    with lock:  # iteration would break under a concurrent inc of a new key
        fold()
        return {k: v for k, v in counters.items() if k.startswith(prefix)}


def snapshot() -> dict:
    """Point-in-time copy of every store (JSON-serializable except
    sub_timers' tuple keys, which stringify as 'parent/name')."""
    with lock:
        fold()
        return {
            "counters": dict(counters),
            "timers": {k: tuple(v) for k, v in timers.items()},
            "sub_timers": {f"{p}/{s}": tuple(v)
                           for (p, s), v in sub_timers.items()},
            "per_func": {k: tuple(v) for k, v in per_func.items()},
            "comm": dict(comm),
        }


def reset_counters() -> None:
    with lock:
        fold()  # what the sources counted so far is cleared with the rest
        counters.clear()
        _gauge_names.clear()


def reset_timers() -> None:
    """Clear the timer stores (the historical ``timing.reset`` scope)."""
    with lock:
        timers.clear()
        sub_timers.clear()
        per_func.clear()
        for k in comm:
            comm[k] = 0


def reset() -> None:
    reset_counters()
    reset_timers()
