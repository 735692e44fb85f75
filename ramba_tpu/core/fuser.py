"""Flush machinery: lazy expression graphs -> one fused, jitted XLA module.

This is the TPU-native counterpart of the reference's two-stage execution
pipeline:

* ``DAG.execute_all`` — collect every pending node and run it in one batch
  (/root/reference/ramba/ramba.py:5080-5105), and
* ``deferred_op.execute`` — emit ONE fused kernel for the batch, name it by a
  hash of its source for caching, and ship it to all workers
  (/root/reference/ramba/ramba.py:8115-8316, hash at :8260-8265).

Differences, by design:

* Instead of generating Python source strings for Numba, the expression graph
  is linearized into a tiny instruction program which is interpreted once
  under ``jax.jit`` tracing; XLA does the loop fusion and GSPMD inserts the
  cross-shard collectives (the reference moves boundary data by hand at
  ramba.py:3549-3694).
* The compile cache is keyed on program *structure* only — leaf shapes/dtypes
  are specialized by jax.jit's own cache, and scalar operands are passed as
  weakly-typed arguments so changing a constant never recompiles.
* Buffer donation replaces the reference's in-place shard mutation: a leaf
  buffer that no live ndarray aliases is donated to XLA so e.g. ``a += 1``
  updates HBM in place (the reference's alias analysis for this is
  ramba.py:8435-8465).

Since the serving refactor, pending state is *per stream*: each
:class:`FlushStream` owns its own pending registry, node-count threshold,
and quarantine scope, so concurrent sessions (``ramba_tpu.serve``) cannot
flush — or poison — each other's half-built programs.  A process-wide
default stream preserves the historical single-stream behavior verbatim;
``_pending`` below IS the default stream's registry dict.  A flush is two
stages — :func:`_flush_prepare` (collect + rewrite + linearize + donation
census + verify, cheap, caller thread) and :func:`_flush_dispatch`
(admission + ladder execution + write-back) — shared by the synchronous
path here and the async compile pipeline in ``serve/pipeline.py`` so the
two can never drift.
"""

from __future__ import annotations

import contextvars
import hashlib
import itertools
import os
import struct
import threading
import time
import warnings
import weakref
from contextlib import contextmanager
from typing import Optional, Sequence

import jax
import numpy as np

from ramba_tpu import common
from ramba_tpu.compile import classes as _classes
from ramba_tpu.compile import persist as _persist
from ramba_tpu.core import layouts as _layouts
from ramba_tpu.core import memo as _memo
from ramba_tpu.core import plancache as _plancache
from ramba_tpu.core.expr import (Const, Expr, Node, Scalar, OPS,
                                 _scalar_key,
                                 semantic_fingerprint as _semantic_fingerprint)
from ramba_tpu.observe import attrib as _attrib
from ramba_tpu.observe import events as _events
from ramba_tpu.observe import ledger as _ledger
from ramba_tpu.observe import observer as _observer
from ramba_tpu.observe import profile as _profile
from ramba_tpu.observe import registry as _registry
from ramba_tpu.observe import slo as _slo
from ramba_tpu.observe import telemetry as _telemetry
from ramba_tpu.parallel import mesh as _mesh
from ramba_tpu.resilience import coherence as _coherence
from ramba_tpu.resilience import degrade as _degrade
from ramba_tpu.resilience import elastic as _elastic
from ramba_tpu.resilience import faults as _faults
from ramba_tpu.resilience import integrity as _integrity
from ramba_tpu.resilience import memory as _memory
from ramba_tpu.resilience.spill import SpilledArray as _SpilledArray
from ramba_tpu.utils import timing as _timing

# Donation is pointless for small buffers and fragments the jit cache (the
# donate mask is part of the compile key); only donate above this size.
DONATE_MIN_BYTES = 1 << 20


def _nbytes(v) -> int:
    """Buffer size, 0 when unknowable — extended dtypes (e.g. PRNG key
    arrays) raise from the ``nbytes`` property itself, so getattr-with-
    default is not enough."""
    try:
        return int(v.nbytes)
    except Exception:
        return 0


# ---------------------------------------------------------------------------
# cross-stream shared state + its locks
# ---------------------------------------------------------------------------

# id(buffer) -> number of live ndarrays whose materialized value IS that
# buffer.  Zero owners at flush time means nothing can observe the buffer
# after this flush, so it is safe to donate.
_const_owners: dict[int, int] = {}
_census_lock = threading.RLock()

# id(leaf value) -> number of prepared-but-not-finished flushes holding it
# as a program input.  A buffer referenced by MORE than one in-flight
# program must not be donated by any of them: streams can share subgraphs
# (and therefore leaves) and a donation in stream A would hand stream B a
# deleted buffer.  On the single default stream exactly one flush is ever
# in flight, so the count is always 1 and the donation decision reduces to
# the historical owners==0 test.
_inflight_leaves: dict[int, int] = {}
_flight_lock = threading.Lock()

# Bounded LRU compile cache; entries from an old mesh epoch are purged on
# the first flush after set_mesh (their sharding constraints baked in the old
# mesh), and user-function keys (fromfunction/apply statics) can't pin
# unbounded executables.  dict preserves insertion order and a hit re-inserts
# its key, so iteration order IS recency order and eviction pops the LRU.
# Shared by every stream (a program's structure is tenant-independent —
# sharing IS what makes coalesced dispatch compile-cache-warm) and guarded
# by _cache_lock now that streams flush concurrently.
_compile_cache: "dict" = {}
_COMPILE_CACHE_MAX = 512
_cache_epoch = 0
_cache_lock = threading.RLock()

# Monotone flush counters (observability; cf. reference dag-count history,
# ramba.py:5120-5128).  Process-wide across all streams.
stats = {"flushes": 0, "compiles": 0, "nodes_flushed": 0, "segments": 0}
_stats_lock = threading.Lock()


# ---------------------------------------------------------------------------
# flush streams
# ---------------------------------------------------------------------------

_stream_ids = itertools.count(1)


class FlushStream:
    """Session-scoped pending registry + flush scope.

    One per serving session (``serve.Session``), plus the process-wide
    default stream.  Each stream owns:

    * its pending registry (ndarrays with a non-Const expression),
    * its ``nodes_since_flush`` counter and ``max_pending_ops`` threshold
      (one tenant's build burst can no longer force-flush another
      tenant's half-built program),
    * its quarantine scope — a flush failure unregisters only THIS
      stream's roots, and
    * its flush ordering: ``_flush_lock`` serializes flushes of the same
      stream (concurrent flushes of one stream would double-execute and
      double-donate the same roots), while different streams flush
      concurrently.
    """

    __slots__ = ("stream_id", "name", "tenant", "max_pending_ops",
                 "quota_bytes", "on_threshold", "inflight", "stats",
                 "nodes_since_flush", "trace_id", "root_span",
                 "deadline_ms", "priority", "_build",
                 "_pending", "_lock", "_flush_lock", "__weakref__")

    def __init__(self, name: Optional[str] = None,
                 tenant: Optional[str] = None,
                 max_pending_ops: Optional[int] = None,
                 quota_bytes: Optional[int] = None):
        self.stream_id = next(_stream_ids)
        self.name = name or f"stream{self.stream_id}"
        self.tenant = tenant
        # None -> the process-wide common.max_pending_ops default
        self.max_pending_ops = max_pending_ops
        # per-tenant HBM quota enforced by memory-governor admission
        self.quota_bytes = quota_bytes
        # hook the serving session installs so threshold auto-flushes go
        # through the async pipeline instead of blocking the build thread
        self.on_threshold = None
        # causal trace identity (serve.Session mints these): every flush
        # span of this stream carries trace_id and chains to root_span
        self.trace_id: Optional[str] = None
        self.root_span: Optional[str] = None
        # overload plane (serve.Session mints these too): per-flush time
        # budget and brownout-shedding exemption — see serve/overload.py
        self.deadline_ms: Optional[float] = None
        self.priority = False
        # in-flight async work (objects with .wait()); serve/pipeline.py
        # maintains this so drain()/materialization can rendezvous
        self.inflight: list = []
        self.stats = {"flushes": 0, "nodes_flushed": 0, "quarantined": 0,
                      "enqueued": 0}
        self.nodes_since_flush = 0
        # the build phase: open (``ramba.dag.build``, counters
        # ``dag.build.n``, ``.ns``) from the first pending node to the
        # flush that collects it
        self._build: Optional[_profile.span] = None
        self._pending: dict[int, "weakref.ref"] = {}
        self._lock = threading.RLock()
        self._flush_lock = threading.RLock()
        _streams.add(self)

    def __repr__(self):
        return (f"<FlushStream {self.name!r} tenant={self.tenant!r} "
                f"pending={len(self._pending)}>")

    # -- registry ----------------------------------------------------------

    def register(self, arr) -> None:
        k = id(arr)

        def _cleanup(ref, _k=k, _s=self):
            with _s._lock:
                if _s._pending.get(_k) is ref:
                    del _s._pending[_k]
                else:
                    return
            with _reg_lock:
                if _arr_streams.get(_k) is _s:
                    del _arr_streams[_k]

        with self._lock:
            self._pending[k] = weakref.ref(arr, _cleanup)

    def unregister(self, arr) -> None:
        with self._lock:
            self._pending.pop(id(arr), None)

    def pending_arrays(self) -> list:
        out = []
        with self._lock:
            refs = list(self._pending.values())
        for r in refs:
            a = r()
            if a is not None:
                out.append(a)
        return out

    def pending_roots(self) -> list:
        """Pending ndarrays in deterministic (creation) order — the program
        the next flush of this stream will run is defined by this set."""
        roots = [a for a in self.pending_arrays()
                 if not isinstance(a._expr, Const)]
        roots.sort(key=lambda a: a._seq)
        return roots

    def _collect(self, *, detach: bool = False) -> list:
        """Atomically snapshot the roots of the next flush and reset the
        node counter.  ``detach`` (the async-enqueue path) additionally
        removes the roots from the registry so a later enqueue cannot
        collect — and double-execute — the same work; the returned strong
        references keep the arrays alive until write-back."""
        with self._lock:
            self.nodes_since_flush = 0
            build, self._build = self._build, None
            roots = []
            for r in list(self._pending.values()):
                a = r()
                if a is not None and not isinstance(a._expr, Const):
                    roots.append(a)
            roots.sort(key=lambda a: a._seq)
            if detach:
                for a in roots:
                    self._pending.pop(id(a), None)
        if detach and roots:
            with _reg_lock:
                for a in roots:
                    if _arr_streams.get(id(a)) is self:
                        del _arr_streams[id(a)]
        if build is not None:
            # the build ends where the flush begins: the caller opens
            # ``ramba.flush.prepare`` next
            build.__exit__(None, None, None)
        return roots

    # -- thresholds --------------------------------------------------------

    def note_node_created(self) -> None:
        """Forced-flush safety valve for unbounded build loops — per
        stream, so one tenant's burst only flushes that tenant's work."""
        with self._lock:
            self.nodes_since_flush += 1
            if self.nodes_since_flush == 1:
                self._build = _profile.span("dag.build").__enter__()
            cap = self.max_pending_ops
            if cap is None:
                cap = common.max_pending_ops
            fire = cap and self.nodes_since_flush >= cap
        if fire:
            hook = self.on_threshold
            if hook is not None:
                hook(self)
            else:
                self.flush()

    # -- flushing ----------------------------------------------------------

    def flush(self, extra: Sequence[Expr] = ()) -> list:
        """Synchronously materialize this stream's pending ndarrays (and
        ``extra`` expressions).  Returns the values of ``extra`` in
        order."""
        with self._flush_lock, stream_scope(self):
            roots = self._collect()
            work = _flush_prepare(self, roots, extra)
            if work is None:
                return []
            return _flush_dispatch(work)

    def drain(self) -> None:
        """Wait for every in-flight async flush of this stream (enqueued
        via serve/pipeline.py) to finish.  Failures surface through the
        tickets / later materialization, not here."""
        for t in list(self.inflight):
            wait = getattr(t, "wait", None)
            if wait is not None:
                try:
                    wait()
                except Exception:
                    pass


# All live streams (weak — a dropped session's stream must be collectable).
# FlushStream has no __eq__, so WeakSet membership is identity, as needed.
_streams: "weakref.WeakSet[FlushStream]" = weakref.WeakSet()

#: The process-wide default stream: everything outside a serve.Session.
_default_stream = FlushStream(name="default")

# Historical module-level registry — tests and debug tooling reach for
# ``fuser._pending`` directly; it IS the default stream's dict (the default
# stream only ever mutates, never replaces, this object).
_pending = _default_stream._pending

# id(arr) -> owning FlushStream for every pending ndarray, so
# materialization can flush the stream that owns the work regardless of
# which thread/session touches the array.
_arr_streams: dict[int, FlushStream] = {}
_reg_lock = threading.RLock()

_current_stream: "contextvars.ContextVar[Optional[FlushStream]]" = \
    contextvars.ContextVar("ramba_flush_stream", default=None)


def current_stream() -> FlushStream:
    s = _current_stream.get()
    return s if s is not None else _default_stream


def default_stream() -> FlushStream:
    return _default_stream


def current_tenant() -> Optional[str]:
    s = _current_stream.get()
    return s.tenant if s is not None else None


@contextmanager
def stream_scope(stream: FlushStream):
    """Make ``stream`` the current stream for the calling context (new
    lazy arrays register into it; ledger/counter attribution follows)."""
    token = _current_stream.set(stream)
    try:
        yield stream
    finally:
        _current_stream.reset(token)


def activate_stream(stream: FlushStream):
    """Non-contextmanager activation (serve.Session.__enter__); returns
    the token for :func:`deactivate_stream`."""
    return _current_stream.set(stream)


def deactivate_stream(token) -> None:
    _current_stream.reset(token)


def all_streams() -> list:
    """Live streams, default first, then by creation order."""
    out = [s for s in list(_streams) if s is not _default_stream]
    out.sort(key=lambda s: s.stream_id)
    return [_default_stream] + out


def stream_of(arr) -> FlushStream:
    """The stream that owns ``arr``'s pending work (current stream when
    the array is not pending anywhere — e.g. already materialized or
    quarantined)."""
    with _reg_lock:
        s = _arr_streams.get(id(arr))
    return s if s is not None else current_stream()


def register_pending(arr) -> None:
    k = id(arr)
    with _reg_lock:
        s = _arr_streams.get(k)
        if s is None:
            s = current_stream()
            _arr_streams[k] = s
    s.register(arr)


def unregister_pending(arr) -> None:
    k = id(arr)
    with _reg_lock:
        s = _arr_streams.pop(k, None)
    if s is not None:
        s.unregister(arr)
    else:
        # never registered under a stream (or already collected); make the
        # historical contract hold for direct callers
        _default_stream.unregister(arr)


def _pending_arrays() -> list:
    """Every pending ndarray across ALL streams (debug tooling and the
    sync barrier read this; per-stream work uses the stream's own)."""
    out = []
    for s in all_streams():
        out.extend(s.pending_arrays())
    return out


def note_node_created(arr=None) -> None:
    """Per-stream forced-flush safety valve.  With ``arr`` given, the
    counter/threshold of the *owning* stream advances; bare calls charge
    the current stream (historical signature)."""
    if arr is not None:
        stream_of(arr).note_node_created()
    else:
        current_stream().note_node_created()


# ---------------------------------------------------------------------------
# owner census (shared across streams; donation safety)
# ---------------------------------------------------------------------------


def owner_incref(buf, const=None) -> None:
    """Count one more live ndarray owning ``buf``.  When the owning
    ``Const`` node is supplied (ndarray._set_expr does), the buffer is
    also registered with the memory governor's live-bytes ledger."""
    with _census_lock:
        _const_owners[id(buf)] = _const_owners.get(id(buf), 0) + 1
    # outside the census lock: the memory ledger takes its own lock and
    # (on spill) calls back into owner_rekey — nesting would deadlock
    if const is not None:
        _memory.on_incref(const)


def owner_decref(buf) -> None:
    k = id(buf)
    with _census_lock:
        n = _const_owners.get(k, 0) - 1
        released = n <= 0
        if released:
            _const_owners.pop(k, None)
        else:
            _const_owners[k] = n
    if released:
        _memory.on_release(buf)


def owner_rekey(old, new) -> None:
    """Migrate the owner census when the memory governor swaps a Const's
    value object (device array ↔ host spill wrapper): the count follows
    the buffer identity, so the donation decision at the next flush sees
    the same aliasing it would have seen without the spill."""
    with _census_lock:
        n = _const_owners.pop(id(old), 0)
        if n > 0:
            _const_owners[id(new)] = _const_owners.get(id(new), 0) + n


def leaf_value(leaf):
    """Device value of a Const leaf, transparently restoring it from a
    host spill if the memory governor evicted it (resilience.memory)."""
    v = leaf.value
    if isinstance(v, _SpilledArray):
        return _memory.restore(leaf)
    return v


def _flight_incref(leaf_vals) -> list:
    keys = []
    with _flight_lock:
        for v in leaf_vals:
            k = id(v)
            _inflight_leaves[k] = _inflight_leaves.get(k, 0) + 1
            keys.append(k)
    return keys


def _flight_decref(keys) -> None:
    with _flight_lock:
        for k in keys:
            n = _inflight_leaves.get(k, 0) - 1
            if n <= 0:
                _inflight_leaves.pop(k, None)
            else:
                _inflight_leaves[k] = n


class _Program:
    """Buffer-free linearization of an expression DAG.

    ``instrs[i] = (op, static, arg_slots)`` where slots < n_leaves index the
    leaf arguments and later slots index prior instruction results.  Holding
    no jax.Array references makes the program safe to retain in the compile
    cache without pinning HBM.

    ``live_cuts`` (empty for a program as linearized) are the instruction
    indices at which a live group ends: ``_build_callable`` holds the
    values live there behind an ``optimization_barrier``.  They are part
    of ``key``, so every cache keyed on it tells the two forms apart.
    """

    __slots__ = ("instrs", "n_leaves", "leaf_kinds", "out_slots",
                 "live_cuts", "key", "key_hash")

    def __init__(self, instrs, n_leaves, leaf_kinds, out_slots,
                 live_cuts=()):
        self.instrs = instrs
        self.n_leaves = n_leaves
        self.leaf_kinds = leaf_kinds
        self.out_slots = tuple(out_slots)
        self.live_cuts = tuple(live_cuts)
        self.key = (tuple(instrs), n_leaves, leaf_kinds, self.out_slots)
        if self.live_cuts:
            self.key += (("live_cuts",) + self.live_cuts,)
        # Hashed at linearize time (the key is part of the capture
        # product) so prepare-side caches keyed on the program pay an
        # O(1) cached hash instead of re-walking the instrs tuple; -1
        # marks an unhashable key (static carrying a list/dict).
        try:
            self.key_hash = hash(self.key)
        except TypeError:
            self.key_hash = -1

    @property
    def live_groups(self) -> int:
        return len(self.live_cuts) + 1


def _linearize(roots: Sequence[Expr]):
    """Iterative postorder DFS over the DAG with node dedup (shared subexprs
    evaluate once — the fusion the reference gets by concatenating codelines
    into a single loop nest, ramba.py:8348-8423)."""
    slot: dict[int, int] = {}
    leaves: list = []
    instrs: list = []
    # first pass: collect leaves in deterministic order
    const_slot: dict[int, int] = {}  # id(buffer) -> leaf slot (dedup aliased)
    order: list[Expr] = []
    seen: set[int] = set()
    stack = [(r, False) for r in reversed(roots)]
    while stack:
        node, done = stack.pop()
        nid = id(node)
        if done:
            order.append(node)
            continue
        if nid in seen:
            continue
        seen.add(nid)
        if isinstance(node, Node):
            stack.append((node, True))
            for a in reversed(node.args):
                stack.append((a, False))
        else:
            order.append(node)
    for node in order:
        nid = id(node)
        if nid in slot:
            continue
        if isinstance(node, Const):
            bid = id(node.value)
            if bid in const_slot:
                slot[nid] = const_slot[bid]
                continue
            const_slot[bid] = len(leaves)
            slot[nid] = len(leaves)
            leaves.append(node)
        elif isinstance(node, Scalar):
            slot[nid] = len(leaves)
            leaves.append(node)
    n_leaves = len(leaves)
    for node in order:
        nid = id(node)
        if nid in slot or not isinstance(node, Node):
            continue
        args = tuple(slot[id(a)] for a in node.args)
        slot[nid] = n_leaves + len(instrs)
        instrs.append((node.op, node.static, args))
    leaf_kinds = tuple("C" if isinstance(l, Const) else "S" for l in leaves)
    out_slots = [slot[id(r)] for r in roots]
    return _Program(tuple(instrs), n_leaves, leaf_kinds, out_slots), leaves


def _build_callable(program: _Program):
    if program.live_cuts:
        return _build_grouped_callable(program)
    instrs = program.instrs
    n_leaves = program.n_leaves
    out_slots = program.out_slots

    def run(*leaf_vals):
        vals = list(leaf_vals)
        for op, static, argslots in instrs:
            vals.append(OPS[op](static, *(vals[s] for s in argslots)))
        return tuple(vals[s] for s in out_slots)

    return run


def _build_grouped_callable(program: _Program):
    """The straight-line callable with a bounded live set: at each of
    ``program.live_cuts`` every value that is live there (made before the
    cut, read after it or returned; Python scalars apart) goes through
    ONE ``jax.lax.optimization_barrier``.  XLA may then neither start
    the next group's work before this group's results exist nor fuse
    across the cut, so what it holds at once is one group's temporaries
    and not the whole program's.  Still one jitted program: one
    executable, one dispatch, the same donation."""
    instrs = program.instrs
    n_leaves = program.n_leaves
    out_slots = program.out_slots
    last_use = _last_use_map(program)
    held_at = {}
    for cut in program.live_cuts:
        top = n_leaves + cut
        held_at[cut] = tuple(
            s for s in range(top)
            if last_use.get(s, 0) >= top
            and (s >= n_leaves or program.leaf_kinds[s] == "C"))

    def run(*leaf_vals):
        vals = list(leaf_vals)
        for i, (op, static, argslots) in enumerate(instrs):
            held = held_at.get(i)
            if held:
                kept = jax.lax.optimization_barrier([vals[s] for s in held])
                for s, v in zip(held, kept):
                    vals[s] = v
            vals.append(OPS[op](static, *(vals[s] for s in argslots)))
        return tuple(vals[s] for s in out_slots)

    return run


def _pending_roots() -> list:
    """Pending ndarrays of the CURRENT stream in deterministic (creation)
    order — the program the next flush will run is defined by this set."""
    return current_stream().pending_roots()


def _prepare_program(exprs: Sequence[Expr]):
    """Rewrite + linearize — shared by flush() and analyze_pending() so both
    always see the identical program.  Returns ``(program, leaves, exprs)``
    where ``exprs`` are the (possibly rewritten) roots, so the RAMBA_VERIFY
    verifier can re-check the very graph that was linearized."""
    if common.rewrite_enabled:
        from ramba_tpu.core.rewrite import rewrite_roots

        try:
            exprs = rewrite_roots(exprs)
        except Exception as e:
            # The rewriter is an optimizer: a crash in it must never take
            # the flush down.  Degrade to the unrewritten graph.
            _registry.inc("resilience.rewrite_bypassed")
            _events.emit({
                "type": "degrade", "site": "rewrite", "action": "rung",
                "from": "rewritten", "to": "unrewritten",
                "error": f"{type(e).__name__}: {e}"[:300],
            })
    program, leaves = _linearize(exprs)
    return program, leaves, exprs


def _program_label(program: _Program) -> str:
    """Stable per-structure label for profiling: hashes only the op sequence
    (statics can hold closures whose repr embeds memory addresses) — the
    reference names kernels sha256(code), ramba.py:8260-8265."""
    text = " ".join(op for op, _, _ in program.instrs) + f"|{program.n_leaves}"
    return "prog_" + hashlib.sha256(text.encode()).hexdigest()[:12]


def _cache_key(program: _Program, donate_key: tuple,
               compile_class=None) -> tuple:
    """Full compile-cache key: structure + donation mask + the trace-time
    semantic fingerprint (+ the shape-bucket compile class, when the
    flush was bucketed — bucketed and exact-shape executables must never
    share an entry)."""
    if compile_class is None:
        return (program.key, donate_key, _semantic_fingerprint())
    return (program.key, donate_key, _semantic_fingerprint(),
            ("class",) + tuple(compile_class))


def _get_compiled(program: _Program, donate_key: tuple,
                  leaf_vals=None, force_backend: Optional[str] = None,
                  compile_class=None):
    """Compile-cache lookup (mesh-epoch aware, true LRU).  Returns
    ``(fn, is_new, fingerprint, backend)`` where ``fingerprint`` is the
    stable per-kernel key the cost ledger files this program under and
    ``backend`` names the lowering that produced ``fn`` (``"xla"`` /
    ``"pallas"``; None for the default XLA lowering when the autotuner is
    not consulted).  The backend-selection seam: with ``RAMBA_AUTOTUNE``
    armed and ``leaf_vals`` provided, ``core/autotune.py`` picks the
    backend per fingerprint from the cost ledger; ``force_backend`` pins
    it (races, prewarms, fallback retries).  XLA executables keep the
    historical cache key so fingerprints stay stable across autotune
    on/off; a Pallas executable lives under ``key + ("pallas",)`` — a
    loser backend ages out through the same LRU as everything else.  The
    whole lookup runs under ``_cache_lock`` — jax.jit object creation is
    lazy (the expensive compile happens at first *call*, outside), so
    the critical section stays short while concurrent streams can never
    corrupt the LRU order or double-count a miss."""
    global _cache_epoch
    from ramba_tpu.core import autotune as _autotune
    with _cache_lock:
        if _cache_epoch != _mesh.mesh_epoch:
            _compile_cache.clear()
            _cache_epoch = _mesh.mesh_epoch
        key = _cache_key(program, donate_key, compile_class)
        fp = _ledger.fingerprint(key)
        if force_backend is not None:
            backend = force_backend
        elif leaf_vals is not None and _autotune.active():
            backend, _via = _autotune.select(fp, program, leaf_vals)
        else:
            backend = None
        cache_key = key if backend != "pallas" else key + ("pallas",)
        fn = _compile_cache.pop(cache_key, None)
        if fn is not None:
            _compile_cache[cache_key] = fn  # re-insert: move to MRU position
            _registry.inc("fuser.cache_hit")
            _ledger.record_cache(fp, "hit")
            return fn, False, fp, backend
        build = None
        if backend == "pallas":
            from ramba_tpu.ops import pallas_backend as _pallas
            try:
                build = _pallas.lower_program(program, leaf_vals)
            except Exception as e:
                _autotune.note_failure(fp, "pallas", e)
                build = None
            if build is None:
                # not lowerable (or lowering failed): degrade to the XLA
                # backend, re-checking the cache under the XLA key
                backend = "xla" if force_backend is None \
                    or _autotune.active() else None
                cache_key = key
                fn = _compile_cache.pop(cache_key, None)
                if fn is not None:
                    _compile_cache[cache_key] = fn
                    _registry.inc("fuser.cache_hit")
                    _ledger.record_cache(fp, "hit")
                    return fn, False, fp, backend
        if len(_compile_cache) >= _COMPILE_CACHE_MAX:
            old_key = next(iter(_compile_cache))  # LRU: least recently used
            _compile_cache.pop(old_key)
            _registry.inc("fuser.cache_evict")
            _ledger.record_cache(_ledger.fingerprint(old_key), "evict")
            _events.emit({
                "type": "cache_evict",
                "key": _ledger.fingerprint(old_key),
                "capacity": _COMPILE_CACHE_MAX,
            })
        # Persistent AOT lane (compile/persist.py): a compile-cache miss
        # consults the on-disk executable cache before paying a compile.
        # A deserialized executable is a hit for accounting purposes —
        # is_new stays False so the ledger shows near-zero compile wall
        # in a warm process.
        if (leaf_vals is not None and backend != "pallas"
                and build is None and _persist.armed()):
            aot = _persist.lookup(fp, leaf_vals, program, donate_key)
            if aot is not None:
                _compile_cache[cache_key] = aot
                _ledger.record_cache(fp, "miss")
                return aot, False, fp, backend
        _faults.check("compile", instrs=len(program.instrs))
        fn = _layouts.RowMajorJit(build if build is not None
                                  else _build_callable(program), donate_key)
        _compile_cache[cache_key] = fn
        with _stats_lock:
            stats["compiles"] += 1
        _registry.inc("fuser.cache_miss")
        _ledger.record_cache(fp, "miss")
        if (leaf_vals is not None and backend != "pallas"
                and build is None and _persist.armed()):
            # register as an AOT candidate (compiles are rare; the one
            # small program-skeleton write stays off the steady state)
            _persist.note_compiled(fp, program, donate_key, leaf_vals,
                                   compile_class=compile_class)
        return fn, True, fp, backend


def _last_use_map(program: _Program) -> dict:
    """slot -> highest slot index that consumes it; program outputs are
    pinned past the end so they are never freed or donated."""
    instrs, n_leaves = program.instrs, program.n_leaves
    last_use: dict[int, int] = {}
    for i, (_op, _st, args) in enumerate(instrs):
        for s in args:
            last_use[s] = n_leaves + i
    inf = n_leaves + len(instrs) + 1
    for s in program.out_slots:
        last_use[s] = inf
    return last_use


def _byte_segment_end(instrs, n_leaves, start: int, slot_bytes: dict,
                      max_seg_bytes: int, seg_cap: int) -> int:
    """First instruction index past a byte-bounded segment starting at
    ``start``: accumulate the estimated bytes each instruction adds to
    the segment's live set (its output slot plus any external inputs it
    pulls in) and stop before the running total crosses
    ``max_seg_bytes``.  Always admits at least one instruction."""
    base = n_leaves + start
    ninstr = len(instrs)
    seen_in: set = set()
    seg_bytes = 0
    end = start
    while end < ninstr:
        if seg_cap and end - start >= seg_cap:
            break
        _op, _st, args = instrs[end]
        cost = slot_bytes.get(n_leaves + end, 0)
        for s in args:
            if s < base and s not in seen_in:
                cost += slot_bytes.get(s, 0)
        if end > start and seg_bytes + cost > max_seg_bytes:
            break
        for s in args:
            if s < base:
                seen_in.add(s)
        seg_bytes += cost
        end += 1
    return end


def _live_order(program: _Program, slot_bytes: dict) -> list:
    """A topological order of ``program``'s instructions that keeps few
    values live: depth-first from the outputs, at every node the operand
    whose sub-DAG makes the most bytes first (Sethi and Ullman's rule,
    with the bytes of the distinct instructions below a node for its
    register need).  ``_linearize`` walks root by root, so a root that is
    an input of the others (PRK's ten ``A += 1``) is laid down whole
    before its first reader; this order interleaves it."""
    instrs, n_leaves = program.instrs, program.n_leaves
    below = []  # bit j set: instruction j is in the sub-DAG
    for i, (_op, _st, args) in enumerate(instrs):
        m = 1 << i
        for s in args:
            if s >= n_leaves:
                m |= below[s - n_leaves]
        below.append(m)
    out_bytes = [slot_bytes.get(n_leaves + i, 0) for i in range(len(instrs))]
    weight = [sum(b for j, b in enumerate(out_bytes[:i + 1]) if m >> j & 1)
              for i, m in enumerate(below)]

    def heaviest_first(slots):
        idx = {s - n_leaves for s in slots if s >= n_leaves}
        return sorted(idx, key=lambda i: (-weight[i], i))

    order, seen = [], set()
    stack = [(i, False) for i in reversed(heaviest_first(program.out_slots))]
    while stack:
        i, done = stack.pop()
        if done:
            order.append(i)
        elif i not in seen:
            seen.add(i)
            stack.append((i, True))
            stack.extend((j, False) for j in
                         reversed(heaviest_first(instrs[i][2])))
    return order


def _live_grouped(program: _Program, leaf_avals,
                  groups: int) -> Optional[_Program]:
    """``program`` in :func:`_live_order`, cut by the byte segmenter into
    ``groups`` live groups of even estimated bytes (the smallest per-group
    target that needs no more; where equal sizes let no target give just
    that many, the next target down: never fewer while a cut is left), as
    a program whose callable holds the values live at each cut behind a
    barrier.  Same leaves, same outputs in the same order, so the
    donation mask carries over.  None when the segmenter finds no cut."""
    from ramba_tpu.analyze import rules as _rules

    n_leaves = program.n_leaves
    slot_bytes = _rules.slot_nbytes(program, leaf_avals)
    order = _live_order(program, slot_bytes)
    new_slot = {n_leaves + i: n_leaves + k for k, i in enumerate(order)}
    instrs = tuple(
        (op, st, tuple(new_slot.get(s, s) for s in args))
        for op, st, args in (program.instrs[i] for i in order))
    slot_bytes = {new_slot.get(s, s): b for s, b in slot_bytes.items()}

    def cuts_for(target):
        cuts, start = [], 0
        while start < len(instrs):
            start = _byte_segment_end(instrs, n_leaves, start, slot_bytes,
                                      target, 0)
            cuts.append(start)
        return cuts[:-1]

    lo, hi = 1, max(1, sum(slot_bytes.values()))  # one group fits in hi
    while lo < hi:
        mid = (lo + hi) // 2
        if len(cuts_for(mid)) < groups:
            hi = mid
        else:
            lo = mid + 1
    cuts = cuts_for(hi)
    if len(cuts) + 1 < groups and hi > 1:
        cuts = cuts_for(hi - 1)
    if not cuts:
        return None
    return _Program(instrs, n_leaves, program.leaf_kinds,
                    [new_slot.get(s, s) for s in program.out_slots],
                    live_cuts=cuts)


def _repetition(program: _Program):
    """``(start, period, count)`` of the unrolled loop that makes up most
    of ``program``, or None: ``count`` whole repetitions of ``period``
    instructions from ``start``, each instruction equal to the one a
    period later in op, static and where its operands come from (a leaf
    by its kind, a value by its distance).  A script that iterates (a
    solver's sweeps, a chain of equal updates) linearizes to this."""
    instrs, n_leaves, kinds = program.instrs, program.n_leaves, \
        program.leaf_kinds
    sig = np.empty(len(instrs), np.int64)
    for i, (op, st, args) in enumerate(instrs):
        rel = tuple(kinds[s] if s < n_leaves else n_leaves + i - s
                    for s in args)
        try:
            sig[i] = hash((op, st, rel))
        except TypeError:  # a static that carries a list or a dict
            sig[i] = hash((op, rel))
    n, mid = len(sig), len(sig) // 2
    for period in np.flatnonzero(sig[mid + 1:] == sig[mid]) + 1:
        if mid + 2 * period > n:
            break
        if not np.array_equal(sig[mid:mid + period],
                              sig[mid + period:mid + 2 * period]):
            continue
        same = sig[:-period] == sig[period:]
        breaks = np.flatnonzero(~same)
        below, above = breaks[breaks < mid], breaks[breaks > mid]
        start = int(below[-1]) + 1 if len(below) else 0
        stop = (int(above[0]) if len(above) else len(same)) + period
        if 2 * (stop - start) >= n:  # a local echo is not the loop
            return start, int(period), (stop - start) // int(period)
    return None


def _segment_ends(program: _Program, seg_size: int) -> list:
    """Where the count-bounded segments of ``program`` end.  A program
    that repeats is cut at the same places of every repetition, so that
    one executable serves every repetition: whole repetitions to a segment
    where a repetition is short, else a repetition in equal parts.  A
    segment takes the fewest repetitions that reach an eighth of
    ``seg_size``: packing more saves a call's fixed cost and costs compile
    time that grows with the executable (six repetitions of 110
    instructions to a call: 13 s more set-up for 12 ms a solve of
    dispatch; PERF.md section 6, PR 37).  Of the numbers
    up to that, the largest that divides the loop's count is taken if it
    is at least half of it, so that the calls at most double; where none
    is, that many, and the repetitions left over are cut with what stands
    after the loop.  Either way a remainder makes no program of its own to
    trace, lower and compile (19 repetitions of 294 instructions, two to a
    segment of 768: a fourth executable and 10 s of a 48 s set-up; PERF.md
    section 6, PR 35).  What stands before and after the loop, and a
    program with no loop, is cut every ``seg_size`` instructions."""
    ninstr = len(program.instrs)
    loop = _repetition(program) if ninstr > seg_size else None
    if loop is None:
        return list(range(seg_size, ninstr, seg_size)) + [ninstr]
    start, period, count = loop
    ends = list(range(seg_size, start, seg_size))
    if start:
        ends.append(start)
    if period <= seg_size:
        want = min(max(1, -(-(seg_size // 8) // period)), seg_size // period,
                   count)
        reps = max((k for k in range(-(-want // 2), want + 1)
                    if count % k == 0), default=want)
        last = start + period * reps * (count // reps)
        ends += list(range(start + period * reps, last + 1, period * reps))
    else:
        parts = -(-period // seg_size)
        ends += [start + k * period + (j + 1) * period // parts
                 for k in range(count) for j in range(parts)]
        last = start + period * count
    ends += list(range(last + seg_size, ninstr, seg_size))
    if ends[-1] != ninstr:
        ends.append(ninstr)
    return ends


#: a program's count-bounded segments, by (program, size): the script of
#: an iterating solver flushes the same thousands of instructions every
#: time, and finding its loop and cutting it is host time with the chip
#: idle (50 to 90 ms of a 13,381-instruction flush; PERF.md section 6, PR
#: 32).  Segments hold no arrays.  Dropped whole when full.
_segments_cache: dict = {}
_SEGMENTS_CACHE_MAX = 64


def _iter_segments(program: _Program, last_use: dict,
                   seg_size: Optional[int] = None, *,
                   slot_bytes: Optional[dict] = None,
                   max_seg_bytes: Optional[int] = None):
    """Split ``program`` into sub-programs of at most ``seg_size``
    (default ``common.max_program_instrs``) instructions, cut where
    ``_segment_ends`` says — or, when ``max_seg_bytes``/``slot_bytes`` are
    given (the ``chunked`` rung), of bounded *estimated live bytes* per
    segment.  Returns the list of
    ``(seg_prog, in_slots, out_here, top)`` where ``in_slots`` are the
    parent-program value slots the segment consumes, ``out_here`` the
    parent slots it must emit (used later or program outputs), and ``top``
    the first parent slot index past this segment."""
    if seg_size is None:
        seg_size = common.max_program_instrs
    if max_seg_bytes and slot_bytes is not None:
        return list(_cut_segments(program, last_use, seg_size, slot_bytes,
                                  max_seg_bytes))
    if program.key_hash == -1:  # unhashable: cut anew every time
        return list(_cut_segments(program, last_use, seg_size))
    key = (_plancache._HashedKey(program.key, program.key_hash), seg_size)
    segments = _segments_cache.get(key)
    if segments is None:
        segments = tuple(_cut_segments(program, last_use, seg_size))
        if len(_segments_cache) >= _SEGMENTS_CACHE_MAX:
            _segments_cache.clear()
        _segments_cache[key] = segments
    return segments


def _cut_segments(program: _Program, last_use: dict, seg_size: int,
                  slot_bytes: Optional[dict] = None,
                  max_seg_bytes: Optional[int] = None):
    instrs, n_leaves = program.instrs, program.n_leaves
    ninstr = len(instrs)
    by_bytes = slot_bytes is not None
    ends = None if by_bytes else iter(_segment_ends(program, seg_size))
    start = 0
    while start < ninstr:
        if by_bytes:
            end = _byte_segment_end(instrs, n_leaves, start, slot_bytes,
                                    max_seg_bytes, seg_size)
        else:
            end = next(ends)
        base, top = n_leaves + start, n_leaves + end
        seg = instrs[start:end]
        in_slots = sorted(
            {s for _o, _s, args in seg for s in args if s < base}
        )
        remap = {s: j for j, s in enumerate(in_slots)}
        nin = len(in_slots)
        seg_instrs = tuple(
            (op, st, tuple(remap[s] if s < base else nin + (s - base)
                           for s in args))
            for op, st, args in seg
        )
        out_here = [s for s in range(base, top) if last_use.get(s, 0) >= top]
        seg_prog = _Program(
            seg_instrs,
            nin,
            tuple(program.leaf_kinds[s] if s < n_leaves else "C"
                  for s in in_slots),
            tuple(nin + (s - base) for s in out_here),
        )
        yield seg_prog, tuple(in_slots), tuple(out_here), top
        start = end


def _run_segmented(program: _Program, leaf_vals: list, donate_idx: tuple,
                   span: Optional[dict] = None,
                   seg_size: Optional[int] = None, *,
                   slot_bytes: Optional[dict] = None,
                   max_seg_bytes: Optional[int] = None,
                   rung: str = "fused"):
    """Execute an oversized program as chained jit calls of at most
    ``seg_size`` (default ``common.max_program_instrs``) instructions each.

    XLA compile time grows superlinearly with program length (a 3000-op
    elementwise chain took minutes on CPU), so one giant jit is a
    scalability hazard the reference never hits only because its tests cap
    chain length.  Segment boundaries cut the dataflow: values crossing a
    boundary become segment outputs carried to the next call.  Each segment
    is cached by its own structure, so a long chain of repeated ops compiles
    ONE segment and reuses it; cross-segment intermediates that die inside a
    segment are donated so the chain still updates HBM in place.
    """
    n_leaves = program.n_leaves
    last_use = _last_use_map(program)
    donate_set = set(donate_idx)
    vals: dict[int, object] = dict(enumerate(leaf_vals))
    for seg_prog, in_slots, out_here, top in _iter_segments(
        program, last_use, seg_size,
        slot_bytes=slot_bytes, max_seg_bytes=max_seg_bytes,
    ):
        seg_donate = []
        for j, s in enumerate(in_slots):
            if last_use.get(s, 0) >= top:
                continue  # still live after this segment
            if s < n_leaves and s not in donate_set:
                continue  # caller-visible leaf not cleared for donation
            if _nbytes(vals[s]) >= DONATE_MIN_BYTES:
                seg_donate.append(j)
        fn, is_new, fp, _backend = _get_compiled(seg_prog, tuple(seg_donate))
        seg_vals = [vals[s] for s in in_slots]
        outs = _execute_compiled(fn, seg_prog, seg_vals, is_new, span=span,
                                 fp=fp, rung=rung, donated=len(seg_donate))
        del seg_vals
        for s in in_slots:
            if last_use.get(s, 0) < top:
                del vals[s]
        for s, v in zip(out_here, outs):
            vals[s] = v
        with _stats_lock:
            stats["segments"] += 1
        _registry.inc("fuser.segments")
        _registry.inc("fuser.segment.miss" if is_new
                      else "fuser.segment.hit")
    return tuple(vals[s] for s in program.out_slots)


def _run_chunked(program: _Program, leaf_vals, donate_idx: tuple,
                 span: Optional[dict] = None):
    """The ``chunked`` rung: the segmented executor bounded by *estimated
    live bytes* per segment (resilience.memory supplies the target)
    instead of instruction count.  Donation-chain semantics are exactly
    ``_run_segmented``'s — mid-chain intermediates (and cleared leaves,
    when admission control routed here with a donate mask) still free as
    they die, which is what bounds the peak live set."""
    from ramba_tpu.analyze import rules as _rules

    avals = _memory._leaf_avals(leaf_vals)
    slot_bytes = _rules.slot_nbytes(program, avals)
    cap = _memory.chunk_target_bytes()
    if span is not None:
        span["chunk_bytes"] = cap
    _registry.inc("fuser.chunked_runs")
    return _run_segmented(program, leaf_vals, donate_idx, span=span,
                          slot_bytes=slot_bytes, max_seg_bytes=cap,
                          rung="chunked")


# compiled function -> {leaf signature: the kernel notes of the call that
# traced it for that signature}.  Weak: an entry dies with its executable
# (LRU eviction, mesh epoch).
_traced_kernel_notes: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def _count_kernel_paths(fn, leaf_vals, kernel_notes: list) -> None:
    """Keep ``<kernel>.path.<path>`` (and ``<kernel>.interpret``) counting
    per flush, cache hit or not.  A call of ``fn`` that traced has counted
    its kernels through ``registry.note_kernel``: its notes are kept with
    ``fn`` under the leaves' signature (jit traces once per signature, and a
    kernel may choose its path by shape).  A call that traced nothing
    replays the notes of the trace it runs into the counters only."""
    by_sig = _traced_kernel_notes.get(fn)
    if by_sig is None and not kernel_notes:
        return  # no kernel noted under this executable: the common case
    sig = tuple(v.aval if isinstance(v, jax.Array) else type(v)
                for v in leaf_vals)
    if kernel_notes:
        if by_sig is None:
            by_sig = _traced_kernel_notes.setdefault(fn, {})
        by_sig[sig] = tuple(kernel_notes)
    else:
        _registry.replay_kernel_notes(by_sig.get(sig, ()))


def _execute_compiled(fn, program: _Program, leaf_vals, is_new: bool,
                      span: Optional[dict] = None, fp: Optional[str] = None,
                      rung: str = "fused", donated: int = 0,
                      backend: Optional[str] = None):
    """Run one compiled program with the shared observability treatment:
    RAMBA_SHOW_CODE dump on first compile, the fence under its profiler
    annotation (``ramba.flush.fence``), first-call
    (trace+lower+XLA compile) vs steady-state timing attribution, a cost
    ledger record filed under ``fp`` (with the degradation ``rung`` this
    execution ran on), and — when ``span`` is given — a per-call child
    record in the flush span.  Used by both the monolithic and segmented
    flush paths so the two can never drift."""
    # Attribution clock starts at call entry — BEFORE the fault hooks — so
    # an injected execute delay lands in the span's compile/dispatch
    # stage exactly like a real host slowdown.
    t_call = time.perf_counter()
    _faults.check("execute", instrs=len(program.instrs))
    _faults.check("oom", instrs=len(program.instrs))
    if is_new and _ledger.cost_enabled() and fp is not None:
        # Before execution: donated input buffers are dead afterwards, and
        # AOT lowering wants live avals.
        _ledger.capture_cost(fp, fn, leaf_vals, backend=backend)
    if is_new and common.show_code:
        import sys

        # jaxpr + lowered StableHLO (the reference's RAMBA_SHOW_CODE
        # dumps the generated Numba source, ramba.py:8266-8284).
        # Lowering only — compiling here would build a throwaway AOT
        # executable the call below cannot reuse.
        print(
            jax.make_jaxpr(_build_callable(program))(*leaf_vals),
            file=sys.stderr,
        )
        try:
            print(fn.lower(*leaf_vals).as_text()[:20000], file=sys.stderr)
        except Exception:
            pass
    bytes_in = sum(_nbytes(v) for v in leaf_vals)
    t0 = time.perf_counter()
    # jax traces (first call, or a new shape under this key) inside the
    # call below; kernels that choose a lowering while traced note it
    # (registry.note_kernel): the notes land on this flush's span, and a
    # later call that traces nothing counts them again, so the
    # ``<kernel>.path.*`` counters move on every flush that runs the kernel
    with _registry.collect_kernel_notes() as kernel_notes:
        outs = fn(*leaf_vals)
    _count_kernel_paths(fn, leaf_vals, kernel_notes)
    dt = time.perf_counter() - t0
    sync_dt = None
    fence_dt = None
    # Cheap device fence: dt above stays the dispatch-time measurement
    # every existing consumer sees; the fence window is the on-device
    # tail the stage ledger files as device_execute.
    if _attrib.fence_enabled() or _ledger.sync_timing():
        # a device failure surfaces here (dispatch is asynchronous) and
        # belongs to this attempt: let the ladder classify it
        with _profile.flush_annotation("fence", span):
            jax.block_until_ready(outs)
        fence_dt = time.perf_counter() - t0 - dt
        # the fence wait is observability's own cost: the device tail
        # would have overlapped the host had we not blocked on it
        _observer.add("fence", fence_dt)
        if _ledger.sync_timing():
            # RAMBA_PERF=sync: a second, device-synchronized sample.
            sync_dt = dt + fence_dt
    # hashed once a call, and only for whoever reads it: the per-function
    # timer, the ledger row, the span's call entry
    timed = not is_new and common.timing_level > 0
    label = (_program_label(program)
             if timed or fp is not None or span is not None else None)
    if is_new:
        # jax.jit compiles lazily: the first call pays trace+lower+XLA
        # compile.  Attribute it separately so per-program execution times
        # stay comparable.
        _timing.add_time("trace_compile_first_call", dt)
    else:
        _timing.add_time("flush_execute", dt)
        if timed:
            _timing.add_func_time(label, dt)
    if fp is not None:
        _ledger.record_execute(
            fp, label, len(program.instrs), rung, dt,
            is_new, bytes_in=bytes_in,
            bytes_out=sum(_nbytes(o) for o in outs),
            donated=donated, sync_seconds=sync_dt,
            tenant=current_tenant(), backend=backend,
        )
    if span is not None:
        if is_new:
            # first call pays trace+lower+XLA compile; the pre-call
            # prelude (cost probe, show_code lowering) bills here too
            _attrib.add_stage(span, "compile", (t0 - t_call) + dt)
        else:
            _attrib.add_stage(span, "dispatch", (t0 - t_call) + dt)
        if fence_dt is not None:
            _attrib.add_stage(span, "device_execute", fence_dt)
        call = {
            "label": label,
            "cache": "miss" if is_new else "hit",
            "seconds": round(dt, 6),
        }
        if backend is not None:
            call["backend"] = backend
        span["calls"].append(call)
        if kernel_notes:
            span.setdefault("kernels", []).extend(kernel_notes)
    return outs


def _attempt_fused(program: _Program, leaf_vals, donate_key: tuple,
                   span: Optional[dict], class_plan=None):
    """Rung 0: the normal fused path (monolithic jit, or the standard
    segmented executor above ``common.max_program_instrs``).  With
    ``RAMBA_COMPILE_CLASSES`` armed and a bucket plan certified for this
    flush, leaves are zero-padded up to the bucket before execution and
    outputs sliced back to the exact extent — the pad/slice wrapper that
    lets a million request shapes share one executable.  Only this rung
    buckets: the lower resilience rungs always run exact shapes, and the
    padded copies are fresh temporaries so donating them is safe while
    the original leaves stay alive for any fallback.  With
    ``RAMBA_AUTOTUNE`` armed this is where the backend race plays out:
    the autotuner may hand back the Pallas lowering, whose first
    (compile-paying) call is deferred through the async compile pipeline
    when one is live, and whose failures degrade to the XLA backend —
    recorded on the ledger — before the resilience ladder is ever
    involved."""
    if (
        common.max_program_instrs
        and len(program.instrs) > common.max_program_instrs
    ):
        return _run_segmented(program, leaf_vals, donate_key, span=span)
    if class_plan is not None:
        padded = _classes.apply(class_plan, leaf_vals)
        outs = _attempt_fused_exec(program, padded, donate_key, span,
                                   compile_class=class_plan.token)
        return _classes.strip(class_plan, outs)
    return _attempt_fused_exec(program, leaf_vals, donate_key, span)


def _attempt_fused_exec(program: _Program, leaf_vals, donate_key: tuple,
                        span: Optional[dict], compile_class=None):
    fn, is_new, fp, backend = _get_compiled(program, donate_key,
                                            leaf_vals=leaf_vals,
                                            compile_class=compile_class)
    if backend == "pallas":
        from ramba_tpu.core import autotune as _autotune

        if is_new and _autotune.mode() == "race":
            # Race compiles must not stall this flush (or, on the async
            # path, other tenants' tickets): when a compile pipeline is
            # live, warm the Pallas executable through it and serve this
            # flush from the XLA backend meanwhile.  (force:<backend>
            # deliberately compiles inline — the operator asked for that
            # backend now, not eventually.)
            pipe = None
            try:
                from ramba_tpu.serve import pipeline as _pipeline
                pipe = _pipeline.current_pipeline()
            except Exception:
                pipe = None
            # single-controller only: async warm completion would skew
            # the per-rank race counts out of SPMD lockstep, and the
            # latch agreement collective relies on that lockstep
            if pipe is not None and hasattr(pipe, "submit_warm") \
                    and jax.process_count() == 1:
                _autotune.maybe_prewarm(fp, program, leaf_vals, donate_key)
                fn, is_new, fp, backend = _get_compiled(
                    program, donate_key, leaf_vals=leaf_vals,
                    force_backend="xla", compile_class=compile_class)
                return _execute_compiled(
                    fn, program, leaf_vals, is_new, span=span, fp=fp,
                    rung="fused", donated=len(donate_key), backend=backend)
        try:
            return _execute_compiled(
                fn, program, leaf_vals, is_new, span=span, fp=fp,
                rung="fused", donated=len(donate_key), backend=backend)
        except _faults.InjectedFault:
            # execute/oom fault sites belong to the resilience ladder,
            # not to backend selection (the "pallas" fault site fires at
            # lowering time, inside _get_compiled)
            raise
        except Exception as e:
            # A Pallas kernel that traced fine can still fail at first
            # call (Mosaic compile) or at dispatch.  Degrade to the XLA
            # backend for this fingerprint — permanently — provided no
            # leaf buffer was consumed by the failed attempt.
            for v in leaf_vals:
                is_deleted = getattr(v, "is_deleted", None)
                if is_deleted is not None and is_deleted():
                    raise
            _autotune.note_failure(fp, "pallas", e)
            with _cache_lock:
                _compile_cache.pop(
                    _cache_key(program, donate_key, compile_class)
                    + ("pallas",), None)
            _events.emit({
                "type": "degrade", "site": "backend", "action": "backend",
                "from": "pallas", "to": "xla",
                "error": f"{type(e).__name__}: {e}"[:300],
            })
            fn, is_new, fp, backend = _get_compiled(
                program, donate_key, leaf_vals=leaf_vals,
                force_backend="xla", compile_class=compile_class)
            return _execute_compiled(
                fn, program, leaf_vals, is_new, span=span, fp=fp,
                rung="fused", donated=len(donate_key), backend=backend)
    return _execute_compiled(fn, program, leaf_vals, is_new, span=span,
                             fp=fp, rung="fused", donated=len(donate_key),
                             backend=backend)


def _run_eager(program: _Program, leaf_vals, span: Optional[dict]):
    """Rung 2: per-op eager dispatch — no jit, no fusion, no donation.
    Blocks on the results so any execution failure surfaces inside this
    rung (eager dispatch is async) rather than at a later materialize."""
    _faults.check("eager")
    t0 = time.perf_counter()
    outs = _build_callable(program)(*leaf_vals)
    outs = jax.block_until_ready(outs)
    dt = time.perf_counter() - t0
    _ledger.record_execute(
        _ledger.fingerprint(_cache_key(program, ())),
        _program_label(program), len(program.instrs), "eager", dt, False,
        bytes_in=sum(_nbytes(v) for v in leaf_vals),
        bytes_out=sum(_nbytes(o) for o in outs),
        tenant=current_tenant(),
    )
    if span is not None:
        span["calls"].append({
            "label": _program_label(program),
            "cache": "eager",
            "seconds": round(dt, 6),
        })
    return outs


def _run_host(program: _Program, leaf_vals, span: Optional[dict]):
    """Rung 3 (last): interpret the whole program on the CPU backend —
    device → host fallback as a first-class path.  Inputs are pulled to
    host memory, the program runs eagerly on CPU, and outputs are placed
    back onto the accelerator mesh when it will accept them (kept
    host-committed otherwise: a degraded-but-correct result beats a
    crash).  Only offered single-controller — under multi-host SPMD no
    single process holds the global array."""
    _faults.check("host")
    import numpy as np
    from jax.sharding import NamedSharding

    t0 = time.perf_counter()
    cpu = jax.devices("cpu")[0]
    host_vals = []
    for kind, v in zip(program.leaf_kinds, leaf_vals):
        if isinstance(v, jax.Array):
            if kind == "S" and v.weak_type:
                # a resident scalar, back to the number it stands for:
                # NumPy's array would lose its weak type
                v = v.item()
            else:
                v = jax.device_put(np.asarray(v), cpu)
        host_vals.append(v)
    with jax.default_device(cpu):
        outs = _build_callable(program)(*host_vals)
    outs = jax.block_until_ready(outs)
    mesh = _mesh.get_mesh()
    res = []
    for o in outs:
        try:
            spec = _mesh.default_spec(o.shape, mesh)
            res.append(jax.device_put(o, NamedSharding(mesh, spec)))
        except Exception as e:
            # the mesh would not take the result back: it stays committed
            # to the host CPU, and the timeline says so
            _registry.inc("resilience.host_committed")
            _events.emit({
                "type": "degrade", "site": "flush",
                "action": "host_committed", "shape": list(o.shape),
                "error": f"{type(e).__name__}: {e}"[:300],
            })
            res.append(o)
    dt = time.perf_counter() - t0
    _ledger.record_execute(
        _ledger.fingerprint(_cache_key(program, ())),
        _program_label(program), len(program.instrs), "host", dt, False,
        bytes_in=sum(_nbytes(v) for v in leaf_vals),
        bytes_out=sum(_nbytes(o) for o in res),
        tenant=current_tenant(),
    )
    if span is not None:
        span["calls"].append({
            "label": _program_label(program),
            "cache": "host",
            "seconds": round(dt, 6),
        })
    return tuple(res)


def _execute_resilient(program: _Program, leaf_vals, donate_key: tuple,
                       span: Optional[dict], skip_fused: bool = False,
                       route_chunked: bool = False,
                       tags: Optional[dict] = None,
                       deadline=None, class_plan=None):
    """Run the program down the degradation ladder (see
    ``resilience.degrade``): fused → split → chunked → eager → host.
    Returns ``(outs, rung_name)``; rung_name is "fused" on the healthy
    path.

    ``skip_fused`` (set when the RAMBA_VERIFY verifier found error
    findings in non-strict mode) starts the ladder at the split rung:
    no monolithic compile and no leaf donation, so a program the
    verifier distrusts can still produce a result without consuming
    caller-visible buffers.

    ``route_chunked`` (set by memory-governor admission control when the
    program cannot fit under the HBM watermark even after eviction)
    starts the ladder at the chunked rung — and, uniquely among
    below-fused rungs, KEEPS the donate mask: no failed attempt has
    consumed anything yet, and donating dead leaves is exactly what
    bounds the chunked peak.

    ``tags`` (e.g. ``{"tenant": ...}``) ride on every degrade event the
    ladder emits so the degradation timeline attributes to a tenant.

    ``deadline`` (a ``serve.overload.Deadline``) makes the ladder
    budget-aware: rungs whose rolling p50 cannot fit the remaining
    budget are pruned (single-controller; rank-local windows must not
    skew an SPMD ladder), every rung attempt re-checks expiry, and the
    elastic watchdog clamps to ``min(watchdog, remaining)``."""
    rungs = []
    if not skip_fused and not route_chunked:
        rungs.append(
            ("fused",
             lambda: _attempt_fused(program, leaf_vals, donate_key, span,
                                    class_plan=class_plan)))
    if (len(program.instrs) > 1 or skip_fused) and not route_chunked:
        cap = common.max_program_instrs or len(program.instrs)
        half = max(1, min(len(program.instrs), cap) // 2)
        # no leaf donation below the fused rung: a donated buffer consumed
        # by a failed attempt could not feed the next rung
        rungs.append(
            ("split",
             lambda: _run_segmented(program, leaf_vals, (), span=span,
                                    seg_size=half, rung="split")))
    if len(program.instrs) > 1 or route_chunked:
        chunk_donate = donate_key if route_chunked else ()
        rungs.append(
            ("chunked",
             lambda: _run_chunked(program, leaf_vals, chunk_donate, span)))
    rungs.append(("eager", lambda: _run_eager(program, leaf_vals, span)))
    try:
        single = jax.process_count() == 1
    except Exception:
        single = True
    if single:
        rungs.append(("host", lambda: _run_host(program, leaf_vals, span)))

    def leaves_alive() -> bool:
        for v in leaf_vals:
            is_deleted = getattr(v, "is_deleted", None)
            if is_deleted is not None and is_deleted():
                return False
        return True

    # Deadline-aware pruning: drop rungs whose rolling p50 cannot fit
    # the remaining budget (lazy import — serve imports this module).
    if deadline is not None:
        from ramba_tpu.serve import overload as _overload

        label = span.get("label", "?") if span else "?"
        tenant = tags.get("tenant") if tags else None
        rungs = _overload.prune_rungs(rungs, deadline, label,
                                      tenant=tenant)

    # Elastic watchdog: every rung attempt checks the "dispatch" fault
    # site (so RAMBA_FAULTS='dispatch:hang:ms=...' can seed a stall) and,
    # when RAMBA_WATCHDOG_S is armed, runs under a deadline — a hang
    # becomes a degrade-classified RankStallError, which the ladder
    # treats like any other failed rung instead of blocking forever.
    # With a request deadline, the per-attempt budget is clamped to
    # min(watchdog, remaining) so one slow rung cannot eat the whole
    # request budget before the ladder can try a cheaper rung.
    wd = _elastic.watchdog_seconds()

    def _guard(rung_name: str, thunk):
        def attempt():
            if deadline is not None:
                from ramba_tpu.serve import overload as _overload

                _overload.check_expired(
                    deadline, span.get("label", "?") if span else "?",
                    tenant=tags.get("tenant") if tags else None)
            _faults.check("dispatch", rung=rung_name)
            if _elastic.cancelled():
                # the watchdog gave up on this attempt while the fault
                # check slept; the ladder has moved on — running the rung
                # now would donate leaf buffers the recovery still owns
                raise RuntimeError(
                    f"abandoned {rung_name} attempt after watchdog stall")
            return thunk()

        if deadline is None:
            if wd is None:
                return attempt
            return lambda: _elastic.with_deadline("dispatch", attempt,
                                                  timeout_s=wd)

        def guarded():
            # clamp at attempt time — the remaining budget has shrunk
            # by however long the earlier rungs ran
            from ramba_tpu.serve import overload as _overload

            eff = _overload.clamp_watchdog(wd, deadline)
            if eff is None:
                return attempt()
            return _elastic.with_deadline("dispatch", attempt,
                                          timeout_s=eff)

        return guarded

    rungs = [(name, _guard(name, fn)) for name, fn in rungs]

    return _degrade.run_ladder("flush", rungs, leaf_check=leaves_alive,
                               tags=tags)


def _leaf_owner_counts(leaves) -> list:
    """Live-alias census per leaf slot: how many materialized ndarrays still
    own each Const leaf's buffer (Scalar leaves own nothing)."""
    with _census_lock:
        return [
            _const_owners.get(id(leaf.value), 0)
            if isinstance(leaf, Const) else 0
            for leaf in leaves
        ]


def _program_event(program: _Program, leaves, donate_key: tuple,
                   label: str, fingerprint: Optional[str] = None,
                   compile_class=None) -> dict:
    """Offline-lintable record of the program a flush is about to run —
    ``python -m ramba_tpu.analyze`` re-checks graph hygiene and donation
    hazards from these events without the live process, and the warm
    pool (``compile/warmpool.py``) ranks traces by the fingerprint +
    compile class recorded here.  Statics are repr-truncated: the
    offline rules need structure (op names, slot refs, donate mask,
    owner counts), not closure identities."""
    ev = {
        "type": "program", "label": label,
        "instrs": [[op, repr(st)[:160], list(args)]
                   for op, st, args in program.instrs],
        "n_leaves": program.n_leaves,
        "leaf_kinds": "".join(program.leaf_kinds),
        "out_slots": list(program.out_slots),
        "donate": list(donate_key),
        "owners": _leaf_owner_counts(leaves),
        "x64": bool(jax.config.jax_enable_x64),
    }
    if fingerprint is not None:
        ev["fingerprint"] = fingerprint
    if compile_class is not None:
        ev["compile_class"] = list(compile_class)
    return ev


def _verify_if_enabled(program: _Program, leaves, exprs, donate_key: tuple,
                       span: dict, label: str, memo_plan=None,
                       class_plan=None) -> bool:
    """RAMBA_VERIFY hook: statically verify the program about to execute
    (see ramba_tpu.analyze).  Strict mode raises ProgramVerificationError
    on error findings — before ``_get_compiled`` is ever reached, so a
    malformed program never compiles, let alone runs.  Non-strict mode
    returns True instead, routing the flush down the degradation ladder
    (skip the fused rung: no monolithic compile, no leaf donation).
    Zero-cost when RAMBA_VERIFY is unset."""
    if not os.environ.get("RAMBA_VERIFY"):
        return False
    from ramba_tpu.analyze import verifier as _verifier

    vmode = _verifier.mode()
    if vmode == "off":
        return False
    findings = _verifier.verify_flush(program, leaves, exprs, donate_key,
                                      label=label, memo_plan=memo_plan,
                                      class_plan=class_plan)
    if findings:
        counts: dict = {}
        for f in findings:
            counts[f.severity] = counts.get(f.severity, 0) + 1
        span["findings"] = counts
    errors = [f for f in findings if f.severity == "error"]
    if not errors:
        return False
    if vmode == "strict":
        from ramba_tpu.analyze.findings import ProgramVerificationError

        raise ProgramVerificationError(errors)
    span["verify_routed"] = True
    return True


# ---------------------------------------------------------------------------
# the staged flush: prepare (cheap, caller thread) -> dispatch (execution)
# ---------------------------------------------------------------------------


class _FlushWork:
    """Everything one flush needs between prepare and dispatch — the unit
    the async pipeline queues.  Holds STRONG references to the roots (a
    detached root left the pending registry at collect time and must not
    be collected before write-back) and to the leaf values (pinned +
    flight-counted until dispatch releases them)."""

    __slots__ = ("stream", "roots", "root_exprs", "extra_n", "program",
                 "leaves", "vexprs", "leaf_vals", "donate_key", "span",
                 "label", "fingerprint", "skip_fused", "pins", "flight",
                 "t_flush", "detached", "enqueued_at", "memo_plan",
                 "memo_hit", "deadline", "is_abandoned", "class_plan",
                 "plan_cert")

    def __init__(self, stream, roots, extra_n):
        self.stream = stream
        self.roots = roots
        self.root_exprs = [a._expr for a in roots]
        self.extra_n = extra_n
        self.program = None
        self.leaves = None
        self.vexprs = None
        self.leaf_vals = None
        self.donate_key = ()
        self.span = None
        self.label = "?"
        self.fingerprint = None
        self.skip_fused = False
        self.pins = ()
        self.flight = ()
        self.t_flush = 0.0
        self.detached = False
        self.enqueued_at = None
        # result memoization (core/memo.py): the certified plan, and the
        # cached output values when a lookup already hit
        self.memo_plan = None
        self.memo_hit = None
        # overload plane (serve/overload.py): the request's time budget,
        # and a pipeline-installed probe for ticket abandonment (late
        # completions discard instead of writing back)
        self.deadline = None
        self.is_abandoned = None
        # shape-bucket compile class (compile/classes.py); None = exact
        self.class_plan = None
        # plan-certificate cache (core/plancache.py): the certificate
        # this flush ran under (redeemed or newly minted)
        self.plan_cert = None


def _gather_leaf_vals(leaves):
    """Resolve leaf values for execution (restoring memory-governor
    spills).  Returns ``(leaf_vals, leaf_bytes)``."""
    leaf_vals = []
    leaf_bytes = 0
    for leaf in leaves:
        if isinstance(leaf, Const):
            v = leaf.value
            if isinstance(v, _SpilledArray):
                # Evicted by the memory governor; bring it home before the
                # donation decision so the census sees the device buffer.
                v = _memory.restore(leaf)
            leaf_vals.append(v)
            leaf_bytes += _nbytes(v)
        else:
            leaf_vals.append(leaf.value)
    return leaf_vals, leaf_bytes


# Scalar operands resident on the device: (the value's type and the
# semantic fingerprint, the value's bits) -> the committed array,
# replicated over the mesh as it stands, that a compiled call takes in the
# number's place.  A Python or NumPy number among a jit call's arguments
# crosses to the device on every call, and where the computation has more
# than one device jit leaves its C++ path for each of them
# (``cpp_pjit_shard_arg_fallback``: PERF.md section 6, PR 31).  Shared by
# every stream, emptied with the mesh, dropped whole when full.
_device_scalars: dict = {}
_DEVICE_SCALARS_MAX = 256
_device_scalars_home = None  # (mesh epoch, the mesh's replicated sharding)
_device_scalars_lock = threading.Lock()


def _scalar_bits(value):
    """What tells two values of one type apart: ``0.0`` from ``-0.0`` and
    one NaN from another, which ``==`` does not."""
    t = type(value)
    if t is float:
        return struct.pack("d", value)
    if t is complex:
        return struct.pack("dd", value.real, value.imag)
    if t is int or t is bool:
        return value
    return value.tobytes()  # a NumPy scalar


def _lives_on(v, home) -> bool:
    """Whether a compiled call can take ``v`` beside an array committed to
    the sharding ``home``: it is no device array, or committed nowhere,
    or lives on the same devices."""
    sharding = getattr(v, "sharding", None)
    return (sharding is None
            or getattr(sharding, "mesh", None) is home.mesh
            or not getattr(v, "committed", True)
            or sharding.device_set == home.device_set)


def _resident_scalars(leaves, leaf_vals) -> list:
    """``leaf_vals`` with each ``Scalar`` leaf's number replaced by its
    array from ``_device_scalars``, for every rung of the ladder alike.
    Eligible are exactly the values whose aval ``Scalar`` tables
    (``expr._scalar_key``); the array has that very aval, ``weak_type``
    included, so the program traced for it is the one traced for the
    number.  A value seen before costs a dictionary lookup
    (``dispatch.scalar.hit``), a new one the transfer it has always cost
    (``dispatch.scalar.put``).  Left as they are: a value of any other
    type or width, one whose array would not have the leaf's aval (a
    leaf built under another x64 regime), and every scalar of a flush
    one of whose arrays is committed to other devices than the mesh's
    (an array that outlived a ``set_mesh``), which a scalar committed to
    the mesh would set against it."""
    global _device_scalars_home
    if not any(isinstance(leaf, Scalar) for leaf in leaves):
        return leaf_vals
    vals = list(leaf_vals)
    hits = puts = 0
    with _device_scalars_lock:
        home = _device_scalars_home
        if home is None or home[0] != _mesh.mesh_epoch:
            _device_scalars.clear()
            # the sharding before the epoch: get_mesh may install the mesh
            where = _mesh.replicated_sharding()
            home = _device_scalars_home = (_mesh.mesh_epoch, where)
        where = home[1]
        if not all(_lives_on(v, where) for v in leaf_vals):
            return leaf_vals
        for i, leaf in enumerate(leaves):
            if not isinstance(leaf, Scalar):
                continue
            kind = _scalar_key(leaf.value)
            if kind is None:
                continue
            key = (kind, _scalar_bits(leaf.value))
            arr = _device_scalars.get(key)
            if arr is None:
                arr = jax.device_put(leaf.value, where)  # host to mesh
                aval = leaf.aval  # a ShapeDtypeStruct, as eval_shape gave it
                if (arr.shape, arr.dtype, arr.weak_type) != (
                        aval.shape, aval.dtype, aval.weak_type):
                    continue
                if len(_device_scalars) >= _DEVICE_SCALARS_MAX:
                    _device_scalars.clear()
                _device_scalars[key] = arr
                puts += 1
            else:
                hits += 1
            vals[i] = arr
    if hits:
        _registry.inc("dispatch.scalar.hit", hits)
    if puts:
        _registry.inc("dispatch.scalar.put", puts)
    return vals


def _donation_mask(leaves, leaf_vals) -> tuple:
    """Donate-eligible leaf slots: big enough, owned by no live ndarray,
    and held by no OTHER in-flight flush (each flush's own flight pin
    counts one, so a single stream behaves exactly as before)."""
    donate = []
    with _census_lock:
        owners = [
            _const_owners.get(id(v), 0) if isinstance(leaf, Const) else 1
            for leaf, v in zip(leaves, leaf_vals)
        ]
    with _flight_lock:
        flights = [_inflight_leaves.get(id(v), 0) for v in leaf_vals]
    for i, (leaf, v) in enumerate(zip(leaves, leaf_vals)):
        if not isinstance(leaf, Const):
            continue
        if (
            _nbytes(v) >= DONATE_MIN_BYTES
            and owners[i] == 0
            and flights[i] <= 1
        ):
            donate.append(i)
    return tuple(donate)


def _quarantine(work: "_FlushWork", e: Exception) -> None:
    """Quarantine: every rung of the ladder failed (or the error was
    fatal).  The roots of THIS program must leave the pending registry,
    or the one broken expression re-enters — and re-fails — every
    subsequent flush of its stream, cascading one error into unbounded
    collateral failures.  The arrays keep their lazy graphs; a later
    materialization re-attempts each one alone (ndarray._value), so
    innocent co-pending arrays still produce their values and only the
    truly broken graph re-raises.  Per-stream: other streams' pending
    work is untouched."""
    for arr in work.roots:
        unregister_pending(arr)  # no-op when the work was detached
    n = len(work.roots)
    work.stream.stats["quarantined"] += n
    _registry.inc("resilience.flush_quarantined", n)
    ev = {
        "type": "flush_error", "label": work.label,
        "quarantined": n,
        "error": f"{type(e).__name__}: {e}"[:300],
    }
    if work.stream.tenant is not None:
        ev["tenant"] = work.stream.tenant
    # Under coherent recovery the error that reached quarantine was
    # agreed by every rank (ladder terminal decisions are agreement rounds), so
    # every rank quarantines the same program on the same epoch; stamping
    # the epoch lets merge-ranks pair the quarantines without guessing.
    epoch = _coherence.last_epoch("flush:rung")
    if epoch:
        ev["coherence_epoch"] = epoch
    _events.emit(ev)


def _release(work: "_FlushWork") -> None:
    _memory.ledger.unpin(work.pins)
    work.pins = ()
    _flight_decref(work.flight)
    work.flight = ()


def _flush_discard(work: "_FlushWork") -> None:
    """Soft-discard prepared work that was shed before dispatch
    (overload plane: queue-full unwind, abandoned-ticket drop, shed
    verdict).  Unlike :func:`_quarantine` this is not a failure — no
    flush_error event, no quarantine counters: the roots just leave the
    pending set with their lazy graphs intact, so each array self-heals
    on next touch via the per-array re-flush path.  Pins and flight
    refs are released so the leaves stay donate-eligible."""
    for arr in work.roots:
        unregister_pending(arr)  # no-op when the work was detached
    _release(work)


def _flush_prepare(stream: FlushStream, roots: list,
                   extra: Sequence[Expr] = (), *,
                   detached: bool = False) -> Optional["_FlushWork"]:
    """:func:`_flush_prepare_body` under ``ramba.flush.prepare``; the
    label and trace id reach the annotation once the body has them."""
    with _profile.flush_annotation("prepare") as ann:
        work = _flush_prepare_body(stream, roots, extra, detached=detached)
        if work is not None:
            ann.set_metadata(**_profile.span_args(work.span))
        return work


def _flush_prepare_body(stream: FlushStream, roots: list,
                        extra: Sequence[Expr], *,
                        detached: bool) -> Optional["_FlushWork"]:
    """Stage 1 of a flush: rewrite + linearize, open the span, gather
    leaf values, take the donation census, emit the program event, pin
    the leaves, and run the RAMBA_VERIFY verifier.  Cheap relative to
    execution — this is the part an async enqueue runs on the caller
    thread.  Returns None when there is nothing to run.

    ``detached`` marks work whose roots already left the pending registry
    (async enqueue): any failure here must quarantine them, or they would
    silently vanish.  On the synchronous path only a verifier rejection
    quarantines (matching the historical single-stream flush)."""
    exprs = [a._expr for a in roots] + list(extra)
    if not exprs:
        return None
    work = _FlushWork(stream, roots, len(exprs) - len(roots))
    work.detached = detached
    work.t_flush = time.perf_counter()
    try:
        rw_before = None
        if common.rewrite_enabled:
            from ramba_tpu.core.rewrite import stats as _rw_stats

            rw_before = dict(_rw_stats)
        program, leaves, vexprs = _prepare_program(exprs)
        linearize_s = time.perf_counter() - work.t_flush
        rewrite_fires = {}
        if rw_before is not None:
            from ramba_tpu.core.rewrite import stats as _rw_stats

            rewrite_fires = {
                k: v - rw_before.get(k, 0)
                for k, v in _rw_stats.items()
                if v != rw_before.get(k, 0)
            }
        label = _program_label(program)
        span = {
            "type": "flush",
            "label": label,
            "instrs": len(program.instrs),
            "n_leaves": program.n_leaves,
            "n_roots": len(roots),
            "linearize_s": round(linearize_s, 6),
            "rewrite_fires": rewrite_fires,
            "calls": [],
            "stages": {},
        }
        if stream is not _default_stream:
            span["stream"] = stream.name
        if stream.tenant is not None:
            span["tenant"] = stream.tenant
        if stream.trace_id is not None:
            # the flush span gets its own span id and chains to the
            # session root; dispatch re-scopes to it so rung/stall/memory
            # events become its children
            span["trace_id"] = stream.trace_id
            span["span_id"] = _telemetry.mint_id()
            span["parent_span"] = stream.root_span
        work.program, work.leaves, work.vexprs = program, leaves, vexprs
        work.label, work.span = label, span

        leaf_vals, leaf_bytes = _gather_leaf_vals(leaves)
        work.leaf_vals = leaf_vals
        work.flight = _flight_incref(leaf_vals)
        donate_key = _donation_mask(leaves, leaf_vals)
        try:
            _faults.check("donate_census", donated=len(donate_key))
        except _faults.InjectedFault:
            # Deliberately corrupt the donate mask (ignore the alias
            # census) — the seeded violation the RAMBA_VERIFY
            # donation-hazard rule exists to catch.  Only reachable under
            # explicit fault injection.
            donate_key = tuple(
                i for i, leaf in enumerate(leaves) if isinstance(leaf, Const)
            )
        work.donate_key = donate_key
        span["donated"] = len(donate_key)
        span["leaf_bytes"] = leaf_bytes
        span["mem_live_bytes"] = _memory.ledger.live_bytes
        _profile.ensure_started()
        _telemetry.ensure_started()
        # In-flight leaves are never spill candidates: admission-
        # triggered (or oom-triggered) eviction during THIS flush must
        # not pull a buffer the program is about to read.
        work.pins = _memory.ledger.pin_values(leaf_vals)
        # Everything above is graph capture and leaf plumbing — the
        # per-flush cost no cache can remove, paid identically whether
        # or not a certificate redeems.  Everything below is the
        # analysis pipeline, which a plan certificate skips; the stage
        # ledger splits the two ("trace" vs "prepare") so the waterfall
        # shows exactly what the fast path saves.
        t_analysis = time.perf_counter()
        # Plan-certificate fast path (RAMBA_PLANCERT; analyze/plancert.py
        # + core/plancache.py): a repeat flush whose certificate's
        # invalidation signature still validates skips the entire
        # analysis pipeline below — class proof, fingerprint derivation,
        # memo certification, and the verifier — behind one
        # version-vector comparison.  A plan:stale-forged "hit" is held
        # aside instead of redeemed: strict mode rejects it below with
        # the same quarantine discipline as a verifier error, warn mode
        # silently re-analyzes.
        plan_hit = None
        stale_hit = None
        if _plancache.enabled():
            try:
                hit = _plancache.lookup(program, leaf_vals, donate_key,
                                        label)
            except Exception:
                hit = None
            if hit is not None and hit.forged:
                if _plancache.strict():
                    stale_hit = hit
            elif hit is not None:
                plan_hit = hit
        if plan_hit is not None:
            # Redeem: every verdict below is adopted from the certificate.
            cert = plan_hit.cert
            class_plan = _plancache.class_plan_from(cert)
            work.class_plan = class_plan
            if class_plan is not None:
                span["compile_class"] = list(class_plan.token)
                span["pad_waste_bytes"] = class_plan.pad_waste_bytes
            work.fingerprint = cert.fingerprint or _ledger.fingerprint(
                _cache_key(program, donate_key,
                           class_plan.token
                           if class_plan is not None else None))
            if _classes.enabled():
                _classes.note_decision(work.fingerprint, class_plan)
            if class_plan is not None:
                _ledger.record_class(work.fingerprint, class_plan.token,
                                     class_plan.pad_waste_bytes,
                                     label=label)
            if _events.trace_enabled():
                pev = _program_event(
                    program, leaves, donate_key, label,
                    fingerprint=work.fingerprint,
                    compile_class=(class_plan.token
                                   if class_plan is not None else None))
                pev["plan_cache"] = "hit"
                if cert.chash is not None:
                    pev["chash"] = cert.chash
                if "trace_id" in span:
                    pev.setdefault("trace_id", span["trace_id"])
                    pev.setdefault("parent_span", span["span_id"])
                _events.emit(pev)
            # The memo plan is rebuilt, not re-certified: only the input
            # version tokens are live state.
            work.memo_plan = None
            if cert.memo_ok:
                try:
                    work.memo_plan = _memo.plan_from_cert(
                        cert.chash, cert.canon_form, cert.leaf_order,
                        cert.effects, leaves, leaf_vals)
                except Exception:
                    work.memo_plan = None
            work.plan_cert = cert
            span["plan_cache"] = "hit"
            if cert.chash is not None:
                span["chash"] = cert.chash
            if cert.finding_counts:
                # the certified verdict's findings, re-stamped so the
                # span is indistinguishable from a fresh analysis
                span["findings"] = dict(cert.finding_counts)
        elif stale_hit is None:
            # Compile-class planning (RAMBA_COMPILE_CLASSES): bucket the
            # leading dim so shape-varying traffic shares executables.
            # The decision is a pure function of (program, shapes,
            # policy), so SPMD ranks agree by construction.  The
            # compile:bucket fault site forges a plan that skips the
            # op-safety proof — the seeded violation the compile-class
            # verify rule exists to catch.
            class_plan = None
            if _classes.enabled():
                try:
                    class_plan = _classes.plan_for(program, leaf_vals)
                except Exception:
                    class_plan = None
            try:
                _faults.check("compile:bucket", label=label)
            except _faults.InjectedFault:
                forged = _classes.forced_plan(program, leaf_vals)
                if forged is not None:
                    class_plan = forged
            work.class_plan = class_plan
            if class_plan is not None:
                span["compile_class"] = list(class_plan.token)
                span["pad_waste_bytes"] = class_plan.pad_waste_bytes
            # The fingerprint folds in the class token: each bucket is
            # its own executable, its own ledger row, its own persist
            # entry.
            work.fingerprint = _ledger.fingerprint(_cache_key(
                program, donate_key,
                class_plan.token if class_plan is not None else None))
            if _classes.enabled():
                _classes.note_decision(work.fingerprint, class_plan)
            if class_plan is not None:
                _ledger.record_class(work.fingerprint, class_plan.token,
                                     class_plan.pad_waste_bytes,
                                     label=label)
            if _events.trace_enabled():
                pev = _program_event(
                    program, leaves, donate_key, label,
                    fingerprint=work.fingerprint,
                    compile_class=(class_plan.token
                                   if class_plan is not None else None))
                if "trace_id" in span:
                    pev.setdefault("trace_id", span["trace_id"])
                    pev.setdefault("parent_span", span["span_id"])
                _events.emit(pev)
            # Result-memoization certification (RAMBA_MEMO; None when
            # off or the program is provably uncacheable).  The plan is
            # built before the verifier runs so the memo-safety rule
            # audits it.
            try:
                work.memo_plan = _memo.plan_for(program, donate_key,
                                                leaves, leaf_vals)
            except Exception:
                work.memo_plan = None
    except Exception as e:
        if detached:
            _quarantine(work, e)
        _release(work)
        raise
    if stale_hit is not None:
        # strict mode: a certificate that fails signature validation is
        # rejected exactly like a verifier error — quarantine + raise
        # before anything compiles.
        from ramba_tpu.analyze.findings import ProgramVerificationError

        err = ProgramVerificationError(
            _plancache.stale_findings(stale_hit, label))
        _quarantine(work, err)
        _release(work)
        raise err
    if plan_hit is None:
        t_verify = time.perf_counter()
        try:
            work.skip_fused = _verify_if_enabled(
                program, leaves, vexprs, donate_key, span, label,
                memo_plan=work.memo_plan, class_plan=work.class_plan,
            )
        except Exception as e:
            _quarantine(work, e)
            _release(work)
            raise
        if os.environ.get("RAMBA_VERIFY"):  # keep the stage ledger sparse
            _attrib.add_stage(span, "verify",
                              time.perf_counter() - t_verify)
        if work.skip_fused:
            # a verifier-distrusted flush must not populate (or consult)
            # the result cache: whatever routed it down the ladder may be
            # the very defect the memo-safety rule flagged.  The class
            # plan is dropped for the same reason — the ladder's fallback
            # rungs run exact shapes, so a flagged bucket claim never
            # touches data.  It must not certify either, for the same
            # reason.
            work.memo_plan = None
            work.class_plan = None
        elif _plancache.enabled():
            # Miss path completed a full, verifier-clean analysis:
            # snapshot it as a certificate for the next repeat.
            try:
                work.plan_cert = _plancache.certify(work)
            except Exception:
                work.plan_cert = None
    if work.memo_plan is not None:
        try:
            work.memo_hit = _memo.lookup(work.memo_plan)
        except Exception:
            work.memo_hit = None
    # Mint the request deadline (serve/overload.py) at prepare time so
    # the budget clock covers queueing.  Lazy import (serve imports this
    # module); gated so the common no-deadline path never pays it.
    if stream.deadline_ms is not None or os.environ.get("RAMBA_DEADLINE_MS"):
        from ramba_tpu.serve import overload as _overload

        work.deadline = _overload.mint_deadline(stream.deadline_ms)
        if work.deadline is not None:
            span["deadline_ms"] = work.deadline.budget_ms
    # The kernel fingerprint rides the span so offline tooling and the
    # incident explainer can join a flush back to its per-fingerprint
    # baselines without the live ledger.
    if work.fingerprint is not None:
        span["fingerprint"] = work.fingerprint
    # Caller-thread attribution: "trace" is linearize + fuse + leaf
    # gather + donation census (unavoidable per flush); "prepare" is the
    # analysis pipeline from there on — class/memo/plan certification or
    # the certificate redemption — minus the verifier, which has its own
    # stage.
    _attrib.add_stage(span, "trace", t_analysis - work.t_flush)
    _attrib.add_stage(
        span, "prepare",
        (time.perf_counter() - t_analysis)
        - span["stages"].get("verify", 0.0))
    return work


def _revalidate_donation(work: "_FlushWork") -> None:
    """Async work dispatches arbitrarily later than it was prepared: a
    buffer that looked donate-safe at enqueue may since have gained a
    live owner (the user materialized an alias) or another in-flight
    program (a different stream enqueued a graph sharing the leaf).
    Donation may only SHRINK here — a smaller mask cannot introduce the
    hazards the enqueue-time verifier checked for."""
    if not work.donate_key:
        return
    fresh = set(_donation_mask(work.leaves, work.leaf_vals))
    kept = tuple(i for i in work.donate_key if i in fresh)
    if kept != work.donate_key:
        work.span["donate_revoked"] = len(work.donate_key) - len(kept)
        work.donate_key = kept
        work.span["donated"] = len(kept)
        work.fingerprint = _ledger.fingerprint(_cache_key(
            work.program, kept,
            work.class_plan.token if work.class_plan is not None else None))
        work.span["fingerprint"] = work.fingerprint


def _finish_memo_hit(work: "_FlushWork") -> list:
    """Complete a flush whose outputs the result cache already holds:
    no admission, no compile, no execution — just write-back and span
    bookkeeping.  The span carries ``cache="memo"`` so trace tooling can
    tell a memo hit from a compile-cache hit, and the slow-flush ledger
    is deliberately NOT fed (a near-zero memo wall would poison the
    program's rolling latency history)."""
    stream, span, program = work.stream, work.span, work.program
    outs = work.memo_hit
    work.memo_hit = None
    _release(work)
    with _stats_lock:
        stats["flushes"] += 1
        stats["nodes_flushed"] += len(program.instrs)
    stream.stats["flushes"] += 1
    stream.stats["nodes_flushed"] += len(program.instrs)
    _registry.inc("fuser.flushes")
    _registry.inc("fuser.nodes_flushed", len(program.instrs))
    if stream.tenant is not None:
        _registry.inc(f"serve.tenant.{stream.tenant}.flushes")
        _registry.inc(f"serve.tenant.{stream.tenant}.nodes",
                      len(program.instrs))
    work.leaf_vals = None
    for arr, expr, val in zip(work.roots, work.root_exprs, outs):
        if arr._expr is expr:
            arr._set_expr(Const(val))
    span["segments"] = 0
    span["compile_s"] = 0.0
    span["execute_s"] = 0.0
    span["cache"] = "memo"
    span["memo_hit"] = True
    span["out_bytes"] = sum(_nbytes(v) for v in outs)
    span["wall_s"] = round(time.perf_counter() - work.t_flush, 6)
    with _profile.span("observe.tail"):
        _attrib.finalize_span(span, fp=work.fingerprint)
        _events.emit(span)
        _slo.observe_span(span)
        _elastic.note_progress("flush")
    return list(outs[len(work.roots):])


def _flush_dispatch(work: "_FlushWork", *, coalesced: int = 0) -> list:
    """Stage 2 of a flush: admission control, ladder execution, Const
    write-back, span finalization.  Returns the values of the work's
    ``extra`` expressions.  Runs on the caller thread (sync path) or the
    pipeline's compile worker (async path).

    The whole stage runs inside the flush span's trace scope, so every
    event emitted underneath — degrade rungs, memory admissions/rejects,
    watchdog stalls, barrier spans — is auto-stamped
    as a child of this flush (observe/telemetry.py)."""
    span = work.span
    with _telemetry.span_scope(span.get("trace_id"), span.get("span_id")):
        return _flush_dispatch_traced(work, coalesced=coalesced)


def _flush_dispatch_traced(work: "_FlushWork", *, coalesced: int = 0) -> list:
    stream, span, program = work.stream, work.span, work.program
    roots, label = work.roots, work.label
    if work.enqueued_at is not None:
        queue_s = time.perf_counter() - work.enqueued_at
        span["queue_s"] = round(queue_s, 6)
        # queue_s spans submit -> this dispatch; the pipeline already
        # billed the group-pop -> this-ticket slice as coalesce
        _attrib.add_stage(
            span, "queue_wait",
            queue_s - span.get("stages", {}).get("coalesce", 0.0))
    if coalesced > 1:
        span["coalesced"] = coalesced
    # Overload shed verdict — before admission, compile, and execution,
    # so a shed costs microseconds.  Epoch-agreed across ranks when
    # coherence is engaged (all ranks shed the identical request set).
    # A shed is a soft discard, not a failure: no quarantine, no
    # flush_error — the roots keep their graphs and self-heal on touch.
    if work.deadline is not None or work.enqueued_at is not None:
        from ramba_tpu.serve import overload as _overload

        try:
            _overload.dispatch_verdict(
                deadline=work.deadline, enqueued_at=work.enqueued_at,
                tenant=stream.tenant,
                priority=getattr(stream, "priority", False), label=label)
        except _overload.OverloadError:
            _flush_discard(work)
            raise
    if (work.memo_hit is None and work.memo_plan is not None
            and work.enqueued_at is not None):
        # Dispatch-time re-lookup (queued work only — the sync path just
        # looked up in prepare): a prepare-time miss may have become a
        # hit while this work sat queued (an earlier ticket with the same
        # canonical key executed and inserted) — this is what turns
        # serving-batch duplicates into CSE merges.
        try:
            work.memo_hit = _memo.lookup(work.memo_plan)
        except Exception:
            pass
    if work.memo_hit is not None:
        return _finish_memo_hit(work)
    tags = {"tenant": stream.tenant} if stream.tenant is not None else None
    leaf_vals = work.leaf_vals
    try:
        if work.detached:
            _revalidate_donation(work)
        t_admit = time.perf_counter()
        # admission may hand back the program with its live set grouped
        # (same leaves, outputs and donation): every rung runs that one
        route_chunked, program = _memory.admit(
            program, leaf_vals, work.donate_key, span,
            tenant=stream.tenant, quota=stream.quota_bytes)
        span["live_groups"] = program.live_groups
        _attrib.add_stage(span, "admit", time.perf_counter() - t_admit)
        # Hedged dispatch: when RAMBA_HEDGE_FACTOR is set and the program
        # is effect-certified pure with no donation, a dispatch running
        # past factor x its rolling p95 races a second attempt; the first
        # result wins and the loser is cancel-flagged.  Gated on the env
        # var so the common path never imports the overload plane here.
        hedge_s = None
        if os.environ.get("RAMBA_HEDGE_FACTOR") and not work.skip_fused:
            from ramba_tpu.serve import overload as _overload

            hedge_s = _overload.hedge_threshold(label, program,
                                                work.donate_key)
        _stages_pre = sum(span["stages"].get(k, 0.0) for k in
                          ("compile", "dispatch", "device_execute"))
        t_ladder = time.perf_counter()
        with _profile.flush_annotation("run", span):
            # from here on no scalar operand is a number: whatever rung
            # runs hands its callable device arrays only
            leaf_vals = _resident_scalars(work.leaves, leaf_vals)
            with warnings.catch_warnings():
                warnings.filterwarnings("ignore", message=".*[Dd]onat.*")
                if hedge_s is not None:
                    outs, rung = _overload.run_hedged(
                        lambda hspan: _execute_resilient(
                            program, leaf_vals, work.donate_key, hspan,
                            skip_fused=work.skip_fused,
                            route_chunked=route_chunked, tags=tags,
                            deadline=work.deadline,
                            class_plan=work.class_plan),
                        hedge_s, span=span, label=label,
                        tenant=stream.tenant)
                else:
                    outs, rung = _execute_resilient(
                        program, leaf_vals, work.donate_key, span,
                        skip_fused=work.skip_fused,
                        route_chunked=route_chunked, tags=tags,
                        deadline=work.deadline,
                        class_plan=work.class_plan)
    except Exception as e:
        _quarantine(work, e)
        raise
    finally:
        _release(work)
    t_writeback = time.perf_counter()
    # Host-side ladder residual — jit-cache lookup, guard/retry control,
    # donation prep, pin release — is dispatch-path overhead: bill the
    # slice of the ladder window the per-call stamps did not cover.
    _attrib.add_stage(
        span, "dispatch",
        (t_writeback - t_ladder)
        - (sum(span["stages"].get(k, 0.0) for k in
               ("compile", "dispatch", "device_execute")) - _stages_pre))
    if rung != "fused":
        span["degraded"] = rung
    with _stats_lock:
        stats["flushes"] += 1
        stats["nodes_flushed"] += len(program.instrs)
    stream.stats["flushes"] += 1
    stream.stats["nodes_flushed"] += len(program.instrs)
    _registry.inc("fuser.flushes")
    _registry.inc("fuser.nodes_flushed", len(program.instrs))
    if stream.tenant is not None:
        _registry.inc(f"serve.tenant.{stream.tenant}.flushes")
        _registry.inc(f"serve.tenant.{stream.tenant}.nodes",
                      len(program.instrs))
    # Shadow recompute audit (RAMBA_AUDIT=<1-in-N>): re-execute a sample
    # of effect-certified pure, non-donating flushes on the eager rung
    # and compare byte identity — the tripwire for silent compute/memory
    # corruption.  The primary outs are ALWAYS what gets served (audit
    # on/off is byte-identical); a mismatch only suppresses the memo
    # insert and evicts, so poison never enters a cache.
    audit_mismatch = False
    if (work.memo_plan is not None and work.memo_plan.certified
            and not work.donate_key and rung == "fused"
            and not work.memo_hit and _integrity.audit_every() > 0):
        shadow_leaves = leaf_vals
        audit_mismatch = _integrity.shadow_audit(
            label, outs,
            lambda: _run_eager(program, shadow_leaves, None),
            plan=work.memo_plan, span=span)
    if work.memo_plan is not None and not audit_mismatch:
        try:
            _memo.insert(work.memo_plan, list(outs))
        except Exception:
            _registry.inc("memo.insert_failed")
    work.leaf_vals = None  # drop donated-buffer refs before write-back
    del leaf_vals
    if (work.is_abandoned is not None and work.is_abandoned()
            and not _coherence.engaged()):
        # The caller abandoned the ticket while this dispatch ran: a
        # late completion must not write results back into a stream
        # nobody is reading.  The arrays keep their lazy graphs and
        # self-heal on next touch.  Single-controller only — under SPMD
        # write-back skew would diverge the next traced program.
        _registry.inc("serve.abandoned_late")
    else:
        for arr, expr, val in zip(roots, work.root_exprs, outs):
            # Async only: skip write-back if the user re-assigned the
            # array's expression while this flush was in flight — their
            # newer graph wins (it still references this one's nodes and
            # will recompute).
            if arr._expr is expr:
                arr._set_expr(Const(val))
    calls = span["calls"]
    span["segments"] = len(calls) - 1 if len(calls) > 1 else 0
    span["compile_s"] = round(
        sum(c["seconds"] for c in calls if c["cache"] == "miss"), 6
    )
    span["execute_s"] = round(
        sum(c["seconds"] for c in calls if c["cache"] == "hit"), 6
    )
    span["cache"] = (
        "miss" if any(c["cache"] == "miss" for c in calls) else "hit"
    )
    span["out_bytes"] = sum(_nbytes(v) for v in outs)
    span["wall_s"] = round(time.perf_counter() - work.t_flush, 6)
    # the span is closed: what the observers do with it from here on is
    # their own time, not the flush's
    with _profile.span("observe.tail"):
        _attrib.add_stage(span, "write_back",
                          time.perf_counter() - t_writeback)
        _attrib.finalize_span(span, fp=work.fingerprint)
        _events.emit(span)
        _ledger.record_flush_wall(span)
        _slo.observe_span(span)
        _elastic.note_progress("flush")
    return list(outs[len(roots):])


def flush(extra: Sequence[Expr] = ()) -> list:
    """Materialize every pending ndarray of the CURRENT stream (and
    ``extra`` expressions) in one fused jit call (or, above
    ``common.max_program_instrs`` instructions, a chain of bounded jit
    calls — see ``_run_segmented``).  Returns the values of ``extra`` in
    order."""
    return current_stream().flush(extra)


def flush_for(arr, extra: Sequence[Expr] = ()) -> list:
    """Flush the stream that owns ``arr``'s pending work (waiting out any
    in-flight async flushes of that stream first), regardless of which
    stream is current — materialization must chase the work to where it
    was built."""
    s = stream_of(arr)
    s.drain()
    return s.flush(extra)


def analyze_pending() -> Optional[dict]:
    """Compile (without executing) the program the next flush would run and
    return XLA's memory analysis — the rebuild's answer to the reference's
    CI memory-behavior tests, which assert that giant fused expressions fit
    in RAM only if no temporaries materialize
    (/root/reference/ramba/tests/test_distributed_array.py:100-108,193-199).
    The pending graph is left pending.  Returns None if nothing is pending.
    """
    roots = _pending_roots()
    exprs = [a._expr for a in roots]
    if not exprs:
        return None
    program, leaves, _vexprs = _prepare_program(exprs)
    avals = []
    for leaf in leaves:
        v = leaf.value
        if isinstance(v, (jax.Array, _SpilledArray)):
            # a spilled leaf carries its device sharding; analysis must
            # not force a restore (analyze_pending never executes)
            avals.append(
                jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=v.sharding)
            )
        else:
            avals.append(jax.ShapeDtypeStruct(jax.numpy.asarray(v).shape,
                                              jax.numpy.asarray(v).dtype))
    out = {"instructions": len(program.instrs), "n_leaves": program.n_leaves}
    if (
        common.max_program_instrs
        and len(program.instrs) > common.max_program_instrs
    ):
        # The next flush will run segmented (_run_segmented), and compiling
        # the monolith here would hit the very superlinear-compile hazard
        # segmentation avoids — so analyze what will actually run: compile
        # each distinct segment (chains repeat one structure) and report the
        # PEAK per-segment sizes, chaining avals with jax.eval_shape.
        # Sharding on intermediates is dropped (eval_shape carries none);
        # GSPMD would propagate it, so temp sizes are an upper bound.
        vals_avals = dict(enumerate(avals))
        last_use = _last_use_map(program)
        # keyed on structure AND input avals: seg_prog.key deliberately
        # excludes shapes/dtypes, but memory numbers depend on them
        seen_keys = {}
        out["segments"] = 0
        peak = {name: 0 for name in (
            "temp_size_in_bytes", "argument_size_in_bytes",
            "output_size_in_bytes", "generated_code_size_in_bytes")}
        for seg_prog, in_slots, out_here, _top in _iter_segments(
            program, last_use
        ):
            seg_avals = [vals_avals[s] for s in in_slots]
            ak = (seg_prog.key,
                  tuple((a.shape, str(a.dtype)) for a in seg_avals))
            ma = seen_keys.get(ak)
            if ma is None:
                compiled = (
                    _layouts.RowMajorJit(_build_callable(seg_prog))
                    .lower(*seg_avals)
                    .compile()
                )
                ma = compiled.memory_analysis()
                seen_keys[ak] = ma
            for name in peak:
                v = getattr(ma, name, None)
                if v is not None:
                    peak[name] = max(peak[name], v)
            out_avals = jax.eval_shape(
                _build_callable(seg_prog), *seg_avals
            )
            for s, av in zip(out_here, out_avals):
                vals_avals[s] = av
            out["segments"] += 1
        out.update(peak)
        return out
    compiled = _layouts.RowMajorJit(
        _build_callable(program)).lower(*avals).compile()
    ma = compiled.memory_analysis()
    for name in ("temp_size_in_bytes", "argument_size_in_bytes",
                 "output_size_in_bytes", "generated_code_size_in_bytes"):
        out[name] = getattr(ma, name, None)
    return out


def sync() -> None:
    """Flush EVERY stream, wait out in-flight async work, and block until
    device completion (the reference's ``ramba.sync`` barriers on a
    remote ``nop``, ramba.py:9843-9849)."""
    waiters = _pending_arrays()
    for s in all_streams():
        s.flush()
    for s in all_streams():
        s.drain()
    jax.block_until_ready(
        [a._expr.value for a in waiters
         if isinstance(a._expr, Const)
         and isinstance(a._expr.value, jax.Array)]  # spilled: nothing in flight
    )
    # a sync is a "the world is settled" point: the buffered trace
    # writer's pending lines belong on disk too
    _events.sync()


def evaluate(expr: Expr):
    """Evaluate one expression (flushing the current stream's pending work
    alongside it)."""
    if isinstance(expr, Const):
        return leaf_value(expr)
    return flush(extra=[expr])[0]
