"""First-class persistent executable cache (the ``RAMBA_CACHE`` dir).

An **AOT lane** beside jax's persistent compilation cache: serialized
``jit(...).lower().compile()`` executables for the top-K fingerprints, so
a second process starts with near-zero compile wall — it deserializes
executables instead of tracing, lowering and recompiling them.

Layout under the RAMBA_CACHE directory (jax's own compilation cache
lives elsewhere, at ``common.compile_cache_dir()``, and is never moved
or switched off from here)::

    <dir>/.ramba_cache          ownership marker (atomic init)
    <dir>/aot/<fp>-<sig>.aot    pickled (blob, in_tree, out_tree) triple
                                from jax.experimental.serialize_executable
    <dir>/programs/<fp>.pkl     pickled program skeleton (instrs, leaf
                                kinds, donation, aval signature, compile
                                class) — lets a fresh process rebuild the
                                warm thunk without replaying user code

Corruption is tolerated, never raised: a bad entry is evicted and the
program recompiles (counted ``compile.persist_corrupt``; fault site
``compile:persist`` seeds exactly this).  Every hit/miss/evict/byte is
counted here and surfaced through ``diagnostics.perf_report()`` and the
``ramba_compile_persist_*`` telemetry series.

Set ``RAMBA_AOT=0`` to disable the AOT lane under an armed RAMBA_CACHE.
"""

from __future__ import annotations

import hashlib
import os
import pickle
import socket
import tempfile
import threading
from typing import Optional, Sequence

import numpy as np

from ramba_tpu import common
from ramba_tpu.observe import registry as _registry
from ramba_tpu.resilience import faults as _faults
from ramba_tpu.resilience import integrity as _integrity

_MARKER = ".ramba_cache"

#: integrity-envelope schema tags for the two persisted record kinds
AOT_SCHEMA = "aot.pkl"
PROGRAM_SCHEMA = "program.pkl"
_lock = threading.RLock()
_state = {"dir": None, "armed": False, "init_error": None}

#: running counters; snapshot() adds derived fields
stats = {
    "hits": 0,
    "misses": 0,
    # hits whose entry was written by a DIFFERENT process (the writer
    # identity rides the payload): a warm start from another process
    "cross_hits": 0,
    "corrupt": 0,
    "stores": 0,
    "store_errors": 0,
    "call_fallbacks": 0,
    "bytes_read": 0,
    "bytes_written": 0,
    "programs_saved": 0,
}


def _writer_identity() -> dict:
    return {"host": socket.gethostname(), "pid": os.getpid()}

# fingerprint -> candidate record for save_topk (bounded; no array refs)
_candidates: dict = {}
_CANDIDATE_MAX = 256


def reconfigure(directory: Optional[str] = None) -> None:
    """Arm the AOT lane on the RAMBA_CACHE directory (or an explicit
    ``directory`` override, used by tests).  Init is atomic and
    failure-tolerant: a bad dir disarms the lane instead of raising."""
    with _lock:
        _state["init_error"] = None
        if directory is None:
            if common._env_flag("RAMBA_AOT", True) is False:
                _state["dir"] = None
                _state["armed"] = False
                return
            directory = common.persistent_cache_path()
        if not directory:
            _state["dir"] = None
            _state["armed"] = False
            return
        _state["dir"] = directory
        _state["armed"] = _init_dir(directory)


def _init_dir(path: str) -> bool:
    try:
        os.makedirs(os.path.join(path, "aot"), exist_ok=True)
        os.makedirs(os.path.join(path, "programs"), exist_ok=True)
        marker = os.path.join(path, _MARKER)
        if not os.path.exists(marker):
            _atomic_write(marker, b"ramba_tpu persistent cache\n")
        return True
    except OSError as e:
        _state["init_error"] = f"{type(e).__name__}: {e}"
        _registry.inc("compile.persist_init_error")
        return False


def _atomic_write(path: str, data: bytes) -> None:
    fd, tmp = tempfile.mkstemp(dir=os.path.dirname(path), prefix=".tmp-")
    try:
        with os.fdopen(fd, "wb") as f:
            f.write(data)
        os.replace(tmp, path)
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise


def armed() -> bool:
    return bool(_state["armed"])


def cache_dir() -> Optional[str]:
    return _state["dir"]


# -- aval signatures ---------------------------------------------------------

def aval_sig(leaf_vals: Sequence) -> Optional[tuple]:
    """Canonical per-leaf (shape, dtype, weak_type) signature as JAX
    itself sees the values — jit specializes on exactly this, so a
    serialized executable is only replayed for a matching signature."""
    import jax

    try:
        avals = jax.eval_shape(lambda *xs: xs, *leaf_vals)
    except Exception:
        return None
    return tuple(
        (tuple(a.shape), np.dtype(a.dtype).str, bool(a.weak_type))
        for a in avals
    )


def _example_vals(sig: tuple) -> list:
    """Concrete example arguments reproducing a signature exactly —
    weak-typed scalars become python literals (jit sees python scalars
    as weak), everything else a zeros array of the strong dtype."""
    import jax.numpy as jnp

    vals = []
    for shape, dtype_str, weak in sig:
        dt = np.dtype(dtype_str)
        if weak and shape == ():
            if dt.kind == "b":
                vals.append(False)
            elif dt.kind in "iu":
                vals.append(0)
            elif dt.kind == "c":
                vals.append(0j)
            else:
                vals.append(0.0)
        else:
            vals.append(jnp.zeros(shape, dt))
    return vals


def _sig_hash(sig: tuple) -> str:
    return hashlib.sha256(repr(sig).encode()).hexdigest()[:12]


def _entry_path(fp: str, sig: tuple) -> str:
    return os.path.join(_state["dir"], "aot", f"{fp}-{_sig_hash(sig)}.aot")


def _program_path(fp: str) -> str:
    return os.path.join(_state["dir"], "programs", f"{fp}.pkl")


# -- AOT dispatcher ----------------------------------------------------------

class AotDispatcher:
    """A deserialized executable behaving like the jit callable the
    fuser expects: called with matching avals it runs the loaded
    executable (zero compile wall); on any mismatch or load-time drift
    it falls back to a lazily-built ``jax.jit`` (counted
    ``call_fallbacks``).  ``lower`` delegates to the fallback jit —
    ``_execute_compiled``/``capture_cost`` call it in guarded blocks."""

    __slots__ = ("_loaded", "_sig", "_program", "_donate", "_fallback",
                 "__weakref__")  # the fuser keeps kernel notes by weak key

    def __init__(self, loaded, sig, program, donate):
        self._loaded = loaded
        self._sig = sig
        self._program = program
        self._donate = donate
        self._fallback = None

    def _jit(self):
        if self._fallback is None:
            from ramba_tpu.core import fuser as _fuser
            from ramba_tpu.core import layouts as _layouts

            self._fallback = _layouts.RowMajorJit(
                _fuser._build_callable(self._program), self._donate)
        return self._fallback

    def __call__(self, *leaf_vals):
        if self._loaded is not None and aval_sig(leaf_vals) == self._sig:
            try:
                return self._loaded(*leaf_vals)
            except Exception:  # noqa: BLE001 — drift → recompile, not crash
                self._loaded = None
        with _lock:
            stats["call_fallbacks"] += 1
        return self._jit()(*leaf_vals)

    def lower(self, *args, **kwargs):
        return self._jit().lower(*args, **kwargs)


# -- lookup / store ----------------------------------------------------------

def lookup(fp: str, leaf_vals: Sequence, program, donate_key):
    """AOT-lane lookup on a fuser compile-cache miss.  Returns an
    :class:`AotDispatcher` or None.  Corrupt entries are evicted and
    recompiled — never raised."""
    if not _state["armed"]:
        return None
    sig = aval_sig(leaf_vals)
    if sig is None:
        return None
    path = _entry_path(fp, sig)
    try:
        _faults.check("compile:persist", fp=fp)
    except _faults.InjectedFault:
        # seeded corruption: clobber the entry so the tolerance path
        # (evict + recompile) runs instead of a clean hit
        try:
            with open(path, "wb") as f:
                f.write(b"corrupt")
        except OSError:
            pass
    if not os.path.exists(path):
        with _lock:
            stats["misses"] += 1
        _registry.inc("compile.persist_miss")
        return None
    try:
        with open(path, "rb") as f:
            raw = f.read()
        # flip seam (RAMBA_FAULTS='aot:blob:flip:...'): seeded silent
        # corruption of the just-read executable, upstream of the digest
        raw = _faults.corrupt("aot:blob", raw, fp=fp)
        payload = pickle.loads(
            _integrity.unwrap(raw, AOT_SCHEMA, site="aot:blob"))
        if payload["fp"] != fp or payload["sig"] != sig:
            raise ValueError("entry key mismatch")
        from jax.experimental import serialize_executable as _se

        blob, in_tree, out_tree = payload["payload"]
        loaded = _se.deserialize_and_load(blob, in_tree, out_tree)
    except Exception as e:  # noqa: BLE001 — tolerate any corruption shape
        with _lock:
            stats["corrupt"] += 1
        _registry.inc("compile.persist_corrupt")
        if not isinstance(e, _integrity.IntegrityError):
            # unwrap already classified digest failures; anything that
            # passed the digest but failed to deserialize is its own
            # integrity incident (the suspect window must count it)
            _integrity.failure("aot:blob", "deserialize",
                               detail=repr(e)[:200], fp=fp)
        try:
            os.unlink(path)
        except OSError:
            pass
        return None
    import jax

    from ramba_tpu.core import layouts as _layouts

    if _layouts.pins(jax.tree_util.tree_leaves(loaded.out_info)):
        # an entry from before layouts were pinned: a deserialized
        # executable cannot say how its results lie (core/layouts.py)
        os.unlink(path)
        with _lock:
            stats["misses"] += 1
        _registry.inc("compile.persist_miss")
        return None
    writer = payload.get("writer")
    cross = bool(writer) and writer != _writer_identity()
    with _lock:
        stats["hits"] += 1
        if cross:
            stats["cross_hits"] += 1
        stats["bytes_read"] += len(raw)
    _registry.inc("compile.persist_hit")
    if cross:
        _registry.inc("compile.persist_cross_hit")
    return AotDispatcher(loaded, sig, program, donate_key)


def note_compiled(fp: str, program, donate_key, leaf_vals,
                  compile_class=None) -> None:
    """Register a fresh demand compile as an AOT candidate and persist
    its program skeleton so another process can warm it.  Compiles are
    rare by definition, so the one small file write stays off the steady
    state."""
    if not _state["armed"]:
        return
    sig = aval_sig(leaf_vals)
    if sig is None:
        return
    with _lock:
        c = _candidates.get(fp)
        if c is not None:
            c["count"] += 1
            return
        if len(_candidates) >= _CANDIDATE_MAX:
            return
        _candidates[fp] = {
            "program": program,
            "donate": tuple(donate_key),
            "sig": sig,
            "compile_class": compile_class,
            # Live leaf shardings: an XLA executable is specialized to its
            # input shardings, so the AOT serialization must compile from
            # examples placed exactly where real traffic places them.
            "shardings": tuple(
                getattr(v, "sharding", None) for v in leaf_vals),
            "count": 1,
        }
    _save_program(fp, program, donate_key, sig, compile_class)


def _save_program(fp, program, donate_key, sig, compile_class) -> None:
    path = _program_path(fp)
    if os.path.exists(path):
        return
    rec = {
        "fp": fp,
        "instrs": tuple(program.instrs),
        "n_leaves": program.n_leaves,
        "leaf_kinds": tuple(program.leaf_kinds),
        "out_slots": tuple(program.out_slots),
        "live_cuts": tuple(program.live_cuts),
        "donate": tuple(donate_key),
        "sig": sig,
        "compile_class": compile_class,
    }
    try:
        _atomic_write(path,
                      _integrity.wrap(pickle.dumps(rec), PROGRAM_SCHEMA))
    except Exception:  # noqa: BLE001 — unpicklable statics: skip, count
        with _lock:
            stats["store_errors"] += 1
        return
    with _lock:
        stats["programs_saved"] += 1


def load_program(fp: str) -> Optional[dict]:
    """Load a persisted program skeleton (warm pool / save_topk in a
    fresh process).  Corrupt records evict, same as AOT entries."""
    if not _state["armed"]:
        return None
    path = _program_path(fp)
    if not os.path.exists(path):
        return None
    try:
        with open(path, "rb") as f:
            raw = f.read()
        rec = pickle.loads(
            _integrity.unwrap(raw, PROGRAM_SCHEMA, site="aot:program"))
        if rec["fp"] != fp:
            raise ValueError("program key mismatch")
        return rec
    except Exception as e:  # noqa: BLE001
        with _lock:
            stats["corrupt"] += 1
        _registry.inc("compile.persist_corrupt")
        if not isinstance(e, _integrity.IntegrityError):
            _integrity.failure("aot:program", "deserialize",
                               detail=repr(e)[:200], fp=fp)
        try:
            os.unlink(path)
        except OSError:
            pass
        return None


def saved_fingerprints() -> list:
    """Fingerprints with a persisted program skeleton."""
    if not _state["armed"]:
        return []
    try:
        names = os.listdir(os.path.join(_state["dir"], "programs"))
    except OSError:
        return []
    return sorted(n[:-4] for n in names if n.endswith(".pkl"))


def _rank_key(fp: str, count: int) -> tuple:
    """Rank candidates by the ledger's exec stats (arrival-weighted),
    falling back to the in-process compile count."""
    try:
        from ramba_tpu.observe import ledger as _ledger

        snap = _ledger.snapshot()
        k = snap.get("kernels", {}).get(fp)
        if k:
            return (int(k.get("exec", {}).get("count", 0)), count)
    except Exception:  # noqa: BLE001
        pass
    return (0, count)


def save_topk(k: int = 8) -> dict:
    """Serialize AOT executables for the top-K candidate fingerprints.
    The ``lower().compile()`` here re-runs compilation AOT-style — a
    real compile each time (JAX's own cache is bypassed so the blob is
    self-contained), but off the request path and bounded by K."""
    report = {"considered": 0, "stored": 0, "skipped": 0, "errors": 0}
    if not _state["armed"]:
        return report
    with _lock:
        cands = [(fp, dict(c)) for fp, c in _candidates.items()]
    cands.sort(key=lambda it: _rank_key(it[0], it[1]["count"]), reverse=True)
    for fp, c in cands[: max(0, int(k))]:
        report["considered"] += 1
        out = store_entry(fp, c["sig"], program_rec=None, candidate=c)
        report[out] = report.get(out, 0) + 1
    return report


def store_entry(fp: str, sig: tuple, program_rec=None,
                candidate=None) -> str:
    """Serialize one executable; returns 'stored' | 'skipped' (already
    present) | 'errors'."""
    if not _state["armed"]:
        return "errors"
    path = _entry_path(fp, sig)
    if os.path.exists(path):
        return "skipped"
    try:
        import jax

        from ramba_tpu.core import fuser as _fuser

        if candidate is not None:
            program = candidate["program"]
            donate = candidate["donate"]
        else:
            program = _fuser._Program(
                program_rec["instrs"], program_rec["n_leaves"],
                program_rec["leaf_kinds"], program_rec["out_slots"],
                program_rec.get("live_cuts", ()))
            donate = program_rec["donate"]
        run = _fuser._build_callable(program)

        # Compiled under its own module name, so jax's persistent cache
        # keys it apart from the demand compile of the same program that
        # this process has usually just written there: the AOT compile
        # is then a FRESH one (see below for why it must be).
        def aot(*leaf_vals):
            return run(*leaf_vals)

        aot.__name__ = aot.__qualname__ = f"ramba_aot_{fp}"
        from ramba_tpu.core import layouts as _layouts

        fn = _layouts.RowMajorJit(aot, donate)
        vals = _example_vals(sig)
        shardings = (candidate or {}).get("shardings")
        if shardings:
            # Match the recorded call-time shardings: a deserialized
            # executable rejects differently-placed leaves, which would
            # silently demote the warm process to a lazy recompile.
            vals = [
                jax.device_put(v, s)
                if s is not None and hasattr(v, "shape") else v
                for v, s in zip(vals, shardings)
            ]
        if fn.pins(*vals):
            # compiled in every process: a deserialized executable cannot
            # say how its results lie (core/layouts.py)
            _registry.inc("compile.persist_store_skipped_pinned")
            return "skipped"
        # Only a FRESH compile is stored.  An executable jax loaded from
        # its own persistent cache serializes to a blob whose XLA:CPU
        # kernel symbols do not resolve in another process ("Function
        # ... not found"), which would poison every warm start after the
        # first.  jax's cache is never moved or switched off for this: a
        # hit (a re-store after an eviction, in a later process) is
        # observed through jax.monitoring and the store skipped — jax's
        # cache already spares that process the compile.
        cache_hits = []

        def on_event(event, **_kw):
            if event == "/jax/compilation_cache/cache_hits":
                cache_hits.append(event)

        jax.monitoring.register_event_listener(on_event)
        try:
            compiled = fn.lower(*vals).compile()
        finally:
            jax.monitoring.unregister_event_listener(on_event)
        if cache_hits:
            _registry.inc("compile.persist_store_skipped_jax_cache")
            return "skipped"
        from jax.experimental import serialize_executable as _se

        blob, in_tree, out_tree = _se.serialize(compiled)
        data = _integrity.wrap(
            pickle.dumps(
                {"fp": fp, "sig": sig, "payload": (blob, in_tree, out_tree),
                 "writer": _writer_identity()}),
            AOT_SCHEMA)
        _atomic_write(path, data)
    except Exception:  # noqa: BLE001 — AOT store is best-effort
        with _lock:
            stats["store_errors"] += 1
        _registry.inc("compile.persist_store_error")
        return "errors"
    with _lock:
        stats["stores"] += 1
        stats["bytes_written"] += len(data)
    _registry.inc("compile.persist_store")
    return "stored"


def snapshot() -> dict:
    with _lock:
        d = dict(stats)
        d["dir"] = _state["dir"]
        d["armed"] = _state["armed"]
        d["init_error"] = _state["init_error"]
        d["candidates"] = len(_candidates)
    return d


def reset() -> None:
    with _lock:
        for key in stats:
            stats[key] = 0
        _candidates.clear()
    reconfigure()


reconfigure()
