"""Elastic job lifecycle: rank health, hang detection, checkpointed resume.

The paper's distributed story assumes every worker stays alive forever.
This module is the layer that lets a multi-rank job survive losing one:

* **Heartbeat** — a per-rank liveness beacon thread
  (:func:`start_heartbeat`) that emits ``heartbeat`` events on the
  observe stream every ``RAMBA_HEARTBEAT_S`` seconds.  Under
  ``RAMBA_TRACE`` the beacons land in the per-rank JSONL files, so
  ``scripts/trace_report.py`` can reconstruct each rank's liveness
  timeline offline and flag gaps (a wedged rank stops beating long
  before it stops holding the collective hostage).
* **Watchdog** — :func:`with_deadline` wraps flush dispatch
  (``core.fuser``) and cross-rank syncs (``parallel.distributed.barrier``)
  with a deadline (``RAMBA_WATCHDOG_S``).  A hang becomes a classified
  :class:`RankStallError` instead of an infinite block; the
  classification (``retryable`` / ``degrade`` / ``fatal``, per-site
  table below, overridable via ``RAMBA_WATCHDOG_CLASS_<SITE>``) routes
  through the existing ``resilience.retry`` classifier, so a stalled
  fused dispatch drops a ladder rung exactly like any other degrade
  failure.
* **CheckpointManager** — periodic step-numbered auto-checkpoints of
  registered array trees under one root, each with a ``MANIFEST.json``
  recording mesh shape, process count, ``jax_enable_x64``, and
  per-leaf shape/dtype/sharding fingerprints; retention-K GC that never
  deletes the newest valid checkpoint.
* **drain-to-checkpoint** — :func:`drain_to_checkpoint` quiesces serve
  sessions and every pending flush stream (under its own deadline)
  before saving, so the checkpoint captures a consistent post-flush
  state.
* **Mesh-reshape resume** — :func:`resume` restores the newest valid
  checkpoint into the *current* mesh even when the rank count changed
  (2→1, 1→2): the restore target is rebuilt from the checkpoint's own
  metadata with current-mesh default shardings and handed to
  ``checkpoint.restore(path, target)``, under HBM-governor admission so
  a near-budget restore evicts/spills first instead of OOMing.

Watchdog classification defaults (see docs/index.md for the runbook):

========== ============ ==================================================
site       class        rationale
========== ============ ==================================================
dispatch   degrade      re-running the identical fused program would hang
                        again; the ladder's next rung changes the program
barrier    fatal        a missing rank cannot be degraded around — the
                        job must drain and resume with a new mesh
drain      fatal        a hang while quiescing means state cannot be
                        trusted; surface it instead of checkpointing junk
heartbeat  retryable    a late beacon is jitter until proven otherwise
========== ============ ==================================================

Env vars: ``RAMBA_WATCHDOG_S`` (deadline seconds; unset/0 disarms),
``RAMBA_WATCHDOG_CLASS_<SITE>`` (classification override),
``RAMBA_HEARTBEAT_S`` (beacon interval, default 5),
``RAMBA_DRAIN_S`` (drain deadline, default 10× watchdog),
``RAMBA_CKPT_EVERY`` / ``RAMBA_CKPT_KEEP`` (CheckpointManager defaults).
"""

from __future__ import annotations

import contextvars
import hashlib
import json
import os
import threading
import time
from typing import Callable, Dict, Optional

import jax
import numpy as np

from ramba_tpu.observe import events as _events
from ramba_tpu.observe import health as _health
from ramba_tpu.observe import registry as _registry
from ramba_tpu.resilience import integrity as _integrity
from ramba_tpu.resilience import coherence as _coherence
from ramba_tpu.resilience import faults as _faults
from ramba_tpu.resilience import memory as _memory


class RankStallError(RuntimeError):
    """A watchdog deadline expired: the wrapped call is still running
    (wedged collective, hung dispatch) past ``RAMBA_WATCHDOG_S``.

    ``stall_classification`` is how ``resilience.retry.classify`` routes
    the error (``"retryable"`` / ``"degrade"`` / ``"fatal"``) — the
    attribute name is duck-typed there to keep retry.py free of an
    elastic import."""

    def __init__(self, site: str, waited_s: float, classification: str,
                 rank: Optional[int] = None):
        self.site = site
        self.waited_s = waited_s
        self.stall_classification = classification
        self.rank = rank
        where = f" on rank {rank}" if rank is not None else ""
        super().__init__(
            f"rank stall at site {site!r}{where}: no completion within "
            f"{waited_s:.3f}s (RAMBA_WATCHDOG_S deadline); "
            f"classified {classification}"
        )


# -- watchdog ---------------------------------------------------------------

_STALL_CLASSES = ("retryable", "degrade", "fatal")
_DEFAULT_STALL_CLASS: Dict[str, str] = {
    "dispatch": "degrade",
    "barrier": "fatal",
    "drain": "fatal",
    "heartbeat": "retryable",
}


def watchdog_seconds() -> Optional[float]:
    """The armed deadline, or None when the watchdog is off (default)."""
    raw = os.environ.get("RAMBA_WATCHDOG_S")
    if not raw:
        return None
    try:
        t = float(raw)
    except ValueError:
        return None
    return t if t > 0 else None


def armed() -> bool:
    return watchdog_seconds() is not None


def _site_env(site: str) -> str:
    return "".join(c if c.isalnum() else "_" for c in site.upper())


def stall_class_for(site: str) -> str:
    raw = os.environ.get(f"RAMBA_WATCHDOG_CLASS_{_site_env(site)}", "")
    raw = raw.strip().lower()
    if raw in _STALL_CLASSES:
        return raw
    return _DEFAULT_STALL_CLASS.get(site, "degrade")


def _rank() -> Optional[int]:
    try:
        return int(jax.process_index()) if jax.process_count() > 1 else None
    except Exception:
        return None


# Set (on the helper thread's context) by with_deadline; flipped when the
# deadline expires.  A wrapped call that sleeps through its deadline and
# then wakes must NOT go on to do the real work — the caller already
# recovered (e.g. the ladder ran the next rung), and a zombie fused
# attempt would donate/delete leaf buffers the live computation still
# owns.  Work already inside XLA cannot be cancelled; this flag is
# checked at safe points (the fuser checks it between the dispatch fault
# site and the rung body).
_cancel_var: contextvars.ContextVar = contextvars.ContextVar(
    "ramba_deadline_cancelled", default=None)


def cancelled() -> bool:
    """True when the current call runs under an expired deadline."""
    ev = _cancel_var.get()
    return ev is not None and ev.is_set()


def with_deadline(site: str, fn: Callable, *,
                  timeout_s: Optional[float] = None):
    """Run ``fn()`` under the watchdog deadline for ``site``.

    Unarmed (no ``RAMBA_WATCHDOG_S`` and no explicit ``timeout_s``) this
    is a plain call — zero threads, zero overhead.  Armed, ``fn`` runs
    on a helper thread (with the caller's contextvars, so stream/tenant
    attribution survives) while the caller waits out the deadline; on
    expiry the caller gets a classified :class:`RankStallError` and the
    wedged call is left behind on its daemon thread — exactly the trade
    a deadline makes: the caller's control flow is worth more than the
    stranded thread."""
    t = timeout_s if timeout_s is not None else watchdog_seconds()
    if t is None or t <= 0:
        return fn()
    box: dict = {}
    ctx = contextvars.copy_context()
    cancel = threading.Event()

    def run():
        try:
            def with_flag():
                _cancel_var.set(cancel)
                return fn()

            box["value"] = ctx.run(with_flag)
        except BaseException as e:  # re-raised on the caller thread
            box["error"] = e

    th = threading.Thread(target=run, name=f"ramba-deadline-{site}",
                          daemon=True)
    t0 = time.monotonic()
    th.start()
    th.join(t)
    if th.is_alive():
        cancel.set()  # the zombie must not do the real work when it wakes
        waited = time.monotonic() - t0
        cls = stall_class_for(site)
        _registry.inc("elastic.stalls")
        _registry.inc(f"elastic.stalls.{site}")
        _events.emit({"type": "stall", "site": site,
                      "waited_s": round(waited, 4),
                      "deadline_s": t, "classification": cls})
        _health.record(outcome="error", source=f"watchdog:{site}",
                       error=f"stall after {waited:.3f}s")
        if site == "dispatch" and _coherence.engaged():
            # Seed the ladder's next flush:rung agreement round with the
            # stall's severity so the fleet degrades (or aborts) together
            # instead of this rank unilaterally abandoning the rung.
            _coherence.propose(
                "flush:rung",
                _coherence.P_FATAL if cls == "fatal" else _coherence.P_DROP)
        raise RankStallError(site, waited, cls, rank=_rank())
    if "error" in box:
        raise box["error"]
    return box["value"]


# -- heartbeat --------------------------------------------------------------

def _heartbeat_interval() -> float:
    try:
        v = float(os.environ.get("RAMBA_HEARTBEAT_S", "") or 5.0)
    except ValueError:
        v = 5.0
    return v if v > 0 else 5.0


class _Heartbeat(threading.Thread):
    """Daemon beacon: one ``heartbeat`` event per interval.  The fault
    site ``heartbeat`` is checked before each beat, so a seeded
    ``heartbeat:hang:ms=...:after=N`` stalls exactly one beacon — the
    deterministic heartbeat-miss the trace-report stall flagging and
    :func:`check_heartbeat` tests key on."""

    def __init__(self, interval_s: float):
        super().__init__(name="ramba-heartbeat", daemon=True)
        self.interval_s = interval_s
        self.beats = 0
        self.last_beat: Optional[float] = None  # monotonic
        self._stop = threading.Event()

    def run(self) -> None:
        while True:
            try:
                _faults.check("heartbeat")
            except Exception:
                pass  # a raising fault plan must not kill the beacon
            if self._stop.is_set():
                return
            self.beats += 1
            self.last_beat = time.monotonic()
            _registry.inc("elastic.heartbeats")
            _events.emit({"type": "heartbeat", "n": self.beats,
                          "interval_s": self.interval_s})
            if self._stop.wait(self.interval_s):
                return

    def halt(self) -> None:
        self._stop.set()


_hb_lock = threading.Lock()
_hb: Optional[_Heartbeat] = None


def start_heartbeat(interval_s: Optional[float] = None) -> None:
    """Start (or restart with a new interval) this rank's beacon."""
    global _hb
    with _hb_lock:
        if _hb is not None:
            _hb.halt()
        _hb = _Heartbeat(interval_s if interval_s and interval_s > 0
                         else _heartbeat_interval())
        _hb.start()


def stop_heartbeat() -> None:
    global _hb
    with _hb_lock:
        if _hb is not None:
            _hb.halt()
            _hb = None


def heartbeat_running() -> bool:
    hb = _hb
    return hb is not None and hb.is_alive()


def last_beat_age() -> Optional[float]:
    """Seconds since this rank's last beacon (None: not started/no beat)."""
    hb = _hb
    if hb is None or hb.last_beat is None:
        return None
    return time.monotonic() - hb.last_beat


def check_heartbeat(max_age_s: Optional[float] = None) -> bool:
    """True when the local beacon is fresh.  Stale (age > ``max_age_s``,
    default 2× the beat interval) emits a ``heartbeat_missed`` lifecycle
    event and returns False — the local symptom of the stall a remote
    watchdog would see as a silent rank."""
    hb = _hb
    if hb is None:
        return True  # not started: nothing to miss
    age = last_beat_age()
    if age is None:
        age = time.monotonic() - (hb.last_beat or 0.0)
    limit = max_age_s if max_age_s and max_age_s > 0 else 2.0 * hb.interval_s
    if age <= limit:
        return True
    _registry.inc("elastic.heartbeat_missed")
    _events.emit({"type": "lifecycle", "phase": "heartbeat_missed",
                  "age_s": round(age, 4), "limit_s": round(limit, 4)})
    return False


# -- progress note (cheap liveness signal from the flush path) --------------

_last_progress: Optional[tuple] = None  # (monotonic, what)


def note_progress(what: str) -> None:
    global _last_progress
    _last_progress = (time.monotonic(), what)


def last_progress_age() -> Optional[float]:
    lp = _last_progress
    return None if lp is None else time.monotonic() - lp[0]


# -- checkpoint manager -----------------------------------------------------

_STEP_PREFIX = "step_"
_STATE_DIR = "state"
_MANIFEST = "MANIFEST.json"
_MANIFEST_FORMAT = 1


def _manifest_digest(man: dict) -> str:
    """Content digest over the manifest body (every field except the
    digest itself, canonical JSON) — pre-digest manifests, which lack
    the field, are accepted unverified."""
    body = {k: v for k, v in man.items() if k != "digest"}
    data = json.dumps(body, sort_keys=True).encode()
    return hashlib.sha256(data).hexdigest()


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, "") or default)
    except ValueError:
        return default


def _barrier(tag: str) -> None:
    from ramba_tpu.parallel import distributed as _distributed

    _distributed.barrier(tag)


def _leaf_fingerprints(vals) -> list:
    import jax.tree_util as jtu

    out = []
    for path, v in jtu.tree_flatten_with_path(vals)[0]:
        sharding = getattr(v, "sharding", None)
        spec = getattr(sharding, "spec", None)
        out.append({
            "path": jtu.keystr(path),
            "shape": [int(s) for s in v.shape],
            "dtype": str(np.dtype(v.dtype)),
            "sharding": str(spec) if spec is not None else None,
        })
    return out


class CheckpointManager:
    """Step-numbered checkpoints of registered array trees under one root.

    Layout: ``<root>/step_<n>/state`` (Orbax, via ``checkpoint.save``'s
    atomic stage+rename) plus ``<root>/step_<n>/MANIFEST.json`` written
    by rank 0 *after* the state publish — a step without a readable,
    matching manifest is torn debris and is never selected by
    :meth:`latest`.  Retention keeps the newest ``keep`` valid steps;
    GC deletes valid steps beyond that and invalid debris older than the
    newest valid step, and by construction can never delete the newest
    valid one."""

    def __init__(self, root: str, *, keep: Optional[int] = None,
                 every_steps: Optional[int] = None):
        self.root = os.path.abspath(root)
        self.keep = keep if keep is not None else _env_int("RAMBA_CKPT_KEEP", 3)
        if self.keep < 1:
            raise ValueError("CheckpointManager keep must be >= 1")
        self.every_steps = (every_steps if every_steps is not None
                            else _env_int("RAMBA_CKPT_EVERY", 0)) or None
        self._registered: Dict[str, Callable] = {}

    # -- registration ------------------------------------------------------

    def register(self, name: str, tree) -> None:
        """Register a pytree (or a zero-arg callable returning one) to be
        captured by :meth:`save` / :meth:`maybe_save`."""
        self._registered[name] = tree if callable(tree) else (lambda: tree)

    def gather(self) -> dict:
        return {name: fn() for name, fn in self._registered.items()}

    # -- paths -------------------------------------------------------------

    def step_dir(self, step: int) -> str:
        return os.path.join(self.root, f"{_STEP_PREFIX}{int(step):08d}")

    def state_path(self, step: int) -> str:
        return os.path.join(self.step_dir(step), _STATE_DIR)

    def manifest_path(self, step: int) -> str:
        return os.path.join(self.step_dir(step), _MANIFEST)

    def all_steps(self) -> list:
        """Every step directory on disk (valid or torn), ascending."""
        if not os.path.isdir(self.root):
            return []
        out = []
        for name in os.listdir(self.root):
            if not name.startswith(_STEP_PREFIX):
                continue
            try:
                out.append(int(name[len(_STEP_PREFIX):]))
            except ValueError:
                continue
        return sorted(out)

    def valid_steps(self) -> list:
        return [s for s in self.all_steps() if self._manifest_ok(s)]

    def latest(self) -> Optional[int]:
        """Newest step with a readable manifest, or None."""
        valid = self.valid_steps()
        return valid[-1] if valid else None

    # -- manifest ----------------------------------------------------------

    def _manifest_ok(self, step: int) -> bool:
        try:
            self.manifest(step)
            return True
        except Exception:
            return False

    def manifest(self, step: int) -> dict:
        """Parse and vet a step's manifest; raises CheckpointCorruptError
        for absent/truncated/mismatched manifests."""
        from ramba_tpu.checkpoint import CheckpointCorruptError

        mpath = self.manifest_path(step)
        if not os.path.exists(mpath):
            raise CheckpointCorruptError(
                f"checkpoint step {step} at {self.step_dir(step)!r} has no "
                f"manifest (torn or foreign write)")
        try:
            with open(mpath, "r", encoding="utf-8") as f:
                man = json.load(f)
        except (ValueError, OSError) as e:
            raise CheckpointCorruptError(
                f"checkpoint step {step} manifest at {mpath!r} is "
                f"unreadable ({type(e).__name__}: {e})") from e
        if not isinstance(man, dict) or man.get("step") != int(step):
            raise CheckpointCorruptError(
                f"checkpoint manifest at {mpath!r} does not describe "
                f"step {step}")
        for key in ("process_count", "mesh_devices", "x64", "leaves"):
            if key not in man:
                raise CheckpointCorruptError(
                    f"checkpoint manifest at {mpath!r} is missing {key!r}")
        want = man.get("digest")
        if want is not None and _integrity.enabled():
            # self-digest over the manifest body: a flipped bit anywhere
            # in the file (leaf fingerprints included) refuses the step
            if _manifest_digest(man) != want:
                _integrity.failure("checkpoint:leaf", "digest",
                                   detail=f"manifest step {step}")
                raise CheckpointCorruptError(
                    f"checkpoint manifest at {mpath!r} failed its "
                    f"self-digest (silent corruption)")
        return man

    def _write_manifest(self, step: int, vals) -> dict:
        from ramba_tpu.parallel import mesh as _mesh

        mesh = _mesh.get_mesh()
        man = {
            "format": _MANIFEST_FORMAT,
            "step": int(step),
            "process_count": int(jax.process_count()),
            "process_index": int(jax.process_index()),
            "mesh_shape": {k: int(v) for k, v in mesh.shape.items()},
            "mesh_devices": int(mesh.devices.size),
            "x64": bool(jax.config.jax_enable_x64),
            "leaves": _leaf_fingerprints(vals),
        }
        man["digest"] = _manifest_digest(man)
        if jax.process_index() == 0:
            mpath = self.manifest_path(step)
            tmp = mpath + ".tmp"
            with open(tmp, "w", encoding="utf-8") as f:
                json.dump(man, f, indent=1, sort_keys=True)
            os.replace(tmp, mpath)
        _barrier("ramba_elastic_manifest")
        return man

    # -- save --------------------------------------------------------------

    def save(self, step: int, tree=None) -> str:
        """Checkpoint ``tree`` (default: the registered trees) as
        ``step``.  Collective: every rank must call with the same step."""
        from ramba_tpu import checkpoint as _checkpoint
        from ramba_tpu.core.ndarray import ndarray

        tree = tree if tree is not None else self.gather()
        if not jax.tree.leaves(tree):
            raise ValueError(
                "CheckpointManager.save: nothing to checkpoint (no tree "
                "given and no registered trees)")
        d = self.step_dir(step)
        if jax.process_index() == 0:
            os.makedirs(d, exist_ok=True)
        _barrier("ramba_elastic_stepdir")
        t0 = time.perf_counter()
        _checkpoint.save(self.state_path(step), tree, force=True)
        vals = jax.tree.map(
            lambda x: x._value() if isinstance(x, ndarray) else np.asarray(x),
            tree,
        )
        self._write_manifest(step, vals)
        _registry.inc("elastic.checkpoints")
        _events.emit({"type": "lifecycle", "phase": "checkpoint_saved",
                      "step": int(step), "path": d,
                      "wall_s": round(time.perf_counter() - t0, 4)})
        self.gc()
        return d

    def maybe_save(self, step: int, tree=None) -> Optional[str]:
        """Auto-checkpoint hook for training loops: saves when ``step``
        lands on the ``every_steps`` cadence, else no-op."""
        if not self.every_steps or int(step) % self.every_steps != 0:
            return None
        return self.save(step, tree)

    # -- retention ---------------------------------------------------------

    def gc(self) -> list:
        """Apply retention-K.  Returns the deleted step numbers.  Invalid
        (torn) steps newer than the newest valid one are left alone — a
        concurrent writer may still be publishing them."""
        import shutil

        valid = self.valid_steps()
        if not valid:
            return []
        newest_valid = valid[-1]
        keep_set = set(valid[-self.keep:])
        doomed = [s for s in self.all_steps()
                  if s not in keep_set and s < newest_valid]
        if jax.process_index() == 0:
            for s in doomed:
                shutil.rmtree(self.step_dir(s), ignore_errors=True)
        _barrier("ramba_elastic_gc")
        if doomed:
            _registry.inc("elastic.checkpoints_gcd", len(doomed))
            _events.emit({"type": "lifecycle", "phase": "checkpoint_gc",
                          "deleted_steps": doomed,
                          "kept": sorted(keep_set)})
        return doomed

    # -- load (same-mesh strict path) --------------------------------------

    def load(self, step: Optional[int] = None, target=None):
        """Restore a step strictly: without ``target`` the world must
        match the manifest (process count, mesh size, x64) — a changed
        mesh raises CheckpointCorruptError pointing at :func:`resume`,
        which rebuilds the target for the current mesh."""
        from ramba_tpu import checkpoint as _checkpoint
        from ramba_tpu.checkpoint import CheckpointCorruptError
        from ramba_tpu.parallel import mesh as _mesh

        if step is None:
            step = self.latest()
            if step is None:
                raise CheckpointCorruptError(
                    f"no valid checkpoint under {self.root!r}")
        man = self.manifest(step)
        _check_x64(man, self.manifest_path(step))
        if target is None:
            mesh = _mesh.get_mesh()
            if (int(man["process_count"]) != int(jax.process_count())
                    or int(man["mesh_devices"]) != int(mesh.devices.size)):
                raise CheckpointCorruptError(
                    f"checkpoint step {step} was saved on "
                    f"{man['process_count']} process(es) / "
                    f"{man['mesh_devices']} device(s) but this run has "
                    f"{jax.process_count()} / {mesh.devices.size}; restore "
                    f"without a target cannot re-shard — use "
                    f"elastic.resume() to restore into the current mesh")
        return _checkpoint.restore(self.state_path(step), target)


def _check_x64(man: dict, where: str) -> None:
    from ramba_tpu.checkpoint import CheckpointCorruptError

    now = bool(jax.config.jax_enable_x64)
    if bool(man.get("x64")) != now:
        raise CheckpointCorruptError(
            f"checkpoint manifest at {where!r} was written with "
            f"jax_enable_x64={bool(man.get('x64'))} but this run has "
            f"{now}; the numeric lattice differs — restoring would "
            f"silently change dtypes")


# -- drain-to-checkpoint ----------------------------------------------------

def _drain_deadline() -> Optional[float]:
    raw = os.environ.get("RAMBA_DRAIN_S")
    if raw:
        try:
            t = float(raw)
            return t if t > 0 else None
        except ValueError:
            pass
    wd = watchdog_seconds()
    return 10.0 * wd if wd is not None else None


def quiesce() -> int:
    """Flush + drain every stream (serve sessions included) and wait for
    device completion; returns the number of live streams quiesced."""
    from ramba_tpu.core import fuser as _fuser

    streams = _fuser.all_streams()
    try:
        from ramba_tpu.serve import pipeline as _pipeline

        p = _pipeline.current_pipeline()
        if p is not None:
            p.quiesce(timeout=_drain_deadline())
    except ImportError:  # serve layer optional at this point
        pass
    _fuser.sync()
    return len(streams)


def drain_to_checkpoint(manager, step: int, tree=None) -> str:
    """Quiesce the whole process (serve sessions, async pipeline, every
    pending flush stream) under the drain deadline, then checkpoint.

    ``manager`` is a :class:`CheckpointManager` or a root path.  Returns
    the step directory.  A hang while draining raises a fatal-classified
    :class:`RankStallError` — checkpointing un-quiesced state would
    publish junk."""
    mgr = manager if isinstance(manager, CheckpointManager) \
        else CheckpointManager(manager)
    _events.emit({"type": "lifecycle", "phase": "drain_begin",
                  "step": int(step)})
    t0 = time.perf_counter()
    n = with_deadline("drain", quiesce, timeout_s=_drain_deadline())
    _events.emit({"type": "lifecycle", "phase": "drain_complete",
                  "step": int(step), "streams": n,
                  "wall_s": round(time.perf_counter() - t0, 4)})
    _registry.inc("elastic.drains")
    return mgr.save(step, tree)


# -- mesh-reshape resume ----------------------------------------------------

def _admit_restore(total_bytes: int) -> int:
    """HBM-governor admission for a restore: when the incoming bytes
    would push the ledger past the watermark, evict/spill first.
    Returns the bytes freed (0 when no budget is configured)."""
    budget = _memory.budget_bytes()
    if budget is None or total_bytes <= 0:
        return 0
    wm = _memory.watermark_bytes(budget) or budget
    need = _memory.ledger.live_bytes + total_bytes - wm
    if need <= 0:
        return 0
    freed = _memory.ledger.evict_until(int(need))
    _registry.inc("elastic.restore_spills")
    _events.emit({"type": "lifecycle", "phase": "restore_admit",
                  "incoming_bytes": int(total_bytes),
                  "need_bytes": int(need), "freed_bytes": int(freed)})
    return freed


class Resumed:
    """Result of :func:`resume`: the restored state plus provenance."""

    __slots__ = ("step", "state", "manifest")

    def __init__(self, step: int, state, manifest: dict):
        self.step = step
        self.state = state
        self.manifest = manifest

    def __repr__(self) -> str:
        return (f"Resumed(step={self.step}, "
                f"from_processes={self.manifest.get('process_count')})")


def resume(path, *, step: Optional[int] = None, mesh=None) -> Resumed:
    """Restore the newest valid checkpoint under ``path`` (a
    :class:`CheckpointManager` root) into the CURRENT mesh.

    The restore target is rebuilt from the checkpoint's own Orbax
    metadata — every leaf becomes a ``jax.ShapeDtypeStruct`` sharded by
    the current mesh's ``default_spec`` — so the rank count may differ
    from the saving run (2→1, 1→2): ``checkpoint.restore(path, target)``
    re-shards each leaf straight onto the new mesh.  Runs under
    HBM-governor admission (:func:`_admit_restore`).  Raises
    ``CheckpointCorruptError`` when no valid step exists, the manifest
    is torn, or the x64 regime changed."""
    import orbax.checkpoint as ocp

    from ramba_tpu import checkpoint as _checkpoint
    from ramba_tpu.checkpoint import CheckpointCorruptError
    from ramba_tpu.parallel import mesh as _mesh_mod

    mgr = path if isinstance(path, CheckpointManager) \
        else CheckpointManager(path)
    if step is None:
        step = mgr.latest()
        if step is None:
            raise CheckpointCorruptError(
                f"no valid checkpoint under {mgr.root!r}")
    man = mgr.manifest(step)
    _check_x64(man, mgr.manifest_path(step))
    mesh = mesh if mesh is not None else _mesh_mod.get_mesh()
    state_path = mgr.state_path(step)
    try:
        with ocp.StandardCheckpointer() as ckptr:
            meta = ckptr.metadata(state_path).item_metadata.tree
    except Exception as e:
        raise CheckpointCorruptError(
            f"checkpoint step {step} at {state_path!r} has unreadable "
            f"metadata ({type(e).__name__}: {e})") from e
    n_meta = len(jax.tree.leaves(meta))
    if n_meta != len(man["leaves"]):
        raise CheckpointCorruptError(
            f"checkpoint step {step}: manifest records "
            f"{len(man['leaves'])} leaves but the state holds {n_meta}")
    from jax.sharding import NamedSharding

    total_bytes = 0

    def tospec(m):
        nonlocal total_bytes
        shape = tuple(int(s) for s in m.shape)
        dt = np.dtype(m.dtype)
        total_bytes += int(np.prod(shape, dtype=np.int64)) * dt.itemsize
        return jax.ShapeDtypeStruct(
            shape, dt,
            sharding=NamedSharding(mesh, _mesh_mod.default_spec(shape, mesh)))

    target = jax.tree.map(tospec, meta)
    _events.emit({"type": "lifecycle", "phase": "resume_begin",
                  "step": int(step),
                  "from_processes": int(man["process_count"]),
                  "to_processes": int(jax.process_count()),
                  "bytes": int(total_bytes)})
    _admit_restore(total_bytes)
    t0 = time.perf_counter()
    state = _checkpoint.restore(state_path, target)
    _registry.inc("elastic.resumes")
    _events.emit({"type": "lifecycle", "phase": "resume_complete",
                  "step": int(step), "bytes": int(total_bytes),
                  "wall_s": round(time.perf_counter() - t0, 4)})
    return Resumed(int(step), state, man)


# -- live mesh reshape -------------------------------------------------------

def _reshape_census():
    """Snapshot every ledger-tracked array as ``(entry, const, value)``
    triples (spilled entries included — their Const still owns the host
    wrapper).  Entries whose owners all died are skipped."""
    triples = []
    led = _memory.ledger
    with led._lock:
        for e in list(led.entries.values()):
            consts = led._live_consts(e)
            if not consts:
                continue
            triples.append((e, consts[0], consts[0].value))
    return triples


def _census_hash31(triples) -> int:
    import hashlib

    lines = sorted(
        f"{tuple(v.shape)}:{np.dtype(v.dtype)}" for _, _, v in triples)
    h = hashlib.sha1("\n".join(lines).encode()).digest()
    return int.from_bytes(h[:4], "big") & 0x7FFFFFFF


def live_reshape(new_mesh, *, manager=None, step: int = 0,
                 max_stage_bytes: Optional[int] = None) -> dict:
    """Reshape the job onto ``new_mesh`` without leaving the process:
    fence → quiesce → reshard every live array in place → commit.

    The ladder, top rung first:

    1. **Live** — a coherence-agreed epoch fence (census hash broadcast
       + go/no-go vote) ensures every rank sees the same array set, the
       serve pipeline and all flush streams quiesce under the drain
       deadline, spilled arrays are restored, and each array is
       resharded onto ``new_mesh``'s default spec via the staged
       collective schedule in ``parallel.reshard`` (governor-admitted,
       bounded peak-live).  Nothing commits until every array has a new
       buffer; then all ledger entries swap atomically and
       ``set_mesh(new_mesh)`` bumps the mesh epoch (invalidating
       compiled programs).
    2. **Fallback** — only when the reshard schedule itself fails (or
       the fleet votes no-go): ``drain_to_checkpoint`` + :func:`resume`
       through ``manager`` (a temp directory when not given), the path
       that used to be the only one.

    Either way the source arrays stay intact until their replacement is
    ready — a failed reshape never tears an array.  Returns a dict with
    ``mode`` (``"live"`` / ``"checkpoint"``), array count, bytes moved,
    and wall seconds."""
    from ramba_tpu.parallel import mesh as _mesh_mod
    from ramba_tpu.parallel import reshard as _reshard

    t0 = time.perf_counter()
    old_mesh = _mesh_mod.get_mesh()
    _events.emit({
        "type": "lifecycle", "phase": "reshape_begin",
        "from_mesh": dict(old_mesh.shape), "to_mesh": dict(new_mesh.shape),
    })
    with_deadline("drain", quiesce, timeout_s=_drain_deadline())
    triples = _reshape_census()
    go = _coherence.P_OK
    if _coherence.engaged():
        mine = _census_hash31(triples)
        agreed = _coherence.agree("elastic:reshape", mine, reduce="bcast")
        if agreed != mine:
            go = _coherence.P_DROP
        decision = _coherence.agree("elastic:reshape:go", go, reduce="max")
    else:
        decision = go
    err: Optional[str] = None
    pairs = []
    total = 0
    if decision == _coherence.P_OK:
        try:
            for e, const, value in triples:
                if e.spilled:
                    value = _memory.ledger.restore(const)
                spec = _mesh_mod.default_spec(value.shape, new_mesh)
                out = _reshard.reshard_value(
                    value, spec, mesh=new_mesh,
                    max_stage_bytes=max_stage_bytes)
                pairs.append((value, out))
                total += int(e.nbytes)
        except (_reshard.ReshardError, _coherence.CoherentAbort) as exc:
            err = f"{type(exc).__name__}: {exc}"[:200]
            pairs = []
    else:
        err = "fleet voted no-go (census hash mismatch on a peer rank)"
    if err is None:
        for old, new in pairs:
            _memory.ledger.swap_value(old, new)
        _mesh_mod.set_mesh(new_mesh)
        _registry.inc("elastic.live_reshapes")
        wall = round(time.perf_counter() - t0, 4)
        _events.emit({
            "type": "lifecycle", "phase": "reshape_live_complete",
            "arrays": len(pairs), "bytes": int(total), "wall_s": wall,
        })
        return {"mode": "live", "arrays": len(pairs),
                "bytes": int(total), "wall_s": wall}

    # Fallback rung: the sources are untouched (no swap happened), so
    # the old checkpoint path still sees a consistent pre-reshape world.
    import tempfile

    _registry.inc("elastic.reshape_fallbacks")
    _events.emit({
        "type": "lifecycle", "phase": "reshape_fallback", "error": err,
    })
    root = manager if manager is not None \
        else tempfile.mkdtemp(prefix="ramba-reshape-")
    tree = {str(i): v for i, (_, _, v) in enumerate(triples)}
    mgr = root if isinstance(root, CheckpointManager) \
        else CheckpointManager(root)
    drain_to_checkpoint(mgr, step, tree)
    res = resume(mgr, step=step, mesh=new_mesh)
    from ramba_tpu.core.ndarray import ndarray as _ndarray

    for i, (_, _, old) in enumerate(triples):
        leaf = res.state[str(i)]
        if isinstance(leaf, _ndarray):  # checkpoint.restore re-wraps
            leaf = leaf._value()
        _memory.ledger.swap_value(old, leaf)
    _mesh_mod.set_mesh(new_mesh)
    wall = round(time.perf_counter() - t0, 4)
    _events.emit({
        "type": "lifecycle", "phase": "reshape_checkpoint_complete",
        "arrays": len(triples), "wall_s": wall,
    })
    return {"mode": "checkpoint", "arrays": len(triples),
            "bytes": int(sum(e.nbytes for e, _, _ in triples)),
            "wall_s": wall}


def report() -> dict:
    """Diagnostics rollup for ``ramba_tpu.diagnostics.report()``."""
    return {
        "watchdog_s": watchdog_seconds(),
        "heartbeat_running": heartbeat_running(),
        # the interval rides along so a reader of this block out of a
        # snapshot can judge last_beat_age_s against the beacon cadence
        # the process was actually configured with
        "heartbeat_interval_s": round(_heartbeat_interval(), 3),
        "heartbeats": int(_registry.get("elastic.heartbeats")),
        "last_beat_age_s": (round(last_beat_age(), 4)
                            if last_beat_age() is not None else None),
        "last_progress_age_s": (round(last_progress_age(), 4)
                                if last_progress_age() is not None else None),
        "stalls": int(_registry.get("elastic.stalls")),
        "checkpoints": int(_registry.get("elastic.checkpoints")),
        "resumes": int(_registry.get("elastic.resumes")),
        "drains": int(_registry.get("elastic.drains")),
        "live_reshapes": int(_registry.get("elastic.live_reshapes")),
        "reshape_fallbacks": int(_registry.get("elastic.reshape_fallbacks")),
    }
