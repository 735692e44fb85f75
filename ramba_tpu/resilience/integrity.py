"""End-to-end data integrity plane: digests, audits, suspect quarantine.

Every byte-carrying seam — persistent AOT executables and program
records (``compile/persist.py``), checkpoint leaves and manifests
(``checkpoint.py``, ``resilience/elastic.py``) — trusts that bytes read
back are the bytes written.  Before this module, corruption was only
caught when deserialization *happened* to throw; a bit-flip that still
parses was served to users as a wrong answer.  This module makes silent data
corruption a first-class, classified fault, in four legs:

**Content digests.**  :func:`wrap` stamps a payload with an envelope —
one header line carrying a schema tag and a sha256 over
``schema : length : payload`` — and :func:`unwrap` verifies it at adopt
time.  A mismatch (or a payload with no envelope at all: a flip that
lands on the header must not demote the blob to "legacy, trust it")
raises :class:`IntegrityError`, which every seam routes to
evict-then-recompute/recompile — **never serve, never crash**.  Each
failure is counted, emitted as an ``integrity`` trace event, and (with
``RAMBA_FLIGHT_DIR`` set) dumped as a flight-recorder incident.

**Shadow recompute audits.**  ``RAMBA_AUDIT=<N>`` samples one in every
``N`` effects-certified pure flushes (the PR-12 certificate proves
re-execution is safe) and re-executes the program on the eager rung —
a genuinely different execution path from the fused jit module —
comparing byte-identity of the outputs.  The verdict is agreed
cross-rank via ``coherence.agree("integrity:audit", reduce="max")`` so
a mismatch on one rank evicts coherently everywhere.  The *primary*
result is always the one served (on a mismatch nobody can say which
side flipped — serving the primary keeps audit-on runs byte-identical
to audit-off runs); the memo insert is suppressed and the plan's memo
entry is evicted so the suspect bytes cannot be served again.

**Suspect quarantine.**  A process accumulating
``RAMBA_INTEGRITY_THRESHOLD`` digest/audit failures (default 3) inside
a sliding ``RAMBA_INTEGRITY_WINDOW_S`` window (default 300 s) flips a
``suspect`` signal: the ``integrity`` event carries it, the
``integrity.suspect`` gauge reads 1, and ``snapshot()`` reports it.

**Offline verification.**  ``scripts/ramba_fsck.py`` walks the AOT
cache and checkpoint digest sidecars, re-verifying every
envelope with :func:`verify_blob` (which never emits — an offline scan
must not strike the live suspect window).

``RAMBA_INTEGRITY=0`` disables stamping and verification everywhere
(envelopes are still *stripped* on read so wrapped and raw blobs both
load) — the escape hatch, and the "OFF phase" the integrity suite leg
uses to reproduce the wrong-answer serve this plane exists to prevent.

Fault site ``audit:shadow`` (``RAMBA_FAULTS='audit:shadow:flip:...'``)
flips the shadow's bytes so audit mismatch handling can be driven
deterministically; the digest seams wire ``aot:blob`` and
``checkpoint:leaf`` the same way.
"""

from __future__ import annotations

import hashlib
import os
import threading
import time
from collections import deque
from typing import Any, Callable, List, Optional, Sequence, Tuple

from ramba_tpu.observe import events as _events
from ramba_tpu.observe import registry as _registry
from ramba_tpu.resilience import faults as _faults

_OFF = ("0", "off", "false", "no")

#: envelope magic — one header line: ``RMBI1 <schema> <sha256hex>\n``
_MAGIC = b"RMBI1 "
_ENVELOPE_VERSION = 1


class IntegrityError(RuntimeError):
    """A payload failed digest verification (or carries no envelope at
    a seam that requires one).  ``site`` names the seam, ``reason`` the
    classified failure shape: ``unstamped`` | ``header`` | ``schema`` |
    ``length`` | ``digest`` | ``deserialize`` | ``audit``."""

    def __init__(self, site: str, reason: str, detail: str = ""):
        self.site = site
        self.reason = reason
        msg = f"integrity failure at {site!r}: {reason}"
        if detail:
            msg += f" ({detail})"
        super().__init__(msg)


_lock = threading.Lock()

#: running counters; snapshot() adds config + suspect state
stats = {
    "stamped": 0,
    "verified": 0,
    "failures": 0,
    "unstamped_evictions": 0,
    "audits": 0,
    "audit_mismatches": 0,
    "audit_numeric": 0,
    "audit_errors": 0,
    "digest_bytes": 0,
    "digest_wall_s": 0.0,
    "audit_wall_s": 0.0,
}

# sliding failure window backing the suspect verdict
_strikes: deque = deque()
# eligible-flush counter for deterministic 1-in-N audit sampling (counts
# only audit-eligible flushes, which are rank-identical under SPMD, so
# every rank samples the SAME flushes)
_audit_counter = [0]


# ---------------------------------------------------------------------------
# configuration
# ---------------------------------------------------------------------------


def enabled() -> bool:
    """Digest stamping + verification gate (``RAMBA_INTEGRITY``,
    default on)."""
    return (os.environ.get("RAMBA_INTEGRITY") or "").strip().lower() \
        not in _OFF


def audit_every() -> int:
    """``RAMBA_AUDIT=<N>`` — shadow-audit one in every N eligible
    flushes; 0 (or unset, or integrity disabled) disarms."""
    if not enabled():
        return 0
    raw = (os.environ.get("RAMBA_AUDIT") or "").strip()
    if not raw or raw.lower() in _OFF:
        return 0
    try:
        return max(0, int(raw))
    except ValueError:
        return 0


def suspect_threshold() -> int:
    try:
        return max(1, int(os.environ.get("RAMBA_INTEGRITY_THRESHOLD", "")
                          or 3))
    except ValueError:
        return 3


def suspect_window_s() -> float:
    try:
        return max(1.0, float(os.environ.get("RAMBA_INTEGRITY_WINDOW_S", "")
                              or 300.0))
    except ValueError:
        return 300.0


# ---------------------------------------------------------------------------
# the envelope (content digests at every seam)
# ---------------------------------------------------------------------------


def _digest(payload: bytes, schema: str) -> str:
    h = hashlib.sha256()
    h.update(f"{schema}:{len(payload)}:".encode())
    h.update(payload)
    return h.hexdigest()


def wrap(payload: bytes, schema: str) -> bytes:
    """Stamp ``payload`` with its content-digest envelope.  Identity
    when the plane is disabled (``RAMBA_INTEGRITY=0``)."""
    if not enabled():
        return payload
    t0 = time.perf_counter()
    header = _MAGIC + schema.encode() + b" " + \
        _digest(payload, schema).encode() + b"\n"
    with _lock:
        stats["stamped"] += 1
        stats["digest_bytes"] += len(payload)
        stats["digest_wall_s"] += time.perf_counter() - t0
    return header + payload


def _split(data: bytes) -> Tuple[str, str, bytes]:
    """Parse an envelope into (schema, digest_hex, payload).  Raises
    ValueError on any malformed header."""
    if not data.startswith(_MAGIC):
        raise ValueError("no envelope magic")
    nl = data.find(b"\n", 0, 256)
    if nl < 0:
        raise ValueError("unterminated envelope header")
    fields = data[len(_MAGIC):nl].split(b" ")
    if len(fields) != 2:
        raise ValueError("malformed envelope header")
    return fields[0].decode("ascii", "replace"), \
        fields[1].decode("ascii", "replace"), data[nl + 1:]


def verify_blob(data: Optional[bytes], schema: str) -> Optional[str]:
    """Offline verification (ramba-fsck): returns ``None`` when the
    envelope checks out, else the classified reason.  Never emits
    events and never strikes the suspect window."""
    if data is None:
        return "missing"
    try:
        got_schema, got_digest, payload = _split(data)
    except ValueError as e:
        return "unstamped" if not data.startswith(_MAGIC) else \
            f"header:{e}"
    if got_schema != schema:
        return f"schema:{got_schema!r}"
    if got_digest != _digest(payload, schema):
        return "digest"
    return None


def unwrap(data: bytes, schema: str, *, site: str,
           record: bool = True) -> bytes:
    """Verify and strip a payload's envelope.

    STRICT at every runtime seam: a payload without an envelope raises
    ``IntegrityError("unstamped")`` — pre-plane on-disk entries get
    evicted once and rewritten stamped, and a flip landing on the
    header bytes cannot smuggle a blob past verification by making it
    look legacy.  With the plane disabled the envelope (when present)
    is stripped without verification so wrapped and raw blobs both
    load."""
    if not enabled():
        try:
            return _split(data)[2]
        except ValueError:
            return data
    t0 = time.perf_counter()
    try:
        got_schema, got_digest, payload = _split(data)
    except ValueError as e:
        reason = "unstamped" if not data.startswith(_MAGIC) else "header"
        if record:
            failure(site, reason, detail=str(e), schema=schema)
        raise IntegrityError(site, reason, str(e)) from None
    if got_schema != schema:
        if record:
            failure(site, "schema", detail=f"{got_schema!r} != {schema!r}")
        raise IntegrityError(site, "schema",
                             f"{got_schema!r} != {schema!r}")
    want = _digest(payload, schema)
    with _lock:
        stats["digest_bytes"] += len(payload)
        stats["digest_wall_s"] += time.perf_counter() - t0
    if got_digest != want:
        if record:
            failure(site, "digest", schema=schema)
        raise IntegrityError(site, "digest",
                             f"stored {got_digest[:12]}.. != "
                             f"recomputed {want[:12]}..")
    with _lock:
        stats["verified"] += 1
    return payload


def file_digest(path: str, chunk: int = 1 << 20) -> str:
    """Streamed sha256 over a file's raw bytes (checkpoint sidecars,
    ramba-fsck)."""
    h = hashlib.sha256()
    with open(path, "rb") as f:
        while True:
            block = f.read(chunk)
            if not block:
                break
            h.update(block)
    return h.hexdigest()


def array_digest(arr: Any) -> str:
    """Logical content digest of one array leaf: sha256 over dtype,
    shape and C-order bytes — sharding-independent, so a resharded
    restore verifies against the digest stamped at save."""
    import numpy as np

    a = np.ascontiguousarray(np.asarray(arr))
    h = hashlib.sha256()
    h.update(str(a.dtype).encode())
    h.update(repr(a.shape).encode())
    h.update(a.tobytes())
    return h.hexdigest()


# ---------------------------------------------------------------------------
# classified failures + suspect quarantine
# ---------------------------------------------------------------------------


def failure(site: str, reason: str, *, detail: str = "", **ctx) -> None:
    """Record one integrity failure: counters, an ``integrity`` trace
    event (a flight-recorder trigger — observe/telemetry.py), and a
    strike on the suspect window."""
    now = time.time()
    with _lock:
        stats["failures"] += 1
        if reason == "unstamped":
            stats["unstamped_evictions"] += 1
        _strikes.append(now)
        window = suspect_window_s()
        while _strikes and now - _strikes[0] > window:
            _strikes.popleft()
        in_window = len(_strikes)
        is_suspect = in_window >= suspect_threshold()
    _registry.inc("integrity.failures")
    _registry.inc(f"integrity.failures.{site}")
    ev = {"type": "integrity", "site": site, "reason": reason,
          "failures_in_window": in_window, "suspect": is_suspect}
    if detail:
        ev["detail"] = detail
    ev.update(ctx)
    _events.emit(ev)
    if is_suspect:
        _registry.gauge("integrity.suspect", 1)


def failure_count(now: Optional[float] = None) -> int:
    """Digest/audit failures inside the current sliding window."""
    now = time.time() if now is None else now
    window = suspect_window_s()
    with _lock:
        while _strikes and now - _strikes[0] > window:
            _strikes.popleft()
        return len(_strikes)


def suspect(now: Optional[float] = None) -> bool:
    """Whether this process has crossed the quarantine threshold."""
    return failure_count(now) >= suspect_threshold()


# ---------------------------------------------------------------------------
# shadow recompute audits
# ---------------------------------------------------------------------------


def _out_bytes(outs: Sequence[Any]) -> List[bytes]:
    """Byte-identity view of one flush's outputs.  Multi-host arrays
    (not fully addressable, not fully replicated) compare their LOCAL
    shards in deterministic index order — each rank audits its own
    bytes and the coherence round merges the verdicts."""
    import numpy as np

    res: List[bytes] = []
    for o in outs:
        if getattr(o, "is_fully_addressable", True) or \
                getattr(o, "is_fully_replicated", False):
            res.append(np.ascontiguousarray(np.asarray(o)).tobytes())
        else:
            shards = sorted(o.addressable_shards,
                            key=lambda sh: str(sh.index))
            res.append(b"".join(
                np.ascontiguousarray(np.asarray(sh.data)).tobytes()
                for sh in shards))
    return res


#: rung-to-rung numerical slack, in units of dtype eps.  The fused jit
#: module and the per-op alternate rung are allowed to round differently
#: (XLA contracts a*b+c into FMA inside a fused module but not across
#: op-by-op dispatches) — a few-ulp divergence between rungs is physics,
#: not corruption.  A flipped BYTE (XOR 0xFF) moves a float by up to 255
#: ulp at that byte's position, far past this slack, so seeded and real
#: flips still classify as mismatches; only a flip confined to the very
#: lowest mantissa bits is indistinguishable from rounding, an inherent
#: limit of cross-rung comparison.
_AUDIT_ULP_SLACK = 64.0


def _classify_divergence(outs: Sequence[Any], primary: List[bytes],
                         shadow: List[bytes]) -> Tuple[int, int]:
    """(mismatch, numeric): byte-identical pairs are clean; inexact
    dtypes diverging within ``_AUDIT_ULP_SLACK`` ulp are benign
    cross-rung rounding (``numeric``); anything else — shape/length
    skew, integer diffs, beyond-slack float diffs — is a mismatch."""
    import numpy as np

    if len(primary) != len(shadow):
        return 1, 0
    numeric = 0
    for o, pb, sb in zip(outs, primary, shadow):
        if pb == sb:
            continue
        dt = np.dtype(getattr(o, "dtype", np.uint8))
        if len(pb) != len(sb) or dt.kind not in "fc":
            return 1, numeric
        pa = np.frombuffer(pb, dtype=dt)
        sa = np.frombuffer(sb, dtype=dt)
        tol = _AUDIT_ULP_SLACK * float(np.finfo(dt).eps)
        if not bool(np.allclose(pa, sa, rtol=tol, atol=tol,
                                equal_nan=True)):
            return 1, numeric
        numeric += 1
    return 0, numeric


def shadow_audit(label: str, outs: Sequence[Any],
                 rerun: Callable[[], Sequence[Any]], *,
                 plan: Any = None, span: Optional[dict] = None) -> bool:
    """Maybe shadow-audit one flush; returns True iff the ranks agreed
    the audit found a mismatch (caller must then suppress the memo
    insert — the primary ``outs`` are still the ones served).

    Sampling is deterministic 1-in-N over *eligible* flushes
    (``RAMBA_AUDIT=<N>``); eligibility (effects-certified pure, no
    donation) is the caller's check and is rank-identical under SPMD,
    so every rank audits the same flushes and the
    ``coherence.agree("integrity:audit")`` round below stays aligned.
    ``rerun`` re-executes the program on an alternate rung; its outputs
    pass through the ``audit:shadow`` flip seam so mismatch handling is
    deterministically drivable."""
    n = audit_every()
    if n <= 0:
        return False
    with _lock:
        _audit_counter[0] += 1
        due = _audit_counter[0] % n == 0
    if not due:
        return False
    from ramba_tpu.resilience import coherence as _coherence

    t0 = time.perf_counter()
    mismatch = 0
    numeric = 0
    try:
        shadow = rerun()
        primary_bytes = _out_bytes(outs)
        shadow_bytes = [
            _faults.corrupt("audit:shadow", b, label=label) or b
            for b in _out_bytes(shadow)
        ]
        mismatch, numeric = _classify_divergence(
            outs, primary_bytes, shadow_bytes)
    except Exception as e:  # noqa: BLE001 — the audit must never fail a flush
        with _lock:
            stats["audit_errors"] += 1
        _registry.inc("integrity.audit_errors")
        _events.emit({"type": "integrity_audit", "label": label,
                      "outcome": "error", "error": repr(e)[:200]})
        return False
    decision = _coherence.agree("integrity:audit", mismatch, reduce="max")
    dt = time.perf_counter() - t0
    with _lock:
        stats["audits"] += 1
        stats["audit_wall_s"] += dt
        stats["audit_numeric"] += numeric
        if decision:
            stats["audit_mismatches"] += 1
    _registry.inc("integrity.audits")
    if span is not None:
        span["audited"] = True
    if not decision:
        ev = {"type": "integrity_audit", "label": label,
              "outcome": "ok", "wall_ms": round(dt * 1e3, 3)}
        if numeric:
            ev["outcome"] = "numeric"
            ev["numeric_outs"] = numeric
        _events.emit(ev)
        return False
    _registry.inc("integrity.audit_mismatches")
    failure("audit:shadow", "audit", detail=label,
            local_mismatch=bool(mismatch))
    if span is not None:
        span["audit_mismatch"] = True
    _evict_plan_blobs(plan)
    return True


def _evict_plan_blobs(plan: Any) -> None:
    """A mismatched audit means the flush's bytes are suspect: evict the
    plan's memo entry so they cannot be served again."""
    if plan is None:
        return
    try:
        from ramba_tpu.core import memo as _memo

        _memo.evict(plan)
    except Exception:  # noqa: BLE001 — eviction is best-effort
        pass


# ---------------------------------------------------------------------------
# diagnostics
# ---------------------------------------------------------------------------


def snapshot() -> dict:
    with _lock:
        d = dict(stats)
        d["digest_wall_s"] = round(d["digest_wall_s"], 6)
        d["audit_wall_s"] = round(d["audit_wall_s"], 6)
    d["enabled"] = enabled()
    d["audit_every"] = audit_every()
    d["suspect"] = suspect()
    d["failures_in_window"] = failure_count()
    d["suspect_threshold"] = suspect_threshold()
    d["suspect_window_s"] = suspect_window_s()
    return d


def reset() -> None:
    """Tests: zero counters, the suspect window and the audit sampler."""
    with _lock:
        for k in stats:
            stats[k] = 0.0 if isinstance(stats[k], float) else 0
        _strikes.clear()
        _audit_counter[0] = 0
