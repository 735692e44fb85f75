"""Effect-certified cross-flush result memoization (``RAMBA_MEMO``).

The compile cache (``fuser._compile_cache``) makes the *second* flush of
a program structure cheap; this cache makes it free — when, and only
when, the static certifier proves that is sound:

* the program's effect class is pure or RNG-keyed and it neither
  donates nor alias-escapes an input
  (:func:`ramba_tpu.analyze.effects.classify_program`);
* its statics fold to value tokens, so it has a canonical semantic
  fingerprint (:func:`ramba_tpu.analyze.canon.canonicalize`);
* every input binds to a *version token*: python scalars by value,
  device buffers by identity-under-weakref — jax arrays are immutable,
  so buffer identity is version identity, and the weakref death hook
  retires a token before ``id()`` reuse can forge it.

The memo key is ``(canonical hash, input tokens in canonical leaf
order, semantic fingerprint)`` — stable across sessions, tenants and
leaf orderings, unlike ``program.key``.

Cached results are ``Const``-wrapped and registered with the fuser's
owner census (``owner_incref(val, const)``), which has three deliberate
consequences: the memory governor's ledger accounts their bytes, its
LRU spiller may evict them to host (a hit transparently restores —
the cache is spill-aware for free), and a cached buffer always has a
live owner so no later flush can donate it out from under the cache.
The cache's own budget (``RAMBA_MEMO_BUDGET``, default 256m) bounds the
*logical* bytes it retains, LRU-evicted on insert.

Verification: the ``memo-safety`` rule (``analyze/rules.py``) audits
every flush-time plan; under ``RAMBA_VERIFY=strict`` an uncertified
plan aborts the flush before execution, and :func:`insert` additionally
refuses uncertified inserts even when rule filtering skipped the rule.
The ``memo:insert`` / ``memo:hit`` fault sites (``RAMBA_FAULTS``)
corrupt the certifier into approving an impure program — the seeded
violation the rule exists to catch.
"""

from __future__ import annotations

import dataclasses
import itertools
import os
import threading
import weakref
from typing import Any, Dict, List, Optional, Tuple

from ramba_tpu import common as _common
from ramba_tpu.analyze import canon as _canon
from ramba_tpu.analyze import effects as _effects
from ramba_tpu.observe import events as _events
from ramba_tpu.observe import registry as _registry
from ramba_tpu.resilience import faults as _faults
from ramba_tpu.resilience import memory as _memory
from ramba_tpu.resilience.spill import SpilledArray as _SpilledArray

_OFF = ("", "0", "off", "false", "no")


def enabled() -> bool:
    """Result memoization armed?  Off by default — ``RAMBA_MEMO=1``."""
    return (os.environ.get("RAMBA_MEMO") or "").strip().lower() not in _OFF


def budget_bytes() -> int:
    """Logical-byte budget for retained results (``RAMBA_MEMO_BUDGET``,
    ``common.parse_bytes`` grammar, default 256m; ``0`` = unbounded)."""
    raw = os.environ.get("RAMBA_MEMO_BUDGET")
    if raw:
        try:
            return max(0, _common.parse_bytes(raw))
        except ValueError:
            pass
    return 256 << 20


def _nbytes(v: Any) -> int:
    try:
        return int(v.nbytes)
    except Exception:
        return 0


@dataclasses.dataclass(frozen=True)
class MemoPlan:
    """One flush's memoization verdict, attached to ``_FlushWork`` and
    audited by the ``memo-safety`` verifier rule.

    ``memoizable`` is the operative decision (a fault site may force it
    True); ``certified`` is the certifier's genuine verdict — the two
    differ exactly when ``memo:insert``/``memo:hit`` injection seeded an
    impure program into the cache path.
    """

    memoizable: bool
    certified: bool
    reason: str
    chash: Optional[str]
    form: Optional[str]
    leaf_order: Tuple[int, ...]
    key: Optional[Tuple[Any, ...]]
    effects: Optional[_effects.EffectReport]


# ---------------------------------------------------------------------------
# input version tokens
# ---------------------------------------------------------------------------

# id(value) -> (token, weakref).  The weakref death callback retires the
# token, so a recycled id() can never alias a dead buffer's version.
_tokens: Dict[int, Tuple[Any, Any]] = {}
_token_lock = threading.Lock()
_token_clock = itertools.count(1)


def _retire_token(key: int, ref: Any) -> None:
    with _token_lock:
        cur = _tokens.get(key)
        if cur is not None and cur[1] is ref:
            del _tokens[key]


def value_token(v: Any) -> Optional[Tuple[Any, ...]]:
    """Version token for one buffer input; None when the value cannot be
    tracked (not weak-referenceable) — the program is then unmemoizable."""
    k = id(v)
    with _token_lock:
        cur = _tokens.get(k)
        if cur is not None and cur[1]() is v:
            return cur[0]
        try:
            ref = weakref.ref(v, lambda r, _k=k: _retire_token(_k, r))
        except TypeError:
            return None
        token = ("buf", next(_token_clock))
        _tokens[k] = (token, ref)
        return token


# ---------------------------------------------------------------------------
# the cache
# ---------------------------------------------------------------------------


class _Entry:
    __slots__ = ("key", "consts", "nbytes", "hits")

    def __init__(self, key: Tuple[Any, ...], consts: List[Any],
                 nbytes: int) -> None:
        self.key = key
        self.consts = consts
        self.nbytes = nbytes
        self.hits = 0


class ResultCache:
    """Canonical-key LRU over Const-wrapped flush results.  dict
    preserves insertion order and hits re-insert, so iteration order is
    recency order and eviction pops the LRU — the compile cache's own
    discipline."""

    def __init__(self) -> None:
        self._entries: Dict[Tuple[Any, ...], _Entry] = {}
        self._lock = threading.Lock()
        self.total_bytes = 0
        self.hits = 0
        self.misses = 0
        self.inserts = 0
        self.evictions = 0
        self.insert_rejects = 0

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def lookup(self, key: Tuple[Any, ...]) -> Optional[List[Any]]:
        with self._lock:
            e = self._entries.pop(key, None)
            if e is None:
                self.misses += 1
                return None
            self._entries[key] = e  # re-insert: MRU position
            e.hits += 1
            self.hits += 1
            consts = list(e.consts)
        vals: List[Any] = []
        for c in consts:
            v = c.value
            if isinstance(v, _SpilledArray):
                v = _memory.restore(c)
            else:
                _memory.ledger.touch(v)
            vals.append(v)
        return vals

    def insert(self, key: Tuple[Any, ...], outs: List[Any]) -> bool:
        from ramba_tpu.core import fuser as _fuser
        from ramba_tpu.core.expr import Const

        consts = []
        nbytes = 0
        for v in outs:
            c = Const(v)
            # census registration: the ledger accounts (and may spill)
            # the buffer, and a live owner blocks later donation of it
            _fuser.owner_incref(v, c)
            consts.append(c)
            nbytes += _nbytes(v)
        evicted: List[_Entry] = []
        with self._lock:
            old = self._entries.pop(key, None)
            if old is not None:
                evicted.append(old)
                self.total_bytes -= old.nbytes
            self._entries[key] = _Entry(key, consts, nbytes)
            self.total_bytes += nbytes
            self.inserts += 1
            limit = budget_bytes()
            if limit:
                while self.total_bytes > limit and len(self._entries) > 1:
                    lru_key = next(iter(self._entries))
                    if lru_key == key:
                        break
                    lru = self._entries.pop(lru_key)
                    self.total_bytes -= lru.nbytes
                    self.evictions += 1
                    evicted.append(lru)
        for e in evicted:
            _release_entry(e)
        if evicted:
            _registry.inc("memo.evictions", len(evicted))
        return True

    def clear(self) -> None:
        with self._lock:
            dead = list(self._entries.values())
            self._entries.clear()
            self.total_bytes = 0
        for e in dead:
            _release_entry(e)

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            looks = self.hits + self.misses
            return {
                "enabled": enabled(),
                "entries": len(self._entries),
                "bytes": self.total_bytes,
                "budget_bytes": budget_bytes(),
                "hits": self.hits,
                "misses": self.misses,
                "hit_rate": round(self.hits / looks, 4) if looks else 0.0,
                "inserts": self.inserts,
                "evictions": self.evictions,
                "insert_rejects": self.insert_rejects,
            }


def _release_entry(e: _Entry) -> None:
    from ramba_tpu.core import fuser as _fuser

    for c in e.consts:
        _fuser.owner_decref(c.value)
    e.consts = []


#: Process-wide result cache.
cache = ResultCache()


def evict(plan: Optional["MemoPlan"]) -> bool:
    """Drop one plan's cached entry (and its census refs).  The
    integrity plane calls this when a shadow audit disagreed with the
    primary result — the cached bytes are suspect and must not be
    served again."""
    if plan is None or plan.key is None:
        return False
    with cache._lock:
        e = cache._entries.pop(plan.key, None)
        if e is None:
            return False
        cache.total_bytes -= e.nbytes
        cache.evictions += 1
    _release_entry(e)
    _registry.inc("memo.evictions")
    return True


def reset() -> None:
    """Drop every cached result and its census refs (tests)."""
    cache.clear()
    with _token_lock:
        _tokens.clear()


# ---------------------------------------------------------------------------
# the flush-path API (fuser._flush_prepare / _flush_dispatch)
# ---------------------------------------------------------------------------


def plan_for(program: Any, donate_key: Tuple[int, ...], leaves: List[Any],
             leaf_vals: List[Any]) -> Optional[MemoPlan]:
    """Certify one prepared flush.  Returns None when memoization is
    disarmed or the program is provably unmemoizable; otherwise a plan
    whose ``key`` binds the canonical hash to the current input
    versions.  The ``memo:insert`` / ``memo:hit`` fault sites corrupt
    the certification (memoizable forced True) so the ``memo-safety``
    rule has a real violation to catch."""
    if not enabled():
        return None
    from ramba_tpu.core.expr import Scalar

    rep = _effects.classify_program(program, tuple(donate_key))
    memoizable = rep.memoizable
    for site in ("memo:insert", "memo:hit"):
        try:
            _faults.check(site)
        except _faults.InjectedFault:
            # certifier corruption: admit this program regardless of its
            # effect class — the seeded violation RAMBA_VERIFY's
            # memo-safety rule exists to catch.  Only reachable under
            # explicit fault injection.
            memoizable = True
    if not memoizable:
        _registry.inc("memo.uncacheable")
        return None
    form = _canon.try_canonicalize(program)
    if form is None:
        _registry.inc("memo.not_canonical")
        return None
    tokens: List[Any] = []
    for slot in form.leaf_order:
        leaf = leaves[slot]
        if isinstance(leaf, Scalar):
            try:
                tokens.append(("s", type(leaf.value).__name__,
                               leaf.value))
                hash(tokens[-1])
            except TypeError:
                return None
        else:
            tok = value_token(leaf_vals[slot])
            if tok is None:
                return None
            tokens.append(tok)
    from ramba_tpu.core import fuser as _fuser

    fingerprint = _fuser._semantic_fingerprint()
    key = (form.chash, tuple(tokens), fingerprint)
    return MemoPlan(
        memoizable=True,
        certified=rep.memoizable,
        reason=rep.reason,
        chash=form.chash,
        form=form.form,
        leaf_order=form.leaf_order,
        key=key,
        effects=rep,
    )


def plan_from_cert(chash: Optional[str], form: Optional[str],
                   leaf_order: Tuple[int, ...],
                   effects: Optional[_effects.EffectReport],
                   leaves: List[Any],
                   leaf_vals: List[Any]) -> Optional[MemoPlan]:
    """Rebuild a certified :class:`MemoPlan` from a plan certificate
    (``analyze/plancert.py``) without re-running effect classification
    or canonicalization — the certificate already vouches for both, and
    its invalidation signature proves the verdicts still hold.  Only the
    live state binds per flush: the per-input version tokens.  Returns
    None when memoization is disarmed, the certificate carried no
    canonical hash, or an input cannot be version-tracked (same
    bail-outs as :func:`plan_for`)."""
    if not enabled() or chash is None:
        return None
    from ramba_tpu.core.expr import Scalar

    tokens: List[Any] = []
    for slot in leaf_order:
        if slot >= len(leaves):
            return None
        leaf = leaves[slot]
        if isinstance(leaf, Scalar):
            try:
                tokens.append(("s", type(leaf.value).__name__,
                               leaf.value))
                hash(tokens[-1])
            except TypeError:
                return None
        else:
            tok = value_token(leaf_vals[slot])
            if tok is None:
                return None
            tokens.append(tok)
    from ramba_tpu.core import fuser as _fuser

    fingerprint = _fuser._semantic_fingerprint()
    key = (chash, tuple(tokens), fingerprint)
    return MemoPlan(
        memoizable=True,
        certified=True,
        reason="",
        chash=chash,
        form=form,
        leaf_order=tuple(leaf_order),
        key=key,
        effects=effects,
    )


def lookup(plan: Optional[MemoPlan]) -> Optional[List[Any]]:
    """Consult the result cache for a certified plan.  A hit returns the
    cached output values (restored from host spill when needed)."""
    if plan is None or not plan.memoizable or plan.key is None:
        return None
    vals = cache.lookup(plan.key)
    if vals is None:
        _registry.inc("memo.miss")
        return None
    _registry.inc("memo.hit")
    _events.emit({
        "type": "memo_hit", "chash": plan.chash, "n_outs": len(vals),
    })
    return vals


def insert(plan: Optional[MemoPlan], outs: List[Any]) -> bool:
    """Insert one flush's outputs under the plan's key.  Strict-mode
    RAMBA_VERIFY refuses any insert the certifier did not approve —
    the backstop behind the memo-safety rule, effective even when rule
    filtering (RAMBA_VERIFY_RULES/_SKIP) bypassed the rule itself."""
    if plan is None or not plan.memoizable or plan.key is None:
        return False
    if not plan.certified:
        from ramba_tpu.analyze import verifier as _verifier

        if _verifier.mode() == "strict":
            cache.insert_rejects += 1
            _registry.inc("memo.insert_rejected")
            _events.emit({
                "type": "memo_insert_rejected", "chash": plan.chash,
                "reason": plan.reason,
            })
            return False
    cache.insert(plan.key, list(outs))
    _registry.inc("memo.insert")
    return True
