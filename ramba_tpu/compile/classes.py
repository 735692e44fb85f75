"""Shape-bucketed compile classes (``RAMBA_COMPILE_CLASSES``).

A serving workload whose request shapes vary per user pays one full XLA
compile per novel shape — the JIT-amortization story only works if
"compile once" is shared *across shapes*.  This module maps dynamic
leading dimensions onto a small set of bucket sizes at flush-prepare
time: leaf arrays are zero-padded up to the bucket, the program executes
at the bucket shape, and outputs are sliced back to the exact request
size.  A million distinct request sizes then share a handful of
executables.

Policy (env ``RAMBA_COMPILE_CLASSES``)::

    off            (default) exact-shape compiles
    pow2           bucket the leading dim up to the next power of two
    linear:<step>  bucket up to the next multiple of <step>

Safety: padding is only sound when no instruction's semantics depend on
the leading extent — a segmented reduction's group count, a stencil's
halo, a reshard plan's split points would all cross the bucket
boundary.  The planner therefore only buckets programs made exclusively
of elementwise instructions (``map`` / ``cast`` / ``round``), whose
rows are computed independently, and additionally requires every output
(and every full-rank leaf) to share the same leading extent so the
pad/slice wrapper is well defined.  Anything else bails out to an
exact-shape compile, counted ``compile.bucket_bailout``.  The claim is
independently re-proven at flush time by the ``compile-class``
RAMBA_VERIFY rule (analyze/rules.py) — a corrupted planner (fault site
``compile:bucket``) is caught there, not on user data.

Cost model: the pad/slice wrappers run as *eager* JAX ops, and XLA
specializes those on operand shapes too — the first time a novel exact
extent ``n`` is seen, the pad kernel itself pays one small constant
compile (~tens of ms), cached by JAX thereafter.  What bucketing
dedupes is the *program* executable, whose compile cost grows with
program size and dominates in real serving graphs; the pad kernel is
O(1) and amortizes as request sizes recur.  bench.py's ``compile``
section therefore measures steady-state p95 over a recurring
request-size working set while still charging first-touch compiles to
``compile_hit_rate``.

The bucket decision is a pure function of (program structure, leaf
shapes, policy), so SPMD ranks agree by construction; per-fingerprint
decisions are recorded for the rank-coherence leg
(``scripts/two_process_suite.py --warmstart-leg``).
"""

from __future__ import annotations

import os
import threading
from typing import Optional, Sequence

import numpy as np

from ramba_tpu.core import expr as _expr
from ramba_tpu.observe import registry as _registry

# Ops whose rows are computed independently of the leading extent.
# Everything else (reductions, segmented reductions, stencils, reshapes,
# shard hints, ...) is shape-sensitive: padded rows would change group
# counts, halos, or layouts and the pad/slice wrapper would be unsound.
SAFE_OPS = frozenset({"map", "cast", "round"})

_lock = threading.Lock()
_mode: tuple = ("off",)

#: running counters, surfaced through diagnostics.perf_report()["compile"]
#: and the ramba_compile_class_* telemetry series
stats = {
    "planned": 0,        # flushes that got a bucket plan
    "padded": 0,         # plans where bucket > exact N (pad actually applied)
    "bailouts": 0,       # unsafe/unbucketable programs (exact-shape fallback)
    "pad_bytes": 0,      # total bytes of zero padding materialized
    "leaf_bytes": 0,     # total leaf bytes of planned flushes (waste denom)
}

# fingerprint -> class token, bounded; the rank-coherence leg compares
# this map across SPMD ranks (decisions are pure, so they must match)
_decisions: dict = {}
_DECISIONS_MAX = 4096


def _parse(value: str) -> tuple:
    v = (value or "").strip().lower()
    if not v or v in ("0", "off", "false", "no", "none"):
        return ("off",)
    if v in ("1", "pow2", "on", "true"):
        return ("pow2",)
    if v.startswith("linear:"):
        try:
            step = int(v.split(":", 1)[1])
        except ValueError:
            step = 0
        if step >= 1:
            return ("linear", step)
    # unknown policy string: fail safe (exact shapes), don't crash a flush
    return ("off",)


def reconfigure() -> None:
    """Re-read ``RAMBA_COMPILE_CLASSES`` (tests toggle the env var)."""
    global _mode
    _mode = _parse(os.environ.get("RAMBA_COMPILE_CLASSES", ""))


def enabled() -> bool:
    return _mode[0] != "off"


def mode() -> tuple:
    return _mode


def bucket_for(n: int, policy: Optional[tuple] = None) -> int:
    """The bucket (padded leading extent) for an exact extent ``n``."""
    p = policy or _mode
    if n <= 0:
        return n
    if p[0] == "pow2":
        b = 1
        while b < n:
            b <<= 1
        return b
    if p[0] == "linear":
        step = p[1]
        return ((n + step - 1) // step) * step
    return n


class ClassPlan:
    """One flush's bucket decision.

    ``token`` joins the fuser cache key (distinct fingerprint per
    bucket); ``pad_slots`` are the leaf slots padded along axis 0 from
    ``n`` to ``bucket``; ``pad_waste_bytes`` is charged to the span and
    the ledger.
    """

    __slots__ = ("token", "n", "bucket", "pad_slots", "pad_waste_bytes")

    def __init__(self, token, n, bucket, pad_slots, pad_waste_bytes):
        self.token = token
        self.n = n
        self.bucket = bucket
        self.pad_slots = pad_slots
        self.pad_waste_bytes = pad_waste_bytes

    def __repr__(self):  # pragma: no cover - debug aid
        return (f"ClassPlan({self.token!r}, n={self.n}, "
                f"bucket={self.bucket}, pads={len(self.pad_slots)})")


def check_program(program) -> Optional[str]:
    """Reason the program is NOT bucketable, or None when every
    instruction is leading-dim independent.  Shared by the planner and
    the ``compile-class`` verify rule so the rule re-derives exactly the
    property the planner claimed."""
    for op, _static, _slots in program.instrs:
        if op not in SAFE_OPS:
            return f"shape-sensitive instr {op!r}"
    return None


def leaf_avals(leaf_vals: Sequence) -> Optional[list]:
    """Conservative (shape, dtype) avals for leaf runtime values; None
    when a leaf defies classification."""
    import jax

    out = []
    for v in leaf_vals:
        try:
            shape = tuple(getattr(v, "shape", None) or ())
            dtype = getattr(v, "dtype", None)
            if dtype is None:
                dtype = np.asarray(v).dtype
            out.append(jax.ShapeDtypeStruct(shape, np.dtype(dtype)))
        except Exception:
            return None
    return out


def slot_avals(program, lavals: Sequence) -> Optional[list]:
    """Chain ``expr.infer_aval`` over the program; None on any inference
    failure (bail to exact shapes rather than guess)."""
    avals = list(lavals)
    for op, static, argslots in program.instrs:
        try:
            avals.append(_expr.infer_aval(op, static,
                                          [avals[s] for s in argslots]))
        except Exception:
            return None
    return avals


def shape_plan(program, lavals: Sequence,
               policy: Optional[tuple] = None) -> Optional[ClassPlan]:
    """The shape half of the safety argument: every output (and every
    full-rank leaf) must share one leading extent N, lower-rank leaves
    must never broadcast onto axis 0 (right-aligned numpy broadcasting
    guarantees this for rank < rank_max).  Returns the plan or None.

    Deliberately does NOT check op safety — the fault site
    ``compile:bucket`` uses this directly to forge an unsafe claim that
    the verify rule must catch."""
    policy = policy or _mode
    avals = slot_avals(program, lavals)
    if avals is None:
        return None
    outs = [avals[s] for s in program.out_slots]
    if not outs or any(len(a.shape) < 1 for a in outs):
        return None
    n = outs[0].shape[0]
    if n < 1 or any(a.shape[0] != n for a in outs):
        return None
    ndim_max = max(len(a.shape) for a in avals)
    if any(len(a.shape) != ndim_max for a in outs):
        return None
    for a in avals:
        if len(a.shape) == ndim_max and a.shape[0] not in (n, 1):
            return None
    bucket = bucket_for(n, policy)
    pad_slots = tuple(
        i for i, a in enumerate(avals[: program.n_leaves])
        if len(a.shape) == ndim_max and a.shape[0] == n
    )
    waste = 0
    if bucket > n:
        for s in pad_slots:
            a = avals[s]
            row = int(np.prod(a.shape[1:], dtype=np.int64)) if len(
                a.shape) > 1 else 1
            waste += (bucket - n) * row * np.dtype(a.dtype).itemsize
    token = (policy[0] if policy[0] != "linear"
             else f"linear:{policy[1]}", bucket)
    return ClassPlan(token, n, bucket, pad_slots, waste)


def plan_for(program, leaf_vals) -> Optional[ClassPlan]:
    """Bucket decision for one flush, or None for an exact-shape
    compile.  Unsafe/unbucketable programs count
    ``compile.bucket_bailout``."""
    if _mode[0] == "off" or not program.instrs:
        return None
    if check_program(program) is not None:
        _bailout()
        return None
    lavals = leaf_avals(leaf_vals)
    if lavals is None:
        _bailout()
        return None
    plan = shape_plan(program, lavals)
    if plan is None:
        _bailout()
        return None
    with _lock:
        stats["planned"] += 1
        if plan.bucket > plan.n:
            stats["padded"] += 1
        stats["pad_bytes"] += plan.pad_waste_bytes
        stats["leaf_bytes"] += sum(
            int(np.prod(a.shape, dtype=np.int64)) * np.dtype(a.dtype).itemsize
            for a in lavals if a.shape
        )
    return plan


def forced_plan(program, leaf_vals) -> Optional[ClassPlan]:
    """Fault-injection hook (``compile:bucket``): a plan that skips the
    op-safety proof, i.e. a corrupted planner claiming an unsafe program
    is bucketable.  The ``compile-class`` verify rule must catch it."""
    if _mode[0] == "off":
        return None
    lavals = leaf_avals(leaf_vals)
    if lavals is None:
        return None
    return shape_plan(program, lavals)


def _bailout() -> None:
    with _lock:
        stats["bailouts"] += 1
    _registry.inc("compile.bucket_bailout")


def apply(plan: ClassPlan, leaf_vals: Sequence) -> list:
    """Zero-pad the planned leaf slots from ``n`` to ``bucket`` along
    axis 0.  Runs eagerly (outside jit): padded copies are fresh
    temporaries, so donating them downstream is always safe."""
    out = list(leaf_vals)
    if plan.bucket <= plan.n:
        return out
    import jax.numpy as jnp

    pad = plan.bucket - plan.n
    # the pad runs eagerly; under multi-process SPMD every rank pads
    # identically, so the op is SPMD-consistent by construction
    for s in plan.pad_slots:
        v = out[s]
        widths = [(0, pad)] + [(0, 0)] * (getattr(v, "ndim", 1) - 1)
        out[s] = jnp.pad(v, widths)
    return out


def strip(plan: ClassPlan, outs: Sequence) -> tuple:
    """Slice bucket-shaped outputs back to the exact request extent.
    Rows 0..n-1 of an elementwise program are byte-identical to the
    exact-shape execution (each row depends only on its own row of the
    full-rank operands), so the result is exact, not approximate."""
    if plan.bucket <= plan.n:
        return tuple(outs)
    return tuple(o[: plan.n] for o in outs)


def note_decision(fingerprint: str, plan: Optional[ClassPlan]) -> None:
    """Record the per-fingerprint class decision (rank-coherence leg)."""
    token = plan.token if plan is not None else None
    with _lock:
        if len(_decisions) >= _DECISIONS_MAX and fingerprint not in _decisions:
            return
        _decisions[fingerprint] = token


def decisions() -> dict:
    """fingerprint -> class token map (None = exact shape)."""
    with _lock:
        return dict(_decisions)


def snapshot() -> dict:
    with _lock:
        d = dict(stats)
    d["mode"] = (":".join(str(p) for p in _mode)
                 if _mode[0] == "linear" else _mode[0])
    lb = d.pop("leaf_bytes")
    d["pad_waste_frac"] = (d["pad_bytes"] / lb) if lb else 0.0
    return d


def reset() -> None:
    with _lock:
        for k in stats:
            stats[k] = 0
        _decisions.clear()
    reconfigure()


reconfigure()
