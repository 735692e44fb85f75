"""Pallas lowering backend for hot fused kernel shapes (`ramba-pallas`).

The fuser's default lowering hands every linearized program to one
``jax.jit`` and lets XLA fuse it.  This module is the *second* lowering:
hand-tiled Pallas kernels for the program shapes the cost ledger shows are
hot — chosen per kernel fingerprint by ``core/autotune.py``, never by the
user.  Three kernel families:

* **elemred** — fused elementwise(+cast/round) chains optionally ending in
  full reductions (``sum``/``prod``/``min``/``max``/``mean`` over the whole
  array).  The 1-D operands are viewed as ``(rows, 128)`` lanes and a 1-D
  grid walks row blocks; elementwise outputs stream block-by-block while
  reduction outputs accumulate **on chip** across sequential grid steps
  (TPU grids execute in order on a core, so a constant-index output block
  is a legal accumulator).
* **segred** — masked segment reductions (``sum``/``prod``/``min``/``max``/
  ``count`` over 1-D data, at most 64 groups; work groups x data), the
  form ``groupby.py`` had before its sorted chunked walk and reachable
  only through the autotune race (ROADMAP D6): per grid step
  the kernel unrolls the (small, static) group count, reduces each group's
  masked lanes, and accumulates ``(num_groups, 128)`` lane partials on
  chip; the cross-lane combine happens outside the kernel.
* **stencil** — the existing ``ops/stencil_pallas.py`` kernel, registered
  here as a named family instead of being an ad-hoc entry point inside
  ``skeletons._eval_stencil``.

Every lowering takes ``interpret=True`` automatically when no TPU backend
is present, so the CPU tier-1 suite executes and parity-checks the very
same kernels.  Parity discipline: the builders replicate the fuser's exact
dtype semantics (including the NEP-50 input casting ``expr._op_map``
applies under x64) by abstractly evaluating the *real* op table with
``jax.eval_shape`` and baking the observed per-instruction dtypes into the
kernel as explicit casts — so elementwise results are byte-identical to
the XLA lowering, and reductions are byte-identical whenever the
reduction itself is order-independent or exact (min/max always; sums and
products of exactly-representable values).
"""

from __future__ import annotations

import threading
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ramba_tpu.core.expr import MAPFN, OPS, _np_loop_dtypes
from ramba_tpu.observe import registry as _registry
from ramba_tpu.resilience import faults as _faults

BACKEND_XLA = "xla"
BACKEND_PALLAS = "pallas"
BACKENDS = (BACKEND_XLA, BACKEND_PALLAS)


# ---------------------------------------------------------------------------
# kernel-family registry
# ---------------------------------------------------------------------------


class KernelFamily:
    """One named Pallas kernel family: an ``available(...)`` eligibility
    predicate and a ``run(...)`` entry point (family-specific signature)."""

    __slots__ = ("name", "available", "run")

    def __init__(self, name: str, available: Callable, run: Callable):
        self.name = name
        self.available = available
        self.run = run


_families: "dict[str, KernelFamily]" = {}
_families_lock = threading.Lock()
_builtins_loaded = False


def register_family(name: str, *, available: Callable, run: Callable) -> None:
    with _families_lock:
        _families[name] = KernelFamily(name, available, run)


def _ensure_builtins() -> None:
    """Import the modules that self-register built-in families (lazy so
    this module stays import-cycle-free)."""
    global _builtins_loaded
    if _builtins_loaded:
        return
    _builtins_loaded = True
    from ramba_tpu.ops import stencil_pallas  # noqa: F401  (registers "stencil")


def family(name: str) -> Optional[KernelFamily]:
    _ensure_builtins()
    with _families_lock:
        return _families.get(name)


def family_names() -> list:
    _ensure_builtins()
    with _families_lock:
        return sorted(_families)


def interpret_mode() -> bool:
    """Pallas kernels interpret (and therefore run anywhere, including the
    CPU tier-1 suite) whenever jax's default backend is not ``"tpu"`` —
    which is what a v5e reports (``device_kind`` "TPU v5 lite").  Every
    lowering that consults this records its choice
    (``registry.note_kernel``), so a run on the chip can assert that
    nothing interpreted."""
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# program classification
# ---------------------------------------------------------------------------

# Homogeneous-dtype ufuncs the elemred kernel may evaluate per block.  The
# cast plan assumes every input leg shares one computation dtype, which
# rules out heterogeneous ufuncs (ldexp, shifts, gcd, heaviside).
_ELEM_OK = frozenset({
    "add", "subtract", "multiply", "true_divide", "divide", "floor_divide",
    "mod", "remainder", "power", "maximum", "minimum", "fmax", "fmin",
    "arctan2", "hypot", "copysign", "logaddexp", "logaddexp2",
    "equal", "not_equal", "less", "less_equal", "greater", "greater_equal",
    "logical_and", "logical_or", "logical_xor",
    "negative", "positive", "absolute", "abs", "fabs", "sqrt", "square",
    "reciprocal", "sign", "exp", "exp2", "expm1", "log", "log2", "log10",
    "log1p", "sin", "cos", "tan", "sinh", "cosh", "tanh",
    "arcsin", "arccos", "arctan", "arcsinh", "arccosh", "arctanh",
    "floor", "ceil", "trunc", "rint",
    "isnan", "isinf", "isfinite", "logical_not", "where",
})

_RED_OK = frozenset({"sum", "prod", "min", "max", "mean"})
_SEG_OK = frozenset({"sum", "prod", "min", "max", "count"})

# TPU-compilable element dtypes (interpret mode accepts anything jnp does)
_TPU_DTYPES = frozenset({"float32", "bfloat16", "int32", "bool"})

_MAX_ELEM_INSTRS = 64
_MAX_SEG_GROUPS = 64
LANES = 128


def _leaf_shape(v) -> tuple:
    return tuple(getattr(v, "shape", ()) or ())


def _vector_length(leaf_vals) -> Optional[int]:
    """Common 1-D length of the array leaves (lane-aligned), or None when
    the leaf set doesn't fit the blocked-1-D kernel families."""
    n = None
    for v in leaf_vals:
        shp = _leaf_shape(v)
        if shp == ():
            continue
        if len(shp) != 1:
            return None
        if n is None:
            n = int(shp[0])
        elif int(shp[0]) != n:
            return None
    if n is None or n < LANES or n % LANES:
        return None
    return n


def _dtypes_tpu_ok(leaf_vals) -> bool:
    if interpret_mode():
        return True
    for v in leaf_vals:
        dt = getattr(v, "dtype", None)
        if dt is not None and str(np.dtype(dt)) not in _TPU_DTYPES:
            return False
    return True


def classify(program, leaf_vals) -> Optional[str]:
    """Kernel family this fused program lowers to (``"elemred"`` /
    ``"segred"``), or None when only the XLA lowering applies."""
    instrs = program.instrs
    if not instrs or len(leaf_vals) != program.n_leaves:
        return None
    if _vector_length(leaf_vals) is None:
        return None
    if not _dtypes_tpu_ok(leaf_vals):
        return None

    if len(instrs) == 1 and instrs[0][0] == "segment_reduce":
        kind, num_groups, dim = instrs[0][1]
        s_data, s_labels = (instrs[0][2] + (None, None))[:2]
        if (
            kind in _SEG_OK
            and dim == 0
            and s_labels is not None
            and 1 <= int(num_groups) <= _MAX_SEG_GROUPS
            and s_data < program.n_leaves and s_labels < program.n_leaves
            and len(_leaf_shape(leaf_vals[s_data])) == 1
            and len(_leaf_shape(leaf_vals[s_labels])) == 1
            and np.dtype(getattr(leaf_vals[s_labels], "dtype",
                                 np.int32)).kind in "iu"
        ):
            return "segred"
        return None

    if len(instrs) > _MAX_ELEM_INSTRS:
        return None
    n_leaves = program.n_leaves
    is_vec = [len(_leaf_shape(v)) == 1 for v in leaf_vals]
    reduce_slots = set()
    any_vec_instr = False
    for i, (op, static, argslots) in enumerate(instrs):
        slot = n_leaves + i
        if any(s in reduce_slots for s in argslots):
            return None  # reduce results must not feed later instructions
        if op == "map":
            (fname,) = static
            if fname not in _ELEM_OK or fname not in MAPFN:
                return None
            is_vec.append(any(is_vec[s] for s in argslots))
        elif op == "cast":
            is_vec.append(is_vec[argslots[0]])
        elif op == "round":
            is_vec.append(is_vec[argslots[0]])
        elif op == "reduce":
            fname, axis, keepdims, _ddof = static
            if fname not in _RED_OK or axis is not None or keepdims:
                return None
            if not is_vec[argslots[0]]:
                return None
            reduce_slots.add(slot)
            is_vec.append(False)
            any_vec_instr = True
        else:
            return None
        if op in ("map", "cast", "round") and is_vec[-1]:
            any_vec_instr = True
    if not any_vec_instr:
        return None
    for s in program.out_slots:
        if s >= n_leaves and not is_vec[s] and s not in reduce_slots:
            return None  # scalar compute outputs stay on the XLA lowering
    return "elemred"


def supports(program, leaf_vals) -> bool:
    try:
        return classify(program, leaf_vals) is not None
    except Exception:
        return False


# ---------------------------------------------------------------------------
# shared lowering helpers
# ---------------------------------------------------------------------------


def _block_rows(rows: int) -> int:
    """Largest 8-aligned divisor of ``rows`` up to 256 — an exact block
    height, so no grid step ever sees a partial block and no tail masking
    is needed.  Falls back to the whole array (grid of 1)."""
    for cand in (256, 128, 64, 32, 16, 8):
        if rows % cand == 0:
            return cand
    return rows


def _all_slot_avals(program, leaf_vals):
    """Abstract per-slot avals (dtype + weak_type) of every leaf and every
    intermediate, produced by the REAL op table — the parity oracle the
    kernel's cast plan is derived from."""
    instrs = program.instrs

    def every_slot(*vals):
        out = list(vals)
        for op, static, argslots in instrs:
            out.append(OPS[op](static, *(out[s] for s in argslots)))
        return tuple(out)

    return jax.eval_shape(every_slot, *leaf_vals)


def _weak_promoted_dtype(avals):
    """Computation dtype for one homogeneous ufunc application, honoring
    NEP-50 weak typing: weak operands participate as python scalars."""
    args = []
    for a in avals:
        if getattr(a, "weak_type", False):
            kind = np.dtype(a.dtype).kind
            args.append({"b": False, "i": 0, "u": 0,
                         "f": 0.0, "c": 0j}.get(kind, a.dtype))
        else:
            args.append(a.dtype)
    return jnp.result_type(*args)


def _map_cast_plan(fname, arg_avals, out_aval):
    """(per-arg cast dtypes | None, output dtype) reproducing
    ``expr._op_map``'s semantics with strong-typed kernel refs: the exact
    NEP-50 loop dtypes when numpy promotion is being enforced (x64), the
    weak-honoring promoted dtype otherwise."""
    if fname == "where":
        loop = _np_loop_dtypes("add", arg_avals[1:]) \
            if jax.config.jax_enable_x64 else None
        val_dt = loop[-1] if loop is not None \
            else _weak_promoted_dtype(arg_avals[1:])
        return (None, val_dt, val_dt), np.dtype(out_aval.dtype)
    loop = _np_loop_dtypes(fname, arg_avals)
    if loop is not None:
        return tuple(np.dtype(d) for d in loop[:-1]), np.dtype(loop[-1])
    cd = _weak_promoted_dtype(arg_avals)
    return tuple(cd for _ in arg_avals), np.dtype(out_aval.dtype)


def _reduce_identity_np(op: str, dtype):
    """Identity element as a *numpy* scalar (safe to close over inside a
    Pallas kernel body) — mirrors ``groupby._reduce_identity``."""
    dt = np.dtype(dtype)
    if op == "sum":
        return np.zeros((), dt)[()]
    if op == "prod":
        return np.ones((), dt)[()]
    if dt == np.dtype(bool):
        return np.asarray(op == "min", dt)[()]
    if np.issubdtype(dt, np.inexact):
        return np.asarray(np.inf if op == "min" else -np.inf, dt)[()]
    info = np.iinfo(dt)
    return np.asarray(info.max if op == "min" else info.min, dt)[()]


_RED_PART = {"sum": jnp.sum, "mean": jnp.sum, "prod": jnp.prod,
             "min": jnp.min, "max": jnp.max}
_RED_COMB = {"sum": jnp.add, "mean": jnp.add, "prod": jnp.multiply,
             "min": jnp.minimum, "max": jnp.maximum}


# ---------------------------------------------------------------------------
# elemred: fused elementwise(+reduce) chains
# ---------------------------------------------------------------------------


def _build_elemred(program) -> Callable:
    instrs = program.instrs
    n_leaves = program.n_leaves
    out_slots = program.out_slots

    def run(*leaf_vals):
        from jax.experimental import pallas as pl

        avals = _all_slot_avals(program, leaf_vals)
        n = _vector_length(leaf_vals)
        rows = n // LANES
        bh = _block_rows(rows)
        grid = rows // bh
        is_vec = [len(_leaf_shape(v)) == 1 for v in leaf_vals]

        # cast plans and reduce metadata, precomputed at trace time so the
        # kernel body is pure ref arithmetic
        plans = []
        reduce_meta = {}
        for i, (op, static, argslots) in enumerate(instrs):
            slot = n_leaves + i
            if op == "map":
                plans.append(_map_cast_plan(
                    static[0], [avals[s] for s in argslots], avals[slot]))
                is_vec.append(any(is_vec[s] for s in argslots))
            elif op == "reduce":
                acc_dt = np.dtype(avals[slot].dtype)
                reduce_meta[slot] = (static[0], acc_dt)
                plans.append(None)
                is_vec.append(False)
            else:
                plans.append(None)
                is_vec.append(len(argslots) == 1 and is_vec[argslots[0]])

        vec_out = [s for s in out_slots
                   if s >= n_leaves and is_vec[s]]
        red_out = sorted(reduce_meta)
        kernel_in = [s for s in range(n_leaves)]

        def kernel(*refs):
            ins = refs[:len(kernel_in)]
            outs = refs[len(kernel_in):]
            gi = pl.program_id(0)
            vals: dict = {}
            for j, s in enumerate(kernel_in):
                vals[s] = ins[j][...] if is_vec[s] else ins[j][0, 0]
            for i, (op, static, argslots) in enumerate(instrs):
                slot = n_leaves + i
                args = [vals[s] for s in argslots]
                if op == "map":
                    casts, out_dt = plans[i]
                    (fname,) = static
                    cargs = [
                        a if d is None or getattr(a, "dtype", None) == d
                        else jnp.asarray(a).astype(d)
                        for a, d in zip(args, casts)
                    ]
                    v = MAPFN[fname](*cargs)
                    if v.dtype != out_dt:
                        v = v.astype(out_dt)
                    vals[slot] = v
                elif op == "cast":
                    vals[slot] = jnp.asarray(args[0]).astype(
                        jnp.dtype(static[0]))
                elif op == "round":
                    vals[slot] = jnp.round(args[0], static[0])
                else:  # reduce: on-chip accumulation across grid steps
                    fname, acc_dt = reduce_meta[slot]
                    x = jnp.asarray(args[0])
                    if x.dtype != acc_dt:
                        x = x.astype(acc_dt)
                    partial = _RED_PART[fname](x)
                    oref = outs[len(vec_out) + red_out.index(slot)]
                    comb = _RED_COMB[fname]

                    @pl.when(gi == 0)
                    def _init(oref=oref, partial=partial):
                        oref[0, 0] = partial

                    @pl.when(gi != 0)
                    def _accum(oref=oref, partial=partial, comb=comb):
                        oref[0, 0] = comb(oref[0, 0], partial)
                    vals[slot] = None  # never read again (classifier)
            for j, s in enumerate(vec_out):
                v = vals[s]
                want = np.dtype(avals[s].dtype)
                if v.dtype != want:
                    v = v.astype(want)
                outs[j][...] = v

        in_specs, kernel_args = [], []
        for s in kernel_in:
            if is_vec[s]:
                in_specs.append(pl.BlockSpec((bh, LANES), lambda i: (i, 0)))
                kernel_args.append(jnp.reshape(leaf_vals[s], (rows, LANES)))
            else:
                in_specs.append(pl.BlockSpec((1, 1), lambda i: (0, 0)))
                kernel_args.append(jnp.reshape(jnp.asarray(leaf_vals[s]),
                                               (1, 1)))
        out_shapes, out_specs = [], []
        for s in vec_out:
            out_shapes.append(jax.ShapeDtypeStruct(
                (rows, LANES), np.dtype(avals[s].dtype)))
            out_specs.append(pl.BlockSpec((bh, LANES), lambda i: (i, 0)))
        for s in red_out:
            out_shapes.append(jax.ShapeDtypeStruct(
                (1, 1), reduce_meta[s][1]))
            out_specs.append(pl.BlockSpec((1, 1), lambda i: (0, 0)))

        interpret = interpret_mode()
        _registry.note_kernel("elemred", "pallas", interpret)
        results = pl.pallas_call(
            kernel,
            grid=(grid,),
            out_shape=out_shapes,
            in_specs=in_specs,
            out_specs=out_specs,
            interpret=interpret,
        )(*kernel_args)
        if not isinstance(results, (list, tuple)):
            results = (results,)

        by_slot = {}
        for j, s in enumerate(vec_out):
            by_slot[s] = jnp.reshape(results[j], (n,))
        for k, s in enumerate(red_out):
            fname, acc_dt = reduce_meta[s]
            r = results[len(vec_out) + k][0, 0]
            if fname == "mean":
                r = r / n
            if r.dtype != np.dtype(avals[s].dtype):
                r = r.astype(np.dtype(avals[s].dtype))
            by_slot[s] = r
        outs = []
        for s in out_slots:
            outs.append(leaf_vals[s] if s < n_leaves else by_slot[s])
        return tuple(outs)

    return run


# ---------------------------------------------------------------------------
# segred: masked segment reductions (groupby)
# ---------------------------------------------------------------------------


def _build_segred(program) -> Callable:
    (op, static, argslots) = program.instrs[0]
    kind, num_groups, _dim = static
    s_data, s_labels = argslots
    out_slots = program.out_slots
    n_leaves = program.n_leaves

    def run(*leaf_vals):
        from jax.experimental import pallas as pl

        avals = _all_slot_avals(program, leaf_vals)
        out_aval = avals[n_leaves]
        acc_dt = np.dtype(out_aval.dtype)
        data = jnp.asarray(leaf_vals[s_data])
        labels = jnp.asarray(leaf_vals[s_labels])
        n = data.shape[0]
        rows = n // LANES
        bh = _block_rows(rows)
        grid = rows // bh
        G = int(num_groups)

        red = "sum" if kind == "count" else kind
        if kind == "count":
            # mirror _op_segment_reduce: count reduces a ones array of the
            # platform int dtype
            data = jnp.ones((n,), acc_dt)
        ident = _reduce_identity_np(red, acc_dt)
        part_fn = _RED_PART[red]
        comb_fn = _RED_COMB[red]

        def kernel(data_ref, labels_ref, out_ref):
            gi = pl.program_id(0)
            d = data_ref[...]
            if d.dtype != acc_dt:
                d = d.astype(acc_dt)
            lb = labels_ref[...]
            parts = []
            for g in range(G):  # static unroll: G is small by eligibility
                contrib = jnp.where(lb == g, d, ident)
                parts.append(part_fn(contrib, axis=0))  # (LANES,)
            block = jnp.stack(parts)  # (G, LANES) lane partials

            @pl.when(gi == 0)
            def _init():
                out_ref[...] = block

            @pl.when(gi != 0)
            def _accum():
                out_ref[...] = comb_fn(out_ref[...], block)

        interpret = interpret_mode()
        _registry.note_kernel("segred", "pallas", interpret)
        partials = pl.pallas_call(
            kernel,
            grid=(grid,),
            out_shape=jax.ShapeDtypeStruct((G, LANES), acc_dt),
            in_specs=[
                pl.BlockSpec((bh, LANES), lambda i: (i, 0)),
                pl.BlockSpec((bh, LANES), lambda i: (i, 0)),
            ],
            out_specs=pl.BlockSpec((G, LANES), lambda i: (0, 0)),
            interpret=interpret,
        )(jnp.reshape(data, (rows, LANES)),
          jnp.reshape(labels, (rows, LANES)))

        out = part_fn(partials, axis=1)  # cross-lane combine
        if out.dtype != acc_dt:
            out = out.astype(acc_dt)
        by_slot = {n_leaves: out}
        return tuple(leaf_vals[s] if s < n_leaves else by_slot[s]
                     for s in out_slots)

    return run


# ---------------------------------------------------------------------------
# entry point: program -> pallas callable
# ---------------------------------------------------------------------------


def lower_program(program, leaf_vals) -> Optional[Callable]:
    """Pallas lowering of a fused program, or None when no kernel family
    matches.  The returned callable has the exact signature and output
    pytree of ``fuser._build_callable(program)`` so the fuser can wrap it
    in ``jax.jit`` (with donation) unchanged.  Raises on lowering-level
    failures (including injected ``RAMBA_FAULTS=pallas:...`` faults) —
    the caller is responsible for degrading to the XLA backend."""
    fam = classify(program, leaf_vals)
    if fam is None:
        return None
    _faults.check("pallas", family=fam, instrs=len(program.instrs))
    if fam == "elemred":
        return _build_elemred(program)
    return _build_segred(program)
