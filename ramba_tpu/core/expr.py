"""Lazy expression graph.

TPU-native replacement for the reference's lazy DAG + deferred-op fuser
(/root/reference/ramba/ramba.py:4387-5130 ``DAG`` and :8039-8532
``deferred_op``).  The reference accumulates op *strings* and compiles the
concatenation with Numba on every worker; here we accumulate structured
expression nodes and flush them as ONE traced/jitted function over sharded
``jax.Array``s (see core/fuser.py).  XLA performs the loop fusion the
reference's ``deferred_op.execute`` does by hand (ramba.py:8140-8255), and
GSPMD inserts the cross-shard communication the reference routes through its
queue transports.

Every node is immutable.  Evaluation semantics live in the ``OPS`` table —
plain Python functions over jax values; no source-string codegen, no eval().
"""

from __future__ import annotations

import time
import types
import weakref
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ramba_tpu.observe import profile as _profile
from ramba_tpu.observe import registry as _registry

# ---------------------------------------------------------------------------
# Nodes
# ---------------------------------------------------------------------------

# A script builds thousands of nodes a flush, so what is counted per node
# takes no lock: plain integers here (one thread builds a stream's DAG; an
# increment lost under contention is a measurement's, not a result's) that
# the registry folds in when it is read (``registry.add_source``).
# ``dag.node.n`` nodes, ``dag.node.ns`` the time in their constructor,
# which is ``infer_aval`` whole; ``dag.infer.hit`` the inferences served
# from a memo or from ``Scalar``'s table.  The time adds up in float
# seconds: two float operations a node cost less than two on integers
# past 2**30.
_node_n = _infer_hit = 0
_node_s = 0.0
_now = time.perf_counter

_registry.add_source(lambda: {"dag.node.n": _node_n,
                              "dag.node.ns": int(_node_s * 1e9),
                              "dag.infer.hit": _infer_hit})


class Expr:
    """Base class. ``aval`` is a jax.ShapeDtypeStruct-like with shape/dtype."""

    __slots__ = ("aval", "__weakref__")

    @property
    def shape(self):
        return self.aval.shape

    @property
    def dtype(self):
        return self.aval.dtype


class Const(Expr):
    """Leaf holding a concrete (usually sharded) jax.Array."""

    __slots__ = ("value",)

    def __init__(self, value):
        self.value = value
        self.aval = jax.typeof(value)


class Scalar(Expr):
    """Leaf holding a python scalar.

    An argument of the jitted flush, weakly typed where the number is, so
    that changing the *value* of a scalar does not invalidate the compile
    cache — the analog of the reference pickling op operands separately from
    the generated source whose name is a hash of the code only
    (ramba.py:8260-8265,8286-8298).  The leaf keeps the number; what the
    compiled call receives is a device array of this leaf's aval, committed
    and replicated over the mesh, from the fuser's table of the values seen
    (``fuser._resident_scalars``): a value met again crosses to the device
    no second time, and jit places no number itself.  Only a value this
    class does not table (below) is still handed over as a number.

    The aval is ``jax.eval_shape(lambda: jnp.asarray(value))``.  For the
    Python and NumPy number types it depends on ``type(value)`` and the
    semantic fingerprint only, so it is abstractly evaluated on first sight
    of a type (a miss, counted under ``dag.infer``) and read from a table
    afterwards (``dag.infer.hit``): identical by construction, ``weak_type``
    included.  A Python ``int`` that does not fit the default integer width
    raises from ``jnp.asarray`` by value, so it is never tabled and goes on
    taking the evaluation; so does a value of any other type.
    """

    __slots__ = ("value",)

    def __init__(self, value):
        global _infer_hit
        self.value = value
        key = _scalar_key(value)
        aval = _scalar_avals.get(key) if key is not None else None
        if aval is None:
            with _profile.span("dag.infer"):
                aval = jax.eval_shape(lambda: jnp.asarray(value))
            if key is not None:
                _scalar_avals[key] = aval
        else:
            _infer_hit += 1
        self.aval = aval


class Node(Expr):
    """Interior node: ``OPS[op](static, *args)``."""

    __slots__ = ("op", "static", "args")

    def __init__(self, op: str, static: tuple, args: Sequence[Expr], aval=None):
        global _node_n, _node_s
        t0 = _now()
        self.op = op
        self.static = static
        self.args = tuple(args)
        if aval is None:
            aval = infer_aval(op, static, [a.aval for a in self.args])
        self.aval = aval
        _node_n += 1
        _node_s += _now() - t0


def as_expr(x: Any) -> Expr:
    if isinstance(x, Expr):
        return x
    if isinstance(x, (bool, int, float, complex, np.bool_, np.integer, np.floating)):
        return Scalar(x)
    if isinstance(x, (np.ndarray, jax.Array)):
        return Const(jnp.asarray(x))
    raise TypeError(f"cannot lift {type(x)} into an expression")


def semantic_fingerprint() -> tuple:
    """Trace-time global configuration the OPS eval rules consult.  Anything
    an eval rule reads while being traced MUST appear here: a program's key
    and an inference memo key capture structure only, so two programs with
    identical structure but different trace-time semantics — e.g. NEP-50
    promotion in ``_np_loop_dtypes``, which keys off ``jax_enable_x64`` —
    would otherwise share one compiled executable, or one inferred aval, and
    silently reuse the wrong numerics (the collision the analyze
    graph-hygiene rule detects)."""
    return (bool(jax.config.jax_enable_x64),)


# (type, semantic fingerprint) -> aval of a lifted scalar of that type
_scalar_avals: dict = {}


def _scalar_key(value):
    """Key of ``Scalar``'s aval table, or None where the aval (or the
    error) depends on more than the value's type."""
    t = type(value)
    if t is int:
        # beyond the default integer width jnp.asarray raises, by value
        half = 1 << (63 if jax.config.jax_enable_x64 else 31)
        if not -half <= value < half:
            return None
    elif not (t is float or t is bool or t is complex
              or issubclass(t, (np.number, np.bool_))):
        return None
    return t, semantic_fingerprint()


# Value-keyed entries: (op, static, arg avals, fingerprint) -> aval.
_aval_memo: dict = {}
# Function-keyed entries: first function of the static (weakly) ->
# {(op, static with a weak reference in each function's place, arg avals,
# fingerprint): aval}.  An inner key holds no function, so an entry dies
# with its first function; a later function at a recycled address is
# another key (a dead reference equals only itself).
_fn_aval_memo: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()
_MEMO_MAX = 8192

_MEMO_SAFE_TYPES = (str, bytes, int, float, complex, bool, type(None),
                    np.dtype, np.generic)


class _NoKey(Exception):
    """The static holds an object the memo may neither key on nor keep."""


def _static_key(x, funcs: list):
    """``x`` as a memo key component: value-hashable members as they are,
    each Python function replaced by a weak reference to it and appended
    to ``funcs``.  Raises ``_NoKey`` for anything else: an identity-hashed
    object (a ``_Lit``, a callable instance) can never hit and would be
    pinned by its own key."""
    if isinstance(x, _MEMO_SAFE_TYPES):
        return x
    if isinstance(x, tuple):
        return tuple([_static_key(e, funcs) for e in x])
    if type(x) is types.FunctionType:
        funcs.append(x)
        return weakref.ref(x)
    if isinstance(x, frozenset) and all(
            isinstance(e, _MEMO_SAFE_TYPES) for e in x):
        return x
    raise _NoKey


def infer_aval(op: str, static: tuple, arg_avals: Sequence[Any]) -> Any:
    """Shape/dtype inference by abstract evaluation of the op's own eval rule —
    guarantees inference always matches execution (the reference instead
    duplicates shape/dtype logic in every ``DAGshape``-returning API function,
    ramba.py:5133-5165).  Memoized: eval_shape costs ~1 ms for a map and the
    whole kernel's trace (Pallas lowering included) for a stencil, which
    would otherwise dominate graph-build time.

    Keyed on the op, the static, the argument avals and
    ``semantic_fingerprint()``.  A Python function in the static (a skeleton's
    kernel, at any depth of its tuples) is keyed by identity and held weakly:
    the same function object over the same avals is inferred once, a fresh
    closure per call misses as before, and the memo never extends a
    function's life.  Any other identity-hashed member (``_Lit`` literals,
    callable objects) leaves the node without a key: it is inferred every
    time and nothing of it is retained.

    Assumed of a function: rebinding its closure cells or globals does not
    change the *shape or dtype* of what it returns.  One that does is served
    the old aval, exactly as the compile cache already serves it the old
    executable (``program.key`` hashes the function by identity too).

    A hit counts ``dag.infer.hit``; a miss ``dag.infer.n`` and its time
    ``dag.infer.ns``.  Hit or miss, the caller's ``dag.node.ns`` holds the
    whole of it: the key, the lookup and the evaluation."""
    global _infer_hit
    fn = OPS[op]
    funcs: list = []
    try:
        key = (op, _static_key(static, funcs), tuple(
            (tuple(a.shape), a.dtype, bool(getattr(a, "weak_type", False)))
            for a in arg_avals
        ), semantic_fingerprint())
        memo = _fn_aval_memo.setdefault(funcs[0], {}) if funcs else _aval_memo
        hit = memo.get(key)
    except (TypeError, _NoKey):  # TypeError: a member that does not hash
        key = None
    else:
        if hit is not None:
            _infer_hit += 1
            return hit
    with _profile.span("dag.infer"):
        out = jax.eval_shape(lambda *a: fn(static, *a), *arg_avals)
    if key is not None:
        if len(memo) >= _MEMO_MAX:
            memo.clear()
        memo[key] = out
    return out


# ---------------------------------------------------------------------------
# Op evaluation table
# ---------------------------------------------------------------------------

OPS: dict[str, Callable] = {}


def defop(name: str) -> Callable[[Callable], Callable]:
    def deco(fn: Callable) -> Callable:
        OPS[name] = fn
        return fn

    return deco


# -- elementwise maps --------------------------------------------------------

UNARY = {
    name: getattr(jnp, name)
    for name in [
        "negative", "positive", "absolute", "abs", "sqrt", "square", "cbrt",
        "reciprocal", "sign", "exp", "exp2", "expm1", "log", "log2", "log10",
        "log1p", "sin", "cos", "tan", "arcsin", "arccos", "arctan", "sinh",
        "cosh", "tanh", "arcsinh", "arccosh", "arctanh", "floor", "ceil",
        "trunc", "rint", "isnan", "isinf", "isfinite", "logical_not", "invert",
        "conj", "conjugate", "real", "imag", "degrees", "radians", "deg2rad",
        "rad2deg", "signbit", "spacing", "fabs", "sinc", "i0", "angle",
    ]
    if hasattr(jnp, name)
}

BINARY = {
    name: getattr(jnp, name)
    for name in [
        "add", "subtract", "multiply", "true_divide", "divide", "floor_divide",
        "mod", "remainder", "fmod", "power", "float_power", "arctan2", "hypot",
        "maximum", "minimum", "fmax", "fmin", "logaddexp", "logaddexp2",
        "logical_and", "logical_or", "logical_xor", "bitwise_and", "bitwise_or",
        "bitwise_xor", "left_shift", "right_shift", "equal", "not_equal",
        "less", "less_equal", "greater", "greater_equal", "copysign",
        "nextafter", "heaviside", "gcd", "lcm", "ldexp",
    ]
    if hasattr(jnp, name)
}

MAPFN: dict[str, Callable] = {}
MAPFN.update(UNARY)
MAPFN.update(BINARY)
MAPFN["where"] = jnp.where
MAPFN["matmul_elem"] = jnp.multiply  # placeholder slot


def _np_loop_dtypes(fname, args):
    """NumPy's exact (input..., output) dtypes for this ufunc application
    under NEP 50 — weak-typed jax values stand in as python scalars.
    Returns None when numpy promotion should not be enforced: x64 disabled
    (32-bit TPU execution keeps jax's own lattice — widening everything to
    f64 there would be both slow and silently truncated anyway), fname not
    a numpy ufunc, or unresolvable."""
    import jax as _jax

    if not _jax.config.jax_enable_x64:
        return None
    uf = getattr(np, fname, None)
    if not isinstance(uf, np.ufunc) or uf.nin != len(args) or uf.nout != 1:
        return None
    ins = []
    for a in args:
        dt = getattr(a, "dtype", None)
        if dt is None:
            if isinstance(a, (bool, int, float, complex)):
                ins.append(type(a))
                continue
            return None
        if getattr(a, "weak_type", False):
            kind = np.dtype(dt).kind
            ins.append({"b": bool, "i": int, "u": int, "f": float,
                        "c": complex}.get(kind, np.dtype(dt)))
        else:
            ins.append(np.dtype(dt))
    try:
        return uf.resolve_dtypes(tuple(ins) + (None,))
    except Exception:
        return None


@defop("map")
def _op_map(static, *args):
    (fname,) = static
    if fname == "where" and len(args) == 3 and jax.config.jax_enable_x64:
        # np.where is not a ufunc; its value operands take the numpy
        # common dtype (NEP 50)
        want = _np_loop_dtypes("add", args[1:])
        if want is not None:
            a2 = args[1] if getattr(args[1], "dtype", None) == want[-1] \
                else jnp.asarray(args[1], want[-1])
            a3 = args[2] if getattr(args[2], "dtype", None) == want[-1] \
                else jnp.asarray(args[2], want[-1])
            return jnp.where(args[0], a2, a3)
    loop = _np_loop_dtypes(fname, args)
    if loop is not None:
        # cast INPUTS to numpy's loop dtypes (computing in the wider type,
        # not just relabeling the result) — the reference computes with
        # numpy/Numba and so gets these semantics for free
        args = tuple(
            a if getattr(a, "dtype", None) == d
            and not getattr(a, "weak_type", True)
            else jnp.asarray(a, d)
            for a, d in zip(args, loop[:-1])
        )
        out = MAPFN[fname](*args)
        if out.dtype != loop[-1]:
            out = out.astype(loop[-1])
        return out
    return MAPFN[fname](*args)


def make_map(fname: str, operands: Sequence[Expr]) -> Expr:
    """Build an elementwise map node, strength-reducing ``power`` by a small
    static integer exponent into a multiply chain.

    Scalar operands are normally runtime arguments (to keep the compile cache
    value-independent), but a runtime exponent forces stablehlo.power — the
    exp/log path on the TPU VPU — where a literal ``x**2`` would compile to one
    multiply.  The reference has the same class of peephole in its codegen
    (division rewritten to multiply-by-reciprocal, ramba.py:6121-6126)."""
    if fname == "power" and len(operands) == 2:
        e = operands[1]
        if (
            isinstance(e, Scalar)
            and isinstance(e.value, (int, np.integer))
            and not isinstance(e.value, (bool, np.bool_))
            and 1 <= int(e.value) <= 4
            and operands[0].dtype != np.bool_  # bool ** int promotes to int8
        ):
            x = operands[0]
            out = x
            for _ in range(int(e.value) - 1):
                out = Node("map", ("multiply",), [out, x])
            return out
    return Node("map", (fname,), list(operands))


@defop("cast")
def _op_cast(static, x):
    (dtype,) = static
    return x.astype(jnp.dtype(dtype))


@defop("round")
def _op_round(static, x):
    (decimals,) = static
    return jnp.round(x, decimals)


# -- reductions --------------------------------------------------------------

REDFN = {
    name: getattr(jnp, name)
    for name in [
        "sum", "prod", "min", "max", "any", "all", "mean", "var", "std",
        "nansum", "nanprod", "nanmin", "nanmax", "nanmean", "nanvar", "nanstd",
        "argmin", "argmax", "nanargmin", "nanargmax", "count_nonzero", "median",
        "nanmedian", "ptp",
    ]
    if hasattr(jnp, name)
}


@defop("reduce")
def _op_reduce(static, x):
    fname, axis, keepdims, ddof = static
    fn = REDFN[fname]
    kwargs = {}
    if fname in ("var", "std", "nanvar", "nanstd") and ddof is not None:
        kwargs["ddof"] = ddof
    if fname in ("argmin", "argmax", "nanargmin", "nanargmax", "median", "nanmedian"):
        # no keepdims arg pre-numpy-2 signature quirks; normalize after
        r = fn(x, axis=axis)
        if keepdims and axis is not None:
            r = jnp.expand_dims(r, axis)
        elif keepdims and axis is None:
            r = jnp.reshape(r, (1,) * x.ndim)
        return r
    return fn(x, axis=axis, keepdims=keepdims, **kwargs)


@defop("reduce_where")
def _op_reduce_where(static, x, mask):
    """Masked reduction — the reference's maskarray path forces guarded
    reduction kernels (ramba.py:5908-5911,8476-8478)."""
    fname, axis, keepdims = static
    fn = REDFN[fname]
    if fname in ("mean",):
        return jnp.sum(jnp.where(mask, x, 0), axis=axis, keepdims=keepdims) / jnp.sum(
            mask, axis=axis, keepdims=keepdims
        )
    identities = {"sum": 0, "prod": 1, "any": False, "all": True}
    if fname in ("min", "max"):
        if x.dtype == jnp.dtype(bool):
            ident = fname == "min"  # min identity=True, max identity=False
        elif jnp.issubdtype(x.dtype, jnp.floating):
            ident = jnp.finfo(x.dtype).max if fname == "min" else jnp.finfo(x.dtype).min
        else:
            ident = jnp.iinfo(x.dtype).max if fname == "min" else jnp.iinfo(x.dtype).min
    else:
        ident = identities[fname]
    return fn(jnp.where(mask, x, ident), axis=axis, keepdims=keepdims)


@defop("cumulative")
def _op_cumulative(static, x):
    fname, axis = static
    # numpy promotes sub-word integer scans to the platform int (int64
    # under x64), same as sum/prod; jnp keeps the input dtype
    kind = jnp.dtype(x.dtype).kind
    if jax.config.jax_enable_x64 and kind in "biu":
        want = {"b": jnp.int64, "i": jnp.int64, "u": jnp.uint64}[kind]
        if jnp.dtype(x.dtype).itemsize < 8:
            x = x.astype(want)
    return getattr(jnp, fname)(x, axis=axis)


# -- indexing / views --------------------------------------------------------


def encode_index(idx) -> tuple:
    """Canonical hashable encoding of a basic index tuple."""
    if not isinstance(idx, tuple):
        idx = (idx,)
    out = []
    for it in idx:
        if it is None:
            out.append(("n",))
        elif it is Ellipsis:
            out.append(("e",))
        elif isinstance(it, slice):
            out.append(("s", it.start, it.stop, it.step))
        elif isinstance(it, (int, np.integer)):
            out.append(("i", int(it)))
        else:
            raise TypeError(f"not a basic index: {it!r}")
    return tuple(out)


def decode_index(enc: tuple):
    out = []
    for it in enc:
        if it[0] == "n":
            out.append(None)
        elif it[0] == "e":
            out.append(Ellipsis)
        elif it[0] == "s":
            out.append(slice(it[1], it[2], it[3]))
        else:
            out.append(it[1])
    return tuple(out)


@defop("getitem")
def _op_getitem(static, x):
    from ramba_tpu.core import slicing

    (enc,) = static
    return slicing.take(x, decode_index(enc))


@defop("setitem")
def _op_setitem(static, x, v):
    from ramba_tpu.core import slicing

    (enc,) = static
    return slicing.put(x, decode_index(enc), v.astype(x.dtype))


@defop("remap_faces")
def _op_remap_faces(static, x):
    """A chain of whole-face copies ``x[.., d, ..] = x[.., s, ..]`` on one
    array (``rewrite.fold_face_copy``): per axis the ``(d, s)`` pairs
    in the script's order."""
    from ramba_tpu.core import slicing

    (maps,) = static
    return slicing.remap(x, maps)


@defop("prolong")
def _op_prolong(static, z):
    """The trilinear prolongation of ``z`` onto zeros
    (``rewrite.fold_prolong``): ``f[0::2, 0::2, 0::2] = z[:-1, :-1, :-1]``,
    then ``f[1:-1] = f[1:-1] + 0.5 * (f[2:] + f[:-2])`` along the first
    ``axes`` axes in turn."""
    from ramba_tpu.core import slicing

    axes, spec = static
    zeros = jnp.zeros(tuple(2 * n - 2 for n in z.shape), z.dtype)
    return slicing.prolong(z, axes, _constrain(zeros, spec))


@defop("getitem_adv")
def _op_getitem_adv(static, x, *indexers):
    """Fancy-index gather.  The reference builds an all2all owner-lookup gather
    machine (ramba.py:6429-6545); on TPU this is a single XLA gather and GSPMD
    owns the communication."""
    enc, arraypos = static
    idx = list(decode_index(enc))
    it = iter(indexers)
    for p in arraypos:
        idx[p] = next(it)
    return x[tuple(idx)]


@defop("setitem_adv")
def _op_setitem_adv(static, x, v, *indexers):
    """Fancy-index scatter (reference: setitem_array_executor,
    ramba.py:6143-6295).  Duplicate indices follow XLA scatter semantics
    (unspecified winner), matching the reference's documented behavior
    (docs/index.md:71)."""
    enc, arraypos = static
    idx = list(decode_index(enc))
    it = iter(indexers)
    for p in arraypos:
        idx[p] = next(it)
    return x.at[tuple(idx)].set(v.astype(x.dtype))


@defop("masked_fill")
def _op_masked_fill(static, x, mask, v):
    """Boolean-mask write as a guarded select — the reference emits
    ``if mask: ...`` codelines (ramba.py:8476-8478); here it is a fused where."""
    return jnp.where(mask, v.astype(x.dtype) if hasattr(v, "astype") else v, x)


@defop("permute")
def _op_permute(static, x):
    from ramba_tpu.ops import transpose_sharded

    (axes,) = static
    return transpose_sharded.transpose(x, axes)


@defop("add_transposed")
def _op_add_transposed(static, b, a):
    """``b + a.T`` of two rank-2 arrays of one dtype
    (``rewrite.fold_add_transposed``)."""
    from ramba_tpu.ops import transpose_sharded

    return transpose_sharded.add_transposed(b, a)


@defop("after")
def _op_after(static, x, y):
    """``x``, once ``y`` exists: both through one
    ``optimization_barrier`` (``ndarray._hold_behind``)."""
    return jax.lax.optimization_barrier((x, y))[0]


@defop("reshape")
def _op_reshape(static, x):
    (shape,) = static
    return jnp.reshape(x, shape)


@defop("broadcast_to")
def _op_broadcast_to(static, x):
    (shape,) = static
    return jnp.broadcast_to(x, shape)


@defop("flip")
def _op_flip(static, x):
    (axes,) = static
    return jnp.flip(x, axes)


# -- structural --------------------------------------------------------------


def _np_common_dtype(args):
    """numpy's NEP-50 common dtype for a join of arrays, or None when jax
    promotion should stand (x64 off, or unresolvable)."""
    if not jax.config.jax_enable_x64:
        return None
    try:
        want = np.result_type(*[np.dtype(a.dtype) for a in args])
    except Exception:
        return None
    return want


@defop("concatenate")
def _op_concatenate(static, *args):
    (axis,) = static
    want = _np_common_dtype(args)
    if want is not None:
        args = [a.astype(want) if a.dtype != want else a for a in args]
    return jnp.concatenate(args, axis=axis)


@defop("stack")
def _op_stack(static, *args):
    (axis,) = static
    want = _np_common_dtype(args)
    if want is not None:
        args = [a.astype(want) if a.dtype != want else a for a in args]
    return jnp.stack(args, axis=axis)


@defop("pad")
def _op_pad(static, x, *consts):
    pad_width, mode = static
    if mode == "constant" and consts:
        return jnp.pad(x, pad_width, mode=mode, constant_values=consts[0])
    if mode == "empty":
        mode = "constant"
    return jnp.pad(x, pad_width, mode=mode)


@defop("moveaxis")
def _op_moveaxis(static, x):
    src, dst = static
    return jnp.moveaxis(x, src, dst)


@defop("repeat")
def _op_repeat(static, x):
    repeats, axis = static
    return jnp.repeat(x, repeats, axis=axis)


@defop("tile")
def _op_tile(static, x):
    (reps,) = static
    return jnp.tile(x, reps)


@defop("tril")
def _op_tril(static, x):
    (k,) = static
    return jnp.tril(x, k)


@defop("triu")
def _op_triu(static, x):
    (k,) = static
    return jnp.triu(x, k)


@defop("diag")
def _op_diag(static, x):
    (k,) = static
    return jnp.diag(x, k)


@defop("sort")
def _op_sort(static, x):
    (axis,) = static
    return jnp.sort(x, axis=axis)


@defop("argsort")
def _op_argsort(static, x):
    (axis,) = static
    return jnp.argsort(x, axis=axis)


@defop("take")
def _op_take(static, x, indices):
    (axis, mode) = static
    return jnp.take(x, indices, axis=axis, mode=mode)


# -- linear algebra ----------------------------------------------------------


@defop("matmul")
def _op_matmul(static, a, b):
    """The reference implements a 3-strategy distributed GEMM by hand
    (ramba.py:2493-3051,6993-7618); on TPU the MXU + GSPMD path is a single
    jnp.matmul with a deliberate accumulation dtype."""
    (prec,) = static
    return jnp.matmul(a, b, precision=prec)


@defop("dot")
def _op_dot(static, a, b):
    (prec,) = static
    return jnp.dot(a, b, precision=prec)


@defop("tensordot")
def _op_tensordot(static, a, b):
    (axes, prec) = static
    return jnp.tensordot(a, b, axes=axes, precision=prec)


@defop("einsum")
def _op_einsum(static, *args):
    (subscripts, prec) = static
    return jnp.einsum(subscripts, *args, precision=prec)


@defop("outer")
def _op_outer(static, a, b):
    return jnp.outer(a, b)


@defop("trace")
def _op_trace(static, a):
    offset, axis1, axis2 = static
    return jnp.trace(a, offset=offset, axis1=axis1, axis2=axis2)


# -- creation ----------------------------------------------------------------


def _constrain(x, spec_tuple):
    """Apply a sharding constraint from an encoded PartitionSpec."""
    from jax.sharding import NamedSharding, PartitionSpec

    from ramba_tpu.parallel import mesh as _mesh

    if spec_tuple is None:
        return x
    spec = PartitionSpec(*spec_tuple)
    try:
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(_mesh.get_mesh(), spec)
        )
    except Exception:  # single-device or incompatible mesh: constraint is moot
        return x


@defop("arange")
def _op_arange(static, start, step):
    n, dtype, spec = static
    x = start + step * jax.lax.iota(jnp.dtype(dtype), n)
    return _constrain(x, spec)


@defop("linspace")
def _op_linspace(static, start, stop):
    num, endpoint, dtype, spec = static
    x = jnp.linspace(start, stop, num, endpoint=endpoint, dtype=jnp.dtype(dtype))
    return _constrain(x, spec)


@defop("full")
def _op_full(static, fill):
    shape, dtype, spec = static
    x = jnp.full(shape, fill, dtype=jnp.dtype(dtype))
    return _constrain(x, spec)


@defop("eye")
def _op_eye(static):
    n, m, k, dtype, spec = static
    return _constrain(jnp.eye(n, m, k=k, dtype=jnp.dtype(dtype)), spec)


@defop("fromfunction")
def _op_fromfunction(static, *args):
    """Index-space filler: the reference's Filler/fromfunction kernels
    (ramba.py:141-150,1535-1595,8952-8961) generate per-shard index loops; here
    broadcasted iotas feed a traced user function and XLA fuses the rest."""
    shape, dtype, spec, fn, with_index = static
    idx = [
        jax.lax.broadcasted_iota(jnp.int32, shape, d) for d in range(len(shape))
    ]
    # _call_kernel gives fromfunction/init_array fillers the same treatment
    # as skeleton kernels: NumPy-ufunc rerouting and auto-lowered data
    # branches (the reference Numba-compiles these fillers too,
    # ramba.py:1535-1595)
    from ramba_tpu.skeletons import _call_kernel

    if with_index:
        r = _call_kernel(fn, *idx, *args)
    else:
        r = _call_kernel(fn, *args)
    r = jnp.asarray(r)
    if dtype is not None:
        r = r.astype(jnp.dtype(dtype))
    if r.shape != tuple(shape):
        r = jnp.broadcast_to(r, shape)
    return _constrain(r, spec)


@defop("random")
def _op_random(static, key, *params):
    """Distributed RNG.  The reference seeds ``seed + worker_num`` per worker
    and runs np.random inside each shard (ramba.py:3824-3825,
    ramba/random/random.py); here a single jax.random call over the sharded
    output shape gives device-count-invariant streams."""
    kind, shape, dtype, spec = static
    shape = tuple(shape)
    dt = jnp.dtype(dtype)
    if kind == "uniform":
        x = jax.random.uniform(key, shape, dtype=dt)
    elif kind == "normal":
        x = jax.random.normal(key, shape, dtype=dt)
    elif kind == "randint":
        lo, hi = params
        x = jax.random.randint(key, shape, lo, hi, dtype=dt)
    elif kind == "uniform_range":
        lo, hi = params
        x = jax.random.uniform(key, shape, dtype=dt, minval=lo, maxval=hi)
    elif kind == "permutation":
        # n is static (the node's output shape)
        x = jax.random.permutation(key, shape[0])
    elif kind == "permutation_array":
        (arr,) = params
        x = jax.random.permutation(key, arr)
    elif kind == "exponential":
        x = jax.random.exponential(key, shape, dtype=dt)
    elif kind == "poisson":
        (lam,) = params
        x = jax.random.poisson(key, lam, shape).astype(dt)
    elif kind == "beta":
        a, b = params
        x = jax.random.beta(key, a, b, shape, dtype=dt)
    elif kind == "gamma":
        (a,) = params
        x = jax.random.gamma(key, a, shape, dtype=dt)
    elif kind == "binomial":
        n, pr = params
        x = jax.random.binomial(key, n, pr, shape).astype(dt)
    elif kind in ("choice", "choice_norepl"):
        replace = kind == "choice"
        if len(params) == 2:
            arr, p = params
            x = jax.random.choice(key, arr, shape, replace=replace, p=p)
        else:
            (arr,) = params
            x = jax.random.choice(key, arr, shape, replace=replace)
    else:
        raise ValueError(kind)
    if kind in ("beta", "gamma") and jax.config.jax_enable_x64:
        # jax<=0.4.37's gamma sampler (a while_loop rejection sampler, also
        # backing beta) miscompiles under SPMD partitioning with x64 enabled:
        # the partitioner emits an s64-vs-s32 compare in the loop condition
        # and the HLO verifier rejects it.  Leave these outputs unconstrained
        # — GSPMD still shards the consumer; only the sampler stays local.
        return x
    return _constrain(x, spec)


@defop("shard_hint")
def _op_shard_hint(static, x):
    (spec,) = static
    return _constrain(x, spec)


# -- host-function escape hatch (smap with a traced python function) ---------


@defop("apply")
def _op_apply(static, *args):
    """Run a user-supplied traceable function over the operands — the
    skeleton layer (smap/sreduce, reference ramba.py:9863-9984) lowers here
    when the function is jax-traceable."""
    (fn,) = static
    return fn(*args)
