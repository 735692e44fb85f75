"""The user-visible distributed array.

TPU-native counterpart of the reference's front-end array stack:

* ``ndarray`` (/root/reference/ramba/ramba.py:5409-6901) — here a thin lazy
  handle over an expression graph whose leaves are sharded ``jax.Array``s.
* ``bdarray`` gid-registry + refcount-triggered remote deletion
  (ramba.py:1049-1158) — not needed: Python GC over the expression graph plus
  jax.Array reference counting frees shards automatically.
* view machinery (views share a gid and a shardview; ramba.py:5545-5565) —
  here a view holds its parent plus a reversible view op; reads re-derive the
  expression from the parent's *current* state, writes push an updated
  expression back through the chain, which gives NumPy view aliasing
  semantics on top of purely functional jax.

Operator methods are installed from op tables like the reference's
``make_method`` loops (ramba.py:7842-7993).
"""

from __future__ import annotations

import builtins
import itertools
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ramba_tpu import common
from ramba_tpu.core import expr as E
from ramba_tpu.core import fuser
from ramba_tpu.core import rewrite as _rewrite
from ramba_tpu.core.expr import Const, Expr, Node, Scalar
from ramba_tpu.observe import profile as _profile
from ramba_tpu.observe import registry as _registry
from ramba_tpu.parallel import mesh as _mesh

_seq_counter = itertools.count()


def _make_map(fname, operands):
    """``E.make_map``, with ``B + A.T`` and ``v - sstencil(...)`` folded
    into one node where the script writes them
    (``rewrite.fold_add_transposed``, ``rewrite.fold_stencil_update``)."""
    if (fname in ("add", "subtract") and len(operands) == 2
            and common.rewrite_enabled):
        folded = _rewrite.fold_stencil_update(fname, operands)
        if folded is None and fname == "add":
            folded = _rewrite.fold_add_transposed(*operands)
        if folded is not None:
            return folded
    return E.make_map(fname, operands)


def _hold_behind(a, b_expr):
    """After ``B += A.T``: ``A``'s pending value held behind one barrier
    with the updated ``B`` (node ``after``), so that what is computed
    from ``A`` next, the block the next exchange sends, waits for that
    update and is not made iterations ahead: in a flush of ten ``B +=
    A.T; A += 1`` one block of A and one received block are live, not
    ten, and not three (an ``A`` made while the one before it is sent).
    The value is ``A``'s own; an ``A`` that is a materialized leaf or a
    view is left as it is."""
    if a is not None and a._base is None and isinstance(a._expr, Node):
        a._set_expr(Node("after", (), [a._expr, b_expr], aval=a._expr.aval))


# ---------------------------------------------------------------------------
# View ops — reversible transforms between a parent array and a derived view.
# ---------------------------------------------------------------------------


class ViewOp:
    def read(self, base_expr: Expr) -> Expr:
        raise NotImplementedError

    def write(self, base_expr: Expr, value_expr: Expr) -> Expr:
        """Return a new base expression with the viewed region replaced."""
        raise NotImplementedError


class SliceView(ViewOp):
    """Basic indexing view (slices/ints/newaxis; ± steps supported — the
    reference's mapslice/shardview algebra, shardview_array.py:414-614)."""

    def __init__(self, enc):
        self.enc = enc

    def read(self, base_expr):
        return Node("getitem", (self.enc,), [base_expr])

    def write(self, base_expr, value_expr):
        return Node("setitem", (self.enc,), [base_expr, value_expr])


class PermuteView(ViewOp):
    """Transpose/moveaxis-family view (reference: remap_axis,
    shardview_array.py:1024-1042)."""

    def __init__(self, axes):
        self.axes = tuple(axes)
        inv = [0] * len(self.axes)
        for i, a in enumerate(self.axes):
            inv[a] = i
        self.inv = tuple(inv)

    def read(self, base_expr):
        return Node("permute", (self.axes,), [base_expr])

    def write(self, base_expr, value_expr):
        return Node("permute", (self.inv,), [value_expr])


class ReshapeView(ViewOp):
    """Reshape is always a live view here (writes map back through the
    row-major bijection); the reference needs an explicit element-remap
    redistribution for the general case (RemoteState.reshape,
    ramba.py:2409-2491) — XLA owns that data movement now."""

    def __init__(self, shape, base_shape):
        self.shape = tuple(shape)
        self.base_shape = tuple(base_shape)

    def read(self, base_expr):
        return Node("reshape", (self.shape,), [base_expr])

    def write(self, base_expr, value_expr):
        return Node("reshape", (self.base_shape,), [value_expr])


class BroadcastView(ViewOp):
    def __init__(self, shape):
        self.shape = tuple(shape)

    def read(self, base_expr):
        return Node("broadcast_to", (self.shape,), [base_expr])

    def write(self, base_expr, value_expr):
        raise ValueError("broadcast views are read-only")


# ---------------------------------------------------------------------------
# ndarray
# ---------------------------------------------------------------------------


def _unary_table():
    return {
        # python operator protocol
        "__neg__": "negative", "__pos__": "positive", "__abs__": "absolute",
        "__invert__": "invert",
    }


_BINOPS = {
    # name -> (python op suffix, map fn)  — reference op tables
    # array_binop_funcs at ramba.py:7893-7921
    "add": "add", "sub": "subtract", "mul": "multiply",
    "truediv": "true_divide", "floordiv": "floor_divide", "mod": "mod",
    "pow": "power", "and": "bitwise_and", "or": "bitwise_or",
    "xor": "bitwise_xor", "lshift": "left_shift", "rshift": "right_shift",
}

_CMPOPS = {
    "lt": "less", "le": "less_equal", "gt": "greater", "ge": "greater_equal",
    "eq": "equal", "ne": "not_equal",
}

# unary methods installed on the class (reference array_unaryop_funcs,
# ramba.py:7923-7960)
_UNARY_METHODS = [
    "abs", "absolute", "sqrt", "square", "exp", "log", "sin", "cos", "tan",
    "arcsin", "arccos", "arctan", "sinh", "cosh", "tanh", "arcsinh",
    "arccosh", "arctanh", "floor", "ceil", "trunc", "isnan", "isinf",
    "negative", "log2", "log10", "log1p", "expm1", "sign", "reciprocal",
]

_REDUCTIONS = ["sum", "prod", "min", "max", "any", "all", "mean"]


class ndarray_flags:
    """Minimal flags object (reference: ndarray_flags ramba.py:5365 and
    set_writeable_executor ramba.py:5358-5365)."""

    __slots__ = ("_arr",)

    def __init__(self, arr):
        self._arr = arr

    @property
    def writeable(self):
        return not self._arr._readonly

    @writeable.setter
    def writeable(self, value):
        arr = self._arr
        if value:
            # every ancestor must be writable (write_expr recurses through
            # the whole view chain, so the flag must agree with it)
            base = arr._base
            while base is not None:
                if base._readonly:
                    raise ValueError(
                        "cannot set WRITEABLE flag to True of this array"
                    )
                base = base._base
        arr._readonly = not value

    def __getitem__(self, name):
        if name in ("WRITEABLE", "writeable"):
            return self.writeable
        raise KeyError(name)

    def __setitem__(self, name, value):
        if name in ("WRITEABLE", "writeable"):
            self.writeable = value
        else:
            raise KeyError(name)


class ndarray:
    __slots__ = ("_expr", "_base", "_view", "_aval", "_seq", "_readonly",
                 "__weakref__")

    # Win dispatch over numpy arrays in mixed expressions.
    __array_priority__ = 100.0

    def __init__(self, expr: Optional[Expr] = None, base: "ndarray" = None,
                 view: ViewOp = None, aval=None):
        self._seq = next(_seq_counter)
        self._base = base
        self._view = view
        self._expr = None
        # views of read-only arrays are read-only (numpy semantics)
        self._readonly = base._readonly if base is not None else False
        if base is not None:
            self._aval = (
                aval if aval is not None
                else view.read(_AbstractLeaf(base._aval)).aval
            )
        else:
            assert expr is not None
            self._set_expr(expr)
            self._aval = expr.aval
            if aval is not None:
                self._aval = aval

    # -- expression plumbing --------------------------------------------------

    def _set_expr(self, new: Expr):
        old = self._expr
        if isinstance(old, Const):
            fuser.owner_decref(old.value)
        self._expr = new
        if isinstance(new, Const):
            fuser.owner_incref(new.value, new)
            fuser.unregister_pending(self)
        else:
            fuser.register_pending(self)
            fuser.note_node_created(self)

    def __del__(self):
        try:
            if self._base is None and isinstance(self._expr, Const):
                fuser.owner_decref(self._expr.value)
        except Exception:
            pass

    def read_expr(self) -> Expr:
        if self._base is None:
            return self._expr
        return self._view.read(self._base.read_expr())

    def write_expr(self, value: Expr):
        # Only the written array's OWN flag gates the write (numpy: a view
        # taken before the base was frozen stays writeable and writes
        # through; ADVICE r1).  The recursion below must therefore bypass
        # the ancestors' flags.
        if self._readonly:
            raise ValueError("assignment destination is read-only")
        self._write_through(value)

    def _write_through(self, value: Expr):
        if self._base is None:
            self._set_expr(value)
        else:
            self._base._write_through(
                self._view.write(self._base.read_expr(), value)
            )

    @property
    def flags(self):
        return ndarray_flags(self)

    # -- basic properties -----------------------------------------------------

    @property
    def shape(self):
        return tuple(self._aval.shape)

    @property
    def dtype(self):
        return np.dtype(self._aval.dtype)

    @property
    def ndim(self):
        return len(self._aval.shape)

    @property
    def size(self):
        return int(np.prod(self._aval.shape, dtype=np.int64)) if self._aval.shape else 1

    @property
    def nbytes(self):
        return self.size * self.dtype.itemsize

    @property
    def itemsize(self):
        return self.dtype.itemsize

    @property
    def T(self):
        return self.transpose()

    @property
    def flat(self):
        return iter(self.reshape(-1).asarray())

    def __len__(self):
        if self.ndim == 0:
            raise TypeError("len() of unsized object")
        return self.shape[0]

    # -- materialization ------------------------------------------------------

    def _value(self) -> jax.Array:
        """Concrete sharded jax.Array for this array (flushes lazy work)."""
        if self._base is None:
            if not isinstance(self._expr, Const):
                # flush the stream that OWNS this array's pending work
                # (waiting out any in-flight async flushes of it first) —
                # materialization from another thread/session must chase
                # the work to where it was built
                fuser.flush_for(self)
            if not isinstance(self._expr, Const):
                # Still lazy after a flush: an earlier failed flush
                # quarantined this array (the fuser pulls the roots of a
                # program that exhausted the degradation ladder out of the
                # pending registry).  Re-attempt this graph alone — an
                # innocent co-pending array materializes fine; a genuinely
                # broken one re-raises its real error here.
                self._set_expr(Const(fuser.flush_for(self,
                                                     extra=[self._expr])[0]))
            # leaf_value restores the buffer if the memory governor
            # spilled it to host while this array was cold
            return fuser.leaf_value(self._expr)
        base = self
        while base._base is not None:
            base = base._base
        return fuser.flush_for(base, extra=[self.read_expr()])[0]

    def asarray(self) -> np.ndarray:
        """Gather to a host NumPy array (reference: ndarray.asarray,
        ramba.py:5735-5765 — per-worker get_view + driver assembly; here a
        single device-to-host transfer).  Under multi-controller SPMD
        (jax.process_count() > 1) shards live on other processes'
        devices; an all-gather collective assembles the full value on
        EVERY process — the reference's MPI mode does the same driver
        assembly over its comm queues.  All processes must call this in
        lockstep (they do: each runs the same program)."""
        from ramba_tpu.utils import timing as _timing

        v = self._value()
        # the read: device-to-host copy and NumPy's conversion (the flush
        # under _value() keeps its own span)
        with _profile.span("read"):
            if not v.is_fully_addressable:
                from jax.experimental import multihost_utils
                from ramba_tpu.parallel import distributed as _distributed

                out = np.asarray(
                    multihost_utils.process_allgather(v, tiled=True))
                _distributed.note_transfer("allgather", out.nbytes)
            else:
                out = np.asarray(v)
        _timing.note_transfer("device_to_host", out.nbytes)
        return out

    def __array__(self, dtype=None, copy=None):
        a = self.asarray()
        return a.astype(dtype) if dtype is not None else a

    def item(self):
        return self.asarray().item()

    def tolist(self):
        return self.asarray().tolist()

    def __bool__(self):
        return bool(self.asarray())

    def __int__(self):
        return int(self.asarray())

    def __float__(self):
        return float(self.asarray())

    def __index__(self):
        return int(self.asarray())

    def __complex__(self):
        return complex(self.asarray())

    def __repr__(self):
        return f"ramba_tpu.ndarray({self.asarray()!r:.200s}, shape={self.shape})"

    def __iter__(self):
        for i in range(len(self)):
            yield self[i]

    def __hash__(self):
        return id(self)

    # -- elementwise helpers ---------------------------------------------------

    def _map(self, fname, *others, reverse=False):
        args = [as_exprable(o) for o in others]
        operands = [self.read_expr()] + args
        if reverse:
            operands = operands[::-1]
        return ndarray(_make_map(fname, operands))

    def _inplace_map(self, fname, other):
        val = _make_map(fname, [self.read_expr(), as_exprable(other)])
        if np.dtype(val.dtype) != self.dtype:
            val = Node("cast", (str(self.dtype),), [val])
        self.write_expr(val)
        if isinstance(val, Node) and val.op == "add_transposed":
            _hold_behind(other._base, val)
        return self

    def astype(self, dtype, copy=True):
        return ndarray(Node("cast", (str(np.dtype(dtype)),), [self.read_expr()]))

    def copy(self):
        return ndarray(self.read_expr())

    def fill(self, value):
        self.write_expr(
            Node("full", (self.shape, str(self.dtype),
                          _mesh.default_spec(self.shape)), [E.as_expr(value)])
        )

    def round(self, decimals=0):
        return ndarray(Node("round", (decimals,), [self.read_expr()]))

    def clip(self, a_min=None, a_max=None):
        out = self
        if a_min is not None:
            out = out._map("maximum", a_min)
        if a_max is not None:
            out = out._map("minimum", a_max)
        return out

    def conj(self):
        return self._map("conj")

    # -- reductions ------------------------------------------------------------

    def _reduce(self, fname, axis=None, keepdims=False, ddof=None):
        axis = _norm_axis(axis, self.ndim)
        out = ndarray(
            Node("reduce", (fname, axis, bool(keepdims), ddof), [self.read_expr()])
        )
        return out

    def var(self, axis=None, keepdims=False, ddof=0):
        return self._reduce("var", axis, keepdims, ddof)

    def std(self, axis=None, keepdims=False, ddof=0):
        return self._reduce("std", axis, keepdims, ddof)

    def argmin(self, axis=None):
        return self._reduce("argmin", axis)

    def argmax(self, axis=None):
        return self._reduce("argmax", axis)

    def cumsum(self, axis=None):
        x = self.reshape(-1) if axis is None else self
        return ndarray(Node("cumulative", ("cumsum", axis if axis is not None else 0),
                            [x.read_expr()]))

    def cumprod(self, axis=None):
        x = self.reshape(-1) if axis is None else self
        return ndarray(Node("cumulative", ("cumprod", axis if axis is not None else 0),
                            [x.read_expr()]))

    # -- shape manipulation (views) -------------------------------------------

    def reshape(self, *shape):
        if len(shape) == 1 and isinstance(shape[0], (tuple, list)):
            shape = tuple(shape[0])
        shape = _fix_reshape(self.size, tuple(int(s) for s in shape))
        if shape == self.shape:
            return self
        return ndarray(base=self, view=ReshapeView(shape, self.shape))

    def ravel(self):
        return self.reshape(-1)

    def reshape_copy(self, *shape):
        """Materialized reshape (reference: ndarray.reshape_copy,
        ramba.py:6719-6720)."""
        return self.reshape(*shape).copy()

    def flatten(self):
        return self.reshape(-1).copy()

    def transpose(self, *axes):
        if len(axes) == 1 and isinstance(axes[0], (tuple, list)):
            axes = tuple(axes[0])
        if not axes:
            axes = tuple(range(self.ndim))[::-1]
        axes = tuple(int(a) % self.ndim for a in axes)
        if axes == tuple(range(self.ndim)):
            return self
        return ndarray(base=self, view=PermuteView(axes))

    def swapaxes(self, a, b):
        axes = list(range(self.ndim))
        axes[a], axes[b] = axes[b], axes[a]
        return self.transpose(axes)

    def squeeze(self, axis=None):
        if axis is None:
            newshape = tuple(s for s in self.shape if s != 1)
        else:
            axs = axis if isinstance(axis, tuple) else (axis,)
            axs = {a % self.ndim for a in axs}
            newshape = tuple(s for i, s in enumerate(self.shape) if i not in axs)
        return self.reshape(newshape)

    def broadcast_to(self, shape):
        return ndarray(base=self, view=BroadcastView(shape))

    def take(self, indices, axis=None, mode="clip"):
        x = self.reshape(-1) if axis is None else self
        return ndarray(
            Node("take", (axis if axis is not None else 0, mode),
                 [x.read_expr(), as_exprable(indices)])
        )

    # -- indexing --------------------------------------------------------------

    def __getitem__(self, idx):
        kind, payload = _classify_index(idx, self.shape)
        if kind == "basic":
            return ndarray(base=self, view=SliceView(payload))
        if kind == "mask":
            from ramba_tpu.core.masked import MaskedArray

            return MaskedArray(self, payload)
        # advanced integer indexing -> gather (copy semantics)
        enc, arraypos, arrays = payload
        return ndarray(
            Node("getitem_adv", (enc, arraypos),
                 [self.read_expr()] + [as_exprable(a) for a in arrays])
        )

    def __setitem__(self, idx, value):
        kind, payload = _classify_index(idx, self.shape)
        if (kind == "basic" and common.rewrite_enabled
                and isinstance(value, ndarray) and value._base is self
                and self._base is None and type(value._view) is SliceView):
            # a face of this array onto another: one node, no view read
            folded = _rewrite.fold_face_copy(self._expr, payload,
                                             value._view.enc)
            if folded is not None:
                self.write_expr(folded)
                return
        vexpr = as_exprable(value)
        if kind == "basic":
            folded = None
            if common.rewrite_enabled and self._base is None:
                # a write of a prolongation onto this array: one node
                folded = _rewrite.fold_prolong(self._expr, payload, vexpr)
            self.write_expr(folded if folded is not None else Node(
                "setitem", (payload,), [self.read_expr(), vexpr]))
        elif kind == "mask":
            mexpr = as_exprable(payload)
            if np.dtype(vexpr.dtype) != self.dtype:
                vexpr = Node("cast", (str(self.dtype),), [vexpr])
            self.write_expr(
                Node("masked_fill", (), [self.read_expr(), mexpr, vexpr])
            )
        else:
            enc, arraypos, arrays = payload
            self.write_expr(
                Node("setitem_adv", (enc, arraypos),
                     [self.read_expr(), vexpr] + [as_exprable(a) for a in arrays])
            )

    # -- linalg ---------------------------------------------------------------

    def dot(self, other):
        from ramba_tpu.ops import linalg

        return linalg.dot(self, other)

    def __matmul__(self, other):
        from ramba_tpu.ops import linalg

        return linalg.matmul(self, other)

    def __rmatmul__(self, other):
        from ramba_tpu.ops import linalg

        return linalg.matmul(other, self)

    # -- numpy protocol -------------------------------------------------------

    def __array_ufunc__(self, ufunc, method, *inputs, **kwargs):
        """Reference: __array_ufunc__ maps ufuncs onto ndarray methods via
        ufunc_map (ramba.py:6860-6894)."""
        name = ufunc.__name__
        out = kwargs.pop("out", None)
        if kwargs.pop("where", True) is not True:
            return NotImplemented
        if method == "__call__":
            if kwargs:
                return NotImplemented
            if name == "divide":
                name = "true_divide"
            if name == "matmul":
                # np_array @ rt_array arrives here (numpy defers via the
                # matmul ufunc, not __rmatmul__)
                from ramba_tpu.ops.linalg import matmul as _mm

                res = _mm(inputs[0], inputs[1])
            elif name not in E.MAPFN:
                return NotImplemented
            else:
                operands = [as_exprable(x) for x in inputs]
                res = ndarray(E.make_map(name, operands))
        elif method == "reduce":
            ufunc_red = {"add": "sum", "multiply": "prod", "minimum": "min",
                         "maximum": "max", "logical_and": "all",
                         "logical_or": "any"}
            if name not in ufunc_red:
                return NotImplemented
            axis = kwargs.pop("axis", 0)
            keepdims = kwargs.pop("keepdims", False)
            dtype = kwargs.pop("dtype", None)
            if kwargs:
                return NotImplemented
            (x,) = inputs
            x = x if isinstance(x, ndarray) else fromarray_auto(x)
            res = x._reduce(ufunc_red[name], axis, keepdims)
            if dtype is not None:
                res = res.astype(dtype)
        else:
            return NotImplemented
        if out is not None:
            (o,) = out if isinstance(out, tuple) else (out,)
            if isinstance(o, np.ndarray):
                # numpy target: materialize and copy back host-side with
                # numpy's ufunc out= casting contract (same_kind — silent
                # float->int truncation must raise like numpy does).
                # (np.add(rt, rt, out=np_buf) and np_buf += rt land here)
                np.copyto(o, res.asarray(), casting="same_kind")
                return o
            val = res.read_expr()
            if np.dtype(val.dtype) != o.dtype:
                val = Node("cast", (str(o.dtype),), [val])
            o.write_expr(val)
            return o
        return res

    def __array_function__(self, func, types, args, kwargs):
        """Reference: HANDLED_FUNCTIONS registry via @implements
        (ramba.py:8536-8543,6825-6858)."""
        from ramba_tpu.core.interop import HANDLED_FUNCTIONS

        if func in HANDLED_FUNCTIONS:
            return HANDLED_FUNCTIONS[func](*args, **kwargs)
        return NotImplemented


class _AbstractLeaf(Expr):
    """Shape/dtype-only leaf used to infer view avals without touching data."""

    __slots__ = ()

    def __init__(self, aval):
        self.aval = aval


def as_exprable(x) -> Expr:
    """Lift operands: ndarray -> its expression; numpy/jax array -> sharded
    Const; python scalar -> weakly typed Scalar leaf."""
    if isinstance(x, ndarray):
        return x.read_expr()
    if isinstance(x, (list, tuple)):
        x = np.asarray(x)
    if isinstance(x, (np.ndarray, jax.Array)) and getattr(x, "ndim", 0) > 0:
        return Const(_device_put_default(x))
    if isinstance(x, (np.ndarray, jax.Array)):
        return Const(jnp.asarray(x))
    return E.as_expr(x)


def put_sharded(x, sharding):
    """Upload a host array under ``sharding``.  Under multi-controller SPMD
    the sharding spans processes, where a plain ``device_put`` of host data
    aborts in native code — instead each process materializes only its own
    addressable shards from the (identical, SPMD) host copy via
    ``make_array_from_callback`` (the reference's MPI mode likewise has
    every rank slice its own piece out of the rank-local copy,
    common.py:49-100)."""
    if jax.process_count() > 1 and getattr(sharding, "mesh", None) is not None:
        xn = np.asarray(x)
        return jax.make_array_from_callback(
            xn.shape, sharding, lambda idx: xn[idx]
        )
    return jax.device_put(x, sharding)


def _device_put_default(x):
    if isinstance(x, jax.Array) and not x.is_fully_addressable:
        return x  # already a global (cross-process) array: keep as is
    x = np.asarray(x) if not isinstance(x, jax.Array) else x
    if isinstance(x, np.ndarray):
        from ramba_tpu.utils import timing as _timing

        _timing.note_transfer("host_to_device", x.nbytes)
    try:
        return put_sharded(x, _mesh.upload_sharding(x.shape))
    except Exception:
        return jnp.asarray(x)


def fromarray_auto(x) -> ndarray:
    return ndarray(as_exprable(x))


def _norm_axis(axis, ndim):
    if axis is None:
        return None
    if isinstance(axis, (tuple, list)):
        return tuple(int(a) % ndim for a in axis)
    return int(axis) % ndim


def _fix_reshape(size, shape):
    if -1 in shape:
        known = int(np.prod([s for s in shape if s != -1], dtype=np.int64))
        shape = tuple(size // max(known, 1) if s == -1 else s for s in shape)
    return shape


def expand_ellipsis(idx: tuple, ndim: int) -> tuple:
    """Replace an Ellipsis with the full slices it stands for (identity
    check: ``in`` would do elementwise == on array items)."""
    n_ellipsis = sum(1 for it in idx if it is Ellipsis)
    if n_ellipsis > 1:
        raise IndexError(
            "an index can only have a single ellipsis ('...')"
        )
    if n_ellipsis:
        pos = next(p for p, it in enumerate(idx) if it is Ellipsis)
        n_specified = sum(1 for i in idx if i is not None and i is not Ellipsis)
        fill = (slice(None),) * (ndim - n_specified)
        idx = idx[:pos] + fill + idx[pos + 1:]
    return idx


# Index lowering, counted where every ``__getitem__`` and ``__setitem__``
# passes: ``dag.index.n`` and ``dag.index.ns``, and ``dag.index.hit`` the
# classifications served from ``_index_memo``; plain integers that the
# registry folds in when it is read (as expr.py's per-node counts, the
# time in float seconds).
_index_n = _index_hit = 0
_index_s = 0.0
_now = time.perf_counter

_registry.add_source(lambda: {"dag.index.n": _index_n,
                              "dag.index.ns": int(_index_s * 1e9),
                              "dag.index.hit": _index_hit})

# A basic index classified before -> the ("basic", enc) it gave, the same
# ``enc`` object on every hit: a node's static holding it then meets the
# inference memo's stored key with the very tuples it was stored under,
# and their comparison ends at each member's identity (the static is
# still hashed whole, by value: ``expr.infer_aval``).  Cleared when it
# holds ``_INDEX_MEMO_MAX`` entries, as that memo is.
_index_memo: dict = {}
_INDEX_MEMO_MAX = 8192
# What a slice member or an item of a basic index may be: ``None`` and
# the integers (``bool`` too), by exact type
_INDEX_INT_TYPES = frozenset({type(None), int, bool, *(
    t for t in set(np.sctypeDict.values()) if issubclass(t, np.integer))})
_INDEX_ITEM_TYPES = _INDEX_INT_TYPES | {type(Ellipsis)}


def _index_key(idx, shape):
    """``_index_memo``'s key of a basic index: the shape, then each item
    with its type, a slice as its members with their types, so that
    ``1``, ``True`` and ``np.int64(1)`` are different keys; None for any
    other index (an array, a list, a mask, a float anywhere).  A slice
    is the marker ``slice`` (which no item equals), its three members and
    their three types, any other item the item and its type, so the flat
    key reads back one way.  The key holds no slice object: a slice
    hashes only from Python 3.12 on."""
    key = [shape]
    for it in (idx if type(idx) is tuple else (idx,)):
        t = type(it)
        if t is slice:
            a, b, c = it.start, it.stop, it.step
            ta, tb, tc = type(a), type(b), type(c)
            if not (ta in _INDEX_INT_TYPES and tb in _INDEX_INT_TYPES
                    and tc in _INDEX_INT_TYPES):
                return None
            key += (slice, a, b, c, ta, tb, tc)
        elif t in _INDEX_ITEM_TYPES:
            key += (it, t)
        else:
            return None
    return tuple(key)


def _classify_index(idx, shape):
    """:func:`_classify` under the index counters, a basic index served
    from ``_index_memo`` once it has been classified.  Only a success is
    kept: an index out of bounds raises on every call."""
    global _index_n, _index_s, _index_hit
    t0 = _now()
    try:
        key = _index_key(idx, shape)
        got = _index_memo.get(key) if key is not None else None
        if got is not None:
            _index_hit += 1
            return got
        got = _classify(idx, shape)
        if key is not None and got[0] == "basic":
            if len(_index_memo) >= _INDEX_MEMO_MAX:
                _index_memo.clear()
            _index_memo[key] = got
        return got
    finally:
        _index_n += 1
        _index_s += _now() - t0


def _classify(idx, shape):
    """Split an index into basic / boolean-mask / advanced-integer cases.

    Reference analog: ndarray.__getitem__ dispatch between slicing views,
    maskarray creation, and the fancy-index gather path
    (ramba.py:5908-5911,6233-6267,6429-6545)."""
    if isinstance(idx, ndarray) and idx.dtype == np.bool_:
        return "mask", idx
    if isinstance(idx, np.ndarray) and idx.dtype == np.bool_:
        return "mask", fromarray_auto(idx)
    if not isinstance(idx, tuple):
        idx = (idx,)
    idx = expand_ellipsis(idx, len(shape))
    has_array = any(
        isinstance(i, (ndarray, np.ndarray, list, jax.Array)) for i in idx
    )
    if not has_array:
        # Bounds-check static integer indices (NumPy raises IndexError; raw
        # jax would clamp silently).
        dim = 0
        for it in idx:
            if it is None:
                continue
            if isinstance(it, (int, np.integer)):
                if dim >= len(shape) or not (-shape[dim] <= it < shape[dim]):
                    raise IndexError(
                        f"index {int(it)} is out of bounds for axis {dim} "
                        f"with size {shape[dim] if dim < len(shape) else 0}"
                    )
            dim += 1
        try:
            return "basic", E.encode_index(idx)
        except TypeError:
            pass
    # advanced: replace array positions with placeholders
    enc_parts = []
    arraypos = []
    arrays = []
    for p, it in enumerate(idx):
        if isinstance(it, (ndarray, np.ndarray, list, jax.Array)):
            arraypos.append(p)
            arrays.append(it if isinstance(it, ndarray) else np.asarray(it))
            enc_parts.append(("i", 0))  # placeholder, substituted at eval
        elif it is None:
            enc_parts.append(("n",))
        elif isinstance(it, slice):
            enc_parts.append(("s", it.start, it.stop, it.step))
        else:
            enc_parts.append(("i", int(it)))
    return "adv", (tuple(enc_parts), tuple(arraypos), arrays)


# ---------------------------------------------------------------------------
# Operator installation (reference: make_method loops, ramba.py:7893-7993)
# ---------------------------------------------------------------------------


def _install_operators():
    for pyname, fname in _BINOPS.items():
        def fwd(self, other, _f=fname):
            if not _is_operand(other):
                return NotImplemented
            return self._map(_f, other)

        def rev(self, other, _f=fname):
            if not _is_operand(other):
                return NotImplemented
            return self._map(_f, other, reverse=True)

        def inp(self, other, _f=fname):
            if not _is_operand(other):
                return NotImplemented
            return self._inplace_map(_f, other)

        setattr(ndarray, f"__{pyname}__", fwd)
        setattr(ndarray, f"__r{pyname}__", rev)
        setattr(ndarray, f"__i{pyname}__", inp)

    for pyname, fname in _CMPOPS.items():
        def cmp(self, other, _f=fname):
            if not _is_operand(other):
                return NotImplemented
            return self._map(_f, other)

        setattr(ndarray, f"__{pyname}__", cmp)

    for pyop, fname in _unary_table().items():
        def un(self, _f=fname):
            return self._map(_f)

        setattr(ndarray, pyop, un)

    def _divmod(self, other):
        return self._map("floor_divide", other), self._map("mod", other)

    ndarray.__divmod__ = _divmod

    for name in _UNARY_METHODS:
        fname = {"abs": "absolute"}.get(name, name)
        if fname not in E.MAPFN:
            continue

        def meth(self, _f=fname):
            return self._map(_f)

        if not hasattr(ndarray, name):
            setattr(ndarray, name, meth)

    def _finish_reduce(r, dtype, out, asarray):
        if dtype is not None:
            r = r.astype(dtype)
        if asarray:
            # Keep the (deferred) result in array form — shape (1,) for a
            # full reduction — so the caller can hold it without forcing a
            # flush (reference: reduction asarray kwarg, used e.g. at
            # ramba.py:6778 and sample pi integration).
            r = r.reshape((1,) if r.ndim == 0 else r.shape)
        if out is not None:
            out.write_expr(r.read_expr())
            return out
        return r

    # NumPy method positional order differs per reduction: sum/prod/mean
    # take (axis, dtype, out), min/max/any/all take (axis, out) — matching
    # exactly so e.g. ``a.min(0, out_arr)`` writes out_arr instead of
    # silently treating it as a dtype (ADVICE r1).  Everything past
    # NumPy's positional tail is keyword-only.
    for red in ("sum", "prod", "mean"):
        def rmeth(self, axis=None, dtype=None, out=None, *, keepdims=False,
                  asarray=False, _f=red):
            return _finish_reduce(
                self._reduce(_f, axis, keepdims), dtype, out, asarray
            )

        setattr(ndarray, red, rmeth)

    for red in ("min", "max", "any", "all"):
        def rmeth2(self, axis=None, out=None, *, keepdims=False,
                   asarray=False, _f=red):
            return _finish_reduce(
                self._reduce(_f, axis, keepdims), None, out, asarray
            )

        setattr(ndarray, red, rmeth2)


def _is_operand(x):
    return isinstance(
        x, (ndarray, np.ndarray, jax.Array, bool, int, float, complex,
            np.generic, list)
    ) or np.isscalar(x)


_install_operators()
