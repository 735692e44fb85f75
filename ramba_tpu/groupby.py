"""Groupby: per-group reductions and group-broadcast binary ops.

Reference: ndarray.groupby + RambaGroupby (/root/reference/ramba/ramba.py:
10290-10643, docs/index.md "Groupby"), which the reference implements on top
of smap_index/sreduce_index plus DAG pattern-rewrite rules that recognize
xarray idioms (rewrite_stack_mean_advindex / rewrite_concatenate_binop_getitem,
ramba.py:4601-4789).

TPU-native design: one lowering, the **sorted chunked walk**, whose work
follows the data for any label array (uneven, empty, unsorted, repeating
groups).  The labels are a runtime operand (another calendar of the same
length compiles nothing); from them a few small tables are built on the
device (a stable argsort, the group starts and sizes, and per chunk its
group and the rows it fetches: ``_walk``).  A chunk is up to ``K`` members
of ONE group; the program is one XLA ``while`` over the chunks, whose body
XLA fuses into one pass:

* ``segment_reduce`` fetches the chunk's rows where they lie (``K``
  dynamic slices of the operand along the segment axis, or one gather
  where the slabs are small and a group has many), folds them, and combines the result into the
  group's slab of the output in place (dynamic-update-slice): the data is
  read once, a group's slab read and written once per chunk.  ``mean``,
  ``var`` and ``std`` share the pass (their accumulators ride the same
  loop).
* the group-broadcast binary ops (``gb - clim``) stay a ``take`` by label
  followed by a fused elementwise op: a take materializes the broadcast
  operand, so where the result is only reduced (``((gb - clim) ** 2)
  .mean()``, the anomaly pattern) ``core/rewrite.py`` replaces the whole
  reduce by ``segment_mapreduce``: the same walk, each chunk's rows
  evaluated against their group's slab and reduced, nothing of the
  operand's size stored.

No scatter anywhere (GSPMD miscompiled scatter-based segment reductions on
sharded layouts, rounds 3 to 5) and nothing is left to GSPMD either: under
a mesh both passes run inside ``shard_map`` over each device's block of the
operand as the default layout cuts it (``_blocks``), with the labels of
the block's own rows.  ``segment_reduce`` combines the per-device partials
across the devices that share the segment axis (psum/pmin/pmax: every
device then holds its block's own columns of the result, of which the
default layout of a kept result is a slice, ``mesh._dividing_spec``); a
mean's partial sums are divided by the group's size over all of them in
the loop, once a group, so nothing follows the combination.
``segment_mapreduce`` takes the per-group operands as the slabs of the
block's own columns (the first pass's result as it stands) and combines
its scalar over the mesh.
One device is the mesh of one: the same walk, nothing to combine.  ``K`` is
chosen from what the code can observe (rows per group, the slab's bytes:
``_chunk_rows``, whose three constants were swept on the chip).

The walk fetches slabs ``x[t]``, so it wants the segment axis slowest on
the device.  XLA:TPU lays out arguments and results to pad least (a
``(T, 721, 1440)`` cube gets TIME minor); ``core/layouts.py`` keeps the
flush's results of rank three or more row-major, on one device and on
every device of a mesh (where the flush also places them: the default
layout, ``mesh.held_spec``), so the block arrives as the walk wants it
and ``segment_reduce``'s result leaves the flush in the default layout
of its own shape.  Where an operand arrives otherwise (an array this
system did not make) XLA puts one
transposed copy of it before the loops (PERF.md section 6, PR 30).  A
split that does not divide its extent is not used: the operand is whole
along that dimension inside ``shard_map``, gathered on entry (the default
layout leaves no such split where one that divides exists).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from ramba_tpu import common
from ramba_tpu.core.expr import OPS, Node, defop
from ramba_tpu.core.ndarray import ndarray, as_exprable
from ramba_tpu.observe import registry as _registry
from ramba_tpu.ops.creation import asarray
from ramba_tpu.parallel import mesh as _mesh

# A chunk's rows are fetched by dynamic slices, each fused into the chunk's
# pass: up to _UNROLL of them, whatever their size.  Slabs up to
# _GATHER_SLAB are fetched by one gather instead where a group has more
# rows than that: the gather stores the chunk, as many rows as fill
# _CHUNK_BYTES, and the loop has that many fewer steps.  Above that size a
# TPU gather of whole slabs is two hundred times slower than the slices.
# PERF.md section 6 (PR 30) has the sweep on the chip behind all three.
_UNROLL = 16
_GATHER_SLAB = 512 << 10
_CHUNK_BYTES = 32 << 20

_COMB = {"sum": jnp.add, "prod": jnp.multiply,
         "min": jnp.minimum, "max": jnp.maximum}
_RED = {"sum": jnp.sum, "prod": jnp.prod, "min": jnp.min, "max": jnp.max}
_PCOMB = {"sum": lax.psum, "min": lax.pmin, "max": lax.pmax,
          "prod": lambda a, axes: jnp.prod(lax.all_gather(a, axes), axis=0)}
_PNAME = {"sum": "psum", "min": "pmin", "max": "pmax", "prod": "all_gather"}


def _reduce_identity(op, dtype):
    """Identity element of a segment reduction, matching jax.ops.segment_*
    semantics for empty segments (sum->0, prod->1, min->dtype max, ...)."""
    dt = jnp.dtype(dtype)
    if op == "sum":
        return jnp.zeros((), dt)
    if op == "prod":
        return jnp.ones((), dt)
    if dt == jnp.bool_:
        return jnp.asarray(op == "min", dt)
    if jnp.issubdtype(dt, jnp.inexact):
        return jnp.asarray(jnp.inf if op == "min" else -jnp.inf, dt)
    info = jnp.iinfo(dt)
    return jnp.asarray(info.max if op == "min" else info.min, dt)


def _chunk_rows(n, num_groups, slab_bytes):
    """Members a chunk holds: no more than a group has on average, so
    that the rows fetched stay proportional to the data whatever the
    group count; up to ``_UNROLL`` (fused slices: the more the faster),
    or as many small slabs as fill ``_CHUNK_BYTES`` (one gather)."""
    per_group = max(1, -(-n // num_groups))
    if per_group <= _UNROLL or slab_bytes > _GATHER_SLAB:
        return min(per_group, _UNROLL)
    return min(per_group, _CHUNK_BYTES // max(slab_bytes, 1))


def _group_edges(labels, num_groups, clip=False):
    """The members ordered by group (stable) and where each group starts
    in that order (``num_groups + 1`` edges).  Labels outside
    ``[0, num_groups)`` sort past the last group (``clip``: into the
    nearest)."""
    i32 = jnp.int32
    lab = labels.astype(i32)
    if clip:
        lab = jnp.clip(lab, 0, num_groups - 1)
    else:
        lab = jnp.where((lab >= 0) & (lab < num_groups), lab, num_groups)
    order = jnp.argsort(lab, stable=True).astype(i32)
    edges = jnp.searchsorted(lab[order], jnp.arange(num_groups + 1, dtype=i32),
                             side="left", method="sort").astype(i32)
    return order, edges


def _walk(labels, num_groups, k, clip=False):
    """The walk's tables, from the labels on the device.  Members are
    ordered by group (stable, so in their own order inside a group) and
    cut into chunks of at most ``k`` of one group; every group has at
    least one chunk, so an empty one still gets its finishing step.  Per
    chunk ``c`` of the static bound ``n // k + num_groups``: its
    ``group``, the ``rows`` it fetches with which of them are ``valid``,
    and whether it is the group's ``last``; ``total`` is how many chunks
    there are and ``counts`` the group sizes.  Labels outside
    ``[0, num_groups)`` belong to no group (``clip``: to the nearest)."""
    n = labels.shape[0]
    i32 = jnp.int32
    order, edges = _group_edges(labels, num_groups, clip)
    starts, counts = edges[:-1], edges[1:] - edges[:-1]
    nch = jnp.maximum(1, -(-counts // k))
    ends = jnp.cumsum(nch).astype(i32)
    c = jnp.arange(n // k + num_groups, dtype=i32)
    group = jnp.minimum(jnp.searchsorted(ends, c, side="right",
                                         method="sort"),
                        num_groups - 1).astype(i32)
    j = c - (ends - nch)[group]
    pos = (starts[group] + j * k)[:, None] + jnp.arange(k, dtype=i32)[None]
    return {"total": ends[-1], "group": group,
            "rows": order[jnp.clip(pos, 0, n - 1)],
            "valid": pos < edges[1:][group][:, None],
            "last": j == nch[group] - 1, "counts": counts}


def _along(v, dim, ndim):
    """A vector laid along ``dim`` of an ``ndim``-dimensional operand."""
    return v.reshape((1,) * dim + (-1,) + (1,) * (ndim - dim - 1))


def _fetch(x, rows, valid, dim):
    """A chunk's rows of ``x`` along ``dim`` as pieces ``(block, ok)``:
    one row a piece, sliced where it lies, or all of them in one gather
    where a chunk holds more than ``_UNROLL``."""
    k = rows.shape[0]
    if k <= _UNROLL:
        return [(lax.dynamic_slice_in_dim(x, rows[i], 1, axis=dim),
                 valid[i:i + 1]) for i in range(k)]
    return [(jnp.take(x, rows, axis=dim), valid)]


def _note(path, num_groups, n, k, how, nbytes):
    """The kernel note of a pass: what the walk chose, and under a mesh
    (``how``: ``_blocks``' entries, the segment dimension, the
    combination) how the operand is split and what a device hands to the
    combination across chips."""
    split = combine = None
    if how is not None:
        ents, dim, combine = how
        split = {"segment": list(ents[dim]),
                 "others": [a for d, e in enumerate(ents) if d != dim
                            for a in e]}
    _registry.note_kernel(
        "segment", path, groups=num_groups, chunk_rows=k,
        chunks=n // k + num_groups,
        fetch="slices" if k <= _UNROLL else "gather", sharded=how is not None,
        split=split, local_rows=n, combine=combine or "none",
        combine_bytes=nbytes if combine else 0)


def _blocks(shape):
    """How a pass cuts an operand of ``shape``: ``(mesh, entries)``, the
    mesh axes along each dimension in the operand's default layout, those
    that divide their extent; ``entries`` is None on one device and for an
    array too small to distribute, where the walk runs as it stands."""
    from ramba_tpu.ops.stencil_sharded import _axis_entries

    mesh = _mesh.get_mesh()
    if mesh.devices.size == 1 or math.prod(shape) < common.dist_threshold:
        return mesh, None
    return mesh, [e if s % math.prod(mesh.shape[a] for a in e) == 0 else ()
                  for e, s in zip(_axis_entries(mesh, shape), shape)]


def _spec(entries):
    return P(*((e[0] if len(e) == 1 else tuple(e)) if e else None
               for e in entries))


def _local_reduce(x, labels, num_groups, dim, op, pres, mean, how=None):
    """The walk over one device's rows: one accumulator per entry of
    ``pres``, folding ``pre(row)`` with ``op``.  With ``mean`` (and
    accumulators of an inexact type) each group's slab is divided by the
    group's size at its last chunk, in the loop: the size over the WHOLE
    segment axis (the sizes summed over ``how``'s devices along it), so
    that the devices' slabs add up to the mean and no pass over the sums
    follows their combination.  Returns the accumulators (``dim`` of size
    ``num_groups``), the group sizes, and whether the division was made."""
    n, nd = x.shape[dim], x.ndim
    shape = x.shape[:dim] + (num_groups,) + x.shape[dim + 1:]
    row = jax.ShapeDtypeStruct((1,), x.dtype)
    dtypes = [jax.eval_shape(lambda r, f=f: _RED[op](f(r), axis=0), row).dtype
              for f in pres]
    accs = tuple(jnp.full(shape, _reduce_identity(op, dt), dt)
                 for dt in dtypes)
    mean = mean and all(jnp.issubdtype(dt, jnp.inexact) for dt in dtypes)
    if n == 0:
        return accs, jnp.zeros((num_groups,), jnp.int32), False
    k = _chunk_rows(n, num_groups,
                    math.prod(shape) // num_groups * x.dtype.itemsize)
    _note("walk_reduce", num_groups, n, k, how,
          sum(math.prod(shape) * jnp.dtype(dt).itemsize for dt in dtypes))
    t = _walk(labels, num_groups, k)
    counts = t["counts"]
    if how is not None and how[0][dim]:
        counts = lax.psum(counts, how[0][dim])

    def body(c, accs):
        g = t["group"][c]
        pieces = _fetch(x, t["rows"][c], t["valid"][c], dim)
        out = []
        for pre, acc in zip(pres, accs):
            ident = _reduce_identity(op, acc.dtype)
            cur = lax.dynamic_slice_in_dim(acc, g, 1, axis=dim)
            for block, ok in pieces:
                v = jnp.where(_along(ok, dim, nd),
                              pre(block).astype(acc.dtype), ident)
                if v.shape[dim] > 1:
                    v = _RED[op](v, axis=dim, keepdims=True)
                cur = _COMB[op](cur, v)
            if mean:
                cur = jnp.where(t["last"][c],
                                cur / counts[g].astype(cur.dtype), cur)
            out.append(lax.dynamic_update_slice_in_dim(acc, cur, g, axis=dim))
        return tuple(out)

    return lax.fori_loop(0, t["total"], body, accs), counts, mean


def _segment_accumulate(x, labels, num_groups, dim, op, pres, mean):
    """``_local_reduce`` over the whole operand: as it stands on one
    device; under a mesh inside ``shard_map`` over each device's block and
    the labels of its rows, the partials combined across the devices that
    share the segment axis and held by every one of them: the block's own
    columns of the result, which a second pass takes as they are and of
    which the default layout of a kept result is a device's own slice."""
    mesh, ents = _blocks(x.shape)
    if ents is None:
        return _local_reduce(x, labels, num_groups, dim, op, pres, mean)
    seg = ents[dim]
    combine = _PNAME[op] if seg else None
    divided = []

    def local(xb, lb):
        accs, counts, done = _local_reduce(
            xb, lb, num_groups, dim, op, pres, mean,
            how=(ents, dim, combine))
        divided.append(done)
        if seg:
            accs = tuple(_PCOMB[op](a, seg) for a in accs)
        return accs, counts

    accs, counts = jax.shard_map(
        local, mesh=mesh, in_specs=(_spec(ents), _spec([seg])),
        out_specs=((_spec(ents[:dim] + [()] + ents[dim + 1:]),) * len(pres),
                   P()), check_vma=False)(x, labels)
    return accs, counts, divided[-1]


# what a pass folds of a row: the row, its square, and their forms that
# skip NaNs (a NaN adds nothing and counts for nothing)
def _row(b):
    return b


def _square(b):
    return b * b


def _nan0(b):
    return jnp.where(jnp.isnan(b), jnp.zeros((), b.dtype), b)


def _nan0_square(b):
    return _square(_nan0(b))


def _not_nan(b):
    return (~jnp.isnan(b)).astype(b.dtype)


#: kind -> (the fold, what each accumulator of the one pass folds of a row)
_PASSES = {
    "sum": ("sum", (_row,)), "prod": ("prod", (_row,)),
    "min": ("min", (_row,)), "max": ("max", (_row,)),
    "mean": ("sum", (_row,)), "var": ("sum", (_row, _square)),
    "nansum": ("sum", (_nan0,)), "nanmean": ("sum", (_nan0, _not_nan)),
    "nanvar": ("sum", (_nan0, _nan0_square, _not_nan)),
}
_PASSES["std"], _PASSES["nanstd"] = _PASSES["var"], _PASSES["nanvar"]


@defop("segment_reduce")
def _op_segment_reduce(static, x, labels):
    kind, num_groups, dim = static
    if kind == "count":
        # the group sizes: the labels say them, no pass over the data
        edges = _group_edges(labels, num_groups)[1]
        counts = edges[1:] - edges[:-1]
        shape = x.shape[:dim] + (num_groups,) + x.shape[dim + 1:]
        return jnp.broadcast_to(_along(counts, dim, x.ndim), shape).astype(
            jnp.int64 if jnp.zeros(0).dtype == jnp.float64 else jnp.int32)
    if kind not in _PASSES:
        raise ValueError(kind)
    op, pres = _PASSES[kind]
    mean = kind in ("mean", "var", "std")
    accs, counts, divided = _segment_accumulate(x, labels, num_groups, dim,
                                                op, pres, mean)
    if mean and not divided:
        accs = [a / _along(counts, dim, x.ndim).astype(a.dtype) for a in accs]
    elif kind.startswith("nan") and kind != "nansum":
        accs = [a / accs[-1] for a in accs[:-1]]  # over what counts
    if kind.endswith(("var", "std")):
        # one traversal: the means of the rows and of their squares
        m, sq = accs
        v = sq - m * m
        return jnp.sqrt(v) if kind.endswith("std") else v
    (out,) = accs
    return out


def _local_mapreduce(static, labels, leaves, how=None):
    """``segment_mapreduce`` over one device's rows: the walk, each
    chunk's rows evaluated against their group's slab and reduced to one
    value (a sum, for ``mean``: the caller divides)."""
    kind, dim, num_groups, roles, instrs = static
    op = "sum" if kind == "mean" else kind
    full = [i for i, r in enumerate(roles) if r == "full"]
    first = leaves[full[0]]
    n, nd = first.shape[dim], first.ndim
    k = _chunk_rows(n, num_groups, sum(
        leaves[i].size // max(n, 1) * leaves[i].dtype.itemsize for i in full))
    t = _walk(labels, num_groups, k, clip=True)  # as ``take`` in mode clip
    others = tuple(a for a in range(nd) if a != dim)

    def chunk(c):
        g = t["group"][c]
        env = {i: (lax.dynamic_slice_in_dim(v, g, 1, axis=dim)
                   if roles[i] == "group" else v)
               for i, v in enumerate(leaves) if roles[i] != "full"}
        fetched = {i: _fetch(leaves[i], t["rows"][c], t["valid"][c], dim)
                   for i in full}
        total = None
        for piece in range(len(fetched[full[0]])):
            vals = []
            for fname, refs in instrs:
                vals.append(OPS["map"]((fname,), *(
                    (fetched[i][piece][0] if i in fetched else env[i])
                    if where == "a" else vals[i] for where, i in refs)))
            r = _RED[op](vals[-1], axis=others)
            r = _RED[op](jnp.where(fetched[full[0]][piece][1], r,
                                   _reduce_identity(op, r.dtype)))
            total = r if total is None else _COMB[op](total, r)
        return total

    dt = jax.eval_shape(chunk, jax.ShapeDtypeStruct((), jnp.int32)).dtype
    _note("walk_broadcast", num_groups, n, k, how, jnp.dtype(dt).itemsize)
    return lax.fori_loop(0, t["total"], lambda c, a: _COMB[op](a, chunk(c)),
                         _reduce_identity(op, dt))


@defop("segment_mapreduce")
def _op_segment_mapreduce(static, labels, *leaves):
    """A full reduction of an elementwise expression whose operands are
    arrays of one shape (``full``), per-group arrays broadcast to it by
    label along ``dim`` (``group``) and scalars: the walk of
    ``segment_reduce``, each chunk's rows evaluated against their
    group's slab and reduced to one value.  Nothing of the operands' size
    is stored.  ``instrs`` is the expression, post-order: ``(fname,
    refs)`` with a ref ``("a", i)`` for leaf ``i`` or ``("t", j)`` for
    instruction ``j``; the last one is what is reduced.  Under a mesh the
    walk runs in ``shard_map``: the ``full`` operands as each device's
    blocks, the ``group`` operands as the slabs of the block's own
    columns, the value combined over every axis that splits the blocks;
    ``mean`` divides by the size of the whole."""
    kind, dim, _, roles, _ = static
    op = "sum" if kind == "mean" else kind
    first = leaves[roles.index("full")]
    mesh, ents = _blocks(first.shape)
    if ents is None:
        acc = _local_mapreduce(static, labels, leaves)
    else:
        axes = tuple(a for e in ents for a in e)
        rest = ents[:dim] + [()] + ents[dim + 1:]
        how = (ents, dim, _PNAME[op] if axes else None)

        def local(lb, *blocks):
            acc = _local_mapreduce(static, lb, blocks, how)
            return _PCOMB[op](acc, axes) if axes else acc

        acc = jax.shard_map(
            local, mesh=mesh, out_specs=P(), check_vma=False,
            in_specs=(_spec([ents[dim]]),) + tuple(
                {"full": _spec(ents), "group": _spec(rest),
                 "scalar": P()}[r] for r in roles))(labels, *leaves)
    return acc / float(first.size) if kind == "mean" else acc


def fuse_broadcast_reduce(node: Node):
    """``reduce(expr)`` over everything, where ``expr`` is elementwise
    over arrays of one shape, scalars and at least one ``take`` of a
    per-group array by one label array (what ``RambaGroupby._binop``
    builds: mode ``clip``, which is how the walk reads a label outside
    the groups; a take that wraps or fills is left alone) ->
    ``segment_mapreduce``.  None where it does not apply: the take then
    materializes its operand, which is correct and, for an operand a
    device can hold twice, all there is to say."""
    kind, axis, keepdims, ddof = node.static
    body = node.args[0]
    shape = tuple(body.aval.shape)
    if (kind not in ("sum", "mean", "min", "max") or keepdims
            or ddof not in (None, 0) or not shape
            or (axis is not None and tuple(axis) != tuple(range(len(shape))))
            or not jnp.issubdtype(body.aval.dtype, jnp.inexact)):
        return None
    leaves, roles, instrs, ref = [], [], [], {}
    labels = dim = None
    stack = [(body, False)]
    while stack:
        e, seen = stack.pop()
        if id(e) in ref:
            continue
        if (isinstance(e, Node) and e.op == "map"
                and tuple(e.aval.shape) == shape):
            if seen:
                instrs.append((e.static[0], tuple(ref[id(a)] for a in e.args)))
                ref[id(e)] = ("t", len(instrs) - 1)
            else:
                stack.append((e, True))
                stack.extend((a, False) for a in e.args)
            continue
        leaf, role = e, "full"
        if not e.aval.shape:
            role = "scalar"
        elif tuple(e.aval.shape) != shape:
            return None
        elif isinstance(e, Node) and e.op == "take":
            leaf, idx = e.args
            d = e.static[0] % len(shape)
            if (e.static[1] != "clip" or idx.aval.ndim != 1
                    or not jnp.issubdtype(idx.aval.dtype, jnp.integer)
                    or leaf.aval.ndim != len(shape)
                    or (labels is not None and (idx is not labels
                                                or d != dim))):
                return None
            labels, dim, role = idx, d, "group"
        ref[id(e)] = ("a", len(leaves))
        leaves.append(leaf)
        roles.append(role)
    if labels is None or "full" not in roles or not instrs:
        return None
    groups = {leaves[i].aval.shape[dim] for i, r in enumerate(roles)
              if r == "group"}
    if len(groups) != 1:
        return None
    return Node("segment_mapreduce",
                (kind, dim, groups.pop(), tuple(roles), tuple(instrs)),
                [labels] + leaves)


def segment_node(base, labels, kind, num_groups, dim):
    """The one node a segment reduction is, whoever asks: the direct call
    and ``core/rewrite.py``'s Xarray rule.  ``labels`` is a leaf."""
    return Node("segment_reduce", (kind, int(num_groups), int(dim)),
                [base, labels])


def broadcast_node(fname, base, other, labels, dim, reverse=False):
    """``base`` against ``other``'s slab of each row's group, elementwise:
    a ``take`` by label (which a reduce over everything fuses away:
    ``fuse_broadcast_reduce``) under one ``map``."""
    from ramba_tpu.core.expr import make_map

    gathered = Node("take", (int(dim), "clip"), [other, labels])
    return make_map(fname, [gathered, base] if reverse else [base, gathered])


class RambaGroupby:
    """Reference: RambaGroupby (ramba.py:10290-10643).

    Reductions return an array whose grouped dimension has size
    ``num_groups`` (``segment_reduce``: the sorted chunked walk).  Binary
    operators broadcast a per-group operand back to the element level
    (the xarray climatology/anomaly pattern the reference's rewrite rules
    target): a ``take`` by label under the elementwise op, which a
    reduction of the result over everything fuses away
    (``segment_mapreduce``)."""

    def __init__(self, arr: ndarray, dim: int, value_to_group, num_groups=None):
        self.arr = arr
        self.dim = int(dim) % arr.ndim
        labels = np.asarray(value_to_group)
        if labels.ndim != 1 or labels.shape[0] != arr.shape[self.dim]:
            raise ValueError(
                "value_to_group must be 1-D with length equal to the grouped "
                f"dimension ({arr.shape[self.dim]}), got {labels.shape}"
            )
        self.labels = labels.astype(np.int32)
        self.num_groups = int(num_groups if num_groups is not None
                              else labels.max() + 1)
        self._on_device = None

    def _labels(self):
        """The labels as the leaf every node of this group-by shares:
        uploaded once, and one object, so that the broadcasts of one
        expression are seen to go by one label array."""
        if self._on_device is None:
            self._on_device = as_exprable(self.labels)
        return self._on_device

    # -- reductions -----------------------------------------------------------

    def _reduce(self, kind):
        return ndarray(segment_node(self.arr.read_expr(), self._labels(),
                                    kind, self.num_groups, self.dim))

    def sum(self):
        return self._reduce("sum")

    def prod(self):
        return self._reduce("prod")

    def min(self):
        return self._reduce("min")

    def max(self):
        return self._reduce("max")

    def mean(self):
        return self._reduce("mean")

    def nanmean(self):
        return self._reduce("nanmean")

    def nansum(self):
        return self._reduce("nansum")

    def var(self):
        return self._reduce("var")

    def std(self):
        return self._reduce("std")

    def nanvar(self):
        return self._reduce("nanvar")

    def nanstd(self):
        return self._reduce("nanstd")

    def count(self):
        return self._reduce("count")

    # -- group-broadcast binary ops -------------------------------------------

    def _binop(self, fname, other, reverse=False):
        if np.isscalar(other) or getattr(other, "ndim", None) == 0:
            # scalar operand: elementwise against the underlying array
            # (reference groupby binops pass scalars straight through to the
            # generated kernel, ramba.py:10610-10643)
            return self.arr._map(fname, other, reverse=reverse)
        other = asarray(other)
        if other.shape[self.dim] != self.num_groups:
            raise ValueError(
                f"group operand must have {self.num_groups} entries along "
                f"dim {self.dim}, got {other.shape}"
            )
        return ndarray(broadcast_node(
            fname, self.arr.read_expr(), other.read_expr(), self._labels(),
            self.dim, reverse))


def _install_groupby_binops():
    table = {
        "add": "add", "sub": "subtract", "mul": "multiply",
        "truediv": "true_divide", "floordiv": "floor_divide", "mod": "mod",
        "pow": "power", "lt": "less", "le": "less_equal", "gt": "greater",
        "ge": "greater_equal", "eq": "equal", "ne": "not_equal",
    }
    for py, fname in table.items():
        def fwd(self, other, _f=fname):
            return self._binop(_f, other)

        def rev(self, other, _f=fname):
            return self._binop(_f, other, reverse=True)

        setattr(RambaGroupby, f"__{py}__", fwd)
        if py not in ("lt", "le", "gt", "ge", "eq", "ne"):
            setattr(RambaGroupby, f"__r{py}__", rev)


_install_groupby_binops()


def _ndarray_groupby(self, dim, value_to_group, num_groups=None):
    return RambaGroupby(self, dim, value_to_group, num_groups)


ndarray.groupby = _ndarray_groupby
