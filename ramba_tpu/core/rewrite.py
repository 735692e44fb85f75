"""Pattern-rewrite rules over the lazy expression graph.

TPU-native rebuild of the reference's DAG peephole rewrites
(/root/reference/ramba/ramba.py:4567-4789), which recognize the op patterns
xarray emits for groupby workloads (docs/index.md:53-58) and replace them
with direct implementations:

* ``rewrite_arange_reshape`` (:4567-4598) — ``arange(n).reshape(s)`` becomes
  a direct per-index filler.  Here that means generating values in the
  *target* sharding via broadcasted iotas instead of materializing a 1-D
  sharded iota and paying an all-to-all reshard on the reshape.
* ``rewrite_stack_mean_advindex`` (:4601-4677) — ``stack([reduce(x[:, idx_g])
  for g])`` (the xarray ``groupby().mean()`` expansion) becomes ONE segment
  reduction instead of k gathers + k reductions + a stack.
* ``rewrite_concatenate_binop_getitem`` (:4680-4789) — ``concatenate([
  x[:, idx_g] ∘ m[g] for g])`` (the xarray anomaly pattern) becomes the
  node of the direct ``gb ∘ m`` (a take of ``m`` by label under one fused
  elementwise op), re-ordered by one gather where the groups are not in
  place.
* ``rewrite_reduce_group_broadcast`` — a reduction over everything of such
  an expression becomes ``segment_mapreduce``: the broadcast operand is
  never stored (ramba_tpu/groupby.py).

Rules run bottom-up once per flush (core/fuser.py); a rule returns a
replacement Node or None.  All matching is defensive: any structural
mismatch leaves the graph untouched.  Four folds run where the script
writes, not at the flush:

* ``fold_face_copy`` (counted as ``rewrite_face_copies``) — ``a[d] =
  a[s]``, a whole hyperplane of an array onto another of the same array,
  and every such copy after it, become ONE ``remap_faces`` node: a
  ghost-layer refresh (NPB MG's ``comm3``, six copies) is one in-place
  pass (``core/slicing.py`` ``remap``), not six writes.
* ``fold_prolong`` (counted as ``rewrite_prolong``) — the five writes of a
  trilinear prolongation onto zeros (NPB MG's ``interp``) become ONE
  ``prolong`` node, which one pass writes (``core/slicing.py``
  ``prolong``).
* ``fold_add_transposed`` (counted as ``rewrite_add_transposed``) —
  ``B += A.T`` of rank-2 arrays becomes ONE ``add_transposed`` node: on a
  square grid of devices one block exchange, read transposed by the
  addition and ordered after ``B`` (``ops/transpose_sharded.py``).
* ``fold_stencil_update`` (counted as ``rewrite_stencil_update``) —
  ``v - sstencil(A, u)`` and ``u + sstencil(S, r)`` of float32 rank-3
  arrays become ONE ``stencil_update`` node, whose update the stencil
  kernel's own store writes (``ops/stencil_pallas.py``).
"""

from __future__ import annotations

import numpy as np

from ramba_tpu.core.expr import Const, Expr, Node, Scalar
from ramba_tpu.observe import registry as _registry
from ramba_tpu.resilience import faults as _faults

REDUCE_KINDS = {"mean", "nanmean", "sum", "nansum", "min", "max", "prod"}


def rewrite_arange_reshape(node: Node):
    """reshape(arange) -> fromfunction in the target shape/sharding
    (reference: ramba.py:4567-4598)."""
    if node.op != "reshape":
        return None
    (shape,) = node.static
    arg = node.args[0]
    if not (isinstance(arg, Node) and arg.op == "arange"):
        return None
    n, dtype, _spec = arg.static
    from ramba_tpu.parallel import mesh as _mesh

    spec = tuple(_mesh.default_spec(shape))
    start, step = arg.args
    shape = tuple(int(s) for s in shape)
    strides = []
    acc = 1
    for s in reversed(shape):
        strides.append(acc)
        acc *= s
    strides = tuple(reversed(strides))
    idx_dtype = "int64" if n > 2**31 else "int32"

    def fill_fn(*a):
        import jax.numpy as jnp

        idx = a[:-2]
        start_v, step_v = a[-2:]
        flat = 0
        for i, st in zip(idx, strides):
            flat = flat + i.astype(jnp.dtype(idx_dtype)) * st
        return (start_v + step_v * flat).astype(jnp.dtype(dtype))

    # hashable wrapper for cache stability across flushes
    filler = _HashedFill(("arange_reshape", shape, str(dtype), idx_dtype),
                         fill_fn)
    return Node(
        "fromfunction", (shape, dtype, spec, filler, True),
        [start, step], aval=None,
    )


class _HashedFill:
    """Wrap a function with a value-based hash key so structurally identical
    rewrites share one compile-cache entry."""

    __slots__ = ("key", "fn")

    def __init__(self, key, fn):
        self.key = key
        self.fn = fn

    def __call__(self, *args):
        return self.fn(*args)

    def __hash__(self):
        return hash(self.key)

    def __eq__(self, other):
        return isinstance(other, _HashedFill) and other.key == self.key


def _single_axis_gather(e: Expr):
    """Match getitem_adv with exactly one integer index array and full slices
    elsewhere.  Returns (base_expr, dim, index_const) or None."""
    if not (isinstance(e, Node) and e.op == "getitem_adv"):
        return None
    enc, arraypos = e.static
    if len(arraypos) != 1:
        return None
    dim = 0
    p = arraypos[0]
    for q, part in enumerate(enc):
        if q == p:
            break
        if part[0] == "n":
            return None
        if part[0] == "s" and part[1:] != (None, None, None):
            return None
        if part[0] == "i":
            return None
        dim += 1
    for q, part in enumerate(enc):
        if q == p:
            continue
        if part[0] != "s" or part[1:] != (None, None, None):
            return None
    idx = e.args[1]
    if not isinstance(idx, Const):
        return None
    return e.args[0], dim, idx


def rewrite_stack_reduce_advindex(node: Node):
    """stack([reduce(x[..., idx_g, ...], axis=dim) for g]) -> segment_reduce
    (reference: rewrite_stack_mean_advindex, ramba.py:4601-4677)."""
    if node.op != "stack" or len(node.args) < 2:
        return None
    (stack_axis,) = node.static
    kind = None
    dim = None
    base = None
    groups = []
    for a in node.args:
        if not (isinstance(a, Node) and a.op == "reduce"):
            return None
        k, raxis, keepdims, ddof = a.static
        if k not in REDUCE_KINDS or keepdims or ddof not in (None, 0):
            return None
        m = _single_axis_gather(a.args[0])
        if m is None:
            return None
        b, d, idx = m
        if raxis != d:
            return None
        if base is None:
            base, dim, kind = b, d, k
        elif b is not base or d != dim or k != kind:
            return None
        groups.append(np.asarray(idx.value))
    # full, disjoint coverage of the grouped dimension; duplicates inside a
    # single group would collapse under segment_reduce (the original sums
    # the element once per occurrence), so reject them too
    n = base.aval.shape[dim]
    if sum(len(g) for g in groups) != n:
        return None
    labels = np.full((n,), -1, np.int64)
    for g, idx in enumerate(groups):
        if idx.ndim != 1 or np.unique(idx).size != idx.size:
            return None
        if np.any(labels[idx] != -1):
            return None
        labels[idx] = g
    if np.any(labels < 0):
        return None
    from ramba_tpu.groupby import segment_node

    out = segment_node(base, Const(_to_device(labels.astype(np.int32))),
                       kind, len(groups), dim)
    # segment_reduce leaves groups on `dim`; stack puts them on stack_axis.
    if stack_axis != dim:
        out = Node("moveaxis", (dim, stack_axis), [out])
    return out


def rewrite_concat_binop_getitem(node: Node):
    """concatenate([binop(x[..., idx_g, ...], m[g]) for g]) ->
    binop(gather(x, cat(idx)), gather(m, group_of_position))
    (reference: rewrite_concatenate_binop_getitem, ramba.py:4680-4789).

    Two per-group operand forms are recognized:

    * plain ``m[g]`` — accepted only when trailing-alignment broadcasting
      places the gathered group axis exactly on the concat axis
      (x.ndim - dim == m.ndim - m_dim, and every m axis left of the group
      axis has size 1); anything else broadcasts differently before and
      after the rewrite, so it is left alone.
    * ``m[g][:, None]`` with 2-D x and m, groups on x axis 1 — the xarray
      climatology/anomaly idiom; lowered to take + transpose.
    """
    if node.op != "concatenate" or len(node.args) < 2:
        return None
    (axis,) = node.static
    base = None
    dim = None
    fname = None
    m_base = None
    swapped = None
    m_dim = None
    newaxis_form = None
    groups = []
    for gi, a in enumerate(node.args):
        if not (isinstance(a, Node) and a.op == "map" and len(a.args) == 2):
            return None
        (f,) = a.static
        lhs, rhs = a.args
        gl = _single_axis_gather(lhs)
        gr = _single_axis_gather(rhs)
        if gl is not None and gr is None:
            gather, other, sw = gl, rhs, False
        elif gr is not None and gl is None:
            gather, other, sw = gr, lhs, True
        else:
            return None
        b, d, idx = gather
        # other must be m[g] (optionally followed by one trailing newaxis)
        sel = _int_select_chain(other, gi)
        if sel is None:
            return None
        mb, mdim, nform = sel
        if base is None:
            base, dim, fname, m_base, swapped, m_dim, newaxis_form = (
                b, d, f, mb, sw, mdim, nform
            )
        elif (b is not base or d != dim or f != fname or mb is not m_base
              or sw != swapped or mdim != m_dim or nform != newaxis_form):
            return None
        groups.append(np.asarray(idx.value))
    if axis != dim:
        return None
    x_ndim = base.aval.ndim
    m_shape = tuple(m_base.aval.shape)
    if newaxis_form:
        # m[g][:, None]: supported shape pattern is 2-D x grouped on axis 1
        # with m laid out (groups, x_rows)
        if not (x_ndim == 2 and dim == 1 and len(m_shape) == 2
                and m_dim == 0):
            return None
    else:
        # plain m[g]: gathered group axis must land on the concat axis
        # under numpy trailing alignment, with no real axes left of it
        if len(m_shape) - m_dim != x_ndim - dim:
            return None
        if any(s != 1 for s in m_shape[:m_dim]):
            return None
    cat_idx = np.concatenate(groups)
    pos_group = np.concatenate(
        [np.full((len(g),), gi, np.int32) for gi, g in enumerate(groups)]
    )
    enc = tuple(
        ("i", 0) if q == dim else ("s", None, None, None)
        for q in range(x_ndim)
    )
    n = base.aval.shape[dim]
    if (not newaxis_form and m_dim == dim and len(m_shape) == x_ndim
            and np.array_equal(np.sort(cat_idx), np.arange(n))):
        # the groups cover every position once: the direct call's node
        # (``gb <op> m``, RambaGroupby._binop), then in the groups' order
        from ramba_tpu.groupby import broadcast_node

        labels = np.empty((n,), np.int32)
        labels[cat_idx] = pos_group
        out = broadcast_node(fname, base, m_base, Const(_to_device(labels)),
                             dim, swapped)
        if np.array_equal(cat_idx, np.arange(n)):
            return out
        return Node("getitem_adv", (enc, (dim,)),
                    [out, Const(_to_device(cat_idx))])
    gathered_x = Node(
        "getitem_adv", (enc, (dim,)),
        [base, Const(_to_device(cat_idx))],
    )
    gathered_m = Node(
        "take", (m_dim, "clip"), [m_base, Const(_to_device(pos_group))]
    )
    if newaxis_form:
        # (n_positions, x_rows) -> (x_rows, n_positions) to align with x
        gathered_m = Node("permute", ((1, 0),), [gathered_m])
    args = [gathered_m, gathered_x] if swapped else [gathered_x, gathered_m]
    return Node("map", (fname,), args)


def _int_select(e: Expr, expect: int):
    """Match getitem picking integer index ``expect`` on exactly one dim,
    full slices elsewhere.  Returns (base, dim) or None."""
    if not (isinstance(e, Node) and e.op == "getitem"):
        return None
    (enc,) = e.static
    dim = None
    at = 0
    for part in enc:
        if part[0] == "i":
            if dim is not None or part[1] != expect:
                return None
            dim = at
            at += 1
        elif part[0] == "s" and part[1:] == (None, None, None):
            at += 1
        else:
            return None
    if dim is None:
        return None
    return e.args[0], dim


def _int_select_chain(e: Expr, expect: int):
    """Match ``m[g]`` or ``m[g][:, None]``.  Returns
    (m_base, group_dim, has_trailing_newaxis) or None."""
    sel = _int_select(e, expect)
    if sel is not None:
        return sel[0], sel[1], False
    # one wrapping getitem of full slices + a single trailing newaxis
    if not (isinstance(e, Node) and e.op == "getitem"):
        return None
    (enc,) = e.static
    if len(enc) < 1 or enc[-1] != ("n",):
        return None
    if any(part[0] != "s" or part[1:] != (None, None, None)
           for part in enc[:-1]):
        return None
    inner = _int_select(e.args[0], expect)
    if inner is None:
        return None
    return inner[0], inner[1], True


def _to_device(x: np.ndarray):
    import jax.numpy as jnp

    return jnp.asarray(x)


def rewrite_align_operand_layouts(node: Node):
    """Fused elementwise operands whose device layouts disagree: wrap
    the minority operands in ``shard_hint`` nodes targeting the most-
    sharded operand's layout, so GSPMD lowers an explicit resharding
    collective (all-to-all / collective-permute — the same lowering
    ``parallel.reshard`` schedules) instead of falling back to
    replicating one side.  Only full-shape concrete leaves participate
    — broadcasting operands, lazy subtrees, and spilled buffers are
    left for GSPMD's own propagation."""
    if node.op != "map" or len(node.args) < 2 or node.aval is None:
        return None
    from jax.sharding import NamedSharding

    from ramba_tpu.parallel import mesh as _mesh

    try:
        mesh = _mesh.get_mesh()
    except Exception:
        return None
    if mesh.size <= 1:
        return None
    out_shape = tuple(node.aval.shape)

    def _leaf_spec(a: Expr):
        if not isinstance(a, Const):
            return None
        v = a.value
        sh = getattr(v, "sharding", None)
        if not isinstance(sh, NamedSharding) or sh.mesh != mesh:
            return None
        if tuple(getattr(v, "shape", ())) != out_shape:
            return None
        entries = tuple(sh.spec)
        while entries and entries[-1] is None:
            entries = entries[:-1]
        return entries

    shaped = [(i, s) for i, s in ((i, _leaf_spec(a))
                                  for i, a in enumerate(node.args))
              if s is not None]
    if len(shaped) < 2 or len({s for _, s in shaped}) < 2:
        return None
    # Dominant layout = the one sharding the most dims (replication is
    # what this rule exists to avoid); ties go to the earliest operand.
    dom = None
    for _, s in shaped:
        if s and (dom is None
                  or sum(1 for e in s if e) > sum(1 for e in dom if e)):
            dom = s
    if not dom:
        return None
    new_args = list(node.args)
    changed = False
    for i, s in shaped:
        if s != dom:
            new_args[i] = Node("shard_hint", (dom,), [node.args[i]])
            changed = True
    if not changed:
        return None
    return Node(node.op, node.static, new_args, aval=node.aval)


def rewrite_reduce_group_broadcast(node: Node):
    """reduce(elementwise(x, take(m, labels))) over everything ->
    segment_mapreduce: the group-broadcast operand is never stored
    (groupby.fuse_broadcast_reduce says when)."""
    if node.op != "reduce":
        return None
    from ramba_tpu.groupby import fuse_broadcast_reduce

    return fuse_broadcast_reduce(node)


def _face(enc, shape):
    """``(axis, index, kind)`` of a basic index that selects ONE whole
    hyperplane, an integer (kind ``"i"``) or a unit-stride slice of
    length one (``"s"``) on one axis and full slices on the others, or
    None."""
    if len(enc) > len(shape):
        return None
    found = None
    for ax, part in enumerate(enc):
        n = shape[ax]
        if part[0] == "i":
            hit = (ax, part[1] + (n if part[1] < 0 else 0), "i")
        elif part[0] == "s":
            start, stop, step = slice(*part[1:]).indices(n)
            if (start, stop, step) == (0, n, 1):
                continue
            if step != 1 or stop - start != 1:
                return None
            hit = (ax, start, "s")
        else:  # a new axis, an ellipsis
            return None
        if found is not None:
            return None
        found = hit
    return found


def fold_face_copy(x: Expr, dst_enc, src_enc):
    """The node of ``x[dst] = x[src]``, both indexes one whole hyperplane
    of the same axis of ``x``, counted as a firing of
    ``rewrite_face_copies``; else None.

    The state after any number of such copies is ``y[i, j, ..] = x[w0(i),
    w1(j), ..]``: a copy ``d <- s`` on axis ``ax`` sets ``w_ax(d)`` to the
    CURRENT ``w_ax(s)``, so copies on one axis compose in the order given
    and copies on different axes commute.  The node keeps each axis' pairs
    in order, a copy onto a ``remap_faces`` node joining its pairs;
    ``slicing.remap`` composes them.  Same array, same dtype, same shape
    on both sides: nothing is cast or broadcast.

    ``ndarray.__setitem__`` asks where the script writes the copy (a basic
    view of an array assigned to a window of the same array), before a
    ``getitem`` or a ``setitem`` node exists, and this is no entry of
    ``RULES``: a rule at the flush has every node above a firing rebuilt
    by ``rewrite_roots``, 17,142 nodes a ``mg-C`` solve with the
    collector's full pause in prepare (PERF.md section 6, PR 35), and no
    script reaches the flush with the pattern but through
    ``__setitem__``."""
    shape = tuple(x.aval.shape)
    dst, src = _face(dst_enc, shape), _face(src_enc, shape)
    if (dst is None or src is None or dst[0] != src[0] or dst[2] != src[2]
            or dst[1] == src[1]):
        return None
    ax, aval = dst[0], x.aval
    if isinstance(x, Node) and x.op == "remap_faces":
        (maps,), (x,) = x.static, x.args
    else:
        maps = ((),) * len(shape)
    maps = maps[:ax] + (maps[ax] + ((dst[1], src[1]),),) + maps[ax + 1:]
    stats["rewrite_face_copies"] += 1
    _registry.inc("rewrite.rewrite_face_copies")
    return Node("remap_faces", (maps,), [x], aval=aval)


def _window(enc, shape):
    """``(start, stop, step)`` on every axis of ``shape`` of a basic index
    of slices alone, or None."""
    if len(enc) > len(shape) or any(part[0] != "s" for part in enc):
        return None
    return (tuple(slice(*part[1:]).indices(n) for part, n in zip(enc, shape))
            + tuple((0, n, 1) for n in shape[len(enc):]))


def _on_axis(shape, ax, start, stop):
    """The window ``start:stop`` on axis ``ax`` of ``shape``, whole on the
    others."""
    return tuple((start, stop, 1) if a == ax else (0, n, 1)
                 for a, n in enumerate(shape))


def _reads(e, x, window) -> bool:
    """Whether ``e`` is the read of ``window`` of ``x``."""
    return (isinstance(e, Node) and e.op == "getitem" and e.args[0] is x
            and _window(e.static[0], tuple(x.aval.shape)) == window)


def _operands(e, fname):
    """Both orders of the two operands of the elementwise ``fname`` node
    ``e``, or nothing: the two orders give the same bits."""
    if (isinstance(e, Node) and e.op == "map" and e.static == (fname,)
            and len(e.args) == 2):
        return (tuple(e.args), tuple(e.args[::-1]))
    return ()


def fold_prolong(x: Expr, dst_enc, v: Expr):
    """The node of ``x[dst] = v``, a write of a trilinear prolongation onto
    zeros, counted as a firing of ``rewrite_prolong``; else None.

    The script (NPB MG's ``interp``): ``f[0::2, 0::2, 0::2] = z[:-1, :-1,
    :-1]`` onto a ``zeros`` of twice ``z``'s extents less two, then along
    each axis in turn ``f[1:-1] = f[1:-1] + 0.5 * (f[2:] + f[:-2])``.  The
    first write makes a ``prolong`` node with no axis done; each pass along
    the next axis joins it.  Rank 3, float32 on both sides (nothing is
    cast), a zero that is +0, the scalar 0.5; any other write, axis order,
    scalar or dtype builds the script's nodes.

    As ``fold_face_copy``, ``ndarray.__setitem__`` asks where the script
    writes, and this is no entry of ``RULES``: a rule at the flush would
    rebuild every node above a firing (PERF.md section 6, PR 35)."""
    if not (isinstance(x, Node) and x.op in ("full", "prolong")):
        return None
    shape = tuple(x.aval.shape)
    if (len(shape) != 3 or x.aval.dtype != np.float32
            or v.aval.dtype != np.float32):
        return None
    window = _window(dst_enc, shape)
    if x.op == "full":
        fill = x.args[0]
        if not (isinstance(fill, Scalar) and fill.value == 0
                and not np.signbit(np.real(fill.value))
                and isinstance(v, Node) and v.op == "getitem"
                and window == tuple((0, n, 2) for n in shape)):
            return None
        (z,) = v.args
        coarse = tuple(z.aval.shape)
        if (len(coarse) != 3
                or any(n != 2 * c - 2 or c < 3 for n, c in zip(shape, coarse))
                or not _reads(v, z, tuple((0, c - 1, 1) for c in coarse))):
            return None
        node = Node("prolong", (0, x.static[2]), [z], aval=x.aval)
    else:
        done, spec = x.static
        if done == 3:
            return None
        n = shape[done]
        mid, up, dn = (_on_axis(shape, done, a, b)
                       for a, b in ((1, n - 1), (2, n), (0, n - 2)))

        def half_sum(e):
            return any(isinstance(s, Scalar) and s.value == 0.5
                       and any(_reads(a, x, up) and _reads(b, x, dn)
                               for a, b in _operands(t, "add"))
                       for s, t in _operands(e, "multiply"))

        if window != mid or not any(_reads(a, x, mid) and half_sum(b)
                                    for a, b in _operands(v, "add")):
            return None
        node = Node("prolong", (done + 1, spec), x.args, aval=x.aval)
    stats["rewrite_prolong"] += 1
    _registry.inc("rewrite.rewrite_prolong")
    return node


def fold_add_transposed(x: Expr, y: Expr):
    """The node of ``x + y`` where ``y`` is the transpose of a rank-2
    array of ``x``'s shape and dtype (``B += A.T``), counted as a firing
    of ``rewrite_add_transposed``; else None.  The transpose is still
    made, once per firing: ``ops/transpose_sharded.py`` reads the
    exchanged block transposed in the addition and orders the exchange
    after ``x``.  ``ndarray`` asks where the script writes the addition,
    as for ``fold_face_copy``."""
    if not (isinstance(y, Node) and y.op == "permute"
            and y.static == ((1, 0),) and len(x.aval.shape) == 2
            and tuple(x.aval.shape) == tuple(y.aval.shape)
            and x.aval.dtype == y.aval.dtype):
        return None
    stats["rewrite_add_transposed"] += 1
    _registry.inc("rewrite.rewrite_add_transposed")
    return Node("add_transposed", (), [x, y.args[0]], aval=y.aval)


def fold_stencil_update(fname: str, operands):
    """The node of ``base - s``, ``base + s`` or ``s + base`` where ``s``
    is a rank-3 ``stencil`` node and ``base`` has its shape and dtype,
    float32 both, nothing broadcast or cast (NPB MG's ``resid`` and
    ``psinv``), counted as a firing of ``rewrite_stencil_update``; else
    None.  The ``stencil_update`` node (``skeletons``) takes the stencil's
    static and operands after the epilogue ``(fname, at)``, ``at`` the
    base's place: on one chip the rank-3 kernel's own store writes the
    update, one pass over HBM fewer.  Any other operand, rank, dtype or
    stencil (``stencil_iter``, a scalar in between) builds the script's
    nodes.  A script that also reads ``s`` elsewhere gets the stencil
    computed once more there.  ``ndarray`` asks where the script writes
    the operation, as for ``fold_face_copy``: no entry of ``RULES``."""
    if fname not in ("subtract", "add") or len(operands) != 2:
        return None
    for at in (0, 1) if fname == "add" else (0,):
        base, s = operands[at], operands[1 - at]
        if (isinstance(s, Node) and s.op == "stencil"
                and len(s.aval.shape) == 3
                and s.aval.dtype == np.float32
                and base.aval.dtype == np.float32
                and tuple(base.aval.shape) == tuple(s.aval.shape)
                and not getattr(s.aval, "weak_type", False)
                and not getattr(base.aval, "weak_type", False)):
            stats["rewrite_stencil_update"] += 1
            _registry.inc("rewrite.rewrite_stencil_update")
            return Node("stencil_update", ((fname, at), *s.static),
                        [base, *s.args], aval=s.aval)
    return None


RULES = [
    rewrite_arange_reshape,
    rewrite_stack_reduce_advindex,
    rewrite_concat_binop_getitem,
    rewrite_reduce_group_broadcast,
    rewrite_align_operand_layouts,
]

# Per-rule fire counts (observability; lets end-to-end tests assert that an
# xarray/pandas idiom actually took the rewritten path — cf. the reference's
# DAG-rewrite debug prints, ramba.py:4567-4789).
stats = {rule.__name__: 0 for rule in RULES}
stats["rewrite_face_copies"] = 0  # fold_face_copy: fired at the build
stats["rewrite_prolong"] = 0  # fold_prolong: fired at the build
stats["rewrite_add_transposed"] = 0  # fold_add_transposed: likewise
stats["rewrite_stencil_update"] = 0  # fold_stencil_update: likewise


def rewrite_roots(roots):
    """Apply RULES bottom-up across the expression forest (iterative — chains
    can be deeper than the Python recursion limit, cf. the fuser's iterative
    linearizer)."""
    _faults.check("rewrite")
    memo: dict[int, Expr] = {}
    out = []
    for root in roots:
        stack = [(root, False)]
        while stack:
            e, done = stack.pop()
            if id(e) in memo:
                continue
            if not isinstance(e, Node):
                memo[id(e)] = e
                continue
            if not done:
                stack.append((e, True))
                for a in e.args:
                    if id(a) not in memo:
                        stack.append((a, False))
                continue
            new_args = [memo[id(a)] for a in e.args]
            if all(n is o for n, o in zip(new_args, e.args)):
                cand = e
            else:
                cand = Node(e.op, e.static, new_args, aval=e.aval)
            for rule in RULES:
                try:
                    r = rule(cand)
                except Exception:
                    # Matching is meant to be defensive (a mismatch returns
                    # None); a rule that *raises* has a bug, and silently
                    # eating it hides the bug forever — count it so the
                    # miss shows up in diagnostics.
                    _registry.inc("resilience.rewrite_rule_error")
                    _registry.inc(
                        f"resilience.rewrite_rule_error.{rule.__name__}"
                    )
                    r = None
                if r is not None:
                    stats[rule.__name__] += 1
                    _registry.inc(f"rewrite.{rule.__name__}")
                    cand = r
                    break
            memo[id(e)] = cand
        out.append(memo[id(root)])
    return out
