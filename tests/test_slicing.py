"""``core/slicing.py``: a basic index reads and writes what NumPy reads and
writes, bit for bit, on the path XLA serves and on the one through the
MXU (one device, a stride on the last axis; a long row in tiles, so that
its cost follows its length), a major and the second-last axis are never
strided by one slice, and a chain of writes is right on the suite's mesh,
where jax's own scatter is not.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

import ramba_tpu as rt
from ramba_tpu.core import fuser, slicing
from ramba_tpu.parallel import mesh as mesh_mod

_MULTIPROC = jax.process_count() > 1

S = slice
INDICES = [
    (S(2, None, 2), S(2, None, 2), S(2, None, 2)),
    (S(1, None, 2), S(0, None, 2), S(1, None, 2)),
    (S(None), S(None), S(None, None, 3)),
    (S(None), S(1, -1, 2)),
    (S(0, -1, 3), S(None)),
    (0, S(None), S(None, None, 2)),
    (S(None), -1, S(1, None, 2)),
    (Ellipsis, S(1, None, 2)),
    (S(None), S(None), 0),
    (S(1, -1), S(1, -1), S(1, -1)),
    (S(4, 4), S(None, None, 2)),
    (S(None, None, 2),),
]


@pytest.fixture
def one_device():
    """The lane and sublane strides go through the MXU on one device."""
    fuser.flush()
    old = mesh_mod.get_mesh()
    mesh_mod.set_mesh(Mesh(np.array(jax.devices()[:1]), ("d0",)))
    try:
        yield
    finally:
        fuser.flush()
        mesh_mod.set_mesh(old)


def same_bits(got, want):
    got, want = np.asarray(got), np.asarray(want)
    return (got.shape == want.shape and got.dtype == want.dtype
            and got.tobytes() == np.ascontiguousarray(want).tobytes())


def field(dtype, shape=(9, 10, 11)):
    a = np.random.default_rng(7).standard_normal(shape) * 100
    a = np.array(jnp.asarray(a).astype(dtype))
    if np.issubdtype(a.dtype, np.floating) or a.dtype == jnp.bfloat16:
        a.flat[1], a.flat[2], a.flat[3] = np.nan, -np.inf, -0.0
    return a


@pytest.mark.skipif(_MULTIPROC, reason="installs a local mesh")
@pytest.mark.parametrize("tile", [None, 2], ids=["whole", "tiled"])
@pytest.mark.parametrize("dtype", ["float32", "int32", "bfloat16"])
@pytest.mark.parametrize("idx", INDICES, ids=[str(i) for i in
                                               range(len(INDICES))])
def test_reads_and_writes_through_the_mxu_are_numpys(idx, dtype, tile,
                                                     one_device, monkeypatch):
    monkeypatch.setattr(slicing, "MXU_MIN_ELEMENTS", 1)
    if tile:  # the eleven lanes in tiles of two outputs, the last ragged
        monkeypatch.setattr(slicing, "LANE_WHOLE", tile)
        monkeypatch.setattr(slicing, "LANE_TILE", tile)
    a = field(jnp.dtype(dtype))
    x = jnp.asarray(a)
    want = a[idx]
    assert same_bits(slicing.take(x, idx), want)
    v = np.array(jnp.asarray(np.arange(want.size).reshape(want.shape)
                             - 7.5).astype(a.dtype))
    w = a.copy()
    w[idx] = v
    assert same_bits(slicing.put(x, idx, jnp.asarray(v)), w)
    w[idx] = v.flat[0] if v.size else 0  # a scalar, broadcast
    assert same_bits(slicing.put(x, idx, jnp.asarray(
        v.flat[0] if v.size else 0, dtype=a.dtype)), w)


@pytest.mark.skipif(_MULTIPROC, reason="installs a local mesh")
def test_which_reads_take_the_product(one_device):
    def lanes(x, idx):
        return slicing._lanes_through_mxu(x, slicing._axes(idx, x.shape))

    x = jnp.zeros((64, 64, 64), jnp.float32)
    assert lanes(x, (S(None, None, 2),) * 3)
    assert lanes(x, (S(None), S(None), S(1, None, 3)))
    assert not lanes(x, (S(None, None, 2), S(None, None, 2), S(None)))
    assert not lanes(jnp.zeros((8, 8, 8), jnp.float32),
                     (S(None, None, 2),) * 3)  # small: the launch is the cost
    wide = jnp.zeros((64, 4096), jnp.float32)
    assert lanes(wide, (S(None), S(None, None, slicing.MXU_MAX_STEP)))
    # few elements kept: the gather reads them, the product would read all
    assert not lanes(wide, (S(None), S(None, None, slicing.MXU_MAX_STEP + 1)))
    for other in (jnp.zeros((64, 64, 64), jnp.int8),
                  jnp.zeros((64, 64, 64), jnp.complex64),
                  jnp.zeros((1 << 18,), jnp.float32)):
        assert not lanes(other, (S(None, None, 2),) * other.ndim)
    # what jax's own lowering has to say stays jax's to say
    assert slicing._axes((None, S(None)), (4, 4)) is None
    assert slicing._axes((S(None, None, -1),), (4,)) is None
    assert slicing._axes((7,), (4,)) is None


def _primitives(jaxpr, name):
    return [e for e in jaxpr.jaxpr.eqns if e.primitive.name == name]


@pytest.mark.skipif(_MULTIPROC, reason="installs a local mesh")
@pytest.mark.parametrize("step", [2, 3])
def test_a_long_row_costs_its_length_not_its_square(step, one_device):
    """``x[:, ::2]`` of a (64, 2^20) array: no operand of a product is
    larger than one tile's selection matrix (traced only: nothing of that
    size is made here), and a row of 5,001 is NumPy's bit for bit."""
    idx = (S(None), S(1, None, step))
    big = jax.ShapeDtypeStruct((64, 1 << 20), jnp.float32)
    assert slicing._lanes_through_mxu(big, slicing._axes(idx, big.shape))
    tile = (slicing.LANE_TILE * step, slicing.LANE_TILE)
    read = jax.make_jaxpr(lambda x: slicing.take(x, idx))(big)
    kept = read.out_avals[0].shape
    write = jax.make_jaxpr(lambda x, v: slicing.put(x, idx, v))(
        big, jax.ShapeDtypeStruct(kept, jnp.float32))
    for jaxpr, sel in ((read, tile), (write, tile[::-1])):
        dots = _primitives(jaxpr, "dot_general")
        assert len(dots) == 4  # a byte plane each
        assert {d.invars[1].aval.shape for d in dots} == {sel}
    a = field(np.float32, (3, 5001))
    want = a[idx]
    assert same_bits(slicing.take(jnp.asarray(a), idx), want)
    v = (np.arange(want.size, dtype=np.float32) - 7.5).reshape(want.shape)
    w = a.copy()
    w[idx] = v
    assert same_bits(slicing.put(jnp.asarray(a), idx, jnp.asarray(v)), w)


@pytest.mark.parametrize("idx", [
    (S(None, None, 2), S(None, None, 2)),
    (S(1, None, 3), S(None, None, 2), S(None, None, 2)),
    (S(None), S(None, None, 2), S(1, None, 2), S(None)),
], ids=["major+second-last", "all-three", "rank-4"])
def test_a_major_and_the_second_last_axis_are_never_strided_together(idx):
    """As one slice that read halts the TPU core (PERF.md section 6, PR
    32): at any size, on the suite's mesh as on one device, the major
    axes are sliced first and the last two behind a barrier."""
    shape = (6, 10, 12) if len(idx) < 4 else (3, 6, 10, 12)
    a = np.arange(np.prod(shape), dtype=np.float32).reshape(shape)
    jaxpr = jax.make_jaxpr(lambda x: slicing.take(x, idx))(jnp.asarray(a))
    assert _primitives(jaxpr, "optimization_barrier")
    slices = _primitives(jaxpr, "slice")
    assert len(slices) >= 2
    for eq in slices:
        strides = eq.params["strides"] or (1,) * len(shape)
        assert not (strides[-2] > 1 and any(st > 1 for st in strides[:-2]))
    assert same_bits(slicing.take(jnp.asarray(a), idx), a[idx])
    np.testing.assert_array_equal(np.asarray(rt.fromarray(a)[idx]), a[idx])


@pytest.mark.parametrize("shape,idx", [
    ((3, 5001), (S(None), S(None, None, 2))),
    ((4100, 3), (S(1, None, 2), S(None))),
    ((2, 2100, 3), (S(None), S(None, None, 3), S(None, None, 2))),
], ids=["lanes", "rows", "second-last"])
def test_a_long_stride_off_the_mxu_is_written_by_jaxs_scatter(shape, idx):
    """``lax.pad`` with zeros between the elements of a long dimension
    takes XLA:TPU minutes to compile (2,498 s for ``x[:, ::2] = v`` of a
    (64, 2^20) array: PERF.md section 6, PR 32): over ``PAD_MAX_EXTENT``
    it is not asked for.  (The suite's mesh has eight devices, so no
    stride goes through the MXU here.)"""
    a = field(np.float32, shape)
    want = a.copy()
    v = np.arange(want[idx].size, dtype=np.float32).reshape(want[idx].shape)
    want[idx] = v
    x = jnp.asarray(a)
    jaxpr = jax.make_jaxpr(lambda x, v: slicing.put(x, idx, v))(x, v)
    assert _primitives(jaxpr, "scatter") and not _primitives(jaxpr, "pad")
    assert same_bits(slicing.put(x, idx, jnp.asarray(v)), want)
    short = np.zeros(tuple(min(n, 2000) for n in shape), np.float32)
    jaxpr = jax.make_jaxpr(lambda x, v: slicing.put(x, idx, v))(
        short, short[idx])
    assert _primitives(jaxpr, "pad") and not _primitives(jaxpr, "scatter")


@pytest.mark.skipif(_MULTIPROC, reason="installs a local mesh")
def test_through_the_mxu_a_long_row_is_written_without_the_scatter(
        one_device):
    big = jax.ShapeDtypeStruct((64, 1 << 20), jnp.float32)
    idx = (S(None), S(None, None, 2))
    jaxpr = jax.make_jaxpr(lambda x, v: slicing.put(x, idx, v))(
        big, jax.ShapeDtypeStruct((64, 1 << 19), jnp.float32))
    assert not _primitives(jaxpr, "scatter")
    # rows strided as well, over more of them than the pad is asked for
    both = (S(None, None, 2), S(None, None, 2))
    tall = jax.ShapeDtypeStruct((4100, 4096), jnp.float32)
    jaxpr = jax.make_jaxpr(lambda x, v: slicing.put(x, both, v))(
        tall, jax.ShapeDtypeStruct((2050, 2048), jnp.float32))
    assert _primitives(jaxpr, "scatter")
    assert not _primitives(jaxpr, "dot_general")


def test_on_a_mesh_the_strides_stay_with_xla():
    if mesh_mod.get_mesh().devices.size == 1:
        pytest.skip("needs the suite's mesh")
    x = jnp.zeros((64, 64, 64), jnp.float32)
    assert not slicing._lanes_through_mxu(x, slicing._axes(
        (S(None, None, 2),) * 3, x.shape))


def test_a_face_written_after_the_interior_is_right_on_the_mesh():
    """jax's ``x.at[...].set`` is a scatter, and XLA's partitioner (jax
    0.9.0) gets this chain of two wrong on zeros sharded over two axes;
    as ``dynamic_update_slice`` it is right."""
    m = 8
    inner = np.random.default_rng(1).standard_normal((m,) * 3).astype(
        np.float32)
    c = rt.zeros((m + 2,) * 3, dtype=np.float32)
    c[1:-1, 1:-1, 1:-1] = rt.fromarray(inner)
    want = np.pad(inner, 1)
    for ax in range(3):
        lo, hi, first, last = ([S(None)] * 3 for _ in range(4))
        lo[ax], hi[ax], first[ax], last[ax] = 0, m + 1, 1, m
        for dst, src in ((lo, last), (hi, first)):
            c[tuple(dst)] = c[tuple(src)]
            want[tuple(dst)] = want[tuple(src)]
    np.testing.assert_array_equal(np.asarray(c), want)


def test_a_strided_write_reads_back_through_the_public_api():
    a = np.arange(6 * 10 * 12, dtype=np.float32).reshape(6, 10, 12)
    x = rt.fromarray(a)
    x[1::2, ::2, 1::2] = x[1::2, ::2, 1::2] * 2.0 + 1.0
    a[1::2, ::2, 1::2] = a[1::2, ::2, 1::2] * 2.0 + 1.0
    np.testing.assert_array_equal(np.asarray(x), a)
    np.testing.assert_array_equal(np.asarray(x[::3, 1::4, ::5]),
                                  a[::3, 1::4, ::5])


# -- whole faces copied onto faces of the same array: one node, one pass ------
from ramba_tpu import common, diagnostics  # noqa: E402
from ramba_tpu.core import rewrite  # noqa: E402
from ramba_tpu.core.expr import Node  # noqa: E402


def _faces(expr):
    """The ``remap_faces`` nodes under ``expr`` and every other op."""
    seen, nodes, others, stack = set(), [], [], [expr]
    while stack:
        e = stack.pop()
        if id(e) in seen or not isinstance(e, Node):
            continue
        seen.add(id(e))
        (nodes if e.op == "remap_faces" else others).append(e)
        stack.extend(e.args)
    return nodes, sorted(e.op for e in others)


def _moved(before, name):
    return diagnostics.counters().get(name, 0) - before.get(name, 0)


def folded(script):
    """The expression a script's copies leave, folded where it wrote them
    (``ndarray.__setitem__``), as the flush's rules then leave it."""
    expr = script().read_expr()
    (root,) = rewrite.rewrite_roots([expr])
    assert root is expr
    return root


def test_comm3_is_six_firings_and_one_node():
    from benchmark.programs import nas_mg

    m = 8
    a = rt.fromarray(field(np.float32, (m + 2,) * 3))
    fired = rewrite.stats["rewrite_face_copies"]
    before = diagnostics.counters()
    root = folded(lambda: nas_mg.comm3(a * 2.0))
    assert rewrite.stats["rewrite_face_copies"] - fired == 6
    assert _moved(before, "rewrite.rewrite_face_copies") == 6
    assert root.op == "remap_faces" and root.args[0].op == "map"
    assert root.static == ((((0, m), (m + 1, 1)),) * 3,)
    assert root.aval.shape == (m + 2,) * 3
    # no getitem and no setitem was ever built
    assert _faces(root) == ([root], ["map"])


def _copies(a, chain):
    """``a[.., d, ..] = a[.., s, ..]`` for each (axis, d, s) of ``chain``,
    on a NumPy or a ramba array."""
    for ax, d, s in chain:
        dst, src = [S(None)] * a.ndim, [S(None)] * a.ndim
        dst[ax], src[ax] = d, s
        a[tuple(dst)] = a[tuple(src)]
    return a


CHAINS = {
    "there-and-back": [(0, 0, 1), (0, 1, 0)],
    "a-swap-that-is-none": [(1, 0, 5), (1, 5, 0)],
    "a-shift-down": [(0, 0, 1), (0, 1, 2), (0, 2, 3)],
    "a-shift-up": [(2, 3, 2), (2, 2, 1), (2, 1, 0)],
    "written-twice": [(1, 4, 0), (1, 4, 7), (1, 0, 4)],
    "axes-interleaved": [(0, 0, 6), (2, 0, 9), (0, 7, 0), (1, 9, 1),
                         (2, 10, 0), (0, 3, 7)],
    "negative": [(0, -1, -2), (1, 0, -1), (2, -1, 1)],
}


@pytest.mark.parametrize("dtype", ["float32", "int32", "int16", "uint8"])
@pytest.mark.parametrize("chain", sorted(CHAINS))
def test_copies_on_one_axis_compose_in_order(chain, dtype):
    a = field(jnp.dtype(dtype))
    x = rt.fromarray(a) + 0
    before = diagnostics.counters()
    nodes, others = _faces(folded(lambda: _copies(x, CHAINS[chain])))
    assert len(nodes) == 1 and others == ["map"]
    assert _moved(before, "rewrite.rewrite_face_copies") == len(CHAINS[chain])
    assert same_bits(x, _copies(a.copy(), CHAINS[chain]))
    assert _moved(before, "faces.path.dus") == 1


def test_a_length_one_slice_is_a_face_and_joins_the_integers_node():
    a = field(np.float32)
    x = rt.fromarray(a) + 0
    x[:, 0:1] = x[:, 8:9]
    x[:, 9] = x[:, 1]
    a[:, 0:1] = a[:, 8:9]
    a[:, 9] = a[:, 1]
    root = folded(lambda: x)
    assert root.op == "remap_faces"
    assert root.static == (((), ((0, 8), (9, 1)), ()),)
    assert same_bits(x, a)


def _partial(x, y):
    x[0, 1:] = x[1, 1:]


def _broadcast(x, y):
    x[:, 0] = x[:, 1, 0:1]


def _cast(x, y):
    x[0] = x[1].astype(np.float64)


def _another_array(x, y):
    x[0] = y[1]


def _another_value(x, y):
    x[2] = (x + 0)[3]


def _strided(x, y):
    x[::2] = x[1::2]


def _negative_step(x, y):
    x[0:1] = x[2:0:-1][0:1]


def _onto_itself(x, y):
    x[..., 3] = x[..., 3]


def _slice_from_integer(x, y):
    x[0:1] = x[1]


def _across_axes(x, y):
    x[1] = x[:, 1]  # a square array


def _two_hyperplanes(x, y):
    x[0:2] = x[2:4]


def _new_axis(x, y):
    x[None, 0] = x[None, 1]


def _a_view_of_a_view(x, y):
    x[1:-1][0] = x[1:-1][1]  # written through a view: another base


NOT_FACE_COPIES = [_partial, _broadcast, _cast, _another_array,
                   _another_value, _strided, _negative_step, _onto_itself,
                   _slice_from_integer, _across_axes, _two_hyperplanes,
                   _new_axis, _a_view_of_a_view]


@pytest.mark.parametrize("write", NOT_FACE_COPIES,
                         ids=lambda f: f.__name__.strip("_"))
def test_anything_else_stays_a_setitem(write):
    a = field(np.float32, (10, 10, 10))
    b = field(np.float32, (10, 10, 10))[::-1].copy()
    x, y = rt.fromarray(a), rt.fromarray(b)
    fired = rewrite.stats["rewrite_face_copies"]
    before = diagnostics.counters()
    write(x, y)
    nodes, others = _faces(folded(lambda: x))
    assert not nodes and "setitem" in others
    assert rewrite.stats["rewrite_face_copies"] == fired
    write(a, b)
    assert same_bits(x, a)
    assert not _moved(before, "faces.path.dus")
    assert not _moved(before, "rewrite.rewrite_face_copies")


def test_with_the_rewriter_off_the_copies_are_the_writes_they_were(
        monkeypatch):
    from benchmark.programs import nas_mg

    a = field(np.float32, (10, 10, 10))
    monkeypatch.setattr(common, "rewrite_enabled", False)
    before = diagnostics.counters()
    got = np.asarray(nas_mg.comm3(rt.fromarray(a) * 1.0))
    assert not _moved(before, "faces.path.dus")
    assert same_bits(got, nas_mg.comm3(a.copy()))


def _halo(shape, kind):
    """The copies of a boundary refresh, axis by axis: ``periodic`` (NPB's
    ``comm3``), ``reflecting``, or ``wide`` (two periodic layers a
    side)."""
    chain = []
    for ax, n in enumerate(shape):
        if kind == "periodic":
            chain += [(ax, 0, n - 2), (ax, n - 1, 1)]
        elif kind == "reflecting":
            chain += [(ax, 0, 1), (ax, -1, -2)]
        else:
            chain += [(ax, 0, n - 4), (ax, 1, n - 3), (ax, n - 2, 2),
                      (ax, n - 1, 3)]
    return chain


RANKS = {1: (12,), 2: (9, 11), 3: (9, 10, 11), 4: (6, 7, 8, 9)}


@pytest.mark.parametrize("where", ["mesh", "one-device"])
@pytest.mark.parametrize("dtype", ["float32", "int32", "bfloat16"])
@pytest.mark.parametrize("kind", ["periodic", "reflecting", "wide"])
@pytest.mark.parametrize("rank", sorted(RANKS))
def test_a_boundary_refresh_is_numpys_bit_for_bit(rank, kind, dtype, where,
                                                  request):
    if where == "one-device":
        if _MULTIPROC:
            pytest.skip("installs a local mesh")
        request.getfixturevalue("one_device")
    a = field(jnp.dtype(dtype), RANKS[rank])
    chain = _halo(a.shape, kind)
    before = diagnostics.counters()
    got = _copies(rt.fromarray(a), chain)
    assert same_bits(got, _copies(a.copy(), chain))
    # small, so the writes one by one: the HLO they had
    assert _moved(before, "faces.path.dus") == 1
    assert not _moved(before, "faces.path.wrap")
    assert _moved(before, "rewrite.rewrite_face_copies") == len(chain)


def test_the_lowering_is_the_parents_hlo_off_the_kernel():
    """On the suite's mesh, and on one device under the threshold, the
    node lowers to the six ``dynamic_update_slice`` it replaced."""
    x = jnp.zeros((10, 10, 10), jnp.float32)
    maps = (((0, 8), (9, 1)),) * 3

    def parent(v):
        for ax in range(3):
            for d, s in maps[ax]:
                i = (S(None),) * ax
                v = slicing.put(v, i + (d,), slicing.take(v, i + (s,)))
        return v

    assert (str(jax.make_jaxpr(lambda v: slicing.remap(v, maps))(x))
            == str(jax.make_jaxpr(parent)(x)))


WRAP_CASES = {
    # shape, kind: ragged last tiles on both axes wherever the side is 2^k + 2
    "cube-18-periodic": ((18, 18, 18), "periodic"),
    "cube-34-periodic": ((34, 34, 34), "periodic"),
    "lanes-130-periodic": ((5, 18, 130), "periodic"),
    "lanes-258-periodic": ((3, 10, 258), "periodic"),
    "lanes-258-reflecting": ((3, 10, 258), "reflecting"),
    "lanes-130-wide": ((8, 18, 130), "wide"),
    "cube-34-reflecting": ((34, 34, 34), "reflecting"),
    "aligned": ((4, 16, 256), "periodic"),
    "rows-across-tiles": ((8, 23, 140), "wide"),
}


@pytest.fixture
def walking(one_device, interpreting_walk, monkeypatch):
    """The in-place walk interpreting (``conftest.interpreting_walk``), on
    one device, with Pallas on whatever the environment says."""
    from ramba_tpu.ops import stencil_pallas

    monkeypatch.setattr(stencil_pallas, "_ENABLED", True)


@pytest.mark.skipif(_MULTIPROC, reason="installs a local mesh")
@pytest.mark.parametrize("dtype", ["float32", "int32"])
@pytest.mark.parametrize("case", sorted(WRAP_CASES))
def test_the_walk_interpreting_is_numpys_bit_for_bit(case, dtype, walking):
    from ramba_tpu.observe import registry

    shape, kind = WRAP_CASES[case]
    a = field(jnp.dtype(dtype), shape)
    chain = _halo(shape, kind)
    maps = tuple(tuple((d % n, s % n) for ax, d, s in chain if ax == k)
                 for k, n in enumerate(shape))
    with registry.collect_kernel_notes() as notes:
        got = jax.jit(lambda v: slicing.remap(v, maps))(jnp.asarray(a))
    (note,) = notes
    assert (note["kernel"], note["path"]) == ("faces", "wrap")
    assert note["interpret"]
    assert note["grid"] == -(-shape[0] // note["block_planes"])
    assert same_bits(got, _copies(a.copy(), chain))


@pytest.mark.skipif(_MULTIPROC, reason="installs a local mesh")
@pytest.mark.parametrize("planes", [1, 2, 5])
@pytest.mark.parametrize("case", ["cube-18-periodic", "lanes-258-reflecting",
                                  "rows-across-tiles", "lanes-130-wide"])
def test_stale_vmem_never_reaches_the_walks_result(case, planes,
                                                   interpreting_walk):
    """The TPU interpreter with every buffer NaN to begin with and reads
    out of bounds refused, blocks that divide the planes and blocks that
    do not: nothing a pipeline did not fetch is ever selected into a cell
    of the array, and what is not visited stays as it lay."""
    from jax.experimental.pallas import tpu as pltpu

    from ramba_tpu.ops import faces_pallas

    shape, kind = WRAP_CASES[case]
    a = np.random.default_rng(5).standard_normal(shape).astype(np.float32)
    chain = _halo(shape, kind)
    rows, lanes = (sorted((d % shape[k], s % shape[k])
                          for ax, d, s in chain if ax == k) for k in (1, 2))
    got = np.asarray(faces_pallas._wrap_jit(
        tuple(rows), tuple(lanes),
        pltpu.InterpretParams(uninitialized_memory="nan"),
        *faces_pallas._sized(shape, planes))(jnp.asarray(a)))
    want = _copies(a.copy(), [c for c in chain if c[0]])
    assert not np.isnan(got).any() and same_bits(got, want)


@pytest.mark.skipif(_MULTIPROC, reason="installs a local mesh")
def test_what_the_walk_takes_and_what_stays_six_writes(walking, monkeypatch):
    from ramba_tpu.ops import faces_pallas

    periodic = (((0, 8), (9, 1)),) * 3

    def path(x, maps):
        from ramba_tpu.observe import registry

        with registry.collect_kernel_notes() as notes:
            jax.eval_shape(lambda v: slicing.remap(v, maps), x)
        return notes[0]["path"]

    cube = jax.ShapeDtypeStruct((10, 10, 10), jnp.float32)
    assert path(cube, periodic) == "wrap"
    assert path(jax.ShapeDtypeStruct((10, 10, 10), jnp.int32),
                periodic) == "wrap"
    # planes alone are XLA's in place; a source that is written is a chain
    assert path(cube, (((0, 8),), (), ())) == "dus"
    assert path(cube, ((), ((0, 1), (1, 2)), ())) == "dus"
    assert path(cube, ((), ((0, 1), (1, 0)), ())) == "wrap"  # composed: 1 <- 1
    for other in (jax.ShapeDtypeStruct((10, 10, 10), jnp.bfloat16),
                  jax.ShapeDtypeStruct((10, 10, 10), jnp.complex64),
                  jax.ShapeDtypeStruct((10, 10), jnp.float32),
                  jax.ShapeDtypeStruct((4, 10, 10, 10), jnp.float32),
                  jax.ShapeDtypeStruct((10, 4, 10), jnp.float32)):
        maps = (((0, 2), (3, 1)),) * len(other.shape)
        assert path(other, maps) == "dus"
    # off the chip the kernel is offered by the suite's switch alone
    monkeypatch.setattr(faces_pallas, "_INTERPRET", False)
    assert path(cube, periodic) == "dus"


def test_on_the_mesh_the_walk_is_never_taken(monkeypatch):
    if mesh_mod.get_mesh().devices.size == 1:
        pytest.skip("needs the suite's mesh")
    from ramba_tpu.ops import faces_pallas

    monkeypatch.setattr(faces_pallas, "_INTERPRET", True)
    x = jnp.zeros((10, 10, 10), jnp.float32)
    assert faces_pallas.available(x.shape, x.dtype)
    composed = [slicing._composed(p) for p in (((0, 8), (9, 1)),) * 3]
    assert not slicing._faces_through_kernel(x, composed)
