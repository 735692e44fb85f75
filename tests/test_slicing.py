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
