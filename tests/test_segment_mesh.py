"""The default layout of an array the mesh does not divide, and the group-by
passes on it (PR 36): ``mesh.default_spec`` gives a split that divides
where the solver's own does not and leaves every other shape where it was;
an array made through the ordinary entry points then holds 1/ndev a device;
both passes of the anomaly pattern run inside ``shard_map`` on it and agree
with the plain reference in float64; what the devices hand to the
combination adds up to the one-device result and is counted.  On tier-1's
CPU devices, as a 2 x 2 and a 4 x 2 mesh."""

import importlib.util
import os
import sys

import jax
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

import ramba_tpu as rt
from ramba_tpu import groupby  # noqa: F401  (registers the segment ops)
from ramba_tpu.core.expr import OPS
from ramba_tpu.observe import registry
from ramba_tpu.parallel import mesh as rmesh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
G = 366


def mesh_of(ndev):
    devs = jax.devices()
    if len(devs) < ndev:
        pytest.skip(f"needs {ndev} devices")
    shape = {1: (1,), 4: (2, 2), 8: (4, 2)}[ndev]
    return Mesh(np.array(devs[:ndev]).reshape(shape),
                ("d0", "d1")[:len(shape)])


@pytest.fixture
def on(request):
    """The program's mesh held to ``ndev`` devices for one test."""
    before = rmesh.get_mesh()

    def use(ndev):
        rmesh.set_mesh(mesh_of(ndev))
        return rmesh.get_mesh()

    yield use
    rmesh.set_mesh(before)


def doy_clim_np():
    path = os.path.join(ROOT, "benchmark", "programs", "doy_clim.py")
    spec = importlib.util.spec_from_file_location("doy_clim", path)
    mod = importlib.util.module_from_spec(spec)
    sys.path.insert(0, ROOT)
    try:
        spec.loader.exec_module(mod)
    finally:
        sys.path.pop(0)
    return mod.doy_clim_np


# -- the layout ---------------------------------------------------------------

@pytest.mark.parametrize("ndev", [4, 8])
@pytest.mark.parametrize("shape", [(10958, 721, 1440), (1462, 16, 64),
                                   (366, 721, 1440), (730, 9, 20)])
def test_a_shape_the_solver_does_not_divide_gets_a_split_that_does(shape,
                                                                   ndev):
    mesh = mesh_of(ndev)
    spec = rmesh.default_spec(shape, mesh)
    assert spec == rmesh.held_spec(shape, mesh)
    assert rmesh._holds(spec, shape, mesh)
    assert rmesh._spec_parallelism(spec, mesh) == ndev
    assert not rmesh._holds(rmesh._natural_spec(shape, mesh), shape, mesh)


def test_the_thirty_year_cube_and_its_climatology_on_the_2x2_mesh():
    """Least cut surface among the splits that divide: time 2 x longitude
    2 for the cube (1/10958 + 1/1440 against 3/1440), longitude 4 for the
    climatology; the axes handed out from the last dimension, so that the
    climatology's layout refines the cube's."""
    mesh = mesh_of(4)
    assert rmesh.default_spec((10958, 721, 1440), mesh) == P("d1", None, "d0")
    assert rmesh.default_spec((366, 721, 1440), mesh) == P(
        None, None, ("d0", "d1"))


#: the spec each shape had before PR 36 (the accepted cells' arrays, the
#: smoke's, and shapes nothing divides), which it keeps
TODAY = {
    1: {s: () for s in [(1000000000,), (15000, 15000), (2922, 721, 1440),
                        (366, 721, 1440), (514, 514, 514),
                        (10958, 721, 1440)]},
    4: {(1000000000,): (("d0", "d1"),), (15000, 15000): ("d0", "d1"),
        (27000, 27000): ("d0", "d1"), (30000, 30000): ("d0", "d1"),
        (8192, 8192): ("d0", "d1"), (32768, 32768): ("d0", "d1"),
        (514, 514, 514): ("d0", "d1"), (512, 512, 512): ("d0", "d1"),
        (258, 258, 258): ("d0", "d1"), (2928, 60, 380): (("d0", "d1"),),
        (46848, 8): (("d0", "d1"),), (64, 512, 1024): (None, "d0", "d1"),
        (40, 9, 20): (("d0", "d1"),), (7, 11, 13): (None, "d0", "d1"),
        (1462,): (("d0", "d1"),)},
    8: {(1000000000,): (("d0", "d1"),), (27000, 27000): ("d0", "d1"),
        (30000, 30000): ("d0", "d1"), (512, 512, 512): ("d0", "d1"),
        (2928, 60, 380): (("d0", "d1"),), (46848, 8): (("d0", "d1"),),
        (64, 512, 1024): (None, "d1", "d0"), (40, 9, 20): ("d0", None, "d1"),
        (1000, 3, 3): (("d0", "d1"),), (7, 11, 13): (None, "d1", "d0")},
}


@pytest.mark.parametrize("ndev,shape", [(n, s) for n in TODAY
                                        for s in TODAY[n]])
def test_a_shape_the_mesh_divides_keeps_its_spec(ndev, shape):
    mesh = mesh_of(ndev)
    spec = rmesh.default_spec(shape, mesh)
    assert tuple(spec) == TODAY[ndev][shape]
    # a flush puts a result there only where jax can hold it so
    assert rmesh.held_spec(shape, mesh) == (
        spec if rmesh._holds(spec, shape, mesh) else None)


# -- an array made through the ordinary entry points --------------------------

def made(shape, seed=0):
    """(ramba array, the same in NumPy): a flush's result."""
    x = np.random.default_rng(seed).uniform(250, 310, shape).astype(
        np.float32)
    r = rt.fromarray(x) * np.float32(1)
    rt.sync()
    return r, x


def labels_of(days):
    """Day of year - 1 with a leap day every fourth year, nobody in group
    300, and three labels outside the groups."""
    lab = (np.arange(days) % 365 + (np.arange(days) // 1461)) % G
    lab[lab == 300] = 301
    lab[[5, 77, days - 1]] = (G, -1, G + 7)
    return lab.astype(np.int32)


@pytest.mark.parametrize("ndev", [4, 8])
@pytest.mark.parametrize("how", ["fromarray", "arange"])
def test_an_array_the_devices_do_not_divide_holds_a_share_a_device(on, ndev,
                                                                   how):
    mesh = on(ndev)
    shape = (1462, 16, 64)
    if how == "fromarray":
        r, _ = made(shape)
    else:  # as ``benchmark/programs/doy_clim.py`` builds its cube
        f = np.float32
        r = (rt.arange(shape[0], dtype=f)[:, None, None] * f(0.5)
             + rt.arange(shape[1], dtype=f)[None, :, None]
             + rt.sin(rt.arange(shape[2], dtype=f)[None, None, :]))
        rt.sync()
    v = r._value()
    assert v.sharding.spec == rmesh.default_spec(shape, mesh)
    shards = v.addressable_shards
    assert len({s.device for s in shards}) == ndev
    assert all(s.data.size * ndev == v.size for s in shards)


def test_a_leaf_nothing_divides_is_whole_on_every_device(on):
    """1,462 labels over four devices: no split divides, so the upload is
    replicated over the mesh and never sits on one device alone, where
    admission could not lower it beside a result pinned to the mesh (its
    estimate then fell to the analytic walk: 227 GB for the 30-year
    cube's set-up, and the flush was routed off the fused rung)."""
    import jax.numpy as jnp

    from ramba_tpu.core import layouts
    from ramba_tpu.resilience import memory

    mesh = on(4)
    v = rt.fromarray(np.arange(1462, dtype=np.float32))._value()
    assert v.sharding.spec == P() and v.sharding.device_set == set(
        mesh.devices.flat)
    avals = memory._leaf_avals([v])
    fn = layouts.RowMajorJit(
        lambda d: (d[:, None, None] * jnp.ones((1462, 16, 64), d.dtype),))
    assert fn._jit_for(tuple(avals)) is not fn._plain  # pinned to the mesh
    assert fn.lower(*avals).compile() is not None


# -- the two passes on it -----------------------------------------------------

def segment_notes(flush):
    return {k["path"]: k for k in flush.get("kernels", ())
            if k["kernel"] == "segment"}


@pytest.mark.parametrize("ndev", [4, 8])
def test_the_anomaly_pattern_runs_sharded_and_agrees_with_the_reference(
        on, ndev):
    on(ndev)
    days = 1462
    X, x = made((days, 16, 64))
    labels = np.clip(labels_of(days), 0, G - 1)  # the reference's domain
    before = registry.prefixed("segment.")
    g = X.groupby(0, labels, G)
    clim = g.mean()
    rms = float((((g - clim) ** 2).mean()) ** 0.5)
    now = registry.prefixed("segment.")
    assert now["segment.path.walk_broadcast"] > before.get(
        "segment.path.walk_broadcast", 0)
    notes = segment_notes(rt.diagnostics.last_flushes(1)[0])
    assert set(notes) == {"walk_reduce", "walk_broadcast"}
    for note in notes.values():
        assert note["sharded"] is True and note["local_rows"] == days // 2
        assert note["split"]["segment"] and note["split"]["others"]
        assert note["combine"] == "psum"
    assert clim._value().sharding.spec == rmesh.default_spec(clim.shape)
    want, want_rms = doy_clim_np()(x, labels, G)
    got = clim.asarray()
    empty = np.isnan(want[:, 0, 0])
    assert empty[300] and np.isnan(got[empty]).all()
    np.testing.assert_allclose(got[~empty], want[~empty], rtol=0, atol=1e-4)
    np.testing.assert_allclose(rms, want_rms, rtol=2e-6)


@pytest.mark.parametrize("kind", ["sum", "mean", "min", "max"])
def test_every_full_reduce_over_the_broadcast_on_the_mesh(on, kind):
    """A group with no member, labels outside the groups (the reduce
    gives them to no group, the broadcast clips them as ``take`` does)."""
    on(4)
    days = 1462
    X, x = made((days, 16, 64), seed=3)
    labels = labels_of(days)
    before = registry.get("segment.path.walk_broadcast")
    g = X.groupby(0, labels, G)
    top = g.max()
    m = np.random.default_rng(4).uniform(250, 310, (G, 16, 64)).astype(
        np.float32)
    got = float(getattr(((g - rt.fromarray(m)) ** 2) * np.float32(0.5) + X,
                        kind)())
    assert registry.get("segment.path.walk_broadcast") > before
    x64, m64 = x.astype(np.float64), m.astype(np.float64)
    want = getattr((x64 - m64[np.clip(labels, 0, G - 1)]) ** 2 * 0.5 + x64,
                   kind)()
    np.testing.assert_allclose(got, want, rtol=2e-6)
    inside = (labels >= 0) & (labels < G)
    want_top = np.stack([x[inside & (labels == k)].max(0, initial=-np.inf)
                         for k in range(G)])
    assert np.array_equal(top.asarray(), want_top)
    assert np.isneginf(want_top[300]).all()


def test_the_devices_partial_sums_add_up_to_the_one_device_result(on):
    """The share test, read for a reduction: each device's block walked
    alone (on a mesh of one) gives what it hands over; the blocks that
    share the time axis add up, bit for bit, to what the mesh holds for
    their columns, and that is the one-device result to rounding."""
    days, shape = 1462, (1462, 16, 64)
    labels = labels_of(days)
    x = np.random.default_rng(5).uniform(250, 310, shape).astype(np.float32)

    def sums(block, lab):
        r = rt.fromarray(block).groupby(0, lab, G).sum().asarray()
        rt.sync()
        return r

    on(1)
    whole = sums(x, labels)
    half, cols = days // 2, shape[2] // 2
    parts = [[sums(x[t * half:(t + 1) * half, :, c * cols:(c + 1) * cols],
                   labels[t * half:(t + 1) * half]) for c in range(2)]
             for t in range(2)]
    on(4)
    X = rt.fromarray(x) * np.float32(1)
    rt.sync()
    assert X._value().sharding.spec == P("d1", None, "d0")
    got = X.groupby(0, labels, G).sum().asarray()
    handed = np.concatenate([parts[0][c] + parts[1][c] for c in range(2)],
                            axis=2)
    assert np.array_equal(got, handed)
    np.testing.assert_allclose(got, whole, rtol=3e-7, atol=0)


@pytest.mark.parametrize("shape,seg_split", [((1462, 16, 64), True),
                                             ((731, 16, 64), False)])
def test_combine_bytes_counts_what_a_device_hands_over(on, shape, seg_split):
    on(4)
    X, _ = made(shape)
    labels = labels_of(shape[0])
    spec = X._value().sharding.spec
    assert (spec[0] is not None) == seg_split
    want = G * 16 * 32 * 4 if seg_split else 0
    for call in range(3):
        before = registry.get("segment.combine_bytes")
        X.groupby(0, labels, G).sum().asarray()
        moved = registry.get("segment.combine_bytes") - before
        # the first call traces the pass for the flush and, where no
        # earlier test has, for the node's type before that; a later one
        # replays the flush's notes
        assert moved in ((want, 2 * want) if call == 0 else (want,))


def _pads(jaxpr, found):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pad":
            found.append(tuple(eqn.invars[0].aval.shape))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _pads(sub, found)
    return found


@pytest.mark.parametrize("op", ["segment_reduce", "segment_mapreduce"])
def test_neither_pass_pads_the_operand(on, op):
    on(4)
    x = jax.ShapeDtypeStruct((1462, 16, 64), np.float32)
    lab = jax.ShapeDtypeStruct((1462,), np.int32)
    if op == "segment_reduce":
        jaxpr = jax.make_jaxpr(
            lambda a, l: OPS[op](("mean", G, 0), a, l))(x, lab)
    else:
        static = ("mean", 0, G, ("full", "group"),
                  (("subtract", (("a", 0), ("a", 1))),
                   ("multiply", (("t", 0), ("t", 0)))))
        jaxpr = jax.make_jaxpr(lambda a, l, m: OPS[op](static, l, a, m))(
            x, lab, jax.ShapeDtypeStruct((G, 16, 64), np.float32))
    text = str(jaxpr)
    assert "shard_map" in text
    assert all(len(s) <= 1 for s in _pads(jaxpr.jaxpr, []))
