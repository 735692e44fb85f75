"""The sorted chunked walk behind ``ndarray.groupby`` (ramba_tpu/groupby.py)
against plain NumPy, in the style of ``benchmark/programs/doy_clim.py``'s
``doy_clim_np``: a loop over the groups on seeded data at a small ragged
size (T = 40, a 9 x 20 grid, 7 groups of uneven size of which one is
empty, labels unsorted), every kind, along dim 0 and 1, on tier-1's
8-device CPU mesh with the segment axis sharded, with the operand
replicated, and with the operand a transposed slice of a 2-D-sharded
array (tests/test_partition.py's case); the group-broadcast for every
binary operator; the Xarray expansions arriving at the direct call's
nodes; and what a steady call counts."""

import operator

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

import ramba_tpu as rt
from ramba_tpu.core import rewrite
from ramba_tpu.observe import registry
from ramba_tpu.parallel import mesh as rmesh

T, H, W, G = 40, 9, 20, 7
KINDS = ("sum", "prod", "min", "max", "count", "mean", "var", "std",
         "nansum", "nanmean", "nanvar", "nanstd")
BINOPS = ("add", "sub", "mul", "truediv", "floordiv", "mod", "pow",
          "lt", "le", "gt", "ge", "eq", "ne")


def labels_of(seed=0):
    """Unsorted, repeating, uneven, and nobody in group 5."""
    lab = np.random.default_rng(seed).integers(0, G - 1, T)
    lab[lab == 5] = 6
    lab[:3] = (6, 0, 6)
    return lab.astype(np.int32)


def cube(seed, shape, nans=False):
    x = np.random.default_rng(seed).uniform(0.5, 1.5, shape)
    if nans:
        x[np.random.default_rng(seed + 1).random(shape) < 0.1] = np.nan
    return x


def reference(kind, x, labels, num_groups, dim):
    """A loop over the groups, in float64."""
    x = np.moveaxis(np.asarray(x, np.float64), dim, 0)
    out = []
    with np.errstate(all="ignore"):
        for g in range(num_groups):
            m = x[labels == g]
            ok = ~np.isnan(m)
            z = np.where(ok, m, 0.0)
            n = np.full(x.shape[1:], float(len(m)))
            r = {"sum": lambda: m.sum(0), "prod": lambda: m.prod(0),
                 "min": lambda: m.min(0, initial=np.inf),
                 "max": lambda: m.max(0, initial=-np.inf),
                 "count": lambda: n, "mean": lambda: m.sum(0) / n,
                 "var": lambda: (m * m).sum(0) / n - (m.sum(0) / n) ** 2,
                 "nansum": lambda: z.sum(0),
                 "nanmean": lambda: z.sum(0) / ok.sum(0),
                 "nanvar": lambda: (z * z).sum(0) / ok.sum(0)
                 - (z.sum(0) / ok.sum(0)) ** 2}
            r["std"] = lambda: np.sqrt(r["var"]())
            r["nanstd"] = lambda: np.sqrt(r["nanvar"]())
            out.append(r[kind]())
    return np.moveaxis(np.stack(out), 0, dim)


def close(got, want, atol=2e-6):
    np.testing.assert_allclose(np.asarray(got, np.float64), want,
                               rtol=2e-5, atol=atol)


def seg_axes(arr, dim):
    """The mesh axes the default layout gives ``arr``'s ``dim``."""
    spec = tuple(arr._value().sharding.spec)
    return spec[dim] if dim < len(spec) else None


def operand(layout, dim, nans=False):
    """(ramba array, the same in NumPy) with its segment axis along
    ``dim``, laid out as ``layout`` says on the 8-device mesh."""
    if layout == "sharded":
        shape = (T, H, W) if dim == 0 else (H, T, W)
        x = cube(1, shape, nans)
        r = rt.fromarray(x)
        if len(jax.devices()) > 1:
            assert seg_axes(r, dim) is not None
    elif layout == "replicated":
        x = cube(2, (T, 2) if dim == 0 else (2, T), nans)
        r = rt.fromarray(x)
        assert tuple(r._value().sharding.spec) == ()
    else:  # a transposed slice of a 2-D-sharded array
        base = cube(3, (T + 4, W + 4) if dim == 1 else (W + 4, T + 4), nans)
        sl = (slice(2, T + 2), slice(1, W + 1)) if dim == 1 else \
            (slice(1, W + 1), slice(2, T + 2))
        x = base[sl].T
        r = rt.fromarray(base)[sl].T
    assert x.shape[dim] == T
    return r, x


@pytest.mark.parametrize("layout", ["sharded", "replicated", "transposed"])
@pytest.mark.parametrize("dim", [0, 1])
@pytest.mark.parametrize("kind", KINDS)
def test_every_kind_against_numpy(kind, dim, layout):
    labels = labels_of()
    r, x = operand(layout, dim, nans=kind.startswith("nan"))
    got = getattr(r.groupby(dim, labels, G), kind)().asarray()
    want = reference(kind, x, labels, G, dim)
    assert got.shape == want.shape
    if kind.endswith("std"):
        # a variance of nothing (one member) rounds to either side of 0
        var = reference(kind[:-3] + "var", x, labels, G, dim)
        got, want = np.where(var > 1e-4, got, 0), np.where(var > 1e-4, want, 0)
    # E[x^2] - E[x]^2 cancels: in float32 a variance is good to 1e-6
    close(got, want, atol=5e-5 if kind.endswith(("var", "std")) else 2e-6)
    if kind == "count":
        assert got.dtype.kind == "i"


@pytest.mark.parametrize("shape,dim", [((1000,), 0), ((6, 3000), 1)])
def test_small_slabs_go_through_the_gather(shape, dim):
    """More than ``_UNROLL`` members a chunk: one gather a chunk."""
    x = cube(5, shape)
    labels = np.random.default_rng(6).integers(0, 3, shape[dim])
    with registry.collect_kernel_notes() as notes:
        got = rt.fromarray(x).groupby(dim, labels, 4).sum().asarray()
    close(got, reference("sum", x, labels, 4, dim))
    assert {n["fetch"] for n in notes if n["kernel"] == "segment"} == {
        "gather"}


@pytest.mark.parametrize("n,groups,slab,rows", [
    (2922, 366, 721 * 1440 * 4, 8),      # doy-clim: a day of the year's 8
    (1464, 92, 721 * 1440 * 4, 16),      # big slabs: slices, up to 16
    (2922, 12, 721 * 1440 * 4, 16),      # never a gather of such slabs
    (2048, 64, 500 * 750 * 4, 16),       # 1.5 MB: 75 x slower gathered
    (8192, 64, 100 * 1000 * 4, 83),      # 400 KB: 32 MiB by one gather
    (65536, 64, 128 * 128 * 4, 512),
    (4000000, 12, 4, 333334),            # 1-D: a group by one gather
    (3, 7, 64, 1),
])
def test_the_rows_a_chunk_fetches(n, groups, slab, rows):
    """What the sweep on the chip chose (scripts/tpu_segment_sweep.py;
    PERF.md section 6, PR 30)."""
    from ramba_tpu import groupby

    assert groupby._chunk_rows(n, groups, slab) == rows


def test_labels_outside_the_groups_belong_to_none():
    x = cube(7, (T, 4, 30))
    labels = labels_of()
    labels[4], labels[9] = -1, G + 3
    got = rt.fromarray(x).groupby(0, labels, G).sum().asarray()
    close(got, reference("sum", x, labels, G, 0))


@pytest.mark.parametrize("name", BINOPS)
def test_group_broadcast_of_every_binary_op(name):
    labels = labels_of()
    x, m = cube(8, (T, H, W)), cube(9, (G, H, W))
    op = getattr(operator, name)
    got = op(rt.fromarray(x).groupby(0, labels, G), rt.fromarray(m))
    close(got.asarray(), op(x, m[labels]))
    if name in ("sub", "truediv", "pow"):  # and with the group on the left
        got = op(rt.fromarray(m), rt.fromarray(x).groupby(0, labels, G))
        close(got.asarray(), op(m[labels], x))


@pytest.fixture
def one_device():
    """The program's mesh held to one device (the fused reduce over a
    group-broadcast is one device's: groupby.fuse_broadcast_reduce)."""
    before = rmesh.get_mesh()
    rmesh.set_mesh(Mesh(np.array(jax.devices()[:1]), ("d0",)))
    yield
    rmesh.set_mesh(before)


def moved(before):
    now = registry.prefixed("segment.path.")
    return {k: v - before.get(k, 0) for k, v in now.items()
            if v != before.get(k, 0)}


@pytest.mark.parametrize("kind", ["sum", "mean", "min", "max"])
def test_a_reduce_over_the_broadcast_stores_nothing(one_device, kind):
    labels = labels_of()
    x, m = cube(10, (T, H, W)), cube(11, (G, H, W))
    X, M = rt.fromarray(x), rt.fromarray(m)
    rt.sync()
    fired = rewrite.stats["rewrite_reduce_group_broadcast"]
    before = registry.prefixed("segment.path.")
    g = X.groupby(0, labels, G)
    got = float(getattr(((g - M) ** 2) * 0.5 + X, kind)())
    assert rewrite.stats["rewrite_reduce_group_broadcast"] == fired + 1
    assert moved(before).get("segment.path.walk_broadcast", 0) >= 1
    want = getattr((x - m[labels]) ** 2 * 0.5 + x, kind)()
    np.testing.assert_allclose(got, want, rtol=2e-5)


@pytest.mark.parametrize("mode", ["clip", "wrap", "fill"])
def test_a_users_take_keeps_its_mode_under_a_full_reduce(one_device, mode):
    """``ndarray.take`` is public and forwards its mode to ``jnp.take``;
    the walk clips, so only a take that clips may become one.  Indices
    below zero and past the end, against NumPy (``fill``: jax's, NaN
    past the end and Python's meaning below zero)."""
    x, m = cube(14, (T, H, W)), cube(15, (G, H, W))
    idx = labels_of().astype(np.int64)
    idx[:6] = (-1, -G, G, G + 2, -G - 3, 2 * G + 1)
    X, M = rt.fromarray(x), rt.fromarray(m)
    rt.sync()
    fired = rewrite.stats["rewrite_reduce_group_broadcast"]
    got = float(((X - M.take(idx, 0, mode=mode)) ** 2).sum())
    assert (rewrite.stats["rewrite_reduce_group_broadcast"] - fired
            == (mode == "clip"))
    if mode == "fill":
        assert np.isnan(got)
        ok = (idx >= -G) & (idx < G)
        x, idx = x[ok], idx[ok]
        got = float(((rt.fromarray(x) - M.take(idx, 0, mode=mode)) ** 2)
                    .sum())
    want = ((x - np.take(m, idx, 0, mode="wrap" if mode == "fill" else mode))
            ** 2).sum()
    np.testing.assert_allclose(got, want, rtol=2e-5)


def test_the_anomaly_pattern_against_the_plain_reference(one_device):
    """The benchmark's solve at toy size against ``doy_clim_np``; the
    steady second call compiles nothing and moves each path once."""
    import importlib.util
    import os

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "programs", "doy_clim.py")
    spec = importlib.util.spec_from_file_location("doy_clim", path)
    doy_clim = importlib.util.module_from_spec(spec)
    import sys
    sys.path.insert(0, os.path.dirname(os.path.dirname(path)))
    try:
        spec.loader.exec_module(doy_clim)
    finally:
        sys.path.pop(0)
    labels = labels_of()
    x = cube(12, (T, H, W)).astype(np.float32)
    X = rt.fromarray(x)
    rt.sync()

    def solve():
        g = X.groupby(0, labels, G)
        clim = g.mean()
        return clim, float((((g - clim) ** 2).mean()) ** 0.5)

    solve()
    before = registry.prefixed("segment.path.")
    misses = registry.get("fuser.cache_miss")
    clim, rms = solve()
    assert registry.get("fuser.cache_miss") == misses
    assert moved(before) == {"segment.path.walk_reduce": 1,
                             "segment.path.walk_broadcast": 1}
    want, want_rms = doy_clim.doy_clim_np(x, labels, G)
    got = clim.asarray()
    assert np.isnan(got[5]).all() and np.isnan(want[5]).all()
    full = np.arange(G) != 5
    np.testing.assert_allclose(got[full], want[full], rtol=0, atol=4e-7)
    # no day reads the empty group's NaN
    np.testing.assert_allclose(rms, want_rms, rtol=2e-6)


def test_another_calendar_of_the_same_length_compiles_nothing(one_device):
    x = cube(13, (T, H, W))
    X = rt.fromarray(x)
    float(X.groupby(0, labels_of(0), G).mean().sum())
    misses = registry.get("fuser.cache_miss")
    labels = labels_of(1)
    got = X.groupby(0, labels, G).sum().asarray()
    assert registry.get("fuser.cache_miss") == misses + 1  # sum: new once
    labels = labels_of(2)
    got = X.groupby(0, labels, G).sum().asarray()
    assert registry.get("fuser.cache_miss") == misses + 1
    close(got, reference("sum", x, labels, G, 0))



def test_the_xarray_expansions_build_the_direct_calls_nodes():
    """``stack(mean(x[:, idx_g]))`` and ``concatenate(x[:, idx_g] - m[g])``
    through core/rewrite.py: the nodes ``gb.mean()`` and ``gb - m``
    build, on the same leaves."""
    labels = np.sort(labels_of()[labels_of() != 6])  # groups 0..4, in place
    n, k = len(labels), 5
    x, m = cube(14, (3, n)), cube(15, (3, k))
    X, M = rt.fromarray(x), rt.fromarray(m)
    rt.sync()
    cols = [np.where(labels == g)[0] for g in range(k)]
    gb = X.groupby(1, labels, k)

    stacked = rt.stack([rt.mean(X[:, idx], axis=1) for idx in cols], axis=1)
    (got,) = rewrite.rewrite_roots([stacked.read_expr()])
    want = gb.mean().read_expr()
    assert (got.op, got.static) == (want.op, want.static) == (
        "segment_reduce", ("mean", k, 1))
    assert got.args[0] is want.args[0]
    assert np.array_equal(got.args[1].value, want.args[1].value)

    parts = [X[:, idx] - M[:, g:g + 1] for g, idx in enumerate(cols)]
    out = rt.concatenate(parts, axis=1)
    close(out.asarray(), x - m[:, labels])
    parts = [X[:, idx] - M[:, g][:, None] for g, idx in enumerate(cols)]
    close(rt.concatenate(parts, axis=1).asarray(), x - m[:, labels])

    # plain m[g] on the grouped axis: the form the rule equates
    x3, m3 = cube(16, (n, 4)), cube(17, (k, 4))
    X3, M3 = rt.fromarray(x3), rt.fromarray(m3)
    rt.sync()
    parts = [X3[idx] - M3[g] for g, idx in enumerate(cols)]
    (got,) = rewrite.rewrite_roots(
        [rt.concatenate(parts, axis=0).read_expr()])
    want = (X3.groupby(0, labels, k) - M3).read_expr()
    assert (got.op, got.static) == (want.op, want.static)
    for a, b in zip(got.args, want.args):
        assert (a is b) or (a.op, a.static) == (b.op, b.static) == (
            "take", (0, "clip"))
    take_got, take_want = got.args[1], want.args[1]
    assert take_got.args[0] is take_want.args[0]
    assert np.array_equal(take_got.args[1].value, take_want.args[1].value)
    close(rt.concatenate(parts, axis=0).asarray(), x3 - m3[labels])
