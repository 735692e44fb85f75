"""The transpose on a mesh (``ops/transpose_sharded.py``): PRK's ``B +=
A.T; A += 1`` against NumPy float32 bit for bit, on the suite's 8-device
mesh (4 x 2: no square grid, GSPMD's transpose) and on a 2 x 2 sub-mesh
(the swap of blocks), with the path each lowering counts, the layouts a
flush leaves, and one swap an iteration."""

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

import ramba_tpu as rt
from ramba_tpu import diagnostics
from ramba_tpu.core import fuser, rewrite
from ramba_tpu.ops import transpose_sharded
from ramba_tpu.parallel import mesh as mesh_mod

F32 = np.float32


@pytest.fixture
def grid_2x2():
    """The program's mesh held to a 2 x 2 grid of four of the devices."""
    if jax.process_count() > 1 or len(jax.devices()) < 4:
        pytest.skip("installs a local 2 x 2 mesh")
    fuser.flush()
    old = mesh_mod.get_mesh()
    mesh_mod.set_mesh(Mesh(np.array(jax.devices()[:4]).reshape(2, 2),
                           ("d0", "d1")))
    try:
        yield
    finally:
        fuser.flush()
        mesh_mod.set_mesh(old)


def moved(before, prefix="transpose."):
    now = diagnostics.counters()
    return {k[len(prefix):]: v - before.get(k, 0) for k, v in now.items()
            if k.startswith(prefix) and v != before.get(k, 0)}


def prk(shape, iterations, rounds):
    """``rounds`` flushes of ``iterations`` PRK iterations on an ``A`` of
    ``shape`` made as PRK makes it, beside NumPy float32; the counters
    the last round moved."""
    n, m = shape
    i = rt.arange(n, dtype=F32)
    j = rt.arange(m, dtype=F32)
    A = i[:, None] * F32(m) + j[None, :]
    B = rt.zeros((m, n), dtype=F32)
    An = np.arange(n, dtype=F32)[:, None] * F32(m) + np.arange(
        m, dtype=F32)[None, :]
    Bn = np.zeros((m, n), F32)
    for _ in range(rounds):
        before = diagnostics.counters()
        for _ in range(iterations):
            B += A.T
            A += 1.0
            Bn += An.T
            An += F32(1)
        rt.sync()
    return A, B, An, Bn, moved(before)


def held_default(x):
    return x._value().sharding.spec == mesh_mod.default_spec(x.shape)


@pytest.mark.parametrize("shape,path,kernel", [
    ((512, 512), "swap", True),     # the grid divides into whole tiles
    ((512, 512), "swap", False),    # the same with XLA's update
    ((768, 512), "swap", True),     # rectangular: blocks (384, 256)
    ((384, 384), "xla", False),     # blocks of 192: no whole lane tile
    ((512, 1024), "xla", False),    # the result's layout is another
])
def test_prk_iterations_on_a_2x2_grid(grid_2x2, monkeypatch, shape, path,
                                      kernel):
    monkeypatch.setattr(transpose_sharded, "_INTERPRET", kernel)
    A, B, An, Bn, counted = prk(shape, 3, 2)
    assert np.array_equal(np.asarray(B), Bn)
    assert np.array_equal(np.asarray(A), An)
    assert held_default(B) and (path != "swap" or held_default(A))
    # the second flush hit: one count an iteration, nothing else
    assert counted.get(f"path.{path}") == 3
    assert sum(v for k, v in counted.items() if k.startswith("path.")) == 3
    if path == "swap":
        block = (shape[0] // 2) * (shape[1] // 2) * 4
        assert counted["exchange_bytes"] == 3 * block
        assert counted.get("interpret", 0) == (3 if kernel else 0)
    else:
        assert "exchange_bytes" not in counted


def test_the_suites_mesh_is_no_square_grid():
    """Eight devices as 4 x 2: GSPMD's transpose, as before, exact."""
    if len(jax.devices()) != 8:
        pytest.skip("tier-1's mesh has eight devices")
    A, B, An, Bn, counted = prk((512, 512), 3, 2)
    assert np.array_equal(np.asarray(B), Bn)
    assert np.array_equal(np.asarray(A), An)
    assert counted == {"path.xla": 3}


def test_one_device_counts_the_local_path(one_device):
    A, B, An, Bn, counted = prk((256, 256), 3, 2)
    assert np.array_equal(np.asarray(B), Bn)
    assert np.array_equal(np.asarray(A), An)
    assert counted == {"path.local": 3}


@pytest.mark.parametrize("axes", [(2, 0, 1), (1, 0, 2)])
def test_a_rank_3_transpose_is_jax_s(grid_2x2, axes):
    x = np.arange(4 * 256 * 256, dtype=F32).reshape(4, 256, 256)
    before = diagnostics.counters()
    got = np.asarray(rt.fromarray(x).transpose(axes) + F32(1))
    assert np.array_equal(got, x.transpose(axes) + F32(1))
    # counted at the flush, and where node inference first traced it
    assert set(moved(before)) == {"path.xla"}


def test_a_plain_transpose_swaps_blocks(grid_2x2):
    """``a.T`` read on its own: the swap, the result in the default
    layout."""
    x = np.arange(512 * 768, dtype=F32).reshape(512, 768)
    before = diagnostics.counters()
    t = rt.fromarray(x).T * F32(2)
    got = np.asarray(t)
    assert np.array_equal(got, x.T * F32(2))
    assert held_default(t)
    assert moved(before)["path.swap"] >= 1


def test_ten_iterations_are_ten_swaps(grid_2x2, monkeypatch):
    """No rewrite merges the transposes of a flush: ten iterations fold
    into ten nodes, and a flush that hits swaps ten blocks."""
    monkeypatch.setattr(transpose_sharded, "_INTERPRET", True)
    fired = rewrite.stats["rewrite_add_transposed"]
    A, B, An, Bn, counted = prk((512, 512), 10, 2)
    assert rewrite.stats["rewrite_add_transposed"] - fired == 20
    assert np.array_equal(np.asarray(B), Bn)
    assert counted["path.swap"] == 10
    assert counted["exchange_bytes"] == 10 * 256 * 256 * 4


def test_the_fold_is_b_plus_a_transposed_only():
    """``B += A.T`` of one shape and dtype folds; another dtype, another
    rank or a transpose on the left is the script's nodes."""
    a = rt.fromarray(np.ones((8, 8), F32))
    b = rt.fromarray(np.ones((8, 8), F32))
    c = rt.fromarray(np.ones((8, 8), np.float64))
    assert (b + a.T)._expr.op == "add_transposed"
    assert (a.T + b)._expr.op == "map"
    assert (c + a.T)._expr.op == "map"
    b += a.T
    assert b._expr.op == "add_transposed"
    assert np.array_equal(np.asarray(b), np.full((8, 8), 2, F32))


def test_a_is_held_behind_the_update_that_read_it():
    """After ``B += A.T`` a pending ``A`` is held behind the updated
    ``B`` (node ``after``), so the next block of A waits for the update;
    its value is A's own.  A materialized ``A`` is left as it is."""
    a = rt.fromarray(np.arange(64, dtype=F32).reshape(8, 8))
    b = rt.zeros((8, 8), dtype=F32)
    b += a.T
    assert isinstance(a._expr, rt.core.expr.Const)
    a += 1.0
    b += a.T
    assert a._expr.op == "after" and a._expr.args[1] is b._expr
    want = np.arange(64, dtype=F32).reshape(8, 8)
    assert np.array_equal(np.asarray(a), want + F32(1))
    assert np.array_equal(np.asarray(b), want.T + (want + F32(1)).T)
