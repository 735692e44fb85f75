"""A trilinear prolongation (NPB MG's ``interp``) is one node: the five
writes folded where the script writes them (``rewrite.fold_prolong``),
what the fold leaves alone, and the node's two lowerings, the writes as
they were and the kernel (``ops/prolong_pallas.py``, interpreted), each to
the last bit of the five writes."""

import numpy as np
import pytest

import ramba_tpu as rt
from benchmark.programs import nas_mg
from ramba_tpu import common, diagnostics
from ramba_tpu.ops import prolong_pallas
from test_nas_mg import program


def moved(before, name):
    return diagnostics.counters().get(name, 0) - before.get(name, 0)


def coarse(n, dtype=np.float32):
    """A random coarse array under a fine one of extents ``n``."""
    shape = (n,) * 3 if isinstance(n, int) else n
    return np.random.default_rng(sum(shape)).standard_normal(
        tuple(m // 2 + 1 for m in shape)).astype(dtype)


def bits(a):
    return np.asarray(a).view(np.uint8)


def writes(z, f, order=(0, 1, 2), half=0.5, cut=1):
    """The script's five writes on a NumPy or a ramba array, or one of
    them changed: the passes in another ``order``, another scalar than
    0.5, or the window ``1:-cut``."""
    f[0::2, 0::2, 0::2] = z[:-1, :-1, :-1]
    for ax in order:
        mid, up, dn = ((slice(None),) * ax + (s,) for s in (
            slice(1, -cut), slice(2, 1 - cut or None), slice(None, -1 - cut)))
        f[mid] = f[mid] + half * (f[up] + f[dn])
    return f


@pytest.mark.parametrize("n", [6, 10, 18, 34])
def test_the_node_is_the_five_writes(n, monkeypatch):
    """The fold off and on, through XLA: the same bits, NumPy's, and ONE
    node of four firings, lowered on ``prolong.path.xla``."""
    z = coarse(n)
    want = writes(z, np.zeros((n,) * 3, np.float32))
    monkeypatch.setattr(common, "rewrite_enabled", False)
    plain = writes(rt.fromarray(z), rt.zeros((n,) * 3, dtype=np.float32))
    assert plain.read_expr().op == "setitem"
    plain = np.asarray(plain)
    monkeypatch.setattr(common, "rewrite_enabled", True)
    before = diagnostics.counters()
    f = writes(rt.fromarray(z), rt.zeros((n,) * 3, dtype=np.float32))
    root = f.read_expr()
    assert root.op == "prolong" and root.static[0] == 3
    assert root.args[0].aval.shape == z.shape
    got = np.asarray(f)
    assert moved(before, "rewrite.rewrite_prolong") == 4
    assert moved(before, "prolong.path.xla") == 1
    assert not moved(before, "prolong.path.pallas")
    np.testing.assert_array_equal(bits(got), bits(plain))
    np.testing.assert_array_equal(bits(got), bits(want))


def test_the_kernel_takes_the_node_over_its_bound(one_device,
                                                  interpreting_prolong):
    """The smallest of ``mg-C``'s fine extents the kernel takes, through
    the flush: ``prolong.path.pallas`` and its note, NumPy's bits."""
    n = min(2 ** k + 2 for k in range(2, 12)
            if 2 ** k + 2 >= prolong_pallas.MIN_EXTENT)
    z = coarse(n)
    before = diagnostics.counters()
    got = np.asarray(writes(rt.fromarray(z),
                            rt.zeros((n,) * 3, dtype=np.float32)))
    assert moved(before, "prolong.path.pallas") == 1
    assert not moved(before, "prolong.path.xla")
    (note,) = [k for k in diagnostics.last_flushes()[-1]["kernels"]
               if k["kernel"] == "prolong"]
    assert note["interpret"] and note["block_planes"] == 2
    assert note["grid"] == n // 2
    assert note["vmem_limit_bytes"] == prolong_pallas.vmem_bytes(z.shape)
    np.testing.assert_array_equal(
        bits(got), bits(writes(z, np.zeros((n,) * 3, np.float32))))


@pytest.mark.parametrize("n", [6, 34, (18, 10, 66)],
                         ids=["6", "34", "18x10x66"])
def test_the_kernel_alone_is_the_five_writes(n, interpreting_prolong):
    """Under the kernel's bound too, and over extents that differ: the
    first and last plane, row and lane of every block are the script's."""
    import jax.numpy as jnp

    z = coarse(n)
    got = prolong_pallas.prolong(jnp.asarray(z), True)
    np.testing.assert_array_equal(
        bits(got), bits(writes(z, np.zeros(got.shape, np.float32))))


@pytest.mark.parametrize("script,fired", [
    (dict(order=(1, 0, 2)), 1),
    (dict(base=1.0), 0),
    (dict(base=-0.0), 0),
    (dict(half=0.25), 1),
    (dict(dtype=np.float64), 0),
    (dict(cut=2), 1),
], ids=["axis-order", "non-zero-base", "negative-zero-base", "scalar",
        "float64", "partial-window"])
def test_what_the_fold_leaves_alone(script, fired):
    """Any other write leaves the script's nodes: the first write folds
    where it is the prolongation's, and nothing after that does."""
    import jax

    dtype, base = script.pop("dtype", np.float32), script.pop("base", 0.0)
    if dtype == np.float64 and not jax.config.jax_enable_x64:
        pytest.skip("float64 is float32 in the x32 regime")
    n = 10
    z = coarse(n, dtype)
    before = diagnostics.counters()
    f = writes(rt.fromarray(z), rt.full((n,) * 3, base, dtype=dtype),
               **script)
    assert f.read_expr().op == "setitem"
    assert moved(before, "rewrite.rewrite_prolong") == fired
    want = writes(z, np.full((n,) * 3, base, dtype), **script)
    np.testing.assert_array_equal(bits(f), bits(want))


def test_a_rehearsal_folds_eight_writes_a_v_cycle():
    """``nas_mg`` at 8^3: two prolongations a V-cycle, four writes each,
    every one folded, and the norm NumPy's."""
    nit = 3
    prog = program(8, nit)
    before = diagnostics.counters()
    (norm,) = prog.solve()
    assert moved(before, "rewrite.rewrite_prolong") == 8 * nit
    assert moved(before, "prolong.path.xla") == 2 * nit
    want = nas_mg.mg_np(8, nit, np.float32, "SWA")[0][-1]
    assert abs(norm - want) <= 2e-5 * want
