"""``core/layouts.py`` is the one place a flush's program becomes a jit.
On the CPU, with the program's mesh held to one device, a result of
rank three or more whose tiles would pad under an eighth is pinned
row-major: such a program is compiled once per signature outside jax's
persistent cache, touches no global configuration when called, is neither
stored nor served by the AOT lane, and its results run on the eager
rung.  (What the TPU's compiler does with the layout is
``tests/test_tpu_compile.py``'s.)"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

import ramba_tpu as rt
from ramba_tpu import diagnostics
from ramba_tpu.compile import persist
from ramba_tpu.core import fuser, layouts
from ramba_tpu.observe import registry
from ramba_tpu.parallel import mesh as rmesh
from ramba_tpu.resilience import faults

SHAPE = (6, 128, 256)  # no padding at all: pinned on one device


@pytest.fixture
def one_device():
    before = rmesh.get_mesh()
    rmesh.set_mesh(Mesh(np.array(jax.devices()[:1]), ("d0",)))
    yield
    rmesh.set_mesh(before)


def cube(seed=0):
    return np.random.default_rng(seed).uniform(
        0.5, 1.5, SHAPE).astype(np.float32)


def test_a_pinned_program_is_compiled_once_and_leaves_the_configuration(
        one_device):
    traces = []

    def program(a, s):
        traces.append(1)
        return (a * s + 1.0, jnp.sum(a))

    fn = layouts.RowMajorJit(program)
    x = jnp.asarray(cube())
    assert fn.pins(x, 2.0) and not fn.pins(x[0], 2.0)
    decided = len(traces)  # one trace a signature
    names = ("jax_persistent_cache_min_compile_time_secs",
             "jax_compilation_cache_include_metadata_in_key",
             "jax_enable_compilation_cache")
    before = {n: getattr(jax.config, n) for n in names}
    seen = []
    watch = jax.config.update
    try:
        jax.config.update = lambda *a: seen.append(a) or watch(*a)
        outs = [fn(x, 2.0) for _ in range(3)]
    finally:
        jax.config.update = watch
    assert not seen and before == {n: getattr(jax.config, n) for n in names}
    pinned = fn._jit_for((x, 2.0))
    assert len(pinned._compiled) == 1
    assert len(traces) == decided == 2  # the lowering reused the decision's
    for y, total in outs:
        np.testing.assert_allclose(np.asarray(y), cube() * 2 + 1, rtol=1e-6)
        assert y.format.layout.major_to_minor == (0, 1, 2)
    # a scalar result's program is the plain jit's
    assert fn._jit_for((x[0], 2.0)) is fn._plain


def test_a_pinned_program_donates_what_the_plain_jit_would(one_device):
    fn = layouts.RowMajorJit(lambda a: (a + 1.0,), (0,))
    x = jnp.asarray(cube())
    (y,) = fn(x)
    assert x.is_deleted() and not y.is_deleted()
    np.testing.assert_allclose(np.asarray(y), cube() + 1, rtol=1e-6)


def test_a_compile_on_another_thread_keeps_its_cache_settings(one_device):
    """The pinned compile's settings are the compiling thread's alone."""
    import threading

    from jax._src import config as jconfig

    def settings():
        return (jconfig.persistent_cache_min_compile_time_secs.value,
                jconfig.compilation_cache_include_metadata_in_key.value)

    fn = layouts.RowMajorJit(lambda a: (a * 3.0,))
    x = jnp.asarray(cube())
    pinned = fn._jit_for((x,))
    real, seen, outside = pinned._jit, {}, settings()

    class Spy:
        def lower(self, *args):
            seen["here"] = settings()
            t = threading.Thread(
                target=lambda: seen.__setitem__("there", settings()))
            t.start()
            t.join()
            return real.lower(*args)

    pinned._jit = Spy()
    fn(x)
    assert seen["here"] == (float("inf"), True)
    assert seen["there"] == outside == settings()


def test_the_aot_lane_neither_stores_nor_serves_a_pinned_program(
        one_device, tmp_path, monkeypatch):
    monkeypatch.setenv("RAMBA_CACHE", str(tmp_path / "cache"))
    persist.reconfigure()
    try:
        assert persist.armed(), persist.snapshot()
        with fuser._cache_lock:
            fuser._compile_cache.clear()
        base = cube(1)
        want = base * 3.0 + 1.0
        np.testing.assert_allclose(np.asarray(rt.array(base) * 3.0 + 1.0),
                                   want, rtol=1e-6)
        skipped = registry.get("compile.persist_store_skipped_pinned")
        rep = persist.save_topk(4)
        assert rep["stored"] == 0, rep
        assert registry.get(
            "compile.persist_store_skipped_pinned") == skipped + 1
        # an entry written before layouts were pinned is dropped, not served
        monkeypatch.setattr(layouts.RowMajorJit, "pins", lambda *a: False)
        for c in persist._candidates.values():
            c["count"] += 1
        assert persist.save_topk(4)["stored"] == 1
        monkeypatch.undo()
        monkeypatch.setenv("RAMBA_CACHE", str(tmp_path / "cache"))
        with fuser._cache_lock:
            fuser._compile_cache.clear()
        hits, misses = (persist.snapshot()[k] for k in ("hits", "misses"))
        out = rt.array(base) * 3.0 + 1.0
        np.testing.assert_allclose(np.asarray(out), want, rtol=1e-6)
        snap = persist.snapshot()
        assert snap["hits"] == hits and snap["misses"] > misses, snap
        assert not os.listdir(tmp_path / "cache" / "aot")
        assert out._value().format.layout.major_to_minor == (0, 1, 2)
    finally:
        monkeypatch.delenv("RAMBA_CACHE")
        persist.reconfigure()


def test_a_pinned_array_runs_on_the_eager_rung(one_device, monkeypatch):
    monkeypatch.setenv("RAMBA_RETRY_ATTEMPTS", "2")
    x = cube(2)
    X = rt.array(x) * 1.0   # a flush's result: pinned
    rt.sync()
    labels = np.arange(SHAPE[0], dtype=np.int32) % 3
    fuser.flush()
    fuser._compile_cache.clear()
    with faults.inject("compile", "always"):
        got = np.asarray(X.groupby(0, labels, 3).max())
    assert diagnostics.last_flushes(1)[0].get("degraded") == "eager"
    want = np.stack([x[labels == g].max(0) for g in range(3)])
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("shape,row_major", [
    ((1462, 8, 128), True),    # whole tiles: the sharding and the layout
    ((1462, 16, 64), False),   # 64 lanes of 128: the sharding alone
    ((64, 128, 256), True),    # a shape the mesh divides: the same rule
])
def test_under_a_mesh_a_distributed_result_gets_the_default_layout(
        shape, row_major):
    """Several devices: the flush knows where a result of rank three
    large enough to distribute goes (``mesh.held_spec``: the default
    layout, whether the solver's own split divides the extents or another
    had to), so it asks for that sharding and, with padding under an
    eighth, for the row-major layout with it; rank two is left alone, and
    so is a shape no split divides."""
    if len(jax.devices()) == 1:
        pytest.skip("tier-1's mesh has eight devices")
    mesh = rmesh.get_mesh()
    want = rmesh.held_spec(shape, mesh)
    assert want == rmesh.default_spec(shape, mesh)
    assert rmesh.held_spec((7, 11, 13), mesh) is None  # nothing divides
    flat = layouts.RowMajorJit(lambda a: (a + 1.0,))
    assert flat._jit_for((jnp.zeros((512, 384)),)) is flat._plain
    fn = layouts.RowMajorJit(lambda a: (a + 1.0, a[:2, :2] * 2.0))
    x = jnp.zeros(shape, jnp.float32)
    # only a pinned LAYOUT keeps a program out of the caches
    assert fn.pins(x) is row_major
    assert layouts.pins([jax.ShapeDtypeStruct(shape, x.dtype)]) is row_major
    assert fn._jit_for((x,)) is not fn._plain
    out, small = fn(x)
    assert out.sharding.spec == want
    assert all(s.data.size * mesh.devices.size == out.size
               for s in out.addressable_shards)
    if row_major:
        assert out.format.layout.major_to_minor == (0, 1, 2)
    assert float(out.sum()) == out.size and float(small.sum()) == 0.0
