"""A flush's scalar operands are on the device when the compiled call is
made (``fuser._resident_scalars``): the callable of every rung receives a
committed array, replicated over the mesh, with the aval the ``Scalar``
leaf recorded, and no Python or NumPy number.  A value met again is a
dictionary hit (``dispatch.scalar.hit``), a new one a put
(``dispatch.scalar.put``).  "The parent's way" below is the flush with
that step taken out: the numbers themselves among jit's arguments.
"""

import contextlib
import enum
import os
import struct
import sys
import threading

import jax
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec

import ramba_tpu as rt
from ramba_tpu import common, diagnostics
from ramba_tpu.core import fuser, layouts
from ramba_tpu.observe import events
from ramba_tpu.ops import stencil_pallas
from ramba_tpu.parallel import mesh as mesh_mod
from ramba_tpu.resilience import faults, memory
from tests.helpers import prk_star_kernel

pytestmark = pytest.mark.skipif(
    jax.process_count() > 1, reason="installs local meshes")


@pytest.fixture(autouse=True)
def _clean():
    fuser.flush()
    faults.configure(None)
    yield
    faults.reset()


def install(ndev):
    if len(jax.devices()) < ndev:
        pytest.skip(f"needs {ndev} devices")
    fuser.flush()
    devs = np.array(jax.devices()[:ndev])
    mesh_mod.set_mesh(Mesh(devs.reshape((2, 2)), ("d0", "d1")) if ndev == 4
                      else Mesh(devs, ("d0",)))


@pytest.fixture(params=[1, 4], ids=["1dev", "2x2"])
def mesh(request):
    """The program's mesh held to one device, or to 2x2."""
    old = mesh_mod.get_mesh()
    install(request.param)
    yield mesh_mod.get_mesh()
    fuser.flush()
    mesh_mod.set_mesh(old)


@pytest.fixture(params=[False, True], ids=["x32", "x64"])
def x64(request):
    old = bool(jax.config.jax_enable_x64)
    jax.config.update("jax_enable_x64", request.param)
    yield request.param
    fuser.flush()
    jax.config.update("jax_enable_x64", old)


@pytest.fixture
def parents_way(monkeypatch):
    """A context in which the numbers go to jit as they used to."""
    @contextlib.contextmanager
    def ctx():
        with monkeypatch.context() as m:
            m.setattr(fuser, "_resident_scalars",
                      lambda leaves, leaf_vals: leaf_vals)
            yield
    return ctx


class Calls:
    """What each compiled call received while the block ran (a spy on
    ``_execute_compiled``: the fused, split, chunked and segmented
    rungs), the flush spans and the counters that moved."""

    def __init__(self, monkeypatch):
        self.patch = monkeypatch.context()
        self.compiled, self.spans = [], []

    def __enter__(self):
        real = fuser._execute_compiled

        def spy(fn, program, leaf_vals, *a, **kw):
            self.compiled.append((fn, program, list(leaf_vals)))
            return real(fn, program, leaf_vals, *a, **kw)

        self.patch.__enter__().setattr(fuser, "_execute_compiled", spy)
        self._c0 = diagnostics.counters()
        events.add_tap(self._tap)
        return self

    def _tap(self, e):
        if e.get("type") == "flush":
            self.spans.append(e)

    def __exit__(self, *exc):
        events.remove_tap(self._tap)
        self.patch.__exit__(*exc)
        c1 = diagnostics.counters()
        self.moved = {k: v - self._c0.get(k, 0) for k, v in c1.items()
                      if v != self._c0.get(k, 0)}
        return False

    def scalars(self):
        """The values the scalar slots of every compiled call held."""
        return [v for _, program, vals in self.compiled
                for kind, v in zip(program.leaf_kinds, vals) if kind == "S"]


def assert_resident(v, mesh):
    assert isinstance(v, jax.Array), type(v)
    assert v.committed and v.shape == ()
    assert v.sharding.is_equivalent_to(
        NamedSharding(mesh, PartitionSpec()), 0), v.sharding


def data(dtype="float32", n=64):
    return rt.fromarray(np.arange(1, n + 1, dtype=dtype))


def bits(x):
    x = np.asarray(x)
    return x.dtype, x.shape, x.tobytes()


# -- no number among the arguments ------------------------------------------


def test_a_steady_flush_hands_the_callable_no_number(mesh, monkeypatch):
    a = data()

    def step():
        nonlocal a
        a += 1.0
        a *= 2
        b = a - np.float32(3)
        return float(rt.sum(b))

    step()
    with Calls(monkeypatch) as calls:
        got = step()
    assert calls.compiled and got == float(np.sum(
        ((np.arange(1, 65, dtype=np.float32) + 1) * 2 + 1) * 2 - 3))
    for _, _, vals in calls.compiled:
        for v in vals:
            assert isinstance(v, jax.Array), type(v)
    assert len(calls.scalars()) == 3
    for v in calls.scalars():
        assert_resident(v, mesh)
    assert calls.moved.get("dispatch.scalar.hit") == 3
    assert "dispatch.scalar.put" not in calls.moved


def test_the_same_value_is_the_same_array_on_every_flush(mesh, monkeypatch):
    a = data()
    seen = []
    for _ in range(3):
        with Calls(monkeypatch) as calls:
            a += 0.5
            rt.sync()
        seen.append(calls.scalars())
    assert [len(s) for s in seen] == [1, 1, 1]
    assert seen[0][0] is seen[1][0] is seen[2][0]


# -- bit-equal to the parent's way -------------------------------------------

SCALARS = [1.5, 3, True, 1 + 2j, np.float32(1.5), np.int64(3)]


@pytest.mark.parametrize("dtype", ["float32", "int32"])
@pytest.mark.parametrize("scalar", SCALARS,
                         ids=[type(s).__name__ for s in SCALARS])
def test_results_are_bit_equal_to_the_parents_way(x64, scalar, dtype,
                                                  parents_way, monkeypatch):
    def run():
        a = data(dtype)
        out = (a + scalar, a * scalar, scalar - a)
        rt.sync()
        return [o._value() for o in out]

    with Calls(monkeypatch) as calls:
        ours = run()
    assert all(isinstance(v, jax.Array) for v in calls.scalars())
    assert len(calls.scalars()) == 3
    with parents_way():
        with Calls(monkeypatch) as calls:
            theirs = run()
    assert [type(v) for v in calls.scalars()] == [type(scalar)] * 3
    for o, t in zip(ours, theirs):
        assert o.weak_type == t.weak_type
        assert bits(o) == bits(t)


@pytest.mark.parametrize("expr,want", [
    (lambda a, i: a + 1.0, "float32"),       # the weak type does not widen
    (lambda a, i: a * 2, "float32"),
    (lambda a, i: a + np.float32(1), "float32"),
    (lambda a, i: i + 1, "int32"),
    (lambda a, i: i + True, "int32"),
    (lambda a, i: i * 2.5, None),            # goes where it went
    (lambda a, i: a * (1 + 2j), None),
    (lambda a, i: i + np.int64(3), None),
], ids=["f32+1.0", "f32*2", "f32+np.f32", "i32+1", "i32+True", "i32*2.5",
        "f32*complex", "i32+np.i64"])
def test_the_result_dtype_is_kept(x64, expr, want, parents_way):
    ours = expr(data("float32"), data("int32"))._value()
    with parents_way():
        theirs = expr(data("float32"), data("int32"))._value()
    assert (ours.dtype, ours.weak_type) == (theirs.dtype, theirs.weak_type)
    assert bits(ours) == bits(theirs)
    if want is not None:
        assert ours.dtype == np.dtype(want)


# -- values never alias -------------------------------------------------------

NAN_A = struct.unpack("d", struct.pack("Q", 0x7FF8000000000000))[0]
NAN_B = struct.unpack("d", struct.pack("Q", 0x7FF8000000000001))[0]


@pytest.mark.parametrize("x,y", [
    (1, 1.0), (1, True), (1.0, True), (0.0, -0.0), (NAN_A, NAN_B),
    (1.0, np.float32(1)), (np.float32(1), np.float64(1)),
    (np.int32(1), np.int64(1)), (1, np.int64(1)), (1j, 1.0),
], ids=["int-float", "int-bool", "float-bool", "zero-negzero", "nan-nan",
        "float-np.f32", "np.f32-np.f64", "np.i32-np.i64", "int-np.i64",
        "complex-float"])
def test_equal_looking_values_never_alias(x, y, monkeypatch):
    fuser._device_scalars.clear()
    got = []
    for s in (x, y, x):
        with Calls(monkeypatch) as calls:
            out = (data("int32") * s)._value()
        (arr,) = calls.scalars()
        want = jax.numpy.asarray(s)
        assert (arr.dtype, arr.weak_type) == (want.dtype, want.weak_type)
        assert bits(arr) == bits(want)
        got.append((arr, bits(out)))
    assert got[0][0] is got[2][0] and got[0][0] is not got[1][0]
    assert len(fuser._device_scalars) == 2
    assert got[0][1] == got[2][1]


def test_negative_zero_keeps_its_sign():
    for s, neg in ((0.0, False), (-0.0, True), (0.0, False)):
        out = np.asarray(data() * s)
        assert np.signbit(out).all() == neg and not out.any()


# -- values the table does not take ------------------------------------------


class Half(float):
    pass


class Three(enum.IntEnum):
    THREE = 3


@pytest.mark.parametrize("value", [Half(0.5), Three.THREE],
                         ids=["float-subclass", "int-enum"])
def test_another_type_goes_on_as_a_number(value, parents_way, monkeypatch):
    before = len(fuser._device_scalars)
    with Calls(monkeypatch) as calls:
        ours = (data() * value)._value()
    assert [type(v) for v in calls.scalars()] == [type(value)]
    assert len(fuser._device_scalars) == before
    assert "dispatch.scalar.put" not in calls.moved
    with parents_way():
        theirs = (data() * value)._value()
    assert bits(ours) == bits(theirs)


@pytest.mark.parametrize("big", [1 << 40, 1 << 70], ids=["2^40", "2^70"])
def test_a_big_python_int_behaves_as_before(x64, big, parents_way):
    def run():
        try:
            return bits((data() * big)._value())
        except OverflowError as e:
            return str(e)

    ours = run()
    with parents_way():
        theirs = run()
    assert ours == theirs
    fits = x64 and big < (1 << 63)
    assert isinstance(ours, tuple) == fits


def test_a_leaf_of_another_regime_is_left_as_it_is(monkeypatch):
    """A ``Scalar`` built under x64 and flushed under x32 holds an aval no
    array made now would have: the number goes on as before."""
    old = bool(jax.config.jax_enable_x64)
    fuser._device_scalars.clear()
    try:
        jax.config.update("jax_enable_x64", True)
        lazy = data("float32") * 0.1
        jax.config.update("jax_enable_x64", False)
        with Calls(monkeypatch) as calls:
            try:
                lazy._value()
            except Exception:
                pass  # what such a flush does is not this test's
        assert [type(v) for v in calls.scalars()] == [float]
        assert not fuser._device_scalars
    finally:
        jax.config.update("jax_enable_x64", old)


# -- the table's life ----------------------------------------------------------


def test_a_mesh_change_empties_the_table_and_the_next_flush_runs(
        monkeypatch):
    old = mesh_mod.get_mesh()
    try:
        install(4)
        with Calls(monkeypatch) as calls:
            assert float(rt.sum(data() + 7.0)) == 64 * 65 / 2 + 64 * 7
        (four,) = calls.scalars()
        assert_resident(four, mesh_mod.get_mesh())
        assert len(four.sharding.device_set) == 4
        install(1)
        with Calls(monkeypatch) as calls:
            assert float(rt.sum(data() + 7.0)) == 64 * 65 / 2 + 64 * 7
        (one,) = calls.scalars()
        assert one is not four
        assert_resident(one, mesh_mod.get_mesh())
        assert len(one.sharding.device_set) == 1
        assert calls.moved.get("dispatch.scalar.put") == 1
        assert list(fuser._device_scalars.values()) == [one]
    finally:
        fuser.flush()
        mesh_mod.set_mesh(old)


def test_an_array_that_outlived_its_mesh_keeps_the_numbers(monkeypatch):
    """Arrays committed to other devices than the mesh's: a scalar
    committed to the mesh would set jit against them."""
    old = mesh_mod.get_mesh()
    try:
        install(4)
        a = data()
        rt.sync()
        install(1)
        with Calls(monkeypatch) as calls:
            got = np.asarray(a + 2.0)
        assert [type(v) for v in calls.scalars()] == [float]
        np.testing.assert_array_equal(
            got, np.arange(1, 65, dtype=np.float32) + 2)
        assert "dispatch.scalar.put" not in calls.moved
    finally:
        del a
        fuser.flush()
        mesh_mod.set_mesh(old)


def test_the_table_stays_bounded_over_a_sweep_of_fresh_values(monkeypatch):
    a = data()
    top = 0
    with Calls(monkeypatch) as calls:
        for k in range(fuser._DEVICE_SCALARS_MAX + 44):
            a = a + (1000.0 + k)
            if k % 20 == 19:
                rt.sync()
                top = max(top, len(fuser._device_scalars))
        rt.sync()
    assert top <= fuser._DEVICE_SCALARS_MAX
    assert len(fuser._device_scalars) <= fuser._DEVICE_SCALARS_MAX
    assert calls.moved["dispatch.scalar.put"] == fuser._DEVICE_SCALARS_MAX + 44
    k = fuser._DEVICE_SCALARS_MAX + 44
    np.testing.assert_allclose(
        np.asarray(a)[:2],
        np.arange(1, 3, dtype=np.float32) + 1000.0 * k + k * (k - 1) / 2,
        rtol=1e-6)


@pytest.mark.parametrize("workers", [2, 2 * (os.cpu_count() or 4)],
                         ids=["two", "more-than-cores"])
def test_streams_flushing_at_once(workers, monkeypatch):
    """Every stream's result is right, each value is in the table once
    however the threads raced, and no leaf went uncounted."""
    fuser._device_scalars.clear()
    errors, sums = [], {}
    start = threading.Barrier(workers)
    rounds, steps = 8, (0.25, 0.5)

    def work(tag):
        try:
            with fuser.stream_scope(fuser.FlushStream(name=f"s{tag}")):
                a = data()
                start.wait(timeout=60)
                for _ in range(rounds):
                    a = (a + steps[tag % 2]) * 1.0
                    fuser.current_stream().flush()
                sums[tag] = float(rt.sum(a))
        except Exception as e:  # noqa: BLE001 - reported below
            errors.append(e)

    threads = [threading.Thread(target=work, args=(t,))
               for t in range(workers)]
    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    c0 = diagnostics.counters()
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
    finally:
        sys.setswitchinterval(switch)
    assert not any(t.is_alive() for t in threads)
    assert not errors, errors
    assert sums == {t: 64 * 65 / 2 + 64 * rounds * steps[t % 2]
                    for t in range(workers)}
    assert sorted(struct.unpack("d", k[1])[0]
                  for k in fuser._device_scalars) == [0.25, 0.5, 1.0]
    c1 = diagnostics.counters()
    moved = sum(c1.get(k, 0) - c0.get(k, 0)
                for k in ("dispatch.scalar.hit", "dispatch.scalar.put"))
    assert moved == workers * rounds * 2  # two scalar leaves a flush
    assert c1.get("dispatch.scalar.put", 0) - c0.get(
        "dispatch.scalar.put", 0) == 3


# -- the PRK solve, and every rung ---------------------------------------------

N, T = 136, 10


class Prk:
    """``B += stencil(star, A); A += 1.0`` ten times and the norm: the
    star cells' solve at a toy order."""

    def __init__(self):
        self.star = rt.stencil(prk_star_kernel(2))
        i = rt.arange(N, dtype=np.float32)
        self.A = i[:, None] + i[None, :]
        self.B = rt.zeros((N, N), dtype=np.float32)
        self.iterations = 0
        rt.sync()

    def solve(self):
        for _ in range(T):
            self.B += rt.sstencil(self.star, self.A)
            self.A += 1.0
        self.iterations += T
        return float(rt.sum(abs(self.B))) / (N - 4) ** 2

    def solve_and_check(self):
        norm = self.solve()
        assert abs(norm - 2.0 * self.iterations) <= 1e-4 * 2 * self.iterations


@pytest.fixture
def prk(mesh, monkeypatch):
    monkeypatch.setattr(stencil_pallas, "_INTERPRET", True)
    p = Prk()
    yield p
    del p.A, p.B


def test_the_second_prk_solve_reads_ten_hits_and_no_put(prk, mesh,
                                                        monkeypatch):
    fuser._device_scalars.clear()
    with Calls(monkeypatch) as first:
        prk.solve_and_check()
    assert first.moved.get("dispatch.scalar.put") == 1  # one VALUE, ten leaves
    assert first.moved.get("dispatch.scalar.hit") == T - 1
    with Calls(monkeypatch) as second:
        prk.solve_and_check()
    assert second.moved.get("dispatch.scalar.hit") == T
    assert "dispatch.scalar.put" not in second.moved
    assert len(second.spans) == 1 and second.spans[0]["cache"] == "hit"
    assert "degraded" not in second.spans[0]
    ((_, program, vals),) = second.compiled
    assert program.leaf_kinds.count("S") == T
    for v in second.scalars():
        assert_resident(v, mesh)
    assert len({id(v) for v in second.scalars()}) == 1


def test_the_grouped_callable_takes_the_arrays(prk, mesh, monkeypatch):
    unit = N * N * 4
    monkeypatch.setattr(memory, "_xla_estimate", lambda program, avals: (
        22 * unit if not program.live_cuts
        else 4 * unit + 18 * unit // program.live_groups))
    memory._est_memo.clear()
    other = memory.ledger.live_bytes - 2 * unit
    monkeypatch.setenv("RAMBA_HBM_WATERMARK", str(other + 14 * unit))
    monkeypatch.setenv("RAMBA_HBM_BUDGET", str(2 * (other + 14 * unit)))
    try:
        with Calls(monkeypatch) as calls:
            prk.solve_and_check()
    finally:
        memory._est_memo.clear()
    assert calls.spans[0]["live_groups"] == 2
    assert "degraded" not in calls.spans[0]
    ((_, program, _),) = calls.compiled
    assert program.live_cuts
    assert len(calls.scalars()) == T
    for v in calls.scalars():
        assert_resident(v, mesh)


def test_the_segmented_rung_takes_the_arrays(prk, mesh, monkeypatch):
    monkeypatch.setattr(common, "max_program_instrs", 6)
    with Calls(monkeypatch) as calls:
        prk.solve_and_check()
    assert calls.spans[0]["segments"] >= 2 and len(calls.compiled) >= 2
    assert len(calls.scalars()) == T
    for v in calls.scalars():
        assert_resident(v, mesh)


@pytest.mark.parametrize("scalar", [2.5, 3, np.float32(2.5), 1 + 2j],
                         ids=["float", "int", "np.f32", "complex"])
def test_the_host_rung_takes_the_arrays_and_keeps_the_weak_type(
        scalar, mesh, parents_way, monkeypatch):
    monkeypatch.setenv("RAMBA_RETRY_ATTEMPTS", "1")
    seen = []
    real = fuser._run_host

    def spy(program, leaf_vals, span):
        seen.append([v for kind, v in zip(program.leaf_kinds, leaf_vals)
                     if kind == "S"])
        return real(program, leaf_vals, span)

    monkeypatch.setattr(fuser, "_run_host", spy)

    def run():
        fuser._compile_cache.clear()
        a = rt.fromarray(np.arange(1, 65, dtype=np.float16))
        with faults.active("compile:always,eager:always"):
            with Calls(monkeypatch) as calls:
                out = (a * scalar + scalar)._value()
        assert calls.spans[-1].get("degraded") == "host"
        return out

    ours = run()
    assert [isinstance(v, jax.Array) for v in seen[-1]] == [True, True]
    with parents_way():
        theirs = run()
    assert [type(v) for v in seen[-1]] == [type(scalar)] * 2
    # float16 * weak float stays float16; a strong float32 widens it
    assert (ours.dtype, ours.weak_type) == (theirs.dtype, theirs.weak_type)
    assert bits(ours) == bits(theirs)


def test_a_pinned_program_takes_the_arrays(monkeypatch):
    """Rank three on one device: ``layouts._Pinned`` keys its executables
    by the arguments' formats, now a real format for the scalar too, the
    same on every call."""
    old = mesh_mod.get_mesh()
    try:
        install(1)
        cube = np.random.default_rng(0).uniform(
            0.5, 1.5, (6, 128, 256)).astype(np.float32)
        a = rt.fromarray(cube)
        pinned = []
        for _ in range(3):
            with Calls(monkeypatch) as calls:
                a = a * 1.5 + 0.25
                rt.sync()
            (fn, _, vals), = calls.compiled
            assert fn.pins(*vals)
            pinned.append(fn._jit_for(vals))
            for v in calls.scalars():
                assert_resident(v, mesh_mod.get_mesh())
                assert layouts._format(v) is not None
        assert pinned[0] is pinned[1] is pinned[2]
        assert isinstance(pinned[0], layouts._Pinned)
        assert len(pinned[0]._compiled) == 1  # one executable, three calls
        want = cube
        for _ in range(3):
            want = want * np.float32(1.5) + np.float32(0.25)
        np.testing.assert_allclose(np.asarray(a), want, rtol=1e-6)
    finally:
        del a
        fuser.flush()
        mesh_mod.set_mesh(old)


def test_weak_and_strong_scalars_of_one_dtype_are_two_signatures():
    """``RowMajorJit`` decides a pin per signature: a resident ``1.0``
    (weak) and a resident ``np.float32(1)`` (strong) have one shape and
    dtype and are still two, as ``float`` and ``np.float32`` were."""
    fn = layouts.RowMajorJit(lambda a, s: (a * s,))
    x = jax.numpy.ones((4, 8), "float16")
    weak = jax.device_put(1.0)
    strong = jax.numpy.asarray(weak, dtype=weak.dtype)
    assert weak.weak_type and not strong.weak_type
    assert weak.dtype == strong.dtype
    fn._jit_for((x, weak))
    fn._jit_for((x, strong))
    assert len(fn._by_signature) == 2
    assert fn(x, weak)[0].dtype == np.float16
    assert fn(x, strong)[0].dtype == strong.dtype
