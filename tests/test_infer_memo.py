"""Node inference is done once per (function object, argument avals).

``expr.infer_aval`` keys a skeleton's kernel by identity and holds it
weakly, ``expr.Scalar`` reads the aval of a scalar type it has seen from a
table, and the kernel-path counters that inference's re-tracing used to
move are kept per flush by the fuser (``_count_kernel_paths``).  Counters:
``dag.infer.n`` misses, ``dag.infer.hit`` hits.
"""

import gc
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import ramba_tpu as rt
from ramba_tpu import diagnostics
from ramba_tpu.core import expr
from tests.helpers import prk_star_kernel


def _moved(before, prefix="dag.infer."):
    after = diagnostics.counters()
    return {k: v - before.get(k, 0) for k, v in after.items()
            if k.startswith(prefix) and v != before.get(k, 0)}


def _grid(n=64):
    i = rt.arange(n, dtype=np.float32)
    A = i[:, None] + i[None, :]
    rt.sync()
    return A


@pytest.fixture
def x64_restored():
    old = bool(jax.config.jax_enable_x64)
    yield
    jax.config.update("jax_enable_x64", old)


def _clear_memos():
    expr._aval_memo.clear()
    expr._fn_aval_memo.clear()
    expr._scalar_avals.clear()


# -- (a) one function object: first sight misses, repeats hit ---------------

def test_ten_stencil_nodes_of_one_function_miss_once():
    star, A = rt.stencil(prk_star_kernel()), _grid()
    before = diagnostics.counters()
    nodes = [rt.sstencil(star, A) for _ in range(10)]
    first = _moved(before)
    assert first.get("dag.infer.n", 0) <= 1
    assert first["dag.infer.hit"] >= 9
    before = diagnostics.counters()
    nodes += [rt.sstencil(star, A) for _ in range(10)]
    again = _moved(before)
    assert "dag.infer.n" not in again and "dag.infer.ns" not in again
    assert again["dag.infer.hit"] == 10
    assert len({(n.shape, n.dtype) for n in nodes}) == 1


# -- (b) two closures of one source never share an entry --------------------

def test_closures_of_one_source_each_miss_and_keep_their_own_aval():
    def make(k):
        def scale(x):
            return x * k
        return scale

    a = rt.arange(16, dtype=np.int32)
    rt.sync()
    by_two, by_half = make(2), make(2.5)
    assert by_two.__code__ is by_half.__code__
    before = diagnostics.counters()
    ints = rt.smap(by_two, a)
    assert _moved(before)["dag.infer.n"] == 1
    before = diagnostics.counters()
    floats = rt.smap(by_half, a)
    assert _moved(before)["dag.infer.n"] == 1  # not served by_two's entry
    assert ints.dtype.kind == "i" and floats.dtype.kind == "f"
    before = diagnostics.counters()
    assert rt.smap(by_two, a).dtype == ints.dtype
    assert rt.smap(by_half, a).dtype == floats.dtype
    again = _moved(before)
    assert "dag.infer.n" not in again and again["dag.infer.hit"] == 2
    assert expr._fn_aval_memo[by_two] is not expr._fn_aval_memo[by_half]


# -- (c) the memo never extends a function's life ---------------------------

def _build_smap(f):
    a = rt.arange(16, dtype=np.float32)
    return rt.smap(f, a)


def _build_stencil(f):
    return rt.sstencil(rt.stencil(f), _grid(32))


def _build_scumulative(f):
    return rt.scumulative(f, lambda carry, x: carry + x,
                          rt.arange(16, dtype=np.float32), associative=True)


@pytest.mark.parametrize("build, make", [
    (_build_smap, lambda: (lambda x: x + 1.0)),
    (_build_stencil, prk_star_kernel),
    (_build_scumulative, lambda: (lambda x, y: x + y)),
], ids=["smap", "sstencil", "scumulative"])
def test_memo_dies_with_its_function(build, make):
    gc.collect()
    held = len(expr._fn_aval_memo)
    f = make()
    ref = weakref.ref(f)
    out = build(f)
    assert f in expr._fn_aval_memo and expr._fn_aval_memo[f]
    # the inner keys hold no function, only weak references to them
    for key in expr._fn_aval_memo[f]:
        assert ref in key[1]
        assert not any(callable(m) and not isinstance(m, weakref.ref)
                       for m in key[1])
    del out, f
    gc.collect()
    assert ref() is None
    assert len(expr._fn_aval_memo) == held


def test_second_function_of_a_static_is_held_weakly_too():
    """The entry lives under the static's first function; a later one that
    dies leaves a dead reference in the key, never a pinned function."""
    def local(x, y):
        return x + y

    final = lambda carry, x: carry + x  # noqa: E731
    ref = weakref.ref(final)
    a = rt.arange(16, dtype=np.float32)
    out = rt.scumulative(local, final, a, associative=True)
    (key,) = expr._fn_aval_memo[local]
    assert weakref.ref(local) in key[1] and ref in key[1]
    del out, final
    gc.collect()
    assert ref() is None
    # a new function can never hit the dead entry, even at its address
    before = diagnostics.counters()
    rt.scumulative(local, lambda carry, x: carry + x, a, associative=True)
    assert _moved(before)["dag.infer.n"] >= 1


# -- (d) a _Lit in the static: no key, no pin --------------------------------

class _Payload:
    scale = 3.0


def test_literal_carrying_static_misses_and_is_not_retained():
    def scaled(x, p):
        return x * p.scale

    a = rt.arange(16, dtype=np.float32)
    rt.sync()
    rt.smap(scaled, a, _Payload())  # anything first-sight is behind us
    payload = _Payload()
    ref = weakref.ref(payload)
    for _ in range(2):
        before = diagnostics.counters()
        out = rt.smap(scaled, a, payload)
        assert _moved(before).get("dag.infer.n", 0) >= 1  # never a hit
    assert not expr._fn_aval_memo.get(scaled)
    # never flushed, so the compile cache (which does hold a program's
    # statics until the LRU turns them out) has not seen the literal
    del out, payload
    gc.collect()
    assert ref() is None


# -- (e) Scalar's table gives eval_shape's aval ------------------------------

_SCALARS = [True, 3, 2.5, 1j, np.float32(1), np.int64(1)]


@pytest.mark.parametrize("x64", [True, False], ids=["x64", "x32"])
@pytest.mark.parametrize("value", _SCALARS,
                         ids=[type(v).__name__ for v in _SCALARS])
def test_scalar_aval_is_eval_shapes(value, x64, x64_restored):
    jax.config.update("jax_enable_x64", x64)
    want = jax.eval_shape(lambda: jnp.asarray(value))
    for _ in range(2):  # first sight or not, then certainly from the table
        got = expr.Scalar(value).aval
        assert (got.shape, got.dtype, got.weak_type) == \
            (want.shape, want.dtype, want.weak_type)
    before = diagnostics.counters()
    expr.Scalar(value)
    assert _moved(before) == {"dag.infer.hit": 1}


@pytest.mark.parametrize("x64, value", [(False, 2**40), (True, 2**70)],
                         ids=["x32-2**40", "x64-2**70"])
def test_python_int_beyond_the_default_width_still_raises(x64, value,
                                                          x64_restored):
    jax.config.update("jax_enable_x64", x64)
    assert expr.Scalar(7).aval.weak_type  # the table has `int` for this regime
    for _ in range(2):
        with pytest.raises(OverflowError):
            expr.Scalar(value)


def test_python_int_within_the_x64_width_is_tabled(x64_restored):
    jax.config.update("jax_enable_x64", True)
    got = expr.Scalar(2**40).aval
    assert got.dtype == np.int64 and got.weak_type


# -- (f) the semantic fingerprint is in every key ---------------------------

def test_flipping_x64_does_not_serve_the_other_regimes_aval(x64_restored):
    ints = jax.ShapeDtypeStruct((8,), np.int32)
    seen = {}
    for x64 in (True, False, True, False):
        jax.config.update("jax_enable_x64", x64)
        got = expr.infer_aval("map", ("true_divide",), [ints, ints])
        want = jax.eval_shape(
            lambda a, b: expr.OPS["map"](("true_divide",), a, b), ints, ints)
        assert got.dtype == want.dtype, x64
        seen[x64] = got.dtype
    assert seen == {True: np.float64, False: np.float32}


# -- (g) kernel-path counters per flush, cache hit or not --------------------

def _flush_paths(out):
    before = diagnostics.counters()
    float(rt.sum(out))
    span = diagnostics.last_flushes(1)[0]
    moved = {k: v for k, v in _moved(before, "stencil.").items()
             if ".path." in k or k.endswith(".interpret")}
    return moved, span


def _noted(span):
    counted = {}
    for note in span.get("kernels", ()):
        name = f"{note['kernel']}.path.{note['path']}"
        counted[name] = counted.get(name, 0) + 1
    return counted


def test_cache_hit_flush_counts_the_paths_its_trace_took():
    star, A = rt.stencil(prk_star_kernel()), _grid()
    traced, span = _flush_paths(rt.sstencil(star, A))
    assert span["calls"][0]["cache"] == "miss"
    assert traced and traced == _noted(span)  # counted once, as noted
    replayed, span = _flush_paths(rt.sstencil(star, A))
    assert span["calls"][0]["cache"] == "hit"
    assert replayed == traced
    assert "kernels" not in span  # span notes stay per trace


def test_replay_follows_the_signature_that_was_traced():
    """One executable serves every shape of a program; a kernel may choose
    its path by shape (here: below ``dist_threshold`` nothing is sharded)."""
    star = rt.stencil(prk_star_kernel(1))
    small, large = _grid(8), _grid(128)
    first = {}
    for name, A in (("small", small), ("large", large)):
        first[name], span = _flush_paths(rt.sstencil(star, A))
        assert first[name] == _noted(span), name
    if len(jax.devices()) > 1:
        assert first["small"] != first["large"]
    for name, A in (("small", small), ("large", large), ("small", small)):
        again, span = _flush_paths(rt.sstencil(star, A))
        assert again == first[name], name
        assert "kernels" not in span


def test_program_without_kernels_keeps_no_notes():
    from ramba_tpu.core import fuser

    x = rt.arange(64, dtype=np.float32)
    rt.sync()
    held = len(fuser._traced_kernel_notes)
    for _ in range(2):
        moved, span = _flush_paths(x * 3.0 + 1.0)
        assert moved == {} and "kernels" not in span
    assert len(fuser._traced_kernel_notes) == held


# -- (h) hits change no bit ---------------------------------------------------

def _prk_pass(star, A, B, iterations=3):
    for _ in range(iterations):
        B += rt.sstencil(star, A)
        A += 1.0
    return float(rt.sum(abs(B)))


def test_second_pass_on_hits_gives_the_bits_of_a_pass_on_misses():
    results = []
    for clear_between in (False, True):
        star, A = rt.stencil(prk_star_kernel()), _grid(48)
        B = rt.zeros((48, 48), dtype=np.float32)
        norms = [_prk_pass(star, A, B)]
        if clear_between:
            _clear_memos()
        before = diagnostics.counters()
        norms.append(_prk_pass(star, A, B))
        moved = _moved(before)
        if clear_between:
            assert moved["dag.infer.n"] >= 3  # stencil, the updates, 1.0
        else:
            assert "dag.infer.n" not in moved and moved["dag.infer.hit"] >= 9
        results.append((norms, B.asarray(), A.asarray()))
    (hit_norms, hit_B, hit_A), (miss_norms, miss_B, miss_A) = results
    assert hit_norms == miss_norms
    np.testing.assert_array_equal(hit_B, miss_B)
    np.testing.assert_array_equal(hit_A, miss_A)
    want = 2 * 3 * 2.0 * (48 - 4) ** 2  # PRK's closed form: norm = 2T a point
    assert hit_norms[1] == pytest.approx(want, rel=1e-5)
