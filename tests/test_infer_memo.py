"""Node inference is done once per (function object, argument avals).

``expr.infer_aval`` keys a skeleton's kernel by identity and holds it
weakly, ``expr.Scalar`` reads the aval of a scalar type it has seen from a
table, and the kernel-path counters that inference's re-tracing used to
move are kept per flush by the fuser (``_count_kernel_paths``).  Counters:
``dag.infer.n`` misses, ``dag.infer.hit`` hits, ``dag.key.hit`` the hits
whose key was found without walking the static.  A basic index is
classified once (``ndarray._index_memo``, ``dag.index.hit``), and the same
encoding it hands back on every hit is what a view's static holds.
"""

import gc
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import ramba_tpu as rt
from benchmark.programs import nas_mg
from ramba_tpu import diagnostics
from ramba_tpu.core import expr, fuser
from ramba_tpu.core import ndarray as nd
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


def _carry_add(carry, x):
    return carry + x


def _build_scumulative(f):
    return rt.scumulative(f, _carry_add, rt.arange(16, dtype=np.float32),
                          associative=True)


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
    # built again (the first never flushed, so no program holds f): every
    # hit, the function's node among them, is found by the static as it
    # stands, and nothing new is kept
    entries = len(expr._fn_aval_memo[f])
    del out
    before = diagnostics.counters()
    out = build(f)
    moved = _moved(before, "dag.")
    assert "dag.infer.n" not in moved and moved["dag.infer.hit"] >= 1
    assert moved["dag.key.hit"] == moved["dag.infer.hit"]
    assert len(expr._fn_aval_memo[f]) == entries
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


def test_a_function_below_the_statics_first_member_is_found_by_the_walk(
        monkeypatch):
    """The unwalked lookup seeks an entry under the static's first member;
    one whose first function lies deeper is stored under that function,
    so each of its hits is found by the walk, and the entry still dies
    with the function."""
    monkeypatch.setitem(expr.OPS, "_nested_fn", lambda static, x: x + 1.0)
    aval = jax.ShapeDtypeStruct((4,), np.float32)
    f = lambda x: x  # noqa: E731
    ref = weakref.ref(f)
    static = (("tag", f),)
    expr.infer_aval("_nested_fn", static, [aval])
    for _ in range(2):
        before = diagnostics.counters()
        got = expr.infer_aval("_nested_fn", static, [aval])
        moved = _moved(before, "dag.")
        assert moved == {"dag.infer.hit": 1}
        assert (got.shape, got.dtype) == ((4,), np.float32)
    assert len(expr._fn_aval_memo[f]) == 1
    del f, static
    gc.collect()
    assert ref() is None


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
        assert "dag.key.hit" not in _moved(before, "dag.")
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
    for i, x64 in enumerate((True, False, True, False)):
        jax.config.update("jax_enable_x64", x64)
        before = diagnostics.counters()
        got = expr.infer_aval("map", ("true_divide",), [ints, ints])
        if i >= 2:  # the regime's own entry, found by the static unwalked
            assert _moved(before, "dag.") == {"dag.infer.hit": 1,
                                              "dag.key.hit": 1}
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


# -- (i) a basic index is classified once ------------------------------------

_SHAPE = (6, 7, 8)
_BASIC = [
    3, np.int64(2), True, -1, -6, None, Ellipsis,
    slice(None), slice(None, 4), slice(-3, None), slice(1, -1, 2),
    slice(None, None, -1), slice(np.int64(1), np.int32(5), np.int8(2)),
    slice(-5, np.int16(-1)),
    (1, slice(None), None), (Ellipsis, 2), (None, slice(1, -1), Ellipsis,
                                            np.int16(-2)),
    (slice(None), None), (None, slice(None)), (slice(1, 3), 2, -1), (),
]


@pytest.mark.parametrize("idx", _BASIC, ids=[repr(i) for i in _BASIC])
def test_a_basic_index_is_classified_once_and_served_the_same_enc(idx):
    nd._index_memo.clear()
    want = nd._classify(idx, _SHAPE)
    assert want[0] == "basic"
    # the key holds a slice's members, never the slice, which hashes only
    # from Python 3.12 on
    assert all(type(m) is not slice for m in nd._index_key(idx, _SHAPE))
    before = diagnostics.counters()
    first = nd._classify_index(idx, _SHAPE)
    moved = _moved(before, "dag.index.")
    assert moved["dag.index.n"] == 1 and "dag.index.hit" not in moved
    before = diagnostics.counters()
    again = nd._classify_index(idx, _SHAPE)
    assert _moved(before, "dag.index.hit") == {"dag.index.hit": 1}
    assert first == again == want
    assert again[1] is first[1]
    # through the array: a view whose static holds that very encoding
    a = rt.zeros(_SHAPE, dtype=np.float32)
    assert a[idx]._view.enc is first[1]


_APART = [(1, True), (1, np.int64(1)), (slice(1, 3), slice(True, 3)),
          (slice(1, 3), slice(np.int64(1), 3)),
          ((slice(None), None), (None, slice(None))),
          ((slice(1, 3), 2), (slice(1, 3, 2),))]


@pytest.mark.parametrize("one, other", _APART,
                         ids=[f"{a!r}-{b!r}" for a, b in _APART])
def test_indexes_of_other_items_or_types_never_share_an_entry(one, other):
    """The key holds each item's type and each slice member's: equal
    values of other types, and other indexes whose flat items read alike,
    are classified each on their own."""
    nd._index_memo.clear()
    got = nd._classify_index(one, _SHAPE)
    before = diagnostics.counters()
    theirs = nd._classify_index(other, _SHAPE)
    assert "dag.index.hit" not in _moved(before, "dag.index.")
    assert theirs == nd._classify(other, _SHAPE)
    assert len(nd._index_memo) == 2 and theirs[1] is not got[1]
    before = diagnostics.counters()
    assert nd._classify_index(one, (6, 7, 9)) == nd._classify(one, (6, 7, 9))
    assert "dag.index.hit" not in _moved(before, "dag.index.")


@pytest.mark.parametrize("idx", [6, -7, (0, 7), (0, 0, np.int64(8)),
                                 (Ellipsis, -9), (Ellipsis, 0, Ellipsis)],
                         ids=repr)
def test_an_index_that_raises_raises_on_every_call(idx):
    nd._index_memo.clear()
    before = diagnostics.counters()
    for _ in range(3):
        with pytest.raises(IndexError):
            nd._classify_index(idx, _SHAPE)
    moved = _moved(before, "dag.index.")
    assert moved["dag.index.n"] == 3 and "dag.index.hit" not in moved
    assert not nd._index_memo


def _not_basic():
    a = rt.arange(6, dtype=np.int32)
    rt.sync()
    return [slice(1.0, 3), (slice(None), slice(0, 2.5)), [0, 2],
            np.array([0, 2]), np.array([True, False] * 3), a % 3,
            a > 2, (a % 3, slice(None)), np.True_, (1, [0, 1])]


@pytest.mark.parametrize("pick", range(10), ids=[
    "float-in-slice", "float-in-second-slice", "list", "numpy-array",
    "numpy-mask", "ndarray", "ndarray-mask", "ndarray-in-tuple", "numpy-bool",
    "list-in-tuple"])
def test_any_other_index_takes_the_path_it_took_and_keeps_nothing(pick):
    idx = _not_basic()[pick]
    nd._index_memo.clear()
    assert nd._index_key(idx, _SHAPE) is None
    before = diagnostics.counters()
    for _ in range(2):
        got = nd._classify_index(idx, _SHAPE)
        assert got[0] == nd._classify(idx, _SHAPE)[0]
    moved = _moved(before, "dag.index.")
    assert moved["dag.index.n"] == 2 and "dag.index.hit" not in moved
    assert not nd._index_memo


def test_the_index_memo_stays_bounded():
    nd._index_memo.clear()
    shape = (4 * nd._INDEX_MEMO_MAX,)
    for i in range(nd._INDEX_MEMO_MAX + 100):
        nd._classify_index(i, shape)
        assert len(nd._index_memo) <= nd._INDEX_MEMO_MAX
    got = nd._classify_index(5, shape)
    assert got == nd._classify(5, shape)
    assert nd._classify_index(5, shape) is got


# -- (j) a second build of a script is its first, from the memos -------------

def _mg_build(n=16, nit=2):
    """NPB MG's timed section up to the norm's expression, never flushed
    (``scripts/dag_count_cost.py``'s build)."""
    cfg = {"n": n, "iterations": nit, "dtype": "float32", "smoother": "SWA",
           "norm": 1.0, "as_published": {"n": 512, "iterations": 20},
           "assumed": {"norm_rtol": 1e-4, "window_rtol": 2e-5}}
    prog = nas_mg.Program(rt, cfg, {"solve": [{"op": "mg"}]},
                          np.random.default_rng(3), 1)
    prog.v = rt.fromarray(nas_mg.wrap_ghosts(nas_mg.zran3(n, prog.dtype)[0]))
    rt.sync()

    def build():
        lt = prog.lt
        u = {lt: rt.zeros((n + 2,) * 3, dtype=prog.dtype)}
        r = {lt: prog.resid(u[lt], prog.v)}
        for _ in range(nit):
            prog.mg3p(u, prog.v, r)
            r[lt] = prog.resid(u[lt], prog.v)
        ri = r[lt][1:-1, 1:-1, 1:-1]
        return rt.sqrt(rt.sum(ri * ri) / float(n) ** 3)

    return build


def _nodes(root):
    """Every node under ``root``, each once, in the order a walk meets
    them: (op, static, shape, dtype, weak_type)."""
    seen, out, stack = set(), [], [root]
    while stack:
        e = stack.pop()
        if not isinstance(e, expr.Node) or id(e) in seen:
            continue
        seen.add(id(e))
        a = e.aval
        out.append((e.op, e.static, tuple(a.shape), a.dtype,
                    bool(getattr(a, "weak_type", False))))
        stack.extend(reversed(e.args))
    return out


def test_a_second_mg_build_is_the_first_served_from_the_memos(monkeypatch):
    build = _mg_build()
    # the first build as it was: every index classified, nothing of the
    # inference memos from before
    with monkeypatch.context() as m:
        m.setattr(nd, "_index_key", lambda idx, shape: None)
        _clear_memos()
        nd._index_memo.clear()
        before = diagnostics.counters()
        first = build()
        cold = _moved(before, "dag.")
    assert "dag.index.hit" not in cold and cold["dag.infer.n"] >= 1
    build()  # fills the index memo
    before = diagnostics.counters()
    second = build()
    warm = _moved(before, "dag.")
    assert warm["dag.node.n"] == cold["dag.node.n"]
    assert warm["dag.index.n"] == cold["dag.index.n"]
    assert warm["dag.index.hit"] == warm["dag.index.n"]
    assert "dag.infer.n" not in warm
    assert warm["dag.key.hit"] == warm["dag.infer.hit"] >= 1
    nodes = _nodes(first.read_expr())
    assert _nodes(second.read_expr()) == nodes
    assert len(nodes) > 50
    keys = [fuser._prepare_program([x.read_expr()])[0].key
            for x in (first, second)]
    assert keys[0] == keys[1]
    del first, second
    rt.sync()
