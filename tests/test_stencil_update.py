"""``rewrite.fold_stencil_update``: where the script writes ``v - s``,
``u + s`` or ``s + u`` of a rank-3 float32 stencil ``s`` and an array of
its shape and dtype, ONE ``stencil_update`` node; every other operation
builds the script's nodes.  Nothing is flushed here: the nodes are read
as they are built.
"""

import itertools

import numpy as np
import pytest

import ramba_tpu as rt
from ramba_tpu import common, diagnostics
from ramba_tpu.core import rewrite


def _p27(a):
    acc = None
    for d in itertools.product((-1, 0, 1), repeat=3):
        term = (0.5, 0.25, 0.125, 0.0625)[sum(abs(x) for x in d)] * a[d]
        acc = term if acc is None else acc + term
    return acc


def _star2(a):
    return 0.25 * (a[0, 1] + a[0, -1] + a[1, 0] + a[-1, 0])


def _cube(dtype="float32", shape=(6, 10, 12)):
    return rt.fromarray(np.ones(shape, dtype))


def _star_update():
    """PRK's ``B += sstencil(star, A)``: rank 2, the node it builds."""
    a, b = (rt.fromarray(np.ones((16, 16), np.float32)) for _ in range(2))
    b += rt.sstencil(rt.stencil(_star2), a)
    return b


#: (what the script writes, the node it makes, firings)
_CASES = {
    "v - s": (lambda s, v: v - s, "stencil_update", 1),
    "u + s": (lambda s, v: v + s, "stencil_update", 1),
    "s + u": (lambda s, v: s + v, "stencil_update", 1),
    "u += s": (lambda s, v: v.__iadd__(s), "stencil_update", 1),
    "s - v": (lambda s, v: s - v, "map", 0),
    "v - 2 * s": (lambda s, v: v - 2 * s, "map", 0),
    "v * s": (lambda s, v: v * s, "map", 0),
    "rank 2": (lambda s, v: _star_update(), "map", 0),
    "mixed dtypes": (lambda s, v: _cube("int32") - s, "map", 0),
    "bfloat16 base": (lambda s, v: v.astype("bfloat16") + s, "map", 0),
    "broadcast base": (
        lambda s, v: _cube(shape=(10, 12)) - s, "map", 0),
    "stencil_iter": (
        lambda s, v: v - rt.sstencil_iterate(rt.stencil(_p27), _cube(), 2),
        "map", 0),
    "rewrites off": (lambda s, v: v - s, "map", 0),
}


@pytest.mark.parametrize("case", list(_CASES))
def test_the_fold_fires_on_rank3_updates_only(case, monkeypatch):
    write, op, fires = _CASES[case]
    monkeypatch.setattr(common, "rewrite_enabled", case != "rewrites off")
    s = rt.sstencil(rt.stencil(_p27), _cube())
    v = _cube()
    before = diagnostics.counters().get("rewrite.rewrite_stencil_update", 0)
    stat = rewrite.stats["rewrite_stencil_update"]
    node = write(s, v).read_expr()
    assert node.op == op
    assert (diagnostics.counters().get("rewrite.rewrite_stencil_update", 0)
            - before) == fires
    assert rewrite.stats["rewrite_stencil_update"] - stat == fires
    if op == "stencil_update":
        (fname, at), *static = node.static
        assert fname == ("subtract" if "-" in case else "add")
        assert at == (1 if case == "s + u" else 0)
        stencil = s.read_expr()
        assert tuple(static) == stencil.static
        assert node.args[1:] == stencil.args
        assert node.args[0] is v.read_expr() or case == "u += s"
        assert (node.aval.shape, node.aval.dtype) == ((6, 10, 12),
                                                      np.float32)
