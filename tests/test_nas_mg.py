"""NAS MG (``benchmark/programs/nas_mg.py``): the NumPy reference against
NPB's published class-S value, the ``ramba_tpu`` port against the
reference at small sizes, each operator alone with the periodic seam, and
the flush that is too long for one program: cut at the same places of
every iteration, so that one executable serves them all.
"""

import numpy as np
import pytest

import ramba_tpu as rt
from benchmark.programs import nas_mg
from ramba_tpu import common, diagnostics
from ramba_tpu.core import fuser
from ramba_tpu.resilience import memory

#: NPB 3.x MG, class S: 32^3, 4 iterations (the published verification
#: value; the norm after each iteration is this repo's own float64 run)
CLASS_S = 0.5307707005734e-04
CLASS_S_BY_ITERATION = [2.933796097632787e-03, 6.315001790622818e-04,
                        1.7360856792372287e-04, 5.307707005734874e-05]


def program(n, nit, smoother="SWA", resident=True):
    cfg = {"n": n, "iterations": nit, "dtype": "float32",
           "smoother": smoother, "norm": 1.0,
           "as_published": {"n": 512, "iterations": 20},
           "stencil_paths": {"kernel": "pallas_padded", "fusion": "xla"},
           "assumed": {"norm_rtol": 1e-4, "window_rtol": 2e-5}}
    prog = nas_mg.Program(rt, cfg, {"solve": [{"op": "mg"}]},
                          np.random.default_rng(3), 1)
    if resident:
        v, prog.charges = nas_mg.zran3(n, prog.dtype)
        prog.v = rt.fromarray(nas_mg.wrap_ghosts(v))
    return prog


def inner(a):
    return np.asarray(a)[1:-1, 1:-1, 1:-1]


def moved(before, name):
    return diagnostics.counters().get(name, 0) - before.get(name, 0)


# -- the reference is the source's -------------------------------------------
def test_mg_np_float64_gives_npbs_class_s_value():
    norms, u, r = nas_mg.mg_np(32, 4, np.float64, "SWA")
    assert abs(norms[-1] - CLASS_S) <= 1e-8 * CLASS_S
    np.testing.assert_allclose(norms, CLASS_S_BY_ITERATION, rtol=1e-12)
    assert u.dtype == r.dtype == np.float64


def test_the_generator_and_the_charges():
    x = nas_mg.randlc_stream(3) * 2.0 ** -46
    np.testing.assert_allclose(x, [0.79452191, 0.86906527, 0.64763173],
                               atol=5e-9)
    # drawn in blocks or whole, the stream and its extremes are the same
    whole = nas_mg.randlc_stream(40 ** 3)
    v, charges = nas_mg.zran3(40, np.float32)
    order = np.argsort(whole)
    assert set(charges[:10]) == set(order[-10:])
    assert set(charges[10:]) == set(order[:10])
    assert v.sum() == 0 and np.abs(v).sum() == 20
    assert (v.ravel()[charges[:10]] == 1).all()


def test_zran3_block_by_block(monkeypatch):
    want, _ = nas_mg.zran3(16, np.float32)
    monkeypatch.setattr(nas_mg, "ZRAN_BLOCK", 1000)  # 4096 = 4 blocks and a bit
    got, _ = nas_mg.zran3(16, np.float32)
    np.testing.assert_array_equal(got, want)


# -- the port against the reference ------------------------------------------
@pytest.mark.parametrize("n,smoother", [(16, "SWA"), (16, "B+"),
                                        (32, "SWA"), (32, "B+")])
def test_a_solve_agrees_with_mg_np_float32(n, smoother):
    prog = program(n, 4, smoother)
    (norm,) = prog.solve()
    norms, u, r = nas_mg.mg_np(n, 4, np.float32, smoother)
    assert abs(norm - norms[-1]) <= 2e-5 * norms[-1]
    # u is O(1) near a charge; r is what is left of +-1 after four cycles
    np.testing.assert_allclose(inner(prog.u), u, rtol=0, atol=2e-6)
    np.testing.assert_allclose(inner(prog.r), r, rtol=0,
                               atol=2e-6 * max(1.0, float(np.abs(u).max())))
    for a in (prog.u, prog.r):  # the ghost layers are the faces they copy
        host = np.asarray(a)
        np.testing.assert_array_equal(host, nas_mg.comm3(host.copy()))


@pytest.mark.parametrize("op", ["resid", "psinv", "rprj3", "interp",
                                "interp_onto_zero"])
def test_an_operator_alone_against_its_numpy_form(op):
    """On a random periodic field, every point compared: the seam (first
    and last planes of every axis) is part of the array."""
    n, f = 16, np.float32
    prog = program(n, 1)
    rng = np.random.default_rng(11)
    a, b = (rng.standard_normal((n, n, n)).astype(f) for _ in range(2))
    z = rng.standard_normal((n // 2,) * 3).astype(f)
    put = lambda x: rt.fromarray(nas_mg.wrap_ghosts(x))  # noqa: E731
    if op == "resid":
        got, want = prog.resid(put(a), put(b)), nas_mg.resid_np(a, b)
    elif op == "psinv":
        got = prog.psinv(put(a), put(b))
        want = nas_mg.psinv_np(a, b, prog.smoother)
    elif op == "rprj3":
        got, want = prog.rprj3(put(a)), nas_mg.rprj3_np(a)
    elif op == "interp":
        got, want = prog.interp(put(z), put(a)), nas_mg.interp_np(z, a)
    else:
        got = prog.interp(put(z), None)
        want = nas_mg.interp_np(z, np.zeros_like(a))
    host = np.asarray(got)
    assert host.dtype == f and host.shape == tuple(s + 2 for s in want.shape)
    np.testing.assert_allclose(host[1:-1, 1:-1, 1:-1], want, rtol=0,
                               atol=2e-5)
    np.testing.assert_array_equal(host, nas_mg.comm3(host.copy()))


def test_the_byte_and_flop_conventions():
    prog = program(512, 20, "B+", resident=False)
    # NPB's own 58 flops a finest point and iteration
    assert prog.algo_flops_per_solve() == 58 * 512 ** 3 * 20
    # at the finest level an iteration is 2 resid + psinv (3 passes each),
    # interp (2 + 1/8) and rprj3 (1 + 1/8): 12.25 passes; a level below has
    # 10 of its own, an eighth the size each time; the first resid is 2
    per_it = prog.algo_bytes_per_solve() / 20 / (512 ** 3 * 4)
    assert abs(per_it - (12 + 10 / 7 + 2 / 20)) < 0.01
    assert prog.stencil_bytes_per_solve() < prog.algo_bytes_per_solve()


# -- a flush too long for one program ------------------------------------------
def test_a_long_solve_runs_segmented_and_equals_the_unsegmented_one(
        monkeypatch):
    prog = program(16, 4)
    monkeypatch.setattr(common, "max_program_instrs", 0)
    (whole,) = prog.solve()
    span = diagnostics.last_flushes()[-1]
    assert span["segments"] == 0 and span["instrs"] > 64
    u_whole, r_whole = np.asarray(prog.u), np.asarray(prog.r)

    monkeypatch.setattr(common, "max_program_instrs", 64)
    before = diagnostics.counters()
    (cut,) = prog.solve()
    span = diagnostics.last_flushes()[-1]
    assert span.get("degraded") is None  # the fused rung
    assert span["segments"] >= 2 and span["cache"] == "miss"
    calls = moved(before, "fuser.segments")
    assert calls == span["segments"] + 1 == len(span["calls"])
    assert (moved(before, "fuser.segment.miss")
            + moved(before, "fuser.segment.hit")) == calls
    assert cut == whole
    np.testing.assert_array_equal(np.asarray(prog.u), u_whole)
    np.testing.assert_array_equal(np.asarray(prog.r), r_whole)

    before = diagnostics.counters()
    (again,) = prog.solve()
    span = diagnostics.last_flushes()[-1]
    assert again == cut and span["cache"] == "hit"
    assert moved(before, "fuser.segment.hit") == calls
    assert moved(before, "fuser.segment.miss") == 0
    assert moved(before, "stencil.path.xla") >= 1


def test_every_iteration_runs_the_same_executables(monkeypatch):
    """Forty iterations linearize to forty repetitions: cut at the same
    places of each, all but the first share their segments."""
    monkeypatch.setattr(common, "max_program_instrs", 192)
    prog = program(8, 40)
    before = diagnostics.counters()
    prog.solve()
    span = diagnostics.last_flushes()[-1]
    calls = moved(before, "fuser.segments")
    assert calls >= span["instrs"] // common.max_program_instrs >= 4
    assert moved(before, "fuser.segment.miss") <= 4
    assert moved(before, "fuser.segment.hit") >= calls - 4


def test_a_program_is_cut_once(monkeypatch):
    """Finding the loop hashes every instruction: the cut places are kept
    by the program's key, for the run, admission's estimate and every
    later flush of the same script."""
    cuts = []
    real = fuser._segment_ends
    monkeypatch.setattr(fuser, "_segment_ends",
                        lambda p, size: cuts.append(len(p.instrs))
                        or real(p, size))
    fuser._segments_cache.clear()
    prog = program(8, 40)
    first = prog.solve()
    span = diagnostics.last_flushes()[-1]
    assert span["segments"] >= 4 and cuts == [span["instrs"]]
    assert prog.solve() == first and len(cuts) == 1
    p = _chain(40)
    last_use = fuser._last_use_map(p)
    assert fuser._iter_segments(p, last_use, 16) is fuser._iter_segments(
        _chain(40), last_use, 16)
    assert fuser._iter_segments(p, last_use, 8) is not fuser._iter_segments(
        p, last_use, 16)


def _chain(n_ops, prefix=0, suffix=0):
    x = rt.zeros(64, dtype="float32")
    for i in range(prefix):
        x = rt.sin(x) * float(i + 2)
    for _ in range(n_ops):
        x = rt.sqrt(x * x + 1.0) - x
    for i in range(suffix):
        x = rt.cos(x) + float(i + 2)
    program, _leaves, _ = fuser._prepare_program([x.read_expr()])
    return program


def _reps(period, count, size):
    """Repetitions to a segment of ``size``: the fewest that reach an
    eighth of it (at most what fits and the count), or the largest divisor
    of the count from half of that up."""
    want = min(max(1, -(-(size // 8) // period)), size // period, count)
    return max((k for k in range(1, want + 1)
                if count % k == 0 and 2 * k >= want), default=want)


def test_segment_ends_follow_the_loop():
    p = _chain(400, prefix=7, suffix=3)
    start, body, count = fuser._repetition(p)  # as rewritten: 4 or 5
    assert 3 <= body <= 5 and count >= 399 and start <= 2 * 7 + 2
    ends = fuser._segment_ends(p, 384)
    assert ends[-1] == len(p.instrs) and ends == sorted(set(ends))
    sizes = np.diff([0] + ends)
    assert sizes.max() <= 384
    # whole iterations to a segment: the fewest that reach an eighth of
    # it, or a divisor of the count that is at least half of that
    reps = _reps(body, count, 384)
    assert reps >= 5  # short repetitions are still packed
    inside = [s for e, s in zip(ends, sizes)
              if start < e <= start + body * count and s >= 5 * body]
    assert inside and all(s == reps * body for s in inside[1:-1])
    assert 2 * inside[1] >= -(-48 // body) * body
    # a loop longer than a segment is cut into equal parts
    ends = fuser._segment_ends(_chain(400), 3)
    assert set(np.diff(ends[2:-2])) <= {2, 3}
    # no loop: every seg_size instructions, as before
    x = rt.zeros(64, dtype="float32")
    for i in np.random.default_rng(5).integers(0, 3, 60):
        x = (x * 2.0, rt.sin(x), rt.cos(x) + x)[i]
    plain, _l, _ = fuser._prepare_program([x.read_expr()])
    n = len(plain.instrs)
    assert fuser._segment_ends(plain, 8) == list(range(8, n, 8)) + [n]


def test_short_repetitions_are_still_packed():
    """At the segment size the flush runs with, a loop of three to five
    instructions a repetition still packs a tenth of a segment and more
    to a call: the fixed cost of a call stays paid once for many."""
    p = _chain(400)
    start, body, count = fuser._repetition(p)
    size = common.max_program_instrs
    ends = fuser._segment_ends(p, size)
    inside = [b - a for a, b in zip([0] + ends, ends)
              if start < b <= start + body * count]
    assert len(inside) >= 3 and inside[1] == _reps(body, count, size) * body
    assert inside[1] >= size // 16 and len(ends) <= 2 * count // 10


@pytest.mark.parametrize("n_ops,fit", [(38, 3), (39, 3), (40, 3), (42, 3),
                                       (47, 3), (98, 10), (102, 10)])
def test_a_remainder_makes_no_program_of_its_own(n_ops, fit):
    """``fit`` repetitions reach an eighth of a segment.  Where a number
    from half of that up divides the count, the loop is cut so many at a
    time; where none does (a prime count), ``fit`` at a time, and the
    repetitions left over are cut with what stands after the loop.  Either
    way every segment inside the loop is the same program and the calls at
    most double (``mg-C``: 19 repetitions of 294 instructions, two to a
    segment, left a fourth executable to trace, lower and compile)."""
    p = _chain(n_ops, suffix=2)
    start, body, count = fuser._repetition(p)
    size = 8 * fit * body
    ends = fuser._segment_ends(p, size)
    reps = _reps(body, count, size)
    whole = start + body * reps * (count // reps)
    inside = [e for e in ends if start < e <= whole]
    assert set(np.diff([start] + inside)) == {reps * body}
    assert inside[-1] == whole and len(inside) <= 2 * -(-count // fit)
    # the rest, left-over repetitions and all, every ``size``
    rest = [e for e in ends if e > whole]
    assert rest == list(range(whole + size, len(p.instrs), size)) + [
        len(p.instrs)]
    segments = fuser._iter_segments(p, fuser._last_use_map(p), size)
    in_loop = {seg.key for (seg, _in, _out, top), e in zip(segments, ends)
               if start < e <= whole}
    assert len(in_loop) <= 2  # the first may read leaves, the rest carry


@pytest.mark.parametrize("start,period,count,tail,size,calls", [
    (9, 110, 19, 111, 768, [9] + [110] * 19 + [111]),       # mg-C, PR 38
    (9, 294, 19, 295, 768, [9] + [294] * 19 + [295]),       # mg-C, PR 35
    (9, 668, 19, 680, 768, [9] + [668] * 19 + [680]),       # mg-C, PR 34
    (9, 294, 20, 1, 768, [9] + [294] * 20 + [1]),
    (0, 10, 97, 5, 768, [100] * 9 + [75]),  # a prime count: ten at a time
    (0, 10, 96, 0, 768, [80] * 12),       # 8 divides, 10 reach 96
    (3, 20, 42, 0, 768, [3] + [60] * 14),  # 3 divides, 5 reach 96
    (0, 10, 23, 900, 768, [100] * 2 + [768, 162]),
    (5, 30, 3, 1000, 1000, [5, 90, 1000]),  # fewer repetitions than reach
], ids=["mg-C", "mg-C-PR35", "mg-C-PR34", "even", "prime", "divisor",
        "three-of-five", "left-over-and-tail", "short-loop"])
def test_the_cut_by_its_numbers(start, period, count, tail, size, calls,
                                monkeypatch):
    """``_segment_ends`` from a loop's place, period and count alone: a
    repetition of an eighth of a segment or more is a call of its own."""
    import types

    n = start + period * count + tail
    monkeypatch.setattr(fuser, "_repetition",
                        lambda p: (start, period, count))
    ends = fuser._segment_ends(types.SimpleNamespace(instrs=[None] * n), size)
    assert list(np.diff([0] + ends)) == calls


def test_admission_estimates_a_segmented_program_by_its_segments(
        monkeypatch):
    """The whole program is never lowered: each distinct segment once,
    and the estimate is the worst segment's plus what is carried past
    it."""
    lowered = []

    def fake(seg, avals):
        lowered.append(len(seg.instrs))
        return 1000 * len(seg.instrs)

    monkeypatch.setattr(memory, "_xla_estimate", fake)
    monkeypatch.delenv("RAMBA_HBM_ESTIMATE", raising=False)
    memory._est_memo.clear()
    p = _chain(400, prefix=7)
    x0 = np.zeros(64, np.float32)
    leaves = [x0 if k == "C" else 1.0 for k in p.leaf_kinds]
    est = memory.estimate_program_bytes(p, leaves)
    memory._est_memo.clear()
    assert max(lowered) <= common.max_program_instrs
    assert len(lowered) <= 4  # the distinct ones only
    carried = sum(64 * 4 if k == "C" else 8 for k in p.leaf_kinds)
    assert 1000 * max(lowered) <= est <= 1000 * max(lowered) + carried + 512


# -- the rank-3 Pallas kernel under the solve ---------------------------------
def sweeps_by_level(lt, nit):
    """27-point sweeps of one solve at each level: the finest has the
    first ``resid`` and per iteration two ``resid``, ``psinv`` and
    ``rprj3``'s P; a level between has ``rprj3``, ``resid``, ``psinv``;
    the coarsest ``psinv`` alone."""
    return {k: 4 * nit + 1 if k == lt else 3 * nit if k > 1 else nit
            for k in range(1, lt + 1)}


def test_the_finest_level_takes_the_kernel_and_the_counters_say_so(
        monkeypatch):
    """With the kernel interpreting and its threshold at the toy's finest
    level (18^3), a solve's operators run on ``pallas_padded`` there and
    on ``xla`` below, the norm is the XLA path's, and the two counters
    move by the sweeps on each side of the predicate: when the flush
    traces, when it hits, and when it runs as chained segments."""
    from ramba_tpu.ops import stencil_pallas, stencil_sharded

    n, nit = 16, 4
    (xla_norm,) = program(n, nit).solve()
    monkeypatch.setattr(stencil_pallas, "_INTERPRET", True)
    monkeypatch.setattr(stencil_pallas, "_ENABLED", True)
    monkeypatch.setattr(stencil_pallas, "_RANK3_MIN_LANES", n + 2)
    monkeypatch.setattr(stencil_sharded, "eligible", lambda *a, **k: False)
    by_level = sweeps_by_level(4, nit)
    want = {"stencil.path.pallas_padded": by_level[4],
            "stencil.path.xla": sum(by_level.values()) - by_level[4]}
    assert want == {"stencil.path.pallas_padded": 17, "stencil.path.xla": 28}
    # what mg-C's solve must read with 514^3 and 258^3 on the kernel
    c = sweeps_by_level(9, 20)
    assert (c[9] + c[8], sum(c.values()) - c[9] - c[8]) == (141, 380)

    prog = program(n, nit)
    assert prog.expected_paths(1) == ("pallas_padded", "xla")
    # a script's first solve also traces each distinct operator once for
    # its result's type (a miss of node inference): A, S, P at the finest
    # level, the three at levels 3 and 2 and S at the coarsest
    inferred = {"stencil.path.pallas_padded": 3, "stencil.path.xla": 7}
    for segment_at, cache in ((0, "miss"), (0, "hit"), (64, "miss"),
                              (64, "hit")):
        monkeypatch.setattr(common, "max_program_instrs", segment_at)
        before = diagnostics.counters()
        (norm,) = prog.solve()
        span = diagnostics.last_flushes()[-1]
        assert span["cache"] == cache and span.get("degraded") is None
        assert (span["segments"] > 0) == bool(segment_at)
        first = moved(before, "dag.infer.n") > 0
        assert first == ((segment_at, cache) == (0, "miss"))
        assert {k: moved(before, k) - first * inferred[k]
                for k in want} == want, (segment_at, cache)
        assert not moved(before, "stencil.degraded")
        assert not moved(before, "stencil.operand_copy")
        assert abs(norm - xla_norm) <= prog.norm_rtol * xla_norm
        if cache == "miss":
            notes = [k for k in span["kernels"] if k["path"] == "pallas_padded"]
            assert notes and all(
                k["interpret"] and k["halo"] == "edge"
                and k["grid"] == -(-(n + 2) // k["block_planes"])
                for k in notes)
    host = np.asarray(prog.r)
    np.testing.assert_array_equal(host, nas_mg.comm3(host.copy()))


def updates(lt, nit):
    """``v - A u`` and ``u + S r`` of one solve, the stencil folded into
    each (``rewrite.rewrite_stencil_update``): per iteration ``resid`` and
    ``psinv`` at the lt - 1 levels above the coarsest and the closing
    ``resid``; the first ``resid``."""
    return (2 * (lt - 1) + 1) * nit + 1


def test_mg_cs_updates_by_the_scripts_count():
    assert updates(9, 20) == 341
    # on the kernel at 514^3 and 258^3: three an iteration at the finest
    # and the first resid, two an iteration at 258^3; XLA's the rest
    fused = 3 * 20 + 1 + 2 * 20
    assert (fused, updates(9, 20) - fused) == (101, 240)
    # a folded update is one instruction of the two: 17 an iteration
    assert 110 - (updates(9, 20) - 1) // 20 == 93


def test_the_update_in_the_kernels_store_changes_no_bit(monkeypatch):
    """A toy solve with the kernel interpreting at its finest level
    (18^3) and ``rewrite_stencil_update`` on, against the same solve with
    the rewriter off: the norm and both arrays to the last bit, on the
    flush that traces and on the one that hits; every firing is written
    by the kernel's store at the finest level and by XLA's map below."""
    from ramba_tpu.ops import stencil_pallas, stencil_sharded

    n, nit, lt = 16, 3, 4
    monkeypatch.setattr(stencil_pallas, "_INTERPRET", True)
    monkeypatch.setattr(stencil_pallas, "_ENABLED", True)
    monkeypatch.setattr(stencil_pallas, "_RANK3_MIN_LANES", n + 2)
    monkeypatch.setattr(stencil_sharded, "eligible", lambda *a, **k: False)
    monkeypatch.setattr(common, "rewrite_enabled", False)
    before = diagnostics.counters()
    prog = program(n, nit)
    (plain,) = prog.solve()
    u, r = np.asarray(prog.u), np.asarray(prog.r)
    assert not moved(before, "rewrite.rewrite_stencil_update")

    monkeypatch.setattr(common, "rewrite_enabled", True)
    prog = program(n, nit)
    fused = 3 * nit + 1
    want = {"rewrite.rewrite_stencil_update": updates(lt, nit),
            "stencil.epilogue.fused": fused,
            "stencil.epilogue.unfused": updates(lt, nit) - fused}
    assert want["stencil.epilogue.unfused"] == 2 * (lt - 2) * nit
    for cache in ("miss", "hit"):
        before = diagnostics.counters()
        (norm,) = prog.solve()
        span = diagnostics.last_flushes()[-1]
        assert span["cache"] == cache and span.get("degraded") is None
        got = {k: moved(before, k) for k in want}
        assert got == want, cache
        assert (got["stencil.epilogue.fused"]
                + got["stencil.epilogue.unfused"] == updates(lt, nit))
        assert not moved(before, "stencil.degraded")
        assert norm == plain
        np.testing.assert_array_equal(np.asarray(prog.u), u)
        np.testing.assert_array_equal(np.asarray(prog.r), r)
        if cache == "miss":
            notes = [k for k in span["kernels"] if k["kernel"] == "stencil"
                     and k.get("epilogue", "none") != "none"]
            assert len(notes) == updates(lt, nit)
            assert {(k["path"], k["epilogue"], k["epilogue_fused"])
                    for k in notes} == {
                ("pallas_padded", "subtract", True),
                ("pallas_padded", "add", True),
                ("xla", "subtract", False), ("xla", "add", False)}


# -- a ghost-layer refresh is one node ----------------------------------------
def refreshes(lt, nit):
    """``comm3`` calls of one solve: per iteration ``rprj3`` at lt - 1
    levels, ``psinv`` at the coarsest, ``interp``, ``resid``, ``psinv`` at
    the lt - 1 above it, and the closing ``resid``; the first ``resid``."""
    return (4 * lt - 2) * nit + 1


def test_mg_cs_refreshes_by_the_scripts_count():
    assert refreshes(9, 20) == 681 and 6 * refreshes(9, 20) == 4086
    # the walk wherever a plane has a whole row tile: 514^3 down to 10^3,
    # not 6^3 (four an iteration) and 4^3 (two)
    assert (4 * 20 + 1) + 6 * 4 * 20 == 561 and 681 - 561 == 6 * 20
    # 12 of a refresh's 13,381 - 681 x 11 instructions are one; then
    # 24 of each of the 160 prolongations' (PR 38): 19 repetitions of 110
    assert 13381 - 11 * refreshes(9, 20) == 5890
    assert 5890 - 23 * 8 * 20 == 2210 and 294 - 23 * 8 == 110


@pytest.mark.parametrize("where", ["mesh", "walk"])
@pytest.mark.parametrize("segment_at", [0, 64], ids=["whole", "segmented"])
def test_the_refresh_as_one_node_changes_no_bit(segment_at, where,
                                                monkeypatch, request):
    """A toy solve with ``rewrite_face_copies`` against the same solve
    with the rewriter off: the norm and both arrays to the last bit, on
    the flush that traces and the one that hits, whole and in segments;
    the two counters read what the script's count says.  ``walk``: one
    device, the kernel interpreting wherever an array has a whole row
    tile: the toy's 18^3 and 10^3, not its 6^3 and 4^3."""
    n, nit, lt = 16, 3, 4
    total, walks = refreshes(lt, nit), (4 * nit + 1) + 4 * nit
    if where == "walk":
        request.getfixturevalue("one_device")
        request.getfixturevalue("interpreting_walk")
    monkeypatch.setattr(common, "max_program_instrs", segment_at)
    monkeypatch.setattr(common, "rewrite_enabled", False)
    prog = program(n, nit)
    before = diagnostics.counters()
    (plain,) = prog.solve()
    u, r = np.asarray(prog.u), np.asarray(prog.r)
    instrs = diagnostics.last_flushes()[-1]["instrs"]
    assert not moved(before, "faces.path.dus")
    assert not moved(before, "faces.path.wrap")

    monkeypatch.setattr(common, "rewrite_enabled", True)
    prog = program(n, nit)
    want = {"faces.path.wrap": walks if where == "walk" else 0,
            "rewrite.rewrite_face_copies": 6 * total,
            "rewrite.rewrite_stencil_update": updates(lt, nit)}
    want["faces.path.dus"] = total - want["faces.path.wrap"]
    for cache in ("miss", "hit"):
        before = diagnostics.counters()
        (norm,) = prog.solve()
        span = diagnostics.last_flushes()[-1]
        assert span["cache"] == cache and span.get("degraded") is None
        assert (span["segments"] > 0) == bool(segment_at)
        # a refresh is one instruction of twelve, a prolongation of 24, an
        # update one of two
        assert span["instrs"] == (instrs - 11 * total - 23 * (lt - 1) * nit
                                  - updates(lt, nit))
        assert {k: moved(before, k) for k in want} == want, cache
        assert norm == plain
        np.testing.assert_array_equal(np.asarray(prog.u), u)
        np.testing.assert_array_equal(np.asarray(prog.r), r)
        if cache == "miss":
            notes = [k for k in span["kernels"] if k["kernel"] == "faces"]
            assert len(notes) == total
            walked = [k for k in notes if k["path"] == "wrap"]
            assert len(walked) == want["faces.path.wrap"] and all(
                k["interpret"] and k["grid"] in (-(-18 // k["block_planes"]),
                                                 -(-10 // k["block_planes"]))
                and k["row_block_planes"] % k["block_planes"] == 0
                for k in walked)
