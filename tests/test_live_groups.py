"""The fused rung bounds its own live set when admission says it must.

A program whose estimate is over the watermark is tried again as the SAME
one jitted program, reordered to keep few values live and cut into live
groups by the byte segmenter, the values live at each cut held behind
``jax.lax.optimization_barrier`` (``fuser._live_grouped``,
``memory._fit_live_groups``).  The program driven here is the PRK loop of
``benchmark/programs/prk_star.py`` (ten ``B += stencil(A); A += 1`` and
the norm, one flush of 32 instructions) at a small order, on one device
and on a 2x2 mesh.

What XLA holds at once is not visible on this backend: the analytic
estimate (``analyze/rules.py`` ``estimate_peak_bytes``) walks the
instructions in program order and cannot see a fusion that makes ten
outputs together, and the CPU's ``memory_analysis()`` reports nothing.  So
every test here SUPPLIES the two forms' estimates by monkeypatching
``memory._xla_estimate``: the program as linearized reads ``WHOLE`` array
sizes, a program in g live groups ``FIXED + SHRINKS / g``.  What is tested
is what admission and the fuser do with those readings.
"""

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

import ramba_tpu as rt
from benchmark.programs import prk_star
from ramba_tpu import diagnostics
from ramba_tpu.core import fuser
from ramba_tpu.observe import events
from ramba_tpu.parallel import mesh as mesh_mod
from ramba_tpu.resilience import faults, memory

N, R, T = 128, 2, 10
UNIT = N * N * 4  # one array
WHOLE, FIXED, SHRINKS = 22, 4, 18  # in arrays: g=2 reads 13, g=3 10, g=4 8.5

CFG = {"n": N, "radius": R, "dtype": "float32",
       "assumed": {"norm_rtol": 1e-4}}
TRAFFIC = {"solve": [{"op": "iterate", "count": T}, {"op": "norm"}]}

_MULTIPROC = jax.process_count() > 1
pytestmark = pytest.mark.skipif(
    _MULTIPROC, reason="installs local meshes; admission is rank-local here")


def fake_estimate(program, avals):
    if not program.live_cuts:
        return WHOLE * UNIT
    return FIXED * UNIT + SHRINKS * UNIT // (program.live_groups)


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    for name in ("RAMBA_HBM_BUDGET", "RAMBA_HBM_WATERMARK",
                 "RAMBA_HBM_ESTIMATE", "RAMBA_CHUNK_BYTES"):
        monkeypatch.delenv(name, raising=False)
    monkeypatch.setattr(memory, "_xla_estimate", fake_estimate)
    faults.configure(None)
    memory._est_memo.clear()
    yield
    faults.reset()
    memory._est_memo.clear()


@pytest.fixture(params=[1, 4], ids=["1dev", "2x2"])
def prk(request):
    """The PRK program set up on a mesh of one device or of 2x2."""
    ndev = request.param
    if len(jax.devices()) < ndev:
        pytest.skip(f"needs {ndev} devices")
    fuser.flush()
    old = mesh_mod.get_mesh()
    devs = np.array(jax.devices()[:ndev])
    mesh_mod.set_mesh(Mesh(devs.reshape((2, 2)), ("d0", "d1")) if ndev == 4
                      else Mesh(devs, ("d0",)))
    prog = prk_star.Program(rt, CFG, TRAFFIC, np.random.default_rng(0), ndev)
    prog.setup()
    try:
        yield prog
    finally:
        del prog.A, prog.B
        mesh_mod.set_mesh(old)


def set_watermark(monkeypatch, arrays):
    """A watermark with room for ``arrays`` array sizes beside what other
    tests left resident (A and B are the flush's own arguments)."""
    other = memory.ledger.live_bytes - 2 * UNIT
    monkeypatch.setenv("RAMBA_HBM_WATERMARK", str(other + arrays * UNIT))
    monkeypatch.setenv("RAMBA_HBM_BUDGET", str(2 * (other + arrays * UNIT)))


class Watch:
    """One solve under an event tap and a spy on what the flush ran."""

    def __init__(self, prog, monkeypatch):
        self.events, self.ran = [], []
        real = fuser._execute_compiled

        def spy(fn, program, leaf_vals, *a, **kw):
            self.ran.append((program, memory._leaf_avals(leaf_vals)))
            return real(fn, program, leaf_vals, *a, **kw)

        c0 = diagnostics.counters()
        events.add_tap(self.events.append)
        try:
            with monkeypatch.context() as m:
                m.setattr(fuser, "_execute_compiled", spy)
                self.out = prog.solve()
        finally:
            events.remove_tap(self.events.append)
        c1 = diagnostics.counters()
        self.moved = {k: v - c0.get(k, 0) for k, v in c1.items()
                      if v != c0.get(k, 0)}
        self.spans = [e for e in self.events if e.get("type") == "flush"]
        self.memory = [e for e in self.events if e.get("type") == "memory"]

    def barriers(self):
        """optimization_barrier equations in the callable of each program
        the flush ran, traced afresh on the leaves' avals."""
        return [str(jax.make_jaxpr(fuser._build_callable(p))(*avals))
                .count("optimization_barrier") for p, avals in self.ran]


def check_against_numpy(prog, iterations):
    """B and A equal ``iterations`` of star_np, and the norm is 2T."""
    i = np.arange(N, dtype=np.float32)
    refA = i[:, None] + i[None, :]
    refB = np.zeros_like(refA)
    for _ in range(iterations):
        refB += prk_star.star_np(refA, R)
        refA += np.float32(1.0)
    np.testing.assert_allclose(np.asarray(prog.B), refB,
                               atol=1e-6 * 2 * iterations)
    np.testing.assert_array_equal(np.asarray(prog.A), refA)


def test_over_the_watermark_runs_fused_in_live_groups(prk, monkeypatch):
    set_watermark(monkeypatch, 14)  # whole 22 is over, two groups read 13
    monkeypatch.setattr(fuser, "DONATE_MIN_BYTES", UNIT)  # toy arrays count
    a0, b0 = prk.A._value(), prk.B._value()
    w = Watch(prk, monkeypatch)
    assert prk.check(w.out) is None, w.out  # the norm is 2T
    assert len(w.spans) == 1
    span = w.spans[0]
    assert "degraded" not in span and "admission" not in span
    assert span["live_groups"] == 2
    assert span["mem_peak_est"] == 13 * UNIT
    assert span["mem_peak_est_ungrouped"] == WHOLE * UNIT
    # one program ran, as ONE call, with one barrier in it
    assert len(w.ran) == 1 and span["segments"] == 0
    assert w.barriers() == [1]
    # admission said yes to the program that ran, and nothing else
    assert [(e["action"], e["ok"], e["live_groups"]) for e in w.memory] \
        == [("admit", True, 2)]
    assert not [e for e in w.events if e.get("type") == "degrade"]
    assert "memory.admission_rejects" not in w.moved
    assert "memory.evictions" not in w.moved
    assert w.moved["memory.live_grouped"] == 1
    # donation as on the fused rung: A and B, and their buffers are gone
    assert span["donated"] == 2
    assert fuser._cache_key(w.ran[0][0], (0, 11)) in fuser._compile_cache
    assert a0.is_deleted() and b0.is_deleted()
    check_against_numpy(prk, T)


@pytest.mark.parametrize("budget", ["roomy", "none"])
def test_an_admitted_program_is_the_callable_of_today(prk, monkeypatch,
                                                      budget):
    if budget == "roomy":
        set_watermark(monkeypatch, 100)
    w = Watch(prk, monkeypatch)
    assert prk.check(w.out) is None, w.out
    span = w.spans[0]
    assert span["live_groups"] == 1
    assert "mem_peak_est_ungrouped" not in span and "degraded" not in span
    assert (span.get("mem_peak_est") == WHOLE * UNIT) == (budget == "roomy")
    assert len(w.ran) == 1 and not w.ran[0][0].live_cuts
    assert w.barriers() == [0]
    assert "memory.live_grouped" not in w.moved
    assert all(e["action"] == "admit" and e["ok"] and e["live_groups"] == 1
               for e in w.memory)
    check_against_numpy(prk, T)


def test_still_over_when_grouped_evicts_and_routes_chunked(prk, monkeypatch):
    set_watermark(monkeypatch, 3)  # under what no grouping shrinks (4)
    w = Watch(prk, monkeypatch)
    assert prk.check(w.out) is None, w.out
    span = w.spans[0]
    assert span.get("degraded") == "chunked" and span["admission"] == "chunked"
    assert span["live_groups"] == 1 and span["segments"] >= 2
    assert span["mem_peak_est"] == WHOLE * UNIT
    assert w.moved["memory.admission_rejects"] == 1
    assert "memory.live_grouped" not in w.moved
    actions = [e["action"] for e in w.memory]
    assert actions[0] == "admit" and not w.memory[0]["ok"]
    assert {"watermark", "reject"} <= set(actions)
    assert w.barriers() == [0] * len(w.ran)
    check_against_numpy(prk, T)


def test_the_grouping_is_cached_and_keys_the_executable(prk, monkeypatch):
    set_watermark(monkeypatch, 14)
    lowered = []
    monkeypatch.setattr(memory, "_xla_estimate", lambda p, avals: (
        lowered.append(p.live_groups), fake_estimate(p, avals))[1])
    first = Watch(prk, monkeypatch)
    assert first.spans[0]["cache"] == "miss"
    assert lowered == [1, 2]  # the program as it stands, then in two groups
    compiles = fuser.stats["compiles"]
    second = Watch(prk, monkeypatch)
    assert second.spans[0]["cache"] == "hit"
    assert second.spans[0]["live_groups"] == 2
    assert second.ran[0][0].key == first.ran[0][0].key
    assert fuser.stats["compiles"] == compiles and lowered == [1, 2]
    assert second.moved["memory.live_grouped"] == 1
    # less room: two groups (13) no longer fit under 11; the two readings
    # say three (4 + 18/3 = 10), and that is another executable
    set_watermark(monkeypatch, 11)
    third = Watch(prk, monkeypatch)
    assert third.spans[0]["live_groups"] == 3
    assert third.spans[0]["cache"] == "miss"
    assert third.spans[0]["mem_peak_est"] == 10 * UNIT
    assert lowered == [1, 2, 3]
    assert third.barriers() == [2]
    assert third.ran[0][0].key != first.ran[0][0].key
    assert fuser.stats["compiles"] == compiles + 1
    assert prk.check(third.out) is None, third.out
    check_against_numpy(prk, 3 * T)


# -- the reorder and the cuts, on the captured program -----------------------


@pytest.fixture
def captured(prk, monkeypatch):
    """The PRK flush's program and leaf avals, as admission sees them."""
    w = Watch(prk, monkeypatch)
    (program, avals), = w.ran
    assert len(program.instrs) == 32 and not program.live_cuts
    return program, avals


def peak_live(program, avals):
    """Array sizes live at once when the instructions run in order."""
    from ramba_tpu.analyze import rules

    return rules.estimate_peak_bytes(program, avals, ()) / UNIT


def test_live_order_interleaves_what_linearize_lays_down_whole(captured):
    program, avals = captured
    grouped = fuser._live_grouped(program, avals, 2)
    # same leaves, same work, outputs in the same order
    assert grouped.n_leaves == program.n_leaves
    assert grouped.leaf_kinds == program.leaf_kinds
    assert sorted(i[0] for i in grouped.instrs) \
        == sorted(i[0] for i in program.instrs)
    # _linearize lays the ten A += 1 down before the first stencil, so
    # all ten are live together; depth-first from the norm, the heavier
    # operand first, holds an iteration or two
    assert peak_live(grouped, avals) < peak_live(program, avals) / 2
    # a valid order: every instruction reads slots made before it
    for k, (_op, _st, args) in enumerate(grouped.instrs):
        assert all(s < grouped.n_leaves + k for s in args)


@pytest.mark.parametrize("groups", [2, 3, 5, 40])
def test_cuts_are_even_and_few(captured, groups):
    program, avals = captured
    grouped = fuser._live_grouped(program, avals, groups)
    cuts = grouped.live_cuts
    assert list(cuts) == sorted(set(cuts)) and 0 < cuts[0] and cuts[-1] < 32
    sizes = np.diff([0, *cuts, 32])
    # as many groups as asked for (one more where equal sizes tie), or
    # every cut there is; of even bytes, so of about as many instructions
    assert groups <= len(sizes) <= groups + 1 or len(sizes) == 32 < groups
    if groups <= 5:
        assert sizes.max() - sizes.min() <= 4
    # the two forms never share a cache entry; the form as linearized
    # keeps the key it always had
    assert grouped.key != program.key
    assert program.key == (program.instrs, program.n_leaves,
                           program.leaf_kinds, program.out_slots)
    assert fuser._cache_key(grouped, ()) != fuser._cache_key(program, ())


def test_a_program_of_one_instruction_cannot_be_grouped(prk):
    x = prk.A + 1.0
    program, leaves, _ = fuser._prepare_program([x._expr])
    avals = memory._leaf_avals([leaf.value for leaf in leaves])
    assert fuser._live_grouped(program, avals, 2) is None
    assert memory._fit_live_groups(program, [leaf.value for leaf in leaves],
                                   (), 10 * UNIT, 5 * UNIT) is None
    del x
    fuser.flush()


@pytest.mark.parametrize("room,first,fits,lowerings", [
    (14, 2, True, 1),   # the first count is the overflow's share: 22/14
    (11, 2, True, 2),   # 13 is over; the two readings say 18/(11-4) -> 3
    (7, 4, True, 2),    # 22/7 -> 4 reads 8.5; 18/(7-4) -> 6 reads 7
    (4, 6, False, 1),   # 4 never shrinks: no count can fit, one lowering
])
def test_fit_finds_the_fewest_groups_in_few_lowerings(
        prk, monkeypatch, room, first, fits, lowerings):
    tried = []
    monkeypatch.setattr(memory, "_xla_estimate", lambda p, avals: (
        tried.append(p.live_groups), fake_estimate(p, avals))[1])
    for _ in range(T):
        prk.B += rt.sstencil(prk.star, prk.A)
        prk.A += 1.0
    program, leaves, _ = fuser._prepare_program(
        [a._expr for a in fuser._pending_roots()])
    leaf_vals = [leaf.value for leaf in leaves]
    fit = memory._fit_live_groups(program, leaf_vals, (), WHOLE * UNIT,
                                  room * UNIT)
    # equal array sizes can tie a count out: the segmenter then gives one more
    assert tried[0] in (first, first + 1) and len(tried) == lowerings
    assert tried == sorted(set(tried))
    if fits:
        grouped, est = fit
        assert grouped.live_groups == tried[-1]
        assert est == fake_estimate(grouped, None) <= room * UNIT
        assert all(FIXED + SHRINKS / g > room for g in tried[:-1])
    else:
        assert fit is None
    # memoized: asking again lowers nothing
    assert memory._fit_live_groups(program, leaf_vals, (), WHOLE * UNIT,
                                   room * UNIT) is fit
    assert len(tried) == lowerings
    rt.sync()
