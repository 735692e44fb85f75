"""What ``benchmark/`` reads from the program, pinned in tier-1.

The benchmark knows a flush by its span (``stages``, ``calls``, ``cache``,
``mem_peak_est``, ``live_groups``, ``kernels``, ``wall_s``, ``degraded``)
and by three registry counters (``observe.tail``, ``read``,
``stencil.path.*``).  ``benchmark/tests/`` checks that on whole cells,
outside the tier-1 command; here the programs of the five cells run at toy
sizes on the CPU mesh, two solves each, and every test reads the record of
those two solves:

``chain``              the chain and its sum, nothing resident
``peek-*``             peek's three reads of a resident D, two offsets each
``star-1dev``          ten PRK iterations and the norm on one device
                       (``pallas_padded``, interpreted)
``star-2x2``           the same on the 2x2 mesh (``sharded``)
``star-*-grouped``     the same over a lowered watermark, which the fused
                       rung answers with live groups (the estimates are
                       supplied as ``tests/test_live_groups.py`` does)

No test here asserts a duration.
"""

import math
import os
import time

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

import ramba_tpu as rt
from benchmark import record, stats
from benchmark import run as harness
from benchmark.programs import chain, prk_star
from ramba_tpu import diagnostics
from ramba_tpu.core import fuser
from ramba_tpu.observe import attrib, events
from ramba_tpu.ops import stencil_pallas
from ramba_tpu.parallel import mesh as mesh_mod
from ramba_tpu.resilience import faults, memory

pytestmark = pytest.mark.skipif(
    jax.process_count() > 1,
    reason="installs local meshes; admission is rank-local here")

N_CHAIN = 1 << 14
N_STAR, R, T = 100, 2, 10  # 100 is no multiple of 128: the padded kernel
UNIT = N_STAR * N_STAR * 4
WHOLE, FIXED, SHRINKS = 22, 4, 18  # in arrays: two groups read 13

#: case -> (the cell whose program it is, program module, traffic, devices)
CASES = {
    "chain": ("chain-1e9", chain, {
        "resident": False, "solve": [{"op": "chain_sum"}]}, None),
    "peek-elem": ("chain-1e9-peek", chain, {
        "resident": True, "offsets_seed": 22,
        "solve": [{"op": "elem", "count": 2}]}, None),
    "peek-slice": ("chain-1e9-peek", chain, {
        "resident": True, "offsets_seed": 22,
        "solve": [{"op": "slice", "count": 2, "width": 1024}]}, None),
    "peek-slice-sum": ("chain-1e9-peek", chain, {
        "resident": True, "offsets_seed": 22,
        "solve": [{"op": "slice_sum", "count": 2, "width": 4096}]}, None),
    "star-1dev": ("star2", prk_star, None, 1),
    "star-2x2": ("star2-x4", prk_star, None, 4),
    "star-1dev-grouped": ("star2", prk_star, None, 1),
    "star-2x2-grouped": ("star2-30000-x4", prk_star, None, 4),
}
STAR_TRAFFIC = {"solve": [{"op": "iterate", "count": T}, {"op": "norm"}]}


#: what a flush span of the default configuration holds, given a budget
SPAN_KEYS = {
    "type", "seq", "ts", "mono", "label", "fingerprint", "instrs",
    "n_leaves", "n_roots", "leaf_bytes", "out_bytes", "donated",
    "rewrite_fires", "linearize_s", "stages", "calls", "segments",
    "compile_s", "execute_s", "cache", "wall_s", "unattributed_s",
    "live_groups", "mem_live_bytes", "mem_peak_est"}
#: ... on the flush that traced a kernel; where admission grouped it
SPAN_KEYS_SOME = {"kernels", "mem_peak_est_ungrouped"}


def fake_estimate(program, avals):
    if not program.live_cuts:
        return WHOLE * UNIT
    return FIXED * UNIT + SHRINKS * UNIT // program.live_groups


class Run:
    """Two solves of one case, each recorded as the harness records a
    solve (``benchmark/run.py`` ``solve()``): the events it emitted, the
    counters it moved, why it failed."""

    def __init__(self, case, prog, flushes, reads):
        self.prog, self.cell = prog, CASES[case][0]
        self.flushes, self.reads = flushes, reads
        self.grouped = case.endswith("-grouped")
        self.paths = tuple(sorted(prog.expected_paths(CASES[case][3])))
        self.solves = [self._solve(), self._solve()]

    def _solve(self):
        tap = []
        c0 = diagnostics.counters()
        events.add_tap(tap.append)
        t0 = time.perf_counter()
        try:
            out = self.prog.solve()
        finally:
            events.remove_tap(tap.append)
        ms = 1e3 * (time.perf_counter() - t0)
        counters = record.counter_delta(c0, diagnostics.counters())
        error = (self.prog.check(out)
                 or record.unclean(tap, counters, interpret_ok=True))
        return harness.Solve(ms, tap, counters, error)

    @property
    def spans(self):
        return [f for s in self.solves for f in s.flushes]


@pytest.fixture(scope="module", params=list(CASES))
def run(request):
    case = request.param
    _cell, module, traffic, ndev = CASES[case]
    if ndev and len(jax.devices()) < ndev:
        pytest.skip(f"needs {ndev} devices")
    mp = pytest.MonkeyPatch()
    fuser.flush()
    faults.configure(None)
    memory._est_memo.clear()
    old_mesh = mesh_mod.get_mesh()
    for name in ("RAMBA_HBM_BUDGET", "RAMBA_HBM_WATERMARK",
                 "RAMBA_HBM_ESTIMATE", "RAMBA_CHUNK_BYTES"):
        mp.delenv(name, raising=False)
    x64 = bool(jax.config.jax_enable_x64)
    jax.config.update("jax_enable_x64", False)  # the chip's regime
    try:
        if module is chain:
            prog = chain.Program(rt, {"n": N_CHAIN, "dtype": "float32"},
                                 traffic, np.random.default_rng(0),
                                 len(jax.devices()))
            reads = sum(op.get("count", 1) for op in traffic["solve"])
            flushes = reads
        else:
            mp.setattr(stencil_pallas, "_INTERPRET", True)
            devs = np.array(jax.devices()[:ndev])
            mesh_mod.set_mesh(
                Mesh(devs.reshape((2, 2)), ("d0", "d1")) if ndev == 4
                else Mesh(devs, ("d0",)))
            prog = prk_star.Program(
                rt, {"n": N_STAR, "radius": R, "dtype": "float32",
                     "assumed": {"norm_rtol": 1e-4}},
                STAR_TRAFFIC, np.random.default_rng(0), ndev)
            flushes = reads = 1
        prog.setup()
        # a budget is known on the chip, so admission stamps its estimate
        # on every span there; here one is supplied, roomy unless the case
        # is the one admission has to group
        if case.endswith("-grouped"):
            mp.setattr(memory, "_xla_estimate", fake_estimate)
            other = memory.ledger.live_bytes - 2 * UNIT
            mp.setenv("RAMBA_HBM_WATERMARK", str(other + 14 * UNIT))
            mp.setenv("RAMBA_HBM_BUDGET", str(2 * (other + 14 * UNIT)))
        else:
            mp.setenv("RAMBA_HBM_BUDGET", str(1 << 40))
            # the CPU's memory_analysis() reports nothing: skip the
            # second compile that asks it
            mp.setenv("RAMBA_HBM_ESTIMATE", "analytic")
        ran = Run(case, prog, flushes, reads)
    finally:
        fuser.flush()
        jax.config.update("jax_enable_x64", x64)
        mesh_mod.set_mesh(old_mesh)
        memory._est_memo.clear()
        mp.undo()
    yield ran


def test_both_solves_are_clean_by_the_harness_own_rules(run):
    """The closed form holds, nothing left the fused rung, no degrade,
    fault or admission event, and the stencil took the cell's path."""
    for s in run.solves:
        assert s.error is None, s.error
        assert record.kernel_paths(s.counters) == run.paths
        assert len(s.flushes) == run.flushes
        assert all("degraded" not in f for f in s.flushes)


def test_stage_keys_are_the_ledgers_and_hold_what_the_metrics_read(run):
    for f in run.spans:
        st = f["stages"]
        assert set(st) <= set(attrib.STAGES), st
        for k in ("trace", "prepare", "admit", "write_back",
                  "device_execute"):
            assert k in st, (k, st)
        assert "compile" in st or "dispatch" in st, st
        assert all(isinstance(v, float) and v >= 0 for v in st.values())


def test_second_solve_hits_the_cache_and_has_no_compile_stage(run):
    first, second = run.solves
    assert {f["cache"] for f in first.flushes} == {"miss"}
    for f in second.flushes:
        assert f["cache"] == "hit"
        assert "compile" not in f["stages"] and "dispatch" in f["stages"]
        assert [c["cache"] for c in f["calls"]] == ["hit"]


def test_admission_keys_have_the_types_the_layer_metrics_expect(run):
    for f in run.spans:
        assert isinstance(f["mem_peak_est"], int) and f["mem_peak_est"] > 0
        assert isinstance(f["live_groups"], int)
        if run.grouped:
            assert f["live_groups"] == 2
            assert f["mem_peak_est"] == 13 * UNIT
            assert f["mem_peak_est_ungrouped"] == WHOLE * UNIT
        else:
            assert f["live_groups"] == 1
            assert "mem_peak_est_ungrouped" not in f


def test_counters_move_with_the_flushes_reads_and_kernel_calls(run):
    first, second = run.solves
    for s in run.solves:
        assert s.counters["observe.tail.n"] == len(s.flushes) == run.flushes
        assert s.counters["read.n"] == run.reads
        assert s.counters["observe.tail.ns"] > 0 and s.counters["read.ns"] > 0
    # a flush that traced nothing counts each kernel call of its program
    # again; the one that traced also counted node inference's trace
    for path in run.paths:
        assert second.counters["stencil.path." + path] == T
        assert first.counters["stencil.path." + path] >= T
    if not run.paths:
        assert not [k for s in run.solves for k in s.counters
                    if k.startswith("stencil.")]
    if run.grouped:
        assert all(s.counters["memory.live_grouped"] == 1
                   for s in run.solves)


def test_the_span_is_the_one_record_of_the_flush(run):
    for f in run.spans:
        assert isinstance(f["unattributed_s"], float)
        assert f["unattributed_s"] >= 0
        assert isinstance(f["wall_s"], float)
        # every key of the default configuration, and no other
        assert SPAN_KEYS <= set(f) <= SPAN_KEYS | SPAN_KEYS_SOME, sorted(f)
        assert f["segments"] == 0 and len(f["calls"]) == 1
        assert set(f["calls"][0]) >= {"label", "cache", "seconds"}
    # the kernels' notes ride the span of the flush that traced them
    traced = [k for f in run.solves[0].flushes for k in f.get("kernels", ())]
    assert {k["path"] for k in traced} == set(run.paths)
    assert all(k["interpret"] for k in traced
               if k["path"].startswith("pallas"))
    assert not [f for f in run.solves[1].flushes if "kernels" in f]


def test_every_span_and_counter_metric_of_the_cell_reads_a_number(run):
    """Each per-layer reader of the cell whose source is the program's own
    span or counters returns a number over these solves, none ``None``."""
    bench = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    ctx = harness.Context(program=run.prog, solves=run.solves, traced=[],
                          warmup=run.solves[:1], stats=stats)
    read = {}
    for m in bench["per_layer"]:
        if (m["source"] != "program_span"
                or run.cell not in m.get("workloads", [run.cell])):
            continue
        value = harness._load_module("layer_metrics", m["name"]).read(ctx)
        assert value is not None, m["name"]
        assert math.isfinite(float(value)), (m["name"], value)
        read[m["name"]] = value
    assert read["flushes_per_solve"] == run.flushes
    assert read["compiles_in_window"] == run.flushes  # the first solve's
    if "live_groups" in read:
        assert read["live_groups"] == (2 if run.grouped else 1)
