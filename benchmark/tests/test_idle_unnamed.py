"""``idle_unnamed_pct`` by hand: the share of the idle time inside solves
that none of the program's ``ramba.*`` annotations carries, read from a
reduction of hand-made events.

    python -m pytest benchmark/tests/test_idle_unnamed.py -q
"""

import importlib.util
import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark import tracered  # noqa: E402


def reader():
    spec = importlib.util.spec_from_file_location(
        "idle_unnamed_pct",
        os.path.join(ROOT, "benchmark/layer_metrics/idle_unnamed_pct.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reduced(frames):
    """Two solves, [0, 1000) and [1100, 2000), each with a device op at
    its edge towards the other, so the gaps are [0, 400) and [500, 990)
    in the first, [1000, 1100) between them, [1110, 1300) and
    [1800, 2000) in the second.  (A gap that runs from one solve into the
    next is ONE gap, given to one frame: what a real trace holds.)"""
    hlo = "%f = f32[] fusion(f32[] %p)"
    ops = {"/device:TPU:0": [(400, 500, hlo), (990, 1000, hlo),
                             (1100, 1110, hlo), (1300, 1800, hlo)]}
    marks = [(0, 1000, "bench_solve"), (1100, 2000, "bench_solve")]
    return tracered.reduce_events(ops, marks + frames, "bench_solve",
                                  {"fusion": "."})


def test_a_gap_under_each_name_and_one_under_none():
    trace = reduced([
        (0, 390, "ramba.dag.build"),         # the gap [0, 400)
        (100, 150, "ramba.host.gc"),         # ... and its child
        (500, 1000, "ramba.flush.run"),      # the gap [500, 990)
        (1105, 1290, "PjitFunction(run)"),   # [1110, 1300): jax's frame
    ])                                       # [1800, 2000): no frame
    gaps = dict(trace["idle_gaps"])
    assert gaps["ramba.dag.build"] == pytest.approx(350e-9)
    assert gaps["ramba.dag.build > ramba.host.gc"] == pytest.approx(50e-9)
    assert gaps["ramba.flush.run"] == pytest.approx(490e-9)
    assert gaps["(between solves)"] == pytest.approx(100e-9)
    assert gaps["PjitFunction(run)"] == pytest.approx(190e-9)
    assert gaps["(no host frame)"] == pytest.approx(200e-9)
    # idle inside solves 1280 ns, of which 390 carry no name of ours
    ctx = types.SimpleNamespace(trace=trace)
    assert reader().read(ctx) == pytest.approx(100 * 390 / 1280)


def test_a_parent_with_no_build_annotation_reads_the_build_unnamed():
    trace = reduced([(500, 1000, "ramba.flush.run")])
    ctx = types.SimpleNamespace(trace=trace)
    assert reader().read(ctx) == pytest.approx(100 * 790 / 1280)


def test_what_the_list_of_ten_cut_off_is_unnamed():
    trace = reduced([(0, 390, "ramba.dag.build"),
                     (500, 1000, "ramba.flush.run")])
    kept = [g for g in trace["idle_gaps"] if g[0] != "ramba.flush.run"]
    ctx = types.SimpleNamespace(trace=dict(trace, idle_gaps=kept))
    assert reader().read(ctx) == pytest.approx(100 * (1280 - 400) / 1280)


def test_no_trace_reads_nothing_and_no_idle_reads_zero():
    assert reader().read(types.SimpleNamespace(trace=None)) is None
    busy = {"window_s": 1.0, "busy_s": 1.0, "idle_gaps": []}
    assert reader().read(types.SimpleNamespace(trace=busy)) == 0.0
