"""The trace reduction: interval arithmetic by hand, then two traces
recorded on the chip in PR 22 (TPU v5 lite) against answers worked out
by a different method (an endpoint sweep that counts depth).

    python -m pytest benchmark/tests/test_tracered.py -q
"""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark import tracered  # noqa: E402

FIX = os.path.join(ROOT, "benchmark", "fixtures")


def covered(intervals, lo, hi, also_not=()):
    """Length of [lo, hi) covered by ``intervals`` and by none of
    ``also_not``, by sweeping endpoints and counting depth."""
    points = []
    for a, b in intervals:
        points += [(max(a, lo), 0, 1), (min(max(b, lo), hi), 0, -1)]
    for a, b in also_not:
        points += [(max(a, lo), 1, 1), (min(max(b, lo), hi), 1, -1)]
    depth, total, last = [0, 0], 0.0, lo
    for x, which, step in sorted(points):
        x = min(max(x, lo), hi)
        if depth[0] > 0 and depth[1] == 0:
            total += x - last
        depth[which] += step
        last = x
    return total


def test_union_complement_subtract_by_hand():
    u = tracered.union([(5, 7), (0, 2), (1, 3), (7, 8), (9, 9)])
    assert u == [(0, 3), (5, 8)]
    assert tracered.length(u) == 6
    assert tracered.complement(u, -1, 10) == [(-1, 0), (3, 5), (8, 10)]
    assert tracered.clip(u, 2, 6) == [(2, 3), (5, 6)]


def test_op_label_adds_up_numbered_copies():
    kind, label = tracered.op_label(
        '%run.17 = f32[15000,15000]{1,0:T(8,128)} custom-call('
        'f32[15008,15232]{1,0:T(8,128)} %pad.8), custom_call_target="x"')
    assert (kind, label) == ("custom-call", "run [custom-call]")
    kind, label = tracered.op_label(
        "%abs_reduce_fusion = (f32[]{:T(128)}, f32[8,8]{1,0:T(8,128)}) "
        "fusion(f32[8,8]{1,0:T(8,128)} %run.19), kind=kLoop")
    assert (kind, label) == ("fusion", "abs_reduce_fusion [fusion]")
    assert tracered.op_label("%collective-permute-done.4 = f32[2]{0} "
                             "collective-permute-done((f32[2]) %x)")[0] \
        == "collective-permute-done"


def test_collectives_own_time_classes_and_gaps_by_hand():
    # one core: start issued 10..12, compute 12..20, done waits 20..26; a
    # synchronous all-reduce 30..34; a kernel 40..50
    ops = {"/device:TPU:0": [
        (10, 12, "%collective-permute-start.1 = f32[2]{0} "
                 "collective-permute-start(f32[2]{0} %a)"),
        (12, 20, "%fusion.3 = f32[8]{0} fusion(f32[8]{0} %a), kind=kLoop"),
        (20, 26, "%collective-permute-done.1 = f32[2]{0} "
                 "collective-permute-done((f32[2]{0}) %s)"),
        (30, 34, "%all-reduce.2 = f32[]{} all-reduce(f32[]{} %x)"),
        (40, 50, "%run.7 = f32[8]{0} custom-call(f32[8]{0} %p)"),
    ]}
    frames = [(0, 60, "bench_solve"), (0, 60, "$x.py:1 solve"),
              (0, 9, "$x.py:2 build"), (26, 29, "$x.py:3 wait")]
    r = tracered.reduce_events(ops, frames, "bench_solve",
                               {"stencil": r"^custom-call", "fusion": "."})
    ns = 1e-9
    assert r["solves"] == 1 and r["devices"] == 1
    assert r["window_s"] == pytest.approx(60 * ns)
    assert r["busy_s"] == pytest.approx((16 + 4 + 10) * ns)
    assert r["compute_s"] == pytest.approx(18 * ns)
    assert r["collective_s"] == pytest.approx((2 + 6 + 4) * ns)
    assert r["class_s"] == {"stencil": pytest.approx(10 * ns),
                            "fusion": pytest.approx(8 * ns)}
    gaps = dict(r["idle_gaps"])
    # 0..10 is mostly `build` and 26..30 mostly `wait`: each is the
    # deepest frame open for half its gap; 34..40 and 50..60 are solve's
    assert gaps == {"$x.py:2 build": pytest.approx(10 * ns),
                    "$x.py:3 wait": pytest.approx(4 * ns),
                    "$x.py:1 solve": pytest.approx(16 * ns)}


def test_a_gap_is_split_among_the_children_of_its_frame():
    frames = tracered.Frames([(0, 100, "solve"), (10, 30, "a"), (12, 20, "b"),
                              (30, 50, "a"), (60, 70, "c"), (95, 120, "d")])
    assert frames.split((0, 100)) == {"solve > a": 40.0, "solve > c": 10.0,
                                      "solve": 50.0}
    assert frames.split((200, 300)) == {"(no host frame)": 100}


def test_nothing_to_read_returns_none():
    assert tracered.reduce_events({}, [(0, 1, "bench_solve")],
                                  "bench_solve", {}) is None
    assert tracered.reduce_events({"/device:TPU:0": [(0, 1, "%a = f32[] "
                                  "fusion()")]}, [], "bench_solve", {}) is None


@pytest.mark.parametrize("name", ["prk_n2000_x4", "chain_1e9_x1"])
def test_recorded_trace_against_the_sweep(name):
    with open(os.path.join(FIX, name + ".expected.json")) as f:
        want = json.load(f)
    path = os.path.join(FIX, name + ".xplane.pb")
    classes = want["classes"]
    device_ops, frames = tracered.read_file(path, "bench_solve")
    got = tracered.reduce_events(device_ops, frames, "bench_solve", classes)
    for key in ("solves", "devices"):
        assert got[key] == want[key]
    for key in ("window_s", "busy_s", "compute_s", "collective_s"):
        assert got[key] == pytest.approx(want[key], rel=1e-9, abs=1e-15)
    assert got["class_s"] == pytest.approx(want["class_s"], rel=1e-9)
    assert got["device_ops"][0][0] == want["top_device_op"]
    assert got["idle_gaps"][0][0] == want["top_idle_gap"]
    assert sum(s for _, s in got["idle_gaps"]) <= got["window_s"] + 1e-12

    # the same totals by the endpoint sweep, from the raw events
    marks = [(a, b) for a, b, n in frames if n == "bench_solve"]
    lo, hi = min(a for a, _ in marks), max(b for _, b in marks)
    busy = compute = collective = 0.0
    for ops in device_ops.values():
        ops = [(a, b, tracered.op_label(h)[0]) for a, b, h in ops]
        every = [(a, b) for a, b, _ in ops]
        coll = [(a, b) for a, b, kind in ops
                if tracered.COLLECTIVE.match(kind)]
        busy += covered(every, lo, hi)
        # the core's line is sequential: what is busy and not a
        # collective is compute, and the other way round
        compute += covered(every, lo, hi, also_not=coll)
        collective += covered(coll, lo, hi)
    n = len(device_ops)
    assert got["busy_s"] == pytest.approx(busy / n / 1e9, rel=1e-9)
    assert got["compute_s"] == pytest.approx(compute / n / 1e9, rel=1e-9)
    assert got["collective_s"] == pytest.approx(
        collective / n / 1e9, rel=1e-9, abs=1e-15)
    assert 0 < got["busy_s"] <= got["window_s"]
