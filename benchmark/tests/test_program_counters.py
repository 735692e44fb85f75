"""The per-layer metrics that read the program's own counters of the time
outside the flush span (``dag.infer``, ``read``, ``observe.tail``), at a
toy size on the CPU.  Run by hand, with ``test_cells.py``:

    python -m pytest benchmark/tests/test_program_counters.py -q
"""

import json
import os

import pytest
from test_cells import ROOT, run_cell, toy_checkout

COUNTED = ("dag_infer_ms", "dag_infer_misses", "read_back_ms",
           "observer_tail_ms")


def rehearse(tmp_path, cell):
    p, last = run_cell(toy_checkout(tmp_path), cell, trace=1)
    assert p.returncode == 0, p.stderr[-2000:]
    assert last["correct"] and last["failed"] == 0, p.stdout[-2000:]
    for name in COUNTED:
        assert "rehearsal." + name in last["metrics"], sorted(last["metrics"])
    return {k[len("rehearsal."):]: v["value"]
            for k, v in last["metrics"].items()}


def test_star2_counts_an_inference_miss_per_iteration(tmp_path):
    with open(os.path.join(ROOT, "benchmark/traffic/iterate10.json")) as f:
        iterations = sum(op.get("count", 0)
                         for op in json.load(f)["solve"])
    m = rehearse(tmp_path, "star2")
    assert m["dag_infer_misses"] >= iterations
    assert m["dag_infer_ms"] > 0
    assert m["read_back_ms"] > 0  # the norm
    assert m["observer_tail_ms"] > 0


def test_peek_counts_its_reads(tmp_path):
    m = rehearse(tmp_path, "chain-1e9-peek")
    assert m["read_back_ms"] > 0
    assert m["observer_tail_ms"] > 0


@pytest.mark.parametrize("metric", COUNTED)
def test_a_program_without_the_counter_reads_nothing(metric):
    """The parent commit has no such counter: the reader returns None and
    the line leaves the metric out."""
    import importlib.util
    import types

    spec = importlib.util.spec_from_file_location(
        metric, os.path.join(ROOT, "benchmark/layer_metrics", metric + ".py"))
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    rt = types.SimpleNamespace(
        diagnostics=types.SimpleNamespace(counters=lambda: {"fuser.x": 1}))
    ctx = types.SimpleNamespace(program=types.SimpleNamespace(rt=rt),
                                solves=[], stats=None)
    assert reader.read(ctx) is None
