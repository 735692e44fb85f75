"""The per-layer metrics that read the lazy-DAG layer's own counters
(``dag.node``, ``dag.index``, ``dag.build``) and the collector's
(``host.gc``), at a toy size on the CPU.  Run by hand, with
``test_cells.py``:

    python -m pytest benchmark/tests/test_dag_counters.py -q
"""

import os

import pytest
import test_cells
from test_cells import ROOT, run_cell, toy_checkout

# ``toy_checkout`` cuts every configuration; ``nas_mg``'s toy size is
# ``test_cell_mg.py``'s to give when the directory runs, this file's alone
test_cells.TOY.setdefault("nas_mg", 16)

COUNTED = ("dag_nodes_per_solve", "dag_node_ms", "dag_index_ms",
           "dag_build_ms", "host_gc_ms")


def rehearse(tmp_path, cell):
    p, last = run_cell(toy_checkout(tmp_path), cell, trace=1)
    assert p.returncode == 0, p.stderr[-2000:]
    assert last["correct"] and last["failed"] == 0, p.stdout[-2000:]
    for name in COUNTED:
        assert "rehearsal." + name in last["metrics"], sorted(last["metrics"])
    # no device trace on the CPU: that reader finds nothing and says so
    assert "rehearsal.idle_unnamed_pct" not in last["metrics"]
    return {k[len("rehearsal."):]: v["value"]
            for k, v in last["metrics"].items()}


@pytest.mark.parametrize("cell", ["star2", "chain-1e9-peek"])
def test_the_cell_counts_its_nodes_the_same_in_two_runs(tmp_path, cell):
    first = rehearse(tmp_path / "a", cell)
    second = rehearse(tmp_path / "b", cell)
    assert first["dag_nodes_per_solve"] == second["dag_nodes_per_solve"] > 0
    for m in (first, second):
        assert m["dag_nodes_per_solve"] == int(m["dag_nodes_per_solve"])
        assert m["dag_node_ms"] > 0 and m["dag_build_ms"] > 0
        assert m["dag_index_ms"] >= 0 and m["host_gc_ms"] >= 0
        # the build is the script with the lazy layer under it ...
        assert m["dag_build_ms"] <= m["host_outside_flush_ms"] + 1.0
    if cell == "star2":
        # ... and holds every node and index of a one-flush solve
        assert (first["dag_node_ms"] + first["dag_index_ms"]
                <= first["dag_build_ms"] + 1.0)
    else:
        # peek slices D 32 times a solve, 16 of them inside a read's
        # flush, where no phase opens: the index counter sees all 32
        assert first["dag_index_ms"] > 0


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
