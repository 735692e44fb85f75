"""The cell ``transpose-x4`` end to end at a toy size on four virtual CPU
devices, from the new files and entries alone: a 512^2 matrix on the
2 x 2 mesh (blocks of 256^2, whole lane tiles, so the transpose takes the
swap as at size).  Importing this file gives ``test_cells.toy_checkout`` a
toy ``n`` for the program ``prk_transpose``; run by hand, not collected by
tier-1:

    python -m pytest benchmark/tests/test_cell_transpose.py -q

``--rehearse-cpu`` prints every metric under a ``rehearsal.`` name:
nothing here is a device number.
"""

import json
import subprocess

import test_cell_doy_clim_30y  # noqa: F401  (the toy sizes of the others)
import test_cells
from test_cells import ROOT, run_cell

CELL = "transpose-x4"
CONFIG = "benchmark/configs/prk-transpose-n49152.json"
N = 512

test_cells.TOY.setdefault("prk_transpose", N)


def toy_checkout(tmp_path, **assumed):
    checkout = test_cells.toy_checkout(tmp_path)
    with open(checkout / CONFIG) as f:
        cfg = json.load(f)
    cfg["assumed"].update(assumed)
    with open(checkout / CONFIG, "w") as f:
        json.dump(cfg, f)
    return checkout


def metric(last, name):
    return last["metrics"]["rehearsal." + name]["value"]


def facts(p):
    window = [ln for ln in p.stdout.splitlines()
              if ln.startswith("benchmark: window")][0]
    return json.loads(window[len("benchmark: window "):])


def test_the_cell_swaps_one_block_an_iteration(tmp_path):
    p, last = run_cell(toy_checkout(tmp_path), CELL, trace=1, devices=4)
    assert p.returncode == 0, p.stderr[-2000:]
    assert last["correct"] is True and last["failed"] == 0, p.stdout[-2000:]
    assert last["attempted"] >= 1 and last["device"]["count"] == 4
    assert metric(last, "flushes_per_solve") == 1
    assert metric(last, "compiles_in_window") == 0
    assert metric(last, "live_groups") == 1
    # ten swaps a solve, a 256^2 float32 block each
    assert metric(last, "transpose_exchange_gb") == 10 * 256 * 256 * 4 / 1e9
    # no device trace on the CPU: those readers find nothing and say so
    for name in ("transpose_ms", "transpose_roofline", "collective_ms",
                 "kernel_roofline"):
        assert "rehearsal." + name not in last["metrics"]
    verify = facts(p)["verify"]
    assert verify["layout"] == verify["A_layout"] == "PartitionSpec('d0', 'd1')"
    assert verify["iterations"] % 10 == 0 and verify["windows"] >= 3
    assert verify["bfloat16_mean_rel_err"] > 1e-4


def test_the_end_to_end_line_of_the_cell(tmp_path):
    p, last = run_cell(toy_checkout(tmp_path), CELL, devices=4)
    assert p.returncode == 0, p.stderr[-2000:]
    assert last["correct"] is True and last["failed"] == 0, p.stdout[-2000:]
    assert {"rehearsal.setup_s", "rehearsal.solve_ms",
            "rehearsal.algo_gbps_per_chip"} == set(last["metrics"])


def test_a_norm_off_the_closed_form_fails_every_solve(tmp_path):
    p, last = run_cell(toy_checkout(tmp_path, norm_rtol=-1.0), CELL,
                       devices=4)
    assert p.returncode == 0, p.stderr[-2000:]
    assert last["failed"] == last["attempted"] >= 1
    assert last["correct"] is False and "want" in facts(p)["failed_first"]


def test_a_limit_that_bfloat16_would_pass_is_not_correct(tmp_path):
    p, last = run_cell(toy_checkout(tmp_path, norm_rtol=1.0), CELL,
                       devices=4)
    assert p.returncode == 0, p.stderr[-2000:]
    assert last["correct"] is False and "would pass bfloat16" in p.stdout


def test_the_parent_is_refused_by_the_probe_at_once(tmp_path):
    """The tree this cell was added to lowers ``A.T`` as GSPMD's
    transpose and has no swap: the probe says so and the run ends with
    exit code 1, before A and B are built."""
    parent = tmp_path / "parent"
    parent.mkdir()
    rev = subprocess.run(
        ["git", "log", "--format=%H", "-n", "1", "--diff-filter=A", "--",
         CONFIG], cwd=ROOT, capture_output=True, text=True).stdout.strip()
    rev = rev + "~1" if rev else "HEAD"  # uncommitted: HEAD is the parent
    tar = subprocess.run(["git", "archive", rev, "ramba_tpu"], cwd=ROOT,
                         capture_output=True, check=True).stdout
    subprocess.run(["tar", "-x", "-C", str(parent)], input=tar, check=True)
    p, last = run_cell(toy_checkout(tmp_path / "bench"), CELL, devices=4,
                       env_extra={"PYTHONPATH": str(parent)})
    assert p.returncode == 1 and last is None, p.stdout[-2000:]
    assert "cannot run this configuration" in p.stderr
    assert "does not take the swap" in p.stderr
    assert "metrics" not in p.stdout


def test_the_readers_of_the_kernel_class():
    """``transpose_ms`` and ``transpose_roofline`` from a reduced trace
    (as ``tracered.reduce_events`` gives it: seconds a chip), and nothing
    where the class is empty or the program has no convention."""
    import importlib.util
    import os
    import types

    from test_cells import HERE

    def reader(name):
        spec = importlib.util.spec_from_file_location(name, os.path.join(
            os.path.dirname(HERE), "layer_metrics", name + ".py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read

    n, chips = 49152, 4
    prog = types.SimpleNamespace(
        transpose_bytes_per_solve=lambda: 3 * 10 * n * n * 4)
    trace = {"solves": 3, "class_s": {"transpose": 3 * 0.120}}
    ctx = types.SimpleNamespace(trace=trace, program=prog, chips=chips,
                                peaks={"hbm_bytes_per_s": 819e9})
    assert abs(reader("transpose_ms")(ctx) - 120.0) < 1e-9
    least = 3 * 10 * n * n * 4 / 819e9 / chips
    assert abs(reader("transpose_roofline")(ctx)
               - 100 * least / 0.120) < 1e-9
    ctx.trace = {"solves": 3, "class_s": {"transpose": 0.0}}
    assert reader("transpose_ms")(ctx) is None
    assert reader("transpose_roofline")(ctx) is None
    ctx.trace, ctx.program = trace, types.SimpleNamespace()
    assert reader("transpose_roofline")(ctx) is None
