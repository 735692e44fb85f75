"""The cell ``doy-clim-30y-x4`` end to end at a toy size on four virtual
CPU devices, from the new files and entries alone: two years of a 9 x 20
grid (730 days, which four does not divide, so the default layout splits
time 2 x longitude 2 and the partial sums are combined across devices as at
size; the 366 labels kept).  Run by hand, not collected by tier-1:

    python -m pytest benchmark/tests/test_cell_doy_clim_30y.py -q

``--rehearse-cpu`` prints every metric under a ``rehearsal.`` name: nothing
here is a device number.
"""

import json
import os
import subprocess

import test_cell_mg  # noqa: F401  (gives ``nas_mg`` its toy ``n``)
import test_cells
from test_cells import ROOT, run_cell

CELL = "doy-clim-30y-x4"
CONFIG = "benchmark/configs/xr-doy-clim-era5grid-30y.json"

test_cells.TOY.setdefault("doy_clim_mesh", 0)  # ``n``, which it ignores
_toy_checkout = test_cells.toy_checkout


def toy_checkout(tmp_path, **assumed):
    """The suite's toy checkout with this configuration cut as well, so
    that whoever rehearses every cell rehearses this one too."""
    checkout = _toy_checkout(tmp_path)
    with open(checkout / CONFIG) as f:
        cfg = json.load(f)
    # 1993 and 1994: 730 days (1991 and 1992 are 731, which nothing
    # divides but longitude four ways: no partial sums to combine)
    cfg.update(grid=[9, 20], years=2, first_year=1993)
    cfg["assumed"].update(assumed)
    with open(checkout / CONFIG, "w") as f:
        json.dump(cfg, f)
    return checkout


test_cells.toy_checkout = toy_checkout


def metric(last, name):
    return last["metrics"]["rehearsal." + name]["value"]


def test_the_cell_runs_both_passes_sharded_and_counts_what_it_combines(
        tmp_path):
    p, last = run_cell(toy_checkout(tmp_path), CELL, trace=1, devices=4)
    assert p.returncode == 0, p.stderr[-2000:]
    assert last["correct"] is True and last["failed"] == 0, p.stdout[-2000:]
    assert last["attempted"] >= 1 and last["device"]["count"] == 4
    assert metric(last, "flushes_per_solve") == 1
    assert metric(last, "compiles_in_window") == 0
    assert metric(last, "live_groups") == 1
    # a device's partial sums, (366, 9, 10) float32, and the scalar
    assert metric(last, "segment_combine_gb") == (366 * 9 * 10 * 4 + 4) / 1e9
    # no device trace on the CPU: those readers find nothing and say so
    for name in ("segment_ms", "segment_roofline", "collective_ms"):
        assert "rehearsal." + name not in last["metrics"]
    window = [ln for ln in p.stdout.splitlines()
              if ln.startswith("benchmark: window")][0]
    facts = json.loads(window[len("benchmark: window "):])["verify"]
    assert facts["T"] == 730 and facts["solves_checked"] >= 3
    assert facts["layout"] == "PartitionSpec('d1', None, 'd0')"
    assert facts["sharded"] == {"walk_reduce": True, "walk_broadcast": True}
    assert facts["clim_max_abs_err"] < 3e-4 < facts["float16_clim_max_abs_err"]
    assert facts["rms_rel_err"] < 2e-5 < facts["float16_rms_rel_err"]


def test_the_end_to_end_line_of_the_cell(tmp_path):
    p, last = run_cell(toy_checkout(tmp_path), CELL, devices=4)
    assert p.returncode == 0, p.stderr[-2000:]
    assert last["correct"] is True and last["failed"] == 0, p.stdout[-2000:]
    assert {"rehearsal.setup_s", "rehearsal.solve_ms",
            "rehearsal.algo_gbps_per_chip"} == set(last["metrics"])


def test_a_miss_of_the_reference_is_not_correct(tmp_path):
    for limit in ("clim_atol", "rms_rtol"):
        sub = tmp_path / limit
        sub.mkdir()
        p, last = run_cell(toy_checkout(sub, **{limit: 1e-12}), CELL,
                           devices=4)
        assert p.returncode == 0, p.stderr[-2000:]
        assert last["correct"] is False and "off the reference" in p.stdout


def test_a_limit_that_float16_would_pass_is_not_correct(tmp_path):
    p, last = run_cell(toy_checkout(tmp_path, clim_atol=10.0), CELL,
                       devices=4)
    assert p.returncode == 0, p.stderr[-2000:]
    assert last["correct"] is False and "would pass float16" in p.stdout


def test_the_parent_is_refused_by_the_probe_at_once(tmp_path):
    """The tree this cell was added to (the commit before PR 36) names a
    four-way split of the time axis that jax cannot hold and keeps the
    second pass off the walk under a mesh: the probe says so and the run
    ends with exit code 1, before the cube is built."""
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
    assert "metrics" not in p.stdout
