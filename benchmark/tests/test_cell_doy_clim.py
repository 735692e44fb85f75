"""The cell ``doy-clim`` end to end at a toy size on the CPU, from the new
files and entries alone (two years of a 9 x 20 grid, the 366 labels kept).
Run by hand, not collected by tier-1:

    python -m pytest benchmark/tests/test_cell_doy_clim.py -q

``--rehearse-cpu`` prints every metric under a ``rehearsal.`` name: nothing
here is a device number.
"""

import json

import test_cells
from test_cells import run_cell

CONFIG = "benchmark/configs/xr-doy-clim-era5grid.json"


def toy_checkout(tmp_path, **assumed):
    checkout = test_cells.toy_checkout(tmp_path)  # conftest.py's: toy sizes
    with open(checkout / CONFIG) as f:
        cfg = json.load(f)
    cfg["assumed"].update(assumed)
    with open(checkout / CONFIG, "w") as f:
        json.dump(cfg, f)
    return checkout


def metric(last, name):
    return last["metrics"]["rehearsal." + name]["value"]


def test_the_cell_runs_both_passes_on_the_walk(tmp_path):
    p, last = run_cell(toy_checkout(tmp_path), "doy-clim", trace=1)
    assert p.returncode == 0, p.stderr[-2000:]
    assert last["correct"] is True and last["failed"] == 0, p.stdout[-2000:]
    assert last["attempted"] >= 1 and last["device"]["count"] == 1
    assert metric(last, "flushes_per_solve") == 1
    assert metric(last, "compiles_in_window") == 0
    # no device trace on the CPU: the new readers find nothing and say so
    assert "rehearsal.segment_ms" not in last["metrics"]
    assert "rehearsal.segment_roofline" not in last["metrics"]
    window = [ln for ln in p.stdout.splitlines()
              if ln.startswith("benchmark: window")][0]
    facts = json.loads(window[len("benchmark: window "):])["verify"]
    assert facts["T"] == 730 and facts["solves_checked"] >= 3
    # the tolerances lie between the system's reading and float16's
    assert facts["clim_max_abs_err"] < 2e-4 < facts["float16_clim_max_abs_err"]
    assert facts["rms_rel_err"] < 2e-5 < facts["float16_rms_rel_err"]


def test_the_end_to_end_line_of_the_cell(tmp_path):
    p, last = run_cell(toy_checkout(tmp_path), "doy-clim")
    assert p.returncode == 0, p.stderr[-2000:]
    assert last["correct"] is True and last["failed"] == 0, p.stdout[-2000:]
    assert {"rehearsal.setup_s", "rehearsal.solve_ms",
            "rehearsal.algo_gbps_per_chip"} == set(last["metrics"])


def test_a_program_on_another_segment_path_is_refused_at_once(tmp_path):
    """What the parent of PR 30 does with this cell's files: its group-by
    takes no walk, so set-up's probe (three days of a 2 x 4 grid) stops
    the run before the cube is built, with another exit code than 0."""
    checkout = toy_checkout(tmp_path, segment_paths=["masked_dense"])
    p, last = run_cell(checkout, "doy-clim")
    assert p.returncode != 0 and last is None
    assert "cannot run this configuration" in p.stderr
    assert "metrics" not in p.stdout


def test_a_miss_of_the_reference_is_not_correct(tmp_path):
    """The reference is computed after the window, so a miss shows in
    ``correct`` (every solve's RMS is held to the first's as it runs, and
    the first's to the reference)."""
    for limit in ("clim_atol", "rms_rtol"):
        sub = tmp_path / limit
        sub.mkdir()
        p, last = run_cell(toy_checkout(sub, **{limit: 1e-12}), "doy-clim")
        assert p.returncode == 0, p.stderr[-2000:]
        assert last["correct"] is False and "off the reference" in p.stdout


def test_a_limit_that_float16_would_pass_is_not_correct(tmp_path):
    """The control is held to the limits at every size: a limit loosened
    until the reference in float16 passes it refuses the run."""
    p, last = run_cell(toy_checkout(tmp_path, clim_atol=10.0), "doy-clim")
    assert p.returncode == 0, p.stderr[-2000:]
    assert last["correct"] is False and "would pass float16" in p.stdout
