"""The cell ``mg-C`` end to end at a toy size on the CPU, from the new
files and entries alone (16^3, the twenty iterations and the class-C
smoother kept; the norm to meet is ``mg_np`` float64's at that size).
Importing this file is what gives ``test_cells.toy_checkout`` a toy ``n``
for the program ``nas_mg``, so rehearse the cells by the directory:

    python -m pytest benchmark/tests -q

Run by hand, not collected by tier-1.  ``--rehearse-cpu`` prints every
metric under a ``rehearsal.`` name: nothing here is a device number.
"""

import json

import test_cells
from test_cells import run_cell

test_cells.TOY.setdefault("nas_mg", 16)

CONFIG = "benchmark/configs/nas-mg-C.json"


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


def test_the_cell_runs_segmented_on_the_xla_stencil(tmp_path):
    p, last = run_cell(toy_checkout(tmp_path), "mg-C", trace=1)
    assert p.returncode == 0, p.stderr[-2000:]
    assert last["correct"] is True and last["failed"] == 0, p.stdout[-2000:]
    assert last["attempted"] >= 1 and last["device"]["count"] == 1
    assert metric(last, "flushes_per_solve") == 1
    assert metric(last, "compiles_in_window") == 0
    assert metric(last, "segments_per_solve") >= 2
    assert metric(last, "live_groups") == 1
    # no device trace on the CPU: that reader finds nothing and says so
    assert "rehearsal.kernel_roofline" not in last["metrics"]
    window = [ln for ln in p.stdout.splitlines()
              if ln.startswith("benchmark: window")][0]
    facts = json.loads(window[len("benchmark: window "):])["verify"]
    assert facts["norms_equal"] is True and facts["solves_checked"] >= 3
    assert facts["norm_rel_err"] < 1e-4
    assert set(facts["window_rel_err"]) == {"resid", "psinv", "rprj3",
                                            "interp"}
    assert max(facts["window_rel_err"].values()) < 2e-5


def test_the_path_and_the_classes_are_the_configurations(tmp_path):
    """What a solve is held to is read from ``nas-mg-C.json``: under
    another name for XLA's path every solve fails its path check, and the
    class ``stencil`` is there for a kernel's custom calls to land in."""
    checkout = test_cells.toy_checkout(tmp_path)
    with open(checkout / CONFIG) as f:
        cfg = json.load(f)
    assert list(cfg["kernel_classes"]) == ["stencil", "fusion"]
    cfg["stencil_paths"]["fusion"] = "no_such_path"
    with open(checkout / CONFIG, "w") as f:
        json.dump(cfg, f)
    p, last = run_cell(checkout, "mg-C")
    assert p.returncode == 0, p.stderr[-2000:]
    assert last["failed"] == last["attempted"] >= 1
    assert "stencil took path ('xla',), want ('no_such_path',)" in p.stdout


def test_the_end_to_end_line_of_the_cell(tmp_path):
    p, last = run_cell(toy_checkout(tmp_path), "mg-C")
    assert p.returncode == 0, p.stderr[-2000:]
    assert last["correct"] is True and last["failed"] == 0, p.stdout[-2000:]
    assert {"rehearsal.setup_s", "rehearsal.solve_ms",
            "rehearsal.algo_gbps_per_chip"} == set(last["metrics"])


def test_a_norm_off_the_published_value_fails_every_solve(tmp_path):
    p, last = run_cell(toy_checkout(tmp_path, norm_rtol=1e-9), "mg-C")
    assert p.returncode == 0, p.stderr[-2000:]
    assert last["failed"] == last["attempted"] >= 1
    assert last["correct"] is False and "off the configuration's" in p.stdout


def test_a_limit_that_bfloat16_would_pass_is_not_correct(tmp_path):
    p, last = run_cell(toy_checkout(tmp_path, window_rtol=0.5), "mg-C")
    assert p.returncode == 0, p.stderr[-2000:]
    assert last["correct"] is False and "would pass" in p.stdout


def test_a_window_off_numpy_is_not_correct(tmp_path):
    p, last = run_cell(toy_checkout(tmp_path, window_rtol=1e-12), "mg-C")
    assert p.returncode == 0, p.stderr[-2000:]
    assert last["correct"] is False and "off NumPy" in p.stdout
