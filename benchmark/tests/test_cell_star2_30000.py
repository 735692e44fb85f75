"""The cell ``star2-30000-x4`` end to end at a toy order on four virtual
devices, under a budget its flush is over as linearized and under when its
live set is grouped; and ``star2-x4`` beside it, admitted as it stands.
Run by hand, not collected by tier-1:

    python -m pytest benchmark/tests/test_cell_star2_30000.py -q

Admission is held to the analytic estimate here (``RAMBA_HBM_ESTIMATE``,
a variable the program already has; ``analyze/rules.py``
``estimate_peak_bytes``), which walks the instructions in order:
``_linearize`` lays the ten ``A += 1`` down before the first stencil (14
arrays live at once), ``fuser._live_order`` interleaves them (6).  So a
budget between the two forces the grouped form without a chip; what the
chip's compiler holds at once is only in a chip run.  ``--rehearse-cpu``
prints every metric under a ``rehearsal.`` name: nothing here is a device
number.
"""

from test_cells import run_cell, toy_checkout

ARRAY = 300 * 300 * 4  # the toy order of test_cells.TOY
#: watermark 0.9 of this: room for 9 arrays
ANALYTIC = {"RAMBA_HBM_ESTIMATE": "analytic"}
TIGHT = {"RAMBA_HBM_BUDGET": str(10 * ARRAY), **ANALYTIC}
ROOMY = {"RAMBA_HBM_BUDGET": str(100 * ARRAY), **ANALYTIC}


def metric(last, name):
    return last["metrics"]["rehearsal." + name]["value"]


def test_the_new_cell_runs_grouped_and_its_sibling_as_it_stands(tmp_path):
    checkout = toy_checkout(tmp_path)
    p, last = run_cell(checkout, "star2-30000-x4", trace=1, devices=4,
                       env_extra=TIGHT)
    assert p.returncode == 0, p.stderr[-2000:]
    assert last["correct"] is True and last["failed"] == 0, p.stdout[-2000:]
    assert last["attempted"] >= 1 and last["device"]["count"] == 4
    assert metric(last, "live_groups") >= 2
    assert metric(last, "flushes_per_solve") == 1
    assert metric(last, "compiles_in_window") == 0
    # the estimate of the program that ran is under the watermark
    assert 0 < metric(last, "admit_est_gb") * 1e9 <= 9 * ARRAY

    p, last = run_cell(checkout, "star2-x4", trace=1, devices=4,
                       env_extra=ROOMY)
    assert p.returncode == 0, p.stderr[-2000:]
    assert last["correct"] is True and last["failed"] == 0, p.stdout[-2000:]
    assert metric(last, "live_groups") == 1
    assert metric(last, "admit_est_gb") * 1e9 > 9 * ARRAY


def test_the_end_to_end_line_of_the_new_cell(tmp_path):
    p, last = run_cell(toy_checkout(tmp_path), "star2-30000-x4", devices=4,
                       env_extra=TIGHT)
    assert p.returncode == 0, p.stderr[-2000:]
    assert last["correct"] is True and last["failed"] == 0, p.stdout[-2000:]
    assert {"rehearsal.setup_s", "rehearsal.solve_ms",
            "rehearsal.algo_gbps_per_chip"} == set(last["metrics"])


def test_without_the_grouping_no_solve_of_the_toy_cell_is_clean(tmp_path):
    """The parent's behaviour, kept reachable by a budget that no grouping
    fits: eviction, then the chunked rung, and every solve counted failed."""
    p, last = run_cell(toy_checkout(tmp_path), "star2-30000-x4", devices=4,
                       env_extra={"RAMBA_HBM_BUDGET": str(3 * ARRAY),
                                  **ANALYTIC})
    assert p.returncode == 0, p.stderr[-2000:]
    assert last["correct"] is False
    assert last["failed"] == last["attempted"] >= 1
