"""Every cell end to end at a toy size on the CPU (Pallas interpreting),
the four-chip cell on four virtual devices; the result line's keys; the
refusals; and a fifth cell added by new files and one ``workloads`` entry.
Run by hand, not collected by tier-1:

    python -m pytest benchmark/tests/test_cells.py -q

Each test copies ``benchmark/`` and ``BENCHMARK.json`` into a temporary
directory with toy orders in the configuration files, and runs the command
there in a fresh process, as the driver does.  ``--rehearse-cpu`` prints
every metric under a ``rehearsal.`` name: nothing here is a device number.
"""

import glob
import json
import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
TOY = {"chain": 300_000, "prk_star": 300}


def toy_checkout(tmp_path):
    """A copy of the benchmark with toy sizes, the program beside it by
    PYTHONPATH (the copy holds BENCHMARK.json and ``paths`` only)."""
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", ".trace",
                                                  "tests", "fixtures"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for path in glob.glob(str(tmp_path / "benchmark/configs/*.json")):
        with open(path) as f:
            cfg = json.load(f)
        cfg["n"] = TOY[cfg["program"]]
        with open(path, "w") as f:
            json.dump(cfg, f)
    return tmp_path


def run_cell(checkout, cell, *, trace=0, devices=1, rehearse=True,
             env_extra=None, with_program=True):
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("RAMBA_", "XLA_FLAGS", "JAX_"))}
    env.update(JAX_PLATFORMS="cpu", RAMBA_TPU_PALLAS_INTERPRET="1",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               JAX_COMPILATION_CACHE_DIR=str(checkout / ".jax_cache"))
    if with_program:
        env["PYTHONPATH"] = ROOT
    env.update(env_extra or {})
    cmd = [sys.executable, "benchmark/run.py", "--workload", cell, "--seed",
           "7", "--seconds", "1", "--trace", str(trace)]
    if rehearse:
        cmd.append("--rehearse-cpu")
    p = subprocess.run(cmd, cwd=checkout, env=env, capture_output=True,
                       text=True, timeout=600)
    lines = p.stdout.strip().splitlines()
    last = None
    if lines and lines[-1].startswith("{"):
        last = json.loads(lines[-1])
    return p, last


def cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return [(w["name"], w["chips"]) for w in json.load(f)["workloads"]]


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell,chips", cells())
def test_cell_end_to_end_at_toy_size(tmp_path, cell, chips, trace):
    p, last = run_cell(toy_checkout(tmp_path), cell, trace=trace,
                       devices=chips)
    assert p.returncode == 0, p.stderr[-2000:]
    assert set(last) == LINE_KEYS  # no device trace on the CPU: no breakdown
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] >= 1
    assert last["device"]["platform"] == "cpu"
    assert last["device"]["count"] == chips
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    declared = {m["name"] for m in bench["per_layer" if trace
                                         else "end_to_end"]
                if cell in m.get("workloads", [cell])}
    names = set(last["metrics"])
    assert names and all(n.startswith("rehearsal.") for n in names)
    assert {n[len("rehearsal."):] for n in names} <= declared
    if trace == 0:
        assert "rehearsal.setup_s" in names and len(names) >= 2
    else:
        # a reader with nothing to read is left out: no device trace here
        assert "rehearsal.warm_first_solve_ms" in names
        assert "rehearsal.kernel_ms" not in names
    for m in last["metrics"].values():
        assert set(m) == {"value", "unit"} and isinstance(m["value"], float)


def test_refuses_to_run_without_a_tpu(tmp_path):
    p, last = run_cell(toy_checkout(tmp_path), "chain-1e9", rehearse=False)
    assert p.returncode != 0 and last is None
    assert "metrics" not in p.stdout and "no TPU" in p.stderr


def test_refuses_a_machine_with_other_chips_than_the_cell(tmp_path):
    p, last = run_cell(toy_checkout(tmp_path), "star2-x4", devices=1)
    assert p.returncode != 0 and last is None and "metrics" not in p.stdout


def test_refuses_to_run_without_the_program(tmp_path):
    p, last = run_cell(toy_checkout(tmp_path), "chain-1e9",
                       with_program=False)
    assert p.returncode != 0 and last is None and "metrics" not in p.stdout


def test_a_forced_fall_through_fails_every_solve(tmp_path):
    # RAMBA_TPU_PALLAS=0 sends the stencil down the XLA path: the values
    # are still right, the path is not the cell's
    p, last = run_cell(toy_checkout(tmp_path), "star2",
                       env_extra={"RAMBA_TPU_PALLAS": "0"})
    assert p.returncode == 0, p.stderr[-2000:]
    assert last["failed"] == last["attempted"] >= 1
    assert last["correct"] is False  # the warm-up solves failed too
    assert "stencil took path" in p.stdout


def test_a_fifth_cell_is_new_files_and_one_entry(tmp_path):
    """``star2-8192-stepwise`` (PERF.md, Open questions): the aligned
    order, one iteration and a norm read per solve.  A configuration
    file, a traffic file and entries in BENCHMARK.json; no file that was
    there changes."""
    checkout = toy_checkout(tmp_path)
    before = {p: os.path.getmtime(p) for p in
              glob.glob(str(checkout / "benchmark/**/*"), recursive=True)
              if os.path.isfile(p)}
    with open(checkout / "benchmark/configs/prk-star2-n15000.json") as f:
        cfg = json.load(f)
    cfg.update(name="prk-star2-n8192", n=256)  # toy stand-in, lane-aligned
    with open(checkout / "benchmark/configs/prk-star2-n8192.json", "w") as f:
        json.dump(cfg, f)
    with open(checkout / "benchmark/traffic/stepwise.json", "w") as f:
        json.dump({"what": "a residual read every sweep", "mesh": [1],
                   "solve": [{"op": "iterate", "count": 1}, {"op": "norm"}],
                   "trace_solves": 4}, f)
    with open(checkout / "BENCHMARK.json") as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "prk-star2-n8192", "source": cfg["source"],
        "file": "benchmark/configs/prk-star2-n8192.json",
        "reduced": ["n"], "why": "the aligned pallas_fast path"})
    bench["workloads"].append({
        "name": "star2-8192-stepwise", "config": "prk-star2-n8192",
        "traffic": "stepwise", "chips": 1, "why": "host path per sweep"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "star2" in m.get("workloads", []):
            m["workloads"].append("star2-8192-stepwise")
    with open(checkout / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)
    p, last = run_cell(checkout, "star2-8192-stepwise")
    assert p.returncode == 0, p.stderr[-2000:]
    assert last["correct"] is True and last["failed"] == 0
    assert "rehearsal.algo_gbps_per_chip" in last["metrics"]
    assert all(os.path.getmtime(p) == t for p, t in before.items())
