"""chip_smoke.py on the CPU mesh: each phase at toy size with the Pallas
stencil kernel interpreting, the refusal to run without a TPU, a forced
kernel fall-through that must FAIL its phase, and the classification of
what the chip's compiler says when it refuses a kernel."""

import os
import subprocess
import sys

import jax
import pytest

import ramba_tpu as rt
from ramba_tpu import skeletons
from ramba_tpu.ops import pallas_backend, stencil_pallas
from ramba_tpu.resilience import retry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

pytestmark = pytest.mark.skipif(
    jax.process_count() > 1,
    reason="the smoke drives one controller, like a user's script")


@pytest.fixture
def interpreting(monkeypatch):
    """The CPU mesh runs the Pallas stencil kernel in interpret mode, so
    the phases take the kernel paths they take on the chip."""
    monkeypatch.setattr(stencil_pallas, "_INTERPRET", True)
    rt.random.seed(chip_smoke.SEED)


def _paths(n):
    return chip_smoke.expected_stencil_paths(n, len(jax.devices()))


def test_semantics_phase(interpreting):
    facts = chip_smoke.phase_semantics(rt, 1 << 14, interpret_ok=True)
    assert facts["rungs"] == ["fused"]


def test_distributed_phase(interpreting):
    facts = chip_smoke.phase_distributed(rt, interpret_ok=True)
    assert facts["rungs"] == ["fused"]
    assert "xla" not in facts["stencil_paths"], facts


@pytest.mark.parametrize("days", [1504, 1462])
def test_groupby_phase(interpreting, days):
    # 1462 days: the eight devices do not divide them, the layout does
    facts = chip_smoke.phase_groupby(rt, days, (6, 20), interpret_ok=True)
    assert facts["rungs"] == ["fused"]
    assert facts["segment_paths"] == ["walk_broadcast", "walk_reduce"]


def test_chain_phase(interpreting):
    facts = chip_smoke.phase_chain(rt, 1 << 18, interpret_ok=True)
    assert facts["chain_flushes"] == 1
    assert abs(facts["sum"] - (1 << 18)) < 1e-3 * (1 << 18)


@pytest.mark.parametrize("n", [256, 200])
def test_stencil_phase(interpreting, n):
    # 256 is lane-aligned (the fast kernel on one device), 200 is not
    facts = chip_smoke.phase_stencil(rt, n, _paths(n), interpret_ok=True)
    assert facts["path"] == "+".join(_paths(n))


@pytest.fixture
def one_device():
    """The mesh of one chip: one device."""
    from jax.sharding import Mesh
    import numpy as np

    from ramba_tpu.parallel import mesh as rmesh

    before = rmesh.get_mesh()
    rmesh.set_mesh(Mesh(np.array(jax.devices()[:1]), ("d0",)))
    yield
    rmesh.set_mesh(before)


def test_stencil3_phase(interpreting, monkeypatch):
    """The 27-point sweep at toy size on the CPU mesh: the sharded path
    and XLA's local blocks, as on four chips."""
    n = 20
    facts = chip_smoke.phase_stencil3(
        rt, n, chip_smoke.expected_stencil_paths3(n, len(jax.devices())),
        interpret_ok=True)
    assert facts["rungs"] == ["fused"]
    assert chip_smoke.expected_stencil_paths3(258, 1) == ("pallas_padded",)
    assert chip_smoke.expected_stencil_paths3(130, 1) == ("xla",)
    assert stencil_pallas._rank3_wins((258,) * 3, jax.numpy.dtype("float32"),
                                      1)


def test_stencil3_phase_on_one_device(interpreting, one_device, monkeypatch):
    """On a mesh of one device, the kernel's threshold lowered to the
    toy's last axis: the kernel, as on one chip; and a sweep that takes
    another path than the one named fails its phase."""
    monkeypatch.setattr(stencil_pallas, "_RANK3_MIN_LANES", 22)
    # the placement check counts every device jax shows: seven of the CPU
    # mesh's eight are outside this mesh
    monkeypatch.setattr(chip_smoke, "_require_sharded", lambda *a, **k: None)
    facts = chip_smoke.phase_stencil3(rt, 22, ("pallas_padded",),
                                      interpret_ok=True)
    assert facts["path"] == "pallas_padded" and facts["max_abs_vs_xla"] < 1e-5
    with pytest.raises(chip_smoke.SmokeFailure, match="took path"):
        chip_smoke.phase_stencil3(rt, 24, ("xla",), interpret_ok=True)


def test_prolong_phase(interpreting):
    """On the CPU mesh the five writes through XLA, as on four chips."""
    n = 10
    facts = chip_smoke.phase_prolong(
        rt, n, chip_smoke.expected_prolong_path(n, len(jax.devices())),
        interpret_ok=True)
    assert facts["path"] == "xla" and facts["rungs"] == ["fused"]
    assert chip_smoke.expected_prolong_path(258, 1) == "pallas"
    assert chip_smoke.expected_prolong_path(10, 1) == "xla"


def test_prolong_phase_on_one_device(interpreting, one_device,
                                     interpreting_prolong):
    """On a mesh of one device, over the kernel's bound: the kernel,
    interpreted, bit for bit the five writes; and a prolongation that
    takes another path than the one named fails its phase."""
    from ramba_tpu.ops import prolong_pallas

    n = prolong_pallas.MIN_EXTENT
    n = min(2 ** k + 2 for k in range(2, 12) if 2 ** k + 2 >= n)
    assert chip_smoke.phase_prolong(rt, n, "pallas", interpret_ok=True)[
        "path"] == "pallas"
    with pytest.raises(chip_smoke.SmokeFailure, match="took"):
        chip_smoke.phase_prolong(rt, 10, "pallas", interpret_ok=True)


def test_stencil_sweeps_phase(interpreting):
    facts = chip_smoke.phase_stencil_sweeps(rt, 128, 3, 4, _paths(128),
                                            interpret_ok=True)
    assert facts["rungs"] == ["fused"]


def test_prk_scalars_phase(interpreting):
    facts = chip_smoke.phase_prk_scalars(rt, 136, 10, _paths(136),
                                         interpret_ok=True)
    assert facts["rungs"] == ["fused"]
    assert facts["scalar_puts_second"] == 0
    assert facts["scalar_hits_second"] >= 10


def test_mg_phase(interpreting):
    facts = chip_smoke.phase_mg(rt, 16, iters=20, interpret_ok=True)
    assert facts["rungs"] == ["fused"] and "xla" in facts["path"]
    assert facts["segments"] >= 2
    assert facts["segment_hits_second"] == facts["segments"]
    assert abs(facts["norm"] - facts["want"]) < 1e-4 * facts["want"]
    # the second solve's DAG is the first's, every index and key a hit
    assert facts["index_hit_share_second"] == 1.0
    assert facts["key_hit_share_second"] == 1.0


def test_axpy_phase(interpreting):
    assert chip_smoke.phase_axpy(rt, 1 << 18, interpret_ok=True)[
        "rungs"] == ["fused"]


def test_broadcast_phase(interpreting):
    assert chip_smoke.phase_broadcast(rt, 512, interpret_ok=True)[
        "rungs"] == ["fused"]


def test_interpreted_kernel_fails_a_chip_phase(interpreting):
    """On the chip nothing may interpret: without ``interpret_ok`` the
    phase fails on the kernel's own record of having interpreted."""
    with pytest.raises(chip_smoke.SmokeFailure, match="interpret"):
        chip_smoke.phase_stencil(rt, 136, _paths(136))


def test_kernel_fall_through_fails_the_phase(interpreting, monkeypatch):
    """The Pallas family's run raises: the stencil falls through to XLA
    shifted slices and computes the right answer, and the phase FAILS on
    the degrade event instead of passing on that answer."""
    fam = pallas_backend.family("stencil")

    def refuse(*a, **k):
        raise RuntimeError("INTERNAL: Mosaic failed to compile TPU kernel")

    monkeypatch.setattr(fam, "run", refuse)
    monkeypatch.setattr(stencil_pallas, "run", refuse)
    # the warning is once per process; the degrade event is every time
    monkeypatch.setattr(skeletons, "_pallas_fallback_warned", False)
    with pytest.warns(UserWarning, match="stencil path unavailable"):
        with pytest.raises(chip_smoke.SmokeFailure) as ei:
            chip_smoke.phase_stencil(rt, 264, _paths(264),
                                     interpret_ok=True)
    ev = rt.diagnostics.resilience_events(5)
    assert any(e["type"] == "degrade" and e.get("site") == "stencil"
               for e in ev), ev
    assert "xla" in str(ei.value) or "degrade" in str(ei.value)


def test_main_refuses_without_a_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       capture_output=True, text=True, timeout=120,
                       cwd=tmp_path, env=env)
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert "ok" not in r.stdout and "{" not in r.stdout, r.stdout


def test_result_line_has_exactly_the_contract_keys():
    import json

    dev = jax.devices()[0]
    for ok in (True, False):
        got = json.loads(chip_smoke.result_line(ok, dev, len(jax.devices())))
        assert got == {"ok": ok, "device": {
            "platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}}


class _XlaError(RuntimeError):
    pass


@pytest.mark.parametrize("msg", [
    # the stencil kernel's own refusal under an earlier Mosaic
    "INTERNAL: Mosaic failed to compile TPU kernel: Failed to prove that a "
    "tile index in dimension 0 is divisible by the tiling (8).",
    # libtpu 0.0.34 on a v5e, a 256 MiB input window (chip run, PR 21)
    "RESOURCE_EXHAUSTED: Allocation (size=268435456) would exceed memory "
    "(size=134217728) :: #allocation2 [shape = 'u8[268435456]{0}', "
    "space=vmem, size = 0x10000000, tag = 'input window allocation for "
    "operator input 0.'] :: tpu_custom_call.1",
    # same chip, the fast stencil kernel at a 64-row block, 8192^2 (PR 21)
    "Scoped allocation with size 20.91M and limit 16.00M exceeded scoped "
    "vmem limit by 4.91M. It should not be possible to run out of scoped "
    "vmem",
])
def test_compile_refusals_are_fatal(msg):
    assert retry.classify(_XlaError(msg)) == "fatal"


def test_runtime_failures_keep_their_classes():
    # an HBM allocation failure at run time (chip run, PR 21) is still oom
    assert retry.classify(_XlaError(
        "RESOURCE_EXHAUSTED: Error allocating device buffer: Attempting to "
        "allocate 7.45G. That was not possible. There are 867.18M free.; "
        "(0x0x0_HBM0)")) == "oom"
    assert retry.classify(_XlaError(
        "INTERNAL: Failed to execute: stream error")) == "retryable"


def test_compile_refusal_surfaces_from_the_fused_rung(monkeypatch):
    """A refusal raised while the fused rung compiles is not retried, not
    handed down the ladder and never reaches the host rung."""
    from ramba_tpu.core import fuser

    calls = []

    def refuse(fn, program, leaf_vals, *a, **k):
        calls.append(k.get("rung"))
        raise _XlaError("INTERNAL: Mosaic failed to compile TPU kernel: no")

    monkeypatch.setattr(fuser, "_execute_compiled", refuse)
    a = rt.arange(4099) * 3.0
    with pytest.raises(_XlaError, match="Mosaic failed"):
        rt.sync()
    assert calls == ["fused"], calls
    ev = rt.diagnostics.resilience_events(5)
    assert not any(e.get("action") in ("retry", "rung") and
                   "Mosaic" in str(e.get("error")) for e in ev), ev
    monkeypatch.undo()
    del a
    rt.sync()


@pytest.mark.parametrize("n,iters,ndev,walks", [
    (512, 20, 1, 561),  # mg-C: 81 at 514^3, 80 at each of 258^3 .. 10^3
    (512, 3, 1, 85), (16, 3, 1, 25), (4, 5, 1, 0), (512, 3, 4, 0),
    (16, 12, 8, 0)])
def test_the_smoke_states_which_refreshes_walk(n, iters, ndev, walks):
    """From the grid, the iterations and the devices alone: the levels
    whose array has a whole row tile, on one chip."""
    assert chip_smoke.expected_face_walks(n, iters, ndev) == walks


@pytest.fixture
def grid_2x2():
    """The mesh of the four-chip host: 2 x 2, of four of the devices."""
    import numpy as np
    from jax.sharding import Mesh

    from ramba_tpu.parallel import mesh as rmesh

    before = rmesh.get_mesh()
    rmesh.set_mesh(Mesh(np.array(jax.devices()[:4]).reshape(2, 2),
                        ("d0", "d1")))
    yield
    rmesh.set_mesh(before)


@pytest.mark.parametrize("n,ndev,path", [(512, 4, "swap"), (4096, 4, "swap"),
                                         (512, 8, "xla"), (384, 4, "xla"),
                                         (512, 1, "local")])
def test_the_smoke_states_which_transposes_swap(n, ndev, path):
    assert chip_smoke.expected_transpose_path(n, ndev) == path


def test_transpose_phase_on_a_2x2_grid(interpreting, grid_2x2, monkeypatch):
    """The four-chip host's leg at toy size: the swap, the kernel
    interpreted, one block a device an iteration; and a transpose that
    takes another path than the one named fails its phase."""
    from ramba_tpu.ops import transpose_sharded

    monkeypatch.setattr(transpose_sharded, "_INTERPRET", True)
    # the placement check counts every device jax shows: four of the CPU
    # mesh's eight are outside this mesh
    monkeypatch.setattr(chip_smoke, "_require_sharded", lambda *a, **k: None)
    facts = chip_smoke.phase_transpose(rt, 512, "swap", interpret_ok=True)
    assert facts["path"] == "swap" and facts["rungs"] == ["fused"]
    assert facts["exchange_bytes"] == 3 * 256 * 256 * 4
    with pytest.raises(chip_smoke.SmokeFailure, match="took"):
        chip_smoke.phase_transpose(rt, 384, "swap", interpret_ok=True)


def test_transpose_phase_on_the_suites_mesh(interpreting):
    n = 512
    facts = chip_smoke.phase_transpose(
        rt, n, chip_smoke.expected_transpose_path(n, len(jax.devices())),
        interpret_ok=True)
    assert facts["path"] == "xla" and facts["exchange_bytes"] == 0
