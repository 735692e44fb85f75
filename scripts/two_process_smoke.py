"""Two-process multi-controller smoke test (CPU backend).

The reference CI runs its whole suite on a 2-worker cluster (mpiexec -n 2,
/root/reference/.github/workflows/python-package.yml:40-46).  The TPU-native
equivalent of that mode is jax multi-controller SPMD: every process runs the
same program, `jax.distributed.initialize` forms the process group, and the
global mesh spans both processes' devices (parallel/distributed.py).

Run with no arguments to launch the 2-process test (exit 0 = pass):

    python scripts/two_process_smoke.py

Each worker: initializes the group, builds the cross-process global mesh,
creates a sharded array, runs a cross-process all-reduce via rt.sum, an
elementwise chain, and checks in_driver() gating.
"""

from __future__ import annotations

import os
import socket
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def worker(rank: int, port: int) -> None:
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 2)

    sys.path.insert(0, REPO)
    from ramba_tpu.parallel import distributed

    distributed.initialize(
        coordinator_address=f"localhost:{port}",
        num_processes=2,
        process_id=rank,
    )
    assert jax.process_count() == 2, jax.process_count()
    assert distributed.process_index() == rank
    assert len(jax.devices()) == 4, jax.devices()
    assert len(distributed.local_devices()) == 2

    import ramba_tpu as rt

    mesh = distributed.global_mesh()
    assert mesh.devices.size == 4
    rt.set_mesh(mesh)

    # sharded creation + fused chain + global reduction (the all-reduce
    # crosses the process boundary)
    n = 1 << 12
    a = rt.arange(n, dtype=float)
    d = rt.sin(a) * rt.sin(a) + rt.cos(a) ** 2
    total = float(rt.sum(d))
    assert abs(total - n) < 1e-6 * n, total

    s = float(rt.sum(a))
    assert s == n * (n - 1) / 2, s

    # sharded-directory save/load across the process boundary: each
    # process writes its own shards + manifest (synchronous host writes),
    # a collective acts as the barrier, then both reassemble the array
    rtd = os.environ["RAMBA_TPU_SMOKE_RTD"]
    big = rt.arange(n, dtype=float) * 3.0
    rt.save(rtd, big)
    float(rt.sum(rt.ones(256)))  # collective: all shards written
    back = rt.load(rtd)
    diff = float(rt.sum((back - big) * (back - big)))
    assert diff == 0.0, diff

    # single-file save under multi-controller: all-gather -> driver rank
    # writes -> barrier (round-4 verdict #4 follow-on; used to refuse)
    npy = os.path.join(os.path.dirname(rtd), "single.npy")
    rt.save(npy, big)
    back1 = rt.load(npy)
    diff1 = float(rt.sum((back1 - big) * (back1 - big)))
    assert diff1 == 0.0, diff1

    # the skeleton surface across the process boundary (round 4): a
    # 3-point spmd halo sweep — the ppermute crosses processes — and a
    # fori_loop stencil; verification is by collective checksum (a global
    # array is not fully addressable from one controller)
    import numpy as np

    v = np.arange(float(n))
    src = rt.arange(n, dtype=float)
    out = rt.zeros(n)
    rt.sync()

    def sweep(s_, d_):
        h = s_.halo(1)
        d_.set_local(h[:-2] + h[1:-1] + h[2:])

    rt.spmd(sweep, src, out)
    exp = np.zeros(n)
    exp[1:-1] = v[:-2] + v[1:-1] + v[2:]
    exp[0] = v[0] + v[1]
    exp[-1] = v[-2] + v[-1]
    got = float(rt.sum(out * out))
    want = float((exp * exp).sum())
    # f32 regime: the checksum accumulates 4096 terms of ~1e8
    assert abs(got - want) <= 1e-5 * max(1.0, abs(want)), (got, want)

    @rt.stencil
    def avg3(a):
        return (a[-1] + a[0] + a[1]) / 3.0

    it = rt.sstencil_iterate(avg3, src, 3)
    e = v.copy()
    for _ in range(3):
        nxt = np.zeros_like(e)
        nxt[1:-1] = (e[:-2] + e[1:-1] + e[2:]) / 3.0
        e = nxt
    got = float(rt.sum(it))
    want = float(e.sum())
    assert abs(got - want) <= 1e-5 * max(1.0, abs(want)), (got, want)

    # driver gating (reference: in_driver() in MPI SPMD mode)
    if distributed.in_driver():
        assert rank == 0
        print("DRIVER_OK", flush=True)
    else:
        assert rank == 1
    print(f"WORKER_{rank}_OK", flush=True)
    distributed.shutdown()


def launch() -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        port = s.getsockname()[1]
    import tempfile

    env = dict(os.environ)
    env["PYTHONPATH"] = REPO
    env["JAX_PLATFORMS"] = "cpu"  # CPU-only harness (workers pin it too)
    env["RAMBA_TPU_SMOKE_RTD"] = os.path.join(
        tempfile.mkdtemp(prefix="rtd_smoke_"), "arr.rtd"
    )
    procs = [
        subprocess.Popen(
            [sys.executable, "-u", os.path.abspath(__file__),
             "WORKER", str(rank), str(port)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True,
        )
        for rank in range(2)
    ]
    ok = True
    for rank, p in enumerate(procs):
        try:
            out, _ = p.communicate(timeout=240)
        except subprocess.TimeoutExpired:
            p.kill()
            out = f"rank {rank}: TIMEOUT"
        if p.returncode != 0 or f"WORKER_{rank}_OK" not in (out or ""):
            ok = False
            print(f"--- rank {rank} rc={p.returncode} ---\n{out}",
                  file=sys.stderr)
    if ok:
        print("two-process smoke: OK")
    return 0 if ok else 1


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "WORKER":
        worker(int(sys.argv[2]), int(sys.argv[3]))
    else:
        sys.exit(launch())
