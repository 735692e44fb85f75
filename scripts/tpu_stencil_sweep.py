"""Pallas stencil block-height sweep on the chip (ROADMAP S4).

PRK star stencil r=2 at 8192^2 f32, one configuration per FRESH process
(the structure-keyed compile cache and leftover HBM buffers make
in-process config toggling invalid), run in sequence by this parent, which
never imports jax: a chip belongs to one process at a time.  Sweeps
RAMBA_TPU_STENCIL_BH x {auto, 64, 128, 256, 512} plus the XLA
shifted-slice path (RAMBA_TPU_PALLAS=0) and a bf16-input variant (half the
HBM traffic).  Each worker refuses to run off the TPU and checks, from the
flush span, that the stencil took the path its configuration names.

Run it through the chip tool, one call:

    python scripts/tpu_stencil_sweep.py

Prints one JSON object, also written to chiprun_out/stencil_sweep.json;
exits non-zero if any configuration failed.  Times are host-clock walls of
a 30-sweep chain ending in a scalar fetch, on the device the JSON names.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_WORKER_SRC = r"""
import json, os, sys, time

sys.path.insert(0, os.environ["RAMBA_SWEEP_REPO"])
import jax
import numpy as np

dev = jax.devices()[0]
if dev.platform != "tpu":
    sys.exit(f"stencil sweep: no TPU (first device is {dev.platform})")
import ramba_tpu as rt

dtype = os.environ.get("RAMBA_SWEEP_DTYPE", "float32")
want_path = os.environ["RAMBA_SWEEP_PATH"]

@rt.stencil
def star2(a):
    return (0.25 * (a[0, 1] + a[0, -1] + a[1, 0] + a[-1, 0])
            + 0.125 * (a[0, 2] + a[0, -2] + a[2, 0] + a[-2, 0]))

sn = 8192
x = rt.fromarray(np.random.RandomState(0).rand(sn, sn).astype(dtype))
rt.sync()
sk = 30

def chain():
    y = x
    for _ in range(sk):
        y = rt.sstencil(star2, y)
    s = rt.sum(y)
    t0 = time.perf_counter()
    float(s)
    return time.perf_counter() - t0

chain()  # trace + compile
span = rt.diagnostics.last_flushes(1)[0]
paths = sorted({k["path"] for k in span.get("kernels", ())})
assert paths == [want_path], (paths, want_path)
assert "degraded" not in span and not rt.diagnostics.resilience_events()
walls = sorted(chain() / sk for _ in range(5))
wall = walls[len(walls) // 2]
print(json.dumps({
    "per_iter_ms_median": wall * 1e3, "samples": len(walls),
    "per_iter_ms_all": [w * 1e3 for w in walls],
    "mflops": 13 * (sn - 4) * (sn - 4) / wall / 1e6,
    "gb_per_s": 2 * sn * sn * np.dtype(dtype).itemsize / wall / 1e9,
    "path": paths[0], "device_kind": dev.device_kind,
    "device_count": len(jax.devices()),
}), flush=True)
"""

CONFIGS = [
    ("bh_auto", "pallas_fast", {}),
    ("bh_64", "pallas_fast", {"RAMBA_TPU_STENCIL_BH": "64"}),
    ("bh_128", "pallas_fast", {"RAMBA_TPU_STENCIL_BH": "128"}),
    ("bh_256", "pallas_fast", {"RAMBA_TPU_STENCIL_BH": "256"}),
    ("bh_512", "pallas_fast", {"RAMBA_TPU_STENCIL_BH": "512"}),
    ("xla_path", "xla", {"RAMBA_TPU_PALLAS": "0"}),
    ("bf16_auto", "pallas_fast", {"RAMBA_SWEEP_DTYPE": "bfloat16"}),
]


def _run(path, env_extra, timeout_s):
    env = dict(os.environ, RAMBA_SWEEP_REPO=REPO, RAMBA_SWEEP_PATH=path,
               **env_extra)
    try:
        r = subprocess.run([sys.executable, "-c", _WORKER_SRC],
                           capture_output=True, text=True,
                           timeout=timeout_s, env=env)
    except subprocess.TimeoutExpired:
        return {"error": f"timed out after {timeout_s:.0f}s"}
    if r.returncode == 0:
        return json.loads(r.stdout.strip().splitlines()[-1])
    # the line that names the exception, and what follows it
    lines = ((r.stderr or "") + (r.stdout or "")).strip().splitlines()
    named = [i for i, ln in enumerate(lines) if "Error" in ln.split(":")[0]]
    tail = lines[named[-1]:] if named else lines[-3:]
    return {"error": f"rc={r.returncode} " + " | ".join(tail)[:1200]}


def main() -> int:
    per_cfg = float(os.environ.get("RAMBA_SWEEP_CFG_TIMEOUT", "600"))
    out = {"configs": {},
           "recorded_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())}
    for name, path, env in CONFIGS:
        out["configs"][name] = _run(path, env, per_cfg)
        print(f"{name}: {out['configs'][name]}", file=sys.stderr, flush=True)
    scored = {k: v["mflops"] for k, v in out["configs"].items()
              if "mflops" in v and not k.startswith("bf16")}
    if scored:
        best = max(scored, key=scored.get)
        out["best"] = {"config": best, "mflops": scored[best]}
    failed = sorted(k for k, v in out["configs"].items() if "error" in v)
    out["failed"] = failed
    os.makedirs(os.path.join(REPO, "chiprun_out"), exist_ok=True)
    with open(os.path.join(REPO, "chiprun_out", "stencil_sweep.json"),
              "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
