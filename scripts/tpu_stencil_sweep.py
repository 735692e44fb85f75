"""Pallas stencil block-height sweep on the chip (ROADMAP S4).

PRK star stencil r=2, one FRESH process per configuration (a shape, a
dtype and the path it has to take), run in sequence by this parent, which
never imports jax: a chip belongs to one process at a time.  Inside a
process every candidate height is its own jitted chain of sweeps, the
kernel called directly (``stencil_pallas.run``) with the candidate handed
over through the kernel's private keyword; ``None`` is the height the
kernel derives, which is what ships.  The configurations:

    8192^2  f32, bf16   pallas_fast    derived, 16, 32
    8192^2  f32         xla            the shifted-slice path
    15000^2, 13500^2  f32  pallas_padded, halo "edge"    derived, 32, 64
    15000^2, 13500^2  f32  pallas_padded, halo "strips"  derived, 32, 64

    514^3, 258^3  f32   pallas_padded, rank 3   derived, planes x rows
    130^3         f32   pallas_padded and xla   the threshold's two readings
    514^3, 258^3  f32   xla                     what the kernel replaces
    514^3, 258^3  f32   pallas_padded, b - A x  the update in the kernel's
                                                store, and after it in XLA

(the padded shapes are what the benchmark's star cells hand the kernel per
chip: the array's own edge on one chip, the block and its four received
strips on four; the cubes are levels 9, 8 and 7 of ``mg-C``'s pyramid,
swept by NPB MG's 27-point operator ``op``: A, 21 taps, or P, 27).  A
rank-3 candidate is ``[block_planes, rows]``, ``null`` for the derived
one; a configuration's ``set`` tries another value of one of the kernel's
own constants (``_CHUNK_VREGS``); its ``update`` sweeps ``b - A x`` for a
second operand ``b``: ``subtract``, the kernel's own store writing it
(the epilogue, its ``block_planes`` sized with ``b``'s block), or
``unfused``, the kernel's result and then XLA's subtraction, which
``device_ms`` counts.  Each worker refuses to run off the TPU, checks from the
kernel's own note that the stencil took the path and the halo its
configuration names, at the height asked for, and compares one sweep with
``skeletons.stencil_interior`` (``equal``: to the bit).  ``to_beat_ms`` is
what the kernel that padded its operand cost a sweep at that shape, the
copies that fed it included (ledger, PR 28): the table's line to beat.

Run it through the chip tool, one call:

    python scripts/tpu_stencil_sweep.py

Prints one JSON object, also written to chiprun_out/stencil_sweep.json;
every candidate's row is appended to chiprun_out/stencil_sweep_rows.jsonl
as it is measured, so a call that is cut keeps what it read; exits
non-zero if any candidate failed.  ``kernel_ms`` is the device time
of the Pallas custom calls of one sweep, from a profiler trace of one
chain; ``device_ms`` that of every op of the sweep (the tails XLA builds
for the padded path with it); ``wall_ms`` the host's clock over the
chain, median of 5; all on the device the JSON names.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_WORKER_SRC = r"""
import glob, json, os, sys, tempfile, time

cfg = json.loads(sys.argv[1])
sys.path.insert(0, cfg["repo"])
import jax
import jax.numpy as jnp
import numpy as np

dev = jax.devices()[0]
if dev.platform != "tpu":
    sys.exit(f"stencil sweep: no TPU (first device is {dev.platform})")
import ramba_tpu as rt
from ramba_tpu import skeletons
from ramba_tpu.observe import registry
from ramba_tpu.ops import stencil_pallas

@rt.stencil
def star2(a):
    return (0.25 * (a[0, 1] + a[0, -1] + a[1, 0] + a[-1, 0])
            + 0.125 * (a[0, 2] + a[0, -2] + a[2, 0] + a[-2, 0]))

sn, dtype, want_path, sk = cfg["n"], cfg["dtype"], cfg["path"], 10
rank = cfg.get("rank", 2)
for name, value in cfg.get("set", {}).items():
    # a candidate for one of the kernel's own constants
    assert hasattr(stencil_pallas, name), name
    setattr(stencil_pallas, name, value)
if rank == 3:
    import itertools
    w = {"A": (-8 / 3, 0.0, 1 / 6, 1 / 12),
         "P": (0.5, 0.25, 0.125, 0.0625)}[cfg["op"]]

    @rt.stencil
    def star2(a):  # NPB MG's 27-point operator, zero weights left out
        acc = None
        for d in itertools.product((-1, 0, 1), repeat=3):
            c = w[sum(abs(v) for v in d)]
            if c:
                term = c * a[d]
                acc = term if acc is None else acc + term
        return acc

slots = (("arr", 0),)
lo, hi, taps = star2.neighborhood(slots)
rs = np.random.RandomState(0)
x = jnp.asarray(rs.rand(*(sn,) * rank), dtype=dtype)
# b - A x: "subtract" in the kernel's store, "unfused" after it in XLA
update = cfg.get("update")
base = jnp.asarray(rs.rand(*(sn,) * rank), dtype=dtype) if update else None
halos = None
if cfg.get("halo") == "strips":
    # what four chips hand the kernel: the block and its received strips
    (t, l), (b, r) = (-lo[0], -lo[1]), hi
    halos = [tuple(jnp.asarray(rs.rand(*shp), dtype=dtype) for shp in
                   ((sn, l), (sn, r), (t, l + sn + r), (b, l + sn + r)))]

def reference(y, b=None):
    v = skeletons.stencil_interior
    if halos is None:
        inner = tuple(slice(-l, y.shape[d] - h)
                      for d, (l, h) in enumerate(zip(lo, hi)))
        s = jnp.zeros_like(y).at[inner].set(
            v(star2.func, lo, hi, slots, [y]))
        return s if b is None else b - s
    w, e, n, s = halos[0]
    ext = jnp.concatenate(
        [n, jnp.concatenate([w, y, e], axis=1), s], axis=0)
    return v(star2.func, lo, hi, slots, [ext])

def device_ns(tdir):
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(tdir, "plugins/profile/*/*.xplane.pb"))
    every = kernel = 0
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:TPU:0"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for e in line.events:
                        every += e.duration_ns
                        if "custom-call(" in e.name:
                            kernel += e.duration_ns
    return every, kernel

def sweep(y, b, rows):
    if want_path == "xla":
        return reference(y, b)
    planes = None
    if rank == 3 and rows is not None:
        planes, rows = rows
    fused = update == "subtract"
    s = stencil_pallas.run(star2.func, lo, hi, slots, [y], taps,
                           halos=halos, epilogue=("subtract", 0) if fused
                           else None, base=b if fused else None,
                           _block_rows=rows, _block_planes=planes)
    return b - s if update == "unfused" else s

for rows in cfg["rows"]:
    row = {"config": cfg["name"], "block_rows_asked": rows}
    try:
        def chain(y, b, rows=rows):
            for _ in range(sk):
                y = sweep(y, b, rows)
            return y
        t0 = time.perf_counter()
        with registry.collect_kernel_notes() as notes:
            run = jax.jit(chain).lower(x, base).compile()
        row["compile_s"] = time.perf_counter() - t0
        if want_path != "xla":
            assert {n["path"] for n in notes} == {want_path}, notes
            assert not any(n["interpret"] for n in notes), notes
            row.update({k: notes[0][k] for k in
                        ("block_planes", "block_rows", "grid",
                         "vmem_limit_bytes", "halo", "operand_copy",
                         "epilogue") if k in notes[0]})
            asked = rows if rank == 2 or rows is None else rows[1]
            assert asked is None or row.get("block_rows", asked) == asked, row
            assert row.get("halo") == cfg.get("halo"), row
            assert row.get("epilogue", "none") == (
                "subtract" if update == "subtract" else "none"), row
            got = jax.jit(lambda y, b, rows=rows: sweep(y, b, rows))(x, base)
            diff = jnp.abs(got.astype(jnp.float32)
                           - jax.jit(reference)(x, base).astype(jnp.float32))
            row["max_abs_diff"] = float(jnp.max(diff))
            row["equal"] = row["max_abs_diff"] == 0.0
            del got, diff
            assert row["max_abs_diff"] < 1e-5 * (taps if rank == 3 else 1), row
        jax.block_until_ready(run(x, base))
        walls = []
        for _ in range(5):
            t0 = time.perf_counter()
            jax.block_until_ready(run(x, base))
            walls.append((time.perf_counter() - t0) / sk * 1e3)
        with tempfile.TemporaryDirectory() as tdir:
            with jax.profiler.trace(tdir):
                jax.block_until_ready(run(x, base))
            every, kernel = device_ns(tdir)
        itemsize = np.dtype(dtype).itemsize
        per = (kernel or every) / sk / 1e9
        row.update({
            "wall_ms": sorted(walls)[2], "device_ms": every / sk / 1e6,
            "kernel_ms": kernel / sk / 1e6,
            "gpoints_per_s": sn ** rank / per / 1e9,
            # the kernel's passes: with the update in its store, three
            "gb_per_s": (3 if update == "subtract" else 2) * sn ** rank
            * itemsize / per / 1e9,
        })
    except Exception as e:
        lines = f"{type(e).__name__}: {e}".splitlines()
        row["error"] = " | ".join(lines)[:600]
    with open(cfg["rows_file"], "a") as f:
        f.write(json.dumps(row) + "\n")
    print(json.dumps(row), flush=True)
print(json.dumps({"device_kind": dev.device_kind,
                  "device_count": len(jax.devices())}), flush=True)
"""

_PADDED_ROWS = [None, 32, 64]
#: rank 3: [block_planes, rows staged at once]; None is what ships
_CUBE_BLOCKS = [None, [10, 64], [8, 128], [7, 176], [6, 256], [5, 264]]
_CUBE_BLOCKS_8 = [None, [16, 64], [16, 136], [12, 264]]
CONFIGS = [
    ("fast_8192", {"n": 8192, "dtype": "float32", "path": "pallas_fast",
                   "rows": [None, 16, 32]}),
    ("fast_8192_bf16", {"n": 8192, "dtype": "bfloat16",
                        "path": "pallas_fast", "rows": [None]}),
    ("xla_8192", {"n": 8192, "dtype": "float32", "path": "xla",
                  "rows": [None]}),
    ("padded_15000", {"n": 15000, "dtype": "float32", "halo": "edge",
                      "path": "pallas_padded", "rows": _PADDED_ROWS,
                      "to_beat_ms": {"star2: kernel 3.828 + pad 2.969":
                                     6.797}}),
    ("padded_13500", {"n": 13500, "dtype": "float32", "halo": "edge",
                      "path": "pallas_padded", "rows": _PADDED_ROWS}),
    ("strips_15000", {
        "n": 15000, "dtype": "float32", "halo": "strips",
        "path": "pallas_padded", "rows": _PADDED_ROWS,
        "to_beat_ms": {"star2-30000-x4: kernel 3.829 + pad 2.971 + "
                       "concatenate 6.352": 13.152}}),
    ("strips_13500", {
        "n": 13500, "dtype": "float32", "halo": "strips",
        "path": "pallas_padded", "rows": _PADDED_ROWS,
        "to_beat_ms": {"star2-x4: kernel 3.095 + pad 2.400 + "
                       "concatenate 5.056": 10.551}}),
    # mg-C's levels 9 and 8, which the rank-3 kernel takes
    ("cube_514_A", {"n": 514, "rank": 3, "op": "A", "dtype": "float32",
                    "halo": "edge", "path": "pallas_padded",
                    "rows": _CUBE_BLOCKS,
                    "to_beat_ms": {"mg-C: the XLA stencil alone, PR 32":
                                   22.2}}),
    ("cube_514_A_v10", {"n": 514, "rank": 3, "op": "A", "dtype": "float32",
                       "halo": "edge", "path": "pallas_padded",
                       "set": {"_CHUNK_VREGS": 10},
                       "rows": [[10, 64], [8, 128]]}),
    ("cube_514_A_v40", {"n": 514, "rank": 3, "op": "A", "dtype": "float32",
                       "halo": "edge", "path": "pallas_padded",
                       "set": {"_CHUNK_VREGS": 40},
                       "rows": [[7, 176], [6, 256]]}),
    ("cube_514_P", {"n": 514, "rank": 3, "op": "P", "dtype": "float32",
                    "halo": "edge", "path": "pallas_padded",
                    "rows": [None]}),
    ("cube_258_A", {"n": 258, "rank": 3, "op": "A", "dtype": "float32",
                    "halo": "edge", "path": "pallas_padded",
                    "rows": _CUBE_BLOCKS_8}),
    # level 7, the threshold's two readings, and the path the kernel
    # replaces at the levels above it
    ("cube_130_A", {"n": 130, "rank": 3, "op": "A", "dtype": "float32",
                    "halo": "edge", "path": "pallas_padded",
                    "rows": [None, [8, 64]]}),
    ("cube_130_A_xla", {"n": 130, "rank": 3, "op": "A", "dtype": "float32",
                        "path": "xla", "rows": [None]}),
    ("cube_258_A_xla", {"n": 258, "rank": 3, "op": "A", "dtype": "float32",
                        "path": "xla", "rows": [None]}),
    ("cube_514_A_xla", {"n": 514, "rank": 3, "op": "A", "dtype": "float32",
                        "path": "xla", "rows": [None]}),
    # b - A x (mg-C's resid): the update in the kernel's store, at the
    # derived block and a plane fewer, against the kernel and XLA's pass
    ("cube_514_A_sub", {"n": 514, "rank": 3, "op": "A", "dtype": "float32",
                        "halo": "edge", "path": "pallas_padded",
                        "update": "subtract", "rows": [None, [3, 264]]}),
    ("cube_514_A_unfused", {"n": 514, "rank": 3, "op": "A",
                            "dtype": "float32", "halo": "edge",
                            "path": "pallas_padded", "update": "unfused",
                            "rows": [None, [4, 264]]}),
    ("cube_258_A_sub", {"n": 258, "rank": 3, "op": "A", "dtype": "float32",
                        "halo": "edge", "path": "pallas_padded",
                        "update": "subtract", "rows": [None, [11, 264]]}),
    ("cube_258_A_unfused", {"n": 258, "rank": 3, "op": "A",
                            "dtype": "float32", "halo": "edge",
                            "path": "pallas_padded", "update": "unfused",
                            "rows": [None, [13, 264]]}),
]
ROWS_FILE = os.path.join(REPO, "chiprun_out", "stencil_sweep_rows.jsonl")


def _run(name, cfg, timeout_s):
    arg = json.dumps(dict(cfg, repo=REPO, name=name, rows_file=ROWS_FILE))
    try:
        r = subprocess.run([sys.executable, "-c", _WORKER_SRC, arg],
                           capture_output=True, text=True, timeout=timeout_s)
    except subprocess.TimeoutExpired:
        return {"error": f"timed out after {timeout_s:.0f}s"}
    rows = [json.loads(ln) for ln in r.stdout.splitlines()
            if ln.startswith("{")]
    if r.returncode == 0 and rows:
        return dict(rows[-1], candidates=rows[:-1])
    # the line that names the exception, and what follows it
    lines = ((r.stderr or "") + (r.stdout or "")).strip().splitlines()
    named = [i for i, ln in enumerate(lines) if "Error" in ln.split(":")[0]]
    tail = lines[named[-1]:] if named else lines[-3:]
    return {"error": f"rc={r.returncode} " + " | ".join(tail)[:1200],
            "candidates": rows}


def main(argv) -> int:
    """``argv``: names of configurations to run (default: all)."""
    per_cfg = float(os.environ.get("RAMBA_SWEEP_CFG_TIMEOUT", "900"))
    unknown = set(argv) - {name for name, _ in CONFIGS}
    if unknown:
        sys.exit(f"stencil sweep: no configuration named {sorted(unknown)}")
    out = {"configs": {},
           "recorded_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())}
    os.makedirs(os.path.dirname(ROWS_FILE), exist_ok=True)
    for name, cfg in CONFIGS:
        if argv and name not in argv:
            continue
        got = out["configs"][name] = dict(_run(name, cfg, per_cfg), **cfg)
        print(f"{name}: {got}", file=sys.stderr, flush=True)
    out["failed"] = sorted(
        name for name, got in out["configs"].items()
        if "error" in got or any("error" in c for c in got["candidates"]))
    with open(os.path.join(REPO, "chiprun_out", "stencil_sweep.json"),
              "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))
    print(f"{'configuration':<16}{'planes':>7}{'rows':>6}{'kernel_ms':>11}"
          f"{'device_ms':>11}{'equal':>7}  to beat (ledger, PRs 28, 32)",
          file=sys.stderr)
    for name, got in out["configs"].items():
        beat = "; ".join(f"{v} ms ({k})"
                         for k, v in got.get("to_beat_ms", {}).items())
        for c in got.get("candidates", ()):
            print(f"{name:<16}{c.get('block_planes', '-'):>7}"
                  f"{c.get('block_rows', '-'):>6}"
                  f"{c.get('kernel_ms', float('nan')):>11.3f}"
                  f"{c.get('device_ms', float('nan')):>11.3f}"
                  f"{str(c.get('equal', '-')):>7}  {beat}", file=sys.stderr)
    return 1 if out["failed"] else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
