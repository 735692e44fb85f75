"""The sorted chunked walk's three constants on the chip (``groupby._UNROLL``,
``_GATHER_SLAB``, ``_CHUNK_BYTES``): how many rows a chunk should fetch,
and for which slabs one gather beats that many dynamic slices.

One process, one chip.  Every candidate is ``segment_reduce`` jitted
alone over a resident operand (rank three ones made row-major, as
``core/layouts.py`` keeps a flush's results), with the constants set for
that candidate: K rows a chunk by slices (``_UNROLL`` = K, no slab
gathered) or by one gather (``_UNROLL`` = 0, every slab gathered,
``_CHUNK_BYTES`` = K slabs).  ``ships`` marks the candidate the constants in the tree choose.  The
operands:

    slab-4MB    (1464, 721, 1440) f32, 92 groups of 16   doy-clim's slabs
    slab-1.5MB  (2048, 500, 750) f32, 64 groups of 32    ragged tiles
    slab-1MB    (4096, 256, 1024) f32, 64 groups of 64   whole tiles
    slab-750KB  (4096, 375, 500) f32, 64 groups of 64    ragged tiles
    slab-500KB  (8192, 250, 500) f32, 64 groups of 128   ragged tiles
    slab-400KB  (8192, 100, 1000) f32, 64 groups of 128  ragged tiles
    slab-36KB   (32768, 30, 300) f32, 64 groups of 512   ragged tiles
    slab-64KB   (65536, 128, 128) f32, 64 groups of 1024 whole tiles
    row-32B     (1000000, 8) f32, 12 groups of 83334     category codes
    row-4B      (4000000,) f32, 12 groups

Run it through the chip tool, one call:

    python scripts/tpu_segment_sweep.py [operand ...]

Prints one JSON object, also written to chiprun_out/segment_sweep.json,
and a table on stderr; exits non-zero if a candidate failed or its group
0 (every group of the two narrow operands) is off NumPy's float64 by more
than 1e-4 of it (float32 sums of up to 333,334 positive members).  ``ms``
is the host's clock over one call and ``block_until_ready``, median of
3 after a warm-up, on the device the JSON names: the passes are tens of
milliseconds to seconds, device-bound; ``gbps`` the operand's bytes over
it.
"""

from __future__ import annotations

import json
import os
import statistics
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

MIB = 1 << 20
#: name, shape, groups, kind, candidates (K or None for "a group's rows",
#: "slices" | "gather")
OPERANDS = [
    ("slab-4MB", (1464, 721, 1440), 92, "mean",
     [(1, "slices"), (2, "slices"), (4, "slices"), (8, "slices"),
      (16, "slices"), (8, "gather"), (16, "gather")]),
    ("slab-1.5MB", (2048, 500, 750), 64, "sum",
     [(16, "slices"), (32, "slices"), (22, "gather"), (32, "gather")]),
    ("slab-1MB", (4096, 256, 1024), 64, "sum",
     [(16, "slices"), (32, "slices"), (32, "gather"), (64, "gather")]),
    ("slab-750KB", (4096, 375, 500), 64, "sum",
     [(16, "slices"), (44, "gather")]),
    ("slab-500KB", (8192, 250, 500), 64, "sum",
     [(16, "slices"), (32, "slices"), (67, "gather")]),
    ("slab-400KB", (8192, 100, 1000), 64, "sum",
     [(8, "slices"), (16, "slices"), (32, "slices"), (16, "gather"),
      (80, "gather")]),
    ("slab-36KB", (32768, 30, 300), 64, "sum",
     [(16, "slices"), (32, "slices"), (16, "gather"), (512, "gather")]),
    ("slab-64KB", (65536, 128, 128), 64, "sum",
     [(8, "slices"), (16, "slices"), (32, "slices"), (64, "slices"),
      (16, "gather"), (64, "gather"), (512, "gather"), (1024, "gather")]),
    ("row-32B", (1000000, 8), 12, "sum",
     [(16, "slices"), (1024, "gather"), (32768, "gather"),
      (None, "gather")]),
    ("row-4B", (4000000,), 12, "sum",
     [(16, "slices"), (262144, "gather"), (None, "gather")]),
]


def main(argv) -> int:
    """``argv``: names of operands to run (default: all)."""
    unknown = set(argv) - {name for name, *_ in OPERANDS}
    if unknown:
        sys.exit(f"segment sweep: no operand named {sorted(unknown)}")
    import jax
    import jax.numpy as jnp
    import numpy as np

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"segment sweep: no TPU (first device is {dev.platform})")
    from ramba_tpu import groupby
    from ramba_tpu.core import layouts
    from ramba_tpu.core.expr import OPS

    shipped = groupby._UNROLL, groupby._GATHER_SLAB, groupby._CHUNK_BYTES
    out = {"device": dev.device_kind, "unroll": shipped[0],
           "gather_slab": shipped[1], "chunk_bytes": shipped[2],
           "operands": {}, "failed": [],
           "recorded_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())}
    for name, shape, groups, kind, candidates in OPERANDS:
        if argv and name not in argv:
            continue
        n, slab = shape[0], int(np.prod(shape[1:], dtype=np.int64)) * 4

        def make():
            v = jnp.full(shape, 4.0, jnp.float32)  # positive: sums add up
            for d, m in enumerate(shape):
                v = v + jnp.sin(jnp.arange(m, dtype=jnp.float32) * (d + 1.3)
                                ).reshape([m if i == d else 1
                                           for i in range(len(shape))])
            return (v,)

        (x,) = layouts.RowMajorJit(make)()
        labels = np.random.default_rng(7).permutation(
            np.arange(n) % groups).astype(np.int32)
        lab = jnp.asarray(labels)
        ships_k = groupby._chunk_rows(n, groups, slab)
        ships = (ships_k, "slices" if ships_k <= shipped[0] else "gather")
        check = range(groups) if len(shape) < 3 else (0,)
        members = [np.asarray(x[np.flatnonzero(labels == g)], np.float64)
                   for g in check]
        want = np.stack([m.mean(0) if kind == "mean" else m.sum(0)
                         for m in members])
        del members
        rows = []
        for k, fetch in candidates:
            per_group = -(-n // groups)
            row = {"rows": k or per_group, "fetch": fetch}
            row["ships"] = (row["rows"], fetch) == ships
            if fetch == "slices":
                groupby._UNROLL, groupby._GATHER_SLAB = row["rows"], 0
            else:
                groupby._UNROLL, groupby._GATHER_SLAB = 0, slab
                groupby._CHUNK_BYTES = row["rows"] * slab
            try:
                fn = jax.jit(lambda a, b: OPS["segment_reduce"](
                    (kind, groups, 0), a, b))
                got = jax.block_until_ready(fn(x, lab))
                times = []
                for _ in range(3):
                    t0 = time.perf_counter()
                    jax.block_until_ready(fn(x, lab))
                    times.append(time.perf_counter() - t0)
                row["ms"] = 1e3 * statistics.median(times)
                row["gbps"] = x.nbytes / statistics.median(times) / 1e9
                row["agrees"] = bool(np.allclose(
                    np.asarray(got[:len(want)]), want, rtol=1e-4, atol=0))
                del got
            except Exception as e:  # noqa: BLE001 - a candidate, not the sweep
                row["error"] = f"{type(e).__name__}: {e}"[:300]
            finally:
                (groupby._UNROLL, groupby._GATHER_SLAB,
                 groupby._CHUNK_BYTES) = shipped
            if "error" in row or not row["agrees"]:
                out["failed"].append(f"{name} {row['rows']} {fetch}")
            print(f"{name}: {row}", file=sys.stderr, flush=True)
            rows.append(row)
        out["operands"][name] = {"shape": shape, "groups": groups,
                                 "kind": kind, "layout": str(x.format.layout),
                                 "candidates": rows}
        del x
    os.makedirs(os.path.join(REPO, "chiprun_out"), exist_ok=True)
    with open(os.path.join(REPO, "chiprun_out", "segment_sweep.json"),
              "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))
    print(f"{'operand':<11}{'rows':>8}{'fetch':>8}{'ms':>11}{'GB/s':>8}"
          f"{'agrees':>8}  ships", file=sys.stderr)
    for name, got in out["operands"].items():
        for c in got["candidates"]:
            print(f"{name:<11}{c['rows']:>8}{c['fetch']:>8}"
                  f"{c.get('ms', float('nan')):>11.3f}"
                  f"{c.get('gbps', float('nan')):>8.1f}"
                  f"{str(c.get('agrees', '-')):>8}  "
                  f"{'<-' if c['ships'] else ''}", file=sys.stderr)
    return 1 if out["failed"] else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
