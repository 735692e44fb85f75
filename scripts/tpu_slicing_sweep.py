"""``core/slicing.py``'s constants on the chip: from what window a stride
on the lane axis is worth a selection product on the MXU
(``MXU_MIN_ELEMENTS``), up to what step (``MXU_MAX_STEP``), and in what
tiles a long row is cut (``LANE_WHOLE``, ``LANE_TILE``).

One process, one chip.  Every candidate is ``slicing.take`` or
``slicing.put`` jitted alone over a resident operand of random BITS (every
pattern, NaNs and infinities among them), with the constants set for that
candidate: ``xla`` is what the module does off the MXU (a read: ``x[idx]``,
a gather of the lane axis; a write: ``lax.pad`` over at most
``PAD_MAX_EXTENT`` elements, jax's scatter over more), ``whole`` one
product over the whole lane axis, ``tile<T>`` tiles of T.  ``ships`` marks
what the constants in the tree choose.  A product's result is held against
NumPy's, byte for byte.

    python -u scripts/tpu_slicing_sweep.py

Prints one JSON object, and a table on stderr row by row; the rows so far
are in chiprun_out/slicing_sweep.json after every candidate, so a call
that is cut still brings them back (PR 32's one call was cut at its
budget in a candidate whose COMPILE takes 2,498 s, the ``lax.pad`` write
of the long row that ``PAD_MAX_EXTENT`` now keeps off; its rows stood in
a pipe's buffer and were lost: NOT YET RUN TO ITS END).  Exits non-zero if
a candidate failed or a product is off NumPy by a bit.  ``ms`` is the host's clock over one call and
``block_until_ready``, median of 5 after a warm-up (of 50 for the small
cubes, whose calls are microseconds), on the device the JSON names.
"""

from __future__ import annotations

import json
import os
import statistics
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

S = slice
#: the whole-axis product is asked only where its matrix is small
WHOLE_MAX_LANES = 2100
#: name, shape, index, candidates
READS = [
    ("row-2^20 ::2", (64, 1 << 20), (S(None), S(None, None, 2)),
     ["xla", "tile128", "tile256", "tile512"]),
    ("mg 257x257x514 ::2", (257, 257, 514),
     (S(None), S(None), S(None, None, 2)),
     ["xla", "whole", "tile128", "tile256"]),
    ("row-2050 ::2", (8192, 2050), (S(None), S(None, None, 2)),
     ["xla", "whole", "tile128", "tile256", "tile512"]),
] + [
    (f"8192^2 ::{st}", (8192, 8192), (S(None), S(None, None, st)),
     ["xla", "tile256"]) for st in (2, 3, 4, 8, 16, 32, 64)
] + [
    (f"cube-{m} ::2", (m + 2,) * 3, (S(None), S(None), S(None, None, 2)),
     ["xla", "whole"]) for m in (8, 16, 32, 64, 128)
]
WRITES = [
    ("row-2^20 ::2", (64, 1 << 20), (S(None), S(None, None, 2)),
     ["xla", "tile128", "tile256", "tile512"]),
    ("mg 257x257x514 ::2", (257, 257, 514),
     (S(None), S(None), S(None, None, 2)),
     ["xla", "whole", "tile256"]),
    ("8192^2 ::8", (8192, 8192), (S(None), S(None, None, 8)),
     ["xla", "tile256"]),
]


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ramba_tpu.core import slicing

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"no TPU: {dev.platform}", file=sys.stderr)
        return 1
    shipped = {k: getattr(slicing, k) for k in
               ("MXU_MIN_ELEMENTS", "MXU_MAX_STEP", "LANE_WHOLE", "LANE_TILE",
                "PAD_MAX_EXTENT")}

    def configure(cand):
        """The module's constants for one candidate."""
        slicing.MXU_MIN_ELEMENTS = 1
        slicing.MXU_MAX_STEP = 1 << 30
        if cand == "xla":
            slicing.MXU_MAX_STEP = 1
        elif cand == "whole":
            slicing.LANE_WHOLE = 1 << 30
        else:
            slicing.LANE_WHOLE = slicing.LANE_TILE = int(cand[4:])

    def restore():
        for k, v in shipped.items():
            setattr(slicing, k, v)

    def what_ships(x, idx):
        axes = slicing._axes(idx, x.shape)
        if not slicing._lanes_through_mxu(x, axes):
            return "xla"
        thin = axes[-1][1]
        return ("whole" if thin <= slicing.LANE_WHOLE
                else f"tile{slicing.LANE_TILE}")

    def bits(shape, seed):
        raw = jax.random.bits(jax.random.key(seed), shape, jnp.uint32)
        return jax.lax.bitcast_convert_type(raw, jnp.float32)

    def timed(fn, args, reps):
        jax.block_until_ready(fn(*args))
        out = []
        for _ in range(5):
            t0 = time.perf_counter()
            for _ in range(reps):
                r = fn(*args)
            jax.block_until_ready(r)
            out.append(1e3 * (time.perf_counter() - t0) / reps)
        return statistics.median(out), r

    rows, bad = [], 0
    out = {"device": dev.device_kind, "shipped": shipped, "rows": rows}
    os.makedirs(os.path.join(REPO, "chiprun_out"), exist_ok=True)

    def keep():
        with open(os.path.join(REPO, "chiprun_out", "slicing_sweep.json"),
                  "w") as f:
            json.dump(out, f, indent=1)

    for kind, table in (("read", READS), ("write", WRITES)):
        for name, shape, idx, cands in table:
            x = bits(shape, 1)
            want = np.asarray(x).view(np.uint32)
            reps = 50 if np.prod(shape) < 1 << 22 else 1
            if kind == "read":
                want, args = want[idx], (x,)
            else:
                v = bits(want[idx].shape, 2)
                want = want.copy()
                want[idx] = np.asarray(v).view(np.uint32)
                args = (x, v)
            ships = what_ships(x, idx)
            for cand in cands:
                if cand == "whole" and shape[-1] > WHOLE_MAX_LANES:
                    continue
                row = {"kind": kind, "operand": name, "shape": list(shape),
                       "candidate": cand, "ships": cand == ships}
                configure(cand)
                try:
                    fn = jax.jit((lambda a: slicing.take(a, idx))
                                 if kind == "read" else
                                 (lambda a, b: slicing.put(a, idx, b)))
                    ms, got = timed(fn, args, reps)
                    same = bool((np.asarray(got).view(np.uint32)
                                 == want).all())
                    row.update(ms=ms, exact=same,
                               gbps=x.nbytes / ms / 1e6)
                    bad += not same
                except Exception as e:  # a candidate the chip refuses
                    row.update(error=f"{type(e).__name__}: {str(e)[:300]}")
                    bad += 1
                finally:
                    restore()
                rows.append(row)
                print(f"{kind:5s} {name:22s} {cand:8s}"
                      f"{'*' if row['ships'] else ' '} "
                      + (f"{row['ms']:10.3f} ms {row['gbps']:8.1f} GB/s "
                         f"exact={row['exact']}" if "ms" in row
                         else row["error"]), file=sys.stderr, flush=True)
                keep()
            del x, args
    print(json.dumps(out))
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
