"""``core/slicing.py``'s constants on the chip: from what window a stride
on the lane axis is worth a selection product on the MXU
(``MXU_MIN_ELEMENTS``), up to what step (``MXU_MAX_STEP``), and in what
tiles a long row is cut (``LANE_WHOLE``, ``LANE_TILE``); and whether at any
size a ghost-layer refresh (NPB MG's ``comm3``: six whole-face copies) is
better left six writes than given to the in-place walk of
``ops/faces_pallas.py`` (at none read: the walk has no threshold), in what
blocks; and from what fine extent a trilinear prolongation (NPB MG's
``interp``: five writes onto zeros) is given to ``ops/prolong_pallas.py``
(``MIN_EXTENT``).

One process, one chip.  Every candidate is ``slicing.take`` or
``slicing.put`` jitted alone over a resident operand of random BITS (every
pattern, NaNs and infinities among them), with the constants set for that
candidate: ``xla`` is what the module does off the MXU (a read: ``x[idx]``,
a gather of the lane axis; a write: ``lax.pad`` over at most
``PAD_MAX_EXTENT`` elements, jax's scatter over more), ``whole`` one
product over the whole lane axis, ``tile<T>`` tiles of T.  ``ships`` marks
what the constants in the tree choose.  A product's result is held against
NumPy's, byte for byte.  A refresh (``faces``) is ``slicing.remap`` jitted
alone with its operand donated, as a flush hands it a temporary: ``dus`` the
six writes one by one (the parent's HLO), ``edge`` the walk over the edge
blocks as the module sizes them, ``edge<P>`` in lane blocks of P planes,
``whole<P>`` a walk over whole blocks of P planes (every byte read and
written once), ``pass`` XLA's ``a + 1`` (what a read and a write of the array
cost); held against NumPy's ``comm3``.  A prolongation (``prolong``) onto
an n^3 array is ``slicing.prolong`` jitted alone, ``writes`` the five
writes one by one (the parent's HLO), ``kernel`` the kernel, ``pass`` XLA's
``a + 1`` of the fine array; several coarse operands a call where they are
small, so that the device's time shows; ``compile_s`` is the host's time to
lower and compile the call, what a shape adds to set-up.  The kernel is
held against the writes bit for bit (NaNs, infinities and -0 among the
operand's values) and both against NumPy's five writes (NaN for NaN).

    python -u scripts/tpu_slicing_sweep.py [reads] [writes] [faces[=18,10]]
        [prolong[=514,258]]

(no argument: all four; ``faces=``, ``prolong=`` with the cubes' sides:
those alone).

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
#: refreshes in one program
ROUNDS = 20
#: side of the cube, candidates: the levels of mg-C's pyramid that have a
#: whole row tile
FACES = [
    (514, ["pass", "dus", "edge", "edge1", "edge12", "whole3"]),
    (258, ["pass", "dus", "edge", "edge2", "edge28", "whole10"]),
    (130, ["pass", "dus", "edge", "edge4", "edge60", "whole30"]),
    (66, ["pass", "dus", "edge", "edge7", "whole66"]),
    (34, ["pass", "dus", "edge", "edge8", "whole34"]),
    (18, ["pass", "dus", "edge", "whole18"]),
    (10, ["pass", "dus", "edge", "whole10"]),
]
#: fine sides of mg-C's prolongations
PROLONG = [514, 258, 130, 66, 34, 18, 10, 6]


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ramba_tpu.core import slicing
    from ramba_tpu.ops import faces_pallas

    wanted = {a.split("=")[0] for a in sys.argv[1:]} or {
        "reads", "writes", "faces", "prolong"}
    sides = [int(n) for a in sys.argv[1:] if a.startswith("faces=")
             for n in a[6:].split(",")]
    fine_sides = [int(n) for a in sys.argv[1:] if a.startswith("prolong=")
                  for n in a[8:].split(",")]
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"no TPU: {dev.platform}", file=sys.stderr)
        return 1
    shipped = {k: getattr(slicing, k) for k in
               ("MXU_MIN_ELEMENTS", "MXU_MAX_STEP", "LANE_WHOLE", "LANE_TILE",
                "PAD_MAX_EXTENT")}
    through_kernel = slicing._faces_through_kernel
    prolong_kernel = slicing._prolong_through_kernel

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
        slicing._faces_through_kernel = through_kernel
        slicing._prolong_through_kernel = prolong_kernel

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
        if kind + "s" not in wanted:
            continue
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

    def refresh(n, cand):
        """One ``comm3`` of an n^3 array as ``cand`` lowers it: ROUNDS of
        them in ONE program over a donated operand, as a flush holds them,
        so that what is read is the device's time and not the host's 0.1
        ms a call (a single call and its wait read 0.5 ms more than the
        device took, at every size).  ``pass`` is ``a + 1``: what one
        read and one write of the array cost as XLA fuses them."""
        m = n - 2
        pairs = ((0, m), (m + 1, 1))
        name = cand.rstrip("0123456789")
        if cand == "pass":
            def f(a):
                return jax.lax.optimization_barrier(a + 1.0)
        elif cand in ("dus", "edge"):  # as the module lowers the node
            if cand == "dus":
                slicing._faces_through_kernel = lambda x, composed: False

            def f(a):
                return slicing.remap(a, (pairs,) * 3)
        else:  # a block of the sweep's, the plane faces XLA's as shipped
            def walk(a, bp):
                if name == "whole":
                    return whole_pass(a, pairs, pairs, False, bp)
                return faces_pallas._wrap_jit(
                    pairs, pairs, False, *faces_pallas._sized(a.shape, bp))(a)

            def f(a):
                a = walk(a, int(cand[len(name):]))
                for d, s in pairs:
                    a = slicing.put(a, (d,), slicing.take(a, (s,)))
                return a

        def rounds(a):
            for _ in range(ROUNDS):
                a = f(a)
            return a

        try:
            fn = jax.jit(rounds, donate_argnums=0)
            x = bits((n, n, n), 3)
            want = np.pad(np.asarray(x).view(np.uint32)[1:-1, 1:-1, 1:-1], 1,
                          mode="wrap")
            reps = 1 if n > 200 else 10
            x = jax.block_until_ready(fn(x))
            ms = []
            for _ in range(5):
                t0 = time.perf_counter()
                for _ in range(reps):
                    x = fn(x)
                jax.block_until_ready(x)
                ms.append(1e3 * (time.perf_counter() - t0) / reps / ROUNDS)
            return (statistics.median(ms), cand == "pass" or bool(
                (np.asarray(x).view(np.uint32) == want).all()))
        finally:
            restore()

    for n, cands in FACES if "faces" in wanted else ():
        if sides and n not in sides:
            continue
        ships = ("edge" if faces_pallas.available((n, n, n), jnp.float32)
                 else "dus")
        for cand in cands:
            row = {"kind": "faces", "operand": f"cube-{n} comm3",
                   "shape": [n, n, n], "candidate": cand,
                   "ships": cand == ships}
            if cand == "edge":
                row["block_planes"] = faces_pallas.block((n, n, n))[:2]
            try:
                ms, same = refresh(n, cand)
                row.update(ms=ms, exact=same, gbps=4.0 * n ** 3 / ms / 1e6)
                bad += not same
            except Exception as e:  # a candidate the chip refuses
                row.update(error=f"{type(e).__name__}: {str(e)[:300]}")
                bad += 1
            rows.append(row)
            print(f"faces cube-{n:<17d} {cand:8s}"
                  f"{'*' if row['ships'] else ' '} "
                  + (f"{row['ms']:10.4f} ms {row['gbps']:8.1f} GB/s "
                     f"exact={row['exact']}" if "ms" in row
                     else row["error"]), file=sys.stderr, flush=True)
            keep()
    for n in PROLONG if "prolong" in wanted else ():
        if fine_sides and n not in fine_sides:
            continue
        row = {"kind": "prolong", "operand": f"cube-{n} interp",
               "shape": [n, n, n], "ships": "kernel" if prolong_kernel(
                   jax.ShapeDtypeStruct((n,) * 3, jnp.float32)) else "writes"}
        try:
            row.update(prolong_row(n, bits))
            bad += not (row["exact"] and row["numpy"])
        except Exception as e:  # a candidate the chip refuses
            row.update(error=f"{type(e).__name__}: {str(e)[:300]}")
            bad += 1
        finally:
            restore()
        rows.append(row)
        print(f"prolong cube-{n:<15d} " + (" ".join(
            f"{k}={row[k]:.4f}" for k in ("pass_ms", "writes_ms", "kernel_ms",
                                          "writes_compile_s",
                                          "kernel_compile_s"))
            + f" exact={row['exact']} numpy={row['numpy']}"
            if "exact" in row else row["error"]), file=sys.stderr, flush=True)
        keep()
    print(json.dumps(out))
    return 1 if bad else 0


def prolong_row(n, bits):
    """Times of ``pass``, ``writes`` and ``kernel`` for one fine side ``n``
    (ms a prolongation, median of 5 after a warm-up), the compile of each
    call, and whether the kernel's bits are the writes' and both NumPy's."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ramba_tpu.core import slicing

    c = n // 2 + 1
    many = max(1, min(20, (64 << 20) // (4 * n ** 3)))
    zs = []
    for k in range(many):
        z = np.asarray(bits((c, c, c), 10 + k)).view(np.uint32)
        z = (z >> 9 | 0x3F800000).view(np.float32) - 1.5  # [-0.5, 0.5)
        z.flat[::97] = -0.0
        z.flat[5::101] = np.inf
        z.flat[7::103] = np.nan
        zs.append(z)
    args = [jax.device_put(z) for z in zs]
    out = {"operands_a_call": many}
    got = {}
    for cand in ("pass", "writes", "kernel"):
        slicing._prolong_through_kernel = lambda f, k=cand: k == "kernel"

        def f(*zz):
            if cand == "pass":
                return [jnp.zeros((n,) * 3, jnp.float32) + z[0, 0, 0]
                        for z in zz]
            return [slicing.prolong(z, 3, jnp.zeros((n,) * 3, jnp.float32))
                    for z in zz]

        t0 = time.perf_counter()
        fn = jax.jit(f).lower(*args).compile()
        out[f"{cand}_compile_s"] = time.perf_counter() - t0
        jax.block_until_ready(fn(*args))
        ms = []
        for _ in range(5):
            t0 = time.perf_counter()
            r = jax.block_until_ready(fn(*args))
            ms.append(1e3 * (time.perf_counter() - t0) / many)
        out[f"{cand}_ms"] = statistics.median(ms)
        got[cand] = [np.asarray(x) for x in r]
    out["exact"] = all(np.array_equal(a.view(np.uint32), b.view(np.uint32))
                       for a, b in zip(got["kernel"], got["writes"]))
    # where the bits differ: a zero's sign, a NaN's payload, or a value
    out["differ"] = {k: int(sum(
        (f(a, b) & (a.view(np.uint32) != b.view(np.uint32))).sum()
        for a, b in zip(got["kernel"], got["writes"]))) for k, f in (
            ("zero_sign", lambda a, b: (a == 0) & (b == 0)),
            ("nan", lambda a, b: np.isnan(a) & np.isnan(b)),
            ("value", lambda a, b: ~((a == 0) & (b == 0))
             & ~(np.isnan(a) & np.isnan(b))))}
    want = [nas_writes(z, n) for z in zs]
    out["numpy"] = all(
        np.array_equal(a, w, equal_nan=True) and np.array_equal(
            np.signbit(a)[~np.isnan(w)], np.signbit(w)[~np.isnan(w)])
        for a, w in zip(got["writes"], want))
    out["gbps"] = 4.0 * n ** 3 / out["kernel_ms"] / 1e6
    return out


def nas_writes(z, n):
    """NumPy's five writes of ``benchmark/programs/nas_mg.py`` ``prolong``."""
    import numpy as np

    from benchmark.programs import nas_mg

    return nas_mg.prolong(z, np.zeros((n,) * 3, np.float32))


def whole_pass(x, rows, lanes, interpret, bp):
    """The sweep's own candidate, not the library's: the remap of
    ``faces_pallas.wrap`` as ONE walk over whole blocks of ``bp`` planes
    that Pallas's pipeline fetches and writes back where they came from,
    every byte of the array read and written once."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from ramba_tpu.ops import faces_pallas

    D = x.shape[0]
    Ho, Wo = faces_pallas._tiled(x.shape)
    by_lane = faces_pallas._by_tile(lanes, 128)
    by_row = faces_pallas._by_tile(rows, 8)

    taking = faces_pallas._taking

    def kernel(in_ref, out_ref):
        def plane(p):
            for r0 in range(0, Ho, 64):
                rws = pl.ds(r0, min(64, Ho - r0))

                def lane_tile(c, rws=rws):
                    return in_ref[p, rws, pl.ds(128 * c, 128)]

                for c in range(Wo // 128):
                    tile = lane_tile(c)
                    if c in by_lane:
                        tile = taking(tile, by_lane[c], lane_tile, 1, 128)
                    out_ref[p, rws, pl.ds(128 * c, 128)] = tile

            def row_tile(t):
                return out_ref[p, pl.ds(8 * t, 8), :]

            for t, takes in by_row.items():
                out_ref[p, pl.ds(8 * t, 8), :] = taking(
                    row_tile(t), takes, row_tile, 0, 8)

        jax.lax.fori_loop(jnp.int32(0), jnp.int32(bp),
                          lambda p, c: plane(p) or c, jnp.int32(0))

    spec = pl.BlockSpec((bp, Ho, Wo), lambda i: (i, 0, 0),
                        memory_space=pltpu.VMEM)
    return pl.pallas_call(
        kernel, grid=(-(-D // bp),),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        in_specs=[spec], out_specs=spec, input_output_aliases={0: 0},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=16 * bp * Ho * Wo + (2 << 20)),
        interpret=interpret, name="ramba_face_whole",
    )(x)


if __name__ == "__main__":
    sys.exit(main())
