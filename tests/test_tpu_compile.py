"""The day-of-year flush at the benchmark's size, compiled for a described
v5e with no chip attached (on-chip-measurement guide, section 2.3), and the
fact about the chip's compiler that ``ramba_tpu/core/layouts.py`` answers:
left alone it lays a (time, 721, 1440) cube out with TIME minor, so a walk
along time costs a copy of the cube; and a strided index of a long row as
``ramba_tpu/core/slicing.py`` lowers it; and the rank-3 stencil kernel at
``mg-C``'s two finest levels, as Mosaic takes it, and a ghost-layer
refresh there, in place.  Nothing here runs on a
TPU and nothing printed is a time.  The only tier-1 file that loads the TPU's
compiler: keep such tests here."""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

from ramba_tpu import groupby  # noqa: F401  (registers the segment ops)
from ramba_tpu.core import layouts, slicing
from ramba_tpu.core.expr import OPS
from ramba_tpu.parallel import mesh as rmesh

GRID, G = (721, 1440), 366
WATERMARK = 0.9 * 16909336064  # resilience/memory.py's share of a v5e's HBM


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture
def one_chip(topo):
    """The program's mesh held to the described chip."""
    before = rmesh.get_mesh()
    rmesh.set_mesh(Mesh(np.array(topo.devices[:1]), ("d0",)))
    yield SingleDeviceSharding(topo.devices[0])
    rmesh.set_mesh(before)


def flush(cube, labels):
    """One solve of ``doy-clim`` as the fuser linearizes it."""
    anomaly = ("mean", 0, G, ("full", "group"),
               (("subtract", (("a", 0), ("a", 1))),
                ("multiply", (("t", 0), ("t", 0)))))
    clim = OPS["segment_reduce"](("mean", G, 0), cube, labels)
    return clim, OPS["segment_mapreduce"](anomaly, labels, cube, clim)


def shapes(days, one_chip, layout=None):
    where = one_chip
    if layout is not None:
        from jax.experimental.layout import Format, Layout
        where = Format(Layout(major_to_minor=layout), one_chip)
    return (jax.ShapeDtypeStruct((days,) + GRID, jnp.float32, sharding=where),
            jax.ShapeDtypeStruct((days,), jnp.int32, sharding=one_chip))


def total(compiled):
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes)


@pytest.mark.parametrize("shape,dtype,keep", [
    ((2922, 721, 1440), "float32", True),    # 7.7 % of padding
    ((366, 721, 1440), "float32", True),
    ((1000, 3, 3), "float32", False),        # row-major tiles: x 114
    ((40, 9, 20), "float32", False),
    ((15000, 15000), "float32", False),      # rank two: the compiler's
    ((1000000,), "float32", False),
    ((64, 512, 1024), "bfloat16", True),
    ((), "float32", False),
])
def test_which_results_stay_row_major(shape, dtype, keep):
    assert layouts.keeps_row_major(
        jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))) is keep


def test_several_devices_leave_ranks_one_and_two_to_the_compiler():
    """Under a mesh a result of rank three is the system's to place and
    to lay out (PR 36), rank two GSPMD's and the compiler's as before."""
    if len(jax.devices()) == 1:
        pytest.skip("tier-1's mesh has eight devices")
    fn = layouts.RowMajorJit(lambda a: (a + 1.0,))
    flat = jnp.zeros((512, 256), jnp.float32)
    assert fn._jit_for((flat,)) is fn._plain
    x = jnp.zeros((4, 128, 256), jnp.float32)
    assert fn.pins(x)
    out = fn(x)[0]
    assert out.sharding.spec == rmesh.default_spec(x.shape)
    assert float(out.sum()) == 4 * 128 * 256


def test_left_alone_the_compiler_puts_time_last_and_copies_the_cube(one_chip):
    """The cube's tiles cover (lon, time), which pad 0.3 %, not (lat,
    lon), which pad 7.7 %, and the climatology's likewise; the walk then
    costs one time-major copy of the cube, and a fourth year is over the
    15.22 GB watermark (PERF.md section 6, PR 30)."""
    c = jax.jit(flush).lower(*shapes(1096, one_chip)).compile()
    (cube, _), _ = c.input_formats
    assert cube.layout.major_to_minor == (1, 2, 0)
    assert c.output_formats[0].layout.major_to_minor == (1, 2, 0)
    copy = 1096 * 728 * 1536 * 4  # time-major, as the chip tiles it
    assert copy < c.memory_analysis().temp_size_in_bytes < 1.4 * copy
    assert total(c) < WATERMARK
    four = jax.jit(flush).lower(*shapes(1461, one_chip)).compile()
    assert total(four) > WATERMARK


def test_the_system_keeps_the_cube_row_major(one_chip):
    def cube(s):
        out = s
        for d, n in enumerate((64,) + GRID):
            shape = [n if i == d else 1 for i in range(3)]
            out = out + jnp.arange(n, dtype=jnp.float32).reshape(shape)
        return (out,)

    s = jax.ShapeDtypeStruct((), jnp.float32, sharding=one_chip)
    assert jax.jit(cube).lower(s).compile().output_formats[
        0].layout.major_to_minor != (0, 1, 2)
    pinned = layouts.RowMajorJit(cube).lower(s).compile()
    assert pinned.output_formats[0].layout.major_to_minor == (0, 1, 2)


def test_the_flush_at_the_cells_size_stores_nothing_of_the_cube(one_chip):
    """One solve of ``doy-clim`` at 8 years on the row-major cube: the two
    walks, no copy of the operand, under the watermark."""
    compiled = layouts.RowMajorJit(flush).lower(
        *shapes(2922, one_chip, (0, 1, 2))).compile()
    assert compiled.output_formats[0].layout.major_to_minor == (0, 1, 2)
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20
    assert 14.6e9 < total(compiled) < WATERMARK
    assert compiled.as_text().count(" while(") == 2


@pytest.fixture
def four_chips(topo):
    """The program's mesh held to the described 2 x 2 host."""
    before = rmesh.get_mesh()
    rmesh.set_mesh(Mesh(np.array(topo.devices).reshape(2, 2), ("d0", "d1")))
    yield rmesh.get_mesh()
    rmesh.set_mesh(before)


def test_the_thirty_year_flush_on_four_chips_fits_and_copies_nothing(
        four_chips):
    """One solve of ``doy-clim-30y-x4``: the cube of 10,958 days in the
    default layout (time 2 x longitude 2, row-major on every device),
    both walks inside ``shard_map``: under the watermark as compiled, the
    climatology out in ITS default layout and row-major, ONE data-sized
    collective (the all-reduce of a device's partial sums, 0.76 GB), and
    no copy, transpose or pad of anything the size of a slab of the
    climatology, let alone of the cube."""
    import re

    from jax.experimental.layout import Format, Layout
    from jax.sharding import NamedSharding, PartitionSpec

    days = 10958
    spec = rmesh.default_spec((days,) + GRID)
    assert spec == PartitionSpec("d1", None, "d0")
    cube = jax.ShapeDtypeStruct(
        (days,) + GRID, jnp.float32, sharding=Format(
            Layout(major_to_minor=(0, 1, 2)), NamedSharding(four_chips, spec)))
    labels = jax.ShapeDtypeStruct(
        (days,), jnp.int32, sharding=NamedSharding(four_chips,
                                                   PartitionSpec()))
    fn = layouts.RowMajorJit(flush)
    assert fn.pins(cube, labels)
    c = fn.lower(cube, labels).compile()
    clim = c.output_formats[0]
    assert clim.layout.major_to_minor == (0, 1, 2)
    assert clim.sharding.spec == rmesh.default_spec((G,) + GRID)
    assert 14.0e9 < total(c) < WATERMARK
    text = c.as_text()
    assert text.count(" while(") == 2
    big = 4 * GRID[0] * GRID[1] // 4  # a quarter of one day's slab
    moved, reduced = [], []
    for m in re.finditer(r"= (\w+)\[([\d,]*)\]\S* "
                         r"(copy|transpose|pad|all-reduce|all-gather|"
                         r"reduce-scatter|all-to-all|collective-permute)"
                         r"(?:-start)?\(", text):
        dtype, dims, op = m.groups()
        nbytes = int(np.prod([int(d) for d in dims.split(",") if d] or [1])
                     ) * np.dtype({"f32": "float32", "s32": "int32",
                                   "u32": "uint32", "pred": "bool"}.get(
                                       dtype, "float32")).itemsize
        if nbytes >= big:
            (reduced if op.startswith(("all", "reduce", "coll"))
             else moved).append((op, dims))
    assert moved == []
    assert reduced == [("all-reduce", "366,721,720")]


@pytest.mark.parametrize("write", [False, True], ids=["read", "write"])
def test_a_strided_long_row_is_work_that_follows_its_length(one_chip, write):
    """``x[:, ::2]`` of a (64, 2^20) float32 array (268 MB), read and
    written: the selection product in tiles is 1.4e11 flops and two copies
    of the array in temporaries as compiled.  As ONE product over the row
    it asked for a 2^20 x 2^19 matrix, a terabyte, and 3e14 flops."""
    idx = (slice(None), slice(None, None, 2))
    x = jax.ShapeDtypeStruct((64, 1 << 20), jnp.float32, sharding=one_chip)
    assert slicing._lanes_through_mxu(x, slicing._axes(idx, x.shape))
    if write:
        v = jax.ShapeDtypeStruct((64, 1 << 19), jnp.float32,
                                 sharding=one_chip)
        c = jax.jit(lambda a, b: slicing.put(a, idx, b)).lower(x, v).compile()
    else:
        c = jax.jit(lambda a: slicing.take(a, idx)).lower(x).compile()
    nbytes = 64 * (1 << 20) * 4
    assert c.memory_analysis().temp_size_in_bytes < 3 * nbytes
    assert c.cost_analysis()["flops"] < 2e11


# -- the rank-3 stencil kernel as Mosaic takes it ----------------------------
@pytest.mark.parametrize("n,weights", [
    (514, (-8 / 3, 0.0, 1 / 6, 1 / 12)),        # A: 21 taps
    (514, (0.5, 0.25, 0.125, 0.0625)),          # P: 27
    (258, (-3 / 17, 1 / 33, -1 / 61, 0.0)),     # S: 19
], ids=["A-514", "P-514", "S-258"])
def test_the_rank_3_kernel_compiles_at_the_cells_sizes(one_chip, n, weights):
    """NPB MG's operators over mg-C's (2^k + 2)^3 arrays: the block the
    kernel derives fits the VMEM it asks for, every copy is one Mosaic
    takes (whole tiles from the operand, the ragged ones as blocks of
    it), and the compiled program is the custom call alone: no pad, slice
    or fusion of the operand's size beside it.  In the x32 regime, the
    chip's: Mosaic takes no 64-bit index, at rank 2 either."""
    import ramba_tpu as rt
    from benchmark.programs import nas_mg
    from ramba_tpu.observe import registry
    from ramba_tpu.ops import stencil_pallas

    st = nas_mg.stencil27(rt, weights)
    slots = (("arr", 0),)
    lo, hi, taps = st.neighborhood(slots)
    x = jax.ShapeDtypeStruct((n, n, n), jnp.float32, sharding=one_chip)
    assert stencil_pallas._rank3_wins(x.shape, x.dtype, 1)
    with registry.collect_kernel_notes() as notes, jax.enable_x64(False):
        compiled = jax.jit(lambda a: stencil_pallas._run_padded(
            st.func, lo, hi, slots, [a], taps, False)).lower(x).compile()
    (note,) = notes
    assert note["path"] == "pallas_padded" and not note["interpret"]
    assert note["grid"] == -(-n // note["block_planes"]) >= 16
    assert not note.get("operand_copy")
    assert note["vmem_limit_bytes"] <= stencil_pallas._vmem_cap()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert " fusion(" not in text and " pad(" not in text
    assert compiled.memory_analysis().temp_size_in_bytes == 0


@pytest.mark.parametrize("n,weights,epilogue,planes", [
    (514, (-8 / 3, 0.0, 1 / 6, 1 / 12), ("subtract", 0), 4),   # resid
    (514, (-3 / 17, 1 / 33, -1 / 61, 0.0), ("add", 0), 4),     # psinv
    (258, (-8 / 3, 0.0, 1 / 6, 1 / 12), ("subtract", 0), 12),
    (258, (-3 / 17, 1 / 33, -1 / 61, 0.0), ("add", 1), 12),
], ids=["A-514", "S-514", "A-258", "S-258"])
def test_the_update_compiles_into_the_kernels_store(one_chip, n, weights,
                                                    epilogue, planes):
    """``v - A u`` and ``u + S r`` at mg-C's two finest levels: the base a
    block of the output's walk, sized into the VMEM the kernel asks for (a
    plane fewer at 514^3, two at 258^3), and the program is the custom
    call alone: no subtraction or addition of the operand's size beside
    it."""
    import ramba_tpu as rt
    from benchmark.programs import nas_mg
    from ramba_tpu.observe import registry
    from ramba_tpu.ops import stencil_pallas

    st = nas_mg.stencil27(rt, weights)
    slots = (("arr", 0),)
    lo, hi, taps = st.neighborhood(slots)
    x = jax.ShapeDtypeStruct((n, n, n), jnp.float32, sharding=one_chip)
    with registry.collect_kernel_notes() as notes, jax.enable_x64(False):
        compiled = jax.jit(lambda a, b: stencil_pallas._run_padded(
            st.func, lo, hi, slots, [a], taps, False, epilogue=epilogue,
            base=b)).lower(x, x).compile()
    (note,) = notes
    assert note["path"] == "pallas_padded" and not note["interpret"]
    assert (note["epilogue"], note["epilogue_fused"]) == (epilogue[0], True)
    assert note["block_planes"] == planes
    assert note["vmem_limit_bytes"] <= stencil_pallas._vmem_cap()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert " fusion(" not in text and " subtract(" not in text
    assert compiled.memory_analysis().temp_size_in_bytes == 0


# -- a ghost-layer refresh in place -------------------------------------------
@pytest.mark.parametrize("n", [514, 258, 130, 66, 34, 18, 10])
def test_a_ghost_layer_refresh_compiles_in_place(one_chip, n, monkeypatch):
    """NPB's ``comm3`` of a (2^k + 2)^3 array as ``slicing.remap`` lowers
    it for one chip, at every level of ``mg-C``'s pyramid that has a whole
    row tile: the custom call and the two plane writes, the operand
    aliased to the result, no temporary; as six writes XLA holds a lane
    face in (8, 128) tiles, one float in a row of 128."""
    from jax._src.pallas.mosaic import pipeline

    from ramba_tpu.observe import registry
    from ramba_tpu.ops import faces_pallas, stencil_pallas

    m = n - 2
    maps = (((0, m), (m + 1, 1)),) * 3
    x = jax.ShapeDtypeStruct((n, n, n), jnp.float32, sharding=one_chip)
    tiled = n * -(-n // 8) * 8 * -(-n // 128) * 128 * 4

    def compiled():
        with registry.collect_kernel_notes() as notes, jax.enable_x64(False):
            c = jax.jit(lambda a: slicing.remap(a, maps),
                        donate_argnums=0).lower(x).compile()
        return c, notes

    parent, (note,) = compiled()
    assert note["path"] == "dus"  # off the chip the kernel is not offered
    face = n * -(-n // 8) * 8 * 128 * 4  # f32[n, n, 1] as tiled: 137 MB
    assert n < 514 or parent.memory_analysis().temp_size_in_bytes >= face
    assert n < 258 or parent.cost_analysis()["bytes accessed"] > 1.4 * tiled
    # the chip's answers: there is no chip to ask here
    monkeypatch.setattr(faces_pallas, "available", lambda *a: True)
    monkeypatch.setattr(faces_pallas, "interpreting", lambda: False)
    monkeypatch.setattr(pipeline, "_get_tpu_generation", lambda: 5)
    walk, (note,) = compiled()
    assert note["path"] == "wrap" and not note["interpret"]
    assert note["vmem_limit_bytes"] <= stencil_pallas._vmem_cap()
    ma = walk.memory_analysis()
    assert ma.temp_size_in_bytes < 8 << 20
    assert ma.alias_size_in_bytes == ma.argument_size_in_bytes == tiled
    text = walk.as_text()
    assert text.count("tpu_custom_call") == 1 and " copy(" not in text
    assert f"f32[{n},{n},1]" not in text


# -- a prolongation written once -----------------------------------------------
@pytest.mark.parametrize("n", [514, 258, 130])
def test_a_prolongation_compiles_to_one_pass(one_chip, n, monkeypatch):
    """NPB's ``interp`` onto a (2^k + 2)^3 array of ``mg-C``'s pyramid as
    ``slicing.prolong`` lowers it for one chip: the custom call alone,
    whose blocks Mosaic takes, in the VMEM it asks for, and no
    temporary; as five writes XLA makes a pass over the fine array each."""
    from ramba_tpu.observe import registry
    from ramba_tpu.ops import prolong_pallas, stencil_pallas

    z = jax.ShapeDtypeStruct((n // 2 + 1,) * 3, jnp.float32,
                             sharding=one_chip)

    def compiled():
        with registry.collect_kernel_notes() as notes, jax.enable_x64(False):
            c = jax.jit(lambda a: slicing.prolong(
                a, 3, jnp.zeros((n,) * 3, a.dtype))).lower(z).compile()
        return c, notes

    writes, (note,) = compiled()
    assert note["path"] == "xla"  # off the chip the kernel is not offered
    tiled = n * -(-n // 8) * 8 * -(-n // 128) * 128 * 4
    assert writes.cost_analysis()["bytes accessed"] > 3 * tiled
    # the chip's answers: there is no chip to ask here
    monkeypatch.setattr(prolong_pallas, "available", lambda *a: True)
    monkeypatch.setattr(prolong_pallas, "interpreting", lambda: False)
    kernel, (note,) = compiled()
    assert note["path"] == "pallas" and not note["interpret"]
    assert note["vmem_limit_bytes"] <= stencil_pallas._vmem_cap()
    assert kernel.memory_analysis().temp_size_in_bytes == 0
    text = kernel.as_text()
    assert text.count("tpu_custom_call") == 1 and " fusion(" not in text


# -- the transpose's swap of blocks on four chips -------------------------------
def test_the_transpose_flush_at_the_cells_size_fits_one_exchange_in_flight(
        topo, monkeypatch):
    """One solve of ``transpose-x4`` as the fuser hands it to admission
    (ten of PRK's ``B += A.T; A += 1`` and the norm, captured at toy size
    on a 2 x 2 grid of CPU devices), compiled at 49,152^2 for the
    described 2 x 2 host without donation, as admission estimates it:
    under the watermark, ten ``collective-permute``s (one swap of blocks
    an iteration) and ten calls of the kernel that reads the received
    block transposed, no ``all-to-all`` or ``all-gather``, and two blocks
    of temporaries: one exchange in flight.  Without the hold on A the
    next block is made while one is sent (16.9 GB); left to XLA, the
    update asks as much (``ops/transpose_sharded.py``)."""
    import re

    from jax.sharding import NamedSharding

    import ramba_tpu as rt
    from ramba_tpu.core import fuser
    from ramba_tpu.ops import pallas_backend

    if len(jax.devices()) < 4:
        pytest.skip("captures on a 2 x 2 grid of the suite's devices")
    before = rmesh.get_mesh()
    fuser.flush()
    rmesh.set_mesh(Mesh(np.array(jax.devices()[:4]).reshape(2, 2),
                        ("d0", "d1")))
    captured = []

    class Captured(Exception):
        pass

    def capture(program, leaf_vals, donate_key, span=None, **kw):
        captured.append((program, leaf_vals))
        raise Captured()

    try:
        n = 512
        i = rt.arange(n, dtype=np.float32)
        a = rt.zeros((n, n), dtype=np.float32) + (i[:, None] * n + i[None, :])
        b = rt.zeros((n, n), dtype=np.float32)
        rt.sync()
        monkeypatch.setattr(fuser._memory, "admit", capture)
        for _ in range(10):
            b += a.T
            a += 1.0
        with pytest.raises(Captured):
            float(rt.sum(abs(b)))
    finally:
        monkeypatch.undo()
        rmesh.set_mesh(before)
    program, leaf_vals = captured[0]
    assert [op for op, _, _ in program.instrs].count("add_transposed") == 10
    four = Mesh(np.array(topo.devices).reshape(2, 2), ("d0", "d1"))
    big = 49152
    rmesh.set_mesh(four)
    monkeypatch.setattr(pallas_backend, "interpret_mode", lambda: False)
    try:
        avals = [jax.ShapeDtypeStruct(
            (big, big), v.dtype,
            sharding=NamedSharding(four, rmesh.default_spec((big, big))))
            if np.ndim(v) == 2 else jax.ShapeDtypeStruct((), np.float32,
                                                         weak_type=True)
            for v in leaf_vals]
        with jax.enable_x64(False):
            c = layouts.RowMajorJit(fuser._build_callable(program)).lower(
                *avals).compile()
    finally:
        rmesh.set_mesh(before)
    block = (big // 2) ** 2 * 4
    assert total(c) < WATERMARK
    assert c.memory_analysis().temp_size_in_bytes <= 2 * block + (64 << 20)
    text = c.as_text()
    count = {op: len(re.findall(r"\b" + op + r"(?:-start)?\(", text))
             for op in ("collective-permute", "all-to-all", "all-gather")}
    assert count == {"collective-permute": 10, "all-to-all": 0,
                     "all-gather": 0}
    assert text.count("ramba_add_transposed") >= 10
