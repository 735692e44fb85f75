"""Compile one solve of a configuration at its real size for a described
``v5e:2x2`` topology, with no chip attached (on-chip-measurement guide,
section 2.3): what XLA:TPU's ``memory_analysis()`` says with and without
donation, which is what ``resilience/memory.py`` admission will estimate
(it lowers once more WITHOUT donation), and which collectives and custom
calls the compiler put in.  Nothing runs on a TPU and nothing printed
here is a time.

    JAX_PLATFORMS=cpu python benchmark/rehearse_compile.py \
        --config prk-star2-n15000 --traffic iterate10 --chips 1 [--n 18000]

The leaves are real arrays on the CPU backend (``--chips 4`` forces four
virtual CPU devices), so the host needs room for them.  The program is
captured at admission, the last step before the ladder runs it; the
steering below (kernels lower for Mosaic, the mesh is the described one)
lives here and not in the program.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class _Captured(Exception):
    pass


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--n", type=int, help="override the configuration's n")
    args = ap.parse_args(argv)

    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={args.chips}")
    sys.path.insert(0, ROOT)

    import jax
    import numpy
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding

    jax.config.update("jax_enable_compilation_cache", False)
    import ramba_tpu as rt
    from ramba_tpu.core import fuser
    from ramba_tpu.ops import pallas_backend
    from ramba_tpu.parallel import mesh as rmesh
    from ramba_tpu.resilience import memory

    from benchmark.run import load_json, load_program

    cfg = load_json(os.path.join(ROOT, "benchmark", "configs",
                                 args.config + ".json"))
    traffic = load_json(os.path.join(ROOT, "benchmark", "traffic",
                                     args.traffic + ".json"))
    if args.n:
        cfg["n"] = args.n
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    cpu_mesh = rt.get_mesh()
    prog = load_program(cfg["program"]).Program(
        rt, cfg, traffic, numpy.random.default_rng(0), args.chips)
    prog.setup()

    # from here on kernels lower for Mosaic, and admission captures
    pallas_backend.interpret_mode = lambda: False
    captured = []

    def capture(program, leaf_vals, donate_key, span=None, **kw):
        captured.append((program, list(leaf_vals), tuple(donate_key)))
        raise _Captured()

    memory.admit = capture
    fuser._memory.admit = capture
    try:
        prog.solve()
    except _Captured:
        pass
    program, leaf_vals, donate_key = captured[0]
    print(f"rehearse: {args.config} / {args.traffic} n={cfg['n']} "
          f"chips={args.chips}: one flush of {len(program.instrs)} "
          f"instructions, {program.n_leaves} leaves, the program would "
          f"donate leaves {donate_key}")

    # the described chips take the place of the CPU mesh's devices
    tpu_mesh = Mesh(numpy.array(topo.devices[:args.chips]).reshape(
        cpu_mesh.devices.shape), cpu_mesh.axis_names)
    rmesh._mesh = tpu_mesh

    def place(v):
        spec = getattr(getattr(v, "sharding", None), "spec", None)
        return NamedSharding(tpu_mesh, spec if spec is not None
                             else jax.sharding.PartitionSpec())

    avals = [jax.ShapeDtypeStruct(numpy.shape(v), numpy.asarray(v).dtype
                                  if not hasattr(v, "dtype") else v.dtype,
                                  sharding=place(v)) for v in leaf_vals]
    big = tuple(i for i, v in enumerate(leaf_vals)
                if getattr(v, "nbytes", 0) >= (1 << 20))
    fn = fuser._build_callable(program)
    for label, donate in (("without donation (admission's estimate)", ()),
                          ("donating the big leaves", big)):
        compiled = jax.jit(fn, donate_argnums=donate).lower(*avals).compile()
        ma = compiled.memory_analysis()
        sizes = {k: int(getattr(ma, k + "_size_in_bytes"))
                 for k in ("argument", "output", "temp", "alias")}
        est = sizes["argument"] + sizes["output"] + sizes["temp"]
        text = compiled.as_text()
        found = {name: len(re.findall(r"\b" + name + r"(?:-start)?\(", text))
                 for name in ("collective-permute", "all-reduce",
                              "all-gather", "all-to-all", "custom-call")}
        print(f"rehearse: {label}: per device " + json.dumps(sizes)
              + f" argument+output+temp = {est / 1e9:.2f} GB,"
              f" minus aliased = {(est - sizes['alias']) / 1e9:.2f} GB;"
              f" ops found: {json.dumps(found)}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
