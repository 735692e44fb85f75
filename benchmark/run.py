"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

A cell is an entry of ``BENCHMARK.json``'s ``workloads``.  Everything that
belongs to one configuration, one traffic mix or one per-layer metric is a
file found by the name in that entry: ``configs/<config>.json`` (via the
configuration's ``file``), ``traffic/<traffic>.json``,
``programs/<program>.py`` (named by the configuration) and
``layer_metrics/<metric>.py``.  Nothing below reads a cell's name.

One SOLVE is the unit: the user's script section from its first array
statement to the value read back on the host.  A run repeats solves back
to back (closed loop, one client) for ``--seconds`` after set-up.  The
benchmark sets no RAMBA_* variable and refuses to run unless jax's first
device is a TPU; ``--rehearse-cpu`` (tests only) runs the same code on the
CPU and prints every metric under a ``rehearsal.`` name.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import glob  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "benchmark")
#: the traced stretch is kept under this directory of the checkout while
#: it is reduced, then removed
TRACE_DIR = os.path.join(HERE, ".trace")
ANNOTATION = "bench_solve"
#: a run that keeps raising is stopped: its state is no longer the cell's
MAX_RAISES = 3
#: solves before the window: the first traces and loads the executables,
#: the second runs them from the program's own cache
WARMUP_SOLVES = 2


def load_json(path):
    with open(path) as f:
        return json.load(f)


def _load_module(kind, name):
    path = os.path.join(HERE, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"benchmark.{kind}.{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_program(name):
    return _load_module("programs", name)


def by_name(entries, name, what):
    found = [e for e in entries if e["name"] == name]
    if len(found) != 1:
        raise SystemExit(f"benchmark: no {what} named {name!r} in "
                         f"BENCHMARK.json")
    return found[0]


class Solve:
    """One solve: its host-clock time, the events and counters the
    program emitted during it, and why it failed (None when it did
    not)."""

    def __init__(self, ms, events, counters, error):
        self.ms, self.events, self.counters, self.error = (
            ms, events, counters, error)

    @property
    def flushes(self):
        return [e for e in self.events if e.get("type") == "flush"]

    def stage_ms(self, keep):
        """Sum over this solve's flush spans of the ``stages`` (host
        clock self times) for which ``keep(name)`` holds."""
        return 1e3 * sum(v for f in self.flushes
                         for k, v in f.get("stages", {}).items() if keep(k))


class Context:
    """What a per-layer metric's reader may look at."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tests only: run off the TPU, name no device metric")
    args = ap.parse_args(argv)

    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = by_name(bench["workloads"], args.workload, "workload")
    cfg_entry = by_name(bench["configs"], cell["config"], "configuration")
    cfg = load_json(os.path.join(ROOT, cfg_entry["file"]))
    traffic = load_json(os.path.join(HERE, "traffic",
                                     cell["traffic"] + ".json"))
    chips = int(cell["chips"])

    sys.path.insert(0, ROOT)
    import jax
    import numpy

    devs = jax.devices()
    dev = devs[0]
    rehearsal = bool(args.rehearse_cpu)
    if dev.platform != "tpu" and not rehearsal:
        print(f"benchmark: no TPU: jax's first device is {dev.platform}:"
              f"{dev.device_kind}", file=sys.stderr)
        return 1
    if len(devs) != chips:
        # the program lays every array over every device jax shows, so a
        # cell runs only on a machine with exactly its chips
        print(f"benchmark: cell {cell['name']} is for {chips} chip(s), jax "
              f"shows {len(devs)}", file=sys.stderr)
        return 1

    import ramba_tpu as rt

    from benchmark import record, stats, tracered

    peaks = None
    if not rehearsal:
        table = load_json(os.path.join(HERE, "peaks.json"))["devices"]
        if dev.device_kind not in table:
            print(f"benchmark: no peaks for device_kind "
                  f"{dev.device_kind!r} in benchmark/peaks.json",
                  file=sys.stderr)
            return 1
        peaks = table[dev.device_kind]
    mesh_shape = [int(v) for v in rt.get_mesh().shape.values() if v > 1] or [1]
    if "mesh" in traffic and list(traffic["mesh"]) != mesh_shape:
        print(f"benchmark: traffic {cell['traffic']} is for mesh "
              f"{traffic['mesh']}, the program's default is {mesh_shape}",
              file=sys.stderr)
        return 1
    info = {"cell": cell["name"], "platform": dev.platform,
            "kind": dev.device_kind, "devices": len(devs),
            "mesh": dict(rt.get_mesh().shape), "jax": jax.__version__,
            "cache_dir": rt.common.compile_cache_dir(),
            "bring_up_s": time.perf_counter() - T_START}
    print("benchmark: " + json.dumps(info), flush=True)

    rng = numpy.random.default_rng(args.seed)
    prog = load_program(cfg["program"]).Program(rt, cfg, traffic, rng, chips)
    want_paths = tuple(sorted(prog.expected_paths(chips)))
    tap = []
    rt.observe.events.add_tap(tap.append)
    raises = 0

    def solve():
        nonlocal raises
        i0, c0 = len(tap), rt.diagnostics.counters()
        t0 = time.perf_counter()
        try:
            out = prog.solve()
            ms = 1e3 * (time.perf_counter() - t0)
            error = prog.check(out)
        except Exception as e:  # a failed solve is counted, not fatal
            ms = 1e3 * (time.perf_counter() - t0)
            error = f"{type(e).__name__}: {str(e)[:500]}"
            raises += 1
            if raises >= MAX_RAISES:
                raise
        events = tap[i0:]
        counters = record.counter_delta(c0, rt.diagnostics.counters())
        error = error or record.unclean(events, counters,
                                        interpret_ok=rehearsal)
        paths = record.kernel_paths(counters)
        if not error and paths != want_paths:
            error = f"stencil took path {paths}, want {want_paths}"
        return Solve(ms, events, counters, error)

    # -- set-up: resident arrays, then this cell's own programs -----------
    prog.setup()
    setup_events = list(tap)
    warmup = [solve() for _ in range(WARMUP_SOLVES)]
    setup_s = time.perf_counter() - T_START
    first = warmup[0].flushes
    print("benchmark: first solve "
          + json.dumps({"ms": warmup[0].ms, "flushes": len(first),
                        "cache": [f.get("cache") for f in first][:4],
                        "stages": first[0].get("stages") if first else None,
                        "setup_s": setup_s}), flush=True)

    # -- the window --------------------------------------------------------
    solves = []
    t_w0 = time.perf_counter()
    while time.perf_counter() - t_w0 < args.seconds:
        solves.append(solve())
    window_s = time.perf_counter() - t_w0

    # -- the traced stretches (--trace 1): a few solves under the profiler.
    # The Python tracer names what the host was doing in an idle gap but
    # slows host-bound solves by tens of per cent, so the numbers come
    # from a stretch without it and only the gaps' names from one with it.
    def stretch(python_tracer):
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        os.makedirs(TRACE_DIR)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = int(python_tracer)
        done = []
        jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
        try:
            for k in range(int(traffic["trace_solves"])):
                with jax.profiler.TraceAnnotation(ANNOTATION, solve=k):
                    done.append(solve())
        finally:
            jax.profiler.stop_trace()
        files = glob.glob(os.path.join(TRACE_DIR, "**", "*.xplane.pb"),
                          recursive=True)
        reduced = None
        if files:
            device_ops, frames = tracered.read_file(files[0], ANNOTATION)
            reduced = tracered.reduce_events(device_ops, frames, ANNOTATION,
                                             prog.kernels())
            print("benchmark: trace " + json.dumps({
                "python_tracer": python_tracer,
                "bytes": os.path.getsize(files[0]),
                "device_ops": {d: len(o) for d, o in device_ops.items()},
                "host_frames": len(frames), "reduced": reduced is not None,
                "solve_ms": [s.ms for s in done]}), flush=True)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        return done, reduced

    traced, trace, more, named = [], None, [], None
    if args.trace:
        traced, trace = stretch(False)
        more, named = stretch(True)
    traced_all = traced + more

    # -- correct: NumPy outside the window, layout, no compile inside -----
    problems = []
    try:
        facts = prog.verify()
    except record.BenchFailure as e:
        facts = {}
        problems.append(f"verify: {e}")
    misses = sum(1 for s in solves + traced_all for f in s.flushes
                 if f.get("cache") == "miss")
    if misses:
        problems.append(f"{misses} compiles inside the window")
    if not solves:
        problems.append("no solve finished inside the window")
    failed = [s for s in solves + traced_all if s.error]
    warm_bad = [s.error for s in warmup if s.error]
    if warm_bad:
        problems.append(f"warm-up solve failed: {warm_bad[0]}")
    bad_setup = record.unclean(setup_events, {}, interpret_ok=rehearsal)
    if bad_setup:
        problems.append(f"set-up: {bad_setup}")

    mem = [d.memory_stats() or {} for d in jax.local_devices()]
    peak = max((m.get("peak_bytes_in_use", 0) for m in mem), default=0)
    ms = [s.ms for s in solves]
    # generous on purpose: a later PR adds readers, not fields
    ctx = Context(cell=cell, cfg=cfg, traffic=traffic, chips=chips,
                  program=prog, peaks=peaks, warmup=warmup, solves=solves,
                  traced=traced, window_s=window_s, trace=trace,
                  setup_s=setup_s, stats=stats)
    group, readers = (("per_layer", "layer_metrics") if args.trace
                      else ("end_to_end", "end_to_end"))
    metrics = {}
    for m in bench[group]:
        if cell["name"] not in m.get("workloads", [cell["name"]]):
            continue
        try:
            reader = _load_module(readers, m["name"])
        except FileNotFoundError:
            problems.append(f"no reader for metric {m['name']}")
            continue
        value = reader.read(ctx)
        if value is not None:  # nothing to read: left out of the line
            name = ("rehearsal." if rehearsal else "") + m["name"]
            metrics[name] = {"value": float(value), "unit": m["unit"]}

    print("benchmark: window " + json.dumps({
        "solves": len(solves), "window_s": window_s,
        "solve_ms_p50": stats.median(ms), "solve_ms_p95":
        stats.percentile(ms, 95), "solve_ms_min": min(ms, default=None),
        "solve_ms_max": max(ms, default=None), "verify": facts,
        "failed_first": failed[0].error if failed else None,
        "problems": problems}, default=str), flush=True)

    device = {"platform": str(dev.platform), "kind": str(dev.device_kind),
              "count": len(devs), "memory_peak_bytes": int(peak)}
    result = {"correct": not problems,
              "attempted": len(solves) + len(traced_all),
              "failed": len(failed), "metrics": metrics, "device": device}
    if args.trace and trace is not None:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        # the gaps' names and seconds are of the stretch under the Python
        # tracer, where the host is slower; busy_s and the ops are not
        result["breakdown"] = {
            "device_ops": trace["device_ops"][:10],
            "idle_gaps": (named or trace)["idle_gaps"][:10]}
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
