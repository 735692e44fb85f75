"""What the lazy-DAG layer's counting costs a node, on the host.

    JAX_PLATFORMS=cpu python scripts/dag_count_cost.py [n] [repeats]

Builds ``benchmark/programs/nas_mg.py``'s DAG (NPB MG's timed section,
the twenty iterations kept; n = 64 by default: the lazy layer does not
know the grid's size, and the node count follows the levels) without
flushing it, and drops it.  Four variants of ``expr.Node.__init__``,
``expr.infer_aval`` and ``ndarray._classify_index``, taken from their own
source so that they cannot drift:

in      as they stand: ``dag.node.n``, ``dag.node.ns``, ``dag.infer.hit``,
        ``dag.key.hit``, ``dag.index.n``, ``dag.index.ns``,
        ``dag.index.hit`` on plain module integers
node    the counts of a node in, the index's three out
out     the counting statements and their clock reads removed
locked  ``out``, with the one locked ``registry.inc("dag.infer.hit")`` a
        hit of the memo paid before the counts left the lock

The variants take turns, the collector is off while a build is timed
(its pauses are ``host.gc.*``'s to count, not this script's), and each
reads the median of its builds: microseconds a node.  ``node_us`` and
``index_us`` are what ``in``'s counters read: the time in a node's
constructor and in an index's classification, each counted once.  The
difference of two builds of 100 ms stands inside the host's noise (a
quarter of a microsecond a node), so ``statements`` reads the same
statements alone, a million times in a loop: a node's three counts
against one locked increment.  A host timing: never a device number.
"""

from __future__ import annotations

import gc
import inspect
import json
import os
import statistics
import sys
import textwrap
import time
import timeit

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import numpy  # noqa: E402

import ramba_tpu as rt  # noqa: E402
from benchmark import run as harness  # noqa: E402
from ramba_tpu.core import expr, ndarray as nd  # noqa: E402
from ramba_tpu.observe import registry  # noqa: E402

#: the counting statements of ``Node.__init__`` and of ``infer_aval``'s
#: hit: what ``out`` removes ({statement: replacement, None to drop it})
NODE_OUT = dict.fromkeys(("global _node_n, _node_s", "t0 = _now()",
                          "_node_n += 1", "_node_s += _now() - t0"))
HIT_OUT = dict.fromkeys(("global _infer_hit, _key_hit", "_infer_hit += 1",
                         "_key_hit += 1"))
HIT_LOCKED = dict(HIT_OUT,
                  **{"_infer_hit += 1": '_registry.inc("dag.infer.hit")'})
#: the counting statements of ``_classify_index`` (its ``finally`` keeps
#: a ``pass``)
INDEX_OUT = dict(dict.fromkeys(("global _index_n, _index_s, _index_hit",
                                "t0 = _now()", "_index_hit += 1",
                                "_index_s += _now() - t0")),
                 **{"_index_n += 1": "pass"})


def variant(fn, swap):
    """``fn`` compiled again from its own source with each statement of
    ``swap`` ({statement: replacement or None}) replaced or removed; every
    statement has to be there."""
    src = textwrap.dedent(inspect.getsource(fn)).splitlines()
    found, out = set(), []
    for line in src:
        stmt = line.strip()
        if stmt in swap:
            found.add(stmt)
            if swap[stmt] is not None:
                out.append(line.replace(stmt, swap[stmt]))
        else:
            out.append(line)
    missing = set(swap) - found
    if missing:
        raise SystemExit(f"dag_count_cost: {fn.__qualname__} no longer "
                         f"holds {sorted(missing)}")
    scope = {}
    exec(compile("\n".join(out), f"<{fn.__qualname__}>", "exec"),
         fn.__globals__, scope)
    return scope[fn.__name__]


_n = _hit = 0
_s = 0.0
_now = time.perf_counter


def _bare():
    pass


def _counts():
    global _n, _s, _hit
    t0 = _now()
    _hit += 1
    _n += 1
    _s += _now() - t0


def _locked():
    registry.inc("dag.infer.hit")


def statements(number=1_000_000):
    """Microseconds a call of a node's counting statements and of the one
    locked increment, the empty call taken off: the fastest of five
    loops."""
    each = {f.__name__.lstrip("_"):
            1e6 * min(timeit.repeat(f, number=number, repeat=5)) / number
            for f in (_bare, _counts, _locked)}
    return {"node_counting_us": each["counts"] - each["bare"],
            "locked_hit_us": each["locked"] - each["bare"]}


def variants():
    node, infer, index = expr.Node.__init__, expr.infer_aval, \
        nd._classify_index
    bare, index_bare = variant(node, NODE_OUT), variant(index, INDEX_OUT)
    return {
        "in": (node, infer, index),
        "node": (node, infer, index_bare),
        "out": (bare, variant(infer, HIT_OUT), index_bare),
        "locked": (bare, variant(infer, HIT_LOCKED), index_bare),
    }


def install(fns):
    expr.Node.__init__, expr.infer_aval, nd._classify_index = fns


def build(prog):
    """NPB's timed section up to the norm's expression, never flushed."""
    lt = prog.lt
    u = {lt: rt.zeros((prog.n + 2,) * 3, dtype=prog.dtype)}
    r = {lt: prog.resid(u[lt], prog.v)}
    for _ in range(prog.nit):
        prog.mg3p(u, prog.v, r)
        r[lt] = prog.resid(u[lt], prog.v)
    ri = r[lt][1:-1, 1:-1, 1:-1]
    return rt.sqrt(rt.sum(ri * ri) / float(prog.n) ** 3)


def main(argv):
    n = int(argv[1]) if len(argv) > 1 else 64
    repeats = int(argv[2]) if len(argv) > 2 else 15
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cfg = harness.load_json(os.path.join(
        ROOT, harness.by_name(bench["configs"], "nas-mg-C",
                              "configuration")["file"]))
    cfg["n"] = n
    traffic = harness.load_json(os.path.join(
        harness.HERE, "traffic", "mg-timed.json"))
    module = harness.load_program(cfg["program"])
    prog = module.Program(rt, cfg, traffic, numpy.random.default_rng(0), 1)
    # the build needs v alone: set-up's NumPy reference is left out
    prog.v = rt.fromarray(module.wrap_ghosts(module.zran3(n, prog.dtype)[0]))
    rt.sync()
    # the pending-ops valve would flush a DAG this long only when several
    # builds pile up: each build is dropped and the count reset below
    kinds = variants()
    times = {k: [] for k in kinds}
    counted = {}
    try:
        for rep in range(repeats + 1):  # the first round fills the memos
            for kind, fns in kinds.items():
                install(fns)
                gc.collect()
                gc.disable()
                c0 = rt.diagnostics.counters()
                t0 = time.perf_counter()
                norm = build(prog)
                dt = time.perf_counter() - t0
                gc.enable()
                c1 = rt.diagnostics.counters()
                del norm
                rt.sync()  # nothing left pending; resets the valve's count
                if rep:
                    times[kind].append(dt)
                    counted[kind] = {
                        k: c1.get(k, 0) - c0.get(k, 0) for k in
                        ("dag.node.n", "dag.node.ns", "dag.index.n",
                         "dag.index.ns", "dag.index.hit", "dag.infer.hit",
                         "dag.key.hit", "dag.infer.n")}
    finally:
        install(kinds["in"])
        gc.enable()
    nodes = counted["in"]["dag.node.n"]
    indexes = counted["in"]["dag.index.n"]
    med = {k: statistics.median(v) for k, v in times.items()}
    out = {
        "n": n, "repeats": repeats, "nodes": nodes,
        "indexes": indexes,
        "counted": counted,
        # in's counters: the constructor's time a node, the
        # classification's an index (the last round's build)
        "node_us": 1e-3 * counted["in"]["dag.node.ns"] / nodes,
        "index_us": 1e-3 * counted["in"]["dag.index.ns"] / indexes,
        "build_ms": {k: 1e3 * v for k, v in med.items()},
        "build_ms_all": {k: [round(1e3 * x, 3) for x in v]
                         for k, v in times.items()},
        "us_per_node": {k: 1e6 * v / nodes for k, v in med.items()},
        # dag.node.n, dag.node.ns and dag.infer.hit together, a node
        "node_counting_us_per_node":
            1e6 * (med["node"] - med["out"]) / nodes,
        # the parent's one locked increment a hit, a node
        "locked_hit_us_per_node": 1e6 * (med["locked"] - med["out"]) / nodes,
        # dag.index.n and dag.index.ns, an index
        "index_counting_us_per_index":
            1e6 * (med["in"] - med["node"]) / indexes,
        "statements": statements(),
    }
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
