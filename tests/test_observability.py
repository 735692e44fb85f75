"""Observability: flush spans, the counter registry, and the trace sink.

Covers the ``ramba_tpu.observe`` package + ``ramba_tpu.diagnostics``:

* every flush emits a span into the in-memory ring with compile/execute
  attribution and a cache flag (miss on first compile, hit on re-run),
* named counters fire for rewrite-rule applications and smap host
  fallbacks,
* ``RAMBA_TRACE=<path>`` produces a valid JSONL file with exactly one
  record per flush (checked in a subprocess so the env var is read at
  import, as in production), and ``scripts/trace_report.py`` summarizes it,
* with tracing disabled the ring still records spans but no file is
  touched.
"""

import gc
import json
import os
import subprocess
import sys
import threading
import time
import warnings

import numpy as np
import pytest

import jax as _jax
import ramba_tpu as rt
from ramba_tpu import common, diagnostics
from ramba_tpu.core import expr, fuser
from ramba_tpu.observe import events

_MULTIPROC = _jax.process_count() > 1

_SPAN_KEYS = (
    "label", "instrs", "n_leaves", "linearize_s", "rewrite_fires",
    "donated", "leaf_bytes", "out_bytes", "segments", "cache",
    "compile_s", "execute_s", "wall_s", "calls",
)


def _run_chain():
    a = rt.arange(512) * 3.0 + 1.0
    return float(rt.sum(a))


def test_flush_span_miss_then_hit():
    fuser.flush()  # drain unrelated pending work
    fuser._compile_cache.clear()
    before = diagnostics.counters()

    v1 = _run_chain()
    span1 = diagnostics.last_flushes(1)[0]
    for k in _SPAN_KEYS:
        assert k in span1, f"flush span missing {k!r}"
    assert span1["type"] == "flush"
    assert span1["cache"] == "miss"
    assert span1["compile_s"] > 0.0
    assert span1["instrs"] >= 1
    assert span1["wall_s"] >= span1["compile_s"]
    assert span1["calls"] and span1["calls"][0]["cache"] == "miss"

    v2 = _run_chain()
    span2 = diagnostics.last_flushes(1)[0]
    assert span2 is not span1
    assert span2["label"] == span1["label"]
    assert span2["cache"] == "hit"
    assert span2["compile_s"] == 0.0
    assert span2["execute_s"] > 0.0
    assert v1 == v2

    after = diagnostics.counters()
    assert after.get("fuser.cache_miss", 0) >= before.get("fuser.cache_miss", 0) + 1
    assert after.get("fuser.cache_hit", 0) >= before.get("fuser.cache_hit", 0) + 1
    assert after.get("fuser.flushes", 0) >= before.get("fuser.flushes", 0) + 2


@pytest.mark.skipif(
    not common.rewrite_enabled, reason="graph rewrites disabled by env"
)
def test_rewrite_fire_counter_and_span():
    fuser.flush()
    before = diagnostics.counters().get("rewrite.rewrite_arange_reshape", 0)
    r = rt.arange(4096).reshape(64, 64)
    np.asarray(r)
    after = diagnostics.counters().get("rewrite.rewrite_arange_reshape", 0)
    assert after >= before + 1
    span = diagnostics.last_flushes(1)[0]
    assert span["rewrite_fires"].get("rewrite_arange_reshape", 0) >= 1


@pytest.mark.skipif(
    _MULTIPROC,
    reason="pure_callback host fallback is single-controller only",
)
def test_host_fallback_counter():
    def countdown(x):
        n = x
        while n > 0:
            n = n - 1.0
        return n

    before = diagnostics.counters().get("skeletons.host_fallback", 0)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        out = np.asarray(rt.smap(countdown, [2.5, -1.0, 0.5]))
    np.testing.assert_allclose(out, [-0.5, -1.0, -0.5])
    after = diagnostics.counters().get("skeletons.host_fallback", 0)
    assert after >= before + 1


def test_branch_lowered_counter():
    before = diagnostics.counters().get("skeletons.branch_lowered", 0)
    out = np.asarray(rt.smap(lambda x: x + 1 if x > 0 else x - 1, [1.0, -1.0]))
    np.testing.assert_allclose(out, [2.0, -2.0])
    after = diagnostics.counters().get("skeletons.branch_lowered", 0)
    assert after >= before + 1


def test_diagnostics_report_and_dump(tmp_path, capsys):
    _run_chain()
    import io

    buf = io.StringIO()
    diagnostics.report(file=buf)
    text = buf.getvalue()
    assert "ramba_tpu diagnostics" in text
    assert "counters" in text
    rank = os.environ.get("RAMBA_TEST_PROC_ID", "0")
    p = diagnostics.dump(str(tmp_path / f"diag_{rank}.json"))
    with open(p) as f:
        snap = json.load(f)
    assert "counters" in snap and "events" in snap


def test_trace_jsonl_one_record_per_flush(tmp_path):
    rank = os.environ.get("RAMBA_TEST_PROC_ID", "0")
    path = tmp_path / f"trace_{rank}.jsonl"
    code = (
        "import numpy as np\n"
        "import ramba_tpu as rt\n"
        "a = rt.arange(256) * 2.0\n"
        "float(rt.sum(a))\n"
        "b = rt.arange(256) * 2.0\n"
        "float(rt.sum(b))\n"
        "np.asarray(rt.arange(1024).reshape(32, 32))\n"
        "from ramba_tpu.core import fuser\n"
        "print('FLUSHES=%d' % fuser.stats['flushes'])\n"
    )
    env = dict(os.environ)
    for k in ("RAMBA_TEST_PROCS", "RAMBA_TEST_PROC_ID", "RAMBA_TEST_COORD",
              "RAMBA_TEST_SHARED_TMP", "RAMBA_PROFILE_DIR"):
        env.pop(k, None)
    env["JAX_PLATFORMS"] = "cpu"
    env["RAMBA_TRACE"] = str(path)
    r = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True,
        text=True, timeout=300,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    n_flushes = int(r.stdout.strip().rsplit("FLUSHES=", 1)[1])

    lines = [ln for ln in path.read_text().splitlines() if ln.strip()]
    evs = [json.loads(ln) for ln in lines]  # every line must parse
    flushes = [e for e in evs if e.get("type") == "flush"]
    assert len(flushes) == n_flushes
    for f in flushes:
        for k in _SPAN_KEYS:
            assert k in f, f"trace record missing {k!r}"
        assert f["cache"] in ("hit", "miss")
    # identical chains: first compiles, second hits the cache
    assert flushes[0]["cache"] == "miss"
    assert any(f["cache"] == "hit" for f in flushes)

    rep = subprocess.run(
        [sys.executable,
         os.path.join(os.path.dirname(__file__), "..", "scripts",
                      "trace_report.py"),
         str(path)],
        capture_output=True, text=True, timeout=120,
    )
    assert rep.returncode == 0, rep.stderr[-2000:]
    assert "flushes:" in rep.stdout
    assert "cache:" in rep.stdout


@pytest.mark.skipif(
    bool(os.environ.get("RAMBA_TRACE")),
    reason="this process has tracing enabled (two-process trace leg)",
)
def test_disabled_trace_writes_no_file():
    assert not events.trace_enabled()
    n0 = len(events.ring)
    _run_chain()
    assert len(events.ring) > n0 or events.ring.maxlen == len(events.ring)
    assert events._trace_file is None  # no sink ever opened


# ---------------------------------------------------------------------------
# the time outside the flush span: dag.infer (misses only: a node whose
# function and avals were seen before is not inferred again), read,
# observe.tail
# ---------------------------------------------------------------------------

_OUTSIDE = ("dag.infer", "read", "observe.tail")


def _outside_delta(before):
    """{counter: movement} of the three spans' counter pairs."""
    after = diagnostics.counters()
    return {f"{n}.{k}": after.get(f"{n}.{k}", 0) - before.get(f"{n}.{k}", 0)
            for n in _OUTSIDE for k in ("n", "ns")}


def _prk_toy(star, iterations, n=64, r=2):
    i = rt.arange(n, dtype=np.float32)
    A = i[:, None] + i[None, :]
    B = rt.zeros((n, n), dtype=np.float32)
    rt.sync()
    before = diagnostics.counters()
    for _ in range(iterations):
        B += rt.sstencil(star, A)
        A += 1.0
    norm = float(rt.sum(abs(B))) / (n - 2 * r) ** 2
    return norm, _outside_delta(before)


def test_dag_infer_counts_stencil_misses():
    from tests.helpers import prk_star_kernel

    """The stencil node's static holds the kernel's function, which the
    memo keys by identity: the first sight of a kernel over given avals
    misses, with its time; every repeat hits, however many iterations."""
    star = rt.stencil(prk_star_kernel())
    hits0 = diagnostics.counters().get("dag.infer.hit", 0)
    norm, moved = _prk_toy(star, 3)
    assert norm == pytest.approx(6.0, rel=1e-5)
    assert moved["dag.infer.n"] >= 1  # the kernel; the loop's other nodes
    assert moved["dag.infer.ns"] > 0  # too where no test built them before
    norm, moved = _prk_toy(star, 5)
    assert norm == pytest.approx(10.0, rel=1e-5)
    assert moved["dag.infer.n"] == 0 and moved["dag.infer.ns"] == 0
    assert diagnostics.counters()["dag.infer.hit"] - hits0 >= 5 + 5 + 5
    # another function object over the same avals: one miss, not one an
    # iteration
    norm, moved = _prk_toy(rt.stencil(prk_star_kernel()), 4)
    assert norm == pytest.approx(8.0, rel=1e-5)
    assert moved["dag.infer.n"] == 1 and moved["dag.infer.ns"] > 0


def test_dag_infer_memo_hits_move_nothing():
    x = rt.arange(512)
    rt.sync()

    def chain():
        return float(rt.sum(rt.sin(x) + x * x))

    want = chain()  # fills the memo: every node of the repeat hits
    before = diagnostics.counters()
    assert chain() == want
    moved = _outside_delta(before)
    assert moved["dag.infer.n"] == 0 and moved["dag.infer.ns"] == 0
    # ... while the read and the observers' tail of that solve did count
    assert moved["read.n"] == 1 and moved["read.ns"] > 0
    assert moved["observe.tail.n"] >= 1


@pytest.mark.parametrize("read", [
    lambda a: a.asarray(),
    lambda a: float(a[3]),
    lambda a: np.asarray(a),
    lambda a: int(rt.sum(a)),
], ids=["asarray", "float", "np.asarray", "int-of-sum"])
def test_each_read_moves_read_n_by_one(read):
    a = rt.arange(64) + 1
    rt.sync()
    before = diagnostics.counters()
    read(a)
    moved = _outside_delta(before)
    assert moved["read.n"] == 1
    assert moved["read.ns"] > 0


def test_observe_tail_counts_flush_spans():
    fuser.flush()
    seen = []
    events.add_tap(seen.append)
    try:
        before = diagnostics.counters()
        a = rt.arange(256) * 2.0
        rt.sync()
        b = a + 1.0
        float(rt.sum(b))
        float(b[5])
        moved = _outside_delta(before)
    finally:
        events.remove_tap(seen.append)
    spans = [e for e in seen if e.get("type") == "flush"]
    assert len(spans) >= 3
    assert moved["observe.tail.n"] == len(spans)
    assert moved["observe.tail.ns"] > 0
    # the tail starts where the span's wall clock stops
    assert all("wall_s" in s for s in spans)


# ---------------------------------------------------------------------------
# the lazy-DAG layer counted where its work happens: dag.node (every node,
# hit or miss), dag.index, dag.build (first pending node to the flush),
# host.gc (the collector's pauses)
# ---------------------------------------------------------------------------


def _moved(before, *names):
    after = diagnostics.counters()
    return {n: after.get(n, 0) - before.get(n, 0) for n in names}


def _reachable_nodes(e, seen=None):
    seen = {} if seen is None else seen
    if isinstance(e, expr.Node) and id(e) not in seen:
        seen[id(e)] = e
        for a in e.args:
            _reachable_nodes(a, seen)
    return seen


@pytest.mark.parametrize("k", [1, 7, 40])
def test_dag_node_counts_every_node_hit_or_miss(k):
    x = rt.arange(256, dtype=np.float32)
    rt.sync()

    def build():
        a = x
        for i in range(k):
            a = a * 2.0 + x if i % 2 else rt.sin(a)
        return a

    names = ("dag.node.n", "dag.node.ns", "dag.infer.n", "dag.infer.hit")
    before = diagnostics.counters()
    first = build()
    m1 = _moved(before, *names)
    nodes = len(_reachable_nodes(first.read_expr()))
    assert nodes >= k
    before = diagnostics.counters()
    second = build()
    m2 = _moved(before, *names)
    # the second build infers nothing and still counts every node and its
    # constructor's time: the memo's key and lookup are inference's cost
    assert m1["dag.node.n"] == m2["dag.node.n"] == nodes
    assert m2["dag.infer.n"] == 0 and m2["dag.infer.hit"] >= nodes
    assert m2["dag.node.ns"] > 0
    assert float(rt.sum(first - second)) == 0.0


def test_dag_index_counts_each_lowered_index():
    a = rt.zeros(64, dtype=np.float32)
    b = rt.arange(64, dtype=np.float32)
    rt.sync()
    before = diagnostics.counters()
    a[1:-1] = b[2:]
    moved = _moved(before, "dag.index.n", "dag.index.ns")
    assert moved["dag.index.n"] == 2 and moved["dag.index.ns"] > 0
    before = diagnostics.counters()
    with pytest.raises(IndexError):
        a[64]
    assert _moved(before, "dag.index.n")["dag.index.n"] == 1
    np.testing.assert_array_equal(
        a.asarray(), np.r_[0.0, np.arange(2, 64), 0.0].astype(np.float32))


def test_dag_build_is_the_host_time_from_first_node_to_flush():
    fuser.flush()
    x = rt.arange(300, dtype=np.float32)
    rt.sync()
    names = ("dag.build.n", "dag.build.ns")
    before = diagnostics.counters()
    fuser.flush()  # nothing pending: no phase, nothing counted
    assert _moved(before, *names) == {"dag.build.n": 0, "dag.build.ns": 0}
    t0 = time.perf_counter_ns()
    y = x * 3.0
    z = y + x
    assert fuser.default_stream()._build is not None
    time.sleep(0.002)
    fuser.flush()
    host = time.perf_counter_ns() - t0
    moved = _moved(before, *names)
    assert moved["dag.build.n"] == 1
    assert 2_000_000 <= moved["dag.build.ns"] <= host
    assert fuser.default_stream()._build is None
    # a read of a view of a materialized array builds its node inside the
    # flush, with nothing pending: no phase opens for it
    float(z[5])
    assert _moved(before, *names)["dag.build.n"] == 1
    # ... and a flush per pending build counts one each
    for _ in range(3):
        float(rt.sum(z * 2.0))
    assert _moved(before, *names)["dag.build.n"] == 4
    del y


def test_host_gc_counts_a_forced_collection():
    names = ("host.gc.n", "host.gc.gen2.n", "host.gc.ns")
    enabled = gc.isenabled()
    gc.disable()
    try:
        before = diagnostics.counters()
        assert _moved(before, *names) == dict.fromkeys(names, 0)
        gc.collect()
        moved = _moved(before, *names)
        assert moved["host.gc.n"] == 1 and moved["host.gc.gen2.n"] == 1
        assert moved["host.gc.ns"] > 0
        before = diagnostics.counters()
        gc.collect(0)
        moved = _moved(before, *names)
        assert moved["host.gc.n"] == 1 and moved["host.gc.gen2.n"] == 0
    finally:
        if enabled:
            gc.enable()


def test_a_build_phase_closed_on_another_thread_still_counts():
    stream = fuser.FlushStream(name="built-here-flushed-there")
    x = rt.arange(128, dtype=np.float32)
    rt.sync()
    before = diagnostics.counters()
    with fuser.stream_scope(stream):
        y = x + 5.0
    assert stream._build is not None
    errors = []

    def flush():
        try:
            stream.flush()
        except Exception as e:  # pragma: no cover - the assertion below
            errors.append(e)

    t = threading.Thread(target=flush)
    t.start()
    t.join()
    assert not errors
    moved = _moved(before, "dag.build.n", "dag.build.ns")
    assert moved["dag.build.n"] == 1 and moved["dag.build.ns"] > 0
    assert stream._build is None
    np.testing.assert_array_equal(
        y.asarray(), np.arange(128, dtype=np.float32) + 5.0)


@pytest.mark.skipif(_MULTIPROC, reason="one profiler session per process")
def test_build_phase_and_collector_on_the_profilers_host_line(tmp_path):
    """``ramba.dag.build`` runs from the first pending node to where
    ``ramba.flush.prepare`` begins, and a pause of the collector shows
    inside whichever span it fell in."""
    from tests.helpers import profiled_host_lines

    x = rt.arange(515, dtype=np.float32)
    rt.sync()
    float(rt.sum(x * 2.0 + 1.0))  # compiled outside the session

    def body():
        with _jax.profiler.TraceAnnotation("test_outer"):
            y = x * 2.0
            gc.collect()
            float(rt.sum(y + 1.0))

    lines = profiled_host_lines(tmp_path, body)
    mine = [evs for evs in lines.values()
            if any(name == "test_outer" for name, *_ in evs)]
    assert len(mine) == 1
    by_name = {}
    for name, a, b, stats in mine[0]:
        by_name.setdefault(name, []).append((a, b, stats))
    (outer,) = by_name["test_outer"]
    (build,) = [e for e in by_name["ramba.dag.build"]
                if outer[0] <= e[0] and e[1] <= outer[1]]
    prepare = by_name["ramba.flush.prepare"][-1]
    assert build[1] <= prepare[0]
    assert prepare[0] - build[1] < 1_000_000  # nothing of ours between
    pauses = [e for e in by_name["ramba.host.gc"]
              if build[0] <= e[0] and e[1] <= build[1]]
    assert pauses and any(p[2].get("generation") in (2, "2")
                          for p in pauses)


@pytest.mark.skipif(_MULTIPROC, reason="one profiler session per process")
def test_annotations_reach_a_callers_profiler_session(tmp_path):
    """With no RAMBA_* variable set, a session the caller starts sees the
    program's names on the caller's host line, inside the caller's own
    annotation: what the benchmark's traced stretch names gaps from."""
    from tests.helpers import profiled_host_lines

    for var in ("RAMBA_PROFILE_DIR", "RAMBA_PROFILE", "RAMBA_TIMING"):
        assert not os.environ.get(var), var
    x = rt.arange(509)
    rt.sync()
    float(rt.sum(x * 2.0))  # compiled outside the session

    def body():
        with _jax.profiler.TraceAnnotation("test_outer"):
            expr.infer_aval(  # a shape no test builds: an inference miss
                "reshape", ((509, 1, 1, 1),), [x.read_expr().aval])
            float(rt.sum(x * 2.0))

    lines = profiled_host_lines(tmp_path, body)
    mine = [evs for evs in lines.values()
            if any(name == "test_outer" for name, *_ in evs)]
    assert len(mine) == 1, "the caller's host line carries its annotation"
    outer = [e for e in mine[0] if e[0] == "test_outer"][0]
    by_name = {}
    for name, a, b, stats in mine[0]:
        if name.startswith("ramba."):
            assert outer[1] <= a and b <= outer[2], name
            by_name.setdefault(name, []).append((a, b, stats))
    for name in ("ramba.flush.prepare", "ramba.flush.run",
                 "ramba.flush.fence", "ramba.read", "ramba.dag.infer",
                 "ramba.observe.tail"):
        assert name in by_name, (name, sorted(by_name))
    # one stable name per stage; the program's label rides as an argument
    label = diagnostics.last_flushes(1)[0]["label"]
    for stage in ("prepare", "run", "fence"):
        assert by_name[f"ramba.flush.{stage}"][-1][2].get("label") == label
    # the fence sits inside the run, the read after the flush
    run, fence = by_name["ramba.flush.run"][-1], by_name["ramba.flush.fence"][-1]
    assert run[0] <= fence[0] and fence[1] <= run[1]
    assert by_name["ramba.read"][-1][0] >= run[1]
