"""Memory-pressure governor: budget, ledger, spill, admission, chunked rung.

Covers ``ramba_tpu.resilience.memory`` + its fuser integration:

* ``common.parse_bytes`` grammar and the ``RAMBA_HBM_BUDGET`` /
  ``RAMBA_HBM_WATERMARK`` / ``RAMBA_CHUNK_BYTES`` knobs,
* the live-bytes ledger riding the fuser's owner census (incref/decref
  deltas, peak high-water mark),
* host spill + transparent restore-on-touch, asserted bit-exact and via
  the host-boundary transfer counters,
* pre-flush admission control under a tight budget: evict, then route to
  the ``chunked`` rung — result identical to NumPy, with the flush span
  and ``memory.*`` counters recording the decision,
* the budgetless default: the fused fast path runs with zero extra
  transfers and zero governor counters,
* oom-class recovery: evict → drop one rung → retry, and the
  ``bytes=`` fault payload the eviction sizing keys on,
* the byte-bounded segmenter backing the ``chunked`` rung.
"""

import numpy as np
import pytest

import jax as _jax
import ramba_tpu as rt
from ramba_tpu import common, diagnostics
from ramba_tpu.core import fuser
from ramba_tpu.observe import registry
from ramba_tpu.resilience import faults, memory, spill
from ramba_tpu.utils import timing

_MULTIPROC = _jax.process_count() > 1


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    """No leaked fault plans or budget env between tests; fast backoff."""
    monkeypatch.setenv("RAMBA_RETRY_BASE_S", "0.001")
    monkeypatch.delenv("RAMBA_HBM_BUDGET", raising=False)
    monkeypatch.delenv("RAMBA_HBM_WATERMARK", raising=False)
    monkeypatch.delenv("RAMBA_HBM_ESTIMATE", raising=False)
    monkeypatch.delenv("RAMBA_CHUNK_BYTES", raising=False)
    faults.configure(None)
    yield
    faults.reset()


# -- parse_bytes / knobs -----------------------------------------------------


def test_parse_bytes_grammar():
    assert common.parse_bytes("1g") == 1 << 30
    assert common.parse_bytes("512k") == 512 << 10
    assert common.parse_bytes("1.5m") == int(1.5 * (1 << 20))
    assert common.parse_bytes("2kb") == 2048
    assert common.parse_bytes("2kib") == 2048
    assert common.parse_bytes("4T") == 4 << 40
    assert common.parse_bytes("64") == 64
    assert common.parse_bytes(128) == 128
    for bad in ("", "abc", "12q"):
        with pytest.raises(ValueError):
            common.parse_bytes(bad)


def test_budget_watermark_chunk_env(monkeypatch):
    monkeypatch.setenv("RAMBA_HBM_BUDGET", "1m")
    assert memory.budget_bytes() == 1 << 20
    assert memory.watermark_bytes() == int((1 << 20) * 0.9)
    monkeypatch.setenv("RAMBA_HBM_WATERMARK", "0.5")
    assert memory.watermark_bytes() == 1 << 19
    monkeypatch.setenv("RAMBA_HBM_WATERMARK", "700k")
    assert memory.watermark_bytes() == 700 << 10
    monkeypatch.setenv("RAMBA_CHUNK_BYTES", "128k")
    assert memory.chunk_target_bytes() == 128 << 10
    monkeypatch.delenv("RAMBA_CHUNK_BYTES")
    monkeypatch.setenv("RAMBA_HBM_WATERMARK", "0.5")
    assert memory.chunk_target_bytes() == max(1 << 16, (1 << 19) // 4)


def test_no_budget_on_cpu_default():
    # CPU backends report no bytes_limit and the env is clean (fixture):
    # the governor must be disabled, not guessing.
    if memory.device_budget_bytes() is None:
        assert memory.budget_bytes() is None
        assert memory.watermark_bytes() is None


# -- the ledger --------------------------------------------------------------


def test_ledger_tracks_realized_leaves():
    import gc

    fuser.flush()
    gc.collect()  # arrays an earlier file left in cycles die now, not below
    before = memory.ledger.live_bytes
    x = rt.fromarray(np.ones(1024, np.float32))
    rt.sync()
    assert memory.ledger.live_bytes == before + 4096
    assert memory.ledger.peak_live_bytes >= before + 4096
    del x
    assert memory.ledger.live_bytes == before


def test_memory_report_shape():
    fuser.flush()
    x = rt.fromarray(np.ones((32, 32), np.float32))
    rt.sync()
    rep = diagnostics.memory_report(top=100)
    for key in ("budget_bytes", "watermark_bytes", "live_bytes",
                "spilled_bytes", "pinned_bytes", "peak_live_bytes",
                "evictions", "restores", "arrays", "top"):
        assert key in rep, key
    assert rep["arrays"] >= 1
    assert any(r["nbytes"] == 4096 for r in rep["top"])
    assert diagnostics.snapshot()["memory"]["arrays"] >= 1
    del x


# -- spill / restore ---------------------------------------------------------


@pytest.mark.skipif(_MULTIPROC, reason="spill requires fully-addressable "
                    "arrays (single-controller)")
def test_spill_restore_round_trip_with_transfer_counters():
    fuser.flush()
    data = np.random.RandomState(1).rand(64, 64).astype(np.float32)
    x = rt.fromarray(data)
    rt.sync()
    d2h0 = timing.comm_stats["device_to_host_bytes"]
    h2d0 = timing.comm_stats["host_to_device_bytes"]
    restores0 = memory.ledger.restores
    freed = memory.ledger.evict_until(memory.ledger.live_bytes or 1)
    assert freed >= data.nbytes
    assert isinstance(x._expr.value, spill.SpilledArray)
    assert memory.ledger.spilled_bytes >= data.nbytes
    assert timing.comm_stats["device_to_host_bytes"] - d2h0 >= data.nbytes
    # touch restores transparently, bit-exact
    out = np.asarray(x)
    np.testing.assert_array_equal(out, data)
    assert isinstance(x._expr.value, _jax.Array)
    assert memory.ledger.restores == restores0 + 1
    assert timing.comm_stats["host_to_device_bytes"] - h2d0 >= data.nbytes
    del x


@pytest.mark.skipif(_MULTIPROC, reason="spill requires fully-addressable "
                    "arrays (single-controller)")
def test_spilled_leaf_computes_correctly():
    # A chain whose LEAF is currently spilled must flush correctly: the
    # flush leaf-gather restores it before execution.
    fuser.flush()
    data = np.arange(2048, dtype=np.float32)
    x = rt.fromarray(data)
    rt.sync()
    memory.ledger.evict_until(memory.ledger.live_bytes or 1)
    assert isinstance(x._expr.value, spill.SpilledArray)
    got = float(rt.sum(x * 2.0 + 1.0))
    exp = float(np.sum(data.astype(np.float64) * 2.0 + 1.0))
    assert got == pytest.approx(exp, rel=1e-4)
    del x


# -- admission control: the acceptance test ----------------------------------


@pytest.mark.skipif(_MULTIPROC, reason="eviction is asserted "
                    "single-controller; SPMD runs the --memory-leg instead")
def test_tight_budget_evicts_and_routes_chunked(monkeypatch):
    fuser.flush()
    # a cold 256 KB array the governor can evict...
    cold_np = np.random.RandomState(2).rand(256, 256).astype(np.float32)
    cold = rt.fromarray(cold_np)
    # ...and a 64 KB working set whose chain estimate alone exceeds the
    # watermark, so eviction cannot save the fused path.
    x_np = np.random.RandomState(3).rand(128, 128).astype(np.float32)
    x = rt.fromarray(x_np)
    rt.sync()
    monkeypatch.setenv("RAMBA_HBM_BUDGET", "150k")
    monkeypatch.setenv("RAMBA_HBM_ESTIMATE", "analytic")
    ev0 = registry.get("memory.evictions")
    rej0 = registry.get("memory.admission_rejects")

    y = x * 2.0 + 1.0
    z = rt.sqrt(y) + y * 0.5
    got = float(rt.sum(z))

    exp = float(np.sum(np.sqrt(x_np * 2.0 + 1.0) + (x_np * 2.0 + 1.0) * 0.5))
    assert got == pytest.approx(exp, rel=1e-3)
    span = diagnostics.last_flushes(1)[0]
    assert span.get("degraded") == "chunked", span
    assert span.get("admission") == "chunked"
    assert span.get("mem_peak_est", 0) > 0
    assert span.get("segments", 0) >= 2, span
    assert registry.get("memory.evictions") > ev0
    assert registry.get("memory.admission_rejects") == rej0 + 1
    assert isinstance(cold._expr.value, spill.SpilledArray)
    evs = [e for e in diagnostics.snapshot()["events"]
           if e.get("type") == "memory"]
    actions = {e.get("action") for e in evs}
    assert {"admit", "watermark", "spill", "reject"} <= actions, actions
    # the evicted array survives, transparently restored on touch
    np.testing.assert_array_equal(np.asarray(cold), cold_np)
    del x, cold


def test_roomy_budget_admits_fused(monkeypatch):
    fuser.flush()
    monkeypatch.setenv("RAMBA_HBM_BUDGET", "64m")
    monkeypatch.setenv("RAMBA_HBM_ESTIMATE", "analytic")
    rej0 = registry.get("memory.admission_rejects")
    got = float(rt.sum(rt.arange(1024) * 2.0 + 1.0))
    assert got == pytest.approx(float(np.sum(np.arange(1024) * 2.0 + 1.0)),
                                rel=1e-6)
    span = diagnostics.last_flushes(1)[0]
    assert "degraded" not in span
    assert "admission" not in span
    assert registry.get("memory.admission_rejects") == rej0


def test_budget_unset_is_transparent():
    # The documented CPU default: no budget -> the governor never
    # estimates, spills, or transfers.  The only host-boundary traffic is
    # the scalar fetch itself.
    fuser.flush()
    ev0 = registry.get("memory.evictions")
    rs0 = registry.get("memory.restores")
    rej0 = registry.get("memory.admission_rejects")
    h2d0 = timing.comm_stats["host_to_device_bytes"]
    d2h0 = timing.comm_stats["device_to_host_bytes"]
    got = float(rt.sum(rt.arange(2048) * 3.0 + 1.0))
    assert got == pytest.approx(float(np.sum(np.arange(2048) * 3.0 + 1.0)),
                                rel=1e-6)
    span = diagnostics.last_flushes(1)[0]
    assert "degraded" not in span
    assert "admission" not in span
    assert registry.get("memory.evictions") == ev0
    assert registry.get("memory.restores") == rs0
    assert registry.get("memory.admission_rejects") == rej0
    assert timing.comm_stats["host_to_device_bytes"] == h2d0
    # one scalar fetch, nothing array-sized
    assert timing.comm_stats["device_to_host_bytes"] - d2h0 <= 64


# -- oom-class recovery ------------------------------------------------------


def test_classify_oom_is_distinct():
    from ramba_tpu.resilience import retry

    assert retry.classify(faults.InjectedResourceExhausted("x", 1)) == "oom"
    assert retry.classify(RuntimeError("RESOURCE_EXHAUSTED: boom")) == "oom"
    assert retry.classify(RuntimeError("DEADLINE_EXCEEDED")) == "retryable"
    assert retry.classify(RuntimeError("anything else")) == "fatal"


@pytest.mark.skipif(_MULTIPROC, reason="eviction is asserted "
                    "single-controller")
def test_injected_oom_evicts_then_drops_one_rung():
    fuser.flush()
    cold_np = np.random.RandomState(4).rand(128, 128).astype(np.float32)
    cold = rt.fromarray(cold_np)
    rt.sync()
    fuser._compile_cache.clear()
    ev0 = registry.get("memory.evictions")
    with faults.inject("oom", "1"):
        got = float(rt.sum(rt.arange(1024) * 5.0 + 7.0))
    assert got == pytest.approx(float(np.sum(np.arange(1024) * 5.0 + 7.0)),
                                rel=1e-6)
    span = diagnostics.last_flushes(1)[0]
    assert span.get("degraded") == "split"
    assert registry.get("memory.evictions") > ev0
    evs = [e for e in diagnostics.snapshot()["events"]
           if e.get("type") == "memory"]
    assert any(e.get("action") == "oom_evict" for e in evs)
    np.testing.assert_array_equal(np.asarray(cold), cold_np)
    del cold


@pytest.mark.skipif(_MULTIPROC, reason="eviction is asserted "
                    "single-controller")
def test_evict_for_oom_sizes_from_bytes_hint():
    fuser.flush()
    # drain any colder residents left by earlier tests so LRU order below
    # is exactly a-then-b
    memory.ledger.evict_until(memory.ledger.live_bytes or 0)
    a = rt.fromarray(np.ones((64, 64), np.float32))   # 16 KB, colder
    b = rt.fromarray(np.ones((128, 128), np.float32))  # 64 KB, warmer
    rt.sync()
    exc = faults.InjectedResourceExhausted("oom", 1, nbytes=4096)
    freed = memory.evict_for_oom(exc)
    assert freed >= 4096
    # LRU: the colder array went first; the byte hint stopped it there
    assert isinstance(a._expr.value, spill.SpilledArray)
    assert isinstance(b._expr.value, _jax.Array)
    del a, b


def test_fault_bytes_payload():
    faults.configure("oom:once:bytes=1g")
    with pytest.raises(faults.InjectedResourceExhausted) as ei:
        faults.check("oom")
    assert ei.value.bytes == 1 << 30
    assert "allocating 1073741824 bytes" in str(ei.value)
    assert "RESOURCE_EXHAUSTED" in str(ei.value)
    with pytest.raises(ValueError):
        faults.configure("oom:once:bytes=nope")
    with pytest.raises(ValueError):
        faults.configure("oom:once:bytes=1k:bytes=2k")


# -- the byte-bounded segmenter ----------------------------------------------


def _toy_instrs(n):
    # a linear chain: instr i consumes slot i, produces slot i+1 (1 leaf)
    return [("op", None, (i,)) for i in range(n)]


def test_byte_segment_end_bounds_live_bytes():
    instrs = _toy_instrs(6)
    slot_bytes = {i: 100 for i in range(7)}
    # tiny cap: always at least one instruction per segment
    ends = []
    start = 0
    while start < 6:
        end = fuser._byte_segment_end(instrs, 1, start, slot_bytes, 1, 0)
        assert end == start + 1
        ends.append(end)
        start = end
    assert ends == [1, 2, 3, 4, 5, 6]
    # roomy cap: one segment swallows the whole chain
    assert fuser._byte_segment_end(instrs, 1, 0, slot_bytes, 10**9, 0) == 6
    # instruction cap still wins over a roomy byte cap
    assert fuser._byte_segment_end(instrs, 1, 0, slot_bytes, 10**9, 2) == 2
    # mid cap: segments stay under the byte bound
    start = 0
    while start < 6:
        end = fuser._byte_segment_end(instrs, 1, start, slot_bytes, 250, 0)
        assert start < end <= 6
        # live estimate per segment: outputs + first-seen external inputs
        assert (end - start) * 100 + 100 <= 350
        start = end


def test_chunk_bytes_env_drives_segment_count(monkeypatch):
    # No budget needed: RAMBA_CHUNK_BYTES alone sizes the chunked rung —
    # drive it directly through the degradation ladder.
    fuser.flush()
    fuser._compile_cache.clear()
    monkeypatch.setenv("RAMBA_CHUNK_BYTES", "64k")
    n = 8192
    a = rt.arange(n) * 2.0
    b = a + 1.0
    c = rt.sqrt(b) * 0.5
    with faults.active("execute:2:oom", seed=0):
        got = float(rt.sum(c))
    exp = float(np.sum(np.sqrt(np.arange(n) * 2.0 + 1.0) * 0.5))
    assert got == pytest.approx(exp, rel=1e-4)
    span = diagnostics.last_flushes(1)[0]
    # fused oomed, split oomed, chunked ran byte-bounded segments
    assert span.get("degraded") == "chunked", span
    assert span.get("chunk_bytes") == 64 << 10
