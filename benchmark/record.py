"""The repo's own record of what a block of work did, and the comparisons
with NumPy that decide ``correct``.

Copied from ``chip_smoke.py`` (PR 21: what ``Recorder.require_clean``
checks, ``_require_sharded``, the band and window pickers) so that later
PRs may change the smoke script without moving the yardstick.  What
differs: the harness keeps one event tap for the whole run and cuts it per
solve, and the windows are partly drawn from ``--seed``.
"""

from __future__ import annotations

import numpy


class BenchFailure(AssertionError):
    """A check of the benchmark did not hold."""


def require(cond, msg):
    if not cond:
        raise BenchFailure(msg)


#: counters that move only when work left the path it claims to be on
HIDING_COUNTERS = ("skeletons.host_fallback", "stencil.degraded",
                   "resilience.retries", "resilience.degrade",
                   "resilience.host_committed", "memory.admission_rejects")

#: event types that mean a flush did not run as one fused program
_BAD_EVENTS = ("degrade", "fault", "flush_error")


def counter_delta(before, after):
    return {k: v - before.get(k, 0) for k, v in after.items()
            if v != before.get(k, 0)}


def flushes(events):
    return [e for e in events if e.get("type") == "flush"]


def unclean(events, counters, *, interpret_ok):
    """Why this block of work did not run as the path it claims: a flush
    below the fused rung, a degrade/retry/fault/admission event, a host
    fallback, or (on the chip) an interpreted Pallas kernel.  None when
    it ran clean."""
    bad = [e for e in events if e.get("type") in _BAD_EVENTS
           or (e.get("type") == "memory"
               and e.get("action") in ("watermark", "reject", "spill",
                                       "oom_evict"))]
    if bad:
        return f"degrade/fault/admission events: {bad[:3]}"
    rungs = sorted({f.get("degraded", "fused") for f in flushes(events)})
    if rungs not in ([], ["fused"]):
        return f"flush ran below the fused rung: {rungs}"
    moved = {n: d for n, d in counters.items() if d > 0 and (
        n.startswith(HIDING_COUNTERS)
        or (n.endswith(".interpret") and not interpret_ok))}
    if moved:
        return f"counters moved: {moved}"
    if not interpret_ok:
        interp = [k for f in flushes(events) for k in f.get("kernels", ())
                  if k.get("interpret")]
        if interp:
            return f"interpreted kernels: {interp}"
    return None


def kernel_paths(counters):
    """The ``stencil.path.*`` counters a block moved, as a sorted tuple
    of path names.  Counters move on every flush, cache hit or not; the
    span's ``kernels`` only when the flush traced."""
    prefix = "stencil.path."
    return tuple(sorted(n[len(prefix):] for n, d in counters.items()
                        if n.startswith(prefix) and d > 0))


def require_sharded(rt, arr, what):
    """``arr`` is laid out over every device of the live mesh: a
    NamedSharding on that mesh in the program's default layout, shards on
    ``len(jax.devices())`` distinct devices, each holding 1/ndev of it:
    nothing replicated, nothing whole on device 0.  Returns the
    PartitionSpec."""
    import jax
    from jax.sharding import NamedSharding

    from ramba_tpu.parallel import mesh as _mesh

    v = arr._value()
    ndev = len(jax.devices())
    require(isinstance(v.sharding, NamedSharding),
            f"{what}: sharding is {type(v.sharding).__name__}")
    require(v.sharding.mesh.devices.size == ndev,
            f"{what}: sharded over a mesh of {v.sharding.mesh.devices.size}")
    expected = NamedSharding(rt.get_mesh(), _mesh.default_spec(v.shape))
    require(v.sharding.is_equivalent_to(expected, v.ndim),
            f"{what}: sharding {v.sharding.spec} != default "
            f"{expected.spec} for shape {v.shape}")
    shards = v.addressable_shards
    devices = {s.device for s in shards}
    require(len(devices) == ndev,
            f"{what}: shards on {len(devices)} devices, want {ndev}")
    for s in shards:
        require(s.data.size * ndev <= v.size + ndev * max(v.shape),
                f"{what}: a shard holds {s.data.size} of {v.size} "
                f"elements on {s.device}")
    return v.sharding.spec


def window_starts(arr, axis, width, rng, n_seeded):
    """Starts of windows of ``width`` along ``axis`` to compare with
    NumPy: both ends, one straddling every shard boundary along that
    axis, and ``n_seeded`` drawn from the seed."""
    n = arr.shape[axis]
    width = min(width, n)
    starts = {0, n - width}
    for s in arr._value().addressable_shards:
        b = s.index[axis].start or 0
        if 0 < b < n:
            starts.add(max(0, min(n - width, b - width // 2)))
    if n > width:
        starts.update(int(x) for x in rng.integers(0, n - width, n_seeded))
    return sorted(starts)


def tol(dtype):
    """Elementwise tolerance for a handful of roundings in ``dtype``."""
    return 64 * float(numpy.finfo(dtype).eps)
