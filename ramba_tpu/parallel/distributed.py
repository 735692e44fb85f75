"""Multi-host bring-up: the reference's cluster launcher, TPU-native.

Reference (/root/reference/ramba):

* Ray mode — driver spawns RemoteState actors over a placement group and
  wires ZMQ queues (ramba.py:10650-10724).
* MPI mode — the whole user program runs SPMD on every rank; rank 0 keeps
  driver semantics via ``in_driver()`` (common.py:49-100, README.md:168-176).
* At ≥100 workers a 2-level aggregation tree batches control messages
  (NUM_WORKERS_FOR_BCAST, common.py:27; tree helpers ramba.py:1825-1850).

TPU-native: a multi-host TPU slice runs one jax process per host
(multi-controller SPMD — exactly the reference's MPI mode).  Every process
executes the same program; ``jax.distributed.initialize`` wires the hosts;
the global device mesh then spans all hosts and XLA runs collectives over
ICI within a slice and DCN across slices.  No control tree is needed — XLA's
dispatch owns cross-host coordination — matching SURVEY §2.6's note.
"""

from __future__ import annotations

import os
from typing import Optional

import jax
import numpy as np

_initialized = False


def _init_kwargs(kwargs: dict) -> dict:
    """Fold ``RAMBA_INIT_TIMEOUT_S`` into the ``jax.distributed.initialize``
    kwargs (as ``initialization_timeout``, seconds).  An explicit kwarg
    from the caller wins; a malformed or non-positive env value is
    ignored."""
    out = dict(kwargs)
    raw = os.environ.get("RAMBA_INIT_TIMEOUT_S")
    if raw:
        try:
            t = float(raw)
        except ValueError:
            t = 0.0
        if t > 0:
            out.setdefault("initialization_timeout", int(max(1, round(t))))
    return out


def initialize(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
    **kwargs,
) -> None:
    """Wire up multi-host execution (reference: worker bring-up at import,
    ramba.py:10650-10724; here explicit because jax owns process groups).

    On TPU pods the arguments are auto-detected from the environment; on
    CPU/GPU clusters pass coordinator_address/num_processes/process_id
    (or set JAX_COORDINATOR_ADDRESS etc.).  Safe to call when single-host:
    with no coordinator configured this is a no-op.
    """
    global _initialized
    if _initialized:
        return
    has_env = (
        coordinator_address is not None
        or os.environ.get("JAX_COORDINATOR_ADDRESS")
        or os.environ.get("TPU_WORKER_HOSTNAMES")
        or os.environ.get("MEGASCALE_COORDINATOR_ADDRESS")
    )
    if not has_env:
        return  # single-host: nothing to do
    # private import, checked against the installed jax 0.9.0 (no public
    # spelling exists); if it moves, this must fail loudly, not skip the check
    from jax._src import xla_bridge

    if xla_bridge.backends_are_initialized():
        # Backend already up (e.g. the process computed before calling
        # initialize); too late to form a process group — stay
        # single-controller.  The reference has the same
        # initialize-at-import-or-never shape (common.py:683-758).
        from ramba_tpu.common import dprint

        dprint(1, "ramba_tpu.distributed.initialize: backend already "
                  "initialized; staying single-process")
        return
    import time

    from ramba_tpu.observe import health as _health
    from ramba_tpu.resilience import faults as _faults
    from ramba_tpu.resilience import retry as _retry

    t0 = time.perf_counter()
    kw = _init_kwargs(kwargs)

    # (CPU multi-controller needs a cross-process collectives backend;
    # the installed jax defaults jax_cpu_collectives_implementation to
    # "gloo", so there is nothing to select here.)

    def connect():
        _faults.check("init_connect")
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
            **kw,
        )

    def cleanup():
        # a half-formed distributed client must be torn down before the
        # next connect attempt can bind the coordinator channel again
        try:
            jax.distributed.shutdown()
        except Exception:
            pass

    try:
        _retry.call("init_connect", connect, on_retry=cleanup)
    except Exception as e:
        # Health event first, then re-raise WITH the original failure
        # chained (RetryBudgetExhausted carries the last connect error as
        # __cause__) — bring-up failures must never lose their root cause.
        _health.record(
            outcome="error", error=repr(e), source="distributed_init",
            init_seconds=time.perf_counter() - t0,
            cause=repr(e.__cause__) if e.__cause__ is not None else None,
        )
        raise
    _initialized = True
    # The process group just formed: any (rank, nprocs) the event stream
    # cached from a pre-bring-up emit is stale.  Re-probe before the health
    # record below so IT already carries the authoritative rank (and lands
    # in the right per-rank trace file).
    from ramba_tpu.observe import events as _events
    from ramba_tpu.resilience import coherence as _coherence

    _events.invalidate_rank()
    _coherence.invalidate()
    _health.record(
        outcome="ok", source="distributed_init",
        init_seconds=time.perf_counter() - t0,
        process_count=jax.process_count(),
        process_index=jax.process_index(),
    )


def barrier(tag: str) -> None:
    """Cross-rank sync point (no-op single-process).  Runs under the
    elastic watchdog deadline: a rank that never arrives (crashed,
    wedged collective) turns the infinite block into a fatal-classified
    ``RankStallError`` on the ranks still alive — the signal the
    drain/resume runbook (docs/index.md) keys on."""
    if jax.process_count() <= 1:
        return
    from jax.experimental import multihost_utils

    from ramba_tpu.resilience import elastic as _elastic

    _elastic.with_deadline(
        "barrier", lambda: multihost_utils.sync_global_devices(tag))


def note_transfer(kind: str, nbytes: int) -> None:
    """Account one cross-process transfer in the observability registry
    (kind: "allgather" | "broadcast" | ...).  Call sites: ndarray.asarray's
    process_allgather, fileio's driver-write flag broadcast."""
    from ramba_tpu.observe import registry as _registry

    _registry.inc(f"distributed.{kind}_count")
    if nbytes:
        _registry.inc(f"distributed.{kind}_bytes", int(nbytes))


def shutdown() -> None:
    global _initialized
    if _initialized:
        jax.distributed.shutdown()
        _initialized = False


def in_driver() -> bool:
    """True on the coordinating process (reference: in_driver() gates
    driver-only code in MPI SPMD mode, common.py:49-100)."""
    return jax.process_index() == 0


def process_index() -> int:
    return jax.process_index()


def process_count() -> int:
    return jax.process_count()


def local_devices() -> list:
    return jax.local_devices()


def global_mesh(ici_shape: Optional[tuple] = None, axis_names=None):
    """Build a mesh spanning every host's devices.

    For multi-slice topologies, put the DCN-connected axis *first* so the
    leading (data-parallel) mesh dimension rides DCN and everything else
    stays on ICI — the layout SURVEY §2.7 calls for.
    """
    from jax.sharding import Mesh

    from ramba_tpu.parallel.mesh import balanced_factors

    devices = jax.devices()
    n = len(devices)
    if ici_shape is None:
        nproc = jax.process_count()
        if nproc > 1 and n % nproc == 0:
            ici_shape = (nproc, n // nproc)
        else:
            ici_shape = tuple(f for f in balanced_factors(n, 2) if f > 1) or (1,)
    if axis_names is None:
        axis_names = tuple(f"d{i}" for i in range(len(ici_shape)))
    return Mesh(np.array(devices).reshape(ici_shape), axis_names=axis_names)
