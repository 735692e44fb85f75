"""Shared helpers for the two numerics legs (round-3 verdict weak #5).

The default leg runs ``jax_enable_x64=True`` so differential tests compare
against NumPy bit-for-bit.  The ``RAMBA_TEST_X64=0`` leg runs the regime
that actually executes on a TPU: jax truncates 64-bit dtypes to 32-bit
(float64→float32, int64→int32, ...), so

* expected *dtypes* must be mapped through jax's truncation lattice
  (``map_dtype``), and
* *value* tolerances must account for float32 arithmetic
  (``default_rtol``/``default_atol``) — value semantics are still checked,
  only the precision differs.
"""

import numpy as np


def x64_enabled() -> bool:
    import jax

    return bool(jax.config.jax_enable_x64)


_TRUNC = {
    np.dtype(np.float64): np.dtype(np.float32),
    np.dtype(np.int64): np.dtype(np.int32),
    np.dtype(np.uint64): np.dtype(np.uint32),
    np.dtype(np.complex128): np.dtype(np.complex64),
}


def map_dtype(dt):
    """Expected dtype under the active regime: identity when x64 is on,
    jax's 64→32-bit truncation lattice when off."""
    dt = np.dtype(dt)
    if x64_enabled():
        return dt
    return _TRUNC.get(dt, dt)


def default_rtol(rtol=None):
    """Comparison rtol for the active regime.  Under x64 callers' tight
    defaults stand; under x32 float32 arithmetic plus reduction
    accumulation needs ~1e-4."""
    if x64_enabled():
        return 1e-10 if rtol is None else rtol
    return max(1e-4, rtol or 0.0)


def default_atol(atol=None):
    if x64_enabled():
        return 1e-12 if atol is None else atol
    return max(1e-4, atol or 0.0)


def oracle():
    """Differential oracle for the active regime: numpy under x64 (NumPy
    semantics are the contract there), jax.numpy under x32 (on TPU the jax
    lattice IS the documented dtype contract — see SURVEY §2.9 note)."""
    if x64_enabled():
        return np
    import jax.numpy as jnp

    return jnp


def local_shard_count() -> int:
    """Expected number of ADDRESSABLE shards of a default-sharded array:
    all workers single-controller, this process's slice of them under the
    cross-process leg (RAMBA_TEST_PROCS)."""
    import jax

    import ramba_tpu as rt

    return max(1, rt.num_workers() // jax.process_count())


def driver_write(fn) -> None:
    """Run a host-side file write once (driver rank) with a cross-process
    barrier — for tests that prepare input files by hand.  Single-process:
    just runs fn."""
    from ramba_tpu.fileio import _driver_write_barrier

    _driver_write_barrier(fn)


def prk_star_kernel(r=2):
    """A fresh function object of the PRK star stencil of radius ``r``
    (weights 1/(2jr), so one sweep of ``i + j`` adds 2 to the norm a
    point); wrap it with ``rt.stencil``."""
    def star(a):
        acc = None
        for j in range(1, r + 1):
            term = (1.0 / (2 * j * r)) * (a[0, j] - a[0, -j]
                                          + a[j, 0] - a[-j, 0])
            acc = term if acc is None else acc + term
        return acc

    return star


def profiled_host_lines(logdir, body) -> dict:
    """Run ``body()`` under a ``jax.profiler`` session the CALLER starts
    (no RAMBA_* variable involved) and return the trace's host lines:
    ``{line name: [(name, start_ns, end_ns, stats)]}`` from the
    ``xplane.pb``, TraceMe arguments as the ``stats`` dict."""
    import glob
    import os
    import warnings

    import jax
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(logdir), profiler_options=opts)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    files = glob.glob(os.path.join(str(logdir), "**", "*.xplane.pb"),
                      recursive=True)
    assert files, "the profiler wrote no xplane.pb"
    lines = {}
    with warnings.catch_warnings():
        # jaxlib's event_stats type warns once when it is first built
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in ProfileData.from_file(files[0]).planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                lines.setdefault(line.name, []).extend(
                    (e.name, e.start_ns, e.start_ns + e.duration_ns,
                     dict(e.stats)) for e in line.events)
    return lines
