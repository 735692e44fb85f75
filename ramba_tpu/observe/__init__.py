"""First-class observability for the flush pipeline.

The reference ships opt-in wall-clock timers and DAG debug dumps
(/root/reference/ramba/ramba.py:923-1019,4481-4509); this package is the
rebuild's production posture on top of those seeds: every flush emits a
structured span (``events``), every subsystem increments named counters in
one registry (``registry``), hardware bring-up lands health records in the
same stream (``health``), every compiled kernel accumulates a cost ledger
entry (``ledger``), and ``RAMBA_PROFILE_DIR`` lines the whole thing up
with jax.profiler/Perfetto traces (``profile``).

Environment variables:

* ``RAMBA_TRACE=<path>`` — append one JSON object per event to ``<path>``
  (``<path>.rank<i>`` per process under multi-controller SPMD).
* ``RAMBA_TRACE_RING=<n>`` — in-memory ring size (default 256; the ring is
  always on, file output only when RAMBA_TRACE is set).
* ``RAMBA_PROFILE_DIR=<dir>`` — capture a jax.profiler trace of the whole
  process from the first flush on.  The annotations need no variable:
  under any profiler session every flush shows as ``ramba.flush.prepare``
  / ``.run`` / ``.fence`` (program label and span trace id as arguments)
  and the counted work outside the span as ``ramba.dag.infer``,
  ``ramba.read`` and ``ramba.observe.tail`` (``profile``).
* ``RAMBA_PERF`` — ``1`` adds XLA cost_analysis capture per kernel;
  ``sync`` also records synchronized execution timing.  The ledger
  itself is always on.
* ``RAMBA_PERF_WINDOW`` — length of the ledger's rolling windows
  (see ``ledger``).
* ``RAMBA_ATTRIB=off`` — disable the always-on ``block_until_ready``
  device fence the stage waterfalls use (``attrib``).
* ``RAMBA_TRACE_SAMPLE=<N>`` — head-sample the JSONL trace file to
  1-in-N trace chains (the in-memory ring stays full-fidelity); chains
  that end in an incident (flush_error, shed, degrade, stall,
  integrity, slo_breach) retroactively flush their buffered span
  chain — the tail latch (``events``).
* ``RAMBA_TRACE_BUFFER=<n>`` — pending-line bound of the buffered trace
  writer (default 2048); overflow drops lines and counts
  ``events.write_dropped`` instead of blocking the flush path.
* ``RAMBA_FLEET_DIR`` — fleet snapshot spool: publish an atomic versioned
  ``diagnostics.snapshot()`` document to ``<dir>/<host>-<pid>-<rank>.json``
  every ``RAMBA_FLEET_INTERVAL_S`` seconds (default 5); the collector in
  ``fleet``/``scripts/fleet_collector.py`` classifies each replica
  healthy/degraded/stale/dead (``RAMBA_FLEET_STALE_X`` /
  ``RAMBA_FLEET_DEAD_X`` x interval age thresholds, defaults 1.5 / 2.0).

Every observability code path self-accounts its own wall time in
``observer`` (the observer-tax ledger): exported as
``ramba_observer_seconds_total{component}`` and
``ramba_observer_tax_frac``.

Public read API lives in ``ramba_tpu.diagnostics`` (``perf_report()`` for
the ledger, including the ``attribution`` section); the fleet-level read
API is ``ramba_tpu.observe.fleet`` (``health()`` / ``rollup()``).
"""

from ramba_tpu.observe import attrib, events, fleet, health, ledger, observer, profile, registry  # noqa: F401
