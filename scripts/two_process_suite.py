"""Cross-process full-suite leg (round-4 verdict #4).

The reference CI runs its ENTIRE test suite on a 2-worker cluster
(`mpiexec -n 2`, /root/reference/.github/workflows/python-package.yml:40-46).
This runner is the rebuild's equivalent: it launches the whole pytest suite
once per rank as jax multi-controller SPMD processes — each rank owns half
of the virtual CPU devices, `jax.distributed.initialize` forms the group
(tests/conftest.py, RAMBA_TEST_PROCS branch), and every collective in every
test crosses the process boundary.

Both ranks run the identical deterministic test order (SPMD: same program
everywhere); host gathers (`ndarray.asarray`) become all-gather
collectives, and file IO writes through the driver rank with a barrier
(ramba_tpu/fileio.py).  Both ranks share one --basetemp so distributed
save/load paths agree across processes; the driver-gated writes keep a
single writer per file.

Usage:
    python scripts/two_process_suite.py [pytest args...]
    # e.g. python scripts/two_process_suite.py tests/test_fusion.py -x
    python scripts/two_process_suite.py --fault-leg

Exit 0 iff BOTH ranks' pytest runs pass.

``--fault-leg`` runs the resilience acceptance leg instead: a 2-rank SPMD
workload under ``RAMBA_FAULTS=compile:once`` — both ranks must inject the
fault in lockstep, retry the flush, produce the correct result, count
``resilience.retries`` >= 1, and stream fault/degrade events into their
per-rank RAMBA_TRACE files.

``--memory-leg`` runs the memory-governor acceptance leg: the same 2-rank
SPMD topology under a deliberately tiny ``RAMBA_HBM_BUDGET`` so pre-flush
admission control must fire on both ranks in lockstep (SPMD: the analytic
estimate is a pure function of the program, so both ranks route to the
``chunked`` rung together), produce the correct result, and stream
``memory`` events into the per-rank traces.  Host spill is intentionally
NOT exercised here: multi-controller arrays are not fully addressable, so
the governor refuses to spill them (memory.py) — the leg asserts the
admission/chunked path, which is the part that must stay rank-lockstepped.

``--perf-leg`` runs the kernel-cost-ledger acceptance leg: the same
2-rank SPMD topology under ``RAMBA_PERF=1``; both ranks run an identical
flush sequence and print the sorted kernel fingerprints from their cost
ledgers (observe/ledger.py).  The runner asserts the two sets are
IDENTICAL — the fingerprints are a pure function of program structure +
donation + semantic regime, so any rank skew here means the ranks
compiled different programs — and then runs
``scripts/trace_report.py --merge-ranks`` over the per-rank traces to
prove the cross-rank merged timeline works end to end.

``--attrib-leg`` runs the critical-path-attribution acceptance leg
(observe/attrib.py): the same 2-rank topology under ``RAMBA_PERF=1``;
each rank asserts its stage sums (plus the unattributed residual)
reconcile with span wall time, then prints its lockstep per-flush stage
signatures.  The runner asserts the marker stream is IDENTICAL across
ranks and that ``trace_report.py --attrib`` (stage
waterfall) and ``--merge-ranks`` (per-rank stage columns, no
divergence) both build from the traces.

``--elastic-leg`` runs the elastic-lifecycle acceptance leg: a 2-rank
SPMD run (heartbeat on, watchdog armed) auto-checkpoints mid-workload
via ``elastic.CheckpointManager.maybe_save`` into a shared directory and
stops — simulating preemption after the save.  A fresh SINGLE-rank
process then ``elastic.resume``s from that directory (mesh reshape:
manifest says 2 processes, the resuming world has 1) and finishes the
workload; a straight 1-rank run of the full workload provides the
reference.  The runner asserts the two final-state sha256 digests are
BYTE-IDENTICAL — the workload is elementwise, so resharding must not
perturb a single bit.

``--serving-leg`` runs the serving-subsystem acceptance leg: each rank
drives a ``serve.Session`` through the async pipeline's staging seam in
SINGLE-THREADED deterministic order (the background worker is disabled
and dispatch is driven inline — SPMD ranks must dispatch identical
program sequences, so the fairness queue's cross-tenant coalescing
reorder is off the table here).  Four identical flushes must coalesce
into ONE fingerprint-matched batch on both ranks; the runner asserts
the coalesced fingerprint AND the full kernel-ledger key sets are
identical across ranks.

``--chaos-leg`` runs the rank-coherent-recovery acceptance leg: a
2-rank SPMD soak where EVERY fault is injected on rank 1 only
(``RAMBA_FAULTS`` ``rank=1`` payloads across the dispatch/execute/oom
sites, seeded), plus one deterministic mid-run fatal burst that drives
a coherent quarantine.  Phase ON (``RAMBA_COHERENCE=on``) asserts the
consensus control plane absorbs the skew: byte-identical per-iteration
results on both ranks, identical coherence decision sequences (same
sites, same epochs, same decisions), identical rung-transition and
retry sequences, equal quarantine counts (each stamped with its
agreement epoch), zero watchdog ``stall`` events, and zero
local-fallback rounds.  Phase OFF re-runs the same seed with
``RAMBA_COHERENCE=off`` and asserts the historical failure mode comes
back: rank-local recovery diverges the rungs, the ranks' host gathers
mispair, and the run ends in differing results / a wedged rank
(deadline-killed) — demonstrating the protocol is what fixes it.

``--reshard-leg`` runs the resharding/elasticity acceptance leg.
Phase 1 (2-rank SPMD): a row-sharded array reshards to column-sharded
then to replicated through the staged device-collective schedule
(coherence plan fence + per-stage gates), asserted byte-identical on
both ranks and within the ledger-verified peak-live bound; then a
rank-skewed mid-reshard fault (``reshard:stage:after=2:rank=1``) must
abort the epoch on BOTH ranks (the stage gate turns rank 1's local
fault into a fleet-wide rollback before any collective mispairs),
after which a clean retry ends byte-identical with zero watchdog
stalls.  Phase 2 (single-rank): the same workload reshapes a 2-device
mesh down to 1 device via ``elastic.live_reshape`` twice — once on the
live rung, once with an injected ``reshard:plan`` fault forcing the
drain→checkpoint→resume fallback — and the two digests must match.

``--telemetry-leg`` runs the live-telemetry acceptance leg: both ranks
serve a traced ``serve.Session`` flush (one FIXED trace_id shared across
ranks — the cross-rank causal chain), start the Prometheus exporter on
an ephemeral port, and scrape their own ``/metrics``.  The runner
asserts each rank's scrape is labeled with its own distinct
``rank="<r>"`` and that the shared trace_id landed in BOTH ranks'
RAMBA_TRACE event files — the inputs ``trace_report.py --trace`` needs
to reconstruct one request across the fleet.

``--fleet-leg`` runs the fleet-observability-federation acceptance leg
(PR 16): three INDEPENDENT replica processes (not SPMD ranks) run the
identical traced serving flush with ``RAMBA_FLEET_DIR`` pointed at one
shared snapshot spool.  The runner drives ``scripts/fleet_collector.py``
through the whole replica lifecycle: all replicas healthy with lockstep
kernel fingerprints, the fleet goodput rollup reconciling against the
raw per-replica spool documents within 1%, an injected torn document
classified stale without a collector crash, a replica SIGKILLed
mid-soak flagged dead within 2x the publish interval, and the
cross-process ``trace_report.py --trace`` chain stitched over the
per-replica trace directories.

``--router-leg`` runs the fleet serving-plane acceptance leg (PR 17):
a router process (its own RAMBA_TRACE stream) drives replica servers
spawned via ``scripts/fleet_router.py`` against one snapshot spool and
one shared artifact tier.  Phase 1 warms the tier from a cold replica
(demand compiles + ``persist.save_topk``) and pins the no-fault
reference digest; phase 2 proves a second cold replica comes up warm
off the shared AOT tier (cross-writer persist hits, byte-identical
digests, shared memo lane off); phase 3 proves the shared memo lane
(cross-replica memo hits, near-zero demand compiles); phase 4 SIGKILLs
the replica serving a tenant mid-soak and asserts the router trips its
fleet breaker, redirects, heals the tenant by deterministic replay on
the survivor, and every tenant's digest stays byte-identical.  The
stitched ``trace_report.py --merge-ranks`` / ``--trace`` views over the
router + replica trace files must show the redirect/heal chain.

``--integrity-leg`` runs the data-integrity acceptance leg (two
phases).  ON: a 2-rank SPMD run with shadow audits armed
(``RAMBA_AUDIT=1``) and a seeded one-shot flip of rank 1's shadow bytes
(``audit:shadow:flip``) — both ranks must agree the audit verdict via
the coherence round (rank 0 saw no local mismatch yet records the
agreed one), suppress the memo insert coherently, serve the correct
primary result, and emit ``integrity`` trace events.  OFF: a
single-process reproduction of the exact wrong-answer serve the plane
prevents — a shared memo blob clobbered with a *valid but wrong*
unstamped payload is served verbatim under ``RAMBA_INTEGRITY=0``, then
caught (evict + recompute, correct answer) with the plane on.

``--memo-leg`` runs the result-memoization acceptance leg: both ranks
under ``RAMBA_MEMO=1`` canonicalize the same program (including its
commutative-operand swap — ``analyze.canonicalize`` must produce the
SAME chash for ``(a+b)*2`` and ``(b+a)*2`` on both ranks) and then
flush it repeatedly over stable buffers.  Memo hits are rank-local
decisions that SKIP dispatch, so the cache MUST hit in lockstep: a
rank that replays from cache while its peer executes would mispair the
post-flush gathers.  The runner asserts both ranks print the identical
canonical hash, the identical hit/insert counts, the correct value,
and that each per-rank trace carries memo-served flush spans
(``cache == "memo"``).

``--plancache-leg`` runs the plan-certificate acceptance leg: both
ranks under ``RAMBA_PLANCERT=1 RAMBA_VERIFY=strict`` flush the same
program repeatedly.  The cache key and invalidation signature are pure
functions of rank-identical state (program structure, avals, mesh
epoch, rule set), so hit/miss decisions MUST be lockstep — a rank
redeeming a certificate while its peer re-analyzes would skew the
flush sequences.  The leg runs the epoch-batched ``agree()`` round at
a small batch size, asserts zero divergences, and the runner compares
hit/store/stale markers across ranks and asserts each per-rank trace
carries certificate-redeemed flush spans (``plan_cache == "hit"``).

``--warmstart-leg`` runs the compile-class / warm-start acceptance leg
(PR 14): two phases of two ranks each, sharing per-rank ``RAMBA_CACHE``
directories across phases.  Under ``RAMBA_COMPILE_CLASSES=pow2`` the
bucket decision is a pure function of (program, shapes, policy), so
both SPMD ranks must pick the IDENTICAL compile class per fingerprint
— skewed classes would compile different executables and desync the
collective schedule.  The cold phase populates each rank's persistent
cache (``persist.save_topk``); the warm phase replays the same shapes
and must hit the AOT lane in LOCKSTEP (equal, nonzero persist-hit
counts on both ranks).  The runner compares the per-rank class-decision
tables within and across phases and the persist hit counts across
ranks.

``--sampling-leg`` runs the trace-retention acceptance leg (PR 20):
two ranks under ``RAMBA_TRACE_SAMPLE=4`` with a rank-skewed
``execute:delay`` fault.  Steady-state sessions use deterministic trace
ids whose sha256 verdict keeps exactly 5 of 48 chains in the file lane
(>= 4x volume drop by construction), the same 5 on both ranks even
while rank 1 runs 40 ms slower per execute.  The runner asserts zero
stalls and zero local-fallback rounds and greps each rank's trace file
for the steady-state volume ratio.
"""

from __future__ import annotations

import os
import shutil
import socket
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# SPMD workload for the fault leg: each rank forms the process group
# itself (no pytest/conftest in the loop), runs a fused chain that must
# survive one injected compile fault per rank, and checks its own retry
# counters.  argv: <rank> <coordinator>.
_FAULT_WORKLOAD = """
import sys
import numpy as np
rank, coord = int(sys.argv[1]), sys.argv[2]
from ramba_tpu.parallel import distributed
distributed.initialize(coordinator_address=coord, num_processes=2,
                       process_id=rank)
import jax
assert jax.process_count() == 2, jax.process_count()
import ramba_tpu as rt
a = rt.arange(4096) * 2.0 + 1.0
s = float(rt.sum(a))
exp = float(np.sum(np.arange(4096) * 2.0 + 1.0))
assert abs(s - exp) <= 1e-5 * abs(exp), (s, exp)
from ramba_tpu import diagnostics
c = diagnostics.counters()
assert c.get('resilience.retries', 0) >= 1, c
print('FAULT_LEG_OK rank=%d retries=%d' % (rank, c['resilience.retries']))
"""


# SPMD workload for the memory leg: each rank forms the process group,
# runs a multi-op chain whose analytic peak estimate exceeds the tiny
# injected HBM budget, and checks that admission control rerouted the
# flush to the chunked rung while still producing the right answer.
# argv: <rank> <coordinator>.
_MEMORY_WORKLOAD = """
import sys
import numpy as np
rank, coord = int(sys.argv[1]), sys.argv[2]
from ramba_tpu.parallel import distributed
distributed.initialize(coordinator_address=coord, num_processes=2,
                       process_id=rank)
import jax
assert jax.process_count() == 2, jax.process_count()
import ramba_tpu as rt
a = rt.arange(65536) * 2.0 + 1.0
b = rt.sqrt(a) + a * 0.5
s = float(rt.sum(b))
an = np.arange(65536) * 2.0 + 1.0
exp = float(np.sum(np.sqrt(an) + an * 0.5))
assert abs(s - exp) <= 1e-3 * abs(exp), (s, exp)
from ramba_tpu import diagnostics
c = diagnostics.counters()
ok = (c.get('memory.admission_rejects', 0) >= 1
      or c.get('memory.evictions', 0) >= 1)
assert ok, c
chunked = [f for f in diagnostics.last_flushes(20)
           if f.get('admission') == 'chunked'
           or f.get('degraded') == 'chunked']
assert chunked, diagnostics.last_flushes(20)
print('MEMORY_LEG_OK rank=%d rejects=%d' % (
    rank, c.get('memory.admission_rejects', 0)))
"""


# SPMD workload for the perf leg: each rank forms the process group, runs
# the same flush sequence twice (so every kernel has both a miss and a
# hit), and prints its ledger's sorted kernel fingerprints for the runner
# to compare across ranks.  argv: <rank> <coordinator>.
_PERF_WORKLOAD = """
import sys
import numpy as np
rank, coord = int(sys.argv[1]), sys.argv[2]
from ramba_tpu.parallel import distributed
distributed.initialize(coordinator_address=coord, num_processes=2,
                       process_id=rank)
import jax
assert jax.process_count() == 2, jax.process_count()
import ramba_tpu as rt
for _ in range(3):
    a = rt.arange(8192) * 2.0 + 1.0
    s = float(rt.sum(a))
    b = rt.sqrt(rt.arange(4096) + 1.0)
    s2 = float(rt.sum(b))
exp = float(np.sum(np.arange(8192) * 2.0 + 1.0))
assert abs(s - exp) <= 1e-5 * abs(exp), (s, exp)
from ramba_tpu import diagnostics
rep = diagnostics.perf_report()
keys = sorted(rep['kernels'])
assert keys, rep
execs = sum(k['exec']['count'] for k in rep['kernels'].values())
assert execs >= 1, rep
print('PERF_LEG_KEYS rank=%d %s' % (rank, ','.join(keys)))
"""


# SPMD workload for the attribution leg: each rank runs the same flush
# sequence, then prints the per-flush stage signatures in lockstep
# order.  They must be identical across ranks: stage stamping is
# deterministic control flow.  Each rank also checks that its stage sums
# plus the unattributed residual reconcile with span wall time.
# argv: <rank> <coordinator>.
_ATTRIB_WORKLOAD = """
import sys
import numpy as np
rank, coord = int(sys.argv[1]), sys.argv[2]
from ramba_tpu.parallel import distributed
distributed.initialize(coordinator_address=coord, num_processes=2,
                       process_id=rank)
import jax
assert jax.process_count() == 2, jax.process_count()
import ramba_tpu as rt
from ramba_tpu import diagnostics
from ramba_tpu.observe import attrib
for _ in range(3):
    a = rt.arange(8192) * 2.0 + 1.0
    s = float(rt.sum(a))
    b = rt.sqrt(rt.arange(4096) + 1.0)
    s2 = float(rt.sum(b))
exp = float(np.sum(np.arange(8192) * 2.0 + 1.0))
assert abs(s - exp) <= 1e-5 * abs(exp), (s, exp)
sigs = []
for f in diagnostics.last_flushes(50):
    st = f.get('stages')
    if st is None:
        continue
    order = [k for k in attrib.STAGES if k in st]
    wall = f.get('wall_s') or 0.0
    tot = sum(st.values()) + f.get('unattributed_s', 0.0)
    assert abs(tot - wall) <= max(0.05 * wall, 1e-3), (wall, tot, st)
    sigs.append(f.get('label', '?') + ':' + ','.join(order))
assert sigs, diagnostics.last_flushes(5)
print('ATTRIB_LEG_STAGES rank=%d %s' % (rank, ';'.join(sigs)))
"""


# SPMD workload for the sampling leg: 48 steady-state serving sessions
# with deterministic trace ids under RAMBA_TRACE_SAMPLE=4.  The
# rank-skewed env fault makes rank 1 slower per execute; the head-sampling
# verdict is a hash of the trace id, so both ranks keep the same chains.
# argv: <rank> <coordinator>.
_SAMPLING_WORKLOAD = """
import sys
import numpy as np
rank, coord = int(sys.argv[1]), sys.argv[2]
from ramba_tpu.parallel import distributed
distributed.initialize(coordinator_address=coord, num_processes=2,
                       process_id=rank)
import jax
assert jax.process_count() == 2, jax.process_count()
import ramba_tpu as rt
from ramba_tpu import serve
from ramba_tpu.observe import events
assert events.trace_sample_every() == 4
# steady state: one-flush sessions with deterministic trace ids; the
# sha256 head-sampling verdict keeps exactly 5 of these 48 chains
tids = ['steady-%03d' % i for i in range(48)]
kept = [t for t in tids if events.trace_sampled_in(t)]
assert len(kept) == 5, kept
x = None
for tid in tids:
    with serve.Session(trace_id=tid) as s:
        a = rt.arange(2048) * 2.0 + 1.0
        x = float(np.asarray(a).sum())
exp = float((np.arange(2048) * 2.0 + 1.0).sum())
assert abs(x - exp) <= 1e-5 * abs(exp), (x, exp)
rt.sync()
ring = events.snapshot_ring()
stalls = sum(1 for e in ring if e.get('type') == 'stall')
local = sum(1 for e in ring if e.get('type') == 'coherence'
            and e.get('outcome') == 'local')
print('SAMPLING_LEG_HEALTH rank=%d stalls=%d local=%d'
      % (rank, stalls, local))
"""


# SPMD workload for the memo leg: each rank forms the process group,
# canonicalizes the shared program (asserting the commutative swap
# collapses to the same chash locally), then flushes it four times over
# stable buffers under RAMBA_MEMO=1 — one insert, three hits.  The
# canonical hash and the hit/insert counters are printed for the runner
# to compare across ranks: the hash is a pure function of program
# structure and the cache decision is deterministic given it, so any
# skew here means the ranks would dispatch different flush sequences.
# argv: <rank> <coordinator>.
_MEMO_WORKLOAD = """
import sys
import numpy as np
rank, coord = int(sys.argv[1]), sys.argv[2]
from ramba_tpu.parallel import distributed
distributed.initialize(coordinator_address=coord, num_processes=2,
                       process_id=rank)
import jax
assert jax.process_count() == 2, jax.process_count()
import ramba_tpu as rt
from ramba_tpu import analyze
from ramba_tpu.core import fuser, memo
assert memo.enabled(), 'RAMBA_MEMO not armed'
a = rt.arange(4096) / 100.0
b = rt.arange(4096) * 0.5 + 1.0
rt.sync()
vals = [float(rt.sum((a + b) * 2.0)) for _ in range(4)]
assert max(vals) == min(vals), vals
p1, _l1, _ = fuser._prepare_program([((a + b) * 2.0)._expr])
p2, _l2, _ = fuser._prepare_program([((b + a) * 2.0)._expr])
c1, c2 = analyze.canonicalize(p1), analyze.canonicalize(p2)
assert c1.chash == c2.chash, (c1.chash, c2.chash)
an = np.arange(4096)
exp = float(np.sum((an / 100.0 + (an * 0.5 + 1.0)) * 2.0))
assert abs(vals[0] - exp) <= 1e-4 * abs(exp), (vals[0], exp)
snap = memo.cache.snapshot()
assert snap['hits'] >= 3, snap
print('MEMO_LEG rank=%d chash=%s hits=%d inserts=%d' % (
    rank, c1.chash, snap['hits'], snap['inserts']))
"""


# SPMD workload for the plancache leg: each rank forms the process
# group, flushes the same fused chain five times under strict verify
# with the plan cache armed, then drains the batched coherence round.
# The cache decision sequence (1 store + 4 hits) is a deterministic
# function of rank-identical inputs, so the printed counters must match
# across ranks, and the agree() exchange must see equal batch counts
# (zero divergences).  argv: <rank> <coordinator>.
_PLANCACHE_WORKLOAD = """
import sys
import numpy as np
rank, coord = int(sys.argv[1]), sys.argv[2]
from ramba_tpu.parallel import distributed
distributed.initialize(coordinator_address=coord, num_processes=2,
                       process_id=rank)
import jax
assert jax.process_count() == 2, jax.process_count()
import ramba_tpu as rt
from ramba_tpu.core import fuser, plancache
assert plancache.enabled(), 'RAMBA_PLANCERT not armed'
a = rt.arange(4096) / 100.0
b = rt.arange(4096) * 0.5 + 1.0
rt.sync()
vals = [float(rt.sum((a + b) * 2.0)) for _ in range(5)]
assert max(vals) == min(vals), vals
an = np.arange(4096)
exp = float(np.sum((an / 100.0 + (an * 0.5 + 1.0)) * 2.0))
assert abs(vals[0] - exp) <= 1e-4 * abs(exp), (vals[0], exp)
plancache.flush_agree()
snap = plancache.snapshot()
assert snap.get('hits', 0) >= 3, snap
assert not snap.get('divergences'), snap
assert not snap.get('stale'), snap
print('PLANCACHE_LEG rank=%d hits=%d stores=%d stale=%d agree=%d '
      'div=%d' % (rank, snap.get('hits', 0), snap.get('stores', 0),
                  snap.get('stale', 0), snap.get('agree_rounds', 0),
                  snap.get('divergences', 0)))
"""


# SPMD workload for the autotune leg: each rank forms the process group
# and drives the same fused chain under RAMBA_AUTOTUNE=race until the
# backend race latches (or the iteration budget runs out), then prints
# its decision table.  Selection is ledger-count-driven, and counts
# advance in lockstep under SPMD, so both ranks must latch the SAME
# backend per fingerprint — the runner compares the tables.
# argv: <rank> <coordinator>.
_AUTOTUNE_WORKLOAD = """
import os
import sys
rank, coord = int(sys.argv[1]), sys.argv[2]
from ramba_tpu.parallel import distributed
distributed.initialize(coordinator_address=coord, num_processes=2,
                       process_id=rank)
import jax
assert jax.process_count() == 2, jax.process_count()
import ramba_tpu as rt
from ramba_tpu.core import autotune
assert autotune.mode() == 'race', autotune.mode()
n = 128 * 256
base = rt.arange(n) / 1000.0
rt.sync()
vals = []
for _ in range(20):
    B = rt.sin(base)
    C = rt.cos(base)
    D = B * B + C * C
    del B, C
    vals.append(float(rt.sum(D)))
    del D
    if autotune.latched_via_autotune():
        break
assert max(vals) == min(vals), vals
rep = autotune.report()
dec = {fp: d['backend'] for fp, d in rep['decisions'].items()}
assert dec, rep
cache = os.environ.get('RAMBA_AUTOTUNE_CACHE')
if cache:
    import json
    with open(cache) as f:
        table = json.load(f)
    for fp, b in dec.items():
        assert table['decisions'][fp]['backend'] == b, (fp, table)
print('AUTOTUNE_LEG_DECISIONS rank=%d %s'
      % (rank, ','.join('%s=%s' % kv for kv in sorted(dec.items()))))
"""


# SPMD workload for the warmstart leg: each rank forms the process
# group, arms the persistent cache on its own RAMBA_CACHE dir, and
# drives the same elementwise chain across four leading extents under
# RAMBA_COMPILE_CLASSES=pow2 (small enough to stay replicated, so the
# eager pad/slice wrapper touches only fully-addressable buffers).  The
# cold phase additionally serializes AOT executables; the warm phase
# must hit them.  Markers carry the per-fingerprint class-decision
# table, the persist hit count, and the compile totals for the runner
# to compare across ranks and phases.  argv: <rank> <coordinator>
# <phase: cold|warm>.
_WARMSTART_WORKLOAD = """
import sys
import numpy as np
rank, coord, phase = int(sys.argv[1]), sys.argv[2], sys.argv[3]
from ramba_tpu.parallel import distributed
distributed.initialize(coordinator_address=coord, num_processes=2,
                       process_id=rank)
import jax
assert jax.process_count() == 2, jax.process_count()
import ramba_tpu as rt
from ramba_tpu import common
from ramba_tpu.compile import classes, persist
from ramba_tpu.observe import ledger
assert classes.enabled(), 'RAMBA_COMPILE_CLASSES not armed'
persist.reconfigure()
assert persist.armed(), persist.snapshot()
for n in (3, 5, 9, 12):
    x = rt.array(np.arange(n * 8, dtype=np.float32).reshape(n, 8))
    y = x * 2.0 + 1.0
    rt.sync()
    got = float(rt.sum(y))
    exp = float(np.sum(np.arange(n * 8, dtype=np.float32)
                       .reshape(n, 8) * 2.0 + 1.0))
    assert abs(got - exp) <= 1e-4 * abs(exp), (n, got, exp)
snap = classes.snapshot()
assert snap['planned'] >= 4, snap
dec = {fp: tok for fp, tok in classes.decisions().items()
       if tok is not None}
assert dec, classes.decisions()
if phase == 'cold':
    rep = persist.save_topk(8)
    assert rep['stored'] + rep['skipped'] >= 1, rep
p = persist.snapshot()
if phase == 'warm':
    assert p['hits'] >= 1, p
ks = ledger.snapshot()['kernels'].values()
compiles = sum(k['compiles'] for k in ks)
compile_s = sum(k['compile_s'] for k in ks)
table = ','.join('%s=%s:%s' % (fp, tok[0], tok[1])
                 for fp, tok in sorted(dec.items()))
print('WARMSTART_LEG rank=%d phase=%s classes=%s persist_hits=%d '
      'compiles=%d compile_s=%.4f'
      % (rank, phase, table, p['hits'], compiles, compile_s))
"""


# SPMD workload for the serving leg: each rank opens one serving session
# and pushes four structurally-identical flushes plus one distinct one
# through the async pipeline's enqueue/dispatch seam, driving dispatch
# inline (worker disabled) so both ranks execute the identical program
# sequence.  argv: <rank> <coordinator>.
_SERVING_WORKLOAD = """
import sys
import numpy as np
rank, coord = int(sys.argv[1]), sys.argv[2]
from ramba_tpu.parallel import distributed
distributed.initialize(coordinator_address=coord, num_processes=2,
                       process_id=rank)
import jax
assert jax.process_count() == 2, jax.process_count()
import ramba_tpu as rt
from ramba_tpu import diagnostics, serve
from ramba_tpu.serve.pipeline import CompilePipeline
pipe = CompilePipeline(coalesce=8)
pipe._ensure_worker = lambda: None  # deterministic: dispatch inline below
with serve.Session(tenant='spmd', pipeline=pipe) as s:
    arrs, tickets = [], []
    for i in range(4):
        arrs.append(rt.arange(8192) * 2.0 + 1.0)
        tickets.append(s.flush())
    group = pipe.queue.pop_group(
        8, fingerprint_of=lambda t: t.work.fingerprint, timeout=0)
    assert len(group) == 4, len(group)
    fp = group[0].work.fingerprint
    pipe._dispatch_group(group)
    for t in tickets:
        assert t.wait(timeout=120) == [] and t.coalesced == 4
    exp = np.arange(8192) * 2.0 + 1.0
    for a in arrs:
        got = np.asarray(a)
        assert np.allclose(got, exp), got[:4]
    b = rt.sqrt(rt.arange(4096) + 1.0)
    t2 = s.flush()
    g2 = pipe.queue.pop_group(
        8, fingerprint_of=lambda t: t.work.fingerprint, timeout=0)
    assert len(g2) == 1, len(g2)
    pipe._dispatch_group(g2)
    t2.wait(timeout=120)
    assert np.allclose(np.asarray(b), np.sqrt(np.arange(4096) + 1.0))
pipe.stop()
rep = serve.tenant_report()
assert rep['spmd']['flushes'] >= 5, rep
assert rep['spmd']['quota_rejects'] == 0, rep
from ramba_tpu.observe import ledger
keys = ledger.kernel_keys()
assert keys, 'empty kernel ledger'
print('SERVING_LEG_COALESCE rank=%d fp=%s' % (rank, fp))
print('SERVING_LEG_KEYS rank=%d %s' % (rank, ','.join(sorted(keys))))
"""


# SPMD workload for the overload leg: a rank-skewed ``serve:admit`` fault
# makes rank 1 PROPOSE shedding the first three flushes; under engaged
# coherence the ``serve:shed`` agreement round must shed them on BOTH
# ranks (identical verdict, same epoch) so the fleet never splits into
# "rank 0 executed a collective rank 1 skipped".  With RAMBA_COHERENCE=off
# the same seed must reproduce the divergence.  argv: <rank> <coordinator>.
_OVERLOAD_WORKLOAD = """
import os, sys, time
import numpy as np
rank, coord = int(sys.argv[1]), sys.argv[2]
from ramba_tpu.parallel import distributed
distributed.initialize(coordinator_address=coord, num_processes=2,
                       process_id=rank)
import jax
assert jax.process_count() == 2, jax.process_count()
import ramba_tpu as rt
from ramba_tpu import serve
from ramba_tpu.serve import overload
from ramba_tpu.serve.pipeline import CompilePipeline
coh = os.environ.get('RAMBA_COHERENCE', 'auto')
pipe = CompilePipeline()
pipe._ensure_worker = lambda: None  # lockstep: dispatch inline below
arrs = []
with serve.Session(tenant='ov', pipeline=pipe) as s:
    for i in range(8):
        a = rt.arange(4096) * float(i + 1) + 0.5
        arrs.append(a)
        t = s.flush()
        group = pipe.queue.pop_group(1, timeout=5)
        assert len(group) == 1, (i, len(group))
        t0 = time.perf_counter()
        pipe._dispatch_group(group)
        try:
            t.wait(timeout=120)
            print('OVERLOAD_RESULT idx=%d verdict=OK' % i, flush=True)
        except overload.ShedError as e:
            wall_ms = (time.perf_counter() - t0) * 1e3
            assert e.shed_classification == 'shed', e
            assert wall_ms < 2000.0, wall_ms  # shed, not executed-then-failed
            print('OVERLOAD_RESULT idx=%d verdict=SHED reason=%s epoch=%s'
                  % (i, e.reason, e.epoch), flush=True)
    if coh == 'on':
        # both ranks shed the identical set, so the self-heal flushes
        # below are the identical collective sequence on every rank
        for i, a in enumerate(arrs):
            got = float(np.asarray(a).sum())
            exp = float((np.arange(4096) * float(i + 1) + 0.5).sum())
            tag = 'OK' if abs(got - exp) <= 1e-3 * max(1.0, abs(exp)) else 'BAD'
            print('OVERLOAD_HEAL idx=%d %s' % (i, tag), flush=True)
    s.close(drain=False)
pipe.stop()
from ramba_tpu.observe import registry
print('OVERLOAD_COUNTS shed=%d fault=%d' % (
    registry.get('serve.shed'), registry.get('serve.shed.fault')),
    flush=True)
"""


# SPMD workload for the telemetry leg: each rank opens a serving session
# that JOINS one fixed trace_id (the same request fanned out across the
# fleet), drives a traced flush through the pipeline seam inline, then
# starts the metrics exporter on an ephemeral port and scrapes itself.
# argv: <rank> <coordinator> <trace_id>.
_TELEMETRY_WORKLOAD = """
import sys
import urllib.request
import numpy as np
rank, coord, trace = int(sys.argv[1]), sys.argv[2], sys.argv[3]
from ramba_tpu.parallel import distributed
distributed.initialize(coordinator_address=coord, num_processes=2,
                       process_id=rank)
import jax
assert jax.process_count() == 2, jax.process_count()
import ramba_tpu as rt
from ramba_tpu import serve
from ramba_tpu.observe import telemetry
from ramba_tpu.serve.pipeline import CompilePipeline
pipe = CompilePipeline(coalesce=8)
pipe._ensure_worker = lambda: None  # deterministic: dispatch inline
with serve.Session(tenant='spmd', pipeline=pipe, trace_id=trace) as s:
    assert s.trace_id == trace
    a = rt.arange(8192) * 2.0 + 1.0
    t = s.flush()
    g = pipe.queue.pop_group(
        8, fingerprint_of=lambda t: t.work.fingerprint, timeout=0)
    assert len(g) == 1, len(g)
    pipe._dispatch_group(g)
    assert t.wait(timeout=120) == []
    assert t.trace_id == trace, t.trace_id
    assert np.allclose(np.asarray(a), np.arange(8192) * 2.0 + 1.0)
pipe.stop()
port = telemetry.start(port=0)
body = urllib.request.urlopen(
    'http://127.0.0.1:%d/metrics' % port, timeout=30).read().decode()
telemetry.stop()
labels = sorted({ln.split('rank=\"')[1].split('\"')[0]
                 for ln in body.splitlines() if 'rank=\"' in ln})
assert 'ramba_serve_tenant_flushes_total' in body, body[:400]
assert 'ramba_flush_e2e_seconds_bucket' in body, body[:400]
print('TELEMETRY_LEG_SCRAPE rank=%d labels=%s port=%d' % (
    rank, ','.join(labels), port))
"""


# Workload for the fleet leg: N INDEPENDENT replica processes (not SPMD
# ranks — each is its own single-process serving job, the fleet topology
# the snapshot spool federates).  Each replica runs the IDENTICAL traced
# serving flush (lockstep kernel fingerprints across the fleet), lets
# the spool publisher autostart off the flush path, forces one
# synchronous publish so the READY marker implies a document on disk,
# then soaks (publishing every RAMBA_FLEET_INTERVAL_S) until killed or
# the soak budget ends.  argv: <idx> <trace_id> <soak_s>.
_FLEET_WORKLOAD = """
import sys
import time
import numpy as np
idx, trace, soak_s = int(sys.argv[1]), sys.argv[2], float(sys.argv[3])
import ramba_tpu as rt
from ramba_tpu import serve
from ramba_tpu.observe import fleet, ledger
from ramba_tpu.serve.pipeline import CompilePipeline
pipe = CompilePipeline(coalesce=8)
pipe._ensure_worker = lambda: None  # deterministic: dispatch inline
with serve.Session(tenant='fleet', pipeline=pipe, trace_id=trace) as s:
    assert s.trace_id == trace
    a = rt.arange(4096) * 3.0 + 1.0  # IDENTICAL program on every replica
    t = s.flush()
    g = pipe.queue.pop_group(
        8, fingerprint_of=lambda t: t.work.fingerprint, timeout=0)
    assert len(g) == 1, len(g)
    pipe._dispatch_group(g)
    assert t.wait(timeout=120) == []
    assert np.allclose(np.asarray(a), np.arange(4096) * 3.0 + 1.0)
pipe.stop()
assert fleet.started(), 'spool publisher must autostart off the flush path'
path = fleet.publish()
assert path, path
print('FLEET_REPLICA_OK idx=%d fps=%s' % (
    idx, ','.join(ledger.kernel_keys())), flush=True)
deadline = time.monotonic() + soak_s
while time.monotonic() < deadline:
    time.sleep(0.05)
print('FLEET_SOAK_DONE idx=%d' % idx, flush=True)
"""


# SPMD workload for the elastic leg, phase 1: two ranks run the first
# half of a deterministic elementwise workload with heartbeat + watchdog
# on, auto-checkpoint at the cadence step into a SHARED root, and stop —
# a preemption right after the save.  argv: <rank> <coordinator> <root>.
_ELASTIC_SPMD_WORKLOAD = """
import sys
import numpy as np
rank, coord, root = int(sys.argv[1]), sys.argv[2], sys.argv[3]
from ramba_tpu.parallel import distributed
distributed.initialize(coordinator_address=coord, num_processes=2,
                       process_id=rank)
import jax
assert jax.process_count() == 2, jax.process_count()
import ramba_tpu as rt
from ramba_tpu.resilience import elastic
elastic.start_heartbeat(0.2)
box = {}
mgr = elastic.CheckpointManager(root, keep=2, every_steps=2)
mgr.register('state', lambda: {'x': box['x']})
box['x'] = rt.arange(8192) * 1.0
for step in (1, 2, 3):
    box['x'] = box['x'] * 1.000001 + float(step)
    if mgr.maybe_save(step):
        print('ELASTIC_LEG_SAVED rank=%d step=%d' % (rank, step))
assert mgr.latest() == 2, mgr.all_steps()
elastic.stop_heartbeat()
print('ELASTIC_LEG_PHASE1_OK rank=%d beats=%d' % (
    rank, elastic.report()['heartbeats']))
"""


# Elastic leg, phase 2: a fresh SINGLE-rank world resumes from the
# 2-rank checkpoint (mesh reshape 2->1) and finishes the workload.
# argv: <root>.
_ELASTIC_RESUME_WORKLOAD = """
import sys
import hashlib
import numpy as np
root = sys.argv[1]
import jax
assert jax.process_count() == 1, jax.process_count()
import ramba_tpu as rt
from ramba_tpu.resilience import elastic
res = elastic.resume(root)
assert res.manifest['process_count'] == 2, res.manifest
assert res.step == 2, res.step
x = rt.asarray(np.asarray(res.state['state']['x']))
for step in (3, 4, 5, 6):
    x = x * 1.000001 + float(step)
digest = hashlib.sha256(np.ascontiguousarray(np.asarray(x))
                        .tobytes()).hexdigest()
print('ELASTIC_LEG_DIGEST %s' % digest)
"""


# Elastic leg, reference: the same workload end to end in one 1-rank
# process, no checkpoint in the loop.  argv: none.
_ELASTIC_REF_WORKLOAD = """
import hashlib
import numpy as np
import ramba_tpu as rt
x = rt.arange(8192) * 1.0
for step in (1, 2, 3, 4, 5, 6):
    x = x * 1.000001 + float(step)
digest = hashlib.sha256(np.ascontiguousarray(np.asarray(x))
                        .tobytes()).hexdigest()
print('ELASTIC_LEG_REF %s' % digest)
"""


# SPMD workload for the reshard leg, phase 1: row → column → replicated
# through the staged schedule, ledger-bound check, then a rank-skewed
# mid-reshard fault that must roll back coherently on BOTH ranks.
# argv: <rank> <coordinator>.
_RESHARD_SPMD_WORKLOAD = """
import sys
import hashlib
import numpy as np
rank, coord = int(sys.argv[1]), sys.argv[2]
from ramba_tpu.parallel import distributed
distributed.initialize(coordinator_address=coord, num_processes=2,
                       process_id=rank)
import jax
assert jax.process_count() == 2, jax.process_count()
import ramba_tpu as rt
from ramba_tpu.observe import registry
from ramba_tpu.parallel import mesh as mesh_mod
from ramba_tpu.parallel import reshard as reshard_mod
from ramba_tpu.resilience import elastic, faults, memory
ax = tuple(mesh_mod.get_mesh().axis_names)
data = np.arange(512 * 64, dtype=np.float32).reshape(512, 64)
ref = hashlib.sha256(np.ascontiguousarray(data).tobytes()).hexdigest()
a = rt.asarray(data)
rt.sync()
cap = 1 << 13
plan = reshard_mod.plan_reshard(a.shape, a.dtype, (ax,), (None,) + (ax,),
                                max_stage_bytes=cap)
assert len(plan.stages) > 1, plan.describe()
live0 = memory.ledger.live_bytes + memory.ledger.transient_bytes
peak0 = memory.ledger.peak_live_bytes
rt.reshard(a, (None,) + (ax,), max_stage_bytes=cap)   # row -> column
peak1 = memory.ledger.peak_live_bytes
bound = (live0 - plan.total_bytes) + plan.peak_bound_bytes
assert peak1 <= max(peak0, bound), (peak1, peak0, bound)
rt.reshard(a, ())                                     # column -> replicated
got = hashlib.sha256(np.ascontiguousarray(a.asarray())
                     .tobytes()).hexdigest()
assert got == ref, (got, ref)
assert memory.ledger.transient_bytes == 0
print('RESHARD_LEG_DIGEST rank=%d %s' % (rank, got), flush=True)
print('RESHARD_LEG_PEAK rank=%d peak=%d bound=%d' % (rank, peak1, bound),
      flush=True)
# rank-skewed mid-reshard fault: rank 1 faults at stage 2; the stage
# gate must turn that into a fleet-wide rollback on the SAME stage.
rt.reshard(a, (ax,), max_stage_bytes=cap)             # back to row
faults.configure('reshard:stage:after=2:rank=1')
try:
    rt.reshard(a, (None,) + (ax,), max_stage_bytes=cap)
    raise SystemExit('expected ReshardError on rank %d' % rank)
except reshard_mod.ReshardError:
    pass
faults.configure(None)
assert registry.get('reshard.rollbacks') >= 1
rt.reshard(a, (None,) + (ax,), max_stage_bytes=cap)   # clean retry
rt.reshard(a, ())
got2 = hashlib.sha256(np.ascontiguousarray(a.asarray())
                      .tobytes()).hexdigest()
assert got2 == ref, (got2, ref)
stalls = elastic.report()['stalls']
assert stalls == 0, stalls
print('RESHARD_LEG_FAULT rank=%d digest=%s rollbacks=%d stalls=%d' % (
    rank, got2, registry.get('reshard.rollbacks'), stalls), flush=True)
"""


# Reshard leg, phase 2: single rank, 2-device mesh reshaped down to 1
# device in place.  argv: <mode> — 'live' runs the top rung, 'checkpoint'
# injects a reshard:plan fault so the drain->checkpoint->resume fallback
# must carry the reshape; both print the same-workload digest.
_RESHARD_LIVE_WORKLOAD = """
import sys
import hashlib
import time
import numpy as np
mode = sys.argv[1]
import jax
assert jax.process_count() == 1, jax.process_count()
import ramba_tpu as rt
from ramba_tpu.parallel import mesh as mesh_mod
from ramba_tpu.resilience import elastic, faults
mesh_mod.set_mesh(jax.sharding.Mesh(np.asarray(jax.devices()[:2]), ('d0',)))
x = rt.arange(8192) * 1.0
for step in (1, 2, 3):
    x = x * 1.000001 + float(step)
np.asarray(x)  # materialise on the 2-device mesh
if mode == 'checkpoint':
    faults.configure('reshard:plan:always')
t0 = time.perf_counter()
res = elastic.live_reshape(
    jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ('d0',)))
wall_ms = (time.perf_counter() - t0) * 1000.0
faults.configure(None)
assert res['mode'] == mode, res
assert mesh_mod.get_mesh().devices.size == 1
for step in (4, 5, 6):
    x = x * 1.000001 + float(step)
digest = hashlib.sha256(np.ascontiguousarray(np.asarray(x))
                        .tobytes()).hexdigest()
print('RESHAPE_DIGEST mode=%s %s wall_ms=%.1f' % (mode, digest, wall_ms))
"""


def run_reshard_leg() -> int:
    """2-rank staged reshard round-trip (byte-identical, ledger-bounded,
    rank-skewed fault rolls back coherently), then a single-rank live
    2-device -> 1-device mesh reshape byte-identical to the
    checkpoint-fallback path."""
    with socket.socket() as s:
        s.bind(("localhost", 0))
        port = s.getsockname()[1]
    basetemp = tempfile.mkdtemp(prefix="ramba_2proc_reshard_")
    trace_base = os.path.join(basetemp, "trace.jsonl")
    budget = float(os.environ.get("RAMBA_TEST_PROCS_TIMEOUT", "600"))

    def base_env():
        env = dict(os.environ)
        env["PYTHONPATH"] = REPO
        for k in ("RAMBA_TEST_PROCS", "RAMBA_TEST_PROC_ID",
                  "RAMBA_TEST_COORD", "RAMBA_TEST_SHARED_TMP",
                  "RAMBA_PROFILE_DIR", "RAMBA_FAULTS", "RAMBA_HBM_BUDGET"):
            env.pop(k, None)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        env["RAMBA_TRACE"] = trace_base
        # tripwire: a mispaired stage collective hangs, and that must
        # fail the leg as a stall instead of wedging CI
        env["RAMBA_WATCHDOG_S"] = "60"
        return env

    # --- phase 1: 2-rank SPMD round-trip + rank-skewed fault ---
    procs, logs = [], []
    for rank in range(2):
        log = open(os.path.join(basetemp, f"rank{rank}.log"), "w")
        logs.append(log)
        procs.append(subprocess.Popen(
            [sys.executable, "-c", _RESHARD_SPMD_WORKLOAD, str(rank),
             f"localhost:{port}"],
            env=base_env(), stdout=log, stderr=subprocess.STDOUT, cwd=REPO,
        ))
    deadline = time.time() + budget
    rcs = [None, None]
    try:
        for i, p in enumerate(procs):
            left = max(5.0, deadline - time.time())
            try:
                rcs[i] = p.wait(timeout=left)
            except subprocess.TimeoutExpired:
                p.kill()
                rcs[i] = -9
    finally:
        for log in logs:
            log.close()
    ok = all(rc == 0 for rc in rcs)

    digests, fault_digests = {}, {}
    for rank in range(2):
        path = os.path.join(basetemp, f"rank{rank}.log")
        with open(path) as f:
            tail = f.read().splitlines()
        joined = "\n".join(tail)
        for ln in tail:
            if ln.startswith(f"RESHARD_LEG_DIGEST rank={rank} "):
                digests[rank] = ln.split()[-1]
            if ln.startswith(f"RESHARD_LEG_FAULT rank={rank} "):
                fault_digests[rank] = ln.split("digest=")[1].split()[0]
        if (f"RESHARD_LEG_DIGEST rank={rank}" not in joined
                or f"RESHARD_LEG_FAULT rank={rank}" not in joined):
            ok = False
        print(f"--- reshard leg phase 1 rank {rank} rc={rcs[rank]} "
              f"({path}) ---")
        print("\n".join(tail[-(4 if ok else 40):]))
    if ok and (digests[0] != digests[1]
               or fault_digests[0] != fault_digests[1]):
        print(f"reshard leg: FAIL (rank digests diverge: {digests}, "
              f"post-fault {fault_digests})")
        ok = False

    # Per-rank traces must carry the reshard timeline: the fenced plan,
    # its stages, and the coherent rollback from the fault phase.
    import json

    if ok:
        for rank in range(2):
            path = f"{trace_base}.rank{rank}"
            try:
                with open(path) as f:
                    evs = [json.loads(ln) for ln in f if ln.strip()]
                n_plan = sum(1 for e in evs if e.get("type") == "reshard"
                             and e.get("action") == "plan")
                n_stage = sum(1 for e in evs if e.get("type") == "reshard"
                              and e.get("action") == "stage")
                n_roll = sum(1 for e in evs if e.get("type") == "reshard"
                             and e.get("action") == "rollback")
                n_stall = sum(1 for e in evs if e.get("type") == "stall")
                print(f"reshard leg rank {rank}: {n_plan} plans, "
                      f"{n_stage} stages, {n_roll} rollbacks, "
                      f"{n_stall} stalls")
                if n_plan < 6 or n_stage < 6 or n_roll != 1 or n_stall:
                    print(f"reshard leg rank {rank}: FAIL (timeline "
                          f"plan={n_plan} stage={n_stage} roll={n_roll} "
                          f"stall={n_stall})")
                    ok = False
            except (OSError, ValueError) as e:
                print(f"reshard leg rank {rank}: FAIL ({e})")
                ok = False

    # --- phase 2: single-rank live 2->1 reshape vs checkpoint path ---
    reshape = {}
    if ok:
        for mode in ("live", "checkpoint"):
            env = base_env()
            env.pop("RAMBA_TRACE", None)
            r = subprocess.run(
                [sys.executable, "-c", _RESHARD_LIVE_WORKLOAD, mode],
                env=env, capture_output=True, text=True, cwd=REPO,
                timeout=budget,
            )
            print(f"--- reshard leg reshape[{mode}] rc={r.returncode} ---")
            out = r.stdout.splitlines()
            print("\n".join(out[-4:]) if r.returncode == 0
                  else (r.stdout + r.stderr))
            if r.returncode != 0:
                ok = False
                continue
            for ln in out:
                if ln.startswith(f"RESHAPE_DIGEST mode={mode} "):
                    reshape[mode] = ln.split()[2]
            if mode not in reshape:
                print(f"reshard leg: FAIL (no digest from {mode} reshape)")
                ok = False
    if ok:
        if reshape["live"] != reshape["checkpoint"]:
            print(f"reshard leg: FAIL (live reshape digest "
                  f"{reshape['live']} != checkpoint path "
                  f"{reshape['checkpoint']})")
            ok = False
        else:
            print(f"reshard leg: live 2->1 mesh reshape is byte-identical "
                  f"to the checkpoint path "
                  f"(sha256 {reshape['live'][:16]}...)")

    print(f"two-process reshard leg: {'OK' if ok else 'FAIL'}")
    if ok:
        shutil.rmtree(basetemp, ignore_errors=True)
    else:
        print(f"reshard leg artifacts kept at {basetemp}")
    return 0 if ok else 1


def run_elastic_leg() -> int:
    """2-rank auto-checkpoint mid-workload, then a 1-rank resume (mesh
    reshape) finishes it; the final state must be byte-identical to a
    straight 1-rank run."""
    with socket.socket() as s:
        s.bind(("localhost", 0))
        port = s.getsockname()[1]
    basetemp = tempfile.mkdtemp(prefix="ramba_2proc_elastic_")
    ckpt_root = os.path.join(basetemp, "ckpts")
    trace_base = os.path.join(basetemp, "trace.jsonl")
    budget = float(os.environ.get("RAMBA_TEST_PROCS_TIMEOUT", "600"))

    def base_env():
        env = dict(os.environ)
        env["PYTHONPATH"] = REPO
        for k in ("RAMBA_TEST_PROCS", "RAMBA_TEST_PROC_ID",
                  "RAMBA_TEST_COORD", "RAMBA_TEST_SHARED_TMP",
                  "RAMBA_PROFILE_DIR", "RAMBA_FAULTS", "RAMBA_HBM_BUDGET"):
            env.pop(k, None)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        env["RAMBA_TRACE"] = trace_base
        # armed but generous: nothing here should stall, and a hang in
        # the checkpoint barrier must fail the leg instead of wedging CI
        env["RAMBA_WATCHDOG_S"] = "60"
        return env

    # --- phase 1: 2-rank run, auto-checkpoint at step 2, stop ---
    procs, logs = [], []
    for rank in range(2):
        log = open(os.path.join(basetemp, f"rank{rank}.log"), "w")
        logs.append(log)
        procs.append(subprocess.Popen(
            [sys.executable, "-c", _ELASTIC_SPMD_WORKLOAD, str(rank),
             f"localhost:{port}", ckpt_root],
            env=base_env(), stdout=log, stderr=subprocess.STDOUT, cwd=REPO,
        ))
    deadline = time.time() + budget
    rcs = [None, None]
    try:
        for i, p in enumerate(procs):
            left = max(5.0, deadline - time.time())
            try:
                rcs[i] = p.wait(timeout=left)
            except subprocess.TimeoutExpired:
                p.kill()
                rcs[i] = -9
    finally:
        for log in logs:
            log.close()
    ok = all(rc == 0 for rc in rcs)
    for rank in range(2):
        path = os.path.join(basetemp, f"rank{rank}.log")
        with open(path) as f:
            tail = f.read().splitlines()
        joined = "\n".join(tail)
        if (f"ELASTIC_LEG_SAVED rank={rank} step=2" not in joined
                or f"ELASTIC_LEG_PHASE1_OK rank={rank}" not in joined):
            ok = False
        print(f"--- elastic leg phase 1 rank {rank} rc={rcs[rank]} "
              f"({path}) ---")
        print("\n".join(tail[-(4 if ok else 40):]))

    # --- phase 2: 1-rank resume finishes; reference runs straight ---
    digests = {}
    if ok:
        for name, code, argv in (
            ("resume", _ELASTIC_RESUME_WORKLOAD, [ckpt_root]),
            ("reference", _ELASTIC_REF_WORKLOAD, []),
        ):
            env = base_env()
            r = subprocess.run(
                [sys.executable, "-c", code, *argv],
                env=env, capture_output=True, text=True, cwd=REPO,
                timeout=budget,
            )
            print(f"--- elastic leg {name} rc={r.returncode} ---")
            out = r.stdout.splitlines()
            print("\n".join(out[-4:]) if r.returncode == 0
                  else (r.stdout + r.stderr))
            if r.returncode != 0:
                ok = False
                continue
            for line in out:
                if line.startswith(("ELASTIC_LEG_DIGEST ",
                                    "ELASTIC_LEG_REF ")):
                    digests[name] = line.split(" ", 1)[1].strip()
            if name not in digests:
                print(f"elastic leg: FAIL (no digest from {name})")
                ok = False

    if ok:
        if digests["resume"] != digests["reference"]:
            print("elastic leg: FAIL (resume digest "
                  f"{digests['resume']} != reference "
                  f"{digests['reference']})")
            ok = False
        else:
            print(f"elastic leg: resume after mesh reshape 2->1 is "
                  f"byte-identical (sha256 {digests['resume'][:16]}...)")

    # The per-rank traces must carry the lifecycle story: heartbeats and
    # the checkpoint_saved event from phase 1.
    import json

    if ok:
        for rank in range(2):
            path = f"{trace_base}.rank{rank}"
            try:
                with open(path) as f:
                    evs = [json.loads(ln) for ln in f if ln.strip()]
                n_beat = sum(1 for e in evs if e.get("type") == "heartbeat")
                n_saved = sum(1 for e in evs if e.get("type") == "lifecycle"
                              and e.get("phase") == "checkpoint_saved")
                print(f"elastic leg rank {rank}: {len(evs)} events, "
                      f"{n_beat} heartbeats, {n_saved} checkpoint_saved")
                if n_beat == 0 or n_saved == 0:
                    print(f"elastic leg rank {rank}: FAIL "
                          f"(beats={n_beat}, saved={n_saved})")
                    ok = False
            except (OSError, ValueError) as e:
                print(f"elastic leg rank {rank}: FAIL ({e})")
                ok = False

    print(f"two-process elastic leg: {'OK' if ok else 'FAIL'}")
    if ok:
        shutil.rmtree(basetemp, ignore_errors=True)
    return 0 if ok else 1


def run_serving_leg() -> int:
    """Two ranks drive serving sessions in deterministic lockstep; the
    coalesced-batch fingerprint and the full kernel-key sets must be
    identical across ranks."""
    with socket.socket() as s:
        s.bind(("localhost", 0))
        port = s.getsockname()[1]
    basetemp = tempfile.mkdtemp(prefix="ramba_2proc_serve_")
    trace_base = os.path.join(basetemp, "trace.jsonl")
    budget = float(os.environ.get("RAMBA_TEST_PROCS_TIMEOUT", "600"))

    procs, logs = [], []
    for rank in range(2):
        env = dict(os.environ)
        env["PYTHONPATH"] = REPO
        for k in ("RAMBA_TEST_PROCS", "RAMBA_TEST_PROC_ID",
                  "RAMBA_TEST_COORD", "RAMBA_TEST_SHARED_TMP",
                  "RAMBA_PROFILE_DIR", "RAMBA_FAULTS", "RAMBA_HBM_BUDGET"):
            env.pop(k, None)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        env["RAMBA_TRACE"] = trace_base
        log = open(os.path.join(basetemp, f"rank{rank}.log"), "w")
        logs.append(log)
        procs.append(subprocess.Popen(
            [sys.executable, "-c", _SERVING_WORKLOAD, str(rank),
             f"localhost:{port}"],
            env=env, stdout=log, stderr=subprocess.STDOUT, cwd=REPO,
        ))

    deadline = time.time() + budget
    rcs = [None, None]
    try:
        for i, p in enumerate(procs):
            left = max(5.0, deadline - time.time())
            try:
                rcs[i] = p.wait(timeout=left)
            except subprocess.TimeoutExpired:
                p.kill()
                rcs[i] = -9
    finally:
        for log in logs:
            log.close()

    ok = all(rc == 0 for rc in rcs)

    # Both ranks must agree on the coalesced-program fingerprint AND the
    # full kernel-key set — SPMD serving means identical dispatch.
    marks = {"SERVING_LEG_COALESCE": [None, None],
             "SERVING_LEG_KEYS": [None, None]}
    for rank in range(2):
        path = os.path.join(basetemp, f"rank{rank}.log")
        with open(path) as f:
            tail = f.read().splitlines()
        for line in tail:
            for mark in marks:
                if line.startswith(f"{mark} rank={rank} "):
                    marks[mark][rank] = line.split(" ", 2)[2]
        if any(marks[m][rank] is None for m in marks):
            ok = False
        print(f"--- serving leg rank {rank} rc={rcs[rank]} ({path}) ---")
        print("\n".join(tail[-(4 if ok else 40):]))
    for mark, (r0, r1) in marks.items():
        if ok and r0 != r1:
            print(f"serving leg: FAIL ({mark} diverges: r0={r0} r1={r1})")
            ok = False
    if ok:
        nkeys = len((marks["SERVING_LEG_KEYS"][0] or "").split(","))
        print(f"serving leg: coalesced {marks['SERVING_LEG_COALESCE'][0]}, "
              f"{nkeys} kernel keys, identical on both ranks")

    # The per-rank traces must carry the tenant-tagged serving events.
    import json

    for rank in range(2):
        path = f"{trace_base}.rank{rank}"
        try:
            with open(path) as f:
                evs = [json.loads(ln) for ln in f if ln.strip()]
            n_co = sum(1 for e in evs if e.get("type") == "serve_coalesce")
            n_tenant = sum(1 for e in evs if e.get("type") == "flush"
                           and e.get("tenant") == "spmd")
            print(f"serving leg rank {rank}: {len(evs)} events, "
                  f"{n_co} coalesce, {n_tenant} tenant-tagged flushes")
            if n_co == 0 or n_tenant == 0:
                print(f"serving leg rank {rank}: FAIL "
                      f"(coalesce={n_co}, tenant-flushes={n_tenant})")
                ok = False
        except (OSError, ValueError) as e:
            print(f"serving leg rank {rank}: FAIL ({e})")
            ok = False

    print(f"two-process serving leg: {'OK' if ok else 'FAIL'}")
    if ok:
        shutil.rmtree(basetemp, ignore_errors=True)
    return 0 if ok else 1


def run_telemetry_leg() -> int:
    """Two ranks share ONE trace_id across their serving sessions, serve
    /metrics concurrently, and scrape themselves; rank labels must be
    distinct and the shared trace must land in both ranks' traces."""
    with socket.socket() as s:
        s.bind(("localhost", 0))
        port = s.getsockname()[1]
    basetemp = tempfile.mkdtemp(prefix="ramba_2proc_telem_")
    trace_base = os.path.join(basetemp, "trace.jsonl")
    shared_trace = "feedfacefeedface"
    budget = float(os.environ.get("RAMBA_TEST_PROCS_TIMEOUT", "600"))

    procs, logs = [], []
    for rank in range(2):
        env = dict(os.environ)
        env["PYTHONPATH"] = REPO
        for k in ("RAMBA_TEST_PROCS", "RAMBA_TEST_PROC_ID",
                  "RAMBA_TEST_COORD", "RAMBA_TEST_SHARED_TMP",
                  "RAMBA_PROFILE_DIR", "RAMBA_FAULTS", "RAMBA_HBM_BUDGET",
                  "RAMBA_METRICS_PORT", "RAMBA_METRICS_FILE",
                  "RAMBA_FLIGHT_DIR"):
            env.pop(k, None)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        env["RAMBA_TRACE"] = trace_base
        log = open(os.path.join(basetemp, f"rank{rank}.log"), "w")
        logs.append(log)
        procs.append(subprocess.Popen(
            [sys.executable, "-c", _TELEMETRY_WORKLOAD, str(rank),
             f"localhost:{port}", shared_trace],
            env=env, stdout=log, stderr=subprocess.STDOUT, cwd=REPO,
        ))

    deadline = time.time() + budget
    rcs = [None, None]
    try:
        for i, p in enumerate(procs):
            left = max(5.0, deadline - time.time())
            try:
                rcs[i] = p.wait(timeout=left)
            except subprocess.TimeoutExpired:
                p.kill()
                rcs[i] = -9
    finally:
        for log in logs:
            log.close()

    ok = all(rc == 0 for rc in rcs)

    # Each rank's scrape must be labeled with its OWN rank — concurrent
    # exporters on one host stay distinguishable after aggregation.
    labels = [None, None]
    for rank in range(2):
        path = os.path.join(basetemp, f"rank{rank}.log")
        with open(path) as f:
            tail = f.read().splitlines()
        for line in tail:
            if line.startswith(f"TELEMETRY_LEG_SCRAPE rank={rank} "):
                labels[rank] = line.split("labels=")[1].split(" ")[0]
        if labels[rank] is None:
            ok = False
        print(f"--- telemetry leg rank {rank} rc={rcs[rank]} ({path}) ---")
        print("\n".join(tail[-(4 if ok else 40):]))
    if ok:
        if labels[0] == labels[1] or labels != [str(r) for r in range(2)]:
            print(f"telemetry leg: FAIL (rank labels not distinct: "
                  f"r0={labels[0]} r1={labels[1]})")
            ok = False
        else:
            print(f"telemetry leg: scrapes labeled rank={labels[0]} / "
                  f"rank={labels[1]}, distinct")

    # One request, two ranks: the shared trace_id must appear in BOTH
    # per-rank event files — what --trace needs to merge the story.
    import json

    for rank in range(2):
        path = f"{trace_base}.rank{rank}"
        try:
            with open(path) as f:
                evs = [json.loads(ln) for ln in f if ln.strip()]
            traced = [e for e in evs if e.get("trace_id") == shared_trace
                      or shared_trace in (e.get("trace_ids") or [])]
            kinds = sorted({e.get("type", "?") for e in traced})
            print(f"telemetry leg rank {rank}: {len(evs)} events, "
                  f"{len(traced)} in trace {shared_trace} ({','.join(kinds)})")
            if not traced:
                print(f"telemetry leg rank {rank}: FAIL (shared trace "
                      f"missing)")
                ok = False
        except (OSError, ValueError) as e:
            print(f"telemetry leg rank {rank}: FAIL ({e})")
            ok = False

    # And the cross-rank causal chain must actually reconstruct.
    if ok:
        merged = subprocess.run(
            [sys.executable, os.path.join(REPO, "scripts", "trace_report.py"),
             trace_base, "--trace", shared_trace],
            capture_output=True, text=True, cwd=REPO,
        )
        print(merged.stdout.strip())
        if merged.returncode != 0 or "2 process(es)" not in merged.stdout:
            print(f"telemetry leg: FAIL (--trace rc={merged.returncode})")
            print(merged.stderr.strip())
            ok = False

    print(f"two-process telemetry leg: {'OK' if ok else 'FAIL'}")
    if ok:
        shutil.rmtree(basetemp, ignore_errors=True)
    return 0 if ok else 1


def run_fleet_leg() -> int:
    """Fleet observability federation acceptance (PR 16): three
    INDEPENDENT replica processes publish into one snapshot spool.  The
    collector must (a) prove every live replica healthy with lockstep
    kernel fingerprints, (b) reconcile the fleet goodput rollup against
    the per-replica spool documents within 1%, (c) classify an injected
    torn document without crashing, (d) flag a replica killed mid-soak
    dead within 2x the publish interval, and (e) the stitched --trace
    view over the per-replica trace dirs must span the replicas."""
    import json
    import signal

    basetemp = tempfile.mkdtemp(prefix="ramba_2proc_fleet_")
    fleet_dir = os.path.join(basetemp, "fleet")
    traces = os.path.join(basetemp, "traces")
    interval = 0.2
    soak_s = 120.0
    shared_trace = "feedfacef1ee70001"
    budget = float(os.environ.get("RAMBA_TEST_PROCS_TIMEOUT", "600"))
    n = 3
    collector = os.path.join(REPO, "scripts", "fleet_collector.py")

    procs, logs = [], []
    for idx in range(n):
        env = dict(os.environ)
        env["PYTHONPATH"] = REPO
        for k in ("RAMBA_TEST_PROCS", "RAMBA_TEST_PROC_ID",
                  "RAMBA_TEST_COORD", "RAMBA_TEST_SHARED_TMP",
                  "RAMBA_PROFILE_DIR", "RAMBA_FAULTS", "RAMBA_HBM_BUDGET",
                  "RAMBA_METRICS_PORT", "RAMBA_METRICS_FILE",
                  "RAMBA_FLIGHT_DIR", "RAMBA_FLEET_DIR",
                  "RAMBA_FLEET_INTERVAL_S", "RAMBA_FLEET_STALE_X",
                  "RAMBA_FLEET_DEAD_X"):
            env.pop(k, None)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        env["RAMBA_FLEET_DIR"] = fleet_dir
        env["RAMBA_FLEET_INTERVAL_S"] = str(interval)
        tdir = os.path.join(traces, f"replica{idx}")
        os.makedirs(tdir, exist_ok=True)
        env["RAMBA_TRACE"] = os.path.join(tdir, "trace.jsonl")
        log = open(os.path.join(basetemp, f"replica{idx}.log"), "w")
        logs.append(log)
        procs.append(subprocess.Popen(
            [sys.executable, "-c", _FLEET_WORKLOAD, str(idx),
             shared_trace, str(soak_s)],
            env=env, stdout=log, stderr=subprocess.STDOUT, cwd=REPO,
        ))

    ok = True
    deadline = time.time() + budget

    def _tail(idx):
        with open(os.path.join(basetemp, f"replica{idx}.log")) as f:
            return f.read().splitlines()

    def _collect(expect_rc, phase):
        nonlocal ok
        r = subprocess.run(
            [sys.executable, collector, fleet_dir, "--json"],
            capture_output=True, text=True, cwd=REPO,
        )
        doc = None
        try:
            doc = json.loads(r.stdout)
        except ValueError:
            pass
        if "Traceback" in r.stderr or doc is None:
            print(f"fleet leg: FAIL ({phase}: collector crashed)")
            print(r.stdout[-2000:] + r.stderr[-2000:])
            ok = False
        elif r.returncode != expect_rc:
            print(f"fleet leg: FAIL ({phase}: collector rc={r.returncode}, "
                  f"want {expect_rc})")
            print(json.dumps(doc.get("health", {}), indent=2)[:2000])
            ok = False
        return doc

    # -- phase A: every replica publishes and goes healthy -------------------
    fps = [None] * n
    while time.time() < deadline and any(f is None for f in fps):
        for idx in range(n):
            if fps[idx] is not None:
                continue
            for line in _tail(idx):
                if line.startswith(f"FLEET_REPLICA_OK idx={idx}"):
                    fps[idx] = line.split("fps=")[1].strip()
            if fps[idx] is None and procs[idx].poll() is not None:
                print(f"fleet leg: FAIL (replica {idx} exited "
                      f"rc={procs[idx].returncode} before READY)")
                print("\n".join(_tail(idx)[-40:]))
                ok = False
                deadline = 0  # bail out of the wait loop
        if ok and any(f is None for f in fps):
            time.sleep(0.1)
    if ok and any(f is None for f in fps):
        print(f"fleet leg: FAIL (timeout waiting for READY markers {fps})")
        ok = False

    if ok:
        if not fps[0] or len(set(fps)) != 1:
            print(f"fleet leg: FAIL (kernel fingerprints not lockstep: "
                  f"{fps})")
            ok = False
        else:
            print(f"fleet leg: {n} replicas ready, lockstep kernel "
                  f"fingerprints [{fps[0]}]")

    if ok:
        doc = _collect(0, "healthy fleet")
        if ok:
            h = doc["health"]
            if (h["fleet_state"] != "healthy"
                    or h["counts"]["healthy"] != n):
                print(f"fleet leg: FAIL (want {n} healthy, got "
                      f"{h['counts']} fleet_state={h['fleet_state']})")
                ok = False
            else:
                ages = [r["age_s"] for r in h["replicas"].values()]
                print(f"fleet leg: collector proves {n} healthy "
                      f"(max snapshot age {max(ages):.2f}s)")

        # rollup reconciliation: fleet goodput vs the raw spool documents
        if ok:
            raw_flushes = raw_nodes = 0
            for f in sorted(os.listdir(fleet_dir)):
                with open(os.path.join(fleet_dir, f)) as fh:
                    d = json.load(fh)
                counters = d["diagnostics"]["counters"]
                raw_flushes += int(counters.get("fuser.flushes", 0))
                raw_nodes += int(counters.get("fuser.nodes_flushed", 0))
            gp = doc["rollup"]["goodput"]
            per_rep_sum = sum(r["flushes"]
                              for r in gp["replicas"].values())
            drift = abs(gp["flushes"] - raw_flushes) \
                / max(1, raw_flushes)
            if (gp["flushes"] != per_rep_sum or drift > 0.01
                    or raw_flushes == 0):
                print(f"fleet leg: FAIL (rollup {gp['flushes']} != "
                      f"per-replica {per_rep_sum} / raw {raw_flushes})")
                ok = False
            else:
                print(f"fleet leg: rollup reconciles (fleet "
                      f"flushes={gp['flushes']} == raw spool sum "
                      f"{raw_flushes}, nodes={raw_nodes})")

    # -- phase B: stitched cross-process trace -------------------------------
    if ok:
        merged = subprocess.run(
            [sys.executable, os.path.join(REPO, "scripts",
                                          "trace_report.py"),
             traces, "--trace", shared_trace],
            capture_output=True, text=True, cwd=REPO,
        )
        print(merged.stdout.strip())
        if (merged.returncode != 0
                or f"{n} process(es)" not in merged.stdout):
            print(f"fleet leg: FAIL (--trace over {traces} "
                  f"rc={merged.returncode})")
            print(merged.stderr.strip())
            ok = False

    # -- phase C: torn document never crashes the collector ------------------
    if ok:
        torn = os.path.join(fleet_dir, "torn-deadbeef-0.json")
        with open(torn, "w") as f:
            f.write('{"schema_version": 1, "replica": "torn-deadbe')
        doc = _collect(2, "torn document")  # stale present -> rc 2
        if ok:
            row = doc["health"]["replicas"].get("torn-deadbeef-0")
            if row is None or row["state"] != "stale":
                print(f"fleet leg: FAIL (torn doc classified {row})")
                ok = False
            else:
                print(f"fleet leg: torn document classified stale "
                      f"({row['reason']}), no crash")
        os.unlink(torn)

    # -- phase D: replica killed mid-soak goes dead within 2x interval -------
    if ok:
        victim = n - 1
        procs[victim].send_signal(signal.SIGKILL)
        procs[victim].wait(timeout=30)
        t_kill = time.monotonic()
        # the last publish predates the kill, so the snapshot's age
        # crosses the dead threshold no later than kill + 2x interval
        time.sleep(2.0 * interval)
        doc = _collect(3, "dead replica")  # dead present -> rc 3
        elapsed = time.monotonic() - t_kill
        if ok:
            dead = [rep for rep, r in doc["health"]["replicas"].items()
                    if r["state"] == "dead"]
            counts = doc["health"]["counts"]
            if len(dead) != 1 or counts["healthy"] != n - 1:
                print(f"fleet leg: FAIL (want 1 dead / {n - 1} healthy "
                      f"{elapsed:.2f}s after kill, got {counts})")
                ok = False
            else:
                age = doc["health"]["replicas"][dead[0]]["age_s"]
                print(f"fleet leg: killed replica {dead[0]} flagged dead "
                      f"at the first scrape past 2x interval "
                      f"({elapsed:.2f}s after SIGKILL, snapshot age "
                      f"{age:.2f}s, dead threshold {2 * interval:.1f}s)")

    for p in procs:
        if p.poll() is None:
            p.terminate()
    for p in procs:
        try:
            p.wait(timeout=10)
        except subprocess.TimeoutExpired:
            p.kill()
    for log in logs:
        log.close()
    print(f"fleet leg: {'OK' if ok else 'FAIL'}")
    if ok:
        shutil.rmtree(basetemp, ignore_errors=True)
    return 0 if ok else 1


# Router-leg driver (PR 17): runs in its OWN subprocess so the router's
# redirect/heal events stream into a dedicated RAMBA_TRACE file that the
# stitched trace view can interleave with the replicas'.  Spawns replica
# servers via scripts/fleet_router.py and walks the serving plane through
# four phases, printing one ROUTER_* marker line per phase for the leg
# runner to assert on.  argv: <traces_dir>.
_ROUTER_DRIVER = """
import os
import sys
import time

traces = sys.argv[1]
sys.path.insert(0, os.path.join(os.environ["PYTHONPATH"], "scripts"))
import fleet_router

from ramba_tpu.fleet.router import Router

TRACE = "deadbeefcafe0001"
SEQ = [("init", {"name": "x", "shape": [256], "fill": 2.0})] + [
    ("affine", {"name": "x", "a": 1.01, "b": float(i)}) for i in range(4)]


def spawn(idx, extra=None):
    tdir = os.path.join(traces, "replica%d" % idx)
    os.makedirs(tdir, exist_ok=True)
    env = {"RAMBA_TRACE": os.path.join(tdir, "trace.jsonl")}
    env.update(extra or {})
    return fleet_router.spawn_replica(env)


def run_session(router, tenant, trace_id=None):
    sid = router.open_session(tenant=tenant, trace_id=trace_id)
    for w, p in SEQ:
        router.step(sid, w, p)
    digest = router.step(sid, "digest")["result"]
    router.close_session(sid)
    return digest


def stop(router, *procs):
    router.shutdown_fleet()
    for p in procs:
        try:
            p.wait(timeout=30)
        except Exception:
            p.kill()


# phase 1: one cold replica pays every compile, fills the shared tier,
# and defines the no-fault reference digest (the workload registry is
# deterministic, so this digest is THE answer for every later phase)
p0, ep0 = spawn(0)
r0 = Router(endpoints=[ep0])
ref = [run_session(r0, t) for t in ("acme", "globex")]
assert len(set(ref)) == 1, ref
c0 = r0.call_replica(ep0, "stats")["counters"]
saved = r0.call_replica(ep0, "save_artifacts", k=16)["saved"]
stop(r0, p0)
print("ROUTER_REF digest=%s compiles=%d aot_stored=%d" % (
    ref[0], c0["fuser.compiles"], saved.get("stored", 0)), flush=True)

# phase 2: cold process, shared AOT tier on but the shared memo lane
# OFF -- every flush demand-compiles, and the compiler must be fed by
# replica 0's persisted executables (cross-writer AOT hits)
p1, ep1 = spawn(1, {"RAMBA_MEMO_SHARED": "0"})
r1 = Router(endpoints=[ep1])
d1 = [run_session(r1, t) for t in ("acme", "globex")]
c1 = r1.call_replica(ep1, "stats")["counters"]
stop(r1, p1)
print("ROUTER_WARM_AOT ok=%d cross=%d compiles=%d" % (
    int(d1 == ref), c1["compile.persist_cross_hit"],
    c1["fuser.compiles"]), flush=True)

# phase 3: cold process, shared memo lane ON -- flushes hit replica 0's
# content-addressed memo blobs and skip the compiler
p2, ep2 = spawn(2)
r2 = Router(endpoints=[ep2])
d2 = [run_session(r2, t) for t in ("acme", "globex")]
c2 = r2.call_replica(ep2, "stats")["counters"]
stop(r2, p2)
print("ROUTER_WARM_MEMO ok=%d shared=%d compiles=%d" % (
    int(d2 == ref), c2["memo.shared_hit"], c2["fuser.compiles"]),
    flush=True)

# phase 4: two replicas, four tenants; SIGKILL the replica serving
# tenant acme mid-soak -- its sessions must redirect off the corpse
# (trip the fleet breaker), heal by deterministic replay on the
# survivor, and finish byte-identical to the phase-1 reference
procs = {}
p3, ep3 = spawn(3)
p4, ep4 = spawn(4)
procs[ep3], procs[ep4] = p3, p4
rt = Router(endpoints=[ep3, ep4])
tenants = ("acme", "globex", "initech", "umbrella")
sids = {t: rt.open_session(
            tenant=t, trace_id=(TRACE if t == "acme" else None))
        for t in tenants}
victim = None
for i, (w, p) in enumerate(SEQ):
    for t in tenants:
        rt.step(sids[t], w, p)
    if i == 1:
        victim = rt.stats()["sessions"][sids["acme"]]["endpoint"]
        procs[victim].kill()
        procs[victim].wait(timeout=30)
d4 = [rt.step(sids[t], "digest")["result"] for t in tenants]
st = rt.stats()
trips = st["replicas"][victim]["breaker"]["trips"]
survivor = ep4 if victim == ep3 else ep3
c4 = rt.call_replica(survivor, "stats")["counters"]
stop(rt, procs[survivor])
print("ROUTER_HEAL ok=%d redirects=%d heals=%d trips=%d "
      "surv_shared=%d trace=%s" % (
          int(all(d == ref[0] for d in d4)), st["redirects"],
          st["heals"], trips, c4["memo.shared_hit"], TRACE), flush=True)
print("ROUTER_DRIVER_OK", flush=True)
"""


def run_router_leg() -> int:
    """Fleet serving-plane acceptance (PR 17): a router process drives
    five replica servers (spawned/killed across four phases) against one
    snapshot spool + shared artifact tier.  Asserts (a) a cold replica
    compiles and persists, (b) a second cold replica comes up WARM off
    the shared AOT tier (cross-writer persist hits, byte-identical
    digests), (c) a third comes up warm off the shared memo lane with
    near-zero demand compiles, (d) a replica SIGKILLed mid-soak trips
    the router's fleet breaker, its tenants redirect + heal by replay
    onto the survivor with byte-identical digests, and (e) the stitched
    trace over router + replica trace files tells the redirect/heal
    story."""
    basetemp = tempfile.mkdtemp(prefix="ramba_2proc_router_")
    fleet_dir = os.path.join(basetemp, "fleet")
    traces = os.path.join(basetemp, "traces")
    budget = float(os.environ.get("RAMBA_TEST_PROCS_TIMEOUT", "900"))

    env = dict(os.environ)
    env["PYTHONPATH"] = REPO
    for k in ("RAMBA_TEST_PROCS", "RAMBA_TEST_PROC_ID",
              "RAMBA_TEST_COORD", "RAMBA_TEST_SHARED_TMP",
              "RAMBA_PROFILE_DIR", "RAMBA_FAULTS", "RAMBA_HBM_BUDGET",
              "RAMBA_METRICS_PORT", "RAMBA_METRICS_FILE",
              "RAMBA_FLIGHT_DIR", "RAMBA_FLEET_DIR",
              "RAMBA_FLEET_INTERVAL_S", "RAMBA_FLEET_STALE_X",
              "RAMBA_FLEET_DEAD_X", "RAMBA_FLEET_ENDPOINT",
              "RAMBA_FLEET_AUTHKEY", "RAMBA_ARTIFACTS", "RAMBA_CACHE",
              "RAMBA_AOT", "RAMBA_MEMO", "RAMBA_MEMO_SHARED",
              "RAMBA_MEMO_SHARED_MAX", "RAMBA_HANDOFF_DIR",
              "RAMBA_ROUTER_TIMEOUT_S", "RAMBA_ROUTER_HEDGE",
              "RAMBA_ROUTER_HEDGE_FACTOR", "RAMBA_ROUTER_MAX_REDIRECTS",
              "RAMBA_BREAKER_THRESHOLD", "RAMBA_TRACE"):
        env.pop(k, None)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["RAMBA_FLEET_DIR"] = fleet_dir
    env["RAMBA_FLEET_INTERVAL_S"] = "0.2"
    env["RAMBA_ARTIFACTS"] = os.path.join(basetemp, "artifacts")
    env["RAMBA_CACHE"] = os.path.join(basetemp, "aot")  # shared AOT tier
    # jax's own cache, placed by the environment in a directory that
    # starts empty: the AOT lane stores only fresh compiles
    env["JAX_COMPILATION_CACHE_DIR"] = os.path.join(basetemp, "jax_cache")
    env["RAMBA_MEMO"] = "1"
    env["RAMBA_BREAKER_THRESHOLD"] = "1"  # first failure trips
    env["RAMBA_ROUTER_TIMEOUT_S"] = "10"
    rdir = os.path.join(traces, "router")
    os.makedirs(rdir, exist_ok=True)
    env["RAMBA_TRACE"] = os.path.join(rdir, "trace.jsonl")

    log_path = os.path.join(basetemp, "driver.log")
    with open(log_path, "w") as log:
        proc = subprocess.Popen(
            [sys.executable, "-c", _ROUTER_DRIVER, traces],
            env=env, stdout=log, stderr=subprocess.STDOUT, cwd=REPO)
        try:
            rc = proc.wait(timeout=budget)
        except subprocess.TimeoutExpired:
            proc.kill()
            rc = -9
    with open(log_path) as f:
        lines = f.read().splitlines()
    marks = {}
    for ln in lines:
        if ln.startswith("ROUTER_"):
            parts = ln.split()
            marks[parts[0]] = dict(
                kv.split("=", 1) for kv in parts[1:] if "=" in kv)

    ok = rc == 0 and "ROUTER_DRIVER_OK" in marks
    if not ok:
        print(f"router leg: FAIL (driver rc={rc}, markers "
              f"{sorted(marks)})")
        print("\n".join(lines[-60:]))

    def _ints(mark):
        return {k: int(v) for k, v in marks[mark].items()
                if v.lstrip("-").isdigit()}

    if ok:
        ref = _ints("ROUTER_REF")
        if ref["compiles"] == 0 or ref["aot_stored"] == 0:
            print(f"router leg: FAIL (cold replica should compile and "
                  f"persist, got {marks['ROUTER_REF']})")
            ok = False
        else:
            print(f"router leg: cold replica paid {ref['compiles']} "
                  f"compiles, persisted {ref['aot_stored']} AOT blobs, "
                  f"reference digest {marks['ROUTER_REF']['digest'][:16]}")

    if ok:
        aot = _ints("ROUTER_WARM_AOT")
        if not aot["ok"] or aot["cross"] == 0:
            print(f"router leg: FAIL (AOT-warm replica: want "
                  f"byte-identical digests + cross-writer persist hits, "
                  f"got {marks['ROUTER_WARM_AOT']})")
            ok = False
        else:
            print(f"router leg: replica 2 warm off the shared AOT tier "
                  f"({aot['cross']} cross-writer hits, "
                  f"{aot['compiles']} demand compiles, digests match)")

    if ok:
        memo = _ints("ROUTER_WARM_MEMO")
        if (not memo["ok"] or memo["shared"] == 0
                or memo["compiles"] >= ref["compiles"]):
            print(f"router leg: FAIL (memo-warm replica: want "
                  f"byte-identical digests, >0 shared memo hits, fewer "
                  f"compiles than cold ({ref['compiles']}), got "
                  f"{marks['ROUTER_WARM_MEMO']})")
            ok = False
        else:
            print(f"router leg: replica 3 warm off the shared memo lane "
                  f"({memo['shared']} cross-replica memo hits, "
                  f"{memo['compiles']} vs cold {ref['compiles']} demand "
                  f"compiles, digests match)")

    if ok:
        heal = _ints("ROUTER_HEAL")
        if (not heal["ok"] or heal["redirects"] == 0
                or heal["heals"] == 0 or heal["trips"] == 0):
            print(f"router leg: FAIL (kill mid-soak: want byte-identical "
                  f"digests + redirects + heals + breaker trips, got "
                  f"{marks['ROUTER_HEAL']})")
            ok = False
        else:
            print(f"router leg: SIGKILL mid-soak healed "
                  f"({heal['redirects']} redirects, {heal['heals']} "
                  f"replay heals, {heal['trips']} breaker trips, "
                  f"survivor made {heal['surv_shared']} shared memo "
                  f"hits, all 4 tenants byte-identical)")

    # stitched trace: router + replica files interleave, and the
    # redirect/heal story is visible in the merged noteworthy stream
    if ok:
        merged = subprocess.run(
            [sys.executable,
             os.path.join(REPO, "scripts", "trace_report.py"),
             traces, "--merge-ranks"],
            capture_output=True, text=True, cwd=REPO)
        if (merged.returncode != 0 or "redirect" not in merged.stdout
                or "heal" not in merged.stdout):
            print(f"router leg: FAIL (--merge-ranks rc="
                  f"{merged.returncode} must show the redirect/heal "
                  f"story)")
            print(merged.stdout[-2000:] + merged.stderr[-2000:])
            ok = False
        else:
            note = [ln for ln in merged.stdout.splitlines()
                    if "redirect" in ln or "heal" in ln]
            print("router leg: stitched trace shows the failover story:")
            print("\n".join(f"  {ln.strip()}" for ln in note[:6]))

    if ok:
        chain = subprocess.run(
            [sys.executable,
             os.path.join(REPO, "scripts", "trace_report.py"),
             traces, "--trace", marks["ROUTER_HEAL"]["trace"]],
            capture_output=True, text=True, cwd=REPO)
        if chain.returncode != 0 or "process(es)" not in chain.stdout:
            print(f"router leg: FAIL (--trace "
                  f"{marks['ROUTER_HEAL']['trace']} rc="
                  f"{chain.returncode})")
            print(chain.stdout[-2000:] + chain.stderr[-2000:])
            ok = False
        else:
            head = chain.stdout.splitlines()[0]
            print(f"router leg: {head.strip()}")

    print(f"router leg: {'OK' if ok else 'FAIL'}")
    if ok:
        shutil.rmtree(basetemp, ignore_errors=True)
    return 0 if ok else 1


def run_perf_leg() -> int:
    """Two ranks under RAMBA_PERF=1; both ledgers must report the same
    kernel fingerprint set, and the merged timeline must build."""
    with socket.socket() as s:
        s.bind(("localhost", 0))
        port = s.getsockname()[1]
    basetemp = tempfile.mkdtemp(prefix="ramba_2proc_perf_")
    trace_base = os.path.join(basetemp, "trace.jsonl")
    budget = float(os.environ.get("RAMBA_TEST_PROCS_TIMEOUT", "600"))

    procs, logs = [], []
    for rank in range(2):
        env = dict(os.environ)
        env["PYTHONPATH"] = REPO
        for k in ("RAMBA_TEST_PROCS", "RAMBA_TEST_PROC_ID",
                  "RAMBA_TEST_COORD", "RAMBA_TEST_SHARED_TMP",
                  "RAMBA_PROFILE_DIR", "RAMBA_FAULTS", "RAMBA_HBM_BUDGET"):
            env.pop(k, None)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        env["RAMBA_PERF"] = "1"
        env["RAMBA_TRACE"] = trace_base
        log = open(os.path.join(basetemp, f"rank{rank}.log"), "w")
        logs.append(log)
        procs.append(subprocess.Popen(
            [sys.executable, "-c", _PERF_WORKLOAD, str(rank),
             f"localhost:{port}"],
            env=env, stdout=log, stderr=subprocess.STDOUT, cwd=REPO,
        ))

    deadline = time.time() + budget
    rcs = [None, None]
    try:
        for i, p in enumerate(procs):
            left = max(5.0, deadline - time.time())
            try:
                rcs[i] = p.wait(timeout=left)
            except subprocess.TimeoutExpired:
                p.kill()
                rcs[i] = -9
    finally:
        for log in logs:
            log.close()

    ok = all(rc == 0 for rc in rcs)

    # Both ranks' ledgers must report the identical kernel-key set:
    # fingerprints are structure-stable, so SPMD lockstep => equal sets.
    keysets = [None, None]
    for rank in range(2):
        path = os.path.join(basetemp, f"rank{rank}.log")
        with open(path) as f:
            tail = f.read().splitlines()
        for line in tail:
            if line.startswith(f"PERF_LEG_KEYS rank={rank} "):
                keysets[rank] = line.split(" ", 2)[2]
        if keysets[rank] is None:
            ok = False
        print(f"--- perf leg rank {rank} rc={rcs[rank]} ({path}) ---")
        print("\n".join(tail[-(4 if ok else 40):]))
    if ok and keysets[0] != keysets[1]:
        print(f"perf leg: FAIL (kernel keys diverge: "
              f"r0={keysets[0]} r1={keysets[1]})")
        ok = False
    elif ok:
        nkeys = len((keysets[0] or "").split(","))
        print(f"perf leg: {nkeys} kernel keys, identical on both ranks")

    # The cross-rank merged timeline must build from the per-rank traces
    # and see both ranks in lockstep.
    if ok:
        merged = subprocess.run(
            [sys.executable, os.path.join(REPO, "scripts", "trace_report.py"),
             trace_base, "--merge-ranks"],
            capture_output=True, text=True, cwd=REPO,
        )
        print(merged.stdout.strip())
        if (merged.returncode != 0
                or "2 rank(s)" not in merged.stdout
                or "rank divergence: none" not in merged.stdout):
            print(f"perf leg: FAIL (merge-ranks rc={merged.returncode})")
            print(merged.stderr.strip())
            ok = False

    print(f"two-process perf leg: {'OK' if ok else 'FAIL'}")
    if ok:
        shutil.rmtree(basetemp, ignore_errors=True)
    return 0 if ok else 1


def run_attrib_leg() -> int:
    """Two ranks under RAMBA_PERF=1; both must stamp lockstep stage
    signatures and reconcile stage sums with span wall; the stage
    waterfall and merged stage columns must build."""
    with socket.socket() as s:
        s.bind(("localhost", 0))
        port = s.getsockname()[1]
    basetemp = tempfile.mkdtemp(prefix="ramba_2proc_attrib_")
    trace_base = os.path.join(basetemp, "trace.jsonl")
    budget = float(os.environ.get("RAMBA_TEST_PROCS_TIMEOUT", "600"))

    procs, logs = [], []
    for rank in range(2):
        env = dict(os.environ)
        env["PYTHONPATH"] = REPO
        for k in ("RAMBA_TEST_PROCS", "RAMBA_TEST_PROC_ID",
                  "RAMBA_TEST_COORD", "RAMBA_TEST_SHARED_TMP",
                  "RAMBA_PROFILE_DIR", "RAMBA_FAULTS", "RAMBA_HBM_BUDGET"):
            env.pop(k, None)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        env["RAMBA_PERF"] = "1"
        env["RAMBA_TRACE"] = trace_base
        log = open(os.path.join(basetemp, f"rank{rank}.log"), "w")
        logs.append(log)
        procs.append(subprocess.Popen(
            [sys.executable, "-c", _ATTRIB_WORKLOAD, str(rank),
             f"localhost:{port}"],
            env=env, stdout=log, stderr=subprocess.STDOUT, cwd=REPO,
        ))

    deadline = time.time() + budget
    rcs = [None, None]
    try:
        for i, p in enumerate(procs):
            left = max(5.0, deadline - time.time())
            try:
                rcs[i] = p.wait(timeout=left)
            except subprocess.TimeoutExpired:
                p.kill()
                rcs[i] = -9
    finally:
        for log in logs:
            log.close()

    ok = all(rc == 0 for rc in rcs)

    marks = {"ATTRIB_LEG_STAGES": [None, None]}
    for rank in range(2):
        path = os.path.join(basetemp, f"rank{rank}.log")
        with open(path) as f:
            tail = f.read().splitlines()
        for line in tail:
            for key in marks:
                if line.startswith(f"{key} rank={rank} "):
                    marks[key][rank] = line.split(" ", 2)[2]
        if any(marks[key][rank] is None for key in marks):
            ok = False
        print(f"--- attrib leg rank {rank} rc={rcs[rank]} ({path}) ---")
        print("\n".join(tail[-(4 if ok else 40):]))
    for key, vals in marks.items():
        if ok and vals[0] != vals[1]:
            print(f"attrib leg: FAIL ({key} diverges: "
                  f"r0={vals[0]} r1={vals[1]})")
            ok = False
    if ok:
        nflush = len((marks["ATTRIB_LEG_STAGES"][0] or "").split(";"))
        print(f"attrib leg: {nflush} lockstep stage signature(s), "
              f"identical on both ranks")

    # The stage waterfall and the merged stage columns must build from
    # the per-rank traces with no rank divergence.
    if ok:
        waterfall = subprocess.run(
            [sys.executable, os.path.join(REPO, "scripts", "trace_report.py"),
             trace_base, "--attrib"],
            capture_output=True, text=True, cwd=REPO,
        )
        print(waterfall.stdout.strip())
        if (waterfall.returncode != 0
                or "stage waterfall" not in waterfall.stdout):
            print(f"attrib leg: FAIL (--attrib rc={waterfall.returncode})")
            print(waterfall.stderr.strip())
            ok = False
    if ok:
        merged = subprocess.run(
            [sys.executable, os.path.join(REPO, "scripts", "trace_report.py"),
             trace_base, "--merge-ranks"],
            capture_output=True, text=True, cwd=REPO,
        )
        print(merged.stdout.strip())
        if (merged.returncode != 0
                or "rank divergence: none" not in merged.stdout
                or "stage seconds per rank:" not in merged.stdout):
            print(f"attrib leg: FAIL (merge-ranks rc={merged.returncode})")
            print(merged.stderr.strip())
            ok = False

    print(f"two-process attrib leg: {'OK' if ok else 'FAIL'}")
    if ok:
        shutil.rmtree(basetemp, ignore_errors=True)
    return 0 if ok else 1


def run_sampling_leg() -> int:
    """Two ranks under RAMBA_TRACE_SAMPLE=4 with a rank-skewed
    execute:delay fault; both ranks stay clean under the skew and
    steady-state file volume must drop >= 4x, to the same hash-selected
    chains on both."""
    import json

    with socket.socket() as s:
        s.bind(("localhost", 0))
        port = s.getsockname()[1]
    basetemp = tempfile.mkdtemp(prefix="ramba_2proc_sampling_")
    trace_base = os.path.join(basetemp, "trace.jsonl")
    budget = float(os.environ.get("RAMBA_TEST_PROCS_TIMEOUT", "600"))

    procs, logs = [], []
    for rank in range(2):
        env = dict(os.environ)
        env["PYTHONPATH"] = REPO
        for k in ("RAMBA_TEST_PROCS", "RAMBA_TEST_PROC_ID",
                  "RAMBA_TEST_COORD", "RAMBA_TEST_SHARED_TMP",
                  "RAMBA_PROFILE_DIR", "RAMBA_HBM_BUDGET",
                  "RAMBA_SLO_P95_MS"):
            env.pop(k, None)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        env["RAMBA_PERF"] = "1"
        env["RAMBA_TRACE"] = trace_base
        env["RAMBA_TRACE_SAMPLE"] = "4"
        # rank-skewed slowness: same env on BOTH ranks (the per-site
        # call counter must advance everywhere), fires on rank 1 only
        env["RAMBA_FAULTS"] = "execute:delay:ms=40:rank=1"
        log = open(os.path.join(basetemp, f"rank{rank}.log"), "w")
        logs.append(log)
        procs.append(subprocess.Popen(
            [sys.executable, "-c", _SAMPLING_WORKLOAD, str(rank),
             f"localhost:{port}"],
            env=env, stdout=log, stderr=subprocess.STDOUT, cwd=REPO,
        ))

    deadline = time.time() + budget
    rcs = [None, None]
    try:
        for i, p in enumerate(procs):
            left = max(5.0, deadline - time.time())
            try:
                rcs[i] = p.wait(timeout=left)
            except subprocess.TimeoutExpired:
                p.kill()
                rcs[i] = -9
    finally:
        for log in logs:
            log.close()

    ok = all(rc == 0 for rc in rcs)

    marks = {"SAMPLING_LEG_HEALTH": [None, None]}
    for rank in range(2):
        path = os.path.join(basetemp, f"rank{rank}.log")
        with open(path) as f:
            tail = f.read().splitlines()
        for line in tail:
            for key in marks:
                if line.startswith(f"{key} rank={rank} "):
                    marks[key][rank] = line.split(" ", 2)[2]
        if any(marks[key][rank] is None for key in marks):
            ok = False
        print(f"--- sampling leg rank {rank} rc={rcs[rank]} ({path}) ---")
        print("\n".join(tail[-(4 if ok else 40):]))

    if ok:
        for rank in range(2):
            fields = dict(kv.split("=") for kv
                          in marks["SAMPLING_LEG_HEALTH"][rank].split())
            if fields["stalls"] != "0" or fields["local"] != "0":
                print(f"sampling leg: FAIL (rank {rank} not clean under "
                      f"skew: {fields})")
                ok = False

    # file-lane check per rank: exactly the 5 hash-selected steady
    # chains on disk (9.6x volume drop)
    if ok:
        for rank in range(2):
            fpath = f"{trace_base}.rank{rank}"
            steady_ids = set()
            try:
                with open(fpath) as f:
                    for line in f:
                        try:
                            e = json.loads(line)
                        except ValueError:
                            continue
                        tid = e.get("trace_id") or ""
                        if tid.startswith("steady-"):
                            steady_ids.add(tid)
            except OSError as exc:
                print(f"sampling leg: FAIL (rank {rank} trace file: {exc})")
                ok = False
                continue
            if len(steady_ids) != 5:
                print(f"sampling leg: FAIL (rank {rank}: {len(steady_ids)} "
                      f"steady chains on disk, expected the 5 hash-selected "
                      f"ones: {sorted(steady_ids)})")
                ok = False
            if ok:
                print(f"sampling leg rank {rank}: 5/48 steady chains on "
                      f"disk (9.6x drop)")

    print(f"two-process sampling leg: {'OK' if ok else 'FAIL'}")
    if ok:
        shutil.rmtree(basetemp, ignore_errors=True)
    return 0 if ok else 1


def run_memo_leg() -> int:
    """Two ranks under RAMBA_MEMO=1; both must compute the identical
    canonical hash and hit the result cache in LOCKSTEP (a hit skips
    dispatch — rank-skewed hits would mispair the post-flush gathers)."""
    with socket.socket() as s:
        s.bind(("localhost", 0))
        port = s.getsockname()[1]
    basetemp = tempfile.mkdtemp(prefix="ramba_2proc_memo_")
    trace_base = os.path.join(basetemp, "trace.jsonl")
    budget = float(os.environ.get("RAMBA_TEST_PROCS_TIMEOUT", "600"))

    procs, logs = [], []
    for rank in range(2):
        env = dict(os.environ)
        env["PYTHONPATH"] = REPO
        for k in ("RAMBA_TEST_PROCS", "RAMBA_TEST_PROC_ID",
                  "RAMBA_TEST_COORD", "RAMBA_TEST_SHARED_TMP",
                  "RAMBA_PROFILE_DIR", "RAMBA_FAULTS", "RAMBA_HBM_BUDGET",
                  "RAMBA_MEMO_BUDGET"):
            env.pop(k, None)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        env["RAMBA_MEMO"] = "1"
        env["RAMBA_TRACE"] = trace_base
        log = open(os.path.join(basetemp, f"rank{rank}.log"), "w")
        logs.append(log)
        procs.append(subprocess.Popen(
            [sys.executable, "-c", _MEMO_WORKLOAD, str(rank),
             f"localhost:{port}"],
            env=env, stdout=log, stderr=subprocess.STDOUT, cwd=REPO,
        ))

    deadline = time.time() + budget
    rcs = [None, None]
    try:
        for i, p in enumerate(procs):
            left = max(5.0, deadline - time.time())
            try:
                rcs[i] = p.wait(timeout=left)
            except subprocess.TimeoutExpired:
                p.kill()
                rcs[i] = -9
    finally:
        for log in logs:
            log.close()

    ok = all(rc == 0 for rc in rcs)

    # The canonical hash is a pure function of program structure and the
    # hit/insert counts a deterministic function of the flush sequence:
    # both markers must be IDENTICAL across ranks.
    markers = [None, None]
    for rank in range(2):
        path = os.path.join(basetemp, f"rank{rank}.log")
        with open(path) as f:
            tail = f.read().splitlines()
        for line in tail:
            if line.startswith(f"MEMO_LEG rank={rank} "):
                markers[rank] = line.split(" ", 2)[2]
        if markers[rank] is None:
            ok = False
        print(f"--- memo leg rank {rank} rc={rcs[rank]} ({path}) ---")
        print("\n".join(tail[-(4 if ok else 40):]))
    if ok and markers[0] != markers[1]:
        print(f"memo leg: FAIL (rank skew: r0={markers[0]} "
              f"r1={markers[1]})")
        ok = False
    elif ok:
        print(f"memo leg: lockstep across ranks ({markers[0]})")

    # Each per-rank trace must carry memo-served flush spans: the hits
    # were real short-circuits, visible to trace_report's memo line.
    import json

    for rank in range(2):
        path = f"{trace_base}.rank{rank}"
        try:
            with open(path) as f:
                evs = [json.loads(ln) for ln in f if ln.strip()]
            n_memo = sum(1 for e in evs if e.get("type") == "flush"
                         and e.get("cache") == "memo")
            print(f"memo leg rank {rank}: {len(evs)} events, "
                  f"{n_memo} memo-served flushes")
            if n_memo < 3:
                print(f"memo leg rank {rank}: FAIL (memo spans={n_memo})")
                ok = False
        except (OSError, ValueError) as e:
            print(f"memo leg rank {rank}: FAIL ({e})")
            ok = False

    print(f"two-process memo leg: {'OK' if ok else 'FAIL'}")
    if ok:
        shutil.rmtree(basetemp, ignore_errors=True)
    return 0 if ok else 1


def run_plancache_leg() -> int:
    """Two ranks under RAMBA_PLANCERT=1 + strict verify; the cache
    key/signature are pure functions of rank-identical state, so both
    ranks must store and redeem certificates in LOCKSTEP (a hit skips
    the analysis pipeline — rank-skewed decisions would desync the
    flush sequences), with zero batched-agree divergences."""
    with socket.socket() as s:
        s.bind(("localhost", 0))
        port = s.getsockname()[1]
    basetemp = tempfile.mkdtemp(prefix="ramba_2proc_plancache_")
    trace_base = os.path.join(basetemp, "trace.jsonl")
    budget = float(os.environ.get("RAMBA_TEST_PROCS_TIMEOUT", "600"))

    procs, logs = [], []
    for rank in range(2):
        env = dict(os.environ)
        env["PYTHONPATH"] = REPO
        for k in ("RAMBA_TEST_PROCS", "RAMBA_TEST_PROC_ID",
                  "RAMBA_TEST_COORD", "RAMBA_TEST_SHARED_TMP",
                  "RAMBA_PROFILE_DIR", "RAMBA_FAULTS", "RAMBA_HBM_BUDGET",
                  "RAMBA_ARTIFACTS", "RAMBA_VERIFY_RULES"):
            env.pop(k, None)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        env["RAMBA_PLANCERT"] = "1"
        env["RAMBA_PLANCERT_AGREE"] = "2"
        env["RAMBA_VERIFY"] = "strict"
        env["RAMBA_TRACE"] = trace_base
        log = open(os.path.join(basetemp, f"rank{rank}.log"), "w")
        logs.append(log)
        procs.append(subprocess.Popen(
            [sys.executable, "-c", _PLANCACHE_WORKLOAD, str(rank),
             f"localhost:{port}"],
            env=env, stdout=log, stderr=subprocess.STDOUT, cwd=REPO,
        ))

    deadline = time.time() + budget
    rcs = [None, None]
    try:
        for i, p in enumerate(procs):
            left = max(5.0, deadline - time.time())
            try:
                rcs[i] = p.wait(timeout=left)
            except subprocess.TimeoutExpired:
                p.kill()
                rcs[i] = -9
    finally:
        for log in logs:
            log.close()

    ok = all(rc == 0 for rc in rcs)

    # Hit/store/stale counts are a deterministic function of the flush
    # sequence over rank-identical state: markers must be IDENTICAL.
    markers = [None, None]
    for rank in range(2):
        path = os.path.join(basetemp, f"rank{rank}.log")
        with open(path) as f:
            tail = f.read().splitlines()
        for line in tail:
            if line.startswith(f"PLANCACHE_LEG rank={rank} "):
                markers[rank] = line.split(" ", 2)[2]
        if markers[rank] is None:
            ok = False
        print(f"--- plancache leg rank {rank} rc={rcs[rank]} ({path}) ---")
        print("\n".join(tail[-(4 if ok else 40):]))
    if ok and markers[0] != markers[1]:
        print(f"plancache leg: FAIL (rank skew: r0={markers[0]} "
              f"r1={markers[1]})")
        ok = False
    elif ok:
        print(f"plancache leg: lockstep across ranks ({markers[0]})")

    # Each per-rank trace must carry certificate-redeemed flush spans:
    # the hits were real analysis skips, visible to trace_report.
    import json

    for rank in range(2):
        path = f"{trace_base}.rank{rank}"
        try:
            with open(path) as f:
                evs = [json.loads(ln) for ln in f if ln.strip()]
            n_hit = sum(1 for e in evs if e.get("type") == "flush"
                        and e.get("plan_cache") == "hit")
            print(f"plancache leg rank {rank}: {len(evs)} events, "
                  f"{n_hit} certificate-redeemed flushes")
            if n_hit < 3:
                print(f"plancache leg rank {rank}: FAIL "
                      f"(plan_cache spans={n_hit})")
                ok = False
        except (OSError, ValueError) as e:
            print(f"plancache leg rank {rank}: FAIL ({e})")
            ok = False

    print(f"two-process plancache leg: {'OK' if ok else 'FAIL'}")
    if ok:
        shutil.rmtree(basetemp, ignore_errors=True)
    return 0 if ok else 1


def run_warmstart_leg() -> int:
    """Cold phase + warm phase of two SPMD ranks each, sharing per-rank
    RAMBA_CACHE dirs across phases.  Both ranks must pick IDENTICAL
    compile classes per fingerprint (the decision is pure in program
    structure, shapes, and policy), and the warm phase must hit the
    pre-seeded persist cache in lockstep (equal, nonzero hit counts)."""
    basetemp = tempfile.mkdtemp(prefix="ramba_2proc_warmstart_")
    budget = float(os.environ.get("RAMBA_TEST_PROCS_TIMEOUT", "600"))
    ok = True
    # markers[phase][rank] -> {"classes": str, "hits": int, ...}
    markers: dict = {}

    for phase in ("cold", "warm"):
        with socket.socket() as s:
            s.bind(("localhost", 0))
            port = s.getsockname()[1]
        procs, logs = [], []
        for rank in range(2):
            env = dict(os.environ)
            env["PYTHONPATH"] = REPO
            for k in ("RAMBA_TEST_PROCS", "RAMBA_TEST_PROC_ID",
                      "RAMBA_TEST_COORD", "RAMBA_TEST_SHARED_TMP",
                      "RAMBA_PROFILE_DIR", "RAMBA_FAULTS",
                      "RAMBA_HBM_BUDGET", "RAMBA_MEMO"):
                env.pop(k, None)
            env["JAX_PLATFORMS"] = "cpu"
            env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
            env["RAMBA_COMPILE_CLASSES"] = "pow2"
            # per-rank cache dir, SHARED across phases: the warm phase
            # reads what its own rank's cold phase stored
            env["RAMBA_CACHE"] = os.path.join(basetemp, f"cache.rank{rank}")
            # jax's own cache, placed by the environment in a directory
            # that starts empty: the AOT lane stores only fresh compiles
            env["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
                basetemp, f"jax_cache.rank{rank}")
            log = open(os.path.join(basetemp, f"{phase}.rank{rank}.log"),
                       "w")
            logs.append(log)
            procs.append(subprocess.Popen(
                [sys.executable, "-c", _WARMSTART_WORKLOAD, str(rank),
                 f"localhost:{port}", phase],
                env=env, stdout=log, stderr=subprocess.STDOUT, cwd=REPO,
            ))
        deadline = time.time() + budget
        rcs = [None, None]
        try:
            for i, p in enumerate(procs):
                left = max(5.0, deadline - time.time())
                try:
                    rcs[i] = p.wait(timeout=left)
                except subprocess.TimeoutExpired:
                    p.kill()
                    rcs[i] = -9
        finally:
            for log in logs:
                log.close()
        phase_ok = all(rc == 0 for rc in rcs)
        markers[phase] = [None, None]
        for rank in range(2):
            path = os.path.join(basetemp, f"{phase}.rank{rank}.log")
            with open(path) as f:
                tail = f.read().splitlines()
            prefix = f"WARMSTART_LEG rank={rank} phase={phase} "
            for line in tail:
                if line.startswith(prefix):
                    fields = dict(
                        kv.split("=", 1)
                        for kv in line[len(prefix):].split(" "))
                    markers[phase][rank] = fields
            if markers[phase][rank] is None:
                phase_ok = False
            print(f"--- warmstart {phase} rank {rank} rc={rcs[rank]} "
                  f"({path}) ---")
            print("\n".join(tail[-(3 if phase_ok else 40):]))
        ok = ok and phase_ok
        if not phase_ok:
            break

    if ok:
        for phase in ("cold", "warm"):
            r0, r1 = markers[phase]
            if r0["classes"] != r1["classes"]:
                print(f"warmstart leg: FAIL ({phase} class skew: "
                      f"r0={r0['classes']} r1={r1['classes']})")
                ok = False
        if ok and markers["cold"][0]["classes"] != \
                markers["warm"][0]["classes"]:
            print("warmstart leg: FAIL (classes drifted across phases)")
            ok = False
        if ok:
            h0 = int(markers["warm"][0]["persist_hits"])
            h1 = int(markers["warm"][1]["persist_hits"])
            if h0 != h1 or h0 < 1:
                print(f"warmstart leg: FAIL (persist hits not lockstep: "
                      f"r0={h0} r1={h1})")
                ok = False
            else:
                print(f"warmstart leg: lockstep classes "
                      f"({markers['warm'][0]['classes']}), "
                      f"{h0} persist hits per rank, warm compiles="
                      f"{markers['warm'][0]['compiles']} "
                      f"(cold={markers['cold'][0]['compiles']})")

    print(f"two-process warmstart leg: {'OK' if ok else 'FAIL'}")
    if ok:
        shutil.rmtree(basetemp, ignore_errors=True)
    return 0 if ok else 1


def run_autotune_leg() -> int:
    """Two ranks under RAMBA_AUTOTUNE=race; both must latch the SAME
    backend per kernel fingerprint (selection is ledger-count-driven and
    counts advance in SPMD lockstep), and each rank's persisted decision
    table must agree with its in-memory decisions."""
    with socket.socket() as s:
        s.bind(("localhost", 0))
        port = s.getsockname()[1]
    basetemp = tempfile.mkdtemp(prefix="ramba_2proc_autotune_")
    budget = float(os.environ.get("RAMBA_TEST_PROCS_TIMEOUT", "600"))

    procs, logs = [], []
    for rank in range(2):
        env = dict(os.environ)
        env["PYTHONPATH"] = REPO
        for k in ("RAMBA_TEST_PROCS", "RAMBA_TEST_PROC_ID",
                  "RAMBA_TEST_COORD", "RAMBA_TEST_SHARED_TMP",
                  "RAMBA_PROFILE_DIR", "RAMBA_FAULTS", "RAMBA_HBM_BUDGET"):
            env.pop(k, None)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        env["RAMBA_AUTOTUNE"] = "race"
        env["RAMBA_AUTOTUNE_K"] = "2"
        env["RAMBA_AUTOTUNE_CACHE"] = os.path.join(
            basetemp, f"autotune.rank{rank}.json")
        log = open(os.path.join(basetemp, f"rank{rank}.log"), "w")
        logs.append(log)
        procs.append(subprocess.Popen(
            [sys.executable, "-c", _AUTOTUNE_WORKLOAD, str(rank),
             f"localhost:{port}"],
            env=env, stdout=log, stderr=subprocess.STDOUT, cwd=REPO,
        ))

    deadline = time.time() + budget
    rcs = [None, None]
    try:
        for i, p in enumerate(procs):
            left = max(5.0, deadline - time.time())
            try:
                rcs[i] = p.wait(timeout=left)
            except subprocess.TimeoutExpired:
                p.kill()
                rcs[i] = -9
    finally:
        for log in logs:
            log.close()

    ok = all(rc == 0 for rc in rcs)

    decisions = [None, None]
    for rank in range(2):
        path = os.path.join(basetemp, f"rank{rank}.log")
        with open(path) as f:
            tail = f.read().splitlines()
        for line in tail:
            if line.startswith(f"AUTOTUNE_LEG_DECISIONS rank={rank} "):
                decisions[rank] = line.split(" ", 2)[2]
        if decisions[rank] is None:
            ok = False
        print(f"--- autotune leg rank {rank} rc={rcs[rank]} ({path}) ---")
        print("\n".join(tail[-(4 if ok else 40):]))
    if ok and decisions[0] != decisions[1]:
        print(f"autotune leg: FAIL (backend decisions diverge: "
              f"r0={decisions[0]} r1={decisions[1]})")
        ok = False
    elif ok:
        print(f"autotune leg: decisions identical on both ranks "
              f"({decisions[0]})")

    print(f"two-process autotune leg: {'OK' if ok else 'FAIL'}")
    if ok:
        shutil.rmtree(basetemp, ignore_errors=True)
    return 0 if ok else 1


def run_memory_leg() -> int:
    """Two ranks under a tiny HBM budget; admission control must route
    both to the chunked rung, in lockstep, with the correct result."""
    with socket.socket() as s:
        s.bind(("localhost", 0))
        port = s.getsockname()[1]
    basetemp = tempfile.mkdtemp(prefix="ramba_2proc_mem_")
    trace_base = os.path.join(basetemp, "trace.jsonl")
    budget = float(os.environ.get("RAMBA_TEST_PROCS_TIMEOUT", "600"))

    procs, logs = [], []
    for rank in range(2):
        env = dict(os.environ)
        env["PYTHONPATH"] = REPO
        for k in ("RAMBA_TEST_PROCS", "RAMBA_TEST_PROC_ID",
                  "RAMBA_TEST_COORD", "RAMBA_TEST_SHARED_TMP",
                  "RAMBA_PROFILE_DIR", "RAMBA_FAULTS"):
            env.pop(k, None)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        # Tiny budget: the 65536-elem f32 chain estimates ~768 KB peak,
        # far over a 100 KB budget, so admission must reject pre-flush.
        env["RAMBA_HBM_BUDGET"] = "100k"
        env["RAMBA_HBM_ESTIMATE"] = "analytic"
        env["RAMBA_TRACE"] = trace_base
        log = open(os.path.join(basetemp, f"rank{rank}.log"), "w")
        logs.append(log)
        procs.append(subprocess.Popen(
            [sys.executable, "-c", _MEMORY_WORKLOAD, str(rank),
             f"localhost:{port}"],
            env=env, stdout=log, stderr=subprocess.STDOUT, cwd=REPO,
        ))

    deadline = time.time() + budget
    rcs = [None, None]
    try:
        for i, p in enumerate(procs):
            left = max(5.0, deadline - time.time())
            try:
                rcs[i] = p.wait(timeout=left)
            except subprocess.TimeoutExpired:
                p.kill()
                rcs[i] = -9
    finally:
        for log in logs:
            log.close()

    ok = all(rc == 0 for rc in rcs)

    # Per-rank traces must show the admission rejection routing to the
    # chunked rung — the memory timeline works under SPMD.
    import json

    for rank in range(2):
        path = f"{trace_base}.rank{rank}"
        try:
            with open(path) as f:
                evs = [json.loads(ln) for ln in f if ln.strip()]
            n_mem = sum(1 for e in evs if e.get("type") == "memory")
            n_reject = sum(1 for e in evs if e.get("type") == "memory"
                           and e.get("action") == "reject")
            print(f"memory leg rank {rank}: {len(evs)} events, "
                  f"{n_mem} memory, {n_reject} rejects")
            if n_mem == 0 or n_reject == 0:
                print(f"memory leg rank {rank}: FAIL "
                      f"(memory={n_mem}, reject={n_reject})")
                ok = False
        except (OSError, ValueError) as e:
            print(f"memory leg rank {rank}: FAIL ({e})")
            ok = False

    for rank in range(2):
        path = os.path.join(basetemp, f"rank{rank}.log")
        with open(path) as f:
            tail = f.read().splitlines()
        if "MEMORY_LEG_OK rank=%d" % rank not in "\n".join(tail):
            ok = False
        print(f"--- memory leg rank {rank} rc={rcs[rank]} ({path}) ---")
        print("\n".join(tail[-(4 if ok else 40):]))
    print(f"two-process memory leg: {'OK' if ok else 'FAIL'}")
    if ok:
        shutil.rmtree(basetemp, ignore_errors=True)
    return 0 if ok else 1


# SPMD workload for the chaos leg: ~two dozen elementwise flush+gather
# iterations under rank-1-only fault injection.  Elementwise programs
# keep the degradation ladder communication-free (no collective inside a
# rung can wedge the healthy rank mid-attempt); the only collectives are
# the coherence agreement rounds and the post-flush all-gather — so with
# coherence ON a terminal failure anywhere makes BOTH ranks skip the
# gather together, and with coherence OFF the skew mispairs the gathers,
# which is exactly the historical failure mode.  Iteration FATAL_AT
# swaps in a one-shot fatal injection (coherent quarantine everywhere);
# errors are printed by their *agreed classification* (retry.classify),
# which is the cross-rank-comparable name for a failure.
# argv: <rank> <coordinator>.
_CHAOS_WORKLOAD = """
import hashlib
import os
import sys
import numpy as np
rank, coord = int(sys.argv[1]), sys.argv[2]
from ramba_tpu.parallel import distributed
distributed.initialize(coordinator_address=coord, num_processes=2,
                       process_id=rank)
import jax
assert jax.process_count() == 2, jax.process_count()
import ramba_tpu as rt
from ramba_tpu.resilience import faults, retry

N = 4096
ITERS = 24
FATAL_AT = 18
base_spec = os.environ.get('RAMBA_FAULTS')
for i in range(ITERS):
    if i == FATAL_AT:
        faults.configure('execute:1:fatal:rank=1')
    elif i == FATAL_AT + 1:
        faults.configure(base_spec)
    try:
        a = (rt.arange(N) + float(i)) * 2.0 + 1.0
        b = a * a - 3.0 * a
        v = b.asarray()
        ref = (np.arange(N) + float(i)) * 2.0 + 1.0
        ref = ref * ref - 3.0 * ref
        good = 'ok' if np.allclose(v, ref, rtol=1e-5) else 'BAD'
        line = 'i=%02d sha=%s %s' % (
            i, hashlib.sha256(v.tobytes()).hexdigest()[:16], good)
        del a, b, v
    except Exception as e:
        line = 'i=%02d err=%s' % (i, retry.classify(e))
    print('CHAOS_RESULT ' + line, flush=True)
print('CHAOS_DONE rank=%d' % rank, flush=True)
"""


def _chaos_env(basetemp: str, trace_base: str, coherence: str) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO
    for k in ("RAMBA_TEST_PROCS", "RAMBA_TEST_PROC_ID",
              "RAMBA_TEST_COORD", "RAMBA_TEST_SHARED_TMP",
              "RAMBA_PROFILE_DIR"):
        env.pop(k, None)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    # Every fault targets rank 1 only — the skew the protocol must absorb.
    env["RAMBA_FAULTS"] = ("dispatch:0.25:rank=1,execute:0.15:rank=1,"
                           "oom:0.1:rank=1:bytes=1m")
    env["RAMBA_FAULTS_SEED"] = "1234"
    env["RAMBA_RETRY_BASE_S"] = "0.01"
    env["RAMBA_WATCHDOG_S"] = "45"  # tripwire: ON phase must never trip it
    env["RAMBA_COHERENCE"] = coherence
    env["RAMBA_TRACE"] = trace_base
    return env


def _chaos_run(basetemp: str, trace_base: str, coherence: str,
               budget: float, grace: float = 30.0):
    """Launch both ranks, wait with a straggler grace window (once one
    rank exits, the other gets ``grace`` seconds before the kill — the
    OFF phase intentionally wedges a rank and must not eat the full
    budget).  Returns per-rank return codes (-9 = killed)."""
    procs, logs = [], []
    with socket.socket() as s:
        s.bind(("localhost", 0))
        port = s.getsockname()[1]
    for rank in range(2):
        env = _chaos_env(basetemp, trace_base, coherence)
        log = open(os.path.join(basetemp, f"{coherence}.rank{rank}.log"),
                   "w")
        logs.append(log)
        procs.append(subprocess.Popen(
            [sys.executable, "-c", _CHAOS_WORKLOAD, str(rank),
             f"localhost:{port}"],
            env=env, stdout=log, stderr=subprocess.STDOUT, cwd=REPO,
        ))
    deadline = time.time() + budget
    shrunk = False
    rcs = [None, None]
    try:
        while any(rc is None for rc in rcs) and time.time() < deadline:
            for i, p in enumerate(procs):
                if rcs[i] is None and p.poll() is not None:
                    rcs[i] = p.returncode
            if not shrunk and sum(rc is not None for rc in rcs) == 1:
                deadline = min(deadline, time.time() + grace)
                shrunk = True
            time.sleep(0.25)
        for i, p in enumerate(procs):
            if rcs[i] is None:
                p.kill()
                p.wait()
                rcs[i] = -9
    finally:
        for log in logs:
            log.close()
    return rcs


def _chaos_events(trace_base: str, rank: int) -> list:
    import json

    path = f"{trace_base}.rank{rank}"
    try:
        with open(path) as f:
            return [json.loads(ln) for ln in f if ln.strip()]
    except (OSError, ValueError):
        return []


def _chaos_results(basetemp: str, coherence: str, rank: int) -> list:
    path = os.path.join(basetemp, f"{coherence}.rank{rank}.log")
    try:
        with open(path) as f:
            return [ln.strip() for ln in f
                    if ln.startswith("CHAOS_RESULT ")]
    except OSError:
        return []


def run_chaos_leg() -> int:
    """Rank-skewed chaos soak: coherence ON must hold the fleet in
    lockstep; coherence OFF (same seed) must reproduce the historical
    divergence failure mode."""
    basetemp = tempfile.mkdtemp(prefix="ramba_2proc_chaos_")
    budget = float(os.environ.get("RAMBA_TEST_PROCS_TIMEOUT", "600"))
    ok = True

    # ---- phase ON: the protocol absorbs the skew -----------------------
    trace_on = os.path.join(basetemp, "trace_on.jsonl")
    rcs = _chaos_run(basetemp, trace_on, "on", budget)
    if rcs != [0, 0]:
        print(f"chaos leg ON: FAIL (rcs={rcs}, expected clean exits)")
        ok = False
    res = [_chaos_results(basetemp, "on", r) for r in range(2)]
    if not res[0] or res[0] != res[1]:
        print(f"chaos leg ON: FAIL (per-iteration results diverge: "
              f"rank0={len(res[0])} lines, rank1={len(res[1])} lines)")
        for l0, l1 in zip(res[0], res[1]):
            if l0 != l1:
                print(f"  rank0: {l0}\n  rank1: {l1}")
        ok = False
    if any("BAD" in ln for ln in res[0] + res[1]):
        print("chaos leg ON: FAIL (numerically wrong result)")
        ok = False
    evs = [_chaos_events(trace_on, r) for r in range(2)]
    coh_seq = [[(e.get("site"), e.get("epoch"), e.get("decision"))
                for e in evs[r] if e.get("type") == "coherence"]
               for r in range(2)]
    rung_seq = [[(e.get("site"), e.get("from"), e.get("to"))
                 for e in evs[r] if e.get("type") == "degrade"
                 and e.get("action") == "rung"] for r in range(2)]
    retry_seq = [[(e.get("site"), e.get("action"), e.get("attempt"))
                  for e in evs[r] if e.get("type") == "degrade"
                  and e.get("action") in ("retry", "exhausted")]
                 for r in range(2)]
    quar = [[e for e in evs[r] if e.get("type") == "flush_error"]
            for r in range(2)]
    stalls = [sum(1 for e in evs[r] if e.get("type") == "stall")
              for r in range(2)]
    local_rounds = [sum(1 for e in evs[r] if e.get("type") == "coherence"
                        and e.get("outcome") == "local") for r in range(2)]
    faults_fired = [sum(1 for e in evs[r] if e.get("type") == "fault")
                    for r in range(2)]
    overrides = sum(1 for e in evs[0] if e.get("type") == "coherence"
                    and e.get("decision") != e.get("proposal"))
    print(f"chaos leg ON: {len(coh_seq[0])}/{len(coh_seq[1])} coherence "
          f"rounds, {len(rung_seq[0])}/{len(rung_seq[1])} rung drops, "
          f"{len(retry_seq[0])}/{len(retry_seq[1])} retries, "
          f"{len(quar[0])}/{len(quar[1])} quarantines, "
          f"faults r0/r1={faults_fired[0]}/{faults_fired[1]}, "
          f"rank0 dragged {overrides}x")
    for name, seq in (("coherence", coh_seq), ("rung", rung_seq),
                      ("retry", retry_seq)):
        if not seq[0] or seq[0] != seq[1]:
            print(f"chaos leg ON: FAIL ({name} decision sequences differ "
                  f"or empty: {len(seq[0])} vs {len(seq[1])})")
            ok = False
    if len(quar[0]) != len(quar[1]) or not quar[0]:
        print(f"chaos leg ON: FAIL (quarantines {len(quar[0])} vs "
              f"{len(quar[1])}, expected equal and >= 1)")
        ok = False
    elif not all(e.get("coherence_epoch") for e in quar[0] + quar[1]):
        print("chaos leg ON: FAIL (quarantine missing coherence_epoch)")
        ok = False
    if stalls != [0, 0]:
        print(f"chaos leg ON: FAIL (stall events {stalls}, expected zero)")
        ok = False
    if local_rounds != [0, 0]:
        print(f"chaos leg ON: FAIL (local-fallback rounds {local_rounds})")
        ok = False
    if faults_fired[0] != 0 or faults_fired[1] == 0:
        print(f"chaos leg ON: FAIL (fault skew wrong: {faults_fired})")
        ok = False
    if overrides == 0:
        print("chaos leg ON: FAIL (rank 0 never overridden — the soak "
              "exercised no skew)")
        ok = False

    # ---- phase OFF: same seed, no protocol → divergence comes back -----
    trace_off = os.path.join(basetemp, "trace_off.jsonl")
    off_rcs = _chaos_run(basetemp, trace_off, "off",
                         min(budget, 150.0), grace=20.0)
    off_res = [_chaos_results(basetemp, "off", r) for r in range(2)]
    off_evs = [_chaos_events(trace_off, r) for r in range(2)]
    off_rungs = [[(e.get("site"), e.get("from"), e.get("to"))
                  for e in off_evs[r] if e.get("type") == "degrade"
                  and e.get("action") == "rung"] for r in range(2)]
    off_stalls = sum(1 for r in range(2) for e in off_evs[r]
                     if e.get("type") == "stall")
    diverged = (off_rcs != [0, 0] or off_res[0] != off_res[1]
                or off_rungs[0] != off_rungs[1] or off_stalls > 0)
    print(f"chaos leg OFF: rcs={off_rcs}, result lines "
          f"{len(off_res[0])}/{len(off_res[1])} "
          f"(identical={off_res[0] == off_res[1]}), rung drops "
          f"{len(off_rungs[0])}/{len(off_rungs[1])}, stalls={off_stalls}")
    if not diverged:
        print("chaos leg OFF: FAIL (coherence off did NOT reproduce the "
              "divergence — the ON-phase result proves nothing)")
        ok = False
    else:
        print("chaos leg OFF: divergence reproduced (expected)")

    print(f"two-process chaos leg: {'OK' if ok else 'FAIL'}")
    if ok:
        shutil.rmtree(basetemp, ignore_errors=True)
    else:
        print(f"chaos leg artifacts kept at {basetemp}")
    return 0 if ok else 1


def _overload_env(trace_base: str, coherence: str) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO
    for k in ("RAMBA_TEST_PROCS", "RAMBA_TEST_PROC_ID",
              "RAMBA_TEST_COORD", "RAMBA_TEST_SHARED_TMP",
              "RAMBA_PROFILE_DIR"):
        env.pop(k, None)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    # Rank 1 alone proposes shedding the first three flushes; the
    # serve:shed agreement must make that the fleet-wide verdict.
    env["RAMBA_FAULTS"] = "serve:admit:3:rank=1"
    env["RAMBA_RETRY_BASE_S"] = "0.01"
    env["RAMBA_WATCHDOG_S"] = "45"
    env["RAMBA_COHERENCE"] = coherence
    env["RAMBA_TRACE"] = trace_base
    return env


def _overload_run(basetemp: str, trace_base: str, coherence: str,
                  budget: float, grace: float = 30.0):
    """Launch both ranks with a straggler grace window (the OFF phase
    intentionally splits the fleet and may wedge one rank on a
    mismatched collective)."""
    procs, logs = [], []
    with socket.socket() as s:
        s.bind(("localhost", 0))
        port = s.getsockname()[1]
    for rank in range(2):
        log = open(os.path.join(basetemp,
                                f"{coherence}.rank{rank}.log"), "w")
        logs.append(log)
        procs.append(subprocess.Popen(
            [sys.executable, "-c", _OVERLOAD_WORKLOAD, str(rank),
             f"localhost:{port}"],
            env=_overload_env(trace_base, coherence),
            stdout=log, stderr=subprocess.STDOUT, cwd=REPO,
        ))
    deadline = time.time() + budget
    shrunk = False
    rcs = [None, None]
    try:
        while any(rc is None for rc in rcs) and time.time() < deadline:
            for i, p in enumerate(procs):
                if rcs[i] is None and p.poll() is not None:
                    rcs[i] = p.returncode
            if not shrunk and sum(rc is not None for rc in rcs) == 1:
                deadline = min(deadline, time.time() + grace)
                shrunk = True
            time.sleep(0.25)
        for i, p in enumerate(procs):
            if rcs[i] is None:
                p.kill()
                p.wait()
                rcs[i] = -9
    finally:
        for log in logs:
            log.close()
    return rcs


def _overload_markers(basetemp: str, coherence: str, rank: int) -> list:
    path = os.path.join(basetemp, f"{coherence}.rank{rank}.log")
    try:
        with open(path) as f:
            return [ln.strip() for ln in f
                    if ln.startswith(("OVERLOAD_RESULT ", "OVERLOAD_HEAL ",
                                      "OVERLOAD_COUNTS "))]
    except OSError:
        return []


def run_overload_leg() -> int:
    """Coherent load shedding under rank-skewed admission faults: ON
    sheds byte-identically on every rank (same set, same epoch, zero
    stalls, zero local fallbacks); OFF reproduces the divergence."""
    import json

    basetemp = tempfile.mkdtemp(prefix="ramba_2proc_overload_")
    budget = float(os.environ.get("RAMBA_TEST_PROCS_TIMEOUT", "600"))
    ok = True

    # ---- phase ON: the shed verdict is epoch-agreed --------------------
    trace_on = os.path.join(basetemp, "trace_on.jsonl")
    rcs = _overload_run(basetemp, trace_on, "on", budget)
    if rcs != [0, 0]:
        print(f"overload leg ON: FAIL (rcs={rcs}, expected clean exits)")
        ok = False
    marks = [_overload_markers(basetemp, "on", r) for r in range(2)]
    sheds = [[ln for ln in marks[r] if "verdict=SHED" in ln]
             for r in range(2)]
    print(f"overload leg ON: markers {len(marks[0])}/{len(marks[1])}, "
          f"sheds {len(sheds[0])}/{len(sheds[1])}")
    if not marks[0] or marks[0] != marks[1]:
        print("overload leg ON: FAIL (marker lines diverge across ranks)")
        for l0, l1 in zip(marks[0], marks[1]):
            if l0 != l1:
                print(f"  rank0: {l0}\n  rank1: {l1}")
        ok = False
    if len(sheds[0]) != 3 or any("epoch=None" in ln for ln in sheds[0]):
        print(f"overload leg ON: FAIL (expected 3 epoch-stamped sheds, "
              f"got {sheds[0]})")
        ok = False
    if any("BAD" in ln for ln in marks[0] + marks[1]):
        print("overload leg ON: FAIL (shed array healed to wrong bytes)")
        ok = False
    for rank in range(2):
        path = f"{trace_on}.rank{rank}"
        try:
            with open(path) as f:
                evs = [json.loads(ln) for ln in f if ln.strip()]
        except (OSError, ValueError) as e:
            print(f"overload leg ON: FAIL (trace rank {rank}: {e})")
            ok = False
            continue
        stalls = sum(1 for e in evs if e.get("type") == "stall")
        local = sum(1 for e in evs if e.get("type") == "coherence"
                    and e.get("outcome") == "local")
        shed_evs = [e for e in evs if e.get("type") == "shed"
                    and e.get("stage") == "dispatch"]
        if stalls or local:
            print(f"overload leg ON: FAIL (rank {rank}: {stalls} stalls, "
                  f"{local} local coherence rounds — agreement broke)")
            ok = False
        if len(shed_evs) != 3 or any(not e.get("epoch")
                                     for e in shed_evs):
            print(f"overload leg ON: FAIL (rank {rank}: shed trace events "
                  f"{len(shed_evs)}, expected 3 epoch-stamped)")
            ok = False

    # ---- phase OFF: same seed, no agreement → rank 1 sheds alone -------
    trace_off = os.path.join(basetemp, "trace_off.jsonl")
    off_rcs = _overload_run(basetemp, trace_off, "off",
                            min(budget, 150.0), grace=20.0)
    off_marks = [_overload_markers(basetemp, "off", r) for r in range(2)]
    diverged = off_rcs != [0, 0] or off_marks[0] != off_marks[1]
    print(f"overload leg OFF: rcs={off_rcs}, markers "
          f"{len(off_marks[0])}/{len(off_marks[1])} "
          f"(identical={off_marks[0] == off_marks[1]})")
    if not diverged:
        print("overload leg OFF: FAIL (coherence off did NOT reproduce "
              "the shed divergence — the ON result proves nothing)")
        ok = False
    else:
        print("overload leg OFF: divergence reproduced (expected)")

    print(f"two-process overload leg: {'OK' if ok else 'FAIL'}")
    if ok:
        shutil.rmtree(basetemp, ignore_errors=True)
    else:
        print(f"overload leg artifacts kept at {basetemp}")
    return 0 if ok else 1


def run_fault_leg() -> int:
    """Two ranks, one injected compile fault each; both must recover."""
    with socket.socket() as s:
        s.bind(("localhost", 0))
        port = s.getsockname()[1]
    basetemp = tempfile.mkdtemp(prefix="ramba_2proc_fault_")
    trace_base = os.path.join(basetemp, "trace.jsonl")
    budget = float(os.environ.get("RAMBA_TEST_PROCS_TIMEOUT", "600"))

    procs, logs = [], []
    for rank in range(2):
        env = dict(os.environ)
        env["PYTHONPATH"] = REPO
        for k in ("RAMBA_TEST_PROCS", "RAMBA_TEST_PROC_ID",
                  "RAMBA_TEST_COORD", "RAMBA_TEST_SHARED_TMP",
                  "RAMBA_PROFILE_DIR"):
            env.pop(k, None)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        env["RAMBA_FAULTS"] = "compile:once"
        env["RAMBA_RETRY_BASE_S"] = "0.01"
        env["RAMBA_TRACE"] = trace_base
        log = open(os.path.join(basetemp, f"rank{rank}.log"), "w")
        logs.append(log)
        procs.append(subprocess.Popen(
            [sys.executable, "-c", _FAULT_WORKLOAD, str(rank),
             f"localhost:{port}"],
            env=env, stdout=log, stderr=subprocess.STDOUT, cwd=REPO,
        ))

    deadline = time.time() + budget
    rcs = [None, None]
    try:
        for i, p in enumerate(procs):
            left = max(5.0, deadline - time.time())
            try:
                rcs[i] = p.wait(timeout=left)
            except subprocess.TimeoutExpired:
                p.kill()
                rcs[i] = -9
    finally:
        for log in logs:
            log.close()

    ok = all(rc == 0 for rc in rcs)

    # Per-rank traces must show the injected fault AND the retry that
    # absorbed it — the degradation timeline works under SPMD.
    import json

    for rank in range(2):
        path = f"{trace_base}.rank{rank}"
        try:
            with open(path) as f:
                evs = [json.loads(ln) for ln in f if ln.strip()]
            n_fault = sum(1 for e in evs if e.get("type") == "fault"
                          and e.get("site") == "compile")
            n_retry = sum(1 for e in evs if e.get("type") == "degrade"
                          and e.get("action") == "retry")
            print(f"fault leg rank {rank}: {len(evs)} events, "
                  f"{n_fault} faults, {n_retry} retries")
            if n_fault == 0 or n_retry == 0:
                print(f"fault leg rank {rank}: FAIL "
                      f"(fault={n_fault}, retry={n_retry})")
                ok = False
        except (OSError, ValueError) as e:
            print(f"fault leg rank {rank}: FAIL ({e})")
            ok = False

    for rank in range(2):
        path = os.path.join(basetemp, f"rank{rank}.log")
        with open(path) as f:
            tail = f.read().splitlines()
        if "FAULT_LEG_OK rank=%d" % rank not in "\n".join(tail):
            ok = False
        print(f"--- fault leg rank {rank} rc={rcs[rank]} ({path}) ---")
        print("\n".join(tail[-(4 if ok else 40):]))
    print(f"two-process fault leg: {'OK' if ok else 'FAIL'}")
    if ok:
        shutil.rmtree(basetemp, ignore_errors=True)
    return 0 if ok else 1




# SPMD workload for the integrity leg's ON phase: both ranks flush
# three distinct effect-certified pure programs under RAMBA_AUDIT=1 so
# every flush is shadow-audited.  The harness arms
# RAMBA_FAULTS='audit:shadow:flip:bytes=1:rank=1:after=1' — exactly one
# audit, on rank 1 only, sees flipped shadow bytes.  The verdict is
# agreed via coherence.agree(reduce="max"), so BOTH ranks must count
# the same single mismatch, suppress the same memo insert, and still
# serve the correct primary values.  argv: <rank> <coordinator>.
_INTEGRITY_WORKLOAD = """
import sys
import numpy as np
rank, coord = int(sys.argv[1]), sys.argv[2]
from ramba_tpu.parallel import distributed
distributed.initialize(coordinator_address=coord, num_processes=2,
                       process_id=rank)
import jax
assert jax.process_count() == 2, jax.process_count()
import ramba_tpu as rt
from ramba_tpu.core import memo
from ramba_tpu.resilience import integrity
assert memo.enabled(), 'RAMBA_MEMO not armed'
assert integrity.audit_every() == 1, 'RAMBA_AUDIT not armed'
a = rt.arange(4096) / 100.0
b = rt.arange(4096) * 0.5 + 1.0
rt.sync()
vals = [float(rt.sum((a + b) * k)) for k in (2.0, 3.0, 4.0)]
an = np.arange(4096)
base = an / 100.0 + (an * 0.5 + 1.0)
for k, v in zip((2.0, 3.0, 4.0), vals):
    exp = float(np.sum(base * k))
    assert abs(v - exp) <= 1e-4 * abs(exp), (k, v, exp)
snap = integrity.snapshot()
assert snap['audits'] >= 3, snap
assert snap['audit_mismatches'] == 1, snap
assert snap['audit_errors'] == 0, snap
msnap = memo.cache.snapshot()
print('INTEGRITY_LEG rank=%d audits=%d mismatches=%d inserts=%d '
      'checksum=%.6f' % (rank, snap['audits'], snap['audit_mismatches'],
                         msnap['inserts'], sum(vals)))
"""


# Single-process workloads for the integrity leg's OFF phase.  Seed:
# flush one memoizable program with the shared artifact tier armed so a
# stamped memo blob lands on disk; print the correct value and the blob
# path.  Probe: a fresh process recomputes the same program — the
# shared lane is keyed by content, so it adopts whatever the blob
# holds.  Between seed and probe the harness replaces the blob with a
# VALID but WRONG unstamped npz: with RAMBA_INTEGRITY=0 the probe
# serves the wrong answer verbatim (the failure mode this plane
# exists to stop); with the plane on the unstamped blob is evicted and
# the recompute serves the correct answer.
_INTEGRITY_SEED_WORKLOAD = """
import os
import numpy as np
import ramba_tpu as rt
from ramba_tpu.core import memo
from ramba_tpu.fleet import artifacts
assert memo.enabled() and artifacts.memo_shared_enabled()
x = rt.fromarray(np.arange(256) * 0.5)
v = float(rt.sum(x * 3.0 + 1.0))
memo_dir = os.path.join(os.environ['RAMBA_ARTIFACTS'], 'memo')
blobs = sorted(n for n in os.listdir(memo_dir) if n.endswith('.npz'))
assert len(blobs) == 1, blobs
print('INTEGRITY_SEED value=%.6f blob=%s' % (v, blobs[0]))
"""

_INTEGRITY_PROBE_WORKLOAD = """
import numpy as np
import ramba_tpu as rt
from ramba_tpu.core import memo
from ramba_tpu.fleet import artifacts
from ramba_tpu.resilience import integrity
x = rt.fromarray(np.arange(256) * 0.5)
v = float(rt.sum(x * 3.0 + 1.0))
snap = artifacts.snapshot()
print('INTEGRITY_PROBE value=%.6f shared_hits=%d corrupt=%d '
      'failures=%d' % (v, snap['memo_hits'], snap['memo_corrupt'],
                       integrity.stats['failures']))
"""


def run_integrity_leg() -> int:
    """Two phases: (ON) 2-rank coherent shadow-audit verdict under a
    seeded rank-1 shadow flip; (OFF) the wrong-answer serve reproduced
    with RAMBA_INTEGRITY=0 and caught with the plane on."""
    with socket.socket() as s:
        s.bind(("localhost", 0))
        port = s.getsockname()[1]
    basetemp = tempfile.mkdtemp(prefix="ramba_2proc_integrity_")
    trace_base = os.path.join(basetemp, "trace.jsonl")
    budget = float(os.environ.get("RAMBA_TEST_PROCS_TIMEOUT", "600"))
    ok = True

    # -- ON phase: coherent audit verdict across ranks -------------------
    procs, logs = [], []
    for rank in range(2):
        env = dict(os.environ)
        env["PYTHONPATH"] = REPO
        for k in ("RAMBA_TEST_PROCS", "RAMBA_TEST_PROC_ID",
                  "RAMBA_TEST_COORD", "RAMBA_TEST_SHARED_TMP",
                  "RAMBA_PROFILE_DIR", "RAMBA_HBM_BUDGET",
                  "RAMBA_MEMO_BUDGET", "RAMBA_ARTIFACTS",
                  "RAMBA_INTEGRITY"):
            env.pop(k, None)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        env["RAMBA_MEMO"] = "1"
        env["RAMBA_AUDIT"] = "1"
        env["RAMBA_FAULTS"] = "audit:shadow:flip:bytes=1:rank=1:after=1"
        env["RAMBA_TRACE"] = trace_base
        log = open(os.path.join(basetemp, f"rank{rank}.log"), "w")
        logs.append(log)
        procs.append(subprocess.Popen(
            [sys.executable, "-c", _INTEGRITY_WORKLOAD, str(rank),
             f"localhost:{port}"],
            env=env, stdout=log, stderr=subprocess.STDOUT, cwd=REPO,
        ))
    deadline = time.time() + budget
    rcs = [None, None]
    try:
        for i, p in enumerate(procs):
            left = max(5.0, deadline - time.time())
            try:
                rcs[i] = p.wait(timeout=left)
            except subprocess.TimeoutExpired:
                p.kill()
                rcs[i] = -9
    finally:
        for log in logs:
            log.close()
    ok = all(rc == 0 for rc in rcs)

    markers = [None, None]
    for rank in range(2):
        path = os.path.join(basetemp, f"rank{rank}.log")
        with open(path) as f:
            tail = f.read().splitlines()
        for line in tail:
            if line.startswith(f"INTEGRITY_LEG rank={rank} "):
                markers[rank] = line.split(" ", 2)[2]
        if markers[rank] is None:
            ok = False
        print(f"--- integrity leg rank {rank} rc={rcs[rank]} ({path}) ---")
        print("\n".join(tail[-(4 if ok else 40):]))
    if ok and markers[0] != markers[1]:
        print(f"integrity leg: FAIL (rank skew: r0={markers[0]} "
              f"r1={markers[1]})")
        ok = False
    elif ok:
        print(f"integrity leg ON: agreed verdict across ranks "
              f"({markers[0]})")

    # The agreed mismatch must be visible as an ``integrity`` trace
    # event on BOTH ranks (rank 0 had no local mismatch — the event is
    # the coherently-agreed one).
    import json

    for rank in range(2):
        path = f"{trace_base}.rank{rank}"
        try:
            with open(path) as f:
                evs = [json.loads(ln) for ln in f if ln.strip()]
            n_int = sum(1 for e in evs if e.get("type") == "integrity"
                        and e.get("site") == "audit:shadow")
            print(f"integrity leg rank {rank}: {len(evs)} events, "
                  f"{n_int} integrity events")
            if n_int < 1:
                ok = False
        except (OSError, ValueError) as e:
            print(f"integrity leg rank {rank}: FAIL ({e})")
            ok = False

    # -- OFF phase: the wrong-answer serve, reproduced then caught -------
    art = os.path.join(basetemp, "artifacts")
    os.makedirs(art, exist_ok=True)

    def run_single(workload, *, integrity_on):
        env = dict(os.environ)
        env["PYTHONPATH"] = REPO
        for k in ("RAMBA_TEST_PROCS", "RAMBA_TEST_PROC_ID",
                  "RAMBA_TEST_COORD", "RAMBA_TEST_SHARED_TMP",
                  "RAMBA_FAULTS", "RAMBA_TRACE", "RAMBA_AUDIT"):
            env.pop(k, None)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        env["RAMBA_MEMO"] = "1"
        env["RAMBA_ARTIFACTS"] = art
        env["RAMBA_INTEGRITY"] = "1" if integrity_on else "0"
        return subprocess.run(
            [sys.executable, "-c", workload], env=env, cwd=REPO,
            capture_output=True, text=True, timeout=budget)

    r = run_single(_INTEGRITY_SEED_WORKLOAD, integrity_on=True)
    seed_val, blob = None, None
    for line in r.stdout.splitlines():
        if line.startswith("INTEGRITY_SEED "):
            fields = dict(f.split("=", 1) for f in line.split()[1:])
            seed_val = float(fields["value"])
            blob = os.path.join(art, "memo", fields["blob"])
    if r.returncode != 0 or blob is None:
        print(f"integrity leg OFF: seed FAILED rc={r.returncode}\n"
              f"{r.stdout[-2000:]}{r.stderr[-2000:]}")
        ok = False
    else:
        # Clobber: a VALID npz of wrong values, UNSTAMPED — the shape a
        # pre-plane cache poisoning takes.  (A bit flip inside the npz
        # usually trips zipfile's CRC; this is the flip that parses.)
        import io

        import numpy as np

        wrong = np.full(1, -12345.0)
        buf = io.BytesIO()
        np.savez(buf, out0=wrong)
        with open(blob, "wb") as f:
            f.write(buf.getvalue())

        r_off = run_single(_INTEGRITY_PROBE_WORKLOAD, integrity_on=False)
        r_on = run_single(_INTEGRITY_PROBE_WORKLOAD, integrity_on=True)

        def probe_fields(r):
            for line in r.stdout.splitlines():
                if line.startswith("INTEGRITY_PROBE "):
                    return dict(f.split("=", 1)
                                for f in line.split()[1:])
            return None

        f_off, f_on = probe_fields(r_off), probe_fields(r_on)
        if r_off.returncode != 0 or f_off is None:
            print(f"integrity leg OFF: probe FAILED rc={r_off.returncode}"
                  f"\n{r_off.stdout[-2000:]}{r_off.stderr[-2000:]}")
            ok = False
        elif not (float(f_off["value"]) == -12345.0
                  and int(f_off["shared_hits"]) >= 1):
            print(f"integrity leg OFF: wrong-answer serve NOT reproduced "
                  f"({f_off} vs seed {seed_val})")
            ok = False
        else:
            print(f"integrity leg OFF: RAMBA_INTEGRITY=0 served the "
                  f"poisoned value {f_off['value']} (seed {seed_val:g})")
        if r_on.returncode != 0 or f_on is None:
            print(f"integrity leg ON: probe FAILED rc={r_on.returncode}"
                  f"\n{r_on.stdout[-2000:]}{r_on.stderr[-2000:]}")
            ok = False
        elif not (abs(float(f_on["value"]) - seed_val) <= 1e-6
                  and int(f_on["corrupt"]) >= 1
                  and int(f_on["failures"]) >= 1):
            print(f"integrity leg ON: poisoned blob not caught ({f_on})")
            ok = False
        else:
            print(f"integrity leg ON: unstamped blob evicted "
                  f"(corrupt={f_on['corrupt']}), recomputed correct "
                  f"value {f_on['value']}")

    print(f"two-process integrity leg: {'OK' if ok else 'FAIL'}")
    if ok:
        shutil.rmtree(basetemp, ignore_errors=True)
    return 0 if ok else 1


def main() -> int:
    if "--fault-leg" in sys.argv[1:]:
        return run_fault_leg()
    if "--chaos-leg" in sys.argv[1:]:
        return run_chaos_leg()
    if "--memory-leg" in sys.argv[1:]:
        return run_memory_leg()
    if "--perf-leg" in sys.argv[1:]:
        return run_perf_leg()
    if "--attrib-leg" in sys.argv[1:]:
        return run_attrib_leg()
    if "--serving-leg" in sys.argv[1:]:
        return run_serving_leg()
    if "--elastic-leg" in sys.argv[1:]:
        return run_elastic_leg()
    if "--reshard-leg" in sys.argv[1:]:
        return run_reshard_leg()
    if "--telemetry-leg" in sys.argv[1:]:
        return run_telemetry_leg()
    if "--fleet-leg" in sys.argv[1:]:
        return run_fleet_leg()
    if "--router-leg" in sys.argv[1:]:
        return run_router_leg()
    if "--autotune-leg" in sys.argv[1:]:
        return run_autotune_leg()
    if "--integrity-leg" in sys.argv[1:]:
        return run_integrity_leg()
    if "--memo-leg" in sys.argv[1:]:
        return run_memo_leg()
    if "--plancache-leg" in sys.argv[1:]:
        return run_plancache_leg()
    if "--warmstart-leg" in sys.argv[1:]:
        return run_warmstart_leg()
    if "--overload-leg" in sys.argv[1:]:
        return run_overload_leg()
    if "--sampling-leg" in sys.argv[1:]:
        return run_sampling_leg()
    pytest_args = sys.argv[1:] or ["tests/"]
    with socket.socket() as s:
        s.bind(("localhost", 0))
        port = s.getsockname()[1]
    basetemp = tempfile.mkdtemp(prefix="ramba_2proc_")
    budget = float(os.environ.get("RAMBA_TEST_PROCS_TIMEOUT", "2400"))

    # Trace leg: both ranks stream flush spans; multi-controller emit
    # writes per-rank files <path>.rank0 / <path>.rank1 (observe/events.py)
    # which are asserted parseable below.
    trace_base = os.path.join(basetemp, "trace.jsonl")

    procs = []
    logs = []
    for rank in range(2):
        env = dict(os.environ)
        env["PYTHONPATH"] = REPO
        env["JAX_PLATFORMS"] = "cpu"  # CPU-only harness (conftest pins it too)
        env.pop("XLA_FLAGS", None)
        env["RAMBA_TEST_PROCS"] = "2"
        env["RAMBA_TEST_PROC_ID"] = str(rank)
        env["RAMBA_TEST_COORD"] = f"localhost:{port}"
        env["RAMBA_TEST_SHARED_TMP"] = os.path.join(basetemp, "shared")
        env["RAMBA_TRACE"] = trace_base
        log = open(os.path.join(basetemp, f"rank{rank}.log"), "w")
        logs.append(log)
        procs.append(subprocess.Popen(
            [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
             f"--basetemp={os.path.join(basetemp, 'tmp')}", *pytest_args],
            env=env, stdout=log, stderr=subprocess.STDOUT, cwd=REPO,
        ))

    deadline = time.time() + budget
    rcs = [None, None]
    try:
        for i, p in enumerate(procs):
            left = max(5.0, deadline - time.time())
            try:
                rcs[i] = p.wait(timeout=left)
            except subprocess.TimeoutExpired:
                p.kill()
                rcs[i] = -9
    finally:
        for log in logs:
            log.close()

    ok = all(rc == 0 for rc in rcs)

    # Both ranks must have produced a parseable JSONL trace with at least
    # one flush span — the observability stream works under SPMD.
    import json

    for rank in range(2):
        path = f"{trace_base}.rank{rank}"
        try:
            with open(path) as f:
                evs = [json.loads(ln) for ln in f if ln.strip()]
            n_flush = sum(1 for e in evs if e.get("type") == "flush")
            bad_rank = sum(1 for e in evs if e.get("rank") != rank)
            print(f"trace rank {rank}: {len(evs)} events, "
                  f"{n_flush} flush spans")
            if n_flush == 0 or bad_rank:
                print(f"trace rank {rank}: FAIL "
                      f"(flush={n_flush}, mis-ranked={bad_rank})")
                ok = False
        except (OSError, ValueError) as e:
            print(f"trace rank {rank}: FAIL ({e})")
            ok = False

    for rank in range(2):
        path = os.path.join(basetemp, f"rank{rank}.log")
        with open(path) as f:
            tail = f.read().splitlines()[-(4 if ok else 40):]
        print(f"--- rank {rank} rc={rcs[rank]} ({path}) ---")
        print("\n".join(tail))
    print(f"two-process suite: {'OK' if ok else 'FAIL'}")
    if ok:
        shutil.rmtree(basetemp, ignore_errors=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
