#!/bin/sh
# Run the suite in both numerics legs (see README "Tests"):
#   x64 on  - NumPy-exact differential comparisons
#   x64 off - the TPU execution regime (32-bit lattice, relaxed tolerance)
set -e
cd "$(dirname "$0")/.."
echo "=== leg 1: x64 (NumPy-exact) ==="
python -m pytest tests/ -q "$@"
echo "=== leg 2: x32 (TPU numerics) ==="
RAMBA_TEST_X64=0 python -m pytest tests/ -q "$@"
echo "=== leg 3: RAMBA_VERIFY=1 (strict flush-time program verifier) ==="
RAMBA_VERIFY=1 python -m pytest tests/ -q "$@"
echo "=== leg 4: 2-process fault injection (RAMBA_FAULTS=compile:once) ==="
python scripts/two_process_suite.py --fault-leg
echo "=== leg 5: 2-process memory governor (tiny RAMBA_HBM_BUDGET) ==="
python scripts/two_process_suite.py --memory-leg
echo "=== leg 6: 2-process kernel cost ledger (RAMBA_PERF=1) ==="
python scripts/two_process_suite.py --perf-leg
echo "=== leg 7: 2-process serving sessions (async pipeline, coalescing) ==="
python scripts/two_process_suite.py --serving-leg
echo "=== leg 8: elastic lifecycle (2-rank checkpoint, 1-rank resume) ==="
python scripts/two_process_suite.py --elastic-leg
echo "=== leg 9: live telemetry (2-rank exporters, shared cross-rank trace) ==="
python scripts/two_process_suite.py --telemetry-leg
echo "=== leg 10: backend autotune race (2-rank, same backend latched per fingerprint) ==="
python scripts/two_process_suite.py --autotune-leg
echo "=== leg 11: 2-process rank-skewed chaos soak (coherent recovery) ==="
python scripts/two_process_suite.py --chaos-leg
echo "=== leg 12: staged resharding + live mesh elasticity (2-rank round-trip, 2->1 reshape) ==="
python scripts/two_process_suite.py --reshard-leg
echo "=== leg 13: effect-certified result memoization (2-rank lockstep cache) ==="
python scripts/two_process_suite.py --memo-leg
echo "=== leg 14: coherent load shedding (2-rank, rank-skewed serve:admit faults) ==="
python scripts/two_process_suite.py --overload-leg
echo "=== leg 15: compile classes + persistent warm start (2-rank lockstep buckets, AOT cache) ==="
python scripts/two_process_suite.py --warmstart-leg
echo "=== leg 16: critical-path attribution (2-rank lockstep stage waterfalls) ==="
python scripts/two_process_suite.py --attrib-leg
echo "=== leg 17: data integrity plane (2-rank agreed audit verdict) ==="
python scripts/two_process_suite.py --integrity-leg
echo "=== leg 18: self-metering observability (head-sampled trace retention under rank skew) ==="
python scripts/two_process_suite.py --sampling-leg
