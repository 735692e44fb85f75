#!/usr/bin/env bash
# Static-quality gate: ramba-lint over a smoke trace, plus ruff + mypy
# when they are installed (CI images have them; minimal containers may
# not — the gate degrades to the parts that exist rather than failing).
#
#   scripts/lint.sh [trace.jsonl ...]
#
# With no arguments, a tiny smoke workload is traced into a tempdir and
# linted strictly (including the --memo-audit replay); passing trace
# paths lints those instead.
set -euo pipefail
cd "$(dirname "$0")/.."

rc=0

if [ "$#" -gt 0 ]; then
    traces=("$@")
else
    td="$(mktemp -d)"
    trap 'rm -rf "$td"' EXIT
    echo "== lint.sh: capturing smoke trace =="
    JAX_PLATFORMS=cpu RAMBA_TRACE="$td/smoke.jsonl" RAMBA_VERIFY=warn \
        RAMBA_MEMO=1 RAMBA_PLANCERT=1 python - <<'EOF'
import numpy as np
import ramba_tpu as rt

a = rt.fromarray(np.arange(64.0).reshape(8, 8))
b = rt.fromarray(np.ones((8, 8)))
for _ in range(3):
    np.asarray((a + b) * 2.0)
np.asarray((a - b).sum())
EOF
    traces=("$td/smoke.jsonl")
fi

echo "== lint.sh: ramba-lint --strict =="
JAX_PLATFORMS=cpu python -m ramba_tpu.analyze --strict "${traces[@]}" || rc=1

echo "== lint.sh: ramba-lint --memo-audit =="
JAX_PLATFORMS=cpu python -m ramba_tpu.analyze --memo-audit "${traces[@]}" || rc=1

echo "== lint.sh: ramba-lint --plan-audit =="
JAX_PLATFORMS=cpu python -m ramba_tpu.analyze --plan-audit "${traces[@]}" || rc=1

echo "== lint.sh: ramba-fsck smoke (seed, verify, flip, repair) =="
ftd="$(mktemp -d)"
# a jax cache of its own: an AOT store is skipped where jax's cache served
# the compile (compile/persist.py), which a shared cache would after one run
if JAX_PLATFORMS=cpu RAMBA_CACHE="$ftd/ramba" JAX_COMPILATION_CACHE_DIR="$ftd/jax" \
        python - <<'EOF'
import os
import sys

import numpy as np

import ramba_tpu as rt
from ramba_tpu.compile import persist

sys.path.insert(0, os.path.join(os.getcwd(), "scripts"))
import ramba_fsck  # noqa: E402

np.asarray(rt.array(np.arange(16.0)) * 3.0 + 1.0)
assert persist.save_topk(4)["stored"] >= 1
root = os.environ["RAMBA_CACHE"]

r = ramba_fsck.scan(cache=root)
assert r["status"] == 0 and r["scanned"] >= 2, r

aot = os.path.join(root, "aot")
blob = os.path.join(
    aot, sorted(n for n in os.listdir(aot) if n.endswith(".aot"))[0])
b = bytearray(open(blob, "rb").read())
b[len(b) // 2] ^= 0xFF
open(blob, "wb").write(bytes(b))

r = ramba_fsck.scan(cache=root)
assert r["status"] == 1 and r["corrupt"] == 1, r

r = ramba_fsck.scan(cache=root, repair=True)
assert r["status"] == 1 and os.path.isdir(os.path.join(root, "quarantine")), r

r = ramba_fsck.scan(cache=root)
assert r["status"] == 0, r
print("fsck smoke: detect + quarantine + clean rescan OK")
EOF
then
    :
else
    echo "lint.sh: ramba-fsck smoke FAILED"
    rc=1
fi
rm -rf "$ftd"

if command -v ruff >/dev/null 2>&1; then
    echo "== lint.sh: ruff =="
    ruff check ramba_tpu tests scripts || rc=1
else
    echo "== lint.sh: ruff not installed, skipping =="
fi

if command -v mypy >/dev/null 2>&1; then
    echo "== lint.sh: mypy (typed-surface gate) =="
    mypy ramba_tpu/analyze ramba_tpu/core/expr.py ramba_tpu/core/memo.py \
        ramba_tpu/core/plancache.py || rc=1
else
    echo "== lint.sh: mypy not installed, skipping =="
fi

exit "$rc"
