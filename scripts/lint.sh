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
if JAX_PLATFORMS=cpu RAMBA_ARTIFACTS="$ftd" python - <<'EOF'
import os
import sys

import numpy as np

from ramba_tpu.fleet import artifacts

sys.path.insert(0, os.path.join(os.getcwd(), "scripts"))
import ramba_fsck  # noqa: E402

artifacts.configure()
assert artifacts.memo_store("fscksmoke0" * 3 + "ab", [np.arange(16.0)])
assert artifacts.memo_store("fscksmoke1" * 3 + "cd", [np.ones(4)])
root = os.environ["RAMBA_ARTIFACTS"]

r = ramba_fsck.scan(artifacts=root)
assert r["status"] == 0 and r["scanned"] >= 2, r

blob = os.path.join(root, "memo", sorted(os.listdir(os.path.join(root, "memo")))[0])
b = bytearray(open(blob, "rb").read())
b[len(b) // 2] ^= 0xFF
open(blob, "wb").write(bytes(b))

r = ramba_fsck.scan(artifacts=root)
assert r["status"] == 1 and r["corrupt"] == 1, r

r = ramba_fsck.scan(artifacts=root, repair=True)
assert r["status"] == 1 and os.path.isdir(os.path.join(root, "quarantine")), r

r = ramba_fsck.scan(artifacts=root)
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
