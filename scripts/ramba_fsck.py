#!/usr/bin/env python
"""ramba-fsck: offline integrity verification of everything ramba_tpu
persists — run it before trusting a warm cache tier, after a machine
came back from a crash, or from cron as a corruption tripwire.

What gets scanned (each an independent leg; a leg with nothing to scan
is skipped, and scanning *nothing at all* is its own exit code so a
misconfigured cron job cannot masquerade as a clean fleet):

* the shared artifact tier (``--artifacts`` / ``RAMBA_ARTIFACTS``):
  memo blobs (``memo/*.npz``), plan certificates (``plancert/*.json``),
  migration handoffs (``handoff/*.manifest.json`` + each checkpoint's
  payload byte census + digest sidecar);
* the persistent executable cache (``--cache`` / ``RAMBA_CACHE``):
  AOT entries (``aot/*.aot``) and program skeletons
  (``programs/*.pkl``);
* checkpoint trees (``--checkpoint PATH``, repeatable): the
  ``<path>.digests.json`` sidecar's file map re-verified byte-for-byte,
  elastic ``MANIFEST.json`` self-digests, recursing over
  ``step_<n>/`` layouts.

Verification uses :func:`ramba_tpu.resilience.integrity.verify_blob`,
which never emits events and never strikes the live suspect window —
an offline scan must not quarantine the process running it.

``--repair`` moves every corrupt entry into a ``quarantine/`` directory
beside its scan root (cache entries are disposable: the runtime
recomputes/recompiles on the resulting miss; a quarantined checkpoint
leaf makes the checkpoint refuse restore loudly instead of serving
silently corrupt state).

Exit status (the contract scripts/lint.sh and cron wrappers consume,
mirroring scripts/fleet_collector.py): ``0`` everything verified,
``1`` corruption found (fix or re-run with ``--repair``), ``4``
nothing to scan anywhere.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import List, Optional

# offline checker: a chip belongs to one process at a time, and this one
# must never be it — set, not defaulted
os.environ["JAX_PLATFORMS"] = "cpu"

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

from ramba_tpu.resilience import integrity as _integrity  # noqa: E402

#: schema tag per scanned blob shape (import-light: the tags are data,
#: re-declared here so fsck never imports jax through the cache modules)
_MEMO_SCHEMA = "memo.npz"
_CERT_SCHEMA = "plancert.json"
_AOT_SCHEMA = "aot.pkl"
_PROGRAM_SCHEMA = "program.pkl"
_DIGESTS_SCHEMA = "ckpt.digests.json"
_DIGESTS_SUFFIX = ".digests.json"

EXIT_CLEAN = 0
EXIT_CORRUPT = 1
EXIT_EMPTY = 4


def _read(path: str) -> Optional[bytes]:
    try:
        with open(path, "rb") as f:
            return f.read()
    except OSError:
        return None


def _quarantine(root: str, path: str, report: dict) -> None:
    """Move one corrupt entry into ``<root>/quarantine/``, keeping the
    relative layout so an operator can inspect what was pulled."""
    import shutil

    qdir = os.path.join(root, "quarantine")
    rel = os.path.relpath(path, root)
    if rel.startswith(".."):
        rel = os.path.basename(path)
    dest = os.path.join(qdir, rel)
    try:
        os.makedirs(os.path.dirname(dest), exist_ok=True)
        shutil.move(path, dest)
        report["quarantined"].append({"path": path, "to": dest})
    except OSError as e:
        report["repair_errors"].append({"path": path, "error": str(e)})


def _bad(report: dict, root: str, path: str, schema: str, reason: str,
         repair: bool) -> None:
    report["corrupt"].append({"path": path, "schema": schema,
                              "reason": reason})
    if repair:
        _quarantine(root, path, report)


def _scan_blob_dir(report: dict, root: str, sub: str, suffix: str,
                   schema: str, repair: bool) -> None:
    d = os.path.join(root, sub)
    try:
        names = sorted(os.listdir(d))
    except OSError:
        return
    for name in names:
        if not name.endswith(suffix) or name.startswith(".tmp-"):
            continue
        path = os.path.join(d, name)
        report["scanned"] += 1
        reason = _integrity.verify_blob(_read(path), schema)
        if reason is not None:
            _bad(report, root, path, schema, reason, repair)


def _payload_census(ckpt_dir: str) -> tuple:
    """(total_bytes, sorted file list) over one checkpoint directory —
    the same census fleet/migrate.py records as ``payload_bytes``."""
    files: List[str] = []
    total = 0
    for r, _dirs, names in os.walk(ckpt_dir):
        for name in names:
            full = os.path.join(r, name)
            files.append(full)
            try:
                total += os.path.getsize(full)
            except OSError:
                pass
    return total, sorted(files)


def _scan_sidecar(report: dict, root: str, side: str, repair: bool) -> None:
    """Verify one checkpoint digest sidecar: the sidecar's own envelope,
    then every file it stamps, byte-for-byte."""
    apath = side[:-len(_DIGESTS_SUFFIX)]
    report["scanned"] += 1
    raw = _read(side)
    reason = _integrity.verify_blob(raw, _DIGESTS_SCHEMA)
    if reason is not None:
        _bad(report, root, side, _DIGESTS_SCHEMA, reason, repair)
        return
    try:
        doc = json.loads(raw[raw.index(b"\n") + 1:])
        files = doc.get("files") or {}
    except (ValueError, AttributeError):
        _bad(report, root, side, _DIGESTS_SCHEMA, "deserialize", repair)
        return
    for rel, want in sorted(files.items()):
        full = os.path.join(apath, rel)
        report["scanned"] += 1
        try:
            size = os.path.getsize(full)
        except OSError:
            _bad(report, root, full, "checkpoint:leaf", "missing", repair)
            continue
        if size != want.get("size"):
            _bad(report, root, full, "checkpoint:leaf",
                 f"length:{size}!={want.get('size')}", repair)
            continue
        if _integrity.file_digest(full) != want.get("sha256"):
            _bad(report, root, full, "checkpoint:leaf", "digest", repair)


def _scan_handoffs(report: dict, root: str, repair: bool) -> None:
    d = os.path.join(root, "handoff")
    try:
        names = sorted(os.listdir(d))
    except OSError:
        return
    for name in names:
        if not name.endswith(".manifest.json"):
            continue
        mpath = os.path.join(d, name)
        report["scanned"] += 1
        try:
            man = json.loads(_read(mpath) or b"")
        except ValueError:
            _bad(report, root, mpath, "handoff.manifest", "deserialize",
                 repair)
            continue
        sid = name[:-len(".manifest.json")]
        ckpt = os.path.join(d, sid)
        want = man.get("payload_bytes")
        if want is not None and os.path.isdir(ckpt):
            got, _files = _payload_census(ckpt)
            if got != want:
                _bad(report, root, mpath, "handoff.manifest",
                     f"payload_bytes:{got}!={want}", repair)
        side = ckpt + _DIGESTS_SUFFIX
        if os.path.exists(side):
            _scan_sidecar(report, root, side, repair)


def _scan_manifest_selfdigest(report: dict, root: str, mpath: str,
                              repair: bool) -> None:
    import hashlib

    report["scanned"] += 1
    try:
        man = json.loads(_read(mpath) or b"")
    except ValueError:
        _bad(report, root, mpath, "elastic.manifest", "deserialize", repair)
        return
    want = man.get("digest") if isinstance(man, dict) else None
    if want is None:
        return  # pre-digest manifest: nothing to verify offline
    body = {k: v for k, v in man.items() if k != "digest"}
    got = hashlib.sha256(
        json.dumps(body, sort_keys=True).encode()).hexdigest()
    if got != want:
        _bad(report, root, mpath, "elastic.manifest", "digest", repair)


def scan_artifacts(root: str, repair: bool = False) -> dict:
    report = _new_report(root, "artifacts")
    _scan_blob_dir(report, root, "memo", ".npz", _MEMO_SCHEMA, repair)
    _scan_blob_dir(report, root, "plancert", ".json", _CERT_SCHEMA, repair)
    _scan_handoffs(report, root, repair)
    return report


def scan_cache(root: str, repair: bool = False) -> dict:
    report = _new_report(root, "cache")
    _scan_blob_dir(report, root, "aot", ".aot", _AOT_SCHEMA, repair)
    _scan_blob_dir(report, root, "programs", ".pkl", _PROGRAM_SCHEMA,
                   repair)
    return report


def scan_checkpoint(path: str, repair: bool = False) -> dict:
    """One checkpoint tree: a direct ``<path>.digests.json`` sidecar, or
    a root holding ``step_<n>/`` layouts (elastic CheckpointManager) —
    every sidecar and MANIFEST self-digest under it."""
    root = os.path.abspath(path)
    report = _new_report(root, "checkpoint")
    side = root + _DIGESTS_SUFFIX
    if os.path.exists(side):
        _scan_sidecar(report, os.path.dirname(root) or root, side, repair)
    for r, _dirs, names in os.walk(root):
        for name in sorted(names):
            full = os.path.join(r, name)
            if name.endswith(_DIGESTS_SUFFIX):
                _scan_sidecar(report, root, full, repair)
            elif name == "MANIFEST.json":
                _scan_manifest_selfdigest(report, root, full, repair)
    return report


def _new_report(root: str, kind: str) -> dict:
    return {"kind": kind, "root": root, "scanned": 0, "corrupt": [],
            "quarantined": [], "repair_errors": []}


def scan(artifacts: Optional[str] = None, cache: Optional[str] = None,
         checkpoints: Optional[List[str]] = None,
         repair: bool = False) -> dict:
    """Importable entry point (bench.py times it; tests drive it).
    Returns ``{"legs": [...], "scanned": n, "corrupt": n, "status": s}``
    with ``status`` matching the CLI exit code."""
    legs = []
    if artifacts and os.path.isdir(artifacts):
        legs.append(scan_artifacts(artifacts, repair))
    if cache and os.path.isdir(cache):
        legs.append(scan_cache(cache, repair))
    for c in checkpoints or []:
        if os.path.exists(c) or os.path.exists(c + _DIGESTS_SUFFIX):
            legs.append(scan_checkpoint(c, repair))
    scanned = sum(leg["scanned"] for leg in legs)
    corrupt = sum(len(leg["corrupt"]) for leg in legs)
    status = EXIT_EMPTY if scanned == 0 else (
        EXIT_CORRUPT if corrupt else EXIT_CLEAN)
    return {"legs": legs, "scanned": scanned, "corrupt": corrupt,
            "status": status}


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="ramba-fsck",
        description="offline integrity verification of ramba_tpu's "
                    "persisted artifacts, caches and checkpoints")
    ap.add_argument("--artifacts", default=os.environ.get("RAMBA_ARTIFACTS"),
                    help="shared artifact tier dir (default: "
                         "RAMBA_ARTIFACTS)")
    ap.add_argument("--cache", default=os.environ.get("RAMBA_CACHE"),
                    help="persistent executable cache dir (default: "
                         "RAMBA_CACHE)")
    ap.add_argument("--checkpoint", action="append", default=[],
                    metavar="PATH",
                    help="checkpoint path or elastic root (repeatable)")
    ap.add_argument("--repair", action="store_true",
                    help="move corrupt entries into quarantine/ beside "
                         "their scan root")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="emit the full report as JSON on stdout")
    args = ap.parse_args(argv)

    result = scan(artifacts=args.artifacts, cache=args.cache,
                  checkpoints=args.checkpoint, repair=args.repair)
    if args.as_json:
        json.dump(result, sys.stdout, indent=1, sort_keys=True)
        sys.stdout.write("\n")
    else:
        for leg in result["legs"]:
            print(f"ramba-fsck: {leg['kind']} {leg['root']}: "
                  f"{leg['scanned']} scanned, "
                  f"{len(leg['corrupt'])} corrupt, "
                  f"{len(leg['quarantined'])} quarantined")
            for c in leg["corrupt"]:
                print(f"  CORRUPT {c['path']} [{c['schema']}] "
                      f"{c['reason']}")
        if not result["legs"]:
            print("ramba-fsck: nothing to scan (set RAMBA_ARTIFACTS / "
                  "RAMBA_CACHE or pass --checkpoint)", file=sys.stderr)
    if result["status"] == EXIT_CORRUPT and args.repair and all(
            not leg["repair_errors"] and
            len(leg["quarantined"]) >= len(leg["corrupt"])
            for leg in result["legs"]):
        print("ramba-fsck: corrupt entries quarantined; rerun to verify")
    return result["status"]


if __name__ == "__main__":
    raise SystemExit(main())
