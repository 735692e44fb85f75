"""Checkpoint/restore of (trees of) distributed arrays via Orbax.

The reference has no checkpointing at all (SURVEY §5 — fileio.save is
already an extension); this module goes further the TPU-native way:
Orbax writes each array's shards from their owning devices (OCDBT format)
and restores them directly into a target sharding, so neither direction
stages the full array on the host.

Resilience contract:

* ``save`` is **atomic**: Orbax writes into a temp sibling
  (``<path>.ramba-tmp``) which is renamed over the final path only once
  the write completed — the published path always holds either the old
  complete checkpoint or the new one, never a torn write.  Under
  multi-controller SPMD all ranks barrier around a rank-0 rename.
* Transient I/O failures retry under ``resilience.retry`` (site
  ``checkpoint_io``); the ``RAMBA_FAULTS=checkpoint_io:...`` injection
  site drives both paths in tests.
* ``restore`` validates what came back (tree structure and per-leaf
  shape/dtype against the target) and wraps unreadable/corrupt
  checkpoints in :class:`CheckpointCorruptError` with the original error
  chained, instead of an opaque Orbax stack.

API:

    ramba_tpu.checkpoint.save(path, {"w": W, "b": B})
    state = ramba_tpu.checkpoint.restore(path)            # saved shardings
    state = ramba_tpu.checkpoint.restore(path, target)    # re-shard to target
"""

from __future__ import annotations

import os
import shutil

import jax
import numpy as np

from ramba_tpu.core.expr import Const
from ramba_tpu.core.fuser import flush
from ramba_tpu.core.ndarray import ndarray
from ramba_tpu.observe import registry as _registry
from ramba_tpu.resilience import faults as _faults
from ramba_tpu.resilience import integrity as _integrity
from ramba_tpu.resilience import retry as _retry


class CheckpointCorruptError(RuntimeError):
    """The on-disk checkpoint is missing, unreadable, structurally wrong,
    or does not match the requested restore target."""


# Deterministic tmp sibling (not mkdtemp): every SPMD rank must compute
# the same staging path, and a crashed writer's debris is findable.
_TMP_SUFFIX = ".ramba-tmp"

# Digest sidecar published by rank 0 after the checkpoint rename: logical
# per-leaf content digests (stamped from the values handed to Orbax, so a
# restore verifies end to end) plus a file-level digest map of the
# published directory (what ramba-fsck and the pre-restore scan verify
# without initializing Orbax).  Lives OUTSIDE the Orbax dir so Orbax's
# own directory handling never sees a foreign file.
_DIGESTS_SUFFIX = ".digests.json"
_DIGESTS_SCHEMA = "ckpt.digests.json"


def digests_path(path: str) -> str:
    return os.path.abspath(path) + _DIGESTS_SUFFIX


def _leaf_items(vals) -> list:
    import jax.tree_util as jtu

    return [(jtu.keystr(p), v)
            for p, v in jtu.tree_flatten_with_path(vals)[0]]


def _write_digests(apath: str, vals) -> None:
    """Rank-0 sidecar publish (post-rename).  Best-effort: a failed
    digest pass removes any stale sidecar rather than leaving one that
    contradicts the new checkpoint."""
    import json
    import tempfile

    side = apath + _DIGESTS_SUFFIX
    if not _integrity.enabled():
        try:  # a stale sidecar must not contradict the new checkpoint
            os.unlink(side)
        except OSError:
            pass
        return
    try:
        leaves = {}
        for keystr, v in _leaf_items(vals):
            if not getattr(v, "is_fully_addressable", True):
                # multi-host shard-split value: no single process holds
                # the global bytes — skip logical digests, keep files
                leaves = None
                break
            leaves[keystr] = {
                "sha256": _integrity.array_digest(v),
                "shape": [int(s) for s in np.shape(v)],
                "dtype": str(np.dtype(getattr(v, "dtype", type(v)))),
            }
        files = {}
        for root, _dirs, names in os.walk(apath):
            for name in names:
                full = os.path.join(root, name)
                rel = os.path.relpath(full, apath)
                files[rel] = {"sha256": _integrity.file_digest(full),
                              "size": os.path.getsize(full)}
        doc = {"schema": 1, "leaves": leaves, "files": files}
        data = _integrity.wrap(json.dumps(doc, sort_keys=True).encode(),
                               _DIGESTS_SCHEMA)
        fd, tmp = tempfile.mkstemp(dir=os.path.dirname(side) or ".",
                                   prefix=".tmp-")
        with os.fdopen(fd, "wb") as f:
            f.write(data)
        os.replace(tmp, side)
        _registry.inc("checkpoint.digests_written")
    except Exception:  # noqa: BLE001 — the sidecar must never fail a save
        try:
            os.unlink(side)
        except OSError:
            pass


def _load_digests(apath: str):
    """Parse a checkpoint's digest sidecar.  ``None`` when absent (a
    pre-plane checkpoint restores unverified); a corrupt sidecar raises —
    an unverifiable checkpoint must not be served silently."""
    import json

    side = apath + _DIGESTS_SUFFIX
    try:
        with open(side, "rb") as f:
            raw = f.read()
    except OSError:
        return None
    if not _integrity.enabled():
        return None
    try:
        payload = _integrity.unwrap(raw, _DIGESTS_SCHEMA,
                                    site="checkpoint:leaf")
        return json.loads(payload.decode())
    except (_integrity.IntegrityError, ValueError) as e:
        raise CheckpointCorruptError(
            f"checkpoint digest sidecar at {side!r} is corrupt ({e})"
        ) from e


def _verify_files(path: str, apath: str, doc: dict) -> None:
    """Pre-restore scan: every file the save stamped must still be
    byte-identical.  This is what catches a clobbered/truncated *leaf*
    file even when its bytes would still deserialize."""
    files = doc.get("files") or {}
    if _faults.configured("checkpoint:leaf") and files:
        # flip seam (RAMBA_FAULTS='checkpoint:leaf:flip:...'): physically
        # corrupt the first stamped data file, upstream of verification —
        # the flip persists on disk, so ramba-fsck finds it offline too
        rel = sorted(files)[0]
        _faults.corrupt_file("checkpoint:leaf", os.path.join(apath, rel))
    for rel, want in sorted(files.items()):
        full = os.path.join(apath, rel)
        try:
            size = os.path.getsize(full)
        except OSError as e:
            _integrity.failure("checkpoint:leaf", "missing", detail=rel)
            raise CheckpointCorruptError(
                f"checkpoint at {path!r} is missing leaf file {rel!r} "
                f"({e})") from e
        if size != want.get("size"):
            _integrity.failure("checkpoint:leaf", "length", detail=rel)
            raise CheckpointCorruptError(
                f"checkpoint at {path!r} leaf file {rel!r} is "
                f"{size} bytes, manifest says {want.get('size')}")
        if _integrity.file_digest(full) != want.get("sha256"):
            _integrity.failure("checkpoint:leaf", "digest", detail=rel)
            raise CheckpointCorruptError(
                f"checkpoint at {path!r} leaf file {rel!r} failed digest "
                f"verification (silent corruption)")


def _verify_leaves(path: str, out, doc: dict) -> None:
    """Post-restore logical check: the restored arrays' content digests
    must match what was stamped at save time — end-to-end coverage of
    the disk -> host -> device path, sharding-independent."""
    leaves = doc.get("leaves")
    if not leaves:
        return
    for keystr, v in _leaf_items(out):
        want = leaves.get(keystr)
        if want is None:
            continue
        if not getattr(v, "is_fully_addressable", True):
            continue
        if _integrity.array_digest(v) != want["sha256"]:
            _integrity.failure("checkpoint:leaf", "digest", detail=keystr)
            raise CheckpointCorruptError(
                f"checkpoint at {path!r} restored leaf {keystr!r} failed "
                f"content-digest verification (silent corruption)")


def _barrier(tag: str) -> None:
    # Delegated so cross-rank checkpoint syncs run under the elastic
    # watchdog deadline (a dead rank -> RankStallError, not a hang).
    from ramba_tpu.parallel import distributed as _distributed

    _distributed.barrier(tag)


def _purge_stale_tmp(apath: str) -> None:
    """Remove a crashed writer's staging debris before staging again.

    Debris comes in two shapes: the ``<path>.ramba-tmp`` sibling itself
    (writer died after Orbax finalized the temp but before the rename)
    and Orbax's own in-progress directories
    (``<path>.ramba-tmp.orbax-checkpoint-tmp-<ts>`` /
    ``<path>.orbax-checkpoint-tmp-<ts>``, writer died mid-write).  The
    latter survive the in-``write()`` purge of the exact tmp path and
    make the next staged save fail (Orbax refuses the incomplete
    checkpoint) or leak disk forever.  Rank 0 sweeps every sibling with
    a matching prefix; all ranks barrier so nobody stages into a
    directory that is being deleted."""
    if jax.process_index() == 0:
        parent, base = os.path.split(apath)
        tmp_base = base + _TMP_SUFFIX
        if os.path.isdir(parent):
            for name in os.listdir(parent):
                if name == tmp_base or \
                        name.startswith(tmp_base + ".") or \
                        name.startswith(base + ".orbax-checkpoint-tmp-"):
                    victim = os.path.join(parent, name)
                    shutil.rmtree(victim, ignore_errors=True)
                    _registry.inc("checkpoint.tmp_purged")
    _barrier("ramba_ckpt_purge")


def save(path: str, tree, *, force: bool = False) -> None:
    """Write a pytree of framework arrays (device-direct, sharded).

    ``force=False`` (Orbax's own safe default) errors if ``path`` already
    holds a checkpoint instead of deleting it; pass ``force=True`` to
    overwrite deliberately.  The write is staged + renamed, so with
    ``force=True`` a crash mid-save leaves the previous checkpoint
    intact."""
    import orbax.checkpoint as ocp

    apath = os.path.abspath(path)
    if os.path.exists(apath) and not force:
        raise ValueError(
            f"refusing to overwrite existing checkpoint at {path!r}; "
            f"pass force=True"
        )
    flush()
    vals = jax.tree.map(
        lambda x: x._value() if isinstance(x, ndarray) else np.asarray(x),
        tree,
    )
    tmp = apath + _TMP_SUFFIX
    _purge_stale_tmp(apath)

    def write():
        _faults.check("checkpoint_io", op="save")
        if jax.process_index() == 0 and os.path.exists(tmp):
            shutil.rmtree(tmp)  # debris from a crashed/failed earlier save
        _barrier("ramba_ckpt_clear")
        with ocp.StandardCheckpointer() as ckptr:
            ckptr.save(tmp, vals, force=True)

    _retry.call("checkpoint_io", write)
    _barrier("ramba_ckpt_written")
    if jax.process_index() == 0:
        if os.path.exists(apath):
            shutil.rmtree(apath)
        os.replace(tmp, apath)
    _barrier("ramba_ckpt_published")
    if jax.process_index() == 0:
        _write_digests(apath, vals)
    _barrier("ramba_ckpt_digests")
    _registry.inc("checkpoint.saves")


def restore(path: str, target=None):
    """Read a checkpoint back as a pytree of framework arrays.

    Without ``target``, arrays come back with the shardings they were
    saved with.  With ``target`` (a pytree of framework arrays or
    ``jax.ShapeDtypeStruct`` with shardings), each leaf restores straight
    into that spec — how a resumed run re-shards a checkpoint onto a
    different mesh."""
    import orbax.checkpoint as ocp

    apath = os.path.abspath(path)
    if not os.path.isdir(apath):
        raise CheckpointCorruptError(f"no checkpoint directory at {path!r}")

    # Integrity pre-scan: verify the published files against the digest
    # sidecar BEFORE Orbax touches them — a clobbered leaf file raises
    # CheckpointCorruptError here even when its bytes still deserialize.
    digests = _load_digests(apath)
    if digests is not None:
        _verify_files(path, apath, digests)

    def spec(x):
        if isinstance(x, ndarray):
            v = x._value()
            return jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=v.sharding)
        if isinstance(x, jax.ShapeDtypeStruct):
            return x
        raise TypeError(
            f"restore target leaves must be framework arrays or "
            f"ShapeDtypeStructs, got {type(x).__name__}"
        )

    tgt = jax.tree.map(spec, target) if target is not None else None

    # Orbax restore is not strict about global shape (a mismatched target
    # silently truncates/pads), so a target is vetted against the
    # checkpoint's own metadata BEFORE any bytes are restored.
    if tgt is not None:
        try:
            with ocp.StandardCheckpointer() as ckptr:
                meta = ckptr.metadata(apath).item_metadata.tree
        except Exception as e:
            raise CheckpointCorruptError(
                f"checkpoint at {path!r} has unreadable metadata "
                f"({type(e).__name__}: {e})"
            ) from e
        _validate_target(path, meta, tgt)

    def read():
        _faults.check("checkpoint_io", op="restore")
        with ocp.StandardCheckpointer() as ckptr:
            if tgt is not None:
                return ckptr.restore(apath, tgt)
            return ckptr.restore(apath)

    try:
        out = _retry.call("checkpoint_io", read)
    except (_retry.RetryBudgetExhausted, _faults.InjectedFault):
        raise
    except Exception as e:
        raise CheckpointCorruptError(
            f"checkpoint at {path!r} is unreadable or does not match the "
            f"restore target ({type(e).__name__}: {e})"
        ) from e
    _validate(path, out, tgt)
    if digests is not None:
        _verify_leaves(path, out, digests)
    _registry.inc("checkpoint.restores")
    return jax.tree.map(lambda v: ndarray(Const(v)), out)


def _validate_target(path: str, meta, tgt) -> None:
    """A restore target must match what the checkpoint actually holds —
    tree structure and per-leaf shape/dtype — before restore runs."""
    got_s, want_s = jax.tree.structure(meta), jax.tree.structure(tgt)
    if got_s != want_s:
        raise CheckpointCorruptError(
            f"checkpoint at {path!r} tree structure {got_s} does not match "
            f"restore target {want_s}"
        )
    for saved, want in zip(jax.tree.leaves(meta), jax.tree.leaves(tgt)):
        if tuple(saved.shape) != tuple(want.shape) or (
            np.dtype(saved.dtype) != np.dtype(want.dtype)
        ):
            raise CheckpointCorruptError(
                f"checkpoint at {path!r} holds leaf "
                f"{tuple(saved.shape)}/{np.dtype(saved.dtype)} but the "
                f"restore target wants {tuple(want.shape)}/{want.dtype}"
            )


def _validate(path: str, out, tgt) -> None:
    """Post-restore validation: every leaf must be an array, and with a
    target the tree structure and per-leaf shape/dtype must match it."""
    for v in jax.tree.leaves(out):
        if not (hasattr(v, "shape") and hasattr(v, "dtype")):
            raise CheckpointCorruptError(
                f"checkpoint at {path!r} restored a non-array leaf "
                f"({type(v).__name__})"
            )
    if tgt is None:
        return
    got_s, want_s = jax.tree.structure(out), jax.tree.structure(tgt)
    if got_s != want_s:
        raise CheckpointCorruptError(
            f"checkpoint at {path!r} tree structure {got_s} does not match "
            f"restore target {want_s}"
        )
    for got, want in zip(jax.tree.leaves(out), jax.tree.leaves(tgt)):
        if tuple(got.shape) != tuple(want.shape) or (
            np.dtype(got.dtype) != np.dtype(want.dtype)
        ):
            raise CheckpointCorruptError(
                f"checkpoint at {path!r} leaf {got.shape}/{got.dtype} does "
                f"not match restore target {want.shape}/{want.dtype}"
            )
