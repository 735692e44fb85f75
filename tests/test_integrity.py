"""End-to-end data integrity plane (``resilience/integrity.py``).

The contract under test, in order of importance:

* **Never serve suspect bytes** — a flipped blob at any stamped seam
  (AOT executable, checkpoint leaf) is detected by digest, evicted, and
  recompiled / rejected; the caller observes the *correct* answer or a
  classified error, never silence and never a crash.
* **Byte identity under audit** — ``RAMBA_AUDIT`` shadow re-execution
  must not perturb primary results: audit-on and audit-off runs of the
  same seeded chain are byte-identical.
* **Visibility** — every detection is an ``integrity`` event, a
  counter, and (past ``RAMBA_INTEGRITY_THRESHOLD`` in the window) a
  ``suspect`` verdict on the event and the ``integrity.suspect`` gauge.
* **Offline scrub** — ``ramba-fsck`` finds at-rest corruption with the
  runtime not even loaded, and ``--repair`` quarantines rather than
  deletes.

The SPMD analog (rank-skewed shadow flips agreed via coherence) is
``scripts/two_process_suite.py --integrity-leg``.
"""

import json
import os
import sys

import numpy as np
import pytest

import ramba_tpu as rt
from ramba_tpu import diagnostics
from ramba_tpu.compile import persist
from ramba_tpu.core import fuser, memo
from ramba_tpu.observe import events, registry, telemetry
from ramba_tpu.resilience import faults, integrity

_SCRIPTS = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "scripts")
if _SCRIPTS not in sys.path:
    sys.path.insert(0, _SCRIPTS)
import ramba_fsck  # noqa: E402


@pytest.fixture(autouse=True)
def _isolate(monkeypatch):
    """Empty pending set, integrity plane at defaults (stamping on,
    audits off), zeroed counters, no faults."""
    fuser.flush()
    faults.configure(None)
    for k in ("RAMBA_INTEGRITY", "RAMBA_AUDIT", "RAMBA_INTEGRITY_THRESHOLD",
              "RAMBA_INTEGRITY_WINDOW_S", "RAMBA_MEMO", "RAMBA_FAULTS",
              "RAMBA_VERIFY", "RAMBA_PLANCERT"):
        monkeypatch.delenv(k, raising=False)
    integrity.reset()
    memo.reset()
    yield
    faults.reset()
    fuser.flush()
    integrity.reset()
    memo.reset()
    for k in ("RAMBA_MEMO", "RAMBA_AUDIT", "RAMBA_INTEGRITY"):
        os.environ.pop(k, None)


# ---------------------------------------------------------------------------
# RAMBA_FAULTS flip mode (the corruption driver itself)
# ---------------------------------------------------------------------------


class TestFlipMode:
    def test_unarmed_is_identity(self):
        data = b"x" * 64
        assert faults.corrupt("memo:blob", data) is data

    def test_flip_is_deterministic_and_bounded(self):
        data = bytes(range(256))
        faults.configure("memo:blob:flip:bytes=2", seed=7)
        first = faults.corrupt("memo:blob", data)
        faults.configure("memo:blob:flip:bytes=2", seed=7)
        again = faults.corrupt("memo:blob", data)
        assert first == again and first != data
        assert len(first) == len(data)
        diff = [i for i in range(len(data)) if first[i] != data[i]]
        assert 1 <= len(diff) <= 2
        # XOR 0xFF self-inverts: re-flipping restores the original
        assert all(first[i] ^ 0xFF == data[i] for i in diff)

    def test_after_is_one_shot(self):
        data = b"payload-bytes" * 4
        faults.configure("memo:blob:flip:bytes=1:after=1", seed=3)
        assert faults.corrupt("memo:blob", data) == data       # call 1
        assert faults.corrupt("memo:blob", data) != data       # call 2 fires
        assert faults.corrupt("memo:blob", data) == data       # call 3

    def test_corrupt_file_flips_in_place(self, tmp_path):
        p = str(tmp_path / "blob.bin")
        data = os.urandom(128)
        with open(p, "wb") as f:
            f.write(data)
        faults.configure("checkpoint:leaf:flip:bytes=3", seed=1)
        assert faults.corrupt_file("checkpoint:leaf", p)
        with open(p, "rb") as f:
            now = f.read()
        assert now != data and len(now) == len(data)

    def test_flip_emits_fault_event(self):
        faults.configure("memo:blob:flip:bytes=1")
        faults.corrupt("memo:blob", b"0123456789")
        ev = events.last(4, type="fault")
        assert any(e.get("site") == "memo:blob" and e.get("mode") == "flip"
                   for e in ev), ev


# ---------------------------------------------------------------------------
# the envelope
# ---------------------------------------------------------------------------


class TestEnvelope:
    def test_roundtrip(self):
        payload = b"the quick brown fox"
        blob = integrity.wrap(payload, "memo.npz")
        assert blob != payload
        assert integrity.unwrap(blob, "memo.npz", site="test") == payload
        assert integrity.stats["stamped"] >= 1
        assert integrity.stats["verified"] >= 1

    def test_every_single_byte_flip_is_detected(self):
        blob = bytearray(integrity.wrap(b"ramba", "memo.npz"))
        for i in range(len(blob)):
            bad = bytes(blob[:i]) + bytes([blob[i] ^ 0xFF]) \
                + bytes(blob[i + 1:])
            with pytest.raises(integrity.IntegrityError):
                integrity.unwrap(bad, "memo.npz", site="test",
                                 record=False)

    def test_unstamped_is_strict(self):
        with pytest.raises(integrity.IntegrityError) as ei:
            integrity.unwrap(b"no envelope here", "memo.npz",
                             site="memo:blob")
        assert ei.value.reason == "unstamped"
        assert integrity.stats["failures"] >= 1
        ev = events.last(4, type="integrity")
        assert ev and ev[-1]["site"] == "memo:blob", ev

    def test_schema_confusion_is_detected(self):
        blob = integrity.wrap(b"payload", "aot.pkl")
        with pytest.raises(integrity.IntegrityError) as ei:
            integrity.unwrap(blob, "memo.npz", site="test", record=False)
        assert ei.value.reason == "schema"

    def test_disabled_plane_strips_without_verifying(self, monkeypatch):
        blob = integrity.wrap(b"payload", "memo.npz")
        monkeypatch.setenv("RAMBA_INTEGRITY", "0")
        assert not integrity.enabled()
        # stamped blobs still load (envelope stripped), raw blobs pass
        # through, and even a flipped digest no longer raises
        assert integrity.unwrap(blob, "memo.npz", site="t") == b"payload"
        assert integrity.unwrap(b"raw", "memo.npz", site="t") == b"raw"
        bad = blob[:10] + bytes([blob[10] ^ 0xFF]) + blob[11:]
        integrity.unwrap(bad, "memo.npz", site="t")  # must not raise
        # and new writes are unstamped (identity)
        assert integrity.wrap(b"new", "memo.npz") == b"new"

    def test_verify_blob_classifies_offline(self):
        blob = integrity.wrap(b"payload", "memo.npz")
        assert integrity.verify_blob(blob, "memo.npz") is None
        assert integrity.verify_blob(None, "memo.npz") == "missing"
        assert integrity.verify_blob(b"raw", "memo.npz") == "unstamped"
        bad = blob[:-2] + bytes([blob[-2] ^ 0xFF]) + blob[-1:]
        assert integrity.verify_blob(bad, "memo.npz") == "digest"
        other = integrity.wrap(b"payload", "aot.pkl")
        assert str(integrity.verify_blob(other, "memo.npz")) \
            .startswith("schema")
        assert integrity.stats["failures"] == 0  # offline: no strikes


# ---------------------------------------------------------------------------
# seam: persistent AOT executables (aot:blob)
# ---------------------------------------------------------------------------


def _unstamp(path):
    """Rewrite a stamped blob as its bare payload: a pre-plane entry."""
    with open(path, "rb") as f:
        raw = f.read()
    payload = integrity.unwrap(raw, persist.AOT_SCHEMA, site="test",
                               record=False)
    with open(path, "wb") as f:
        f.write(payload)


def _bad_payload(path):
    """A valid envelope around bytes that do not deserialize."""
    with open(path, "wb") as f:
        f.write(integrity.wrap(b"not a pickle", persist.AOT_SCHEMA))


class _CacheDir:
    """Arm the AOT lane on ``root`` for the duration of a ``with``."""

    def __init__(self, root):
        self.root = root

    def __enter__(self):
        self.saved = {k: os.environ.get(k)
                      for k in ("RAMBA_CACHE", "RAMBA_AOT")}
        os.environ["RAMBA_CACHE"] = self.root
        os.environ.pop("RAMBA_AOT", None)
        persist.reconfigure()
        assert persist.armed(), persist.snapshot()
        with fuser._cache_lock:
            fuser._compile_cache.clear()
        return self

    def __exit__(self, *exc):
        faults.configure(None)
        for k, v in self.saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        persist.reset()


def _seed_cache(root, base):
    """Compile ``base * 5 - 2`` with the AOT lane on ``root`` and store
    its executable; returns the stored ``.aot`` paths."""
    np.asarray(rt.array(base) * 5.0 - 2.0)
    assert persist.save_topk(4)["stored"] >= 1
    aot_dir = os.path.join(root, "aot")
    return [os.path.join(aot_dir, n) for n in sorted(os.listdir(aot_dir))
            if n.endswith(".aot")]


class TestAotBlobSeam:
    @pytest.mark.parametrize("corruption",
                             ["flip", "unstamped", "deserialize"])
    def test_flip_evicts_recompiles_correct_answer(self, tmp_path,
                                                   corruption):
        root = str(tmp_path / "cache")
        with _CacheDir(root):
            base = np.arange(40, dtype=np.float32).reshape(5, 8)
            blobs = _seed_cache(root, base)
            assert blobs
            with fuser._cache_lock:
                fuser._compile_cache.clear()
            c0 = persist.snapshot()["corrupt"]
            i0 = integrity.stats["failures"]
            u0 = integrity.stats["unstamped_evictions"]
            if corruption == "flip":
                faults.configure("aot:blob:flip:bytes=2")
            else:
                for p in blobs:
                    (_unstamp if corruption == "unstamped"
                     else _bad_payload)(p)
            out = np.asarray(rt.array(base) * 5.0 - 2.0)  # must NOT raise
            np.testing.assert_array_equal(out, base * 5.0 - 2.0)
            snap = persist.snapshot()
            assert snap["corrupt"] >= c0 + 1, snap
            assert integrity.stats["failures"] >= i0 + 1
            assert registry.get("compile.persist_corrupt") >= 1
            assert any(not os.path.exists(p) for p in blobs)  # evicted
            ev = events.last(8, type="integrity")
            assert any(e["site"] == "aot:blob" for e in ev), ev
            if corruption == "unstamped":
                assert integrity.stats["unstamped_evictions"] >= u0 + 1
            if corruption == "deserialize":
                assert any(e["reason"] == "deserialize" for e in ev), ev


# ---------------------------------------------------------------------------
# seam: checkpoint leaves + sidecar (checkpoint:leaf)  [satellite: leaf
# clobber must raise CheckpointCorruptError]
# ---------------------------------------------------------------------------


def _ckpt_tree():
    return {"w": rt.arange(64).reshape(8, 8) * 1.5, "b": rt.arange(8) * 0.25}


def _sidecar_doc(path):
    from ramba_tpu import checkpoint

    with open(checkpoint.digests_path(path), "rb") as f:
        raw = f.read()
    payload = integrity.unwrap(raw, checkpoint._DIGESTS_SCHEMA,
                               site="test", record=False)
    return json.loads(payload.decode())


class TestCheckpointIntegrity:
    def test_save_writes_sidecar_restore_verifies(self, tmp_path):
        pytest.importorskip("orbax.checkpoint")
        from ramba_tpu import checkpoint

        p = str(tmp_path / "ck")
        checkpoint.save(p, _ckpt_tree())
        assert os.path.exists(checkpoint.digests_path(p))
        doc = _sidecar_doc(p)
        assert doc["files"], doc
        v0 = integrity.stats["verified"]
        back = checkpoint.restore(p)
        np.testing.assert_allclose(np.asarray(back["w"]),
                                   np.arange(64).reshape(8, 8) * 1.5)
        assert integrity.stats["verified"] > v0

    def test_clobbered_leaf_file_raises(self, tmp_path):
        # satellite: physical same-length corruption of a leaf data file
        pytest.importorskip("orbax.checkpoint")
        from ramba_tpu import checkpoint

        p = str(tmp_path / "ck")
        checkpoint.save(p, _ckpt_tree())
        doc = _sidecar_doc(p)
        rel = max(doc["files"], key=lambda r: doc["files"][r]["size"])
        full = os.path.join(os.path.abspath(p), rel)
        size = os.path.getsize(full)
        with open(full, "wb") as f:
            f.write(b"\x5a" * size)              # same length, wrong bytes
        i0 = integrity.stats["failures"]
        with pytest.raises(checkpoint.CheckpointCorruptError):
            checkpoint.restore(p)
        assert integrity.stats["failures"] > i0
        ev = events.last(6, type="integrity")
        assert any(e["site"] == "checkpoint:leaf" for e in ev), ev

    def test_flip_seam_detected_then_found_by_fsck(self, tmp_path):
        pytest.importorskip("orbax.checkpoint")
        from ramba_tpu import checkpoint

        p = str(tmp_path / "ck")
        checkpoint.save(p, _ckpt_tree())
        faults.configure("checkpoint:leaf:flip:bytes=2")
        with pytest.raises(checkpoint.CheckpointCorruptError):
            checkpoint.restore(p)
        # the flip persisted on disk: the offline scrubber finds it with
        # no faults armed and no runtime state
        faults.configure(None)
        r = ramba_fsck.scan(checkpoints=[p])
        assert r["status"] == ramba_fsck.EXIT_CORRUPT, r
        assert r["corrupt"] >= 1

    def test_legacy_checkpoint_without_sidecar_restores(self, tmp_path):
        pytest.importorskip("orbax.checkpoint")
        from ramba_tpu import checkpoint

        p = str(tmp_path / "ck")
        checkpoint.save(p, _ckpt_tree())
        os.remove(checkpoint.digests_path(p))
        back = checkpoint.restore(p)          # unverified but served
        np.testing.assert_allclose(np.asarray(back["b"]),
                                   np.arange(8) * 0.25)

    def test_corrupt_sidecar_raises(self, tmp_path):
        pytest.importorskip("orbax.checkpoint")
        from ramba_tpu import checkpoint

        p = str(tmp_path / "ck")
        checkpoint.save(p, _ckpt_tree())
        sp = checkpoint.digests_path(p)
        raw = bytearray(open(sp, "rb").read())
        raw[len(raw) // 2] ^= 0xFF
        open(sp, "wb").write(bytes(raw))
        with pytest.raises(checkpoint.CheckpointCorruptError):
            checkpoint.restore(p)

    def test_disabled_plane_writes_no_sidecar(self, tmp_path, monkeypatch):
        pytest.importorskip("orbax.checkpoint")
        from ramba_tpu import checkpoint

        monkeypatch.setenv("RAMBA_INTEGRITY", "0")
        p = str(tmp_path / "ck")
        checkpoint.save(p, _ckpt_tree())
        assert not os.path.exists(checkpoint.digests_path(p))
        back = checkpoint.restore(p)
        np.testing.assert_allclose(np.asarray(back["b"]),
                                   np.arange(8) * 0.25)


class TestElasticManifestDigest:
    def test_tampered_manifest_raises(self, tmp_path):
        pytest.importorskip("orbax.checkpoint")
        from ramba_tpu.checkpoint import CheckpointCorruptError
        from ramba_tpu.resilience import elastic

        mgr = elastic.CheckpointManager(str(tmp_path / "mgr"))
        mgr.register("s", {"x": rt.arange(6) * 1.0})
        mgr.save(1)
        man = mgr.manifest(1)
        assert man.get("digest")              # stamped at publish
        with open(mgr.manifest_path(1)) as f:
            doc = json.load(f)
        doc["x64"] = not doc["x64"]           # field tamper, digest kept
        with open(mgr.manifest_path(1), "w") as f:
            json.dump(doc, f)
        with pytest.raises(CheckpointCorruptError, match="digest"):
            mgr.manifest(1)


# ---------------------------------------------------------------------------
# shadow recompute audits (audit:shadow)
# ---------------------------------------------------------------------------


def _audited_flush(scale):
    a = rt.fromarray(np.arange(512.0) / 100.0)
    b = rt.fromarray(np.arange(512.0) * 0.5 + 1.0)
    return float(rt.sum((a + b) * scale))


class TestShadowAudit:
    def test_clean_flush_audits_without_mismatch(self, monkeypatch):
        monkeypatch.setenv("RAMBA_MEMO", "1")
        monkeypatch.setenv("RAMBA_AUDIT", "1")
        memo.reset()
        expect = float(np.sum((np.arange(512.0) / 100.0
                               + np.arange(512.0) * 0.5 + 1.0) * 2.0))
        got = _audited_flush(2.0)
        assert got == pytest.approx(expect, rel=1e-12)
        snap = integrity.snapshot()
        assert snap["audits"] >= 1, snap
        assert snap["audit_mismatches"] == 0, snap
        assert snap["audit_errors"] == 0, snap

    def test_flipped_shadow_flags_mismatch_serves_primary(self,
                                                          monkeypatch):
        monkeypatch.setenv("RAMBA_MEMO", "1")
        monkeypatch.setenv("RAMBA_AUDIT", "1")
        memo.reset()
        faults.configure("audit:shadow:flip:bytes=4")
        expect = float(np.sum((np.arange(512.0) / 100.0
                               + np.arange(512.0) * 0.5 + 1.0) * 3.0))
        got = _audited_flush(3.0)          # primary result must be served
        assert got == pytest.approx(expect, rel=1e-12)
        snap = integrity.snapshot()
        assert snap["audits"] >= 1, snap
        assert snap["audit_mismatches"] >= 1, snap
        assert snap["audit_errors"] == 0, snap
        # a flush whose audit disagreed must not seed the memo cache
        assert memo.cache.snapshot()["entries"] == 0
        ev = events.last(8, type="integrity")
        assert any(e["site"] == "audit:shadow" for e in ev), ev

    def test_audit_on_off_byte_identity(self, monkeypatch):
        """Fuzz leg: a seeded op chain produces byte-identical results
        with audits off and with every eligible flush audited."""
        monkeypatch.setenv("RAMBA_MEMO", "1")

        def _chain():
            rng = np.random.default_rng(1234)
            outs = []
            a = rt.fromarray(rng.standard_normal(256))
            b = rt.fromarray(rng.standard_normal(256))
            for _ in range(4):
                k = float(rng.uniform(0.5, 2.0))
                c = (a * k + b) - 0.25
                outs.append(np.asarray(c).tobytes())
                outs.append(np.asarray(rt.sum(c * c)).tobytes())
            return outs

        monkeypatch.delenv("RAMBA_AUDIT", raising=False)
        memo.reset()
        baseline = _chain()
        fuser.flush()
        memo.reset()
        integrity.reset()
        monkeypatch.setenv("RAMBA_AUDIT", "1")
        audited = _chain()
        assert baseline == audited
        snap = integrity.snapshot()
        assert snap["audits"] >= 1, snap
        assert snap["audit_mismatches"] == 0, snap


# ---------------------------------------------------------------------------
# suspect quarantine
# ---------------------------------------------------------------------------


class TestSuspectQuarantine:
    def test_threshold_trips_suspect(self, monkeypatch):
        monkeypatch.setenv("RAMBA_INTEGRITY_THRESHOLD", "2")
        registry.gauge("integrity.suspect", 0)
        f0 = registry.get("integrity.failures")
        assert not integrity.suspect()
        integrity.failure("aot:blob", "digest", detail="t1")
        assert integrity.failure_count() == 1
        assert not integrity.suspect()
        assert events.last(1, type="integrity")[-1]["suspect"] is False
        assert registry.get("integrity.suspect") == 0
        integrity.failure("aot:blob", "digest", detail="t2")
        assert integrity.suspect()
        ev = events.last(1, type="integrity")[-1]
        assert ev["suspect"] is True and ev["failures_in_window"] == 2
        assert registry.get("integrity.suspect") == 1
        assert registry.get("integrity.failures") == f0 + 2
        assert integrity.snapshot()["suspect"] is True

    def test_window_expires_strikes(self, monkeypatch):
        monkeypatch.setenv("RAMBA_INTEGRITY_THRESHOLD", "1")
        integrity.failure("memo:blob", "digest", detail="old")
        now = __import__("time").time()
        assert integrity.suspect(now)
        assert not integrity.suspect(now + integrity.suspect_window_s() + 1)

    def test_integrity_is_a_flight_trigger(self):
        assert "integrity" in telemetry.FLIGHT_TRIGGERS

    def test_diagnostics_surface(self):
        integrity.failure("memo:blob", "digest", detail="probe")
        rep = diagnostics.integrity_report()
        assert rep["failures"] >= 1
        snap = diagnostics.snapshot()
        assert snap.get("integrity", {}).get("failures", 0) >= 1


# ---------------------------------------------------------------------------
# ramba-fsck (offline scrub)
# ---------------------------------------------------------------------------


class TestFsck:
    def test_empty_tier_is_exit_empty(self, tmp_path):
        r = ramba_fsck.scan(cache=str(tmp_path))
        assert r["status"] == ramba_fsck.EXIT_EMPTY and r["scanned"] == 0

    def test_detect_repair_quarantine_rescan(self, tmp_path):
        root = str(tmp_path / "cache")
        with _CacheDir(root):
            _seed_cache(root, np.arange(16, dtype=np.float32))
        r = ramba_fsck.scan(cache=root)
        assert r["status"] == ramba_fsck.EXIT_CLEAN and r["scanned"] >= 2
        aot_dir = os.path.join(root, "aot")
        victim = os.path.join(aot_dir, sorted(os.listdir(aot_dir))[0])
        raw = bytearray(open(victim, "rb").read())
        raw[len(raw) // 2] ^= 0xFF
        open(victim, "wb").write(bytes(raw))
        r = ramba_fsck.scan(cache=root)
        assert r["status"] == ramba_fsck.EXIT_CORRUPT and r["corrupt"] == 1
        r = ramba_fsck.scan(cache=root, repair=True)
        assert r["status"] == ramba_fsck.EXIT_CORRUPT
        qdir = os.path.join(root, "quarantine")
        assert os.path.isdir(qdir)
        assert not os.path.exists(victim)     # moved, not deleted
        r = ramba_fsck.scan(cache=root)
        assert r["status"] == ramba_fsck.EXIT_CLEAN, r

    def test_cli_exit_codes(self, tmp_path):
        root = str(tmp_path / "cache")
        with _CacheDir(root):
            _seed_cache(root, np.arange(8, dtype=np.float32))
        assert ramba_fsck.main(["--cache", root]) == ramba_fsck.EXIT_CLEAN
        assert ramba_fsck.main(["--cache", str(tmp_path / "nope")]) \
            == ramba_fsck.EXIT_EMPTY
