"""Backend-health event source: which platform a run actually executed on.

A run whose backend came up wrong must say so in the same stream as its
flush spans, not in an opaque error string:

* ``record()`` — explicit health record (``distributed.initialize`` files
  its bring-up outcome and timings here),
* ``record_mesh()`` — automatic record on the FIRST default-mesh creation
  (parallel/mesh.py), so every traced run carries at least one health line
  stating which platform actually executed.
"""

from __future__ import annotations

from typing import Optional

from ramba_tpu.observe import events, registry

_mesh_recorded = False


def record(
    platform: Optional[str] = None,
    device_count: Optional[int] = None,
    init_seconds: Optional[float] = None,
    outcome: str = "ok",
    error: Optional[str] = None,
    **extra,
) -> dict:
    """Emit one health event.  ``outcome``: "ok" | "recovered" | "error".
    Returns the emitted event dict.
    """
    ev = {"type": "health", "outcome": outcome}
    if platform is not None:
        ev["platform"] = platform
    if device_count is not None:
        ev["device_count"] = int(device_count)
    if init_seconds is not None:
        ev["init_seconds"] = round(float(init_seconds), 4)
    if error:
        ev["error"] = str(error)[-800:]
    ev.update(extra)
    registry.inc(f"health.{outcome}")
    return events.emit(ev)


def record_recovery(source: str, retries: int, **extra) -> dict:
    """A transient failure healed after ``retries`` re-attempt(s) — the
    resilience retry engine reports recoveries here so incidents that
    did NOT become hard failures still show up in the health stream."""
    return record(outcome="recovered", source=source,
                  retries=int(retries), **extra)


def record_mesh(mesh, init_seconds: float) -> None:
    """Health record for the first default mesh (one per process)."""
    global _mesh_recorded
    if _mesh_recorded:
        return
    _mesh_recorded = True
    try:
        dev = mesh.devices.flat[0]
        record(
            platform=getattr(dev, "platform", None),
            device_count=int(mesh.devices.size),
            init_seconds=init_seconds,
            outcome="ok",
            source="default_mesh",
            mesh_shape={k: int(v) for k, v in mesh.shape.items()},
        )
    except Exception:  # observability must never break bring-up
        pass
