"""Observer-tax ledger: the observability plane meters itself.

Every measurement path in ``observe/`` costs wall time that the flush it
measures must pay — the device fence serializes dispatch, event emits
serialize on a lock and (with ``RAMBA_TRACE``) buffer a JSONL line,
telemetry renders walk every store.  This module is the plane's own
bill: each observability code path self-accounts its wall seconds into a
per-component ledger, exported as ``ramba_observer_seconds_total
{component}`` plus a single ``observer_tax_frac`` — observer seconds
over total attributed flush wall.

Components (what each window covers):

* ``events``    — one ``events.emit``: stamp + ring append + JSONL
                  serialize/enqueue + the writer drain attempt.
* ``fence``     — ``block_until_ready`` wall beyond the dispatch tail
                  (the device time attribution pays to observe).
* ``ledger``    — kernel-ledger bookkeeping (``record_execute``,
                  ``record_flush_wall``; the ``compile`` event's emit
                  self-accounts under ``events``).
* ``telemetry`` — one Prometheus ``render()``.
* ``flight``    — one flight-recorder dump.

Windows may nest (an emit inside a dump bills both components), so
the total is a slight over-count: the bound errs against us, never
for us.

Import-light by design: stdlib only at module scope, so every other
observe/ module (including events.py at the bottom of the import DAG)
can bill itself without a cycle.
"""

from __future__ import annotations

import threading
import time
from contextlib import contextmanager
from typing import Optional

_lock = threading.Lock()

# component -> [total_seconds, count]
_tax: "dict[str, list]" = {}


def add(component: str, seconds: float) -> None:
    """Bill ``seconds`` of observer wall time to ``component``."""
    if seconds < 0:
        return
    with _lock:
        ent = _tax.get(component)
        if ent is None:
            ent = _tax[component] = [0.0, 0]
        ent[0] += seconds
        ent[1] += 1


@contextmanager
def taxed(component: str):
    """Scope whose wall time bills to ``component`` (even on error —
    a failing observer still spent the time)."""
    t0 = time.perf_counter()
    try:
        yield
    finally:
        add(component, time.perf_counter() - t0)


def total_s() -> float:
    with _lock:
        return sum(ent[0] for ent in _tax.values())


def tax_frac() -> Optional[float]:
    """Observer seconds / attributed flush wall (stages + residual), or
    None before any flush has been attributed.  The denominator is the
    work being observed, so the frac reads as "cents on the dollar"."""
    from ramba_tpu.observe import attrib as _attrib

    denom = _attrib.flush_wall_total()
    if denom <= 0:
        return None
    return round(total_s() / denom, 6)


def snapshot() -> dict:
    """JSON-serializable ledger dump (diagnostics ``observer`` section)."""
    with _lock:
        comps = {k: {"seconds": round(v[0], 6), "count": v[1]}
                 for k, v in sorted(_tax.items())}
        total = sum(ent[0] for ent in _tax.values())
    out = {"components": comps, "total_s": round(total, 6)}
    frac = tax_frac()
    if frac is not None:
        out["tax_frac"] = frac
    return out


def reset() -> None:
    with _lock:
        _tax.clear()
