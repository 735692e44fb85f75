#!/usr/bin/env python
"""Offline roofline analysis of a perf capture (`ramba-roofline`).

Takes one capture — ``RAMBA_PERF=1 python bench.py`` stdout, a
``diagnostics.dump()`` snapshot, or a raw ``perf_report()`` dump — and
reports, per compiled kernel, how close it ran to the hardware's peak
and which ceiling (HBM bandwidth or compute) it sits under::

    RAMBA_PERF=sync python bench.py > new.json
    python scripts/roofline_report.py new.json
    python scripts/roofline_report.py new.json --peaks peaks.json --json

Device time per kernel prefers the capture's synchronized window
(``sync`` p50, RAMBA_PERF=sync) and falls back to dispatch-time p50 —
flagged ``dispatch`` in the output, an upper bound on device time under
async dispatch.  The peak table resolves, in order: ``--peaks`` (inline
JSON or a file path), the peak table recorded in the capture itself
(bench.py stamps ``peaks`` + ``device_kind``), then the builtin
per-device_kind table in ramba_tpu/observe/attrib.py.

Exit status: 0 report printed; 2 usage/input error (no kernels, no
flops/bytes — run the capture with RAMBA_PERF=1).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from ramba_tpu.observe import attrib  # noqa: E402
from scripts.perf_diff import load_capture  # noqa: E402


def _capture_extras(path: str) -> dict:
    """device_kind / peaks recorded in the capture (bench.py stamps
    them); empty when absent."""
    try:
        with open(path) as f:
            text = f.read()
        try:
            obj = json.loads(text)
        except json.JSONDecodeError:
            obj = None
            for line in reversed(text.splitlines()):
                line = line.strip()
                if line.startswith("{"):
                    try:
                        obj = json.loads(line)
                        break
                    except json.JSONDecodeError:
                        continue
        if not isinstance(obj, dict):
            return {}
        return {k: obj[k] for k in ("device_kind", "peaks") if k in obj}
    except OSError:
        return {}


def _resolve_peaks(args_peaks, extras: dict) -> dict:
    if args_peaks:
        text = args_peaks
        if not args_peaks.lstrip().startswith("{"):
            with open(args_peaks) as f:
                text = f.read()
        obj = json.loads(text)
        if not isinstance(obj, dict):
            raise ValueError("--peaks must be a JSON object")
        # either a bare {"peak_gbps", "peak_tflops"} entry or a
        # per-device_kind table like RAMBA_PEAKS_JSON
        if "peak_gbps" in obj or "peak_tflops" in obj:
            return {"peak_gbps": float(obj.get("peak_gbps") or 0.0),
                    "peak_tflops": float(obj.get("peak_tflops") or 0.0),
                    "source": "--peaks",
                    "device_kind": extras.get("device_kind")}
        kind = extras.get("device_kind")
        low = (kind or "").lower()
        for key, entry in obj.items():
            if key.lower() in low:
                return {"peak_gbps": float(entry.get("peak_gbps") or 0.0),
                        "peak_tflops": float(entry.get("peak_tflops") or 0.0),
                        "source": f"--peaks:{key}",
                        "device_kind": kind}
        raise ValueError(
            f"--peaks has no entry matching device_kind {kind!r}")
    rec = extras.get("peaks")
    if isinstance(rec, dict) and (rec.get("peak_gbps")
                                  or rec.get("peak_tflops")):
        return {"peak_gbps": float(rec.get("peak_gbps") or 0.0),
                "peak_tflops": float(rec.get("peak_tflops") or 0.0),
                "source": "capture", "device_kind": extras.get("device_kind")}
    peaks = attrib.peak_table(extras.get("device_kind"))
    if peaks is None:
        raise ValueError(
            f"no peaks known for device_kind "
            f"{extras.get('device_kind')!r}: pass --peaks")
    return peaks


def _device_seconds(entry: dict) -> tuple:
    """(seconds, source) for one capture kernel entry."""
    sync = (entry.get("sync") or {}).get("p50_s")
    if sync:
        return float(sync), "sync"
    ex = entry.get("exec") or {}
    p50 = ex.get("p50_s")
    if p50:
        return float(p50), "dispatch"
    count, total = ex.get("count"), ex.get("total_s")
    if count and total:
        return float(total) / int(count), "dispatch"
    return 0.0, "none"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="per-kernel roofline report from a perf capture"
    )
    ap.add_argument("capture", help="bench JSON / perf dump")
    ap.add_argument("--peaks", help="peak table override: inline JSON or "
                    "a file path (bare entry or per-device_kind table)")
    ap.add_argument("--top", type=int, default=20,
                    help="show at most N kernels (default 20)")
    ap.add_argument("--json", action="store_true",
                    help="emit the report as one JSON object")
    args = ap.parse_args(argv)
    try:
        cap = load_capture(args.capture)
        extras = _capture_extras(args.capture)
        peaks = _resolve_peaks(args.peaks, extras)
    except (OSError, ValueError) as e:
        print(f"roofline_report: {e}", file=sys.stderr)
        return 2
    rows = []
    skipped = 0
    for fp, k in cap["kernels"].items():
        flops = float(k.get("flops") or 0.0)
        by = float(k.get("bytes_accessed") or 0.0)
        dev_s, src = _device_seconds(k)
        row = attrib.classify(flops, by, dev_s, peaks)
        if row is None:
            skipped += 1
            continue
        row["fingerprint"] = fp
        row["label"] = k.get("label", "?")
        row["device_p50_s"] = round(dev_s, 6)
        row["device_time_source"] = src
        rows.append(row)
    if not rows:
        print(f"roofline_report: {args.capture}: no kernel has "
              "flops/bytes + a time window (run with RAMBA_PERF=1, "
              "ideally RAMBA_PERF=sync)", file=sys.stderr)
        return 2
    rows.sort(key=lambda r: r["frac_of_peak"], reverse=True)
    shown = rows[:args.top]
    if args.json:
        print(json.dumps({
            "capture": args.capture,
            "device_kind": peaks.get("device_kind"),
            "peaks": {"peak_gbps": peaks["peak_gbps"],
                      "peak_tflops": peaks["peak_tflops"],
                      "source": peaks["source"]},
            "kernels": shown,
            "skipped": skipped,
        }, indent=1))
        return 0
    print(f"roofline_report: {args.capture}: "
          f"device_kind={peaks.get('device_kind') or '?'} "
          f"peaks={peaks['peak_gbps']:g} GB/s / "
          f"{peaks['peak_tflops']:g} TFLOPs ({peaks['source']})")
    print(f"  {len(rows)} kernel(s), {skipped} skipped "
          "(no cost model or no time window)")
    for r in shown:
        line = (f"  {r['fingerprint']} {r['label']:<18s}"
                f" {r['bound']:<9s} peak={r['frac_of_peak']:.2%}"
                f" bw={r['achieved_gb_per_s']:g}GB/s"
                f" fl={r['achieved_tflops']:g}TFLOPs"
                f" dev={r['device_p50_s']:.6f}s"
                f" ({r['device_time_source']})")
        if "intensity" in r:
            line += f" oi={r['intensity']:g} ridge={r['ridge']:g}"
        print(line)
    if any(r["device_time_source"] == "dispatch" for r in shown):
        print("  note: 'dispatch' rows time host dispatch, not the "
              "device — recapture with RAMBA_PERF=sync for true "
              "device windows")
    return 0


if __name__ == "__main__":
    sys.exit(main())
