"""From a profiler trace (``*.xplane.pb``) to numbers.

What a trace of the chip holds (looked at by hand, PR 22): a plane
``/device:TPU:<i>`` per chip with the lines ``XLA Modules`` (one event
per executable run) and ``XLA Ops`` (one event per HLO op, named by its
whole HLO text, in sequence on the core); and a plane ``/host:CPU`` with
a line per thread, of which the interpreter's carries the Python tracer's
frames (``$file.py:line func``) and every ``TraceAnnotation``.  All share
one clock, in nanoseconds.

The traced stretch is the span from the first to the last annotation of
the name the harness puts round each solve.  Inside it:

busy      union of the core's op intervals (``XLA Ops`` is sequential: an
          op's time includes what it waits for), averaged over the chips
gaps      the complement, each gap given to the deepest host frame open
          for at least half of it and to that frame's children inside it
classes   device seconds by class of compute op: the program module's
          regular expressions over ``<kind> <label>``
collective  the collective ops' own time on the core.  The line is
          sequential, so nothing else runs on the core meanwhile: a
          ``-done`` op lasts as long as the core waits for the transfer,
          and one that finished under a kernel costs microseconds.  (The
          time in flight, ``-start`` to ``-done``, says nothing end to
          end: it moves with the order XLA issues the ops in.)

Interval arithmetic is on plain (start, end) tuples so that the tests can
feed it by hand.
"""

from __future__ import annotations

import re

OP_LINE = "XLA Ops"
COLLECTIVE = re.compile(
    r"^(collective-permute|all-reduce|all-gather|all-to-all|reduce-scatter"
    r"|collective-broadcast|send|recv)(-start|-done)?$")
_KIND = re.compile(r"(?:^|[\s)])([a-z][a-z0-9-]*)\(")
_SUFFIX = re.compile(r"[.\d]+$")
#: entries of ``device_ops`` and ``idle_gaps``
TOP = 10
#: a chip's gaps are named longest first until this share of its idle time
#: has a name, and at most this many of them: a pass over a million Python
#: frames for each microsecond gap between two ops buys nothing
NAME_SHARE, NAME_GAPS = 0.99, 512


def op_label(hlo):
    """``%run.17 = f32[..] custom-call(..)`` -> (``custom-call``,
    ``run [custom-call]``): the op's kind, and a label that adds up over
    the numbered copies of one op."""
    name, _, rest = hlo.partition(" = ")
    m = _KIND.search(rest)
    kind = m.group(1) if m else "?"
    base = _SUFFIX.sub("", name.lstrip("%")) or name
    return kind, f"{base} [{kind}]"


def union(intervals):
    """Sorted, disjoint union of (start, end) intervals."""
    out = []
    for a, b in sorted(i for i in intervals if i[1] > i[0]):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def length(intervals):
    return sum(b - a for a, b in intervals)


def clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def complement(disjoint, lo, hi):
    """Gaps of a sorted disjoint union inside [lo, hi)."""
    gaps, at = [], lo
    for a, b in disjoint:
        if a > at:
            gaps.append((at, a))
        at = max(at, b)
    if hi > at:
        gaps.append((at, hi))
    return gaps


class Frames:
    """The host line's frames, (start, end, name), as arrays: naming a
    gap is a few vectorised passes over them."""

    def __init__(self, frames):
        import numpy

        frames = sorted(frames, key=lambda f: (f[0], -f[1]))
        self.a = numpy.array([f[0] for f in frames], dtype=numpy.float64)
        self.b = numpy.array([f[1] for f in frames], dtype=numpy.float64)
        self.names = [f[2] for f in frames]

    def split(self, gap):
        """Whom a gap belongs to: {label: nanoseconds}.  The deepest
        (shortest) frame F open for at least half of the gap, and under
        it the outermost frames that lie inside both, summed by name as
        ``F > child``; what they do not cover stays with ``F``."""
        import numpy

        g0, g1 = gap
        if not self.names:
            return {"(no host frame)": g1 - g0}
        over = numpy.minimum(self.b, g1) - numpy.maximum(self.a, g0)
        ok = over * 2 >= (g1 - g0)
        if not ok.any():
            return {"(no host frame)": g1 - g0}
        i = int(numpy.argmin(numpy.where(ok, self.b - self.a, numpy.inf)))
        top, lo, hi = self.names[i], max(g0, self.a[i]), min(g1, self.b[i])
        inside = numpy.nonzero((self.a >= lo) & (self.b <= hi)
                               & (self.b > self.a))[0]
        out, end = {}, lo
        for j in inside:  # sorted by start, longest first: outermost win
            if j != i and self.a[j] >= end:
                label = f"{top} > {self.names[j]}"
                out[label] = out.get(label, 0.0) + self.b[j] - self.a[j]
                end = self.b[j]
        out[top] = (g1 - g0) - sum(out.values())
        return out


def reduce_events(device_ops, frames, annotation, classes):
    """``device_ops``: {device: [(start, end, hlo_text)]}; ``frames``:
    [(start, end, name)] of the host line; ``classes``: {class: regex}.
    Times in nanoseconds in, seconds out.  None when the annotation or
    the device ops are missing: there is nothing to read."""
    marks = [(a, b) for a, b, n in frames if n == annotation]
    device_ops = {d: ops for d, ops in device_ops.items() if ops}
    if not marks or not device_ops:
        return None
    lo, hi = min(a for a, _ in marks), max(b for _, b in marks)
    solves = union(marks)
    ndev = len(device_ops)
    pats = {c: re.compile(p) for c, p in classes.items()}
    index = Frames([f for f in frames if f[2] != annotation])
    busy = compute_s = coll_s = 0.0
    class_s = {c: 0.0 for c in pats}
    by_label, gap_s = {}, {}
    for ops in device_ops.values():
        compute, coll = [], []
        for a, b, hlo in sorted(ops):
            a, b = max(a, lo), min(b, hi)
            if b <= a:
                continue
            kind, label = op_label(hlo)
            by_label[label] = by_label.get(label, 0.0) + (b - a)
            if COLLECTIVE.match(kind):
                coll.append((a, b))
                continue
            compute.append((a, b))
            for c, pat in pats.items():
                if pat.search(f"{kind} {label}"):
                    class_s[c] += b - a
                    break
        busy_u = union(compute + coll)
        busy += length(busy_u)
        compute_s += length(union(compute))
        coll_s += length(union(coll))
        gaps = sorted(complement(busy_u, lo, hi),
                      key=lambda g: g[0] - g[1])
        idle, named = length(gaps), 0.0
        for i, g in enumerate(gaps):
            if i >= NAME_GAPS or named >= NAME_SHARE * idle:
                split = {"(shorter gaps, not named)": g[1] - g[0]}
            elif length(clip(solves, *g)) * 2 < g[1] - g[0]:
                split = {"(between solves)": g[1] - g[0]}
            else:
                split = index.split(g)
            named += g[1] - g[0]
            for name, ns in split.items():
                gap_s[name] = gap_s.get(name, 0.0) + ns

    def ranked(d):
        return [[k, v / ndev / 1e9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]

    return {"window_s": (hi - lo) / 1e9, "solves": len(marks),
            "devices": ndev, "busy_s": busy / ndev / 1e9,
            "compute_s": compute_s / ndev / 1e9,
            "collective_s": coll_s / ndev / 1e9,
            "class_s": {c: v / ndev / 1e9 for c, v in class_s.items()},
            "device_ops": ranked(by_label), "idle_gaps": ranked(gap_s)}


def read_xplane(data, annotation):
    """(device_ops, frames) of a ``ProfileData``.  The host line is the
    one that carries the annotation: it is named after the thread, which
    is named after the interpreter (``python``, ``python3``)."""
    device_ops, frames = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = device_ops.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == OP_LINE:
                    ops.extend((e.start_ns, e.start_ns + e.duration_ns,
                                e.name) for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                events = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                          for e in line.events]
                if any(name == annotation for _, _, name in events):
                    frames.extend(events)
    return device_ops, frames


def read_file(path, annotation):
    from jax.profiler import ProfileData

    return read_xplane(ProfileData.from_file(path), annotation)
