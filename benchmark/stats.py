"""Statistics of the harness: medians and percentiles over the whole
window, unrounded.  No cell's name is read here."""

from __future__ import annotations

import math


def median(xs):
    xs = sorted(xs)
    n = len(xs)
    if not n:
        return None
    mid = n // 2
    return xs[mid] if n % 2 else 0.5 * (xs[mid - 1] + xs[mid])


def percentile(xs, q):
    """Nearest-rank percentile: the smallest value with at least q % of
    the samples at or below it."""
    xs = sorted(xs)
    if not xs:
        return None
    return xs[max(0, math.ceil(q / 100.0 * len(xs)) - 1)]
