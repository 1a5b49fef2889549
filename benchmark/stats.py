"""Small statistics the metrics share."""

from __future__ import annotations

import math
import statistics


def percentile(values, q: float) -> float | None:
    """Linear-interpolated percentile (q in 0..100); None if empty."""
    xs = sorted(values)
    if not xs:
        return None
    if len(xs) == 1:
        return float(xs[0])
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return float(xs[lo] + (xs[hi] - xs[lo]) * (pos - lo))


def median(values) -> float | None:
    xs = list(values)
    return float(statistics.median(xs)) if xs else None


def window_rate(slice_seconds, tokens_per_slice: float, chips: int
                ) -> float | None:
    """All the tokens of the window's whole slices over all the time those
    slices took, over chips: a stall inside the window lowers it."""
    total = float(sum(slice_seconds))
    if total <= 0:
        return None
    return len(slice_seconds) * tokens_per_slice / total / chips


def slice_rate(slice_seconds, tokens_per_slice: float, chips: int
               ) -> float | None:
    """Tokens in a slice over the MEDIAN slice wall time, over chips: the
    pace between hiccups. It stands beside ``window_rate`` and never
    replaces it; the distance between the two is ``stall_pct``."""
    med = median(slice_seconds)
    if not med:
        return None
    return tokens_per_slice / med / chips


def stall_pct(slice_seconds, window_s: float) -> float | None:
    """Share of the whole slices' time that the median pace does not
    explain: ``1 - slices * median / window``."""
    med = median(slice_seconds)
    if not med or window_s <= 0:
        return None
    return 100.0 * (1.0 - len(slice_seconds) * med / window_s)


def union_length(intervals) -> float:
    """Total length of the union of ``(start, end)`` intervals."""
    return float(sum(e - s for s, e in merge(intervals)))


def merge(intervals) -> list:
    """Union of intervals as a sorted list of disjoint ``(start, end)``."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def subtract_length(a, b) -> float:
    """Length of ``union(a)`` that ``union(b)`` does not cover."""
    a, b = merge(a), merge(b)
    total, j = 0.0, 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                total += b[k][0] - cur
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            total += e - cur
    return total
