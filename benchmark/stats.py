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


def spread(values) -> float | None:
    """The distance between the first and the third quartile, as
    ``statistics.quantiles(values, n=4)`` gives them, as a share of the
    median: what a bound is set from and judged by."""
    xs = list(values)
    if len(xs) < 2:
        return None
    q = statistics.quantiles(xs, n=4)
    med = statistics.median(xs)
    return (q[2] - q[0]) / med if med else None


def driver_spreads(sets) -> tuple:
    """The two spreads the driver judges a bound by, from sets of runs of
    one cell. Tightness: the mean of the sets' spreads, each set without
    its run farthest from its median (a bound is too tight where this is
    over half of it). Looseness: the widest spread of the sets as they are
    (a bound over eight times this, and over 1 %, is too loose)."""
    trimmed = []
    for runs in sets:
        med = statistics.median(runs)
        far = max(range(len(runs)), key=lambda i: abs(runs[i] - med))
        trimmed.append(spread(runs[:far] + runs[far + 1:]))
    return sum(trimmed) / len(trimmed), max(spread(runs) for runs in sets)


def bound_from_spreads(spreads, factor: float = 2.5, floor: float = 0.01,
                       step: float = 0.005) -> float:
    """The bound a metric gets from the spreads of its sets of runs (every
    cell that reports it, the widest deciding): ``factor`` times the widest,
    at least ``floor``, rounded up to a whole number of ``step``s. With
    2.5 (ISSUE 33's rule) every whole set's spread is under 0.4 of the
    bound; a set without its farthest run spreads about half as much, so
    the driver's tightness reading comes to about a fifth of the bound and
    its looseness reading to 2.5 times under it: room on both sides."""
    raw = max(floor, factor * max(spreads))
    return round(math.ceil(raw / step - 1e-9) * step, 6)


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
