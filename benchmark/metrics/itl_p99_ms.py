"""99th percentile of the gap a client sees between one chunk of its
stream and the next, over every gap that ended inside the window."""

from benchmark import stats


def read(run):
    if "requests" not in run.raw or run.window_ns is None:
        return None
    lo, hi = run.window_ns
    gaps = [(b - a) * 1e-6 for r in run.raw["requests"]
            for a, b in zip(r["chunk_ns"], r["chunk_ns"][1:])
            if lo <= b <= hi]
    return stats.percentile(gaps, 99)
