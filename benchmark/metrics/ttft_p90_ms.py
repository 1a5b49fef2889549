"""90th percentile of the time from when a request was DUE (not sent) to
its first chunk at the client, over every request due inside the window.
A request that failed counts as having waited the whole window."""

from benchmark import stats


def measured(run) -> list:
    lo, hi = run.window_ns
    return [r for r in run.raw["requests"]
            if r["due_ns"] is not None and lo <= r["due_ns"] <= hi]


def read(run):
    if "requests" not in run.raw or run.window_ns is None:
        return None
    lo, hi = run.window_ns
    waits = [(r["first_ns"] - r["due_ns"]) * 1e-6
             if r["first_ns"] is not None else (hi - lo) * 1e-6
             for r in measured(run)]
    return stats.percentile(waits, 90)
