"""How late the load generator ran: 99th percentile of send time minus
due time. A starved generator must not be read as a fast server."""

from benchmark import stats


def read(run):
    if "requests" not in run.raw or run.window_ns is None:
        return None
    lo, hi = run.window_ns
    late = [(r["sent_ns"] - r["due_ns"]) * 1e-6 for r in run.raw["requests"]
            if r["due_ns"] is not None and r["sent_ns"] is not None
            and lo <= r["due_ns"] <= hi]
    return stats.percentile(late, 99)
