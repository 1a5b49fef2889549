"""Tokens that reached the clients inside the window, over the window."""


def read(run):
    if "requests" not in run.raw or run.window_ns is None:
        return None
    lo, hi = run.window_ns
    n = sum(k for r in run.raw["requests"]
            for t, k in zip(r["chunk_ns"], r["chunk_tokens"])
            if lo <= t <= hi)
    return n / ((hi - lo) * 1e-9)
