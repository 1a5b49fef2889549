"""Share of the window the loop spent inside ``next()`` of its input
iterator (host span ``bench.next_batch``: slicing the pool and starting
the host-to-device copy)."""


def read(run):
    if run.window_ns is None:
        return None
    lo, hi = run.window_ns
    spent = sum(e - s for name, s, e, _ in run.spans
                if name == "bench.next_batch" and s >= lo and e <= hi)
    return 100.0 * spent / (hi - lo)
