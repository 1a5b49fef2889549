"""Kind ``serve_open``: an open loop. Requests arrive by a Poisson process
at the rate the traffic file fixes, whatever the system does; each is timed
from when it was DUE, so a stall is charged to every request it delays, and
how late the generator itself ran is reported beside it.

The gaps between arrivals are one fixed sequence (exponential, from the
traffic's ``sizes_seed``), the same for every ``--seed``, like the sizes. Arrivals start
``lead_seconds`` before the window opens, so the window sees a system that
is already serving; requests due inside the window are the measured ones,
and the run waits for them to finish after it closes."""

from __future__ import annotations

import queue
import threading
import time

from benchmark.loading import sibling

common = sibling(__file__, "serve_common.py")
TOLERANCES = common.TOLERANCES


def arrival_offsets(traffic: dict, n: int):
    """Seconds from the start of arrivals to each of ``n`` arrivals."""
    import numpy as np

    gaps = np.random.default_rng(traffic["sizes_seed"] + 1).exponential(
        1.0 / traffic["rate_per_s"], n)
    return np.cumsum(gaps)


def run(run) -> None:
    traffic = run.traffic
    lead = traffic["lead_seconds"]
    n = int(traffic["rate_per_s"] * (run.seconds + lead) * 1.5) + 32
    workers, stop = [], threading.Event()
    handle = None
    try:
        handle = common.start_engine(run)
        requests = common.make_requests(run, n)
        offsets = arrival_offsets(traffic, n)
        records, lock = [], threading.Lock()
        run.raw["requests"] = records
        todo: queue.Queue = queue.Queue()

        def worker():
            while True:
                item = todo.get()
                if item is None:
                    return
                req, due_ns = item
                rec = common.stream_request(run, handle, req, due_ns)
                with lock:
                    records.append(rec)

        workers.extend(threading.Thread(target=worker, daemon=True,
                                        name=f"bench-worker-{i}")
                       for i in range(traffic["client_threads"]))
        for t in workers:
            t.start()
        sent = [0]

        def arrivals(t0_ns: int):
            for req, off in zip(requests, offsets):
                due_ns = t0_ns + int(off * 1e9)
                wait = (due_ns - time.perf_counter_ns()) * 1e-9
                if wait > 0 and stop.wait(wait):
                    return
                if stop.is_set():
                    return
                todo.put((req, due_ns))
                sent[0] += 1

        t0_ns = time.perf_counter_ns()
        gen = threading.Thread(target=arrivals, args=(t0_ns,), daemon=True,
                               name="bench-arrivals")
        gen.start()
        time.sleep(lead)
        run.counters["open"] = common.stats_now(handle)
        run.open_window()
        common.trace_middle(run, handle)
        run.counters["close"] = common.stats_now(handle)
        run.close_window()
        stop.set()
        gen.join(timeout=30)
        # Requests due inside the window finish after it; wait for them.
        lo, hi = run.window_ns
        deadline = time.perf_counter() + traffic["drain_seconds"]
        while time.perf_counter() < deadline:
            with lock:
                done = len(records)
            if done >= sent[0]:
                break
            time.sleep(0.05)
        run.raw["abandon"] = True
        for _ in workers:
            todo.put(None)
        with lock:
            terminal = [r for r in records if lo <= r["due_ns"] <= hi]
        # every measured request's wait, so that a tail can be read afterwards
        run.say("ttft_ms_by_due_order", values=[
            None if r["first_ns"] is None
            else round((r["first_ns"] - r["due_ns"]) * 1e-6, 1)
            for r in sorted(terminal, key=lambda r: r["due_ns"])])
        run.counters["close"] = {
            **common.stats_now(handle),
            "queued_at_close": run.counters["close"]["queued"],
            "active_at_close": run.counters["close"]["active"]}
        run.check("all_due_requests_ended",
                  len(records) >= sent[0],
                  f"{sent[0] - len(records)} requests still in flight "
                  f"{traffic['drain_seconds']} s after the window")
        common.finish(run, handle, terminal, shed_allowed=False)
    finally:
        stop.set()
        run.raw["abandon"] = True
        common.stop_engine(run, handle, workers)
