"""Kind ``serve_closed``: a closed loop of ``clients_per_slot * max_batch``
clients, each sending its next request when its last stream ends, so the
engine's admission queue is never empty (offline generation, rollout
callers). Clients start in set-up; the window opens once as many streams
as the engine has slots have had a first token. Tokens and the gaps
between chunks are timed at the client."""

from __future__ import annotations

import threading
import time

from benchmark.loading import sibling

common = sibling(__file__, "serve_common.py")
TOLERANCES = common.TOLERANCES


def run(run) -> None:
    engine = run.params["engine"]
    n_clients = run.traffic["clients_per_slot"] * engine["max_batch"]
    threads, stop = [], threading.Event()
    handle = None
    try:
        handle = common.start_engine(run)
        requests = common.make_requests(run, run.traffic["pool_requests"])
        records, lock, taken = [], threading.Lock(), [0]
        run.raw["requests"] = records

        def client(k: int):
            # Client k sends requests k, k + n_clients, ...: whichever
            # thread runs first, each client's own sequence is fixed.
            for req in requests[k::n_clients]:
                if stop.is_set():
                    return
                with lock:
                    taken[0] += 1
                rec = common.stream_request(run, handle, req)
                with lock:
                    records.append(rec)

        threads.extend(threading.Thread(target=client, args=(i,),
                                        daemon=True,
                                        name=f"bench-client-{i}")
                       for i in range(n_clients))
        for t in threads:
            t.start()
        deadline = time.perf_counter() + 600
        while common.stats_now(handle)["admitted"] < 1 + engine["max_batch"]:
            if time.perf_counter() > deadline:
                raise RuntimeError("the engine's slots never filled")
            time.sleep(0.01)

        run.counters["open"] = common.stats_now(handle)
        run.open_window()
        common.trace_middle(run, handle)
        run.counters["close"] = common.stats_now(handle)
        run.close_window()

        run.check("request_pool_lasted",
                  taken[0] < len(requests) - 2 * n_clients,
                  f"all {len(requests)} requests of the pool were taken "
                  f"before the window closed")
        stop.set()
        run.raw["abandon"] = True  # streams in flight are not waited for
        lo, hi = run.window_ns
        with lock:
            terminal = [r for r in records
                        if lo <= r["done_ns"] <= hi
                        or (r["error"] and "abandoned" not in r["error"])]
        common.finish(run, handle, terminal, shed_allowed=False)
    finally:
        stop.set()
        run.raw["abandon"] = True
        common.stop_engine(run, handle, threads)
