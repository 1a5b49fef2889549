"""Core-API microbenchmarks against the cluster backend.

Control-plane counts on a host, not chip speeds: measures the task/actor/
object hot paths the way the reference's perf suite does
(``python/ray/_private/ray_perf.py:93-236``, driven nightly by
``release/microbenchmark/run_microbenchmark.py:14-31``) — tasks/s sync and
async, 1:1 and 1:n actor calls/s, put/get ops/s and GB/s — but against a
real multi-process ``cluster_utils.Cluster`` rather than a single-node
runtime, so every number includes the scheduler RPC, borrow-registration
RPCs, and worker dispatch.

Usage:  python -m ray_tpu.scripts.microbench [--out MICROBENCH.json]
Emits one JSON object: {metric: {"value": .., "unit": ..}, ...}.
"""

from __future__ import annotations

import argparse
import json
import sys
import time


def _rate(n: int, dt: float) -> float:
    return n / dt if dt > 0 else float("inf")


def run_all(num_nodes: int = 2, cpus_per_node: int = 4) -> dict:
    import numpy as np

    import ray_tpu
    from ray_tpu.cluster.cluster_utils import Cluster

    results: dict = {}

    def record(name, value, unit):
        results[name] = {"value": round(value, 2), "unit": unit}
        print(f"{name}: {value:,.1f} {unit}", file=sys.stderr, flush=True)

    ray_tpu.shutdown()
    cluster = Cluster()
    for _ in range(num_nodes):
        cluster.add_node(num_cpus=cpus_per_node)
    cluster.wait_for_nodes()
    ray_tpu.init(cluster.address)

    try:
        @ray_tpu.remote
        def noop():
            return None

        @ray_tpu.remote
        class Counter:
            def __init__(self):
                self.n = 0

            def inc(self):
                self.n += 1
                return self.n

        # Warm every node's worker pool so measurements exclude process
        # forks (SPREAD defeats the prefer-local fast path, which would
        # otherwise keep the warmup on the driver's node).
        ray_tpu.get(
            [
                noop.options(scheduling_strategy="SPREAD").remote()
                for _ in range(2 * cpus_per_node * num_nodes)
            ],
            timeout=120,
        )

        # 1. tasks, sync: submit one, wait, repeat.
        n = 200
        t0 = time.perf_counter()
        for _ in range(n):
            ray_tpu.get(noop.remote(), timeout=30)
        record("tasks_sync_per_s", _rate(n, time.perf_counter() - t0), "ops/s")

        # 2. tasks, async: submit a burst, then drain.
        n = 500
        t0 = time.perf_counter()
        ray_tpu.get([noop.remote() for _ in range(n)], timeout=120)
        record("tasks_async_per_s", _rate(n, time.perf_counter() - t0), "ops/s")

        # 3. actor calls 1:1 sync.
        a = Counter.remote()
        ray_tpu.get(a.inc.remote(), timeout=30)
        n = 200
        t0 = time.perf_counter()
        for _ in range(n):
            ray_tpu.get(a.inc.remote(), timeout=30)
        record("actor_calls_sync_per_s", _rate(n, time.perf_counter() - t0),
               "ops/s")

        # 4. actor calls 1:1 async (client-side pipelining).
        n = 500
        t0 = time.perf_counter()
        ray_tpu.get([a.inc.remote() for _ in range(n)], timeout=120)
        record("actor_calls_async_per_s", _rate(n, time.perf_counter() - t0),
               "ops/s")

        # 5. actor calls 1:n — one driver fanning out to 8 actors.
        pool = [Counter.remote() for _ in range(8)]
        ray_tpu.get([b.inc.remote() for b in pool], timeout=60)
        n_per = 60
        t0 = time.perf_counter()
        ray_tpu.get(
            [b.inc.remote() for _ in range(n_per) for b in pool], timeout=120)
        record("actor_calls_1_to_n_per_s",
               _rate(n_per * len(pool), time.perf_counter() - t0), "ops/s")

        # 6. put/get small objects.
        n = 300
        t0 = time.perf_counter()
        refs = [ray_tpu.put(i) for i in range(n)]
        record("put_small_per_s", _rate(n, time.perf_counter() - t0), "ops/s")
        t0 = time.perf_counter()
        ray_tpu.get(refs, timeout=60)
        record("get_small_per_s", _rate(n, time.perf_counter() - t0), "ops/s")

        # 7. put/get throughput on a 256 MiB array (zero-copy numpy path).
        big = np.zeros(256 * 1024 * 1024, dtype=np.uint8)
        gib = big.nbytes / (1024 ** 3)
        t0 = time.perf_counter()
        ref = ray_tpu.put(big)
        record("put_gib_per_s", gib / (time.perf_counter() - t0), "GiB/s")
        t0 = time.perf_counter()
        out = ray_tpu.get(ref, timeout=60)
        assert out.nbytes == big.nbytes
        record("get_gib_per_s", gib / (time.perf_counter() - t0), "GiB/s")
        del big, out, ref

        # 8. cross-node task arg: ship ~64 MiB to a forced-remote task.
        @ray_tpu.remote(num_cpus=cpus_per_node)  # can't co-locate w/ driver node's tasks
        def size_of(arr):
            return arr.nbytes

        payload = np.zeros(64 * 1024 * 1024, dtype=np.uint8)
        pref = ray_tpu.put(payload)
        t0 = time.perf_counter()
        nbytes = ray_tpu.get(size_of.remote(pref), timeout=120)
        dt = time.perf_counter() - t0
        assert nbytes == payload.nbytes
        record("task_arg_64mib_ms", dt * 1e3, "ms")
    finally:
        ray_tpu.shutdown()
        cluster.shutdown()
    return results


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="MICROBENCH.json")
    ap.add_argument("--nodes", type=int, default=2)
    ap.add_argument("--cpus", type=int, default=4)
    args = ap.parse_args()
    results = run_all(args.nodes, args.cpus)
    # Preserve sections other writers own (scalebench.py merges its
    # "scalability" results into the same file).
    extra = {}
    import os

    if os.path.exists(args.out):
        try:
            with open(args.out) as f:
                prior = json.load(f)
            extra = {k: v for k, v in prior.items()
                     if k not in ("cmd", "backend", "nodes",
                                  "cpus_per_node", "metrics")}
        except (OSError, ValueError):
            pass
    payload = {
        "cmd": " ".join(sys.argv),
        "backend": "cluster",
        "nodes": args.nodes,
        "cpus_per_node": args.cpus,
        "metrics": results,
        **extra,
    }
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=2)
    print(json.dumps(payload))


if __name__ == "__main__":
    main()
