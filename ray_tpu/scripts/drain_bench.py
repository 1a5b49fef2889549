"""Drain-vs-crash recovery latency + gang-recovery MTTR benchmarks.

At pod scale, recovery LATENCY — not just recovery correctness —
dominates (MLPerf TPU-pod studies, PAPERS.md): a heartbeat-timeout crash
detection burns ``node_death_timeout_s`` of dead time per preemption,
while a proactive drain reconstructs actors on surviving nodes before
the departing node exits. This script measures both paths on a local
multi-node ``Cluster`` and prints how long each took:

    python -m ray_tpu.scripts.drain_bench

Round 12 adds the GANG half — the placement-group reservation is now a
first-class migration citizen (head ``RESCHEDULING`` state machine), so
the probe that matters for elastic fleets is ``pg_reschedule_ms``: wall
time from a gang bundle losing its node (drain initiated, or the node
killed outright) to the group's reservation being CREATED again on
healthy nodes. ``--gang`` runs it for both triggers, plus a seeded
preemption schedule against an elastic ``DataParallelTrainer``
(num_workers=2, min_workers=1) whose downtime ledger must attribute
every lost second to preemption/drain/reschedule, or the script exits
non-zero. The JSON lines are printed and written nowhere.
"""

from __future__ import annotations

import argparse
import json
import threading
import time


def _wait_actor_on_other_node(head, actor_id: str, avoid_node: str,
                              timeout: float = 60.0) -> float:
    """Seconds until the actor is ALIVE on a node other than
    ``avoid_node``."""
    t0 = time.monotonic()
    deadline = t0 + timeout
    while time.monotonic() < deadline:
        info = head.rpc_get_actor(actor_id, timeout=1.0)
        if info and info["state"] == "ALIVE" and \
                info["node_id"] != avoid_node:
            return time.monotonic() - t0
        time.sleep(0.01)
    raise TimeoutError(f"actor {actor_id} not recovered in {timeout}s")


def _one_round(proactive: bool) -> float:
    """Recovery latency (s) for one fresh cluster: actor pinned on a
    victim node, victim removed via drain (proactive) or SIGKILL-style
    crash (heartbeat-timeout detection)."""
    import ray_tpu
    from ray_tpu.cluster.cluster_utils import Cluster
    from ray_tpu.util.scheduling_strategies import (
        NodeAffinitySchedulingStrategy,
    )

    ray_tpu.shutdown()
    cluster = Cluster()
    cluster.add_node(num_cpus=2)  # survivor (hosts the driver store)
    victim = cluster.add_node(num_cpus=2)
    cluster.wait_for_nodes()
    ray_tpu.init(cluster.address)
    try:
        @ray_tpu.remote
        class Probe:
            def ping(self):
                return "pong"

        actor = Probe.options(
            max_restarts=-1,
            scheduling_strategy=NodeAffinitySchedulingStrategy(
                victim.node_id),
        ).remote()
        assert ray_tpu.get(actor.ping.remote(), timeout=30) == "pong"
        if proactive:
            cluster.head.rpc_drain_node(
                victim.node_id, "bench", 30.0, wait=False)
        else:
            cluster.kill_node(victim)
        return _wait_actor_on_other_node(
            cluster.head, actor._actor_id, victim.node_id)
    finally:
        ray_tpu.shutdown()
        cluster.shutdown()


# -- gang-recovery MTTR (placement-group reschedule latency) ---------------


def _wait_pg_restored(pg, avoid_node: str,
                      timeout: float = 90.0) -> float:
    """Seconds until the group is CREATED again with every bundle on an
    alive node other than ``avoid_node`` and at least one completed
    reschedule."""
    import ray_tpu
    from ray_tpu.util.placement_group import placement_group_table

    t0 = time.monotonic()
    deadline = t0 + timeout
    while time.monotonic() < deadline:
        table = placement_group_table(pg) or {}
        alive = {n["NodeID"] for n in ray_tpu.nodes() if n["Alive"]}
        placement = table.get("placement") or []
        if (table.get("state") == "CREATED"
                and table.get("reschedules", 0) >= 1
                and placement
                and all(nid in alive and nid != avoid_node
                        for nid, _bi in placement)):
            return time.monotonic() - t0
        time.sleep(0.02)
    raise TimeoutError(
        f"gang reservation not restored within {timeout}s "
        f"(state={placement_group_table(pg)!r})")


def _gang_round(trigger: str) -> dict:
    """``pg_reschedule_ms`` for one fresh cluster: a 2-bundle SPREAD
    gang loses a bundle's node to a drain (``trigger='drain'``) or a
    kill (``trigger='node_death'``); measured drain/kill ->
    reservation whole again on healthy nodes."""
    import ray_tpu
    from ray_tpu.cluster.cluster_utils import Cluster
    from ray_tpu.util.placement_group import (
        placement_group,
        placement_group_table,
        remove_placement_group,
    )

    ray_tpu.shutdown()
    cluster = Cluster()
    for _ in range(3):
        cluster.add_node(num_cpus=2)
    cluster.wait_for_nodes()
    ray_tpu.init(cluster.address)
    try:
        pg = placement_group([{"CPU": 1}, {"CPU": 1}], strategy="SPREAD")
        ray_tpu.get(pg.ready(), timeout=60)
        table = placement_group_table(pg)
        victim_nid = table["bundle_nodes"][1]
        victim = next(n for n in cluster.nodes
                      if n.node_id == victim_nid)
        t0 = time.monotonic()
        if trigger == "drain":
            cluster.head.rpc_drain_node(
                victim_nid, "bench-gang", 30.0, wait=False)
        else:
            cluster.kill_node(victim)
        restored_s = _wait_pg_restored(pg, victim_nid)
        out = {
            "trigger": trigger,
            "pg_reschedule_ms": round(
                (time.monotonic() - t0) * 1e3, 1),
            "restored_wait_ms": round(restored_s * 1e3, 1),
            "bundles": 2,
            "bundles_lost": 1,
        }
        remove_placement_group(pg)
        return out
    finally:
        ray_tpu.shutdown()
        cluster.shutdown()


def _gang_goodput(seed: int) -> dict:
    """Elastic-gang goodput envelope under a seeded preemption
    schedule: a 2-worker (min 1) checkpointing trainer survives one
    graceful drain and one hard node kill (replacement capacity delayed
    so the gang genuinely runs SHRUNK, then regrows); every lost second
    must land in the ledger under a preemption/drain/reschedule cause
    with ``FailureConfig.max_failures=0`` intact."""
    import random

    import ray_tpu
    from ray_tpu import train
    from ray_tpu.cluster.cluster_utils import Cluster
    from ray_tpu.train import session
    from ray_tpu.train.checkpoint import Checkpoint
    from ray_tpu.util.placement_group import placement_group_table

    rng = random.Random(f"{seed}:gang-goodput")
    ray_tpu.shutdown()
    cluster = Cluster()
    # Driver node too small for a gang bundle (CPU:2): bundles live
    # only on the 2-cpu worker nodes, so losing one with no spare
    # capacity forces a GENUINE shrunk-world window — the gang can't
    # quietly re-home onto the driver's node.
    cluster.add_node(num_cpus=1)  # driver node: survives
    for _ in range(2):
        cluster.add_node(num_cpus=2)
    cluster.wait_for_nodes()
    ray_tpu.init(cluster.address)

    def train_fn(config):
        start = 0
        ckpt = session.get_checkpoint()
        if ckpt is not None:
            start = ckpt.to_dict().get("step", -1) + 1
        for i in range(start, config["steps"]):
            time.sleep(0.25)
            session.report(
                {"step": i, "world": session.get_world_size()},
                checkpoint=Checkpoint.from_dict({"step": i}))

    trainer = train.DataParallelTrainer(
        train_fn,
        train_loop_config={"steps": 36},
        scaling_config=train.ScalingConfig(
            num_workers=2, min_workers=1, placement_strategy="SPREAD",
            resources_per_worker={"CPU": 2}),
        run_config=train.RunConfig(
            failure_config=train.FailureConfig(max_failures=0)),
    )
    faults = {"drain": 0, "kill": 0}

    def gang_victim(wait_s: float = 30.0):
        # Wait for the gang's reservation to exist before injecting: a
        # slow pg.ready() on a loaded box must delay the fault, not
        # skip it (a zero-fault run would commit a vacuous envelope).
        deadline = time.monotonic() + wait_s
        while time.monotonic() < deadline:
            pgs = placement_group_table() or {}
            gang = next((v for v in pgs.values()
                         if v["state"] in ("CREATED", "RESCHEDULING")),
                        None)
            if gang is not None:
                nids = {nid for nid, _bi in gang["placement"]}
                # Never the driver's node (cluster.nodes[0]):
                # preempting the node hosting the driver's own
                # agent/store measures harness collapse, not gang
                # recovery.
                victim = next((n for n in list(cluster.nodes)[1:]
                               if n.node_id in nids), None)
                if victim is not None:
                    return victim
            time.sleep(0.25)
        return None

    def schedule():
        # One graceful drain (preemption notice), then one hard kill
        # with DELAYED replacement — the shrink/regrow window. The kill
        # waits out the drain restart (so both faults land on separate
        # attempts), and the replacement lags past heartbeat death
        # detection + a few steps, so the gang genuinely RUNS at the
        # surviving world size before regrowing.
        time.sleep(rng.uniform(1.0, 2.0))
        victim = gang_victim()
        if victim is not None:
            cluster.head.rpc_drain_node(
                victim.node_id, "bench-preempt", 10.0, wait=False)
            faults["drain"] += 1
            cluster.add_node(num_cpus=2)
        time.sleep(rng.uniform(6.0, 8.0))
        victim = gang_victim()
        if victim is not None:
            cluster.kill_node(victim)
            faults["kill"] += 1
            time.sleep(rng.uniform(9.0, 11.0))  # shrunk-world window
            cluster.add_node(num_cpus=2)

    injector = threading.Thread(target=schedule, daemon=True)
    injector.start()
    try:
        from ray_tpu.util.goodput import attribution_ok

        result = trainer.fit()
        injector.join(timeout=60.0)
        gp = dict(result.goodput or {})
        attributed, sums = attribution_ok(gp)
        worlds = sorted({m.get("world") for m in result.metrics_history
                         if m.get("world") is not None})
        final_pg = trainer.final_pg_state or {}
        alive = {n["NodeID"] for n in ray_tpu.nodes() if n["Alive"]}
        pg_alive = (final_pg.get("state") == "CREATED" and all(
            nid in alive for nid, _bi in final_pg.get("placement", [])))
        return {
            "seed": seed,
            "faults": dict(faults),
            # A passing envelope must have actually been attacked: a
            # zero-fault run (injector raced a slow setup) proves
            # nothing and must not commit as preemption evidence.
            "faults_injected": faults["drain"] >= 1
            and faults["kill"] >= 1,
            "completed": result.error is None,
            "budget_intact": result.error is None,  # max_failures=0
            "goodput": gp,
            "goodput_pct": gp.get("goodput_pct"),
            "downtime_fully_attributed": attributed and sums,
            "worlds_seen": worlds,
            "pg_final_state": final_pg.get("state"),
            "pg_reschedules": final_pg.get("reschedules", 0),
            "pg_alive_on_healthy_nodes": pg_alive,
        }
    finally:
        ray_tpu.shutdown()
        cluster.shutdown()


def run_gang(seed: int) -> dict:
    """The full gang-recovery section: MTTR for both triggers + the
    seeded elastic-goodput envelope."""
    rounds = {t: _gang_round(t) for t in ("drain", "node_death")}
    return {
        "mttr": rounds,
        "goodput_envelope": _gang_goodput(seed),
    }


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--gang", action="store_true",
                    help="also run the gang-recovery MTTR probe + the "
                         "seeded elastic-goodput envelope")
    ap.add_argument("--seed", type=int, default=12,
                    help="preemption-schedule seed for the gang "
                         "goodput envelope (printed on failure so "
                         "the run is replayable)")
    args = ap.parse_args(argv)

    drain_s = _one_round(proactive=True)
    crash_s = _one_round(proactive=False)
    entry = {
        "proactive_drain_ms": round(drain_s * 1000, 1),
        "crash_detection_ms": round(crash_s * 1000, 1),
    }
    print(json.dumps(entry))
    if not args.gang:
        return entry

    gang = run_gang(args.seed)
    env = gang["goodput_envelope"]
    print(json.dumps(gang, default=str))
    ok = (env["completed"] and env["faults_injected"]
          and env["downtime_fully_attributed"]
          and env["pg_alive_on_healthy_nodes"])
    if not ok:
        raise SystemExit(
            f"gang probe FAILED (replay with --seed {args.seed}): "
            f"{env}")
    return gang


if __name__ == "__main__":
    main()
