"""Placement-group rescheduling: the gang reservation outlives its nodes.

The head's RESCHEDULING state machine (reference:
``gcs_placement_group_manager.cc`` reschedule-on-dead path) re-runs the
reserve 2PC for lost bundles on healthy nodes; these tests cover the
node-death and drain triggers, the 2PC rollback edge cases (idempotent
prepare under retried/severed replies, mid-2PC failpoint crashes,
kill_node mid-2PC), the remove-vs-reschedule race, parked hard-affinity
fallback, the elastic DataParallelTrainer shrink/regrow composition,
and the seeded preemption-schedule envelope (``-m slow``).
"""

import threading
import time

import pytest

import ray_tpu
from ray_tpu.cluster.cluster_utils import Cluster
from ray_tpu.util import failpoints
from ray_tpu.util.placement_group import (
    placement_group,
    placement_group_table,
    remove_placement_group,
)


@pytest.fixture(autouse=True)
def _clean_chaos():
    from ray_tpu.cluster.rpc import channel_chaos

    failpoints.reset()
    channel_chaos.clear()
    yield
    failpoints.reset()
    channel_chaos.clear()


def wait_for(cond, timeout=30.0, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return
        time.sleep(0.05)
    raise TimeoutError(f"timed out waiting for {msg}")


@pytest.fixture()
def cluster3():
    """Driver node + two 2-cpu workers (the driver's node is
    cluster3.nodes[0] and is never a victim)."""
    ray_tpu.shutdown()
    c = Cluster()
    c.add_node(num_cpus=4)
    c.add_node(num_cpus=2)
    c.add_node(num_cpus=2)
    c.wait_for_nodes()
    ray_tpu.init(c.address)
    yield c
    ray_tpu.shutdown()
    c.shutdown()


def _gang(strategy="SPREAD"):
    pg = placement_group([{"CPU": 1}, {"CPU": 1}], strategy=strategy)
    assert ray_tpu.get(pg.ready(), timeout=60) == pg.id
    return pg


def _node_of(cluster, node_id):
    return next(n for n in cluster.nodes if n.node_id == node_id)


def _restored(pg, min_reschedules=1):
    def check():
        t = placement_group_table(pg) or {}
        if t.get("state") != "CREATED":
            return False
        if t.get("reschedules", 0) < min_reschedules:
            return False
        alive = {n["NodeID"] for n in ray_tpu.nodes() if n["Alive"]}
        return all(nid in alive for nid, _bi in t["placement"])

    return check


def _no_leaked_bundles(cluster):
    """Every reservation an agent holds is explained by a live group's
    placement on that node."""
    pgs = cluster.head.rpc_placement_group_table() or {}
    expected = set()
    for pg_id, pg in pgs.items():
        if pg.get("state") in ("CREATED", "RESCHEDULING"):
            for nid, bi in pg.get("placement", []):
                expected.add((nid, f"{pg_id}:{bi}"))
    leaks = []
    for node in cluster.nodes:
        for key in node.rpc_bundle_table():
            if (node.node_id, key) not in expected:
                leaks.append((node.node_id[-12:], key))
    return leaks


# -- reschedule triggers ----------------------------------------------------


def test_node_death_moves_pg_to_rescheduling_then_created(cluster3):
    pg = _gang("STRICT_SPREAD")
    table = placement_group_table(pg)
    assert table["state"] == "CREATED"
    assert table["reschedules"] == 0
    assert table["live_bundles"] == [0, 1]
    victim_nid = table["bundle_nodes"][1]
    cluster3.kill_node(_node_of(cluster3, victim_nid))
    wait_for(_restored(pg), timeout=60,
             msg="PG restored on healthy nodes after node death")
    table = placement_group_table(pg)
    assert table["reschedules"] == 1
    assert victim_nid not in {nid for nid, _ in table["placement"]}
    # The surviving bundle never moved.
    assert table["bundle_nodes"][0] == \
        placement_group_table(pg)["bundle_nodes"][0]
    assert _no_leaked_bundles(cluster3) == []
    remove_placement_group(pg)


def test_drain_migrates_bundles_and_vacates_old_node(cluster3):
    pg = _gang("SPREAD")
    table = placement_group_table(pg)
    # Pick a bundle hosted off the driver's node.
    driver_nid = cluster3.nodes[0].node_id
    bi = next(b for b, nid in table["bundle_nodes"].items()
              if nid != driver_nid)
    victim = _node_of(cluster3, table["bundle_nodes"][bi])
    cluster3.head.rpc_drain_node(
        victim.node_id, "preempt-notice", 15.0, wait=False)
    wait_for(_restored(pg), timeout=60, msg="PG migrated off drain")
    table = placement_group_table(pg)
    assert victim.node_id not in {nid for nid, _ in table["placement"]}

    def vacated():
        # The old reservation was returned while the node still lived
        # (no leaked carve-out on a DRAINING node) — or the drain
        # finished first and the reservation died with the node; under
        # load either ordering is legal, a reservation held by an
        # ALIVE node is not.
        if victim.rpc_bundle_table() == {}:
            return True
        return not any(n["NodeID"] == victim.node_id and n["Alive"]
                       for n in ray_tpu.nodes())

    wait_for(vacated, timeout=30, msg="old bundle vacated or node gone")
    remove_placement_group(pg)


def test_task_pinned_to_migrated_bundle_reresolves(cluster3):
    from ray_tpu.util import PlacementGroupSchedulingStrategy

    pg = _gang("STRICT_SPREAD")
    table = placement_group_table(pg)
    victim_nid = table["bundle_nodes"][1]
    cluster3.kill_node(_node_of(cluster3, victim_nid))

    @ray_tpu.remote(num_cpus=1)
    def where():
        import ray_tpu._private.worker as worker_mod

        return worker_mod.backend().node_id

    # Submitted while the bundle's node is dead / RESCHEDULING: the
    # task parks, re-resolves to the bundle's NEW home, and runs —
    # instead of erroring against the old placement.
    strategy = PlacementGroupSchedulingStrategy(
        placement_group=pg, placement_group_bundle_index=1)
    ref = where.options(scheduling_strategy=strategy).remote()
    got = ray_tpu.get(ref, timeout=90)
    assert got != victim_nid
    wait_for(_restored(pg), timeout=30)
    assert placement_group_table(pg)["bundle_nodes"][1] == got
    remove_placement_group(pg)


def test_pubsub_lifecycle_events_on_reschedule(cluster3):
    pg = _gang("STRICT_SPREAD")
    sub_id = "test-pg-events"
    cluster3.head.rpc_pubsub_subscribe(
        sub_id, "PLACEMENT_GROUPS", [pg.id])
    victim_nid = placement_group_table(pg)["bundle_nodes"][1]
    cluster3.kill_node(_node_of(cluster3, victim_nid))
    wait_for(_restored(pg), timeout=60)
    states = []
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        got = cluster3.head.rpc_pubsub_poll(sub_id, 0.5)
        if got is None:
            break
        for msg in got[0]:
            states.append(msg["data"]["state"])
        if "CREATED" in states:
            break
    # Holders learn the group moved: coalescing may collapse the
    # RESCHEDULING->CREATED run to the latest state, but the terminal
    # CREATED (with the new placement) must arrive.
    assert "CREATED" in states, states
    cluster3.head.rpc_pubsub_unsubscribe(sub_id)
    remove_placement_group(pg)


# -- 2PC rollback edge cases ------------------------------------------------


def test_prepare_bundle_idempotent_no_double_reserve(cluster3):
    """A prepare replayed after a lost reply must not carve the node
    twice (exactly-once reservation)."""
    node = cluster3.nodes[1]
    avail_before = node.pool.available().get("CPU", 0.0)
    assert node.rpc_prepare_bundle("pg-test-idem", 0, {"CPU": 1}) is True
    assert node.rpc_prepare_bundle("pg-test-idem", 0, {"CPU": 1}) is True
    avail_after = node.pool.available().get("CPU", 0.0)
    assert avail_before - avail_after == 1.0  # ONE carve-out, not two
    assert node.rpc_commit_bundle("pg-test-idem", 0) is True
    # Commit replay (severed reply retry) is also an ack.
    assert node.rpc_commit_bundle("pg-test-idem", 0) is True
    node.rpc_return_bundle("pg-test-idem", 0)
    assert node.pool.available().get("CPU", 0.0) == avail_before
    # Commit of a returned bundle must not resurrect it.
    assert node.rpc_commit_bundle("pg-test-idem", 0) is True
    assert node.rpc_bundle_table() == {}


def test_commit_severed_channel_exactly_once(cluster3):
    """Reschedule commit whose reply is severed after a complete send:
    the agent committed, the head retries, the retry is an ack — one
    reservation, PG restored."""
    from ray_tpu.cluster.rpc import channel_chaos

    pg = _gang("STRICT_SPREAD")
    table = placement_group_table(pg)
    victim_nid = table["bundle_nodes"][1]
    # Sever exactly one head->agent commit_bundle reply.
    rid = channel_chaos.add_rule(
        "sever", src=[cluster3.head.address], method="commit_bundle",
        times=1, label="test-sever")
    try:
        cluster3.kill_node(_node_of(cluster3, victim_nid))
        wait_for(_restored(pg), timeout=90,
                 msg="PG restored through severed commit")
    finally:
        channel_chaos.clear("test-sever")
    assert _no_leaked_bundles(cluster3) == []
    remove_placement_group(pg)


def test_mid_2pc_prepare_crash_rolls_back(cluster3):
    """An injected prepare failure mid-reschedule rolls back cleanly
    (no leaked per-node reservation) and the retry succeeds."""
    pg = _gang("STRICT_SPREAD")
    victim_nid = placement_group_table(pg)["bundle_nodes"][1]
    failpoints.arm("head.pg.prepare", "raise,once")
    cluster3.kill_node(_node_of(cluster3, victim_nid))
    wait_for(_restored(pg), timeout=90,
             msg="PG restored after injected prepare crash")
    assert _no_leaked_bundles(cluster3) == []
    armed = failpoints.list_armed()
    assert "head.pg.prepare" not in armed  # once: fired and disarmed
    remove_placement_group(pg)


def test_injected_coordinator_crash_self_heals(cluster3):
    """A reschedule coordinator killed at head.pg.before_reschedule
    dies for real (the injection is not a no-op) and the monitor loop
    restarts a fresh coordinator — the group can never wedge in
    RESCHEDULING with nothing driving it."""
    pg = _gang("STRICT_SPREAD")
    victim_nid = placement_group_table(pg)["bundle_nodes"][1]
    failpoints.arm("head.pg.before_reschedule", "raise,once")
    cluster3.kill_node(_node_of(cluster3, victim_nid))
    wait_for(_restored(pg), timeout=90,
             msg="monitor restarted the crashed coordinator")
    assert _no_leaked_bundles(cluster3) == []
    assert "head.pg.before_reschedule" not in failpoints.list_armed()
    remove_placement_group(pg)


def test_scaling_config_validates_min_workers():
    from ray_tpu.train import ScalingConfig

    with pytest.raises(ValueError, match="min_workers"):
        ScalingConfig(num_workers=2, min_workers=4)
    with pytest.raises(ValueError, match="min_workers"):
        ScalingConfig(num_workers=2, min_workers=0)
    assert ScalingConfig(num_workers=2, min_workers=2).min_workers == 2


def test_kill_node_mid_2pc_rolls_back(cluster3):
    """kill_node between prepare and commit (commit raise + target
    killed): the coordinator re-derives, nothing leaks, the group still
    lands on whatever healthy capacity remains."""
    pg = _gang("SPREAD")
    table = placement_group_table(pg)
    driver_nid = cluster3.nodes[0].node_id
    bi = next(b for b, nid in table["bundle_nodes"].items()
              if nid != driver_nid)
    first_victim = _node_of(cluster3, table["bundle_nodes"][bi])
    # Stall the reschedule's first commit, and kill the replacement
    # target mid-2PC from a side thread.
    failpoints.arm("head.pg.commit", "delay:1.0,once")

    def kill_replacement():
        # Wait until a replacement prepared (bundle appears on a node
        # that is NOT in the current placement), then kill that node.
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            placed = {nid for nid, _b in (
                placement_group_table(pg) or {}).get("placement", [])}
            for node in list(cluster3.nodes):
                if node.node_id == driver_nid:
                    continue
                if node.node_id not in placed and node.rpc_bundle_table():
                    cluster3.kill_node(node)
                    return
            time.sleep(0.05)

    killer = threading.Thread(target=kill_replacement, daemon=True)
    cluster3.kill_node(first_victim)
    killer.start()
    cluster3.add_node(num_cpus=2)  # replacement capacity either way
    cluster3.wait_for_nodes()
    killer.join(timeout=35)
    wait_for(_restored(pg), timeout=120,
             msg="PG restored after kill mid-2PC")
    assert _no_leaked_bundles(cluster3) == []
    remove_placement_group(pg)


def test_remove_racing_reschedule_rolls_back(cluster3):
    """remove_placement_group while the group is RESCHEDULING: the
    coordinator sees REMOVED and gives back everything it prepared —
    no resurrection, no leaked reservation."""
    pg = _gang("STRICT_SPREAD")
    victim_nid = placement_group_table(pg)["bundle_nodes"][1]
    # Hold the reschedule in its backoff window so the remove wins.
    failpoints.arm("head.pg.prepare", "delay:0.5")
    cluster3.kill_node(_node_of(cluster3, victim_nid))
    wait_for(lambda: placement_group_table(pg)["state"] in
             ("RESCHEDULING", "CREATED"), timeout=60)
    remove_placement_group(pg)
    failpoints.reset()
    wait_for(lambda: placement_group_table(pg)["state"] == "REMOVED",
             timeout=10)

    def settled():
        return _no_leaked_bundles(cluster3) == []

    wait_for(settled, timeout=30, msg="all reservations returned")
    # CPU capacity is whole again on surviving nodes.
    wait_for(lambda: ray_tpu.available_resources().get("CPU", 0.0) ==
             ray_tpu.cluster_resources().get("CPU", 0.0),
             timeout=30, msg="capacity restored")


def test_hard_affinity_parked_on_rescheduling_pgs_old_node(cluster3):
    """A task hard-pinned to the node a RESCHEDULING group just lost
    falls back to soft affinity instead of a guaranteed pending
    timeout (the parked-affinity fallback composing with reschedule)."""
    from ray_tpu.util.scheduling_strategies import (
        NodeAffinitySchedulingStrategy,
    )

    pg = _gang("STRICT_SPREAD")
    victim_nid = placement_group_table(pg)["bundle_nodes"][1]
    victim = _node_of(cluster3, victim_nid)
    cluster3.kill_node(victim)

    @ray_tpu.remote(num_cpus=1)
    def probe():
        return "ok"

    ref = probe.options(
        scheduling_strategy=NodeAffinitySchedulingStrategy(
            victim_nid)).remote()
    assert ray_tpu.get(ref, timeout=90) == "ok"
    wait_for(_restored(pg), timeout=60)
    remove_placement_group(pg)


# -- state / metrics surfaces ----------------------------------------------


def test_state_placement_groups_surface(cluster3):
    from ray_tpu import state

    pg = _gang("SPREAD")
    table = state.placement_groups()
    assert pg.id in table
    entry = state.placement_groups(pg.id)
    assert entry["state"] == "CREATED"
    assert sorted(entry["bundle_nodes"]) == [0, 1]
    assert entry["live_bundles"] == [0, 1]
    assert entry["reschedules"] == 0
    assert "_resched_active" not in entry  # coordinator keys stripped
    remove_placement_group(pg)


def test_reschedule_metrics_families(cluster3):
    from ray_tpu.util import metrics as _metrics

    pg = _gang("STRICT_SPREAD")
    victim_nid = placement_group_table(pg)["bundle_nodes"][1]
    cluster3.kill_node(_node_of(cluster3, victim_nid))
    wait_for(_restored(pg), timeout=60)

    def emitted():
        body = _metrics.prometheus_text()
        return ("ray_tpu_pg_reschedules_total" in body
                and 'cause="node_death"' in body
                and "ray_tpu_pg_reschedule_seconds" in body)

    wait_for(emitted, timeout=10, msg="reschedule metrics emitted")
    remove_placement_group(pg)


# -- elastic trainer composition -------------------------------------------


@pytest.fixture()
def cluster_elastic():
    """Driver node too small for a gang bundle (CPU:2): bundles live
    only on the worker nodes, so a kill with no spare capacity forces a
    genuine shrunk-world window instead of a quiet re-home onto the
    driver's node."""
    ray_tpu.shutdown()
    c = Cluster()
    c.add_node(num_cpus=1)
    c.add_node(num_cpus=2)
    c.add_node(num_cpus=2)
    c.wait_for_nodes()
    ray_tpu.init(c.address)
    yield c
    ray_tpu.shutdown()
    c.shutdown()


def _elastic_trainer(steps):
    from ray_tpu import train
    from ray_tpu.train import session
    from ray_tpu.train.checkpoint import Checkpoint

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

    return train.DataParallelTrainer(
        train_fn,
        train_loop_config={"steps": steps},
        scaling_config=train.ScalingConfig(
            num_workers=2, min_workers=1, placement_strategy="SPREAD",
            resources_per_worker={"CPU": 2}),
        run_config=train.RunConfig(
            failure_config=train.FailureConfig(max_failures=0)),
    )


def test_elastic_gang_survives_kill_budget_intact(cluster_elastic):
    """Hard node loss of a gang bundle: the trial completes with
    max_failures=0 (exempt), its downtime fully attributed to planned
    causes, and the SAME placement group ends CREATED on healthy nodes
    with a completed reschedule."""
    c = cluster_elastic
    trainer = _elastic_trainer(steps=24)
    state = {}

    def killer():
        time.sleep(2.0)
        table = None
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline:
            pgs = placement_group_table() or {}
            table = next((v for v in pgs.values()
                          if v["state"] == "CREATED"), None)
            if table is not None:
                break
            time.sleep(0.1)
        assert table is not None
        driver_nid = c.nodes[0].node_id
        victim_nid = next(nid for nid, _bi in table["placement"]
                          if nid != driver_nid)
        state["victim"] = victim_nid
        c.kill_node(_node_of(c, victim_nid))
        time.sleep(3.0)
        c.add_node(num_cpus=2)

    t = threading.Thread(target=killer, daemon=True)
    t.start()
    result = trainer.fit()
    t.join(timeout=30)
    assert result.error is None  # budget (max_failures=0) intact
    assert result.metrics["step"] == 23
    gp = result.goodput
    assert abs(sum(gp["by_cause"].values()) - gp["downtime_s"]) < 1e-6
    assert all(cause == "preemption" or cause == "reschedule"
               or cause.startswith("drain")
               for cause in gp["by_cause"]), gp
    final = trainer.final_pg_state
    assert final is not None and final["state"] == "CREATED"
    assert final["reschedules"] >= 1
    alive = {n["NodeID"] for n in ray_tpu.nodes() if n["Alive"]}
    assert all(nid in alive for nid, _bi in final["placement"])
    assert state["victim"] not in {nid for nid, _bi in final["placement"]}


def test_elastic_gang_shrinks_then_regrows(cluster_elastic, tmp_path):
    """With replacement capacity withheld until the gang is observably
    running at the surviving world size, the trial genuinely SHRINKS,
    then regrows to full when the head reschedules the lost bundle —
    the regrow restart is attributed to the reschedule cause."""
    from ray_tpu import train
    from ray_tpu.train import session
    from ray_tpu.train.checkpoint import Checkpoint

    c = cluster_elastic
    sentinel = str(tmp_path / "shrunk")

    def train_fn(config):
        start = 0
        ckpt = session.get_checkpoint()
        if ckpt is not None:
            start = ckpt.to_dict().get("step", -1) + 1
        for i in range(start, config["steps"]):
            time.sleep(0.25)
            if session.get_world_size() == 1:
                # Worker-side proof the shrunk world is RUNNING (same
                # host: the killer waits on this file, so the
                # replacement only arrives after real shrunk steps).
                with open(config["sentinel"], "w") as f:
                    f.write(str(i))
            session.report(
                {"step": i, "world": session.get_world_size()},
                checkpoint=Checkpoint.from_dict({"step": i}))

    trainer = train.DataParallelTrainer(
        train_fn,
        train_loop_config={"steps": 60, "sentinel": sentinel},
        scaling_config=train.ScalingConfig(
            num_workers=2, min_workers=1, placement_strategy="SPREAD",
            resources_per_worker={"CPU": 2}),
        run_config=train.RunConfig(
            failure_config=train.FailureConfig(max_failures=0)),
    )

    import os

    def killer():
        time.sleep(2.0)
        pgs = placement_group_table() or {}
        table = next((v for v in pgs.values()
                      if v["state"] == "CREATED"), None)
        if table is None:
            return
        driver_nid = c.nodes[0].node_id
        victim_nid = next(nid for nid, _bi in table["placement"]
                          if nid != driver_nid)
        c.kill_node(_node_of(c, victim_nid))
        # Replacement only AFTER the gang observably runs shrunk (or a
        # generous cap so a broken shrink path can't wedge the test).
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline \
                and not os.path.exists(sentinel):
            time.sleep(0.1)
        time.sleep(1.0)  # a few more shrunk steps
        c.add_node(num_cpus=2)

    t = threading.Thread(target=killer, daemon=True)
    t.start()
    result = trainer.fit()
    t.join(timeout=150)
    assert result.error is None
    assert result.metrics["step"] == 59
    worlds = {m.get("world") for m in result.metrics_history}
    assert 1 in worlds, f"gang never ran shrunk: {worlds}"
    assert 2 in worlds
    gp = result.goodput
    assert "reschedule" in gp["by_cause"], gp  # the regrow restart
    assert abs(sum(gp["by_cause"].values()) - gp["downtime_s"]) < 1e-6


def test_tune_gang_trial_drain_exempt_from_max_failures():
    """A gang tune trial lost to a drain restarts without consuming
    max_failures and KEEPS its placement group through the retry."""
    from ray_tpu.train import session
    from ray_tpu.train.checkpoint import Checkpoint
    from ray_tpu.tune.trial_runner import Trial, TrialRunner

    ray_tpu.shutdown()
    c = Cluster()
    # Driver node too small for the gang bundle: the trial's PG must
    # land on a (drainable) worker node.
    c.add_node(num_cpus=1)
    c.add_node(num_cpus=2)
    c.add_node(num_cpus=2)
    c.wait_for_nodes()
    ray_tpu.init(c.address)
    try:
        def trainable(config):
            start = 0
            ckpt = session.get_checkpoint()
            if ckpt is not None:
                start = ckpt.to_dict().get("step", -1) + 1
            for i in range(start, 14):
                time.sleep(0.25)
                session.report(
                    {"step": i},
                    checkpoint=Checkpoint.from_dict({"step": i}))

        drained = {}

        def drainer():
            deadline = time.monotonic() + 20
            while time.monotonic() < deadline:
                pgs = placement_group_table() or {}
                table = next((v for v in pgs.values()
                              if v["state"] == "CREATED"), None)
                if table is not None and table["placement"]:
                    nid = table["placement"][0][0]
                    if nid != c.nodes[0].node_id:
                        time.sleep(1.0)  # let the trial report once
                        c.head.rpc_drain_node(
                            nid, "spot-preempt", 10.0, wait=False)
                        drained["node"] = nid
                        c.add_node(num_cpus=2)
                        return
                time.sleep(0.1)

        t = threading.Thread(target=drainer, daemon=True)
        t.start()
        trial = Trial({}, resources={
            "bundles": [{"CPU": 2}], "strategy": "PACK"})
        runner = TrialRunner(trainable, [trial], max_failures=0)
        runner.run()
        t.join(timeout=30)
        assert drained, "drainer never found the gang's node"
        assert trial.status == "TERMINATED", (trial.status, trial.error)
        assert trial.num_failures == 0  # drain restarts are exempt
        assert trial.last_result["step"] == 13
        gp = trial.goodput()
        assert all(cause == "preemption" or cause.startswith("drain")
                   for cause in gp["by_cause"]), gp
    finally:
        ray_tpu.shutdown()
        c.shutdown()


# -- seeded preemption schedule (drain_bench's envelope) --------------------


@pytest.mark.slow
def test_seeded_gang_preemption_schedule_envelope():
    """``drain_bench --gang``'s goodput envelope end to end:
    seed 12's drain+kill schedule against the elastic gang — trial
    completes, PG ends ALIVE on healthy nodes, downtime 100%%
    attributed to planned causes, budget intact."""
    from ray_tpu.scripts import drain_bench

    env = drain_bench._gang_goodput(seed=12)
    assert env["faults_injected"], env  # the schedule actually attacked
    assert env["completed"] and env["budget_intact"], env
    assert env["downtime_fully_attributed"], env
    assert env["pg_final_state"] == "CREATED", env
    assert env["pg_alive_on_healthy_nodes"], env
    assert env["pg_reschedules"] >= 1, env
