"""Chaos soak harness: a mixed workload under a seeded fault schedule.

The standing adversarial test for the recovery machinery (drain
protocol, lineage re-execution, retry-budget exemption, actor
reconstruction, RPC reconnect windows): drive tasks, restartable
actors, and puts/gets on a multi-node ``Cluster`` while a seeded
scheduler injects faults from ≥4 classes —

  * **partition** — symmetric drop rules head↔victim
    (``Cluster.partition``), healed inside the heartbeat-death window;
  * **delay** — a delay-range rule on every RPC to a victim agent;
  * **sever** — sever-after-send on agent→head traffic (the
    ``maybe_executed`` ambiguity path) at p<1;
  * **kill** — ``Cluster.kill_node`` on a victim (heartbeat-timeout
    death; lineage re-execution + actor reconstruction), with a
    replacement node added so capacity survives;
  * **failpoints** — raise/delay arms at absorbed sites
    (event-batch upload, head snapshot, client ref flush);

plus exactly one graceful drain carrying a ``max_retries=0`` probe task
(the retry-budget-exemption invariant). Everything is derived from ONE
seed (``--seed`` / ``RAY_TPU_CHAOS_SEED``): the same seed replays the
same fault schedule, and the seed is printed on failure.

Invariants checked after the run settles:

  1. every driver-visible result is correct (tasks, actor calls, puts);
  2. the drain-exempt ``max_retries=0`` task completed (budgets burn
     only for non-exempt causes);
  3. ``state.memory_leaks()`` is empty;
  4. the federated ``/metrics/cluster`` body still scrapes;
  5. the head directory is consistent with the agent stores (no
     location on a dead node; per-node store reports join cleanly);
  6. the standing serve probe (a deployment serving throughout the
     soak) completed at least one request, and every probe either
     completed or shed/failed cleanly — a request that HANGS through a
     partition/kill is a lost request the latency plane never saw.

Usage::

    python -m ray_tpu.scripts.chaos_soak --seed 7 --duration 20

The result (fault counts, violations, per-fault MTTR, the seed that
replays it) is printed as one JSON line and written nowhere.
"""

from __future__ import annotations

import argparse
import json
import os
import random
import threading
import time


class _Soak:
    def __init__(self, seed: int, duration_s: float, n_victims: int = 2):
        self.seed = seed
        self.duration_s = duration_s
        self.n_victims = n_victims
        self.rng = random.Random(f"{seed}:soak-schedule")
        self.faults: dict[str, int] = {}
        self.violations: list[str] = []
        self.mttr_ms: list[float] = []
        self.tasks_ok = 0
        self.actor_calls_ok = 0
        self.puts_ok = 0
        self.serve_ok = 0
        self.serve_shed = 0
        self.llm_ok = 0
        self.llm_shed = 0
        self.llm_failed_fast = 0
        self.train_reports = 0
        self.train_goodput: "dict | None" = None
        self.gang_goodput: "dict | None" = None
        self.gang_reschedules = 0
        self.dataflow_ok = 0
        self.dataflow_failed = 0
        self.dataflow_spilled = 0
        self.dataflow_restores = 0
        self.signal_queries_ok = 0
        self.signal_queries_failed = 0
        self.signal_slo_transitions = 0
        self.signal_missed_evals = 0
        self.autoscaler_rounds_ok = 0
        self.autoscaler_rounds_failed = 0
        self.autoscaler_launches = 0
        self.autoscaler_launch_failures = 0
        self.autoscaler_quarantines = 0
        self.autoscaler_scale_downs = 0
        self.autoscaler_preemptions = 0
        self._autoscaler = None
        self._as_provider = None
        self._as_cluster = None
        self._fleet_work = None
        self._stop = threading.Event()
        # The streaming-dataflow probe's small-store node: exempt from
        # kill/drain (its custom resource exists nowhere else, so losing
        # it would just park every later probe round — the harness
        # starving itself, not a system fault); partitions/delays still
        # hit it.
        self._dataflow_node = None
        # The graceful-drain victim: the fault injector must not kill or
        # partition the node the drain (and its retry-exemption probe)
        # is pinned to — that would be the harness racing itself, not a
        # system fault.
        self._drain_victim = None

    # -- fault injection ---------------------------------------------------

    def _probe_mttr(self, fault: str, t_fault: float,
                    victim_node_id: str | None = None) -> None:
        """Time from fault injection to the next successful round trip
        THROUGH the faulted path: pinned to the victim node while it
        lives (default scheduling would stay on the driver's node and
        measure nothing), SPREAD across the survivors after a kill."""
        import ray_tpu
        from ray_tpu.util.scheduling_strategies import (
            NodeAffinitySchedulingStrategy,
        )

        @ray_tpu.remote(max_retries=3)
        def _probe():
            return "ok"

        def strategy():
            # Re-evaluated every round: the victim can die (a drain or
            # kill racing this probe) mid-wait, and pinning every
            # remaining round to a corpse would read as a violation.
            if victim_node_id is not None:
                try:
                    if any(n["NodeID"] == victim_node_id and n["Alive"]
                           for n in ray_tpu.nodes()):
                        return NodeAffinitySchedulingStrategy(
                            victim_node_id)
                except Exception:
                    pass
            return "SPREAD"

        # Generous deadline: on a saturated CI box, kill recovery is
        # death-detection (~5s) + worker cold-forks, which stretches
        # arbitrarily under load — a tight bound here reads as a fake
        # invariant violation.
        deadline = time.monotonic() + 120.0
        while time.monotonic() < deadline:
            if self._stop.is_set():
                return  # soak is settling: don't probe a closing cluster
            try:
                ref = _probe.options(
                    scheduling_strategy=strategy()).remote()
                if ray_tpu.get(ref, timeout=10.0) == "ok":
                    self.mttr_ms.append(
                        (time.monotonic() - t_fault) * 1e3)
                    return
            except Exception:
                pass
            time.sleep(0.1)
        if not self._stop.is_set():
            self.violations.append(
                f"{fault}: no successful probe within 120s of injection")

    def _fault_loop(self, cluster) -> None:
        from ray_tpu.cluster.rpc import channel_chaos
        from ray_tpu.util import failpoints

        classes = ["partition", "delay", "sever", "kill", "failpoint"]
        # One kill max per soak (each kill spends a node + respawn);
        # everything else repeats on the seeded schedule.
        killed = False
        while not self._stop.is_set():
            time.sleep(self.rng.uniform(1.0, 2.5))
            if self._stop.is_set():
                return
            victims = [n for n in cluster.nodes[1:]  # node 0 = driver's
                       if n is not self._drain_victim]
            if not victims:
                continue
            victim = self.rng.choice(victims)
            fault = self.rng.choice(classes)
            if fault == "kill" and killed:
                fault = "partition"
            if fault == "kill" and victim is self._dataflow_node:
                fault = "partition"  # see _dataflow_node comment
            t0 = time.monotonic()
            try:
                if fault == "partition":
                    # Shorter than the heartbeat-death window: the cut
                    # must be invisible to the application.
                    cluster.partition([["head"], [victim]])
                    time.sleep(self.rng.uniform(0.5, 2.0))
                    cluster.heal()
                elif fault == "delay":
                    rid = channel_chaos.add_rule(
                        "delay", dst=[victim.address],
                        arg=(0.005, 0.05), label="soak")
                    time.sleep(self.rng.uniform(1.0, 3.0))
                    channel_chaos.remove(rid)
                elif fault == "sever":
                    rid = channel_chaos.add_rule(
                        "sever", src=[victim.address],
                        dst=[cluster.head.address],
                        prob=0.3, label="soak")
                    time.sleep(self.rng.uniform(1.0, 3.0))
                    channel_chaos.remove(rid)
                elif fault == "kill":
                    killed = True
                    cluster.kill_node(victim)
                    cluster.add_node(num_cpus=4)  # replacement capacity
                elif fault == "failpoint":
                    arm = self.rng.choice([
                        {"agent.worker_events.upload": "raise,p=0.3"},
                        {"head.snapshot.before_persist": "raise"},
                        {"client.flush_refs.before": "delay:0.02"},
                        {"agent.heartbeat": "delay:0.2"},
                        # LLM engine scheduler faults: a delayed decode
                        # step and a flaky admission — the engine must
                        # requeue/recover and every probe stream still
                        # finish, shed typed, or fail fast.
                        {"serve.llm.before_step": "delay:0.08"},
                        {"serve.llm.before_admit": "raise,p=0.5"},
                    ])
                    # Engine replicas are worker processes: serve.llm
                    # sites need the cluster-wide control-plane fanout;
                    # the head/agent/driver sites arm locally (the
                    # in-process cluster shares this failpoint table).
                    if any(s.startswith("serve.llm.") for s in arm):
                        from ray_tpu import state

                        setter = state.set_failpoints
                    else:
                        setter = failpoints.set_failpoints
                    setter(arm)
                    time.sleep(self.rng.uniform(1.0, 3.0))
                    setter({site: None for site in arm})
            except Exception as e:
                from ray_tpu.util import metrics as _metrics

                _metrics.count_loop_restart("soak.fault")
                self.violations.append(f"injecting {fault}: {e!r}")
                continue
            self.faults[fault] = self.faults.get(fault, 0) + 1
            self._probe_mttr(
                fault, t0,
                victim_node_id=None if fault == "kill"
                else victim.node_id)

    # -- workload ----------------------------------------------------------

    def _workload(self, cluster, deadline: float) -> None:
        import ray_tpu

        @ray_tpu.remote
        def work(i):
            time.sleep(0.02)
            return i * i

        @ray_tpu.remote
        class Tally:
            def __init__(self):
                self.n = 0

            def bump(self):
                self.n += 1
                return 1

        actors = [Tally.options(max_restarts=-1,
                                max_task_retries=-1).remote()
                  for _ in range(2)]
        rng = random.Random(f"{self.seed}:workload")
        batch = 0
        while time.monotonic() < deadline:
            batch += 1
            n = rng.randint(6, 12)
            # SPREAD so the tasks actually land on victim nodes (the
            # default hybrid policy would keep them on the driver's).
            refs = [work.options(scheduling_strategy="SPREAD").remote(i)
                    for i in range(n)]
            call_refs = [a.bump.remote() for a in actors]
            payload = os.urandom(rng.randint(1 << 10, 64 << 10))
            put_ref = ray_tpu.put(payload)
            try:
                results = ray_tpu.get(refs, timeout=120.0)
                if results != [i * i for i in range(n)]:
                    self.violations.append(
                        f"batch {batch}: wrong task results {results!r}")
                else:
                    self.tasks_ok += n
                for r in ray_tpu.get(call_refs, timeout=120.0):
                    if r != 1:
                        self.violations.append(
                            f"batch {batch}: actor call returned {r!r}")
                    else:
                        self.actor_calls_ok += 1
                back = ray_tpu.get(put_ref, timeout=60.0)
                if back != payload:
                    self.violations.append(
                        f"batch {batch}: put/get roundtrip corrupted")
                else:
                    self.puts_ok += 1
            except Exception as e:
                self.violations.append(
                    f"batch {batch}: driver-visible error {e!r}")
            del put_ref

    def _serve_probe_setup(self) -> "object | None":
        """Deploy the standing serve probe and verify one warm-up round
        trip BEFORE any fault is injected (so the invariant separates
        'serve broke under faults' from 'serve never worked')."""
        from ray_tpu import serve

        @serve.deployment(name="soak_probe", num_replicas=2)
        def probe_fn(x):
            return x

        handle = serve.run(probe_fn.bind())
        import ray_tpu

        if ray_tpu.get(handle.remote(41), timeout=60.0) != 41:
            raise RuntimeError("serve probe warm-up returned wrong value")
        self.serve_ok += 1
        return handle

    def _serve_probe_loop(self, handle, deadline: float) -> None:
        """Standing serve invariant under faults: every probe request
        must either complete or fail FAST and cleanly (a deadline shed,
        a replica error while the controller re-reconciles) — a request
        that HANGS past its budget means the request path lost a
        request without shedding it, which is the one behavior a
        latency SLO cannot absorb."""
        import ray_tpu

        while time.monotonic() < deadline and not self._stop.is_set():
            t0 = time.monotonic()
            try:
                r = ray_tpu.get(
                    handle.options(deadline_s=20.0).remote(7),
                    timeout=45.0)
                if r == 7:
                    self.serve_ok += 1
                else:
                    self.violations.append(
                        f"serve probe returned wrong value {r!r}")
            except Exception:  # noqa: BLE001 — classified by duration
                took = time.monotonic() - t0
                if self._stop.is_set():
                    return  # settling cluster: not a verdict
                if took > 40.0:
                    self.violations.append(
                        f"serve probe HUNG {took:.1f}s (neither "
                        f"completed nor shed cleanly)")
                else:
                    self.serve_shed += 1
            time.sleep(0.5)

    def _llm_probe_setup(self):
        """Deploy the standing streaming-LLM probe (a small always-on
        continuous-batching engine) and prove one full stream BEFORE any
        fault is injected."""
        from ray_tpu import serve
        from ray_tpu.serve.llm_engine import LLMEngine

        eng = serve.deployment(
            name="soak_llm", num_replicas=1,
            max_concurrent_queries=16)(LLMEngine)
        handle = serve.run(eng.bind(
            model="gpt2", max_batch=2, cache_len=32, max_prompt_len=8,
            max_new_tokens=4))
        toks = [t for ch in handle.stream([3, 1, 4], 4) for t in ch]
        if len(toks) != 4:
            raise RuntimeError(
                f"llm probe warm-up stream incomplete: {toks!r}")
        self.llm_ok += 1
        return handle

    def _llm_probe_loop(self, handle, deadline: float) -> None:
        """Standing mid-stream invariant under faults: every probe
        stream must FINISH (all tokens, in order), shed TYPED, or fail
        fast — a stream that hangs past 40s through a partition/kill
        lost tokens the decode plane never accounted for, which is the
        one behavior the never-hang contract cannot absorb."""
        from ray_tpu.serve._observability import RequestShedError

        while time.monotonic() < deadline and not self._stop.is_set():
            t0 = time.monotonic()
            try:
                toks = [t for ch in handle.options(
                    deadline_s=20.0).stream([7, 2, 9], 4) for t in ch]
                if len(toks) == 4:
                    self.llm_ok += 1
                else:
                    self.violations.append(
                        f"llm probe stream incomplete: {toks!r}")
            except RequestShedError:
                self.llm_shed += 1
            except Exception:  # noqa: BLE001 — classified by duration
                took = time.monotonic() - t0
                if self._stop.is_set():
                    return
                if took > 40.0:
                    self.violations.append(
                        f"llm probe stream HUNG {took:.1f}s (neither "
                        f"finished, shed, nor failed fast)")
                else:
                    self.llm_failed_fast += 1
            time.sleep(0.8)

    def _train_probe(self, deadline: float) -> None:
        """Standing train invariant under faults: a small checkpointing
        trial must keep reporting steps — or restart cleanly from its
        checkpoint — for the whole fault schedule, and its downtime
        ledger must attribute every non-productive second to a cause
        (a gap the ledger can't explain means the goodput plane lost
        track of the trial)."""
        from ray_tpu import train
        from ray_tpu.train import session
        from ray_tpu.train.checkpoint import Checkpoint

        steps = max(6, int(self.duration_s / 0.6))

        def train_fn(config):
            start = 0
            ckpt = session.get_checkpoint()
            if ckpt is not None:
                start = ckpt.to_dict().get("step", -1) + 1
            for i in range(start, config["steps"]):
                time.sleep(0.4)
                session.report(
                    {"step": i},
                    checkpoint=Checkpoint.from_dict({"step": i}))

        try:
            result = train.DataParallelTrainer(
                train_fn,
                train_loop_config={"steps": steps},
                scaling_config=train.ScalingConfig(num_workers=1),
                run_config=train.RunConfig(
                    failure_config=train.FailureConfig(max_failures=8)),
            ).fit()
        except Exception as e:  # noqa: BLE001
            if not self._stop.is_set():
                self.violations.append(f"train probe crashed: {e!r}")
            return
        if result.error is not None:
            self.violations.append(
                f"train probe ended in error: {result.error!r}")
            return
        self.train_reports = len(result.metrics_history)
        gp = result.goodput or {}
        self.train_goodput = gp
        if not result.metrics or result.metrics.get("step") != steps - 1:
            self.violations.append(
                f"train probe lost steps: last metrics "
                f"{result.metrics!r}")
        by_cause = gp.get("by_cause") or {}
        if abs(sum(by_cause.values())
               - (gp.get("downtime_s") or 0.0)) > 1e-6:
            self.violations.append(
                f"train probe downtime not fully attributed: "
                f"{gp!r}")
        if any(not c for c in by_cause):
            self.violations.append(
                f"train probe downtime with empty cause: {by_cause!r}")

    def _gang_probe(self) -> None:
        """Standing PG-migration invariant: an elastic gang trial
        (num_workers=2, min_workers=1, max_failures=0) holding a
        placement group through the whole seeded kill/drain schedule
        must COMPLETE — its reservation migrates (RESCHEDULING ->
        CREATED on healthy nodes) instead of dying, every lost second
        lands in the ledger under a preemption/drain/reschedule cause,
        and the failure budget stays untouched (completing with
        max_failures=0 proves it)."""
        from ray_tpu import train
        from ray_tpu.train import session
        from ray_tpu.train.checkpoint import Checkpoint

        steps = max(6, int(self.duration_s / 0.6))

        def train_fn(config):
            start = 0
            ckpt = session.get_checkpoint()
            if ckpt is not None:
                start = ckpt.to_dict().get("step", -1) + 1
            for i in range(start, config["steps"]):
                time.sleep(0.4)
                session.report(
                    {"step": i},
                    checkpoint=Checkpoint.from_dict({"step": i}))

        trainer = train.DataParallelTrainer(
            train_fn,
            train_loop_config={"steps": steps},
            scaling_config=train.ScalingConfig(
                num_workers=2, min_workers=1,
                placement_strategy="SPREAD",
                resources_per_worker={"CPU": 1}),
            run_config=train.RunConfig(
                failure_config=train.FailureConfig(max_failures=0)),
        )
        try:
            result = trainer.fit()
        except Exception as e:  # noqa: BLE001
            if not self._stop.is_set():
                self.violations.append(f"gang probe crashed: {e!r}")
            return
        if result.error is not None:
            self.violations.append(
                f"gang probe burned its failure budget "
                f"(max_failures=0): {result.error!r}")
            return
        if not result.metrics or result.metrics.get("step") != steps - 1:
            self.violations.append(
                f"gang probe lost steps: last metrics "
                f"{result.metrics!r}")
        from ray_tpu.util.goodput import attribution_ok

        gp = result.goodput or {}
        self.gang_goodput = gp
        planned, sums = attribution_ok(gp)
        if not sums:
            self.violations.append(
                f"gang probe downtime not fully attributed: {gp!r}")
        if not planned:
            self.violations.append(
                f"gang probe downtime with unplanned cause(s) "
                f"(every second must be preemption/drain/reschedule): "
                f"{gp.get('by_cause')!r}")
        final_pg = trainer.final_pg_state or {}
        self.gang_reschedules = final_pg.get("reschedules", 0)
        if final_pg.get("state") != "CREATED":
            self.violations.append(
                f"gang probe PG did not end ALIVE: "
                f"{final_pg.get('state')!r}")
        else:
            import ray_tpu

            try:
                alive = {n["NodeID"] for n in ray_tpu.nodes()
                         if n["Alive"]}
                stale = [nid for nid, _bi in
                         final_pg.get("placement", [])
                         if nid not in alive]
                if stale:
                    self.violations.append(
                        f"gang probe PG placed on dead node(s) "
                        f"{stale!r} at completion")
            except Exception:
                pass

    def _drain_once(self, cluster) -> None:
        """One graceful drain mid-soak with a budget-exemption probe: a
        max_retries=0 task pinned to the drained node must complete."""
        import ray_tpu
        from ray_tpu.util.scheduling_strategies import (
            NodeAffinitySchedulingStrategy,
        )

        victims = [n for n in cluster.nodes[1:]
                   if n is not self._dataflow_node]
        if not victims:
            return
        victim = self.rng.choice(victims)
        self._drain_victim = victim  # injector steers clear of it

        @ray_tpu.remote(max_retries=0)
        def fragile():
            time.sleep(1.5)
            return "exempt-ok"

        ref = fragile.options(
            scheduling_strategy=NodeAffinitySchedulingStrategy(
                victim.node_id)).remote()
        time.sleep(0.4)  # in flight on the victim
        try:
            res = cluster.head.rpc_drain_node(
                victim.node_id, "soak-drain", 1.0)
            if not res.get("ok"):
                self.violations.append(f"drain refused: {res!r}")
            if victim in cluster.nodes:
                cluster.nodes.remove(victim)
                victim.stop()
            if ray_tpu.get(ref, timeout=120.0) != "exempt-ok":
                self.violations.append(
                    "drain-exempt task returned wrong value")
        except Exception as e:
            self.violations.append(
                f"retry-budget exemption violated (max_retries=0 task "
                f"lost to a drain did not complete): {e!r}")
        self.faults["drain"] = self.faults.get("drain", 0) + 1

    # -- streaming-dataflow probe ------------------------------------------

    def _dataflow_probe_setup(self, cluster):
        """Add the probe's dedicated SMALL-store node (12 MiB): every
        probe round pushes ~2x its capacity through it, so dynamic
        splitting + spill-to-URI + restore run continuously while the
        fault schedule rages. The whole soak cluster spills to the
        shared URI (config set before cluster boot)."""
        node = cluster.add_node(num_cpus=2, store_capacity=12 << 20,
                                resources={"dataflow_probe": 8})
        cluster.wait_for_nodes()
        self._dataflow_node = node
        return node

    def _dataflow_probe_loop(self, deadline: float) -> None:
        """Standing invariant: every round of the generation->map->
        consume pipeline under memory pressure either completes or
        fails typed within the round budget — a hang is a violation.
        At least one round must complete over the soak."""
        import numpy as np

        import ray_tpu
        from ray_tpu import data

        @ray_tpu.remote(resources={"dataflow_probe": 1}, max_retries=3)
        def gen(seed):
            rng = np.random.default_rng(seed)
            # ~1 MiB per block, 16 blocks/round = ~16 MiB through a
            # 12 MiB store (plus the map stage's output copy).
            return {"tokens": rng.random((4096, 64), dtype=np.float32)}

        rounds = 0
        while time.monotonic() < deadline and not self._stop.is_set():
            t0 = time.monotonic()
            try:
                # 90s: the box runs every standing probe (serve, llm,
                # train, gang, signal, autoscaler fleet) concurrently —
                # generation on the 2-CPU probe node is the round's
                # long pole, and the budget must absorb co-probe load
                # spikes while staying under the 150s hang threshold.
                refs = [gen.remote(rounds * 100 + i) for i in range(16)]
                done, _ = ray_tpu.wait(refs, num_returns=len(refs),
                                       timeout=90.0)
                if len(done) < len(refs):
                    raise RuntimeError(
                        f"generation incomplete ({len(done)}/16)")
                ds = data.Dataset(list(refs)).map_batches(
                    lambda b: {"tokens": b["tokens"] * 2.0})
                n = 0
                for _batch in ds.iter_batches(batch_size=1024):
                    n += 1
                if n <= 0:
                    raise RuntimeError("pipeline yielded no batches")
                self.dataflow_ok += 1
            except Exception:
                # Typed failure under chaos is allowed (a partitioned
                # probe node parks generation); hanging is not.
                self.dataflow_failed += 1
            if self._stop.is_set():
                return  # settling cluster: not a verdict
            took = time.monotonic() - t0
            if took > 150.0:
                self.violations.append(
                    f"dataflow probe round HUNG {took:.1f}s (neither "
                    f"completing nor failing fast)")
                return
            # Peak spilled-object count on the shared target (frees
            # drain the target between rounds, so sample at the round
            # boundary where pressure is highest).
            try:
                st = self._dataflow_node.rpc_store_stats()
                self.dataflow_spilled = max(
                    self.dataflow_spilled,
                    int(st.get("spilled_objects", 0)))
            except Exception:
                pass
            rounds += 1

    def _signal_probe_setup(self) -> bool:
        """Register a sentinel SLO that can never legitimately burn:
        any burning/recovery transition over the soak is the evaluator
        flapping on scrape gaps, not a real breach."""
        from ray_tpu import state

        st = state.slo_status()
        if not st.get("ok", False):
            return False  # signal plane disabled: nothing to probe
        reg = state.register_slo("soak-sentinel",
                                 "qps < 1000000 over 10s")
        if not reg.get("ok"):
            return False
        # Prove one query round trip BEFORE faults start (the serve
        # probe's discipline): under the fault schedule a saturated box
        # can starve every later round, and "never completed a query"
        # must mean the plane broke, not that the probe never got a
        # healthy turn.
        if state.query_metrics({"op": "gauge_last",
                                "name": "ray_tpu_node_worker_count",
                                "window_s": 60.0}).get("ok"):
            self.signal_queries_ok += 1
        return True

    def _signal_probe_loop(self, deadline: float) -> None:
        """Standing invariant: the head's history ring keeps answering
        windowed queries while agents are partitioned/killed — the ring
        is head-local state, so a partition starves it of NEW samples
        but must never make a query stall or error. A stalled query is
        a violation; per-round results are counted for the evidence
        line."""
        from ray_tpu import state

        while time.monotonic() < deadline and not self._stop.is_set():
            t0 = time.monotonic()
            try:
                res = state.query_metrics({
                    "op": "gauge_last",
                    "name": "ray_tpu_node_worker_count",
                    "window_s": 60.0})
                if res.get("ok"):
                    self.signal_queries_ok += 1
                else:
                    self.signal_queries_failed += 1
            except Exception:
                self.signal_queries_failed += 1
            if self._stop.is_set():
                return  # settling cluster: not a verdict
            took = time.monotonic() - t0
            if took > 30.0:
                self.violations.append(
                    f"signal query STALLED {took:.1f}s under faults "
                    f"(the ring must answer from head-local history)")
                return
            time.sleep(0.5)

    # -- autoscaler probe --------------------------------------------------

    def _autoscaler_probe_setup(self, cluster) -> bool:
        """Stand up a ``LocalNodeProvider`` fleet the fault schedule
        rides: fleet demand uses a custom resource only autoscaler-
        launched nodes carry, so every probe round exercises the full
        scale-up path (bin-pack -> create_node -> boot -> schedule) and
        the teardown exercises drain-before-terminate scale-down. A
        provider terminate of a node the head still reports ALIVE is an
        instant violation (goodput-loss scale-down). One clean round
        runs here, BEFORE faults start; then ``create_node`` itself is
        put on the seeded fault schedule so the backoff/quarantine boot
        loop earns its keep."""
        import ray_tpu
        from ray_tpu.autoscaler import LocalNodeProvider, StandardAutoscaler

        provider = LocalNodeProvider(cluster)
        real_terminate = provider.terminate_node

        def checked_terminate(node_id):
            try:
                alive = any(n["NodeID"] == node_id and n["Alive"]
                            for n in cluster.head.rpc_nodes())
            except Exception:
                alive = False
            if alive:
                self.violations.append(
                    f"autoscaler terminated {node_id[:12]} while the "
                    f"head still reported it ALIVE (drain-before-"
                    f"terminate violated)")
            real_terminate(node_id)

        provider.terminate_node = checked_terminate
        self._as_provider = provider
        self._as_cluster = cluster
        self._autoscaler = StandardAutoscaler(
            cluster.address, provider,
            node_types={
                # Catalog order is the packer's preference order: spot
                # first (Podracer economics — preemptible is the normal
                # case), on-demand as the quarantine fall-through.
                "fleet_spot": {"num_cpus": 2,
                               "resources": {"fleet": 2}, "spot": True},
                "fleet_ondemand": {"num_cpus": 2,
                                   "resources": {"fleet": 2}},
            },
            max_workers=3,
            idle_timeout_s=1.5,
            launch_cooldown_s=0.2,
            backoff_base_s=0.2,
            backoff_max_s=1.0,
            quarantine_failures=3,
            quarantine_cooldown_s=3.0,
        )

        @ray_tpu.remote(num_cpus=1, resources={"fleet": 1}, max_retries=5)
        def fleet_work(i):
            time.sleep(0.05)
            return i

        self._fleet_work = fleet_work
        self._autoscaler_round(0, budget_s=30.0)
        self.autoscaler_rounds_ok += 1
        # From here on, launches fail on the seeded schedule: with two
        # feasible types, backoff + quarantine fall-through must keep
        # demand satisfiable anyway. (Settle's failpoints.reset()
        # disarms this before the end-state round.)
        from ray_tpu.util import failpoints

        failpoints.set_failpoints(
            {"autoscaler.before_create": "raise:chaos,p=0.25"})
        return True

    def _autoscaler_round(self, tag: int, budget_s: float,
                          heed_stop: bool = True) -> None:
        """One demand burst: submit fleet-only tasks (no standing node
        carries the resource), pump the reconcile loop until all land.
        Raises if the budget expires with demand unsatisfied.
        ``heed_stop`` aborts at soak teardown (mid-soak rounds only —
        the end-state round runs AFTER settle, with ``_stop`` set)."""
        import ray_tpu

        refs = [self._fleet_work.remote(tag * 10 + i) for i in range(4)]
        pending = list(refs)
        pump_deadline = time.monotonic() + budget_s
        while pending and time.monotonic() < pump_deadline:
            report = self._autoscaler.update()
            self.autoscaler_launches += len(report["launched"])
            self.autoscaler_launch_failures += len(
                report["launch_failures"])
            self.autoscaler_scale_downs += len(report["terminated"])
            _, pending = ray_tpu.wait(
                pending, num_returns=len(pending), timeout=1.0)
            if pending and heed_stop and self._stop.is_set():
                raise RuntimeError("soak stopping mid-round")
        if pending:
            raise RuntimeError(
                f"fleet demand unsatisfied ({len(pending)}/4 pending "
                f"after {budget_s:.0f}s)")
        ray_tpu.get(refs, timeout=10.0)

    def _autoscaler_preempt_drill(self) -> bool:
        """Simulate a provider preemption notice on one live spot fleet
        node: drain(reason="preemption"). The reconcile loop must
        reclaim the slot and close the ledger with cause
        ``preemption``."""
        a = self._autoscaler
        live = set(self._as_provider.non_terminated_nodes())
        spots = [nid for nid, t in a._node_type_of.items()
                 if t == "fleet_spot" and nid in live
                 and nid not in a._draining]
        if not spots:
            return False
        self._as_cluster.head.rpc_drain_node(
            spots[0], "preemption", 10.0, wait=False)
        self.autoscaler_preemptions += 1
        return True

    def _autoscaler_probe_loop(self, deadline: float) -> None:
        """Standing invariant: a fleet-only demand burst is satisfied
        through autoscaler scale-up within the round budget even while
        faults land on the launched nodes and ``create_node`` itself
        fails on the seeded schedule. A round may fail typed under
        chaos; hanging is a violation. One round rides a simulated spot
        preemption."""
        preempted = False
        tag = 1
        while time.monotonic() < deadline and not self._stop.is_set():
            t0 = time.monotonic()
            try:
                self._autoscaler_round(tag, budget_s=45.0)
                self.autoscaler_rounds_ok += 1
                if not preempted:
                    preempted = self._autoscaler_preempt_drill()
            except Exception:
                if self._stop.is_set():
                    return  # settling cluster: not a verdict
                self.autoscaler_rounds_failed += 1
            took = time.monotonic() - t0
            if took > 120.0:
                self.violations.append(
                    f"autoscaler probe round HUNG {took:.1f}s (fleet "
                    f"demand neither satisfied nor failing fast)")
                return
            tag += 1
            # Gentle cadence: the soak box runs every other standing
            # probe too, and this one spawns node agents.
            time.sleep(1.0)

    def _autoscaler_end_state(self, cluster) -> None:
        """Post-storm verdicts: demand still satisfiable (no stuck
        quarantine — the schedule is over and cooldowns expired), fleet
        scales to zero with every termination drained first, and the
        head's terminate ledger is fully cause-attributed."""
        a = self._autoscaler
        try:
            try:
                self._autoscaler_round(999, budget_s=30.0,
                                       heed_stop=False)
                self.autoscaler_rounds_ok += 1
            except Exception as e:  # noqa: BLE001
                self.violations.append(
                    f"autoscaler demand unsatisfied after soak (stuck "
                    f"quarantine/backoff?): {e!r}")
            self.autoscaler_quarantines = sum(
                1 for st in a._type_state.values()
                if st.quarantined_until > 0)
            # Zero-goodput-loss scale-down: idle the whole fleet out.
            # The provider hook asserts drained-first on every
            # terminate; the ledger check below does attribution.
            a.idle_timeout_s = 0.0
            sd_deadline = time.monotonic() + 30.0
            while (self._as_provider.non_terminated_nodes()
                   and time.monotonic() < sd_deadline):
                report = a.update()
                self.autoscaler_scale_downs += len(report["terminated"])
                time.sleep(0.1)
            if self._as_provider.non_terminated_nodes():
                self.violations.append(
                    "autoscaler fleet failed to scale to zero after "
                    "the soak")
            with cluster.head._lock:
                acks = {nid: rec["cause"] for nid, rec
                        in cluster.head._terminate_acks.items()}
            fleet_acks = {nid: c for nid, c in acks.items()
                          if nid in set(a.launched)}
            bad = {nid[:12]: c for nid, c in fleet_acks.items()
                   if not (c == "preemption" or c.startswith("drain:")
                           or c.startswith("failure:"))}
            if bad:
                self.violations.append(
                    f"unattributed fleet terminations in ledger: {bad}")
            if (self.autoscaler_preemptions
                    and "preemption" not in fleet_acks.values()):
                self.violations.append(
                    "spot preemption not attributed as 'preemption' "
                    "in the terminate ledger")
        finally:
            a.stop()

    # -- invariants --------------------------------------------------------

    def _check_invariants(self, cluster) -> None:
        from ray_tpu import state

        # Leak sweeper: nothing flagged after settle.
        try:
            leaks = state.memory_leaks()
            if leaks:
                self.violations.append(
                    f"memory_leaks non-empty after settle: "
                    f"{[r['object_id'][:16] for r in leaks]}")
        except Exception as e:
            self.violations.append(f"memory_leaks unreachable: {e!r}")
        # Federated scrape still serves the whole cluster.
        try:
            from ray_tpu.cluster.gcs_client import GcsClient

            gcs = GcsClient(cluster.address)
            try:
                body = gcs.metrics.cluster_text()
            finally:
                gcs.close()
            if "ray_tpu_" not in body:
                self.violations.append(
                    "federated /metrics/cluster body has no ray_tpu_ "
                    "series")
        except Exception as e:
            self.violations.append(f"/metrics/cluster scrape: {e!r}")
        # Head directory consistent with the agent stores: no location
        # pointing at a dead node, and the per-node store reports join.
        try:
            alive = {n["NodeID"] for n in state.nodes() if n["Alive"]}
            for rec in state.list_objects(limit=10_000):
                stale = set(rec.get("locations") or ()) - alive
                if stale:
                    self.violations.append(
                        f"directory entry {rec['object_id'][:16]} "
                        f"located on dead node(s) {sorted(stale)}")
            for rep in state.object_store_stats():
                if rep.get("node_id") not in alive:
                    self.violations.append(
                        f"store report from non-alive node "
                        f"{rep.get('node_id')!r}")
        except Exception as e:
            self.violations.append(f"directory/store check: {e!r}")
        # No leaked per-node bundle reservations: every reservation an
        # agent still holds must be explained by a live group's
        # placement on that node (a failed/rolled-back 2PC round or a
        # kill mid-2PC must never strand a carve-out). Settle-retried:
        # an in-flight reschedule's PREPARED bundles (or a post-remove
        # rollback still in the coordinator's hands) are a transient,
        # self-correcting state, not a leak — only a PERSISTENT orphan
        # is a violation.
        def _bundle_leaks() -> list:
            pgs = cluster.head.rpc_placement_group_table() or {}
            expected: set = set()
            pending_pgs = set()
            for pg_id, pg in pgs.items():
                if pg.get("state") in ("CREATED", "RESCHEDULING"):
                    for nid, bi in pg.get("placement", []):
                        expected.add((nid, f"{pg_id}:{bi}"))
                elif pg.get("state") == "PENDING":
                    # A queued group's reserve 2PC may legitimately
                    # hold PREPARED bundles with placement still [] —
                    # its prepares can block in pool.acquire for up to
                    # 60s, past the settle window below.
                    pending_pgs.add(pg_id)
            leaks = []
            for node in list(cluster.nodes):
                try:
                    held = node.rpc_bundle_table()
                except Exception:
                    continue  # node stopping: nothing held
                for key in held:
                    if key.rsplit(":", 1)[0] in pending_pgs:
                        continue
                    if (node.node_id, key) not in expected:
                        leaks.append(
                            f"leaked bundle reservation {key} on node "
                            f"{node.node_id[-12:]} (no live placement "
                            f"group explains it)")
            return leaks

        try:
            leak_deadline = time.monotonic() + 30.0
            leaks = _bundle_leaks()
            while leaks and time.monotonic() < leak_deadline:
                time.sleep(1.0)
                leaks = _bundle_leaks()
            self.violations.extend(leaks)
        except Exception as e:
            self.violations.append(f"bundle-leak check: {e!r}")

    # -- driver ------------------------------------------------------------

    def run(self) -> dict:
        import ray_tpu
        from ray_tpu.cluster.cluster_utils import Cluster
        from ray_tpu.core.config import config

        # One knob seeds every chaos RNG in this process AND (via env)
        # every process the cluster spawns; restored on exit so an
        # in-process caller doesn't inherit the soak's seed.
        prev_env_seed = os.environ.get("RAY_TPU_CHAOS_SEED")
        os.environ["RAY_TPU_CHAOS_SEED"] = str(self.seed)
        config.override("chaos_seed", self.seed)
        # The streaming-dataflow probe's relief valve: the whole soak
        # cluster spills to one shared URI (so a killed node's spilled
        # objects restore instead of recomputing), and a small split
        # target keeps the probe's ~1 MiB blocks splitting for real.
        import shutil
        import tempfile

        spill_dir = tempfile.mkdtemp(prefix="ray_tpu_soak_spill_")
        config.override("spill_uri", f"file://{spill_dir}")
        config.override("target_block_size_bytes", 256 << 10)
        try:
            return self._run_seeded(ray_tpu, Cluster)
        finally:
            if prev_env_seed is None:
                os.environ.pop("RAY_TPU_CHAOS_SEED", None)
            else:
                os.environ["RAY_TPU_CHAOS_SEED"] = prev_env_seed
            config.reset("chaos_seed")
            config.reset("spill_uri")
            config.reset("target_block_size_bytes")
            shutil.rmtree(spill_dir, ignore_errors=True)

    def _run_seeded(self, ray_tpu, Cluster) -> dict:
        ray_tpu.shutdown()
        cluster = Cluster()
        cluster.add_node(num_cpus=4)  # driver node: survives
        for _ in range(self.n_victims):
            cluster.add_node(num_cpus=4)
        cluster.wait_for_nodes()
        ray_tpu.init(cluster.address)
        deadline = time.monotonic() + self.duration_s
        # Serve probe deploys (and proves one round trip) BEFORE faults
        # start; under faults its standing invariant is complete-or-
        # shed-cleanly, never hang.
        serve_handle = None
        try:
            serve_handle = self._serve_probe_setup()
        except Exception as e:  # noqa: BLE001
            self.violations.append(f"serve probe deploy failed: {e!r}")
        llm_handle = None
        try:
            llm_handle = self._llm_probe_setup()
        except Exception as e:  # noqa: BLE001
            self.violations.append(f"llm probe deploy failed: {e!r}")
        dataflow_ready = False
        try:
            self._dataflow_probe_setup(cluster)
            dataflow_ready = True
        except Exception as e:  # noqa: BLE001
            self.violations.append(
                f"dataflow probe setup failed: {e!r}")
        signal_ready = False
        try:
            signal_ready = self._signal_probe_setup()
        except Exception as e:  # noqa: BLE001
            self.violations.append(f"signal probe setup failed: {e!r}")
        autoscaler_ready = False
        try:
            autoscaler_ready = self._autoscaler_probe_setup(cluster)
        except Exception as e:  # noqa: BLE001
            self.violations.append(
                f"autoscaler probe setup failed: {e!r}")
        injector = threading.Thread(
            target=self._fault_loop, args=(cluster,), daemon=True)
        injector.start()
        try:
            # First third: faults only; then one graceful drain rides
            # along; workload runs throughout.
            workload = threading.Thread(
                target=self._workload, args=(cluster, deadline),
                daemon=True)
            workload.start()
            train_probe = threading.Thread(
                target=self._train_probe, args=(deadline,), daemon=True)
            train_probe.start()
            gang_probe = threading.Thread(
                target=self._gang_probe, daemon=True)
            gang_probe.start()
            if serve_handle is not None:
                threading.Thread(
                    target=self._serve_probe_loop,
                    args=(serve_handle, deadline), daemon=True).start()
            if llm_handle is not None:
                threading.Thread(
                    target=self._llm_probe_loop,
                    args=(llm_handle, deadline), daemon=True).start()
            if dataflow_ready:
                threading.Thread(
                    target=self._dataflow_probe_loop,
                    args=(deadline,), daemon=True).start()
            if signal_ready:
                threading.Thread(
                    target=self._signal_probe_loop,
                    args=(deadline,), daemon=True).start()
            if autoscaler_ready:
                threading.Thread(
                    target=self._autoscaler_probe_loop,
                    args=(deadline,), daemon=True).start()
            time.sleep(min(self.duration_s / 3.0, 10.0))
            self._drain_once(cluster)
            workload.join(timeout=self.duration_s + 180.0)
            if workload.is_alive():
                self.violations.append("workload wedged past deadline")
            # The trial restarts from checkpoint under kills: give it
            # the same generous settle the workload gets before calling
            # a hang.
            train_probe.join(timeout=self.duration_s + 240.0)
            if train_probe.is_alive():
                self.violations.append(
                    "train probe wedged past deadline (neither "
                    "reporting nor restarting)")
            # The gang trial rides the same kill/drain schedule and may
            # spend windows SHRUNK waiting for bundle reschedules: give
            # it the train probe's settle budget too.
            gang_probe.join(timeout=self.duration_s + 240.0)
            if gang_probe.is_alive():
                self.violations.append(
                    "gang probe wedged past deadline (gang neither "
                    "completing, shrinking, nor regrowing)")
            # Fault quota: a soak that recovered slowly (MTTR probes
            # stretch the schedule on a loaded box) keeps injecting —
            # bounded — until at least 4 DISTINCT fault classes landed
            # (the drain rides along and doesn't count), so a short run
            # still earns its adversarial coverage instead of passing on
            # e.g. three delays and nothing else.
            quota_deadline = time.monotonic() + 2 * self.duration_s
            while (len(set(self.faults) - {"drain"}) < 4
                   and not self.violations
                   and time.monotonic() < quota_deadline):
                time.sleep(0.5)
        finally:
            self._stop.set()
            # The injector's MTTR probe can run up to 120s per fault;
            # the join must outlast it or an orphaned probe records
            # spurious violations into a settling cluster.
            injector.join(timeout=150.0)
        # Settle: heal everything, let frees/heartbeats drain.
        cluster.heal()
        from ray_tpu.cluster.rpc import channel_chaos
        from ray_tpu.util import failpoints

        channel_chaos.clear("soak")
        failpoints.reset()
        time.sleep(2.0)
        self._check_invariants(cluster)
        if serve_handle is not None and self.serve_ok < 1:
            self.violations.append(
                "serve probe never completed a request")
        if llm_handle is not None and self.llm_ok < 1:
            self.violations.append(
                "llm probe never completed a stream")
        if dataflow_ready:
            if self.dataflow_ok < 1:
                self.violations.append(
                    "dataflow probe never completed a round")
            # Restores are cumulative per agent and can land on any
            # live node (the head picks the restore target): sum the
            # survivors for the evidence line.
            for node in list(cluster.nodes):
                try:
                    self.dataflow_restores += int(
                        node.rpc_store_stats().get("spill_restores", 0))
                except Exception:
                    continue
        if signal_ready:
            from ray_tpu import state

            if self.signal_queries_ok < 1:
                self.violations.append(
                    "signal probe never completed a query")
            try:
                sent = (state.slo_status().get("slos") or {}).get(
                    "soak-sentinel") or {}
                # missed_evals counts held evaluations (scrape gaps
                # under partition) — evidence, not a fault. Any
                # transition on a can't-burn sentinel IS the evaluator
                # flapping on those gaps.
                self.signal_slo_transitions = int(
                    sent.get("transitions", 0))
                self.signal_missed_evals = int(
                    sent.get("missed_evals", 0))
                if self.signal_slo_transitions:
                    self.violations.append(
                        f"sentinel SLO flapped "
                        f"{self.signal_slo_transitions}x on scrape "
                        f"gaps (evaluator must hold state when the "
                        f"window has no samples)")
                state.remove_slo("soak-sentinel")
            except Exception as e:  # noqa: BLE001
                self.violations.append(
                    f"signal probe teardown: {e!r}")
        if autoscaler_ready:
            try:
                self._autoscaler_end_state(cluster)
            except Exception as e:  # noqa: BLE001
                self.violations.append(
                    f"autoscaler probe end-state: {e!r}")
        try:
            from ray_tpu import serve

            serve.shutdown()
        except Exception:
            pass
        # The seed makes any line replayable:
        # RAY_TPU_CHAOS_SEED=<seed> python -m ray_tpu.scripts.chaos_soak
        entry: dict = {
            "seed": self.seed,
            "duration_s": round(float(self.duration_s), 1),
            "faults": dict(self.faults),
            "faults_injected": sum(self.faults.values()),
            "violations": list(self.violations),
            "n_violations": len(self.violations),
            "tasks_ok": self.tasks_ok,
            "actor_calls_ok": self.actor_calls_ok,
            "puts_ok": self.puts_ok,
            "serve_ok": self.serve_ok,
            "serve_shed": self.serve_shed,
            "llm_ok": self.llm_ok,
            "llm_shed": self.llm_shed,
            "llm_failed_fast": self.llm_failed_fast,
            "train_reports": self.train_reports,
            "train_goodput": self.train_goodput,
            "gang_goodput": self.gang_goodput,
            "gang_reschedules": self.gang_reschedules,
            "dataflow_ok": self.dataflow_ok,
            "dataflow_failed": self.dataflow_failed,
            "dataflow_spilled": self.dataflow_spilled,
            "dataflow_restores": self.dataflow_restores,
            "signal_queries_ok": self.signal_queries_ok,
            "signal_queries_failed": self.signal_queries_failed,
            "signal_slo_transitions": self.signal_slo_transitions,
            "signal_missed_evals": self.signal_missed_evals,
            "autoscaler_rounds_ok": self.autoscaler_rounds_ok,
            "autoscaler_rounds_failed": self.autoscaler_rounds_failed,
            "autoscaler_launches": self.autoscaler_launches,
            "autoscaler_launch_failures": self.autoscaler_launch_failures,
            "autoscaler_quarantines": self.autoscaler_quarantines,
            "autoscaler_scale_downs": self.autoscaler_scale_downs,
            "autoscaler_preemptions": self.autoscaler_preemptions,
        }
        if self.mttr_ms:
            entry["mttr_ms"] = {
                "mean": round(sum(self.mttr_ms) / len(self.mttr_ms), 1),
                "max": round(max(self.mttr_ms), 1),
                "n": len(self.mttr_ms),
            }
        ray_tpu.shutdown()
        cluster.shutdown()
        return entry


def run(seed: int, duration_s: float = 20.0, n_victims: int = 2) -> dict:
    return _Soak(seed, duration_s, n_victims).run()


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--seed", type=int,
                        default=int(os.environ.get(
                            "RAY_TPU_CHAOS_SEED", "0")) or None,
                        help="chaos seed (default: RAY_TPU_CHAOS_SEED, "
                             "else random)")
    parser.add_argument("--duration", type=float, default=20.0)
    parser.add_argument("--victims", type=int, default=2)
    args = parser.parse_args(argv)
    seed = args.seed if args.seed is not None \
        else random.SystemRandom().randrange(1 << 31)
    entry = run(seed, args.duration, args.victims)
    print(json.dumps(entry, default=str))
    if entry["n_violations"]:
        print(f"CHAOS SOAK FAILED ({entry['n_violations']} violations); "
              f"replay with RAY_TPU_CHAOS_SEED={seed}", flush=True)
        return 1
    print(f"chaos soak passed: {entry['faults_injected']} faults "
          f"({entry['faults']}), seed={seed}", flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
