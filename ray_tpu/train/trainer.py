"""DataParallelTrainer: worker group + training loop + fault tolerance.

Reference parity (SURVEY.md §3.4): ``BaseTrainer.fit``
(``train/base_trainer.py:339``) -> ``DataParallelTrainer``
(``data_parallel_trainer.py:244``) -> ``BackendExecutor.start``
(``_internal/backend_executor.py:93``) creates a ``WorkerGroup`` of actors
in the trial's placement group, initializes per-worker sessions, runs the
user loop, and consumes results through ``TrainingIterator._fetch_next_result``
(``trainer.py:155``). Worker failure => group restart from the latest
checkpoint within ``FailureConfig.max_failures`` (elastic restart).

TPU-native difference: a worker is a *host*; the inner loop is a jitted
step over the host's device mesh, so the framework never touches gradients —
placement, sessions, checkpoints, and failure handling only.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

import ray_tpu
from ray_tpu.core import ids
from ray_tpu.core.object_ref import ActorError, TaskError
from ray_tpu.train.checkpoint import Checkpoint
from ray_tpu.train.config import (
    CheckpointConfig,
    FailureConfig,
    JaxConfig,
    RunConfig,
    ScalingConfig,
)
from ray_tpu.train import session as session_mod
from ray_tpu.util import (
    PlacementGroupSchedulingStrategy,
    placement_group,
    remove_placement_group,
)
from ray_tpu.util import goodput as _goodput
from ray_tpu.util.queue import Queue


@dataclass
class Result:
    metrics: Optional[dict]
    checkpoint: Optional[Checkpoint]
    error: Optional[BaseException] = None
    metrics_history: List[dict] = field(default_factory=list)
    # Downtime-ledger rollup for the whole fit(): wall_s, downtime_s,
    # by_cause (drain:<reason> / preemption / failure), restarts,
    # goodput_pct, per-rank last step seconds + skew.
    goodput: Optional[dict] = None


# The downtime ledger is shared with Tune trials (one accounting
# implementation): ray_tpu.util.goodput.GoodputLedger.
_GoodputLedger = _goodput.GoodputLedger


def _lost_to_drain(exc: BaseException) -> bool:
    """Did this failure come from the cluster's drain/preemption path?
    Matched against the HEAD-generated cause formats only ("node <id>
    died: drained: …" / "node <id> draining: …"), so an application
    error that merely mentions draining can never loop the trainer."""
    import re

    return re.search(
        r"node \S+ (died: drained:|draining:)", str(exc)) is not None


class TrainingWorkerPreempted(ActorError):
    """A node hosting training workers entered DRAINING (preemption
    notice / scale-down): the attempt restarts from the latest checkpoint
    PROACTIVELY — before the node dies — instead of waiting out a
    heartbeat timeout, and the restart does not consume
    ``FailureConfig.max_failures`` (the trainer-level analog of the
    task retry-budget preemption exemption)."""


class TrainingGroupResized(ActorError):
    """An elastic gang's placement group reports restored capacity
    (the head finished rescheduling lost bundles onto healthy nodes)
    while the current attempt runs at a SHRUNK world size: restart from
    the latest checkpoint at the larger size. A planned regrow, not a
    failure — exempt from ``FailureConfig.max_failures``; its downtime
    is attributed to the ``reschedule`` cause."""


class _TrainWorker:
    """Actor hosting one training worker (rank)."""

    def __init__(self, rank: int):
        self.rank = rank

    def node_id(self) -> str:
        """Which cluster node this worker landed on (for rank layout)."""
        import ray_tpu._private.worker as worker_mod

        return getattr(worker_mod.backend(), "node_id", "local")

    def setup_jax(
        self, group: str, rank: int, world_size: int,
        local_rank: int, local_world_size: int, jax_config,
    ) -> bool:
        """Join the group's jax.distributed runtime (Backend.on_start
        analog, ``train/torch/config.py:129-181``). Blocks until all
        ranks connect, so the trainer must call it on all workers
        concurrently."""
        import os

        from ray_tpu.parallel import distributed as dist

        os.environ["RAY_TPU_LOCAL_RANK"] = str(local_rank)
        dist.initialize(
            group, rank, world_size,
            platform=jax_config.platform,
            num_cpu_devices=jax_config.num_cpu_devices,
            timeout=jax_config.init_timeout,
        )
        return True

    def setup_torch(self, group: str, rank: int, world_size: int,
                    local_rank: int, torch_config) -> bool:
        """Join the group's torch.distributed process group (TorchTrainer
        backend hook; ``train/torch/config.py:129-181`` analog). Rank 0
        publishes its master addr/port through the cluster KV — the same
        rendezvous channel the JAX runtime uses."""
        import datetime
        import os

        import torch.distributed as tdist

        from ray_tpu.parallel import distributed as rdz

        if rank == 0:
            addr = rdz.publish_coordinator(group)
        else:
            addr = rdz.wait_coordinator(group, torch_config.init_timeout)
        os.environ["RAY_TPU_LOCAL_RANK"] = str(local_rank)
        # Torch-ecosystem conventions (accelerate/transformers read these
        # even when the process group is already initialized).
        host, _, port = addr.rpartition(":")
        os.environ["MASTER_ADDR"] = host
        os.environ["MASTER_PORT"] = port
        os.environ["RANK"] = str(rank)
        os.environ["WORLD_SIZE"] = str(world_size)
        os.environ["LOCAL_RANK"] = str(local_rank)
        tdist.init_process_group(
            torch_config.backend,
            init_method=f"tcp://{addr}",
            rank=rank,
            world_size=world_size,
            timeout=datetime.timedelta(seconds=torch_config.init_timeout),
        )
        return True

    def run(self, train_fn, config, session_kwargs):
        session_mod.init_session(**session_kwargs)
        try:
            train_fn(config)
        finally:
            q = session_kwargs["results_queue"]
            q.put({"type": "finished", "rank": self.rank})
            session_mod.shutdown_session()
        return self.rank


class WorkerGroup:
    """N worker actors inside one placement group
    (``train/_internal/worker_group.py:92``).

    Default (fixed gang): owns a fresh group sized for the full
    ``scaling.num_workers``. Elastic: the trainer passes the ONE
    long-lived group it holds across attempts plus the bundle indices
    that currently have a live node — this attempt runs at that
    (possibly shrunk) world size while the head's reschedule
    coordinator migrates the lost bundles in the background."""

    def __init__(self, scaling: ScalingConfig,
                 num_workers: Optional[int] = None,
                 pg=None, bundle_indices: Optional[List[int]] = None):
        self.scaling = scaling
        self.owns_pg = pg is None
        self.num_workers = num_workers or scaling.num_workers
        if pg is None:
            bundles = scaling.as_placement_group_bundles()
            pg = placement_group(
                bundles, strategy=scaling.placement_strategy)
            ray_tpu.get(pg.ready(), timeout=120)
        self.pg = pg
        if bundle_indices is None:
            bundle_indices = list(range(self.num_workers))
        self.bundle_indices = list(bundle_indices)[: self.num_workers]
        worker_cls = ray_tpu.remote(_TrainWorker)
        self.workers = [
            worker_cls.options(
                num_cpus=0,
                scheduling_strategy=PlacementGroupSchedulingStrategy(
                    placement_group=self.pg,
                    placement_group_bundle_index=self.bundle_indices[i],
                ),
            ).remote(i)
            for i in range(self.num_workers)
        ]

    def run_all(self, train_fn, config, session_kwargs_per_worker) -> list:
        return [
            w.run.remote(train_fn, config, kw)
            for w, kw in zip(self.workers, session_kwargs_per_worker)
        ]

    def shutdown(self):
        for w in self.workers:
            try:
                ray_tpu.kill(w)
            except Exception:
                pass
        if self.owns_pg:
            try:
                remove_placement_group(self.pg)
            except Exception:
                pass


class _CheckpointManager:
    """Track reported checkpoints, keep top-K (``CheckpointConfig``,
    ``tune/execution/checkpoint_manager.py`` analog)."""

    def __init__(self, config: CheckpointConfig):
        self.config = config
        self.checkpoints: List[tuple] = []  # (score, iteration, Checkpoint)
        self.latest: Optional[Checkpoint] = None

    def register(self, checkpoint: Checkpoint, metrics: dict, iteration: int):
        self.latest = checkpoint
        attr = self.config.checkpoint_score_attribute
        score = metrics.get(attr) if attr else iteration
        if score is None:
            score = iteration
        sign = 1 if self.config.checkpoint_score_order == "max" else -1
        self.checkpoints.append((sign * score, iteration, checkpoint))
        self.checkpoints.sort(key=lambda t: (-t[0], -t[1]))
        if self.config.num_to_keep is not None:
            del self.checkpoints[self.config.num_to_keep :]

    @property
    def best(self) -> Optional[Checkpoint]:
        return self.checkpoints[0][2] if self.checkpoints else self.latest


def _shard_dataset(ds, n: int, equal: bool = True):
    """Per-worker shards: Data datasets via split(); arrays/lists striped."""
    if ds is None:
        return [None] * n
    if hasattr(ds, "split"):
        return ds.split(n, equal=equal)
    try:
        return [ds[i::n] for i in range(n)]
    except TypeError:
        return [ds] * n


class DataParallelTrainer:
    def __init__(
        self,
        train_loop_per_worker: Callable,
        *,
        train_loop_config: Optional[dict] = None,
        scaling_config: Optional[ScalingConfig] = None,
        run_config: Optional[RunConfig] = None,
        datasets: Optional[Dict[str, Any]] = None,
        resume_from_checkpoint: Optional[Checkpoint] = None,
    ):
        self.train_fn = train_loop_per_worker
        self.config = dict(train_loop_config or {})
        self.scaling = scaling_config or ScalingConfig()
        self.run_config = run_config or RunConfig()
        self.datasets = datasets or {}
        self.resume_checkpoint = resume_from_checkpoint

    # -- one attempt ------------------------------------------------------

    def _run_attempt(
        self, ckpt_mgr: _CheckpointManager, metrics_history: List[dict],
        ledger: Optional["_GoodputLedger"] = None,
        pg=None,
    ) -> Optional[dict]:
        """Run the worker group to completion; returns last metrics.
        Raises on worker failure (caller handles elasticity). With
        ``pg`` (the elastic path's long-lived group) the attempt runs
        on the bundles that currently have a live node — shrunk world
        size while the head migrates the rest — and a regrow watcher
        interrupts it when the group's capacity is whole again."""
        n = self.scaling.num_workers
        bundle_indices: Optional[List[int]] = None
        if pg is not None:
            bundle_indices = self._wait_live_bundles(pg)[:n]
            n = len(bundle_indices)
        drain_stop = threading.Event()
        drained_nodes: set = set()
        # Subscribe to drain events BEFORE placing anything: a preemption
        # notice for a node hosting this group triggers a checkpoint-
        # restore restart while the node is still up, not after a
        # heartbeat timeout. (The watcher records every draining node;
        # the consume loop intersects with the group's nodes.)
        threading.Thread(
            target=self._watch_drains,
            args=(drained_nodes, drain_stop), daemon=True,
        ).start()
        regrow_evt: Optional[threading.Event] = None
        if pg is not None and n < self.scaling.num_workers:
            regrow_evt = threading.Event()
            threading.Thread(
                target=self._watch_regrow,
                args=(pg, n, regrow_evt, drain_stop), daemon=True,
            ).start()
        group = WorkerGroup(self.scaling, num_workers=n, pg=pg,
                            bundle_indices=bundle_indices)
        # Pinned to the driver's node: a results queue riding a node a
        # preemption takes would read as a budget-consuming trial
        # failure (see queue.driver_node_options).
        from ray_tpu.util.queue import driver_node_options

        queue = Queue(actor_options=driver_node_options())
        try:
            shards = {
                name: _shard_dataset(ds, n) for name, ds in self.datasets.items()
            }
            start_ckpt = ckpt_mgr.latest or self.resume_checkpoint
            node_ranks, local_ranks, node_ids = self._compute_ranks(group)
            self._on_group_start(group, node_ranks, local_ranks)
            session_kwargs = [
                {
                    "world_rank": i,
                    "world_size": n,
                    "local_rank": local_ranks[i],
                    "node_rank": node_ranks[i],
                    "results_queue": queue,
                    "checkpoint": start_ckpt,
                    "dataset_shards": {
                        name: sh[i] for name, sh in shards.items()
                    },
                }
                for i in range(n)
            ]
            run_refs = group.run_all(self.train_fn, self.config, session_kwargs)
            return self._consume_results(
                queue, run_refs, n, ckpt_mgr, metrics_history,
                drained_nodes=drained_nodes, group_nodes=set(node_ids),
                ledger=ledger, regrow_evt=regrow_evt,
            )
        finally:
            drain_stop.set()
            queue.shutdown()
            group.shutdown()

    def _compute_ranks(self, group: WorkerGroup) -> tuple[list, list, list]:
        """node_rank + local_rank (+ raw node id) per worker, from actual
        actor placement (``backend_executor.py:339-404`` init_session
        rank layout)."""
        node_ids = ray_tpu.get(
            [w.node_id.remote() for w in group.workers], timeout=60
        )
        node_order: list[str] = []
        counts: dict[str, int] = {}
        node_ranks, local_ranks = [], []
        for nid in node_ids:
            if nid not in counts:
                counts[nid] = 0
                node_order.append(nid)
            node_ranks.append(node_order.index(nid))
            local_ranks.append(counts[nid])
            counts[nid] += 1
        return node_ranks, local_ranks, node_ids

    def _watch_drains(self, drained_nodes: set,
                      stop_evt: threading.Event) -> None:
        """Long-poll the head's NODES pubsub feed and record every node
        that enters DRAINING (the local backend has no head/pubsub: the
        watcher is a no-op there)."""
        from ray_tpu._private import worker as worker_mod

        head = getattr(worker_mod.backend(), "head", None)
        if head is None:
            return
        sub_id = f"train-drain:{ids.new_task_id()[:12]}"
        try:
            head.call("pubsub_subscribe", sub_id, "NODES")
            while not stop_evt.is_set():
                try:
                    got = head.call("pubsub_poll", sub_id, 1.0,
                                    timeout=10.0)
                except Exception:
                    return  # backend shutting down / head gone
                if got is None:
                    # Head restarted / subscription TTL'd away: poll
                    # returns None instantly for an unknown sub, so
                    # re-subscribe (not re-poll) or this would hot-spin.
                    time.sleep(0.5)
                    try:
                        head.call("pubsub_subscribe", sub_id, "NODES")
                    except Exception:
                        return
                    continue
                for m in got[0]:
                    data = m.get("data") or {}
                    if data.get("state") == "DRAINING" and \
                            data.get("node_id"):
                        drained_nodes.add(data["node_id"])
        finally:
            try:
                head.call("pubsub_unsubscribe", sub_id)
            except Exception:
                pass

    @staticmethod
    def _pg_table(pg) -> dict:
        from ray_tpu.util.placement_group import placement_group_table

        return placement_group_table(pg) or {}

    def _live_bundles(self, pg) -> List[int]:
        """Bundle indices whose node is alive and schedulable right now
        (the head's table carries them; a backend without per-bundle
        liveness — the local backend — reports all bundles once the
        group is CREATED)."""
        table = self._pg_table(pg)
        live = table.get("live_bundles")
        if live is None:
            if table.get("state") == "CREATED":
                return list(range(len(table.get("bundles") or
                                      [None] * self.scaling.num_workers)))
            return []
        return list(live)

    def _wait_live_bundles(self, pg, timeout: float = 300.0) -> List[int]:
        """Block until at least ``min_workers`` bundles have live nodes
        (the elastic floor): a gang that lost everything waits for the
        head's reschedule coordinator to land replacements rather than
        burning an attempt on an unplaceable world."""
        floor = max(1, self.scaling.min_workers or self.scaling.num_workers)
        deadline = time.monotonic() + timeout
        while True:
            live = self._live_bundles(pg)
            if len(live) >= floor:
                return sorted(live)
            state = self._pg_table(pg).get("state")
            if state in ("REMOVED", "INFEASIBLE"):
                raise RuntimeError(
                    f"elastic gang placement group is {state}")
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"elastic gang never reached min_workers={floor} "
                    f"live bundles within {timeout}s (live={live})")
            time.sleep(0.25)

    def _watch_regrow(self, pg, current_n: int,
                      regrow_evt: threading.Event,
                      stop_evt: threading.Event) -> None:
        """Poll the group's table while an attempt runs SHRUNK: the
        moment more bundles are live than the attempt is using (the
        head finished rescheduling onto a replacement node), signal the
        consume loop to restart at the larger world size."""
        while not stop_evt.is_set():
            try:
                if len(self._live_bundles(pg)) > current_n:
                    regrow_evt.set()
                    return
            except Exception:
                return  # backend shutting down
            stop_evt.wait(0.5)

    def _on_group_start(self, group, node_ranks, local_ranks) -> None:
        """Framework-backend hook run before the training loops start
        (``Backend.on_start`` analog). Default: nothing."""

    def _consume_results(
        self, queue, run_refs, n, ckpt_mgr, metrics_history,
        drained_nodes: Optional[set] = None,
        group_nodes: Optional[set] = None,
        ledger: Optional["_GoodputLedger"] = None,
        regrow_evt: Optional[threading.Event] = None,
    ) -> Optional[dict]:
        """TrainingIterator: drain worker reports; rank-0 metrics win
        (``train/trainer.py:155 _fetch_next_result``)."""
        finished: set[int] = set()
        last_metrics: Optional[dict] = None
        while len(finished) < n:
            if drained_nodes and group_nodes and \
                    (drained_nodes & group_nodes):
                # A worker's node is leaving (preemption/scale-down):
                # restart from the latest checkpoint NOW, while that
                # node still serves its objects, instead of discovering
                # the loss via heartbeat timeout mid-step.
                raise TrainingWorkerPreempted(
                    "a training worker's node is draining; restarting "
                    "the group from the latest checkpoint")
            if regrow_evt is not None and regrow_evt.is_set():
                # Capacity restored while running shrunk: re-form the
                # collective at the larger world size from the latest
                # checkpoint (planned, budget-exempt).
                raise TrainingGroupResized(
                    "gang capacity restored; regrowing the group from "
                    "the latest checkpoint")
            # Fail fast if a worker actor died (its queue would stay silent).
            ready, _ = ray_tpu.wait(run_refs, num_returns=n, timeout=0.0)
            for r in ready:
                ray_tpu.get(r)  # raises ActorError/TaskError on failure
            try:
                msg = queue.get(timeout=1.0)
            except Exception:
                continue
            if msg["type"] == "finished":
                finished.add(msg["rank"])
                continue
            if msg["type"] == "report":
                if ledger is not None:
                    ledger.observe_report(msg)
                if msg["checkpoint"] is not None and msg["rank"] == 0:
                    ckpt_mgr.register(
                        msg["checkpoint"], msg["metrics"], msg["iteration"]
                    )
                if msg["rank"] == 0:
                    last_metrics = msg["metrics"]
                    metrics_history.append(msg["metrics"])
        for r in run_refs:
            ray_tpu.get(r, timeout=60)
        return last_metrics

    # -- public -----------------------------------------------------------

    def fit(self) -> Result:
        ckpt_mgr = _CheckpointManager(self.run_config.checkpoint_config)
        metrics_history: List[dict] = []
        max_failures = self.run_config.failure_config.max_failures
        ledger = _GoodputLedger()
        attempt = 0
        elastic = self.scaling.min_workers is not None
        pg = None
        # Terminal snapshot of the elastic gang's PG table (state /
        # placement / reschedule count), captured before the group is
        # released — the chaos harness's "PG ends ALIVE" invariant
        # reads it off the finished trainer.
        self.final_pg_state: Optional[dict] = None
        if elastic:
            # ONE long-lived reservation for the whole fit(): bundle
            # loss moves it to RESCHEDULING (the head migrates bundles
            # to healthy nodes) instead of killing it — attempts shrink
            # to the live bundles and regrow when capacity returns.
            bundles = self.scaling.as_placement_group_bundles()
            pg = placement_group(
                bundles, strategy=self.scaling.placement_strategy)
            ray_tpu.get(pg.ready(), timeout=120)
        try:
            while True:
                resched_before = (
                    self._pg_table(pg).get("reschedules", 0)
                    if pg is not None else 0)
                try:
                    last_metrics = self._run_attempt(
                        ckpt_mgr, metrics_history, ledger, pg=pg)
                    return Result(
                        metrics=last_metrics,
                        checkpoint=ckpt_mgr.best,
                        metrics_history=metrics_history,
                        goodput=ledger.summary(),
                    )
                except TrainingWorkerPreempted as e:
                    # Preemption exemption: a planned node departure
                    # restarts the group (from the latest checkpoint)
                    # WITHOUT consuming the failure budget.
                    ledger.mark_down(_goodput.downtime_cause(e))
                    time.sleep(0.2)
                except TrainingGroupResized:
                    # Planned regrow to restored capacity: exempt, and
                    # the restart cost is the reschedule's to carry.
                    ledger.mark_down("reschedule")
                    time.sleep(0.2)
                except (ActorError, TaskError) as e:
                    if _lost_to_drain(e):
                        # A group actor (worker or results queue) died
                        # WITH a draining/preempted node before the
                        # drain watcher could classify it: same
                        # exemption, same restart.
                        ledger.mark_down(_goodput.downtime_cause(e))
                        time.sleep(0.2)
                        continue
                    if elastic and self._gang_migrating(pg, resched_before):
                        # A gang bundle's node died outright (hard spot
                        # preemption, no notice): the reservation is
                        # RESCHEDULING, not dead — on a preemptible
                        # fleet this is the normal case, not a failure.
                        # Restart shrunk from the latest checkpoint,
                        # budget intact.
                        ledger.mark_down("preemption")
                        time.sleep(0.2)
                        continue
                    ledger.mark_down("failure")
                    attempt += 1
                    if max_failures >= 0 and attempt > max_failures:
                        return Result(
                            metrics=metrics_history[-1]
                            if metrics_history else None,
                            checkpoint=ckpt_mgr.best,
                            error=e,
                            metrics_history=metrics_history,
                            goodput=ledger.summary(),
                        )
                    # Elastic restart: new group resumes from latest
                    # checkpoint.
                    time.sleep(0.2)
        finally:
            # Session stop: the trial's per-rank gauge series (step
            # time, anatomy phases) must not outlive the trial on
            # the scrape (LC001 discipline — the local backend's worker
            # threads never die to trigger the agent's sweep).
            try:
                _goodput.retract_trial(ledger.trial)
            except Exception:
                pass
            if pg is not None:
                try:
                    table = self._pg_table(pg)
                    if table.get("state") == "RESCHEDULING":
                        # The trial finished at shrunk world size while
                        # the head was still migrating the lost
                        # bundles: let the reservation settle (bounded)
                        # so the terminal snapshot — the "gang ended
                        # ALIVE on healthy nodes" evidence — reflects
                        # the migration's outcome, not its midpoint.
                        settle = time.monotonic() + 20.0
                        while time.monotonic() < settle and \
                                table.get("state") == "RESCHEDULING":
                            time.sleep(0.25)
                            table = self._pg_table(pg)
                    self.final_pg_state = table
                except Exception:
                    pass
                try:
                    remove_placement_group(pg)
                except Exception:
                    pass

    def _gang_migrating(self, pg, resched_before: int) -> bool:
        """Is this attempt's loss a gang-bundle node loss the head is
        already migrating (PG RESCHEDULING now, or a reschedule
        completed since the attempt started)?"""
        if pg is None:
            return False
        try:
            table = self._pg_table(pg)
        except Exception:
            return False
        return (table.get("state") == "RESCHEDULING"
                or table.get("reschedules", 0) > resched_before)


class JaxTrainer(DataParallelTrainer):
    """DataParallelTrainer whose workers drive jax on their local devices.

    The torch/TF/horovod backends of the reference
    (``train/torch/config.py:113``) become: before the loops start, every
    worker joins ONE ``jax.distributed`` process group — rank 0 publishes
    the coordinator address through the cluster KV
    (``ray_tpu.parallel.distributed``), all ranks call
    ``jax.distributed.initialize``, and ``jax.devices()`` then spans every
    worker host. Gradient communication happens inside the jitted step
    (XLA collectives on ICI/DCN); the framework only does placement,
    sessions, checkpoints, and failure handling.
    """

    def __init__(self, *args, jax_config: Optional[JaxConfig] = None, **kwargs):
        super().__init__(*args, **kwargs)
        self.jax_config = jax_config or JaxConfig()

    def _on_group_start(self, group, node_ranks, local_ranks) -> None:
        if not self.jax_config.distributed:
            return
        # The in-process local backend runs worker "actors" as threads of
        # ONE process — jax.distributed (one runtime per OS process) can't
        # span them. Multi-host setup needs the cluster backend, where each
        # worker is its own process; on the local backend each worker just
        # uses the process-wide JAX runtime as-is.
        from ray_tpu._private import worker as worker_mod
        from ray_tpu.core.local_backend import LocalBackend

        if isinstance(worker_mod.backend(), LocalBackend):
            return
        from ray_tpu.parallel import distributed as dist

        group_name = f"train-{ids.new_task_id()[:12]}"
        local_world = {}
        for nr in node_ranks:
            local_world[nr] = local_world.get(nr, 0) + 1
        # All setup calls must be in flight together: initialize() blocks
        # until every rank has connected to the coordinator.
        refs = [
            w.setup_jax.remote(
                group_name, i, self.scaling.num_workers,
                local_ranks[i], local_world[node_ranks[i]], self.jax_config,
            )
            for i, w in enumerate(group.workers)
        ]
        try:
            ray_tpu.get(refs, timeout=self.jax_config.init_timeout + 60)
        finally:
            dist.clear_group(group_name)
