"""Node agent: per-node daemon (raylet equivalent).

Mirrors ``src/ray/raylet/node_manager.h``: owns the node's resources and
worker processes. Implements:

  * worker pool — forked Python worker processes, cached when idle
    (``worker_pool.h:80``); a dead worker's in-flight task is failed by
    storing an error object (the owner then retries);
  * local task dispatch — FIFO queue + blocking resource acquisition, the
    LocalTaskManager analog;
  * placement-group bundle 2PC participant — prepare/commit/return
    (``node_manager.proto:375`` PrepareBundleResources/CommitBundleResources);
  * local object store — creates this node's C++ shm segment and serves
    object bytes to peer nodes (``ObjectManager::Push`` analog, pull-based);
  * heartbeats to the head with the live resource view
    (``gcs_heartbeat_manager.h``).
"""

from __future__ import annotations

import collections
import os
import subprocess
import sys
import threading
import time
from typing import Optional

from ray_tpu._native.shm_store import ShmStore
from ray_tpu.cluster.rpc import (
    ConnectionLost,
    RpcClient,
    RpcServer,
    channel_chaos,
)
from ray_tpu.core import ids
from ray_tpu.util import failpoints
from ray_tpu.core.object_ref import ObjectLostError
from ray_tpu.core.config import config
from ray_tpu.core.resources import ResourcePool

DEFAULT_STORE_CAPACITY = config.object_store_capacity_bytes


class _Worker:
    def __init__(self, worker_id, proc, address=None, env_key=""):
        self.worker_id = worker_id
        self.proc = proc
        self.address = address
        self.started_at = time.time()
        self.client: Optional[RpcClient] = None
        self.client_id: Optional[str] = None  # ref-table holder id
        self.ready = threading.Event()
        self.current_task = None  # (task_spec, release_fn) while executing
        self.is_actor = False
        self.actor_id = None
        # Runtime-env hash this process was spawned under; the pool never
        # leases a worker across env keys ("" = plain environment).
        self.env_key = env_key


class NodeAgent:
    def __init__(
        self,
        head_address: str,
        *,
        num_cpus: float | None = None,
        resources: dict | None = None,
        store_capacity: int = DEFAULT_STORE_CAPACITY,
        host: str = "127.0.0.1",
        session: str | None = None,
        memory_usage_threshold: float | None = None,
        memory_limit_bytes: int | None = None,
        labels: dict | None = None,
    ):
        self.node_id = ids.new_node_id()
        # Provisioning metadata (node_type, spot, ...) carried to the
        # head at registration; the autoscaler and status surfaces read
        # it from the node table. A spot node's preemption still arrives
        # through the preemption watcher / SIGTERM — labels only say
        # WHICH nodes can vanish that way.
        self.labels = dict(labels or {})
        self.head_address = head_address
        # Reconnect window so a restarting head (GCS FT) doesn't fail
        # in-flight add_location/register calls from this agent.
        self.head = RpcClient(
            head_address, reconnect_window=config.head_reconnect_window_s)
        node_res = {"CPU": float(num_cpus if num_cpus is not None else os.cpu_count() or 8)}
        node_res.update(resources or {})
        self.pool = ResourcePool(node_res)
        self.total_resources = dict(node_res)
        session = session or f"s{os.getpid()}"
        self.store_path = f"/dev/shm/ray_tpu_{session}_{self.node_id[-8:]}"
        self.store = ShmStore(self.store_path, store_capacity, create=True)
        # Spill target (external_storage.py:72 analog): cold primary
        # copies move here under memory pressure; restored on demand.
        # Default: a per-session local dir (dies with the node). With
        # config.spill_uri set, spills go to the shared remote backend
        # and the head records them so a DEAD node's spilled objects
        # restore from the URI instead of recomputing (spill_storage.py).
        from ray_tpu.cluster import spill_storage

        self.spill_dir = f"/tmp/ray_tpu_spill_{session}_{self.node_id[-8:]}"
        spill_uri = config.spill_uri
        if spill_uri:
            # A typo'd URI must fail agent boot, not the first
            # memory-pressure spill.
            self.spill_backend = spill_storage.backend_for(spill_uri)
        else:
            self.spill_backend = spill_storage.local_backend(self.spill_dir)
        self._spill_lock = threading.Lock()
        # Foreign-URI restore backends (rpc_restore_from_uri for objects
        # another node spilled under a different/older spill_uri),
        # bounded small — a cluster normally has ONE spill target.
        self._restore_backends: dict[str, object] = {}
        self._deferred_deletes: set[str] = set()

        self._lock = threading.RLock()
        self._workers: dict[str, _Worker] = {}
        # Idle pools keyed by runtime-env hash (worker_pool.cc keys its
        # pools by runtime-env hash the same way; "" = no runtime env).
        self._idle: dict[str, list[_Worker]] = {}
        self._max_workers = max(
            config.worker_min_pool,
            int(node_res.get("CPU", 4)) * config.workers_per_cpu,
        )
        # Set BEFORE the dispatch thread starts: _checkout_worker touches
        # these, and a task can dispatch while __init__ is still running.
        self._prestart_target = 0
        self._replenish_evt = threading.Event()
        # Materialized runtime-env package cache (per node, content-hashed).
        self._rtenv_cache_root = f"/tmp/ray_tpu_rtenv_{session}"
        os.makedirs(self._rtenv_cache_root, exist_ok=True)
        self._bundles: dict[tuple, ResourcePool] = {}
        self._bundle_state: dict[tuple, str] = {}  # PREPARED | COMMITTED
        self._task_queue: list[dict] = []
        self._queue_cv = threading.Condition(self._lock)
        # Draining (DrainRaylet analog): set by the head's drain
        # coordinator (or a preemption self-drain). A draining node
        # finishes what it has but admits no new leased pushes and
        # gossips zero availability.
        self._draining = False
        self._drain_reason: Optional[str] = None
        # Specs popped from the queue but not yet bound to a worker
        # (acquiring resources / waiting for a fork): they are neither
        # "queued" nor "running", and the drain coordinator's quiescence
        # probe must not mistake that window for an idle node.
        self._dispatch_inflight = 0
        # Demand of queued-or-acquiring tasks, not yet debited from the
        # pool: leased-push admission compares against available minus this.
        self._committed: dict[str, float] = {}
        self._shutdown = threading.Event()
        # Task state records for the state API (GetTasksInfo analog):
        # PENDING on enqueue, RUNNING on dispatch, final state from the
        # worker's batched event reports.
        self._task_records: "collections.OrderedDict[str, dict]" = (
            collections.OrderedDict()
        )
        self._task_records_cap = max(16, config.task_record_retention)
        # Task ids cancelled before the dispatcher picked them up (covers
        # the queue→checkout window where a task is in neither place).
        # Ordered so the bound evicts oldest-first.
        self._cancelled_tasks: "collections.OrderedDict[str, bool]" = (
            collections.OrderedDict()
        )
        # Object-serving counters (tests assert the chunked path is used).
        self._fetch_stats = {"whole": 0, "info": 0, "chunks": 0}
        # Owner-directory clients, for pushing dead-worker error
        # locations straight to the owning client (bounded LRU).
        self._owner_clients: "collections.OrderedDict[str, RpcClient]" = (
            collections.OrderedDict()
        )
        # Node reporter (reference: dashboard/modules/reporter +
        # _private/log_monitor.py). Worker stdout/stderr is captured to
        # per-worker files under log_dir (the batched worker_events tee
        # to the head stays the live-follow push path); the index below
        # keeps dead workers' logs reachable for post-mortems.
        self.log_dir = f"/tmp/ray_tpu_wlogs_{session}_{self.node_id[-8:]}"
        try:
            os.makedirs(self.log_dir, exist_ok=True)
        except OSError:
            self.log_dir = None  # degrade: workers inherit our fds
        self._worker_logs: "collections.OrderedDict[str, dict]" = (
            collections.OrderedDict()
        )
        # Per-worker CPU/RSS telemetry: latest snapshot + /proc cpu-tick
        # history for utilization deltas + the gauge children we have
        # exported (so dead workers' series get pruned).
        self._worker_stats: dict[str, dict] = {}
        self._cpu_prev: dict[str, tuple] = {}
        self._exported_gauges: set[tuple] = set()
        # Per-worker JAX/XLA device snapshots (util/device_telemetry),
        # shipped on the worker-events batch; exported as
        # ray_tpu_device_* gauges by the telemetry pass and pruned with
        # the worker. The exported set tracks (worker_id, device|None)
        # children so retraction is exact.
        self._device_stats: dict[str, dict] = {}
        self._exported_device: set[tuple] = set()
        # Serve gauge children created by each worker's shipped
        # observations (replica ongoing / router queue depth /
        # reconcile), retracted when the worker dies so a dead replica
        # vanishes from the federated scrape.
        self._serve_gauges: dict[str, set] = {}
        # Training goodput gauge children (the per-rank straggler
        # gauge), same retraction lifecycle as the serve gauges.
        self._train_gauges: dict[str, set] = {}
        # Last-applied worker-events batch seq per (worker_id, pid):
        # the flusher resends a batch whose ack was severed under its
        # original seq, and this table absorbs the replay (bounded,
        # insertion-ordered — the rpc_worker_events idempotence).
        self._event_seqs: "collections.OrderedDict[tuple, int]" = (
            collections.OrderedDict())
        # Remote profiler captures (state.capture_profile): manifest by
        # capture id; trace files live under log_dir and stream back
        # through read_capture_file (the log-read plane's chunked shape).
        self._captures: "collections.OrderedDict[str, dict]" = (
            collections.OrderedDict()
        )
        # One sampler at a time: a fresh=True RPC racing the telemetry
        # loop would otherwise compute cpu%% over a ~ms window (one
        # scheduler tick reads as ~1000%%) and fight over the gauge set.
        self._telemetry_lock = threading.Lock()
        self._last_sample = 0.0
        # OOM forensics: bounded index of pre-kill memory reports the
        # monitor wrote under log_dir (ray-tpu memory --node surfaces
        # them; the victim's death cause carries the path).
        self._oom_reports: list[dict] = []
        # Object-store gauge bookkeeping: evictions is cumulative in the
        # native stats — exported as a counter by delta. spill_denied is
        # agent-side cumulative (surfaced in store stats for the bench).
        self._evictions_exported = 0
        self._store_gauges_exported = False
        self._spill_denied = 0
        self._spill_restores = 0
        # Resource-view gossip (reference: ray_syncer.h:88 — nodes share
        # resource views so scheduling needn't centralize). Membership
        # (who exists / who died) still comes from the head, the GCS's
        # job; LOAD flows node<->node by anti-entropy push-pull: each
        # tick we bump our own versioned entry and exchange views with
        # `gossip_fanout` random peers; entries merge by per-origin
        # version. Consumers: rpc_peer_view (clients pick spillback
        # targets without a head RPC).
        self._cluster_view: dict[str, dict] = {}
        self._view_version = 0
        self._gossip_clients: "collections.OrderedDict[str, RpcClient]" = (
            collections.OrderedDict()
        )
        # Runtime-armed failpoint table for THIS node's workers: kept so
        # workers forked AFTER a cluster-wide arm still inherit it (they
        # are armed at registration) — without this, a chaos arm only
        # covers the workers alive at fanout time.
        self._worker_failpoints: dict[str, str] = {}
        # Same replay contract for network-chaos rules: wire-shaped rule
        # dicts (label folded in) re-applied to late-forked workers, so
        # an in-force partition isn't invisible to a worker spawned
        # mid-experiment.
        self._worker_channel_rules: list[dict] = []

        self._server = RpcServer(self, host)
        self.address = self._server.address
        # Chaos source identity: this agent's outbound clients (head
        # heartbeats, gossip, owner notifies) carry the agent address so
        # Cluster.partition's symmetric drop rules cut both directions.
        self.head.chaos_src = self.address
        self.head.call(
            "register_node", self.node_id, self.address,
            self.total_resources, self.store_path, self.labels,
        )
        threading.Thread(target=self._heartbeat_loop, daemon=True).start()
        threading.Thread(target=self._dispatch_loop, daemon=True).start()
        # Kept joinable: stop() waits the reaper out before detaching the
        # shm store (its release_dead on a detached segment is a crash).
        self._reap_thread = threading.Thread(
            target=self._reap_loop, daemon=True)
        self._reap_thread.start()
        if config.worker_telemetry_interval_s > 0:
            threading.Thread(
                target=self._telemetry_loop, daemon=True).start()
        if config.gossip_interval_s > 0:
            threading.Thread(target=self._gossip_loop, daemon=True).start()
        if config.preemption_poll_interval_s > 0 and (
                config.preemption_signal_file
                or config.preemption_metadata_url):
            threading.Thread(
                target=self._preemption_watcher, daemon=True).start()
        # OOM protection (memory_monitor.h / worker_killing_policy.h
        # analog): watch node memory, kill the newest task's worker under
        # pressure; its refs raise OutOfMemoryError.
        from ray_tpu.cluster.memory_monitor import MemoryMonitor

        self.memory_monitor = MemoryMonitor(
            self, usage_threshold=memory_usage_threshold,
            limit_bytes=memory_limit_bytes,
        )
        self.memory_monitor.start()
        # Object-store occupancy gauges exist from boot (the telemetry
        # loop keeps them fresh; scrapes refresh them too).
        try:
            self._export_store_gauges()
        except Exception:
            pass
        # Prestart plain-env workers up to the node's CPU count (reference:
        # worker_pool.cc PrestartWorkers) so a first burst that spills onto
        # this node doesn't serialize behind interpreter cold starts.
        n_prestart = min(
            int(config.worker_prestart_per_cpu
                * self.total_resources.get("CPU", 0.0)),
            self._max_workers,
        )
        self._prestart_target = n_prestart
        if n_prestart > 0:
            threading.Thread(
                target=self._prestart_workers, args=(n_prestart,),
                daemon=True,
            ).start()
            # Keep the plain-env pool warm for the REST of the node's
            # life: actor creations consume idle workers permanently
            # (dedicated processes), so without replenishment the Nth
            # actor cold-forks again (reference worker_pool prestart is
            # likewise demand-refreshed).
            threading.Thread(
                target=self._replenish_loop, daemon=True).start()

    def _replenish_loop(self) -> None:
        while not self._shutdown.is_set():
            if not self._replenish_evt.wait(1.0):
                continue  # not signaled: only checkout demand replenishes
            if self._shutdown.is_set():
                return
            self._replenish_evt.clear()
            while not self._shutdown.is_set():
                with self._lock:
                    idle = len(self._idle.get("", []))
                    live = len([w for w in self._workers.values()
                                if w.proc.poll() is None
                                and not w.is_actor])
                    need = (idle < self._prestart_target
                            and live < self._max_workers)
                if not need:
                    break
                try:
                    w = self._spawn_worker()
                    if w.ready.wait(config.worker_start_timeout_s):
                        self._return_worker(w)
                    else:
                        break
                except (OSError, RuntimeError):
                    break  # replenish is an optimization, never fatal

    def _prestart_workers(self, n: int) -> None:
        # Deferred + serialized: a cluster booting many agents at once must
        # not fork an interpreter storm that starves node registration;
        # each fork waits for the previous worker to come up, and demand
        # that arrives meanwhile shrinks what's left to prestart.
        self._shutdown.wait(config.worker_prestart_delay_s)
        for _ in range(n):
            if self._shutdown.is_set():
                return
            with self._lock:
                idle = sum(len(v) for v in self._idle.values())
                live = len([w for w in self._workers.values()
                            if w.proc.poll() is None])
                if idle >= n or live >= self._max_workers:
                    return
            try:
                w = self._spawn_worker()
                if w.ready.wait(config.worker_start_timeout_s):
                    self._return_worker(w)
            except (OSError, RuntimeError):
                return  # prestart is an optimization, never fatal
            # Space the forks out: since workers stopped pre-importing
            # jax, forks complete in ~0.3s and N agents' prestarts
            # otherwise compress into one interpreter storm exactly when
            # a mass cluster boot needs the CPU (the slow-fork era
            # staggered this by accident).
            if self._shutdown.wait(config.worker_prestart_spacing_s):
                return

    # -- worker pool ------------------------------------------------------

    def _spawn_worker(self, env_key: str = "",
                      resolved_env: dict | None = None) -> _Worker:
        worker_id = "w-" + os.urandom(6).hex()
        env = dict(os.environ)
        env["RAY_TPU_NODE_ID"] = self.node_id
        env["RAY_TPU_WORKER_ID"] = worker_id
        # The framework must be importable by `-m ray_tpu...` no matter
        # where the DRIVER ran from (it may have put ray_tpu on sys.path
        # itself): pin our own package root onto the worker's path.
        import ray_tpu as _pkg

        pkg_root = os.path.dirname(os.path.dirname(
            os.path.abspath(_pkg.__file__)))
        prior = env.get("PYTHONPATH", "")
        if pkg_root not in prior.split(os.pathsep):
            env["PYTHONPATH"] = (
                pkg_root + (os.pathsep + prior if prior else ""))
        cwd = None
        python = sys.executable
        if resolved_env is not None:
            # Materialize packages (content-hash cached) and bake the env
            # into the subprocess: env_vars directly, py_modules +
            # working_dir via PYTHONPATH, working_dir as cwd — the
            # interpreter picks all of it up at start, no worker-side code.
            from ray_tpu._private import runtime_env as rtenv

            recipe = rtenv.ensure_local(
                resolved_env,
                lambda k: self.head.call("kv_get", k),
                self._rtenv_cache_root,
            )
            env.update(recipe["env_vars"])
            # The framework itself may be importable only via the agent's
            # cwd; a changed cwd must not break `-m ray_tpu...` startup.
            import ray_tpu as _pkg

            pkg_root = os.path.dirname(os.path.dirname(
                os.path.abspath(_pkg.__file__)))
            prior = env.get("PYTHONPATH", "")
            env["PYTHONPATH"] = os.pathsep.join(
                recipe["py_paths"] + [pkg_root]
                + ([prior] if prior else [])
            )
            cwd = recipe["cwd"]
            if recipe.get("python"):
                # pip env: the worker runs under the per-env virtualenv
                # interpreter (its site-packages shadow the cluster's).
                python = recipe["python"]
        # The pool is language-aware like the reference's (worker_pool.h:80
        # keys processes by language + runtime env): a "cpp::<bin>" key
        # spawns that native binary with the same worker flags the Python
        # workerproc takes; everything after argv is shared.
        if env_key.startswith("cpp::"):
            argv = [env_key[len("cpp::"):]]
        else:
            argv = [python, "-m", "ray_tpu.cluster.workerproc"]
        # Per-worker log capture (log_monitor.py analog): the process's
        # raw stdout/stderr land in files the reporter RPCs serve; the
        # structured line tee to the head (worker_events) is unaffected.
        out_path = err_path = None
        out_f = err_f = None
        if self.log_dir is not None:
            try:
                out_path = os.path.join(self.log_dir, f"{worker_id}.out")
                err_path = os.path.join(self.log_dir, f"{worker_id}.err")
                out_f = open(out_path, "ab")
                err_f = open(err_path, "ab")
            except OSError:
                if out_f is not None:  # second open failed: no fd leak
                    out_f.close()
                out_path = err_path = out_f = err_f = None
        if out_f is None:
            stdout = (sys.stdout.fileno()
                      if hasattr(sys.stdout, "fileno") else None)
            stderr = (sys.stderr.fileno()
                      if hasattr(sys.stderr, "fileno") else None)
        else:
            stdout, stderr = out_f, err_f
        try:
            proc = subprocess.Popen(
                [
                    *argv,
                    "--head", self.head_address,
                    "--agent", self.address,
                    "--node-id", self.node_id,
                    "--store", self.store_path,
                    "--worker-id", worker_id,
                ],
                env=env,
                cwd=cwd,
                stdout=stdout,
                stderr=stderr,
            )
        finally:
            # Popen holds its own descriptors; ours would just leak.
            for f in (out_f, err_f):
                if f is not None:
                    f.close()
        w = _Worker(worker_id, proc, env_key=env_key)
        with self._lock:
            self._workers[worker_id] = w
            if out_path is not None:
                self._worker_logs[worker_id] = {
                    "worker_id": worker_id,
                    "node_id": self.node_id,
                    "pid": proc.pid,
                    "stdout_path": out_path,
                    "stderr_path": err_path,
                    "started_at": w.started_at,
                    "ended_at": None,
                }
                while len(self._worker_logs) > config.worker_log_retention:
                    old = self._worker_logs.popitem(last=False)[1]
                    for p in (old["stdout_path"], old["stderr_path"]):
                        try:
                            os.unlink(p)
                        except OSError:
                            pass
        return w

    def rpc_register_worker(self, worker_id, address, client_id=None):
        with self._lock:
            w = self._workers.get(worker_id)
            if w is None:
                return False
            w.address = address
            w.client_id = client_id  # its holder id in the head's ref table
            w.client = RpcClient(address)
            w.client.chaos_src = self.address
            armed = dict(self._worker_failpoints)
            chan_rules = list(self._worker_channel_rules)
        if armed:
            # Late-forked workers inherit the runtime-armed table —
            # BEFORE ready.set(), so no task can dispatch to a
            # not-yet-armed worker.
            try:
                w.client.call("set_failpoints", armed, timeout=5.0)
            except Exception:
                pass
        if chan_rules:
            try:
                w.client.call(
                    "set_channel_chaos", chan_rules, "", timeout=5.0)
            except Exception:
                pass
        w.ready.set()
        return True

    def _checkout_worker(self, timeout: float | None = None,
                         env_key: str = "",
                         resolved_env: dict | None = None,
                         dedicated: bool = False) -> _Worker:
        """Idle worker of the SAME runtime env, or a fresh one spawned
        into it (lease grant, ``PopWorker`` analog). ``dedicated`` (actor
        creation) bypasses the pool cap: an actor keeps its process for
        life, so counting it against the task pool would let N long-lived
        actors starve every future task on the node — the reference's
        worker pool likewise caps only poolable workers."""
        if timeout is None:
            timeout = config.worker_start_timeout_s
        with self._lock:
            pool = self._idle.get(env_key)
            if pool:
                w = pool.pop()
                if dedicated and env_key == "":
                    # The actor keeps this process for life: top the
                    # plain pool back up in the background.
                    self._replenish_evt.set()
                return w
            if env_key == "":
                self._replenish_evt.set()  # pool empty: warm it for next
            n_live = len([w for w in self._workers.values()
                          if w.proc.poll() is None and not w.is_actor])
            can_spawn = dedicated or n_live < self._max_workers
            victim = None
            if not can_spawn:
                # At capacity with nothing idle in THIS env: retire an
                # idle worker of another env to make room — otherwise a
                # node whose slots filled with (now idle) plain workers
                # could never serve a runtime_env task at all.
                victim = next(
                    (w for key, lst in self._idle.items()
                     if key != env_key for w in lst),
                    None,
                )
                if victim is not None:
                    self._idle[victim.env_key].remove(victim)
                    self._workers.pop(victim.worker_id, None)
                    can_spawn = True
        if victim is not None:
            victim.proc.kill()
            if victim.client_id:
                try:
                    self.head.call("ref_client_dead", victim.client_id)
                except Exception:
                    pass
            try:
                self.store.release_dead(victim.proc.pid)
            except Exception:
                pass
        if can_spawn:
            w = self._spawn_worker(env_key, resolved_env)
        else:
            # Every slot is BUSY: wait for one of this env's workers to
            # come back (or for capacity to free via task turnover).
            deadline = time.monotonic() + timeout
            while True:
                with self._lock:
                    pool = self._idle.get(env_key)
                    if pool:
                        w = pool.pop()
                        break
                    n_live = len([w_ for w_ in self._workers.values()
                                  if w_.proc.poll() is None
                                  and not w_.is_actor])
                    if n_live < self._max_workers:
                        can_spawn = True
                        break
                if time.monotonic() > deadline:
                    raise TimeoutError("no worker became available")
                time.sleep(0.005)
            if can_spawn:
                w = self._spawn_worker(env_key, resolved_env)
        if not w.ready.wait(timeout):
            raise TimeoutError(f"worker {w.worker_id} failed to start")
        return w

    def _return_worker(self, w: _Worker):
        with self._lock:
            if w.proc.poll() is None and not w.is_actor:
                w.current_task = None
                self._idle.setdefault(w.env_key, []).append(w)

    # -- task dispatch ----------------------------------------------------

    def rpc_submit_task(self, spec: dict):  # idempotent
        """Enqueue a task; the dispatcher leases a worker when resources
        allow. Returns immediately (results flow through the store).

        Idempotent under the task model's own contract: a replayed
        plain-task enqueue re-executes a task lineage recovery is
        allowed to re-run anyway (results land by oid, last-write-
        wins), and a replayed ACTOR push dedups at the actor's single
        worker (``_is_duplicate_push`` — exactly-once per
        incarnation)."""
        self._requeue(spec)
        return True

    def _requeue(self, spec: dict) -> None:
        """The one queue-admission sequence (record + commit + enqueue +
        notify) — submit, checkout-timeout retry, and dispatch-failure
        retry must all account identically."""
        self._record_task(spec, "PENDING")
        with self._queue_cv:
            self._commit_locked(spec)
            self._task_queue.append(spec)
            self._queue_cv.notify()

    def rpc_submit_tasks(self, specs: list):
        """Head-placed batch enqueue: one RPC, one queue notify."""
        for spec in specs:
            self._record_task(spec, "PENDING")
        with self._queue_cv:
            for spec in specs:
                self._commit_locked(spec)
            self._task_queue.extend(specs)
            self._queue_cv.notify()
        return True

    def rpc_submit_tasks_leased(self, specs: list):
        """Direct (head-bypassing) submission under a client-held
        scheduling-key lease — the decentralized half of lease pipelining
        (reference: leased-worker task pushes, direct_task_transport.cc).
        This node is NOT obligated to accept: a spec is admitted only if
        its demand fits the node's UNCOMMITTED capacity (available minus
        everything already queued), so a leased burst can never pile up
        behind running tasks while other nodes sit idle — overflow spills
        back through the head, which still balances the cluster. Returns
        the list of REJECTED indices; the client reschedules those through
        the head and drops its lease."""
        failpoints.hit("agent.lease.push")
        rejected = []
        accepted = []
        with self._queue_cv:
            if self._draining:
                # A draining node takes no new work: the client's leased
                # burst spills back through the head, which excludes us.
                return list(range(len(specs)))
            avail = self.pool.available()
            for k, v in self._committed.items():
                avail[k] = avail.get(k, 0.0) - v
            for i, spec in enumerate(specs):
                demand = spec["demand"]
                if all(avail.get(k, 0.0) >= v for k, v in demand.items()):
                    for k, v in demand.items():
                        avail[k] = avail.get(k, 0.0) - v
                    self._commit_locked(spec)
                    accepted.append(spec)
                else:
                    rejected.append(i)
            for spec in accepted:
                # Record BEFORE the dispatcher can see the spec (the lock
                # is reentrant): a fast task's RUNNING/FINISHED must not
                # be overwritten by a late PENDING.
                self._record_task(spec, "PENDING")
            self._task_queue.extend(accepted)
            self._queue_cv.notify()
        return rejected

    # -- queued-demand accounting (admission control for leased pushes) ----

    def _commit_locked(self, spec: dict) -> None:
        """Caller holds self._lock. PG tasks draw on bundle capacity (carved
        out of the pool at prepare time), not on free node capacity."""
        if spec.get("pg_id") is not None:
            return
        for k, v in spec.get("demand", {}).items():
            self._committed[k] = self._committed.get(k, 0.0) + v

    def _uncommit(self, spec: dict) -> None:
        """After the dispatcher's acquire resolves (either way), the demand
        is reflected in (or irrelevant to) pool availability."""
        if spec.get("pg_id") is not None:
            return
        with self._lock:
            for k, v in spec.get("demand", {}).items():
                n = self._committed.get(k, 0.0) - v
                if n <= 1e-9:
                    self._committed.pop(k, None)
                else:
                    self._committed[k] = n

    # -- task state records (state API) -----------------------------------

    def _task_key(self, spec: dict) -> str:
        return spec.get("task_id") or spec.get("oids", ["?"])[0]

    def _record_task(self, spec: dict, state: str):
        rec = {
            "task_id": self._task_key(spec),
            "name": spec.get("fname") or spec.get("method")
            or spec.get("class_name", "task"),
            "type": "ACTOR_CREATION_TASK" if spec.get("actor_create")
            else "NORMAL_TASK",
            "state": state,
            "submitted_at": time.time(),
            "start_time": None,
            "end_time": None,
            "error": None,
        }
        with self._lock:
            old = self._task_records.get(rec["task_id"])
            if old is not None:
                if state in ("PENDING", "RUNNING") and \
                        old.get("state") in ("FINISHED", "FAILED",
                                             "CANCELLED"):
                    # A duplicate delivery of an already-settled task
                    # (retried push whose first reply was lost) must not
                    # regress the terminal record: the duplicate will be
                    # refused at the worker and no further event comes.
                    return
                old["state"] = state
                return
            if len(self._task_records) >= self._task_records_cap:
                self._task_records.popitem(last=False)
                self._count_task_record_eviction()
            self._task_records[rec["task_id"]] = rec

    def _count_task_record_eviction(self) -> None:
        """One tick per record the bounded ring pushed out — a 100k-task
        burst keeps agent RSS flat and the eviction rate visible."""
        from ray_tpu.util import metrics as _metrics

        try:
            _metrics.TASK_RECORDS_EVICTED.inc(
                tags={"node_id": self.node_id})
        except Exception:
            pass

    def rpc_worker_events(self, worker_id, pid, task_events,  # idempotent
                          log_lines, spans=None, device=None, serve=None,
                          train=None, seq=None, dropped=None):
        """Batched observability report from a worker: authoritative task
        records (with timings/outcome + per-phase wall-ns), captured
        stdout/stderr lines, finished tracing spans (forwarded to the
        head's span store), an optional device-telemetry snapshot,
        serve request-path observations, and training goodput
        observations (both replayed into THIS registry — the one the
        federated scrape sees; worker registries are never scraped).

        Idempotent per (worker, pid, seq): the flusher resends a batch
        whose reply was lost under its original sequence number, and
        the replay is absorbed here — without the dedup, a severed ack
        double-counted every serve/goodput observation in the batch
        (the exact-count planes' cross-check benches are built to
        catch precisely that)."""
        failpoints.hit("agent.worker_events.upload")
        if self._is_duplicate_event_batch(worker_id, pid, seq):
            return True
        if serve:
            try:
                from ray_tpu.serve import _observability as _serve_obs

                keys = _serve_obs.apply_events(
                    serve, node_id=self.node_id, worker=worker_id)
                if keys:
                    with self._lock:
                        self._serve_gauges.setdefault(
                            worker_id, set()).update(keys)
            except Exception:
                pass  # observability must never fail the event upload
        if train:
            try:
                from ray_tpu.util import goodput as _goodput

                keys = _goodput.apply_events(
                    train, node_id=self.node_id, worker=worker_id)
                if keys:
                    with self._lock:
                        self._train_gauges.setdefault(
                            worker_id, set()).update(keys)
            except Exception:
                pass
        if task_events:
            # Feed the phase histogram so p50/p99 per phase is
            # scrapeable without the state API (one observe per phase
            # per finished task; tag cardinality is bounded by the
            # three phase names).
            from ray_tpu.util import metrics as _metrics

            for rec in task_events:
                for phase, ns in (rec.get("phases") or {}).items():
                    try:
                        _metrics.TASK_PHASE_SECONDS.observe(
                            ns / 1e9,
                            tags={"node_id": self.node_id, "phase": phase})
                    except Exception:
                        pass
        with self._lock:
            if device is not None:
                self._device_stats[worker_id] = device
            for rec in task_events:
                old = self._task_records.get(rec["task_id"])
                if old is not None and rec.get("submitted_at") is None:
                    # The agent saw the submit; the worker only saw the run.
                    rec["submitted_at"] = old.get("submitted_at")
                if len(self._task_records) >= self._task_records_cap:
                    self._task_records.popitem(last=False)
                    self._count_task_record_eviction()
                self._task_records[rec["task_id"]] = rec
        if log_lines:
            try:
                self.head.call(
                    "worker_logs", self.node_id, pid, log_lines)
            except Exception:
                pass  # head restarting/unreachable: logs are best-effort
        if spans or dropped:
            # Node-attributed so the head's trace assembly can apply
            # this node's clock offset to the batch; the truncation
            # count rides along (worker registries are never scraped,
            # so a clipped span buffer is only visible via this path).
            try:
                self.head.call(
                    "report_spans", spans or [], self.node_id,
                    dropped=dropped or 0)
            except Exception:
                pass
        failed = [r for r in task_events if r.get("state") == "FAILED"]
        if failed:
            # Error feed (reference: error_info pubsub to the driver).
            try:
                self.head.call("publish", "ERRORS", self.node_id, {
                    "node_id": self.node_id, "pid": pid,
                    "errors": [
                        {"task_id": r["task_id"], "name": r.get("name"),
                         "error": r.get("error")} for r in failed
                    ],
                })
            except Exception:
                pass
        return True

    def _is_duplicate_event_batch(self, worker_id, pid, seq) -> bool:
        """Record-and-test a worker event batch's sequence number (the
        replay-absorb half of rpc_worker_events' idempotence). Keyed by
        (worker_id, pid) so a restarted worker's fresh numbering never
        collides with its previous incarnation's."""
        if seq is None:
            return False  # legacy/probe caller: no dedup contract
        key = (worker_id, pid)
        with self._lock:
            last = self._event_seqs.get(key)
            if last is not None and seq <= last:
                return True
            self._event_seqs[key] = seq
            self._event_seqs.move_to_end(key)
            while len(self._event_seqs) > 4096:
                self._event_seqs.popitem(last=False)
        return False

    def rpc_list_task_records(self, limit: int = 1000):
        with self._lock:
            return [dict(r) for r in list(self._task_records.values())[-limit:]]

    def _dispatch_loop(self):
        while not self._shutdown.is_set():
            with self._queue_cv:
                while not self._task_queue and not self._shutdown.is_set():
                    self._queue_cv.wait(0.5)
                if self._shutdown.is_set():
                    return
                spec = self._task_queue.pop(0)
                self._dispatch_inflight += 1
            threading.Thread(
                target=self._dispatch_tracked, args=(spec,), daemon=True
            ).start()

    def _dispatch_tracked(self, spec: dict):
        try:
            self._dispatch_one(spec)
        finally:
            with self._lock:
                self._dispatch_inflight -= 1

    def _bundle_pool(self, spec) -> Optional[ResourcePool]:
        pg_id, idx = spec.get("pg_id"), spec.get("bundle_index", -1)
        if pg_id is None:
            return None
        with self._lock:
            if idx >= 0:
                return self._bundles.get((pg_id, idx))
            for (p, _i), pool in self._bundles.items():
                if p == pg_id and pool.feasible(spec.get("demand", {})):
                    return pool
        return None

    def _consume_cancel(self, task_id) -> bool:
        with self._lock:
            if task_id is not None and task_id in self._cancelled_tasks:
                self._cancelled_tasks.pop(task_id, None)
                return True
        return False

    def _dispatch_one(self, spec: dict):
        if self._consume_cancel(spec.get("task_id")):
            self._uncommit(spec)
            self._cancel_spec(spec)
            return
        demand = spec.get("demand", {})
        pool = self.pool
        if spec.get("pg_id") is not None:
            deadline = time.monotonic() + 60.0
            while True:
                bp = self._bundle_pool(spec)
                if bp is not None and bp.try_acquire(demand):
                    pool = bp
                    acquired = True
                    break
                if time.monotonic() > deadline:
                    self._fail_task(spec, "placement group bundle unavailable")
                    return
                time.sleep(0.01)
        else:
            acquired = pool.acquire(demand, timeout=300.0)
            self._uncommit(spec)  # demand now reflected in pool (or failed)
        if not acquired:
            self._fail_task(spec, f"resources {demand} unavailable")
            return
        rtenv = spec.get("runtime_env")
        env_key = (rtenv or {}).get("env_key", "")
        if spec.get("lang") == "cpp":
            bin_path = spec.get("cpp_worker_bin") or config.cpp_worker_bin
            if not bin_path or not os.path.exists(bin_path):
                pool.release(demand)
                self._fail_task(
                    spec,
                    "no C++ worker binary for this cluster (set "
                    "RAY_TPU_CPP_WORKER_BIN or pass worker_bin= to "
                    f"cpp_function; got {bin_path!r})",
                )
                return
            env_key = "cpp::" + bin_path
        try:
            w = self._checkout_worker(
                env_key=env_key,
                resolved_env=rtenv,
                dedicated=bool(spec.get("actor_create")),
            )
        except (TimeoutError, RuntimeError, OSError) as e:
            pool.release(demand)
            if isinstance(e, TimeoutError) and \
                    spec.setdefault("_checkout_misses", 0) < 2:
                # No worker became available in time — transient under
                # load (interpreter cold starts on a saturated host are
                # unbounded). Requeue rather than fail: the reference's
                # lease request simply stays queued in this situation.
                spec["_checkout_misses"] += 1
                self._requeue(spec)
                return
            # RuntimeError/OSError: runtime-env materialization failed
            # (missing package, bad zip) — surfaced as the task's error,
            # matching the reference's runtime-env setup failures.
            if isinstance(e, TimeoutError):
                self._fail_task(
                    spec,
                    f"no worker became available after "
                    f"{spec.get('_checkout_misses', 0) + 1} attempts of "
                    f"{config.worker_start_timeout_s:.0f}s (node "
                    f"saturated?)")
            else:
                self._fail_task(spec, f"worker setup failed: {e}")
            return
        self._record_task(spec, "RUNNING")
        w.current_task = {
            "spec": spec, "pool": pool, "demand": demand, "released": False,
            "started_at": time.monotonic(),
        }
        # A cancel that raced the queue→checkout window parked its id in
        # the cancelled set; honor it now that the task is attributable.
        if not spec.get("actor_create") and self._consume_cancel(
                spec.get("task_id")):
            self._release_current(w)
            self._return_worker(w)
            self._cancel_spec(spec)
            return
        try:
            failpoints.hit("agent.dispatch.before_push")
            if spec.get("actor_create"):
                w.is_actor = True
                w.actor_id = spec["actor_id"]
                w.client.call("create_actor", spec)
                try:
                    self.head.call(
                        "register_actor", spec["actor_id"], self.node_id,
                        w.address, spec.get("class_name", "Actor"),
                        spec.get("name"),
                    )
                except ValueError as e:
                    # Registration refused (name conflict, or the actor was
                    # killed while starting): record the death for callers
                    # and retire the worker — it already constructed state.
                    self._release_current(w)
                    w.is_actor = False
                    w.actor_id = None
                    try:
                        self.head.call(
                            "register_actor_failed", spec["actor_id"], str(e)
                        )
                    except Exception:
                        pass
                    w.proc.kill()
            else:
                if w.client.call("push_task", spec) is False:
                    # Duplicate admission: this worker process already
                    # accepted the same task id (a retried push whose
                    # first delivery lost only its reply). The first
                    # copy owns the task's fate — just release this
                    # dispatch's lease and return the worker.
                    with self._lock:
                        self._release_current(w)
                        w.current_task = None
                    self._return_worker(w)
        except Exception as e:  # worker died between checkout and push
            # The task never STARTED on the corpse, so retrying with a
            # fresh worker is always safe (unlike a mid-execution death,
            # which _on_worker_failure handles with retry budgets). A
            # pooled worker can die in this window legitimately: its
            # agent-death watchdog fires under extreme load, the OOM
            # killer picks it, an operator kills the pid.
            # CLAIM the task atomically against the reap loop: whoever
            # pops current_task owns the spec's fate — without this, the
            # reaper could fail the refs while we requeue (spurious error
            # + duplicate execution).
            with self._lock:
                current = w.current_task
                w.current_task = None
            if current is not None and not current["released"]:
                current["released"] = True
                current["pool"].release(current["demand"])
            retries = spec.setdefault("_dispatch_retries", 0)
            if current is None:
                # The reaper claimed it first and already settled the
                # task's fate; just make sure the corpse is cleaned up.
                self._on_worker_failure(w, f"dispatch failed: {e}",
                                        requeued=True)
            elif current.get("cancelled"):
                # A force-cancel killed the worker in this very window:
                # the task's fate is TaskCancelledError, never a retry
                # (the cancel marker was consumed; a requeue would run
                # a cancelled task to completion).
                self._on_worker_failure(w, f"dispatch failed: {e}",
                                        requeued=True)
                self._cancel_spec(spec)
            elif current.get("oom_reason"):
                from ray_tpu.core.object_ref import OutOfMemoryError

                self._on_worker_failure(w, f"dispatch failed: {e}",
                                        requeued=True)
                self._store_task_error(
                    spec,
                    OutOfMemoryError(spec.get("fname", "task"),
                                     current["oom_reason"]),
                    "FAILED",
                )
            elif not spec.get("actor_create") and retries < 2:
                spec["_dispatch_retries"] = retries + 1
                self._requeue(spec)
                self._on_worker_failure(w, f"dispatch failed: {e}",
                                        requeued=True)
            else:
                self._on_worker_failure(w, f"dispatch failed: {e}")
                self._fail_task(spec, f"worker died: dispatch failed: {e}")

    @staticmethod
    def _release_current(w: _Worker):
        current = w.current_task
        if current is not None and not current["released"]:
            current["released"] = True
            current["pool"].release(current["demand"])

    def rpc_task_done(self, worker_id):  # idempotent
        """Worker finished its current task; release + return to pool.

        Replay-absorbing: a worker whose task-done ACK was severed
        retries, and the second delivery must be a no-op — without the
        guard the replay appended the worker to the idle pool TWICE,
        and the dispatcher could lease one process for two concurrent
        tasks. The claim is taken ATOMICALLY under the lock (a pure
        current_task check would race a concurrent replay still
        between the check and the idle-pool append)."""
        with self._lock:
            w = self._workers.get(worker_id)
            current = w.current_task if w is not None else None
            if w is None or current is None or current.get("_done"):
                return False  # unknown worker, or a replayed done
            current["_done"] = True  # first delivery owns the return
        self._release_current(w)
        self._return_worker(w)
        return True

    def rpc_task_blocked(self, worker_id):
        """The worker's task is blocked in get(): free its resources so
        other (possibly nested) tasks can run (raylet parity for workers
        blocked in ray.get)."""
        with self._lock:
            w = self._workers.get(worker_id)
        if w is not None:
            self._release_current(w)
        return True

    def rpc_task_unblocked(self, worker_id):
        with self._lock:
            w = self._workers.get(worker_id)
        if w is None or w.current_task is None:
            return False
        current = w.current_task
        if current["released"]:
            current["pool"].acquire(current["demand"],
                                    timeout=config.cpu_reacquire_budget_s)
            current["released"] = False
        return True

    def _end_borrows(self, spec: dict):
        """Release the task's in-flight arg borrows on its behalf (the
        worker that would normally report task end is gone)."""
        if spec.get("borrowed") and spec.get("task_id"):
            try:
                self.head.call("ref_task_end", spec["task_id"])
            except Exception:
                pass

    def _fail_task(self, spec: dict, reason: str):
        from ray_tpu.core.object_ref import TaskError

        err = TaskError(spec.get("fname", "task"), reason, reason)
        self._store_task_error(spec, err, "FAILED")

    def _cancel_spec(self, spec: dict):
        from ray_tpu.core.object_ref import TaskCancelledError

        err = TaskCancelledError(spec.get("fname", "task"))
        self._store_task_error(spec, err, "CANCELLED")

    def _store_task_error(self, spec: dict, err: Exception, state: str):
        from ray_tpu.core import serialization as ser

        self._record_task(spec, state)
        self._end_borrows(spec)
        meta, chunks = ser.serialize(err)
        owner = spec.get("owner_addr")
        for oid in spec["oids"]:
            try:
                self.store.put(oid, chunks, b"E" + meta)
            except Exception:
                continue
            try:
                self.head.call("add_location", oid, self.node_id,
                               is_error=True, owner_addr=owner or "")
            except Exception:
                # Head unreachable (partition / shutdown): the owner
                # notify below still unblocks the owner directly, and
                # the owner's lineage path recovers otherwise. A failed
                # directory report must not kill the calling thread
                # (the reap loop runs through here).
                pass
            if owner:
                # Unblock the owner's local wait directly (its get() no
                # longer long-polls the head for self-owned refs).
                try:
                    self._owner_notify(owner, oid)
                except Exception:
                    pass

    def _owner_notify(self, owner: str, oid: str) -> None:
        with self._lock:
            c = self._owner_clients.get(owner)
            if c is None:
                if len(self._owner_clients) > 256:
                    old = self._owner_clients.popitem(last=False)[1]
                    old.close()
                c = self._owner_clients[owner] = RpcClient(
                    owner, timeout=10.0)
                c.chaos_src = self.address
        c.call("owner_add_location", oid, self.node_id, self.address,
               self.store_path, True, 0, timeout=10.0)

    def rpc_cancel_task(self, task_id: str, force: bool = False):  # idempotent
        """CancelTask analog (``core_worker.proto`` CancelTask → raylet).
        Queued: dropped here, TaskCancelledError stored. Running:
        force kills the worker process (its lease/pins are reclaimed by
        the reap path); otherwise the cancel is forwarded to the worker
        for cooperative delivery. Returns True if the task was found."""
        with self._queue_cv:
            self._cancelled_tasks[task_id] = True
            while len(self._cancelled_tasks) > 10_000:
                # Oldest-first eviction: never the id just inserted.
                self._cancelled_tasks.popitem(last=False)
            for i, spec in enumerate(self._task_queue):
                if spec.get("task_id") == task_id:
                    self._task_queue.pop(i)
                    self._cancelled_tasks.pop(task_id, None)
                    break
            else:
                spec = None
        if spec is not None:
            self._uncommit(spec)
            self._cancel_spec(spec)
            return True
        with self._lock:
            target = next(
                (w for w in self._workers.values()
                 if w.current_task is not None
                 and w.current_task["spec"].get("task_id") == task_id),
                None,
            )
            if target is None:
                return False
            target.current_task["cancelled"] = True
            self._cancelled_tasks.pop(task_id, None)
            if force:
                # Kill UNDER the lock: outside it, the task could finish
                # and the worker be re-leased to an innocent task first.
                target.proc.kill()  # reap loop stores TaskCancelledError
                return True
            client = target.client
        try:
            client.call("cancel_task", task_id, False)
        except Exception:
            return False
        return True

    def kill_worker_oom(self, w: _Worker, reason: str,
                        expected_task=None) -> bool:
        """Memory-monitor kill: the task fails with OutOfMemoryError (not
        a retriable worker death), actors go through their restart
        budget. The reap loop finishes the cleanup. ``expected_task`` is
        the current_task the monitor observed when it picked the victim —
        if the worker has since finished it (and possibly taken an
        unrelated task or gone idle), the kill is aborted."""
        with self._lock:
            current = w.current_task
            if expected_task is not None and current is not expected_task:
                return False
            if current is not None:
                current["oom_reason"] = reason
            w.proc.kill()
        return True

    def write_oom_report(self, reason: str, victim: _Worker,
                         current_task=None):
        """OOM forensics: snapshot WHY the node is out of memory —
        per-worker RSS, shm store occupancy, and the top resident
        objects by owner/callsite — to a bounded JSON report under the
        agent's log dir BEFORE the kill destroys the evidence. Returns
        the report path (None when log capture is disabled); the
        victim's death cause carries it so a post-mortem
        ``ray-tpu memory --node <id>`` / ``state.get_log`` explains the
        kill instead of just reporting it."""
        if self.log_dir is None:
            return None
        import json as _json

        from ray_tpu.cluster.memory_monitor import system_memory

        used, total = system_memory()
        try:
            workers = self.rpc_worker_stats(fresh=True)
        except Exception:
            workers = []
        top_objects = []
        store_stats = {}
        try:
            # Bounded scan: the node is OUT OF MEMORY right now — a
            # capped join (may miss objects on a huge directory) beats
            # deferring the kill while RSS keeps climbing.
            rep = self.rpc_object_store_stats(max_objects=256)
            store_stats = rep.get("stats", {})
            top_objects = (rep.get("objects") or [])[:20]
        except Exception:
            pass
        spec = (current_task or {}).get("spec") or {}
        ts = time.time()
        report = {
            "ts": round(ts, 3),
            "node_id": self.node_id,
            "reason": reason,
            "victim": {
                "worker_id": victim.worker_id,
                "pid": victim.proc.pid,
                "is_actor": victim.is_actor,
                "actor_id": victim.actor_id,
                "task": spec.get("fname") or spec.get("method")
                or spec.get("class_name"),
                "task_id": spec.get("task_id"),
            },
            "system_memory": {"used_bytes": used, "total_bytes": total},
            "workers": [
                {"worker_id": s.get("worker_id"), "pid": s.get("pid"),
                 "rss_bytes": s.get("rss_bytes"),
                 "is_actor": s.get("is_actor")}
                for s in workers
            ],
            "object_store": store_stats,
            "top_objects": top_objects,
        }
        path = os.path.join(
            self.log_dir,
            f"oom_report_{victim.worker_id}_{int(ts * 1000)}.json")
        try:
            with open(path, "w") as f:
                _json.dump(report, f, indent=1, default=str)
        except OSError:
            return None
        with self._lock:
            self._oom_reports.append({
                "path": path, "ts": round(ts, 3), "reason": reason,
                "worker_id": victim.worker_id,
            })
            # Bounded like the capture index — evicted entries take
            # their FILES with them (sustained pressure churns victims;
            # the index trim alone would grow log_dir without bound).
            evicted, self._oom_reports = (
                self._oom_reports[:-16], self._oom_reports[-16:])
        for old in evicted:
            try:
                os.unlink(old["path"])
            except OSError:
                pass
        return path

    def discard_oom_report(self, path: str) -> None:
        """The kill this report was written for never landed (the
        victim's task ended meanwhile): drop the orphan — no death
        cause references it."""
        with self._lock:
            self._oom_reports = [r for r in self._oom_reports
                                 if r.get("path") != path]
        try:
            os.unlink(path)
        except OSError:
            pass

    def record_oom_kill(self, cause: str, victim: _Worker,
                        current_task=None, report_path=None):
        """An OOM kill actually happened: bump the per-node counter
        (visible in /metrics/cluster via federation) and emit a
        structured NODES event in the drain-event shape, so OOM kills
        surface on the control plane, not just in the victim's stderr."""
        from ray_tpu.util import metrics as _metrics

        try:
            _metrics.OOM_KILLS_TOTAL.inc(tags={"node_id": self.node_id})
        except Exception:
            pass
        spec = (current_task or {}).get("spec") or {}
        try:
            self.head.call("publish", "NODES", self.node_id, {
                "node_id": self.node_id,
                "state": "OOM_KILL",
                "reason": cause,
                "worker_id": victim.worker_id,
                "task": spec.get("fname") or spec.get("method")
                or spec.get("class_name"),
                "report_path": report_path,
            })
        except Exception:
            pass  # head restarting: the kill itself is not best-effort

    def _on_worker_failure(self, w: _Worker, cause: str,
                           requeued: bool = False):
        """Clean up a dead worker. ``requeued``: the caller already put
        the task back on the queue (pre-start death), so its refs must
        NOT be failed here."""
        with self._lock:
            self._workers.pop(w.worker_id, None)
            pool = self._idle.get(w.env_key)
            if pool is not None and w in pool:
                pool.remove(w)
            rec = self._worker_logs.get(w.worker_id)
            if rec is not None and rec["ended_at"] is None:
                rec["ended_at"] = time.time()
            # Latest device snapshot dies with the worker; its exported
            # gauge children are retracted on the next telemetry pass.
            self._device_stats.pop(w.worker_id, None)
            current = None if requeued else w.current_task
            w.current_task = None
        if w.proc.poll() is None:
            w.proc.kill()
            try:
                w.proc.wait(timeout=5)
            except Exception:
                pass
        # Reclaim shm pins the dead process can never release.
        try:
            self.store.release_dead(w.proc.pid)
        except Exception:
            pass
        if w.is_actor and w.actor_id:
            try:
                self.head.call("mark_actor_dead", w.actor_id, cause,
                               True, w.address)
            except Exception:
                pass
        if w.client_id:
            # The process's holder registrations die with it.
            try:
                self.head.call("ref_client_dead", w.client_id)
            except Exception:
                pass
        if current is not None:
            if not current["released"]:
                current["released"] = True
                current["pool"].release(current["demand"])
            spec = current["spec"]
            if spec.get("actor_create"):
                self._end_borrows(spec)
            elif current.get("cancelled"):
                # Force-cancel killed this worker on purpose: the result is
                # TaskCancelledError, not a retriable worker death.
                self._cancel_spec(spec)
            elif current.get("oom_reason"):
                from ray_tpu.core.object_ref import OutOfMemoryError

                self._store_task_error(
                    spec,
                    OutOfMemoryError(spec.get("fname", "task"),
                                     current["oom_reason"]),
                    "FAILED",
                )
            else:
                self._fail_task(spec, f"worker died: {cause}")  # ends borrows

    def _reap_loop(self):
        """Detect dead worker processes (WorkerPool's disconnect handling)
        and retry deletes deferred while readers held the object."""
        while not self._shutdown.wait(0.2):
            with self._lock:
                dead = [
                    w for w in self._workers.values() if w.proc.poll() is not None
                ]
                deferred = list(self._deferred_deletes)
            for w in dead:
                try:
                    self._on_worker_failure(
                        w, f"exit code {w.proc.returncode}"
                    )
                except Exception:
                    # The reap loop must survive anything one corpse's
                    # cleanup throws (chaos-partitioned head, store
                    # teardown races): a dead reaper leaks every later
                    # worker death.
                    continue
            for oid in deferred:
                if self.store.delete(oid) or not self.store.contains(oid):
                    with self._lock:
                        self._deferred_deletes.discard(oid)

    # -- actors -----------------------------------------------------------

    def rpc_kill_actor(self, actor_id, no_restart=True):
        with self._lock:
            target = next(
                (w for w in self._workers.values() if w.actor_id == actor_id),
                None,
            )
        if target is None:
            return False
        if no_restart:
            try:
                self.head.call("mark_actor_dead", actor_id,
                               "killed via ray_tpu.kill", False)
            except Exception:
                pass
            target.is_actor = False  # already marked dead; don't re-mark
            target.actor_id = None
        # With no_restart=False, the reap loop observes the death and the
        # head reconstructs within the max_restarts budget.
        target.proc.kill()
        return True

    def rpc_actor_ctor_failed(self, actor_id, cause):
        # A raising constructor is deterministic — restarting would just
        # raise again (reference restarts only on process failure).
        try:
            self.head.call("mark_actor_dead", actor_id, cause, False)
        except Exception:
            pass
        return True

    def rpc_detach_actor_worker(self, actor_id):
        """Drain-migration support: the head already owns this actor's
        state transition (RESTARTING on another node), so the OLD
        incarnation's worker is detached from its actor binding and
        killed — the reap loop then does plain worker cleanup instead of
        reporting a second, budget-consuming actor death."""
        with self._lock:
            target = next(
                (w for w in self._workers.values()
                 if w.actor_id == actor_id),
                None,
            )
            if target is None:
                return False
            target.is_actor = False
            target.actor_id = None
        target.proc.kill()
        return True

    # -- drain / preemption (node_manager.proto DrainRaylet analog) --------

    def rpc_drain_self(self, reason: str = "requested",
                       deadline_s: float | None = None):
        """The head's drain coordinator (or our own preemption watcher)
        says this node is leaving: stop admitting leased pushes; queued
        and running tasks keep going until the coordinator's deadline."""
        with self._lock:
            self._draining = True
            self._drain_reason = reason
        return True

    def rpc_drain_status(self):  # idempotent
        """Quiescence probe for the drain coordinator: queued tasks plus
        busy non-actor workers (actor processes hold their creation spec
        as current_task for life, so they never count as 'running')."""
        with self._lock:
            running = self._dispatch_inflight + sum(
                1 for w in self._workers.values()
                if w.current_task is not None and not w.is_actor
                and w.proc.poll() is None
            )
            return {
                "draining": self._draining,
                "reason": self._drain_reason,
                "queued": len(self._task_queue),
                "running": running,
            }

    def _self_drain(self, reason: str = "preemption") -> None:
        """Self-initiated drain (SIGTERM / preemption notice): ask the
        head to run the drain protocol for us — wait=False because the
        coordinator will call back into this agent (drain_self, then
        shutdown_node once quiesced)."""
        with self._lock:
            if self._draining:
                return
            self._draining = True
            self._drain_reason = reason
        try:
            self.head.call(
                "drain_node", self.node_id, reason, None, False,
                timeout=10.0)
        except Exception:
            # Head unreachable and the node is going away regardless:
            # local stop is the only remaining graceful option.
            self.stop()

    def _preemption_watcher(self) -> None:
        """Pluggable preemption-signal poll (the metadata-server watcher
        of cloud deployments; file-triggered in tests). Detection
        self-initiates a drain with reason="preemption" so actors migrate
        and owners get the retry-budget exemption BEFORE the VM vanishes."""
        interval = max(0.05, config.preemption_poll_interval_s)
        sig_file = config.preemption_signal_file
        url = config.preemption_metadata_url
        while not self._shutdown.wait(interval):
            with self._lock:
                if self._draining:
                    return
            if sig_file and self._signal_file_hit(sig_file):
                self._self_drain("preemption")
                return
            if url and self._metadata_preempted(url):
                self._self_drain("preemption")
                return

    def _signal_file_hit(self, path: str) -> bool:
        """The signal file preempts every node when empty, or only the
        nodes whose ids appear in its contents."""
        try:
            with open(path) as f:
                body = f.read().strip()
        except OSError:
            return False
        return body == "" or self.node_id in body

    @staticmethod
    def _metadata_preempted(url: str) -> bool:
        """GCE-shaped poll: .../instance/preempted returns "TRUE" once
        the termination notice lands."""
        import urllib.request

        try:
            req = urllib.request.Request(
                url, headers={"Metadata-Flavor": "Google"})
            with urllib.request.urlopen(req, timeout=2.0) as resp:
                body = resp.read().decode("utf-8", "replace").strip()
            return body.upper() in ("TRUE", "PREEMPTED", "1")
        except Exception:
            return False

    # -- placement group bundles (2PC participant) ------------------------

    def rpc_prepare_bundle(self, pg_id, bundle_index, bundle):  # idempotent
        with self._lock:
            if (pg_id, bundle_index) in self._bundles:
                # Idempotent replay: the head's prepare landed but its
                # reply was lost (severed channel / reconnect retry).
                # Acquiring again would double-reserve the node for one
                # logical bundle — exactly-once reservation means the
                # retry is an ack, not a second carve-out.
                return True
        if not self.pool.feasible(bundle):
            raise ValueError(f"bundle {bundle} infeasible on node {self.node_id}")
        if not self.pool.acquire(
                bundle, timeout=config.bundle_reserve_timeout_s):
            raise TimeoutError(f"bundle {bundle} not reservable on {self.node_id}")
        with self._lock:
            if (pg_id, bundle_index) in self._bundles:
                # Lost the race against a concurrent replay that
                # acquired first: give this acquisition back.
                self.pool.release(bundle)
                return True
            self._bundles[(pg_id, bundle_index)] = ResourcePool(bundle)
            self._bundle_state[(pg_id, bundle_index)] = "PREPARED"
        return True

    def rpc_commit_bundle(self, pg_id, bundle_index):  # idempotent
        with self._lock:
            # Idempotent: committing an already-committed (or unknown —
            # returned while the commit retried) bundle changes nothing.
            if (pg_id, bundle_index) in self._bundles:
                self._bundle_state[(pg_id, bundle_index)] = "COMMITTED"
        return True

    def rpc_bundle_table(self):
        """This node's live placement-group reservations:
        ``{"<pg_id>:<bundle_index>": state}`` (PREPARED | COMMITTED).
        The chaos soak's leak invariant joins this against the head's
        PG table — a reservation here that no live group's placement
        explains is a leaked carve-out."""
        with self._lock:
            return {
                f"{pg_id}:{bi}": state
                for (pg_id, bi), state in self._bundle_state.items()
            }

    def rpc_return_bundle(self, pg_id, bundle_index):  # idempotent
        with self._lock:
            pool = self._bundles.pop((pg_id, bundle_index), None)
            self._bundle_state.pop((pg_id, bundle_index), None)
            # Reference semantics: removing a PG kills the work running
            # in its bundles (gcs_placement_group_manager removal path).
            # Without this, returning the reservation below would
            # oversubscribe the node for as long as a straggler runs.
            # Scoped to THIS bundle: returning one bundle (a reschedule
            # rollback or a single migrated bundle's vacate) must not
            # kill a SIBLING bundle's healthy workers on the same node
            # — only any-bundle tasks (bundle_index < 0, whose pool we
            # never recorded) die with whichever bundle goes first.
            victims = [
                w for w in self._workers.values()
                if w.current_task is not None
                and w.current_task["spec"].get("pg_id") == pg_id
                and w.current_task["spec"].get(
                    "bundle_index", -1) in (-1, bundle_index)
                and w.proc.poll() is None
            ]
        for w in victims:
            w.proc.kill()  # reap loop stores the task error / actor death
        if pool is not None:
            # Return the bundle's FULL reservation. Any just-killed (or
            # killed-but-unreaped) worker's release drains into this now-
            # orphaned pool object, not the node pool, so returning the
            # total cannot double-free — while returning only
            # pool.available() would permanently leak whatever a
            # not-yet-reaped worker still held (observed: a finished tune
            # trial starving the next trial's PG).
            self.pool.release(pool.total)
        return True

    # -- node reporter: logs / stacks / telemetry --------------------------
    # (reference: dashboard/modules/reporter/reporter_agent.py and
    # _private/log_monitor.py — per-worker log files, py-spy stack
    # dumps/profiles, and per-process cpu/mem stats, served by the node.)

    def _log_record(self, worker_id: str) -> dict:
        with self._lock:
            rec = self._worker_logs.get(worker_id)
        if rec is None:
            raise ValueError(
                f"no log capture for worker {worker_id!r} on node "
                f"{self.node_id} (unknown worker, or capture disabled)")
        return rec

    @staticmethod
    def _log_path(rec: dict, stream: str) -> str:
        if stream in ("out", "stdout"):
            return rec["stdout_path"]
        if stream in ("err", "stderr"):
            return rec["stderr_path"]
        raise ValueError(f"stream must be out|err, got {stream!r}")

    def rpc_list_worker_logs(self):
        """Every worker (live and recently dead) with captured logs:
        id, pid, file paths+sizes, lifetime, actor binding."""
        with self._lock:
            recs = [dict(r) for r in self._worker_logs.values()]
            live = {
                w.worker_id: w for w in self._workers.values()
                if w.proc.poll() is None
            }
        out = []
        for rec in recs:
            w = live.get(rec["worker_id"])
            rec["alive"] = w is not None
            rec["is_actor"] = bool(w is not None and w.is_actor)
            rec["actor_id"] = w.actor_id if w is not None else None
            for stream in ("stdout", "stderr"):
                try:
                    rec[f"{stream}_bytes"] = os.path.getsize(
                        rec[f"{stream}_path"])
                except OSError:
                    rec[f"{stream}_bytes"] = 0
            out.append(rec)
        return out

    def rpc_read_worker_log(self, worker_id, stream: str = "out",
                            offset: int | None = None,
                            max_bytes: int = 1 << 20,
                            tail_lines: int | None = None):
        """One bounded read of a worker's captured stdout/stderr.
        ``tail_lines`` reads the file end (the ``ray logs`` default);
        otherwise reads [offset, offset+max_bytes) — pass the returned
        ``offset`` back to poll-follow."""
        path = self._log_path(self._log_record(worker_id), stream)
        try:
            size = os.path.getsize(path)
        except OSError:
            size = 0
        max_bytes = max(1, min(int(max_bytes), 8 << 20))
        if tail_lines is not None:
            start = max(0, size - max_bytes)
            try:
                with open(path, "rb") as f:
                    f.seek(start)
                    blob = f.read(max_bytes)
            except OSError:  # file evicted/unlinked between stat and read
                blob = b""
            n = int(tail_lines)
            lines = blob.decode("utf-8", "replace").splitlines()
            data = "\n".join(lines[-n:]) if n > 0 else ""
            if data:
                data += "\n"
            return {"worker_id": worker_id, "stream": stream,
                    "offset": size, "size": size, "data": data}
        start = min(max(0, int(offset or 0)), size)
        try:
            with open(path, "rb") as f:
                f.seek(start)
                blob = f.read(max_bytes)
        except OSError:
            blob = b""
        return {"worker_id": worker_id, "stream": stream,
                "offset": start + len(blob), "size": size,
                "data": blob.decode("utf-8", "replace")}

    def rpc_follow_worker_log(self, worker_id, stream: str = "out",
                              offset: int = 0, idle_timeout_s: float = 10.0,
                              poll_s: float = 0.2):
        """Server-streamed tail -f of a worker log (use with
        ``call_stream``): yields ``{"offset", "data"}`` chunks as the
        file grows, ends after the worker is gone and drained, or after
        ``idle_timeout_s`` without growth."""
        rec = self._log_record(worker_id)
        path = self._log_path(rec, stream)
        offset = max(0, int(offset))
        last_growth = time.monotonic()
        while not self._shutdown.is_set():
            try:
                size = os.path.getsize(path)
            except OSError:
                return
            if offset < size:
                with open(path, "rb") as f:
                    f.seek(offset)
                    blob = f.read(1 << 16)
                offset += len(blob)
                last_growth = time.monotonic()
                yield {"offset": offset,
                       "data": blob.decode("utf-8", "replace")}
                continue
            with self._lock:
                w = self._workers.get(worker_id)
                dead = w is None or w.proc.poll() is not None
            if dead or time.monotonic() - last_growth > idle_timeout_s:
                return
            time.sleep(poll_s)

    def _live_worker(self, worker_id) -> _Worker:
        with self._lock:
            w = self._workers.get(worker_id)
        if w is None or w.proc.poll() is not None:
            raise ValueError(
                f"no live worker {worker_id!r} on node {self.node_id}")
        if w.client is None and not w.ready.wait(5.0):
            raise ValueError(f"worker {worker_id!r} is not serving yet")
        return w

    def rpc_dump_worker_stack(self, worker_id):
        """Instantaneous all-thread stack report of one worker
        (``ray stack`` per-worker hop)."""
        return self._live_worker(worker_id).client.call(
            "dump_stack", timeout=15.0)

    def rpc_profile_worker(self, worker_id, duration_s: float = 1.0,
                           interval_s: float = 0.01):
        """Time-sampled profile of one worker (py-spy record analog);
        returns the plain-data profile from util/stack_sampler."""
        w = self._live_worker(worker_id)
        prof = w.client.call(
            "profile", float(duration_s), float(interval_s),
            timeout=float(duration_s) + 30.0)
        prof["node_id"] = self.node_id
        prof["pid"] = w.proc.pid
        return prof

    def rpc_device_stats(self, fresh: bool = False):
        """Per-worker JAX/XLA device snapshots on this node. Steady
        state comes from the workers' batched reports; ``fresh`` RPCs
        every live worker for an immediate snapshot (workers that never
        imported jax answer with a stub)."""
        with self._lock:
            live = {
                w.worker_id: w for w in self._workers.values()
                if w.proc.poll() is None
            }
            snaps = {wid: dict(s) for wid, s in self._device_stats.items()
                     if wid in live}
        if fresh:
            # Concurrent, short per-worker budget: a GIL-starved worker
            # must not serialize the poll past the head's per-agent
            # fanout timeout (which would drop this node's HEALTHY
            # snapshots along with the stuck one).
            targets = [(wid, w.client) for wid, w in live.items()
                       if w.client is not None]
            if targets:
                from concurrent.futures import ThreadPoolExecutor

                def one(item):
                    wid, client = item
                    try:
                        return wid, client.call("device_stats",
                                                timeout=3.0)
                    except Exception:
                        return wid, None

                with ThreadPoolExecutor(
                        max_workers=min(8, len(targets))) as pool:
                    for wid, snap in pool.map(one, targets):
                        if snap is not None:
                            snaps[wid] = snap
        out = []
        for wid, snap in snaps.items():
            snap["worker_id"] = wid
            snap["node_id"] = self.node_id
            out.append(snap)
        return out

    def rpc_capture_profile(self, worker_id, duration_s: float = 1.0,
                            interval_s: float = 0.01):
        """Remote profiler capture: open a timed ``jax.profiler.trace``
        window in the worker (stack-sampler fallback off-jax). The
        worker writes the trace files DIRECTLY into this node's capture
        dir (same host, shared filesystem — no trace bytes on the
        worker→agent hop); the returned manifest's files stream back to
        remote clients via read_capture_file."""
        import shutil

        w = self._live_worker(worker_id)
        base = self.log_dir
        if base is None:
            import tempfile

            base = tempfile.mkdtemp(prefix="ray_tpu_tprof_")
        cap_id = f"tprof-{worker_id}-{os.urandom(3).hex()}"
        cap_dir = os.path.join(base, cap_id)
        os.makedirs(cap_dir, exist_ok=True)
        try:
            res = w.client.call(
                "capture_profile", float(duration_s), float(interval_s),
                cap_dir, timeout=float(duration_s) + 60.0)
        except Exception:
            shutil.rmtree(cap_dir, ignore_errors=True)
            raise
        # Manifest from OUR walk of the dir, not the worker's claim —
        # read_capture_file trusts these names when joining paths.
        names = []
        for dirpath, _dirs, fnames in os.walk(cap_dir):
            for fname in fnames:
                path = os.path.join(dirpath, fname)
                try:
                    names.append({
                        "name": os.path.relpath(path, cap_dir),
                        "size": os.path.getsize(path),
                    })
                except OSError:
                    continue
        manifest = {
            "capture_id": cap_id,
            "node_id": self.node_id,
            "worker_id": worker_id,
            "kind": res.get("kind"),
            "duration_s": res.get("duration_s"),
            "files": sorted(names, key=lambda f: f["name"]),
        }
        with self._lock:
            self._captures[cap_id] = {**manifest, "dir": cap_dir}
            evict = []
            while len(self._captures) > 16:  # bound trace-dir disk use
                evict.append(self._captures.popitem(last=False)[1])
        for old in evict:
            shutil.rmtree(old["dir"], ignore_errors=True)
        return manifest

    def rpc_read_capture_file(self, capture_id, name, offset: int = 0,
                              max_bytes: int = 1 << 20):
        """One bounded read of a capture's trace file ([offset,
        offset+max_bytes)) — the same poll-follow shape as
        read_worker_log, so big TPU traces stream instead of riding one
        frame."""
        with self._lock:
            m = self._captures.get(capture_id)
        if m is None:
            raise ValueError(
                f"no capture {capture_id!r} on node {self.node_id}")
        if not any(f["name"] == name for f in m["files"]):
            raise ValueError(
                f"capture {capture_id} has no file {name!r}")
        path = os.path.join(m["dir"], name)
        start = max(0, int(offset))
        max_bytes = max(1, min(int(max_bytes), 8 << 20))
        try:
            size = os.path.getsize(path)
            with open(path, "rb") as f:
                f.seek(start)
                blob = f.read(max_bytes)
        except OSError as e:
            # The trace file vanished mid-stream (external cleanup):
            # raising makes the client's download FAIL rather than
            # silently hand over a truncated, corrupt trace.
            raise ValueError(
                f"capture {capture_id} file {name!r} unreadable: {e}")
        return {"name": name, "offset": start + len(blob), "size": size,
                "data": blob}

    def rpc_metrics_text(self):
        """This agent process's full registry in Prometheus exposition
        format — the per-node input to the head's /metrics/cluster
        federation. Store occupancy is refreshed per scrape (it is one
        cheap native call; worker /proc sampling stays on the loop).

        Scrape-cost self-accounting: the render-time gauge is set to
        the PREVIOUS scrape's cost before rendering, so the cost of
        serving metrics is itself visible in the body — one scrape
        behind by construction (this scrape's cost can't be known
        until after the text is built)."""
        import time as _time

        from ray_tpu.util import metrics as _metrics

        try:
            self._export_store_gauges()
            _metrics.AGENT_METRICS_RENDER_SECONDS.set(
                getattr(self, "_last_metrics_render_s", 0.0),
                tags={"node_id": self.node_id})
        except Exception:
            pass
        t0 = _time.perf_counter()
        body = _metrics.prometheus_text()
        self._last_metrics_render_s = _time.perf_counter() - t0
        return body

    def rpc_has_worker(self, worker_id):
        """Routing probe for the head: does this node know the worker?"""
        with self._lock:
            w = self._workers.get(worker_id)
            return {
                "known": worker_id in self._worker_logs or w is not None,
                "live": w is not None and w.proc.poll() is None,
            }

    @staticmethod
    def _read_proc(pid: int):
        """(cpu_ticks, rss_bytes) for a pid from /proc, or None where
        /proc isn't available (telemetry degrades to disabled)."""
        try:
            with open(f"/proc/{pid}/stat", "rb") as f:
                # Fields after the parenthesized comm (which may contain
                # spaces): index 11/12 are utime/stime (fields 14/15).
                parts = f.read().rsplit(b")", 1)[1].split()
            ticks = int(parts[11]) + int(parts[12])
            with open(f"/proc/{pid}/statm", "rb") as f:
                rss_pages = int(f.read().split()[1])
            return ticks, rss_pages * os.sysconf("SC_PAGE_SIZE")
        except (OSError, ValueError, IndexError):
            return None

    def _sample_worker_stats(self) -> list:
        """Sample every live worker's CPU/RSS/uptime, refresh the
        Prometheus gauges (pruning dead workers' series), and cache the
        snapshot for rpc_worker_stats. Serialized, and rate-limited to
        one pass per 200ms: cpu%% needs a meaningful tick delta."""
        with self._telemetry_lock:
            return self._sample_worker_stats_locked()

    def _sample_worker_stats_locked(self) -> list:
        from ray_tpu.util import metrics as _metrics

        hz = os.sysconf("SC_CLK_TCK") or 100
        now = time.monotonic()
        if self._shutdown.is_set():
            return []  # stopping: never re-export retracted series
        if now - self._last_sample < 0.2 and self._worker_stats:
            with self._lock:
                return [dict(s) for s in self._worker_stats.values()]
        self._last_sample = now
        with self._lock:
            workers = [
                (w.worker_id, w.proc.pid, w.started_at, w.is_actor,
                 w.actor_id)
                for w in self._workers.values() if w.proc.poll() is None
            ]
        stats: dict[str, dict] = {}
        for wid, pid, started_at, is_actor, actor_id in workers:
            got = self._read_proc(pid)
            if got is None:
                continue
            ticks, rss = got
            prev = self._cpu_prev.get(wid)
            cpu = 0.0
            if prev is not None and now > prev[1]:
                cpu = max(0.0, (ticks - prev[0]) / hz / (now - prev[1])
                          * 100.0)
            self._cpu_prev[wid] = (ticks, now)
            stats[wid] = {
                "worker_id": wid,
                "node_id": self.node_id,
                "pid": pid,
                "cpu_percent": round(cpu, 2),
                "rss_bytes": rss,
                "uptime_s": round(time.time() - started_at, 2),
                "is_actor": is_actor,
                "actor_id": actor_id,
            }
        exported = set()
        for s in stats.values():
            tags = {"node_id": self.node_id, "worker_id": s["worker_id"],
                    "pid": str(s["pid"])}
            exported.add((s["worker_id"], str(s["pid"])))
            _metrics.WORKER_CPU_PERCENT.set(s["cpu_percent"], tags=tags)
            _metrics.WORKER_RSS_BYTES.set(s["rss_bytes"], tags=tags)
            _metrics.WORKER_UPTIME_SECONDS.set(s["uptime_s"], tags=tags)
        _metrics.NODE_WORKER_COUNT.set(
            len(stats), tags={"node_id": self.node_id})
        for wid, pid in self._exported_gauges - exported:
            tags = {"node_id": self.node_id, "worker_id": wid, "pid": pid}
            _metrics.WORKER_CPU_PERCENT.remove(tags=tags)
            _metrics.WORKER_RSS_BYTES.remove(tags=tags)
            _metrics.WORKER_UPTIME_SECONDS.remove(tags=tags)
            self._cpu_prev.pop(wid, None)
        # Serve gauges are keyed off THEIR OWN table, not the /proc
        # sample history: a replica that shipped gauge events and died
        # before its first telemetry sample never entered
        # _exported_gauges, but its series must still be retracted.
        # Liveness comes from the worker TABLE (not `stats`): serve
        # gauges are event-driven, so a spurious retraction on one
        # transient /proc read failure would never be re-exported for
        # an idle replica.
        live_wids = {wid for wid, *_ in workers}
        with self._lock:
            dead_serve = [wid for wid in self._serve_gauges
                          if wid not in live_wids]
            dead_train = [wid for wid in self._train_gauges
                          if wid not in live_wids]
        for wid in dead_serve:
            self._retract_serve_series(wid)
        for wid in dead_train:
            self._retract_train_series(wid)
        self._exported_gauges = exported
        self._export_device_gauges(set(stats))
        self._export_store_gauges_locked()
        with self._lock:
            self._worker_stats = stats
        return list(stats.values())

    def _export_device_gauges(self, live_workers: set) -> None:
        """Refresh the ray_tpu_device_* families from the workers' latest
        device snapshots, pruning dead workers' children (same lifecycle
        as the /proc gauges). The node-level device count is always set —
        0 is the documented stub on nodes where jax never loads."""
        from ray_tpu.util import metrics as _metrics

        with self._lock:
            for wid in list(self._device_stats):
                if wid not in live_workers:
                    del self._device_stats[wid]
            snaps = {wid: s for wid, s in self._device_stats.items()}
        exported: set[tuple] = set()
        n_devices = 0
        for wid, snap in snaps.items():
            wtags = {"node_id": self.node_id, "worker_id": wid}
            comp = snap.get("compile") or {}
            _metrics.DEVICE_JAX_COMPILES.set(
                comp.get("backend_compiles", 0), tags=wtags)
            _metrics.DEVICE_JAX_COMPILE_SECONDS.set(
                comp.get("compile_seconds", 0.0), tags=wtags)
            _metrics.DEVICE_JAX_CACHE_HITS.set(
                comp.get("cache_hits", 0), tags=wtags)
            _metrics.DEVICE_JAX_CACHE_MISSES.set(
                comp.get("cache_misses", 0), tags=wtags)
            exported.add((wid, None))
            devices = snap.get("devices") or []
            n_devices = max(n_devices, len(devices))
            for d in devices:
                dev = f"{d.get('platform', '?')}:{d.get('id', -1)}"
                dtags = {**wtags, "device": dev}
                _metrics.DEVICE_MEM_IN_USE.set(
                    d.get("bytes_in_use", 0), tags=dtags)
                _metrics.DEVICE_MEM_PEAK.set(
                    d.get("peak_bytes_in_use", 0), tags=dtags)
                _metrics.DEVICE_MEM_LIMIT.set(
                    d.get("bytes_limit", 0), tags=dtags)
                exported.add((wid, dev))
        _metrics.DEVICE_COUNT.set(
            n_devices, tags={"node_id": self.node_id})
        for wid, dev in self._exported_device - exported:
            self._retract_device_series(wid, dev)
        self._exported_device = exported

    def _retract_serve_series(self, wid: str) -> None:
        """Drop the serve gauge children a dead worker's events created
        (same lifecycle as the /proc and device gauges)."""
        with self._lock:
            keys = self._serve_gauges.pop(wid, None)
        if keys:
            try:
                from ray_tpu.serve import _observability as _serve_obs

                _serve_obs.retract_gauges(keys, self.node_id)
            except Exception:
                pass

    def _retract_train_series(self, wid: str) -> None:
        """Drop the goodput gauge children (per-rank step time) a dead
        worker's events created — a finished trial's ranks must vanish
        from the federated scrape."""
        with self._lock:
            keys = self._train_gauges.pop(wid, None)
        if keys:
            try:
                from ray_tpu.util import goodput as _goodput

                _goodput.retract_gauges(keys, self.node_id)
            except Exception:
                pass

    def _retract_device_series(self, wid: str, dev: str | None) -> None:
        """Drop one exported device-gauge child: the compile-counter
        family for ``dev is None``, the per-device memory family
        otherwise. The ONE place listing the gauge families, shared by
        the telemetry prune pass and agent-stop cleanup."""
        from ray_tpu.util import metrics as _metrics

        wtags = {"node_id": self.node_id, "worker_id": wid}
        if dev is None:
            _metrics.DEVICE_JAX_COMPILES.remove(tags=wtags)
            _metrics.DEVICE_JAX_COMPILE_SECONDS.remove(tags=wtags)
            _metrics.DEVICE_JAX_CACHE_HITS.remove(tags=wtags)
            _metrics.DEVICE_JAX_CACHE_MISSES.remove(tags=wtags)
        else:
            dtags = {**wtags, "device": dev}
            _metrics.DEVICE_MEM_IN_USE.remove(tags=dtags)
            _metrics.DEVICE_MEM_PEAK.remove(tags=dtags)
            _metrics.DEVICE_MEM_LIMIT.remove(tags=dtags)

    def _telemetry_loop(self):
        interval = config.worker_telemetry_interval_s
        while not self._shutdown.wait(interval):
            try:
                self._sample_worker_stats()
            except Exception:
                continue  # telemetry is best-effort, never fatal

    def rpc_worker_stats(self, fresh: bool = False):
        """Latest per-worker CPU/RSS/uptime snapshot (GetNodeStats
        analog); ``fresh`` forces an immediate sample pass."""
        with self._lock:
            snap = [dict(s) for s in self._worker_stats.values()]
        if fresh or not snap:
            try:
                snap = self._sample_worker_stats()
            except Exception:
                pass
        return snap

    # -- object serving ---------------------------------------------------

    def _restore_backend_for(self, uri: str):
        """The spill backend behind ``uri`` — the node's own backend
        when it matches (the common case: one cluster-wide spill_uri),
        else a cached foreign-URI backend (restore of objects spilled
        under an older config)."""
        if uri == getattr(self.spill_backend, "uri", None):
            return self.spill_backend
        with self._lock:
            be = self._restore_backends.get(uri)
            if be is None:
                from ray_tpu.cluster import spill_storage

                if len(self._restore_backends) > 8:
                    self._restore_backends.clear()
                be = self._restore_backends[uri] = \
                    spill_storage.backend_for(uri)
        return be

    def _count_restore(self) -> None:
        from ray_tpu.util import metrics as _metrics

        self._spill_restores += 1
        try:
            _metrics.SPILL_RESTORES_TOTAL.inc(
                tags={"node_id": self.node_id})
        except Exception:
            pass

    def rpc_restore_from_uri(self, oid, uri, owner=None):
        """Restore one spilled object from a (remote) spill target into
        THIS node's store — the recovery half of remote spill: the head
        routes a dead node's spilled objects here instead of letting
        lineage recompute them. Idempotent: an already-present object
        returns True without touching the target. ``owner`` (the owning
        client's directory address, when the head knows it) gets the
        new location pushed directly so self-owned gets unblock without
        a head sweep. Returns whether the object is now in this store."""
        if self.store.contains(oid):
            return True
        try:
            failpoints.hit("agent.restore.before_fetch")
            backend = self._restore_backend_for(uri)
        except Exception:
            return False
        got = backend.read(oid)
        if got is None:
            return False
        meta, data = got
        for attempt in range(4):
            try:
                # Not pinned (same contract as local spill restores):
                # the URI copy stays the durable one until the object is
                # freed, so a re-eviction only costs a re-fetch.
                self.store.put(oid, data, meta)
                break
            except Exception:
                # Store full: make room the same way a put does, then
                # retry; a restore that cannot fit gives up (the caller
                # falls back to lineage recomputation).
                if attempt == 3 or self.rpc_spill(
                        len(data) + config.spill_headroom_bytes) <= 0:
                    return False
        self._count_restore()
        if owner:
            try:
                self._owner_notify(owner, oid)
            except Exception:
                pass  # owner gone/partitioned: the head sweep resolves
        return True

    def rpc_fetch_object(self, oid):
        """Serve an object's (meta, data) to a peer in ONE frame — the
        small-object path. Large objects go through fetch_object_info +
        fetch_object_chunk (ObjectManager chunked transfer,
        ``object_manager.h:117``). Falls back to the spill file and
        best-effort restores it into the store (RestoreSpilledObjects
        analog)."""
        self._fetch_stats["whole"] += 1
        got = self.store.get(oid)
        if got is not None:
            data, meta = got
            try:
                return meta, bytes(data)
            finally:
                self.store.release(oid)
        restored = self._restore_from_spill(oid)
        if restored is None:
            return None
        return restored

    def _restore_from_spill(self, oid):
        try:
            failpoints.hit("agent.restore.before_fetch")
        except failpoints.FailpointError:
            return None  # chaos: restore fails, caller degrades
        got = self.spill_backend.read(oid)
        if got is None:
            return None
        meta, data = got
        try:
            # Restored copies are NOT pinned: they may be re-evicted (the
            # spill target remains the durable copy until the object is
            # freed).
            self.store.put(oid, data, meta)
        except Exception:
            pass
        self._count_restore()
        return meta, data

    def rpc_fetch_object_info(self, oid, inline_max: int = 0):
        """(meta, data_size, data_or_None) for a pull, or None if absent.
        Data rides inline when it fits in ``inline_max`` — the small-object
        fast path stays ONE round trip; only large objects pay an extra
        info RPC before chunking. Restores a spilled object into the store
        so subsequent chunk reads hit shared memory."""
        self._fetch_stats["info"] += 1
        got = self.store.get(oid)
        if got is not None:
            data, meta = got
            try:
                if len(data) <= inline_max:
                    return meta, len(data), bytes(data)
                return meta, len(data), None
            finally:
                self.store.release(oid)
        restored = self._restore_from_spill(oid)
        if restored is None:
            return None
        meta, data = restored
        if len(data) <= inline_max:
            return meta, len(data), bytes(data)
        return meta, len(data), None

    def rpc_fetch_object_stream(self, oid, size: int, chunk: int):
        """Server-streamed chunks of the object ([0, size) in ``chunk``
        slices): ONE request, N pipelined frames — removes the per-chunk
        round trip of rpc_fetch_object_chunk (the reference's object
        manager push streams chunks the same way over gRPC,
        ``object_manager.cc`` chunked push). Each chunk pins/releases
        independently so eviction/spill mid-stream degrades to the
        chunk-read fallback instead of holding a pin for the whole
        transfer."""
        self._fetch_stats["streams"] = self._fetch_stats.get("streams", 0) + 1
        for off in range(0, size, chunk):
            piece = self.rpc_fetch_object_chunk(
                oid, off, min(chunk, size - off))
            if piece is None:
                raise ObjectLostError(
                    f"object {oid[:16]}… lost mid-stream at offset {off}")
            yield piece

    def rpc_fetch_object_chunk(self, oid, offset: int, length: int):
        """One bounded chunk of the object's data ([offset, offset+length)).
        Stateless: each chunk pins/releases independently, so eviction or
        spilling mid-transfer is handled by the spill-file fallback."""
        failpoints.hit("agent.fetch.chunk")
        self._fetch_stats["chunks"] += 1
        got = self.store.get(oid)
        if got is not None:
            data, _meta = got
            try:
                return bytes(data[offset:offset + length])
            finally:
                self.store.release(oid)
        return self.spill_backend.read_range(oid, offset, length)

    def rpc_spill(self, bytes_needed: int):  # idempotent (level-triggered)
        """Move cold, unreferenced primary copies to disk until
        ``bytes_needed`` arena bytes are freed. Returns bytes freed
        (local_object_manager.h:110,122 / SpillObjects analog)."""
        # Ask the head for this node's directory slice BEFORE taking the
        # spill lock: a slow/partitioned head (60s socket) must not wedge
        # every other thread waiting to spill (memory monitor, puts
        # under pressure). Staleness is already tolerated — each
        # candidate is re-checked against the live store under the lock.
        try:
            oids = self.head.call("objects_on_node", self.node_id)
        except Exception:
            oids = []
        spilled_remote: list[str] = []
        spilled_bytes = 0
        with self._spill_lock:
            cands = []
            for oid in oids:
                try:
                    info = self.store.info(oid)
                except RuntimeError:
                    return 0  # segment unlinked under us: nothing to spill
                if info is not None and info["refcount"] == 0:
                    cands.append(
                        (info["lru_tick"], oid,
                         info["data_size"] + info["meta_size"])
                    )
            cands.sort()  # coldest first
            freed = 0
            for _tick, oid, size in cands:
                if freed >= bytes_needed:
                    break
                got = self.store.get(oid)  # pins while we copy out
                if got is None:
                    continue
                data, meta = got
                try:
                    failpoints.hit("agent.spill.before_write")
                    written = self.spill_backend.write(
                        oid, bytes(meta), bytes(data))
                except Exception:
                    # Chaos raise or target I/O error: this object stays
                    # resident; pressure continues, never corrupts.
                    self.store.release(oid)
                    continue
                self.store.release(oid)
                if self.store.evict(oid):  # despite pin: bytes now on disk
                    freed += size
                    spilled_bytes += written
                    if self.spill_backend.remote:
                        spilled_remote.append(oid)
                else:
                    self.spill_backend.delete(oid)
            if freed < bytes_needed:
                # Pressure signal: the store could not make the room a
                # put asked for (everything left is referenced/pinned) —
                # the put will raise StoreFullError after its retries.
                from ray_tpu.util import metrics as _metrics

                # A replayed spill request re-counting a denial skews a
                # stats counter, never execution state — the handler
                # stays level-triggered.  # analyze: ignore[RT002]
                self._spill_denied += 1  # analyze: ignore[RT002]
                try:
                    _metrics.OBJECT_SPILL_DENIED.inc(
                        tags={"node_id": self.node_id})
                except Exception:
                    pass
        if spilled_bytes:
            from ray_tpu.util import metrics as _metrics

            try:
                _metrics.SPILL_BYTES_TOTAL.inc(
                    spilled_bytes, tags={"node_id": self.node_id})
            except Exception:
                pass
        if spilled_remote:
            # Remote target: record the spilled copies with the head so
            # a DEAD node's objects restore from the URI instead of
            # recomputing. OUTSIDE the spill lock (a slow/partitioned
            # head must not wedge other spilling threads) and
            # best-effort — an unrecorded spill only degrades recovery
            # back to lineage recomputation.
            try:
                self.head.call("add_spilled", spilled_remote,
                               self.spill_backend.uri, timeout=10.0)
            except Exception:
                pass
        return freed

    def rpc_free_object(self, oid):  # idempotent
        """Head says nothing references this object anymore: drop the shm
        copy and any spilled copy (free-on-zero broadcast target). The
        spill lock orders this against an in-progress spill pass, so a
        spill can't recreate the target copy after we delete it."""
        with self._spill_lock:
            self.store.pin(oid, False)
            if not self.store.delete(oid) and self.store.contains(oid):
                # Actively read right now (zero-copy views alive); the reap
                # loop retries until readers release.
                with self._lock:
                    self._deferred_deletes.add(oid)
            self.spill_backend.delete(oid)
        return True

    def rpc_delete_object(self, oid):
        self.store.delete(oid)
        self.spill_backend.delete(oid)
        try:
            self.head.call("remove_location", oid, self.node_id)
        except Exception:
            pass
        return True

    def rpc_delete_spilled(self, oid, uri):  # idempotent
        """Drop one object from a spill target this node can reach (the
        head's free fanout for a DEAD node's remote-spilled copy — the
        spiller is gone, so any live node does the delete)."""
        try:
            return self._restore_backend_for(uri).delete(oid)
        except Exception:
            return False

    def rpc_store_stats(self):
        stats = self.store.stats()
        try:
            # With a shared remote spill target every node reports the
            # TARGET's totals (the pool is cluster-wide by design);
            # node-local spill dirs keep the per-node meaning.
            sp = self.spill_backend.stats()
            stats["spilled_objects"] = sp["objects"]
            stats["spilled_bytes"] = sp["bytes"]
        except OSError:
            stats["spilled_objects"] = 0
            stats["spilled_bytes"] = 0
        stats["spill_denied"] = self._spill_denied
        stats["spill_restores"] = self._spill_restores
        return stats

    def _object_attr(self, oid: str) -> dict:
        """The put-time attribution embedded in a sealed object's store
        meta ({} when absent — pre-attribution writers, error markers)."""
        from ray_tpu.core import serialization as ser

        got = self.store.get(oid)
        if got is None:
            return {}
        _data, meta = got
        try:
            return ser.meta_field(meta[1:], "attr") or {}
        except Exception:
            return {}
        finally:
            self.store.release(oid)

    def rpc_object_store_stats(self, oids=None,
                               include_objects: bool = True,
                               max_objects: int | None = None):
        """Memory-observability report for this node: shm ``stats()``
        joined with per-key ``info()`` (size/refcount/pinned) and the
        attribution riding each entry's meta, plus the OOM-report index.
        ``oids`` is normally the head's directory slice for this node
        (the store keys are digests, so the oid list comes from the
        directory); None = ask the head ourselves. ``max_objects``
        bounds the per-key scan for latency-sensitive callers (the
        pre-kill OOM snapshot) — a capped scan may miss objects."""
        with self._lock:
            reports = [dict(r) for r in self._oom_reports]
        report = {"node_id": self.node_id, "ts": time.time(),
                  "stats": self.rpc_store_stats(),
                  "oom_reports": reports}
        if not include_objects:
            return report
        if oids is None:
            try:
                oids = self.head.call("objects_on_node", self.node_id,
                                      timeout=5.0)
            except Exception:
                oids = []
        objs = []
        now = time.time()
        if max_objects is not None:
            oids = list(oids)[:max_objects]
        for oid in oids:
            try:
                info = self.store.info(oid)
            except RuntimeError:
                break  # segment unlinked under us: stats-only report
            if info is None:
                continue  # freed/spilled since the directory snapshot
            attr = self._object_attr(oid)
            created = attr.get("created_at")
            objs.append({
                "object_id": oid,
                "size": info["data_size"] + info["meta_size"],
                "refcount": info["refcount"],
                "pinned": info["pinned"],
                "sealed": True,
                "owner": attr.get("owner", ""),
                "task": attr.get("task", ""),
                "callsite": attr.get("callsite", ""),
                "age_s": round(now - created, 3) if created else None,
            })
        objs.sort(key=lambda r: r["size"], reverse=True)
        report["objects"] = objs
        return report

    def _export_store_gauges(self):
        with self._telemetry_lock:
            self._export_store_gauges_locked()

    def _export_store_gauges_locked(self):
        """Refresh the per-node object-store gauge family (used/capacity/
        objects + the eviction counter by delta). Same lifecycle as the
        worker gauges: the stop path retracts the node's series."""
        from ray_tpu.util import metrics as _metrics

        if self._shutdown.is_set():
            return  # stopping: never re-export retracted series
        try:
            st = self.rpc_store_stats()
        except RuntimeError:
            return  # segment unlinked under us
        tags = {"node_id": self.node_id}
        _metrics.OBJECT_STORE_BYTES_USED.set(st["used"], tags=tags)
        _metrics.OBJECT_STORE_BYTES_CAPACITY.set(st["capacity"], tags=tags)
        _metrics.OBJECT_STORE_OBJECTS.set(st["num_objects"], tags=tags)
        delta = st["num_evictions"] - self._evictions_exported
        if delta > 0:
            _metrics.OBJECT_STORE_EVICTIONS.inc(delta, tags=tags)
        self._evictions_exported = st["num_evictions"]
        self._store_gauges_exported = True

    # -- lifecycle --------------------------------------------------------

    # -- resource-view gossip ----------------------------------------------

    def _my_view_entry(self) -> dict:
        with self._lock:
            qdepth = len(self._task_queue)
            self._view_version += 1
            version = self._view_version
            draining = self._draining
        return {
            # A draining node gossips zero availability so no peer picks
            # it as a spillback target (leased admission rejects anyway).
            "available": {} if draining else dict(self.pool.available()),
            "queue": qdepth,
            "version": version,
            "address": self.address,
            "ts": time.time(),
        }

    def _merge_view(self, theirs: dict) -> None:
        with self._lock:
            for nid, entry in (theirs or {}).items():
                if nid == self.node_id:
                    continue  # we are authoritative for ourselves
                cur = self._cluster_view.get(nid)
                if cur is None or entry.get("version", 0) > \
                        cur.get("version", 0):
                    self._cluster_view[nid] = entry

    def rpc_gossip(self, their_view: dict) -> dict:  # idempotent
        """Push-pull anti-entropy exchange: merge the caller's view,
        return ours (ray_syncer.h bidirectional sync analog)."""
        self._merge_view(their_view)
        with self._lock:
            return dict(self._cluster_view)

    def rpc_peer_view(self) -> dict:
        """The gossiped cluster load view, for client-side spillback
        target selection (no head involved)."""
        with self._lock:
            return dict(self._cluster_view)

    def _gossip_client(self, address: str) -> RpcClient:
        with self._lock:
            c = self._gossip_clients.get(address)
            if c is None:
                if len(self._gossip_clients) > 128:
                    self._gossip_clients.popitem(last=False)[1].close()
                c = self._gossip_clients[address] = RpcClient(
                    address, timeout=10.0)
                c.chaos_src = self.address
            return c

    def _gossip_loop(self):
        import random

        tick = 0
        interval = config.gossip_interval_s
        while not self._shutdown.wait(interval):
            tick += 1
            # Adaptive cadence: anti-entropy converges in O(log n) rounds
            # regardless of interval, so large clusters don't need a
            # faster drum — but n agents x fanout at a fixed 0.5s means
            # O(n) cluster-wide RPCs/s, which measurably drags small
            # shared-core deployments (and the 1-core CI box). Stretch
            # the interval with peer count; freshness consumers gate on
            # entry ts anyway.
            with self._lock:
                n_peers = max(0, len(self._cluster_view) - 1)  # minus self
            # Capped stretch: entries must stay fresher than the
            # spillback consumer's staleness gate (client.py
            # _spill_to_peers, 10s) even after O(log n) propagation hops
            # — unbounded growth would silently disable peer spillback
            # at exactly the scale gossip exists for.
            interval = config.gossip_interval_s * min(
                8.0, max(1.0, n_peers / 4.0))
            mine = self._my_view_entry()
            with self._lock:
                self._cluster_view[self.node_id] = mine
            if tick % max(1, config.gossip_membership_every) == 1:
                # Membership from the head (its job): learn joins, drop
                # nodes it declared dead.
                try:
                    nodes = self.head.call("nodes", timeout=5.0)
                    alive = {n["NodeID"]: n["Address"]
                             for n in nodes if n["Alive"]}
                    with self._lock:
                        for nid, addr in alive.items():
                            if nid != self.node_id and \
                                    nid not in self._cluster_view:
                                self._cluster_view[nid] = {
                                    "available": {}, "queue": 0,
                                    "version": 0, "address": addr,
                                    "ts": 0.0,
                                }
                        for nid in list(self._cluster_view):
                            if nid != self.node_id and nid not in alive:
                                del self._cluster_view[nid]
                except Exception:
                    pass  # head hiccup: keep gossiping the stale view
            with self._lock:
                peers = [(nid, e["address"])
                         for nid, e in self._cluster_view.items()
                         if nid != self.node_id and e.get("address")]
                snapshot = dict(self._cluster_view)
            if not peers:
                continue
            for _nid, addr in random.sample(
                    peers, min(config.gossip_fanout, len(peers))):
                try:
                    theirs = self._gossip_client(addr).call(
                        "gossip", snapshot, timeout=5.0)
                    self._merge_view(theirs)
                except (ConnectionLost, OSError):
                    continue  # peer down: membership refresh cleans up

    def _heartbeat_loop(self):
        beats = 0
        while not self._shutdown.wait(config.heartbeat_interval_s):
            try:
                failpoints.hit("agent.heartbeat")
                resp = self.head.call(
                    "heartbeat", self.node_id, self.pool.available(),
                    timeout=5.0,
                )
                if not resp.get("ok"):
                    # Head declared us dead: actually exit (kill workers,
                    # stop serving) instead of running on as a zombie node.
                    self.stop()
                    return
                beats += 1
                if beats % max(1, config.clock_probe_every_beats) == 0:
                    self._probe_clock()
            except Exception:
                continue

    def _probe_clock(self):
        """NTP-style offset estimate against the head's clock, riding
        the heartbeat cadence: offset = ((t1-t0)+(t2-t3))/2 with rtt as
        the quality weight. The head's trace assembly shifts this node's
        span timestamps by the min-RTT-filtered median, so cross-node
        critical paths don't invert at machine clock skew. Suppressed:
        the probe must never generate spans of its own (it would recurse
        into the very plane it calibrates)."""
        from ray_tpu.util import tracing as _tracing

        try:
            with _tracing.suppressed():
                t0 = time.time()
                t1, t2 = self.head.call("clock_probe", t0, timeout=5.0)
                t3 = time.time()
                offset = ((t1 - t0) + (t2 - t3)) / 2.0
                rtt = (t3 - t0) - (t2 - t1)
                self.head.call("report_clock", self.node_id, offset,
                               rtt, timeout=5.0)
        except Exception:
            pass  # best-effort: next beat re-probes

    # -- chaos / fault-injection control plane -----------------------------

    def rpc_set_failpoints(self, specs: dict, include_workers: bool = True):
        """Arm/disarm failpoints in this agent's process and (by default)
        every live worker process on this node — including workers forked
        LATER (the armed table re-applies at worker registration)."""
        out = {"agent": failpoints.set_failpoints(specs)}
        if include_workers:
            with self._lock:
                for site, spec in (specs or {}).items():
                    if spec:
                        self._worker_failpoints[site] = spec
                    else:
                        self._worker_failpoints.pop(site, None)
            with self._lock:
                workers = [w for w in self._workers.values()
                           if w.client is not None
                           and w.proc.poll() is None]
            for w in workers:
                try:
                    out[w.worker_id] = w.client.call(
                        "set_failpoints", specs, timeout=5.0)
                except Exception as e:
                    out[w.worker_id] = {"error": repr(e)}
        return out

    def rpc_list_failpoints(self):
        """This agent's armed table plus each live worker's (the fold
        the head's list surface promises — a worker-side arm that
        errored must be visible as its absence here)."""
        out = {"agent": failpoints.list_armed()}
        with self._lock:
            workers = [(w.worker_id, w.client)
                       for w in self._workers.values()
                       if w.client is not None and w.proc.poll() is None]
        for wid, client in workers:
            try:
                out[wid] = client.call("list_failpoints", timeout=5.0)
            except Exception as e:
                out[wid] = {"error": repr(e)}
        return out

    def rpc_set_channel_chaos(self, rules: list, label: str = "",
                              include_workers: bool = True):
        n = channel_chaos.add_rule_dicts(rules, label)
        if include_workers:
            with self._lock:
                # Kept for replay at worker registration (the failpoint
                # table's contract): a worker forked mid-partition must
                # still observe the cut.
                self._worker_channel_rules.extend(
                    dict(r, label=label) if label and not r.get("label")
                    else dict(r)
                    for r in rules)
            # Workers tag their clients with THIS node's identity, so
            # node-keyed rules (partitions) genuinely cut their traffic
            # too. Best-effort: a worker mid-spawn arms nothing.
            for w in self._live_worker_clients():
                try:
                    w.call("set_channel_chaos", rules, label, timeout=5.0)
                except Exception:
                    continue
        return n

    def rpc_clear_channel_chaos(self, label: str | None = None,
                                include_workers: bool = True):
        n = channel_chaos.clear(label)
        if include_workers:
            with self._lock:
                if label is None:
                    self._worker_channel_rules = []
                else:
                    self._worker_channel_rules = [
                        r for r in self._worker_channel_rules
                        if r.get("label") != label]
            for w in self._live_worker_clients():
                try:
                    w.call("clear_channel_chaos", label, timeout=5.0)
                except Exception:
                    continue
        return n

    def _live_worker_clients(self):
        with self._lock:
            return [w.client for w in self._workers.values()
                    if w.client is not None and w.proc.poll() is None]

    def rpc_worker_addresses(self):  # idempotent (read-only)
        """Live workers' RPC server addresses. Partition group
        resolution folds these into a node's address set: traffic
        addressed DIRECTLY to a worker (cross-node actor pushes, owner
        notifies) must observe the node's cut, not just traffic to the
        agent."""
        with self._lock:
            return [w.address for w in self._workers.values()
                    if w.address and w.proc.poll() is None]

    def rpc_list_channel_chaos(self):
        return channel_chaos.describe()

    def rpc_event_stats(self):
        """Per-RPC-handler timing stats (event_stats.h analog)."""
        return self._server.handler_stats()

    def rpc_ping(self):
        return "pong"

    def rpc_shutdown_node(self):
        threading.Thread(target=self.stop, daemon=True).start()
        return True

    def close_outbound_clients(self):
        """Close this agent's outbound clients (head, gossip, owner) so
        threads blocked in a reconnect window (head client retries for
        head_reconnect_window_s) or spinning against an armed chaos rule
        observe ``_closed`` and exit NOW — a stopped or chaos-killed
        agent must not leave heartbeat/gossip threads retrying past
        teardown into the next test's cluster. Used by the graceful stop
        path and by ``Cluster.kill_node``'s ungraceful chaos path."""
        with self._lock:
            outbound = [self.head, *self._gossip_clients.values(),
                        *self._owner_clients.values()]
        for c in outbound:
            try:
                c.close()
            except Exception:
                pass

    def stop(self):
        with self._lock:
            if getattr(self, "_stopped", False):
                done = self._stop_done
            else:
                done = None
                self._stopped = True
                self._stop_done = threading.Event()
        if done is not None:
            # Another thread (e.g. the drain coordinator's shutdown_node
            # RPC) is already stopping this agent: wait it out so callers
            # get the synchronous contract — by return, the store is
            # closed/unlinked and no native call can race a new segment.
            done.wait(15.0)
            return
        try:
            self._stop_inner()
        finally:
            self._stop_done.set()

    def _stop_inner(self):
        self._shutdown.set()
        # Retract this node's telemetry series (tests run many agents per
        # process; a stopped node must not leave stale gauge children).
        try:
            from ray_tpu.util import metrics as _metrics

            # Under the telemetry lock so a sampling pass in flight
            # can't re-export a series after we retract it.
            with self._telemetry_lock:
                for wid, pid in self._exported_gauges:
                    tags = {"node_id": self.node_id, "worker_id": wid,
                            "pid": pid}
                    _metrics.WORKER_CPU_PERCENT.remove(tags=tags)
                    _metrics.WORKER_RSS_BYTES.remove(tags=tags)
                    _metrics.WORKER_UPTIME_SECONDS.remove(tags=tags)
                self._exported_gauges = set()
                _metrics.NODE_WORKER_COUNT.remove(
                    tags={"node_id": self.node_id})
                for wid, dev in self._exported_device:
                    self._retract_device_series(wid, dev)
                self._exported_device = set()
                _metrics.DEVICE_COUNT.remove(
                    tags={"node_id": self.node_id})
                # Object-store + OOM series die with the node: a dead
                # node must not keep reporting occupancy into the
                # federated scrape.
                tags = {"node_id": self.node_id}
                if self._store_gauges_exported:
                    _metrics.OBJECT_STORE_BYTES_USED.remove(tags=tags)
                    _metrics.OBJECT_STORE_BYTES_CAPACITY.remove(tags=tags)
                    _metrics.OBJECT_STORE_OBJECTS.remove(tags=tags)
                    self._store_gauges_exported = False
                _metrics.OBJECT_STORE_EVICTIONS.remove(tags=tags)
                _metrics.OBJECT_SPILL_DENIED.remove(tags=tags)
                _metrics.SPILL_BYTES_TOTAL.remove(tags=tags)
                _metrics.SPILL_RESTORES_TOTAL.remove(tags=tags)
                _metrics.OOM_KILLS_TOTAL.remove(tags=tags)
                # Serve + goodput gauge children die with the node too.
                for wid in list(self._serve_gauges):
                    self._retract_serve_series(wid)
                for wid in list(self._train_gauges):
                    self._retract_train_series(wid)
        except Exception:
            pass
        with self._lock:
            workers = list(self._workers.values())
        for w in workers:
            if w.proc.poll() is None:
                w.proc.kill()
        for w in workers:
            try:
                w.proc.wait(timeout=5)
            except Exception:
                pass
        self._server.stop()
        self.close_outbound_clients()
        # The reap loop may be mid-iteration on the workers just killed;
        # let it finish before the store detaches (release_dead on a
        # closed segment is guarded, but ordering keeps cleanup complete).
        try:
            self._reap_thread.join(timeout=10.0)
        except RuntimeError:
            pass  # stop() invoked from the reap thread itself
        self.store.close(unlink=True)


def main():
    import argparse
    import signal

    parser = argparse.ArgumentParser()
    parser.add_argument("--head", required=True)
    parser.add_argument("--num-cpus", type=float, default=None)
    parser.add_argument("--resources", default="{}")
    parser.add_argument("--store-capacity", type=int, default=DEFAULT_STORE_CAPACITY)
    parser.add_argument("--session", default=None)
    args = parser.parse_args()
    import json

    # Standalone agents sweep dead runs' leaked shm segments before
    # allocating their own (same hygiene as cluster_utils.Cluster).
    from ray_tpu.util.shm_sweep import sweep_stale_shm

    sweep_stale_shm()
    agent = NodeAgent(
        args.head,
        num_cpus=args.num_cpus,
        resources=json.loads(args.resources),
        store_capacity=args.store_capacity,
        session=args.session,
    )
    print(f"NODE_ADDRESS={agent.address}", flush=True)

    # SIGTERM is a preemption/termination notice (spot TPU pods get one
    # seconds before the VM vanishes): self-drain so the head migrates
    # actors and owners get the retry exemption, instead of dying as a
    # crash. A second SIGTERM (or SIGINT) stops immediately.
    def _on_signal(signum, _frame):
        if signum == signal.SIGTERM and not agent._shutdown.is_set():
            with agent._lock:
                first = not agent._draining
            if first:
                threading.Thread(
                    target=agent._self_drain, args=("preemption",),
                    daemon=True,
                ).start()
                return
        threading.Thread(target=agent.stop, daemon=True).start()

    signal.signal(signal.SIGTERM, _on_signal)
    signal.signal(signal.SIGINT, _on_signal)
    agent._shutdown.wait()
    agent.stop()


if __name__ == "__main__":
    main()
