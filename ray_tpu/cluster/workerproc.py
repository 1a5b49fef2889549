"""Worker process: executes tasks and hosts actors (CoreWorker equivalent).

The execution side of ``src/ray/core_worker``: receives pushed tasks over
RPC (``core_worker.proto:382`` PushTask), deserializes with cloudpickle,
resolves ObjectRef args through the object plane, runs the function, and
stores returns in the node's shm store + registers locations with the head
(the task-execution callback path, ``_raylet.pyx:956``).

A worker executes ONE task at a time on its executor thread; actor workers
are dedicated: the actor constructor is the first queued item and method
calls execute in arrival order (sequence-numbered actor queue analog).
Nested ``ray_tpu.*`` calls inside user code work because the worker installs
a full ClusterBackend as the process-wide backend.
"""

from __future__ import annotations

import argparse
import io
import os
import queue
import sys
import threading
import time
import traceback

from ray_tpu.cluster.rpc import RpcClient, RpcServer
from ray_tpu.core import attribution
from ray_tpu.util import failpoints
from ray_tpu.util import metrics as _wp_metrics
from ray_tpu.core import serialization as ser
from ray_tpu.core.cancellation import CancelRegistry
from ray_tpu.core.object_ref import (
    ActorError,
    ObjectRef,
    TaskCancelledError,
    TaskError,
)


class _TeeStream(io.TextIOBase):
    """Write-through stdout/stderr wrapper that also line-buffers into a
    shared list for the log forwarder (reference: per-worker log files
    tailed by ``_private/log_monitor.py`` and pushed to the driver)."""

    def __init__(self, inner, sink: list, lock: threading.Lock):
        self._inner = inner
        self._sink = sink
        self._lock = lock
        self._partial = ""

    def write(self, s):
        self._inner.write(s)
        self._partial += s
        if "\n" in self._partial:
            *lines, self._partial = self._partial.split("\n")
            with self._lock:
                self._sink.extend(lines)
        return len(s)

    def flush(self):
        self._inner.flush()

    def isatty(self):
        return False


class _PhaseClock:
    """Wall-ns accumulator for the per-task phase breakdown
    (``get_args`` = arg fetch + deserialize, ``execute`` = user code,
    ``put_outputs`` = result serialize + object-store put). ``lap``
    closes the current phase; phases ride the task-event record to the
    agent and surface in ``state.summarize_tasks()``/``timeline()``."""

    __slots__ = ("_phases", "_t")

    def __init__(self, phases: dict):
        self._phases = phases
        self._t = time.monotonic_ns()

    def lap(self, name: str) -> None:
        now = time.monotonic_ns()
        self._phases[name] = self._phases.get(name, 0) + (now - self._t)
        self._t = now


class WorkerHandler:
    def __init__(self, head_address, agent_address, node_id, store_path, worker_id):
        from ray_tpu.cluster.client import ClusterBackend

        self.worker_id = worker_id
        self.agent = RpcClient(agent_address)
        self.backend = ClusterBackend(
            head_address, node_id=node_id, store_path=store_path,
            agent_address=agent_address, process_kind="w",
        )
        from ray_tpu._private import worker as worker_mod

        worker_mod._backend = self.backend  # nested API calls inside tasks
        from ray_tpu.core.config import config

        self._hooks = (
            lambda: self.agent.call("task_blocked", self.worker_id),
            # Unblock re-acquires the CPU slot and the agent-side
            # acquire may legitimately wait up to its full re-acquire
            # budget when the node is saturated (many tasks cycling few
            # slots under memory pressure) — the RPC timeout must
            # outlast it or the worker kills a healthy task with
            # ConnectionLost. Derived from the budget knob so the two
            # can't drift; the analyzer checks the declared relation.
            lambda: self.agent.call(
                "task_unblocked", self.worker_id,
                # timeout-budget: outlasts config.cpu_reacquire_budget_s
                timeout=config.cpu_reacquire_budget_s + 30.0),
        )
        self._q: queue.Queue = queue.Queue()
        # Named concurrency groups: each gets its own queue + executor
        # threads (reference actor concurrency groups — a long call in
        # one group never blocks another group's methods).
        self._group_queues: dict[str, queue.Queue] = {}
        self._actor_instance = None
        self._actor_dead_cause: str | None = None
        self._actor_id: str | None = None
        # Threaded actors (max_concurrency > 1): method calls may not run
        # before the constructor finishes, and extra executor threads are
        # only spawned after it (so the ctor itself is never raced).
        self._actor_ready = threading.Event()
        # Observability buffers, shipped to the agent in batches by the
        # event flusher (keeps the task hot path free of extra RPCs).
        self._ev_lock = threading.Lock()
        self._log_lines: list = []
        self._task_events: list = []
        # Cancellation registry: ids cancelled before they ran, and the
        # executor-thread ident of each currently running task (so a
        # cooperative cancel can target the right thread).
        self._cancels = CancelRegistry(threading.Lock())
        # Async actors (reference: asyncio event loop per actor,
        # _raylet.pyx:1023): one loop thread, created on first coroutine
        # method; in-flight coroutine futures by task id for cancel.
        self._aio_loop = None
        self._aio_lock = threading.Lock()
        self._async_futs: dict[str, object] = {}
        # Whether this worker hosts an ASYNC actor (any coroutine method):
        # set after the ctor; async actors route every call via the loop.
        self._actor_is_async = False
        # Completion bookkeeping for async tasks runs here, off the loop.
        self._async_done_q: queue.Queue = queue.Queue()
        threading.Thread(target=self._async_done_loop, daemon=True).start()
        # Function-table cache: content hash -> deserialized function
        # (bounded LRU — long-lived workers must not accumulate every
        # function a driver ever exported).
        import collections

        self._fn_cache: "collections.OrderedDict[str, object]" = (
            collections.OrderedDict())
        # Duplicate-delivery suppression for pushed calls (bounded,
        # insertion-ordered): a caller that loses the REPLY to a push
        # (sever-after-send chaos, network blip) retries the same spec —
        # same task id — against this incarnation; accepting it twice
        # would double user-visible side effects. An actor RESTART is a
        # fresh process (empty set), so legitimate replay still runs.
        self._seen_pushes: "collections.OrderedDict[str, bool]" = (
            collections.OrderedDict())
        sys.stdout = _TeeStream(sys.stdout, self._log_lines, self._ev_lock)
        sys.stderr = _TeeStream(sys.stderr, self._log_lines, self._ev_lock)
        threading.Thread(target=self._event_flush_loop, daemon=True).start()
        threading.Thread(target=self._exec_loop, daemon=True).start()

    # -- observability -----------------------------------------------------

    def _record(self, spec, kind: str):
        rec = {
            "task_id": spec.get("task_id") or spec.get("oids", ["?"])[0],
            "name": spec.get("fname") or spec.get("method")
            or spec.get("class_name", "task"),
            "type": kind,
            "state": "RUNNING",
            "submitted_at": spec.get("submitted_at"),
            "start_time": time.time(),
            "end_time": None,
            "error": None,
            # Wall-ns per execution phase (get_args/execute/put_outputs),
            # filled by a _PhaseClock as the task advances.
            "phases": {},
        }
        return rec

    def _finish(self, rec, error: str | None):
        rec["state"] = "FAILED" if error else "FINISHED"
        rec["end_time"] = time.time()
        rec["error"] = error
        with self._ev_lock:
            self._task_events.append(rec)

    def _event_flush_loop(self):
        import collections

        from ray_tpu.util import device_telemetry, tracing
        from ray_tpu.util import metrics as _metrics

        pid = os.getpid()
        last_dev_ship = 0.0
        # Agent-liveness watchdog (reference: a worker whose raylet dies
        # exits with it, core_worker shutdown-on-raylet-death). Workers
        # are killed by the agent on clean shutdown; when the agent dies
        # ABRUPTLY (node crash, chaos kill, aborted test fixture) nothing
        # would reap us — a jax-loaded orphan per worker piles real load
        # onto the box. The flusher doubles as the probe: consecutive
        # failed agent calls over ~3s mean the agent is gone.
        consecutive_fail = 0
        idle_rounds = 0
        # Failed uploads are RESENT as-is under their ORIGINAL sequence
        # number, and the agent's rpc_worker_events dedups on (worker,
        # pid, seq): a reply lost after the agent applied the batch
        # (maybe_executed) makes the resend an ack instead of a
        # double-count — the serve/goodput planes promise exact counts,
        # and the old requeue-into-the-buffer path re-shipped the same
        # observations under what was effectively a new identity.
        unacked: "collections.deque" = collections.deque()
        ship_seq = 0
        while True:
            time.sleep(0.25)
            # Attach jax compile-counter listeners the moment a task's
            # import makes jax available (idempotent; narrows the
            # uncounted window to compiles racing this tick).
            try:
                device_telemetry.ensure_listeners()
            except Exception:
                _metrics.count_loop_restart("worker.event_flush")
            with self._ev_lock:
                # Drain in place: the tee streams hold a reference to
                # THESE list objects — rebinding would orphan them.
                lines = self._log_lines[:]
                del self._log_lines[:]
                events = self._task_events[:]
                del self._task_events[:]
            spans = tracing.drain() if tracing.is_enabled() else []
            # Span-buffer truncation count rides the batch (no-silent-caps:
            # a worker clipping spans must show up in the head's scrape,
            # and worker registries are never scraped directly).
            span_drops = tracing.drain_dropped() if tracing.is_enabled() \
                else 0
            # Serve request-path observations (phase histograms, shed
            # counters, replica gauges) ride the same batch; the module
            # is only consulted if something in this process imported
            # serve (a worker that never served ships nothing).
            serve_events = []
            so = sys.modules.get("ray_tpu.serve._observability")
            if so is not None:
                try:
                    serve_events = so.drain_events()
                except Exception:
                    serve_events = []
                    _metrics.count_loop_restart("worker.event_flush")
            # Training goodput observations (dataset stage/iterator
            # samples, step phases, downtime) ride the same batch; the
            # module is only consulted if something in this process
            # imported the data/train path.
            train_events = []
            go = sys.modules.get("ray_tpu.util.goodput")
            if go is not None:
                try:
                    train_events = go.drain_events()
                except Exception:
                    train_events = []
                    _metrics.count_loop_restart("worker.event_flush")
            if not lines and not events and not spans and not span_drops \
                    and not serve_events and not train_events \
                    and not unacked:
                idle_rounds += 1
                # Probe liveness every ~2s when idle; every round while
                # failures are accumulating (fast exit once the agent
                # actually died).
                if idle_rounds < 8 and consecutive_fail == 0:
                    continue
            idle_rounds = 0
            # Device telemetry rides the same batch, throttled to ~1/s;
            # None until this worker's own code has initialised a JAX
            # backend (the snapshot is never the first touch: that would
            # take the chip, or pre-empt jax.distributed.initialize).
            device = None
            now = time.monotonic()
            if device_telemetry.backend_initialized() \
                    and now - last_dev_ship >= 1.0:
                try:
                    device = device_telemetry.snapshot()
                    last_dev_ship = now
                except Exception:
                    device = None
                    _metrics.count_loop_restart("worker.event_flush")
            if lines or events or spans or span_drops or serve_events \
                    or train_events or device is not None or not unacked:
                # New content — or an empty liveness probe when nothing
                # is pending resend (the resend IS the probe otherwise).
                ship_seq += 1
                unacked.append((ship_seq, events, lines, spans, device,
                                serve_events or None,
                                train_events or None,
                                span_drops or None))
            while len(unacked) > 8:
                # Bounded resend queue: give the oldest batch's
                # exact-count planes back to their buffers (they count
                # their own overflow drops). Re-shipping under a new
                # seq can double-apply only if one of its 8+ failed
                # sends secretly landed — the narrow corner the bound
                # trades for bounded memory.
                (_, _, _, _, _, drop_serve, drop_train,
                 drop_spans) = unacked.popleft()
                # The evicted batch's truncation count folds back into
                # the buffer — losing the loss-counter is the one drop
                # this plane can never absorb silently.
                try:
                    if drop_spans:
                        tracing.requeue_dropped(drop_spans)
                except Exception:
                    _metrics.count_loop_restart("worker.event_flush")
                # Independent requeues: a failing serve requeue must
                # not also cost the batch's goodput observations.
                try:
                    if drop_serve and so is not None:
                        so.requeue_events(drop_serve)
                except Exception:
                    _metrics.count_loop_restart("worker.event_flush")
                try:
                    if drop_train and go is not None:
                        go.requeue_events(drop_train)
                except Exception:
                    _metrics.count_loop_restart("worker.event_flush")
            while unacked:
                (seq, b_events, b_lines, b_spans, b_device, b_serve,
                 b_train, b_drops) = unacked[0]
                try:
                    self.agent.call(
                        "worker_events", self.worker_id, pid, b_events,
                        b_lines, b_spans, b_device, b_serve, b_train,
                        seq=seq, dropped=b_drops)
                    unacked.popleft()
                    consecutive_fail = 0
                except Exception:
                    _metrics.count_loop_restart("worker.event_flush")
                    consecutive_fail += 1
                    if consecutive_fail >= 12:
                        os._exit(1)  # agent is gone: die with the node
                    break  # keep the batch; resend same seq next round

    # -- rpc surface (called by agent and by remote callers) ---------------

    def _is_duplicate_push(self, spec: dict) -> bool:
        """Record-and-test the spec's task id against pushes this process
        already accepted (at-most-once admission per incarnation)."""
        task_id = spec.get("task_id")
        if not task_id:
            return False
        with self._ev_lock:
            if task_id in self._seen_pushes:
                return True
            self._seen_pushes[task_id] = True
            while len(self._seen_pushes) > 4096:
                self._seen_pushes.popitem(last=False)
        return False

    def rpc_push_task(self, spec: dict):  # idempotent
        if self._is_duplicate_push(spec):
            # Refused (False): the agent releases this dispatch's lease;
            # the first delivery owns the task's fate.
            return False
        self._q.put(("task", spec))
        return True

    def rpc_create_actor(self, spec: dict):
        self._actor_id = spec["actor_id"]
        # Group queues exist from the start so calls routed to a group
        # can never race the constructor (their executor threads spawn
        # after the ctor and gate on _actor_ready regardless).
        for group in (spec.get("concurrency_groups") or {}):
            self._group_queues[group] = queue.Queue()
        self._q.put(("actor_ctor", spec))
        return True

    def rpc_push_actor_task(self, spec: dict):  # idempotent
        if self._is_duplicate_push(spec):
            # The caller's retry after a lost reply (sever-after-send):
            # the first delivery is (or was) executing — exactly-once
            # observable effect per incarnation.
            return True
        group = spec.get("concurrency_group")
        q = self._group_queues.get(group) if group else None
        if group and q is None:
            rec = self._record(spec, "ACTOR_TASK")
            self._store_error(
                spec,
                TaskError(
                    spec.get("method", "actor_task"),
                    f"actor has no concurrency group {group!r}",
                    "no-such-group",
                ),
            )
            self._end_borrows(spec)
            # Visible to the state API like every other failure path.
            self._finish(rec, f"no concurrency group {group!r}")
            return False
        (q or self._q).put(("actor_task", spec))
        return True

    def rpc_ping(self):
        return "pong"

    def rpc_set_failpoints(self, specs: dict):
        """Arm/disarm failpoints in this worker process (the tail of the
        head -> agents -> workers control-plane fanout)."""
        return failpoints.set_failpoints(specs)

    def rpc_list_failpoints(self):
        return failpoints.list_armed()

    def rpc_set_channel_chaos(self, rules: list, label: str = ""):
        from ray_tpu.cluster.rpc import channel_chaos

        return channel_chaos.add_rule_dicts(rules, label)

    def rpc_clear_channel_chaos(self, label: str | None = None):
        from ray_tpu.cluster.rpc import channel_chaos

        return channel_chaos.clear(label)

    # -- stack introspection (reporter-agent py-spy analog, in-process) ----

    def rpc_dump_stack(self):
        """Instantaneous stack report of every thread in this worker
        (``ray stack`` target; serves the agent/head routing chain)."""
        from ray_tpu.util import stack_sampler

        return stack_sampler.dump_stacks(
            header=f"worker {self.worker_id} (pid {os.getpid()})")

    def rpc_profile(self, duration_s: float = 1.0,
                    interval_s: float = 0.01):
        """Time-sampled profile of this worker's threads. Blocking is
        fine: the RPC server is thread-per-connection, so the executor
        keeps running the task being profiled."""
        from ray_tpu.util import stack_sampler

        prof = stack_sampler.sample(duration_s, interval_s)
        prof["worker_id"] = self.worker_id
        return prof

    def rpc_capture_profile(self, duration_s: float = 1.0,
                            interval_s: float = 0.01,
                            out_dir: str | None = None):
        """Timed profiler window over this worker: ``jax.profiler.trace``
        when this process has jax loaded (XLA host+device tracks), the
        stack sampler otherwise. With ``out_dir`` (the agent's capture
        dir — same host, shared filesystem) the trace files are written
        THERE and only a ``{kind, files: {name: size}}`` manifest rides
        the RPC; a multi-hundred-MB TPU trace never transits a frame.
        Without it, falls back to inline ``{name: bytes}``."""
        from ray_tpu.util import device_telemetry

        if out_dir is not None:
            return device_telemetry.capture_to_dir(
                out_dir, float(duration_s), float(interval_s),
                worker_id=self.worker_id)
        return device_telemetry.capture(
            float(duration_s), float(interval_s),
            worker_id=self.worker_id)

    def rpc_device_stats(self):
        """Immediate device snapshot of this worker (state API's fresh
        path; the batched flusher remains the steady-state feed)."""
        from ray_tpu.util import device_telemetry

        return device_telemetry.snapshot()

    def rpc_cancel_task(self, task_id: str, force: bool = False):
        """Cancel a task this worker holds. Queued: marked so the executor
        skips it and stores TaskCancelledError. Running: the class is
        injected into the executor thread (best-effort — delivery waits
        out any C-level block); a running COROUTINE is cancelled through
        its asyncio future instead. ``force`` is handled by the agent
        killing the process; by the time it reaches us it degrades to
        cooperative.
        """
        with self._ev_lock:
            fut = self._async_futs.get(task_id)
        if fut is not None:
            return "running" if fut.cancel() else "queued"
        running = self._cancels.cancel(task_id, TaskCancelledError)
        return "running" if running else "queued"

    # -- execution ---------------------------------------------------------

    def _begin_cancellable(self, spec) -> bool:
        """Register this thread as the runner of ``spec``. Returns False if
        the task was already cancelled (caller must not run it)."""
        return self._cancels.begin(spec.get("task_id"), threading.get_ident())

    def _end_cancellable(self, spec) -> None:
        """Unregister; if a cancel raced with completion, clear the
        injected-but-undelivered exception so it cannot land on the NEXT
        task this thread runs."""
        self._cancels.end(spec.get("task_id"), threading.get_ident())

    def _store_cancelled(self, spec, rec) -> None:
        name = spec.get("fname") or spec.get("method", "task")
        self._store_error(spec, TaskCancelledError(name))
        self._end_borrows(spec)
        rec["state"] = "CANCELLED"
        rec["end_time"] = time.time()
        rec["error"] = "cancelled"
        with self._ev_lock:
            self._task_events.append(rec)

    def _exec_loop(self, q: queue.Queue | None = None):
        q = q if q is not None else self._q
        while True:
            kind, spec = q.get()
            try:
                if kind == "task":
                    # finally: a late-delivered cancel injection escaping
                    # _run_task's handlers must not skip the lease release.
                    try:
                        self._run_task(spec)
                    finally:
                        self.agent.call("task_done", self.worker_id)
                elif kind == "actor_ctor":
                    self._run_actor_ctor(spec)
                elif kind == "actor_task":
                    self._run_actor_task(spec)
            except Exception:
                _wp_metrics.count_loop_restart("worker.exec")
                traceback.print_exc()

    def _resolve_function(self, spec):
        """Function-table lookup (reference function_manager fetch +
        cache): specs carry a content hash; the blob comes from the
        cluster KV once and the DESERIALIZED function is reused for
        every subsequent task with the same hash."""
        blob = spec.get("func")
        if blob is not None:  # legacy inline-blob spec (lineage replays)
            return ser.loads(blob)
        h = spec["func_hash"]
        func = self._fn_cache.get(h)
        if func is None:
            blob = self.backend.head.call("kv_get", h)
            if blob is None:
                raise TaskError(
                    spec.get("fname", "task"),
                    f"function {h} missing from the cluster function table",
                    "fn-table-miss",
                )
            func = ser.loads(blob)
            self._fn_cache[h] = func
            if len(self._fn_cache) > 256:
                self._fn_cache.popitem(last=False)
        else:
            self._fn_cache.move_to_end(h)
        return func

    def _resolve(self, args, kwargs):
        # Argument materialization pulls at the LOWEST priority class
        # (pull_manager.h ordering: get > wait > task args) — a worker
        # hydrating a queued task's args must not starve a user's
        # explicit ray.get. ONE batched get for all ref args: the
        # location long-poll batches and fetches run concurrently.
        refs = [a for a in args if isinstance(a, ObjectRef)] + [
            v for v in kwargs.values() if isinstance(v, ObjectRef)
        ]
        if not refs:
            return list(args), dict(kwargs)
        with self.backend.pull_priority_override(self.backend.PULL_ARGS):
            values = iter(self.backend.get(refs))
            args = [next(values) if isinstance(a, ObjectRef) else a
                    for a in args]
            kwargs = {
                k: next(values) if isinstance(v, ObjectRef) else v
                for k, v in kwargs.items()
            }
        return args, kwargs

    def _store_result(self, spec, result):
        oids, num_returns = spec["oids"], spec.get("num_returns", 1)
        if num_returns == "streaming":
            # Generator protocol: yield i -> return-index i; a _StreamEnd
            # after the last item marks the length. A mid-stream failure
            # stores the error AT the failing index (the consumer raises
            # there) — the generic oids error path is disabled since
            # index 0 may already hold a yielded item.
            from ray_tpu.core.ids import task_of_object
            from ray_tpu.core.object_ref import _StreamEnd

            task_id = task_of_object(oids[0])[0]
            from ray_tpu.core import ids as _ids

            spec["oids"] = []
            owner = spec.get("owner_addr")
            i = 0
            try:
                for item in result:
                    self.backend.put_with_id(
                        _ids.object_id_for(task_id, i), item, owner=owner)
                    i += 1
                self.backend.put_with_id(
                    _ids.object_id_for(task_id, i), _StreamEnd(),
                    owner=owner)
            except BaseException as e:  # noqa: BLE001
                self.backend.put_with_id(
                    _ids.object_id_for(task_id, i),
                    TaskError(spec.get("fname", "task"),
                              traceback.format_exc(), repr(e)),
                    is_error=True, owner=owner,
                )
                raise
            return
        if num_returns == 1:
            values = [result]
        else:
            values = list(result)
            if len(values) != num_returns:
                raise ValueError(
                    f"task declared num_returns={num_returns} but returned "
                    f"{len(values)}"
                )
        for oid, v in zip(oids, values):
            self.backend.put_with_id(oid, v, owner=spec.get("owner_addr"))

    def _store_error(self, spec, err: BaseException):
        for oid in spec["oids"]:
            self.backend.put_with_id(oid, err, is_error=True,
                                     owner=spec.get("owner_addr"))

    def _end_borrows(self, spec):
        """Release the task's arg borrows — AFTER flushing our own holder
        registrations, so a ref this task deserialized and kept can never
        be freed in the gap (borrower handoff ordering)."""
        if spec.get("borrowed") and spec.get("task_id"):
            self.backend.flush_refs()
            try:
                self.backend.head.call("ref_task_end", spec["task_id"])
            except Exception:
                pass

    def _run_task(self, spec):
        rec = self._record(spec, "NORMAL_TASK")
        if not self._begin_cancellable(spec):
            self._store_cancelled(spec, rec)
            return
        # Only plain tasks hold a per-task lease worth releasing while
        # blocked; actor lifetime resources stay held (reference semantics).
        self.backend._block_hooks = self._hooks
        err = None
        clock = _PhaseClock(rec["phases"])
        try:
            from ray_tpu.util import tracing

            func = self._resolve_function(spec)
            args, kwargs = ser.loads(spec["args"])
            args, kwargs = self._resolve(args, kwargs)
            clock.lap("get_args")
            # Chaos sites inside the try: a raise-action failpoint is
            # stored as the task's error (visible, retryable), a kill
            # action crashes the process mid-protocol — both the faults
            # the owner-side recovery machinery must absorb.
            failpoints.hit("worker.execute.before")
            # Attribution context: puts made while the task runs (its
            # returns AND nested ray_tpu.put calls in user code) carry
            # the creating task's name.
            with attribution.task_context(spec.get("fname", "task"),
                                          spec.get("callsite")):
                if spec.get("trace_ctx"):
                    tracing.enable()  # the driver traces: continue here
                    with tracing.span(
                            f"run:{spec.get('fname', 'task')}",
                            {"task_id": spec.get("task_id"),
                             "worker_id": self.worker_id},
                            parent=spec["trace_ctx"]):
                        result = func(*args, **kwargs)
                else:
                    result = func(*args, **kwargs)
                clock.lap("execute")
                self._store_result(spec, result)
                clock.lap("put_outputs")
                failpoints.hit("worker.execute.after")
        except BaseException as e:  # noqa: BLE001 — stored, not dropped
            err = repr(e)
            if isinstance(e, (TaskError, ActorError)):
                self._store_error(spec, e)
            else:
                self._store_error(
                    spec,
                    TaskError(
                        spec.get("fname", "task"), traceback.format_exc(), repr(e)
                    ),
                )
        finally:
            # Nested so a cancel injection delivered INSIDE this finally
            # (the tiny window before _end_cancellable clears it) cannot
            # abort the remaining cleanup steps.
            try:
                self._end_cancellable(spec)
            finally:
                self.backend._block_hooks = None
                try:
                    self._end_borrows(spec)
                finally:
                    self._finish(rec, err)

    def _run_actor_ctor(self, spec):
        rec = self._record(spec, "ACTOR_CREATION_TASK")
        err = None
        clock = _PhaseClock(rec["phases"])
        try:
            cls = ser.loads(spec["func"])
            args, kwargs = ser.loads(spec["args"])
            args, kwargs = self._resolve(args, kwargs)
            clock.lap("get_args")
            with attribution.task_context(
                    spec.get("fname", "actor.__init__"),
                    spec.get("callsite")):
                self._actor_instance = cls(*args, **kwargs)
            clock.lap("execute")
        except BaseException as e:  # noqa: BLE001
            err = repr(e)
            self._actor_dead_cause = traceback.format_exc()
            try:
                self.agent.call(
                    "actor_ctor_failed", self._actor_id, self._actor_dead_cause
                )
            except Exception:
                pass
        finally:
            import asyncio
            import inspect

            inst = self._actor_instance
            if inst is not None:
                # Async actor = any public coroutine method (class-level
                # scan; instance descriptors stay untouched). Reference:
                # async actors get an asyncio loop, and ALL their methods
                # run on it.
                self._actor_is_async = any(
                    asyncio.iscoroutinefunction(f)
                    for _, f in inspect.getmembers(
                        type(inst), inspect.isfunction)
                )
            self._end_borrows(spec)
            self._finish(rec, err)
            self._actor_ready.set()
            for _ in range(int(spec.get("max_concurrency", 1)) - 1):
                threading.Thread(target=self._exec_loop, daemon=True).start()
            for group, n in (spec.get("concurrency_groups") or {}).items():
                gq = self._group_queues[group]  # created at rpc_create_actor
                for _ in range(max(1, int(n))):
                    threading.Thread(
                        target=self._exec_loop, args=(gq,), daemon=True
                    ).start()

    def _ensure_aio_loop(self):
        import asyncio

        with self._aio_lock:
            if self._aio_loop is None:
                loop = asyncio.new_event_loop()
                threading.Thread(
                    target=loop.run_forever, daemon=True).start()
                self._aio_loop = loop
        return self._aio_loop

    def _run_actor_task_async(self, spec, method):
        """Async-actor call (reference async actors: EVERY method of an
        async actor runs on its ONE event loop — coroutines interleave at
        await points, sync methods block the loop while they run, so
        actor state keeps loop-serialized mutual exclusion). The executor
        thread only resolves args and schedules; completion bookkeeping
        (store/borrows/record, which do blocking RPCs) runs on a
        dedicated completion thread, never the loop."""
        import asyncio

        rec = self._record(spec, "ACTOR_TASK")
        if not self._begin_cancellable(spec):
            self._store_cancelled(spec, rec)
            return
        task_id = spec.get("task_id")
        fut = None
        clock = _PhaseClock(rec["phases"])
        try:
            args, kwargs = ser.loads(spec["args"])
            args, kwargs = self._resolve(args, kwargs)
            clock.lap("get_args")
            failpoints.hit("worker.execute.before")
            if asyncio.iscoroutinefunction(
                    getattr(method, "__func__", method)):
                coro = method(*args, **kwargs)
            else:
                # sync method of an async actor: run ON the loop (blocks
                # other coroutines for its duration — reference behavior)
                async def coro_wrapper():
                    return method(*args, **kwargs)

                coro = coro_wrapper()

            # Attribution rides the asyncio Task's context (contextvar):
            # nested ray_tpu.put calls inside the method attribute to it
            # like every sync path, without leaking to interleaved
            # coroutines at await points.
            async def attributed(inner=coro):
                with attribution.task_context(
                        spec.get("method", "actor_task"),
                        spec.get("callsite")):
                    return await inner

            fut = asyncio.run_coroutine_threadsafe(
                attributed(), self._ensure_aio_loop())
            if task_id:
                with self._ev_lock:
                    self._async_futs[task_id] = fut
        except BaseException as e:  # noqa: BLE001
            if isinstance(e, (TaskError, ActorError)):
                self._store_error(spec, e)
            else:
                self._store_error(
                    spec,
                    TaskError(spec.get("method", "actor_task"),
                              traceback.format_exc(), repr(e)),
                )
            self._end_borrows(spec)
            self._finish(rec, repr(e))
            return
        finally:
            # Registered in _async_futs (or failed): cancel now targets
            # the future, not this thread. A cancel landing inside the
            # resolve phase above still injects into this thread and is
            # handled by the except path like the sync flow.
            self._end_cancellable(spec)

        def done(f):
            if task_id:
                with self._ev_lock:
                    self._async_futs.pop(task_id, None)
            if f.cancelled():
                # Same record shape as a sync cancel: CANCELLED, not FAILED.
                self._store_cancelled(spec, rec)
                return
            # The coroutine ran between the schedule and this callback:
            # everything since the get_args lap is the execute phase
            # (includes loop queueing — the time the CALL took).
            clock.lap("execute")
            err = None
            try:
                with attribution.task_context(
                        spec.get("method", "actor_task"),
                        spec.get("callsite")):
                    self._store_result(spec, f.result())
                clock.lap("put_outputs")
                failpoints.hit("worker.execute.after")
            except BaseException as e:  # noqa: BLE001
                err = repr(e)
                if isinstance(e, (TaskError, ActorError)):
                    self._store_error(spec, e)
                else:
                    self._store_error(
                        spec,
                        TaskError(spec.get("method", "actor_task"),
                                  "".join(traceback.format_exception(e)),
                                  repr(e)),
                    )
            finally:
                try:
                    self._end_borrows(spec)
                finally:
                    self._finish(rec, err)

        # Done-callbacks fire on the thread that resolves the future (the
        # loop thread) — hand the blocking bookkeeping to the completion
        # worker so a slow head RPC can't stall every other coroutine.
        fut.add_done_callback(
            lambda f: self._async_done_q.put((done, f)))

    def _async_done_loop(self):
        while True:
            fn, fut = self._async_done_q.get()
            try:
                fn(fut)
            except Exception:
                _wp_metrics.count_loop_restart("worker.async_done")
                traceback.print_exc()

    def _run_actor_task(self, spec):
        self._actor_ready.wait(timeout=300.0)
        inst = self._actor_instance
        if inst is not None and self._actor_is_async:
            m = getattr(inst, spec.get("method", ""), None)
            if m is not None:
                return self._run_actor_task_async(spec, m)
        rec = self._record(spec, "ACTOR_TASK")
        if not self._begin_cancellable(spec):
            self._store_cancelled(spec, rec)
            return
        err = None
        clock = _PhaseClock(rec["phases"])
        try:
            if self._actor_instance is None:
                raise ActorError(
                    f"actor is dead: {self._actor_dead_cause or 'not constructed'}"
                )
            args, kwargs = ser.loads(spec["args"])
            args, kwargs = self._resolve(args, kwargs)
            clock.lap("get_args")
            failpoints.hit("worker.execute.before")
            method = getattr(self._actor_instance, spec["method"])
            with attribution.task_context(
                    spec.get("method", "actor_task"),
                    spec.get("callsite")):
                result = method(*args, **kwargs)
                clock.lap("execute")
                self._store_result(spec, result)
                clock.lap("put_outputs")
                failpoints.hit("worker.execute.after")
        except BaseException as e:  # noqa: BLE001
            err = repr(e)
            if isinstance(e, (TaskError, ActorError)):
                self._store_error(spec, e)
            else:
                self._store_error(
                    spec,
                    TaskError(
                        spec.get("method", "actor_task"),
                        traceback.format_exc(),
                        repr(e),
                    ),
                )
        finally:
            try:
                self._end_cancellable(spec)
            finally:
                try:
                    self._end_borrows(spec)
                finally:
                    self._finish(rec, err)


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--head", required=True)
    parser.add_argument("--agent", required=True)
    parser.add_argument("--node-id", required=True)
    parser.add_argument("--store", required=True)
    parser.add_argument("--worker-id", required=True)
    args = parser.parse_args()

    handler = WorkerHandler(
        args.head, args.agent, args.node_id, args.store, args.worker_id
    )
    server = RpcServer(handler)
    handler.agent.call(
        "register_worker", args.worker_id, server.address,
        handler.backend.client_id,
    )
    threading.Event().wait()  # serve forever; the agent kills us


if __name__ == "__main__":
    main()
