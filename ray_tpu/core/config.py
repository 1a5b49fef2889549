"""System configuration registry (``RAY_CONFIG`` analog).

Reference: ``src/ray/common/ray_config_def.h`` + ``ray_config.h`` — a
single typed registry of tunables, each overridable from the environment
without code changes. Here every knob ``foo_bar`` reads its override from
``RAY_TPU_FOO_BAR`` (parsed to the declared type) at first access;
``config.foo_bar`` afterwards is cached process-wide.

Usage:
    from ray_tpu.core.config import config
    interval = config.heartbeat_interval_s

Tests / embedders can force values with ``config.override(name, value)``
(and ``config.reset()`` to drop all overrides and re-read the env).
"""

from __future__ import annotations

import os
import threading
from typing import Any

_ENV_PREFIX = "RAY_TPU_"


def _parse_bool(raw: str) -> bool:
    return raw.strip().lower() in ("1", "true", "yes", "on")


# name -> (type, default). The single source of truth for system knobs.
_DEFS: dict[str, tuple[type, Any]] = {
    # -- control plane -----------------------------------------------------
    "heartbeat_interval_s": (float, 0.25),
    "node_death_timeout_s": (float, 5.0),
    "head_reconnect_window_s": (float, 15.0),
    "head_snapshot_interval_s": (float, 0.2),
    # Write-behind persistence (head _PersistentStore): the flusher
    # thread commits the coalesced dirty queue as ONE sqlite transaction
    # every interval (whole batches land or don't — never a torn row);
    # max_batch bounds one transaction's statement count. Crash loss
    # window <= one interval; snapshot/shutdown flush synchronously.
    "head_persist_flush_interval_s": (float, 0.05),
    "head_persist_max_batch": (int, 2_000),
    # Tracing spans the head retains (ring buffer; older spans drop and
    # the drop counter surfaces in rpc_pubsub_stats / metrics). Bounds
    # head RSS under a 100k-task burst's span upload.
    "head_span_retention": (int, 100_000),
    # -- trace assembly (cluster/traces.py flight recorder) ----------------
    # Assembled traces the head keeps after tail sampling (bounded ring;
    # evictions counted in ray_tpu_head_traces_dropped_total).
    "head_trace_retention": (int, 512),
    # Tail-sampling keep probability for unremarkable traces. Errored
    # traces and traces slower than trace_slow_threshold_s are ALWAYS
    # kept — sampling only thins the healthy fast ones.
    "trace_sample_rate": (float, 0.05),
    "trace_slow_threshold_s": (float, 1.0),
    # A pending trace finalizes (tail-sampling decision) once its span
    # stream has been quiet this long; spans per trace are capped (the
    # clip is counted, never silent).
    "trace_quiet_s": (float, 1.5),
    "trace_max_spans": (int, 4096),
    # Agents probe the head's clock every Nth heartbeat (NTP-style
    # request/response timestamps -> per-node offset for cross-node
    # span alignment); 0 disables probing.
    "clock_probe_every_beats": (int, 10),
    # -- worker pool -------------------------------------------------------
    "workers_per_cpu": (int, 4),
    "worker_start_timeout_s": (float, 60.0),
    "worker_min_pool": (int, 4),
    # Plain-env workers forked at agent boot (worker_pool.cc prestart);
    # 0 disables. The delay keeps mass cluster boots from fork-storming.
    "worker_prestart_per_cpu": (float, 1.0),
    "worker_prestart_delay_s": (float, 2.0),
    # Pause between consecutive prestart forks (per agent): keeps a mass
    # cluster boot's fork storm off the CPU exactly when node
    # registration needs it.
    "worker_prestart_spacing_s": (float, 1.0),
    # -- node reporter (per-worker observability) --------------------------
    # Agent sampling cadence for per-worker CPU/RSS/uptime gauges
    # (reporter_agent.py analog); 0 disables the telemetry loop.
    "worker_telemetry_interval_s": (float, 1.0),
    # Dead workers whose log files stay indexed (and on disk) per agent.
    "worker_log_retention": (int, 1000),
    # -- resource-view gossip (ray_syncer.h analog) ------------------------
    # Node agents exchange per-node load views peer-to-peer so spillback
    # can place directly on a peer without the head. 0 disables gossip.
    "gossip_interval_s": (float, 0.5),
    "gossip_fanout": (int, 2),
    # Refresh membership (join/dead) from the head every N gossip ticks.
    "gossip_membership_every": (int, 10),
    # -- object plane ------------------------------------------------------
    "object_store_capacity_bytes": (int, 512 << 20),
    "transfer_chunk_bytes": (int, 4 << 20),
    "transfer_whole_fetch_max_bytes": (int, 8 << 20),
    "transfer_pull_concurrency": (int, 8),
    # Objects up to this many chunks pull via ONE streaming RPC (server
    # pipelines chunk frames); bigger objects fan out over parallel
    # per-chunk pulls on multiple connections.
    "transfer_stream_max_chunks": (int, 8),
    # Cap on total in-flight chunked-pull bytes per process; blocked
    # pulls admit by priority get > wait > args (pull_manager.h analog).
    "pull_max_inflight_bytes": (int, 256 << 20),
    "spill_headroom_bytes": (int, 64 << 10),
    # Remote spill target (external_storage.py analog): a URI whose
    # scheme picks a registered spill backend (cluster/spill_storage.py;
    # "file:///shared/dir" ships). "" keeps the per-node session spill
    # dir — node-local, so a dead node takes its spilled objects with
    # it. With a remote URI the head records every spilled object and
    # lineage recovery RESTORES it from the target onto a live node
    # instead of recomputing (or losing) it.
    "spill_uri": (str, ""),
    # -- data plane --------------------------------------------------------
    # Dynamic block splitting: read/map tasks split output blocks bigger
    # than this into store-friendly pieces (each its own object) so one
    # skewed multi-GiB block cannot OOM the store. 0 disables splitting
    # (legacy single-object stage outputs).
    "target_block_size_bytes": (int, 128 << 20),
    # -- memory protection -------------------------------------------------
    "memory_usage_threshold": (float, 0.95),
    "memory_limit_bytes": (int, 0),  # 0 = no aggregate-RSS limit
    "memory_monitor_interval_s": (float, 0.25),
    # -- memory observability ----------------------------------------------
    # Record a trimmed user-code callsite on every put/task-return object
    # (``ray memory`` callsite column analog). Off by default: the stack
    # walk is measurable on hot put paths; the cheap fields — owner
    # worker id, creating task name, creation time — are always on.
    "record_callsite": (bool, False),
    # Head-side leak sweeper: an object alive longer than the threshold
    # with zero registered holders (or held refs whose every replica is
    # gone) is flagged in ``state.memory_leaks()`` / ``ray-tpu memory
    # --leaks``. 0 disables the sweeper.
    "leak_age_threshold_s": (float, 300.0),
    "leak_sweep_interval_s": (float, 5.0),
    # -- tasks -------------------------------------------------------------
    "task_default_max_retries": (int, 3),
    "pending_task_timeout_s": (float, 120.0),
    # How long a caller blocks for an actor's registration to appear on
    # the head (mass actor creation forks one process per actor; deep
    # bursts need room).
    "actor_register_timeout_s": (float, 60.0),
    # Lease pipelining (direct_task_transport.h analog): how many specs a
    # client batches into one schedule/submit RPC. (Leased-push admission
    # itself is capacity-based, not depth-based — see
    # node_agent.rpc_submit_tasks_leased.)
    "submit_batch_max": (int, 256),
    # Unplaceable-spec retry backoff (client _retry_heap): the first
    # re-schedule attempt comes after base_s, doubling per miss up to
    # max_s. A flat timer at 100k parked specs re-batched EVERY tick
    # through schedule_batch — ~400 head RPCs per 250ms of pure misses;
    # backoff decays that to a trickle while staying responsive when
    # capacity appears within the first few attempts.
    "submit_retry_base_s": (float, 0.25),
    "submit_retry_max_s": (float, 2.0),
    # Finished-task records each node agent retains (ring; evictions
    # count into ray_tpu_task_records_evicted_total).
    "task_record_retention": (int, 10_000),
    # Nested-timeout budgets (the analyzer's timeout-budget annotations
    # relate inner RPC timeouts to these — edit one side and `ray-tpu
    # analyze` fails instead of a healthy task dying):
    # how long an agent's task_unblocked handler may block re-acquiring
    # the CPU slot on a saturated node...
    "cpu_reacquire_budget_s": (float, 300.0),
    # ...and how long a 2PC prepare may block carving out a PG bundle's
    # reservation on a busy node.
    "bundle_reserve_timeout_s": (float, 60.0),
    # -- node drain / preemption -------------------------------------------
    # Default deadline a graceful drain gives in-flight tasks before the
    # node is force-removed (DrainRaylet deadline analog).
    "drain_deadline_s": (float, 30.0),
    # Agent-side preemption watcher cadence; the watcher thread only
    # starts when a signal source below is configured.
    "preemption_poll_interval_s": (float, 1.0),
    # Test/ops hook: a node self-drains with reason="preemption" when
    # this file exists and is empty or contains its node id.
    "preemption_signal_file": (str, ""),
    # Cloud hook: metadata endpoint polled for a termination notice
    # (GCE: .../computeMetadata/v1/instance/preempted returns "TRUE").
    "preemption_metadata_url": (str, ""),
    # -- autoscaler execution half (boot-loop robustness) -------------------
    # Wall-clock budget for one provider create_node call; past it the
    # launch counts as failed (the provider call may still land — the
    # reconcile loop adopts it via non_terminated_nodes on a later pass).
    "autoscaler_launch_timeout_s": (float, 120.0),
    # Jittered exponential backoff between launch attempts for a node
    # type whose last create failed: base * 2^(failures-1), capped.
    "autoscaler_launch_backoff_base_s": (float, 1.0),
    "autoscaler_launch_backoff_max_s": (float, 30.0),
    # Consecutive boot failures before a node type is quarantined
    # (benched for the cooldown; demand falls through to the next
    # feasible type) — a flapping provider can never hot-loop create.
    "autoscaler_quarantine_failures": (int, 3),
    "autoscaler_quarantine_cooldown_s": (float, 60.0),
    # -- chaos / fault injection -------------------------------------------
    # One seed for ALL chaos randomness (failpoint probability RNGs,
    # network-chaos delay/jitter draws, soak schedules, the chaos test's
    # victim choice) so any chaos run replays from one env var. 0 =
    # unseeded (OS entropy).
    "chaos_seed": (int, 0),
    # -- signal plane (head metrics history + SLO evaluation) --------------
    # The head self-scrapes its own federated /metrics/cluster body into
    # a bounded in-memory time-series ring every interval; 0 disables
    # the scrape loop (and every history-backed surface falls back to
    # its single-scrape behaviour).
    "signal_scrape_interval_s": (float, 2.0),
    # Per-series retention window: samples older than this age out of
    # the ring (bounded-retention discipline — head RSS must not grow
    # with uptime).
    "signal_history_s": (float, 600.0),
    # Hard cap on distinct series the ring retains; past it the
    # least-recently-updated series is evicted (and counted into
    # ray_tpu_head_signal_evictions_total).
    "signal_max_series": (int, 50_000),
    # SLO evaluator cadence (burn-rate state machine over the ring);
    # 0 disables the loop. Defaults to the scrape cadence.
    "slo_eval_interval_s": (float, 2.0),
    # Consecutive breaching evaluations before an SLO transitions to
    # burning (hysteresis: one scrape gap or blip must not flap it).
    "slo_burn_evals": (int, 3),
    # -- pubsub ------------------------------------------------------------
    "pubsub_max_buffer": (int, 10_000),
    "pubsub_subscriber_ttl_s": (float, 120.0),
    # -- security ----------------------------------------------------------
    "cluster_token": (str, ""),
    # -- cross-language ----------------------------------------------------
    # Default C++ worker binary agents spawn for lang="cpp" tasks (the
    # reference's equivalent is the per-language worker command the raylet
    # worker pool is configured with, worker_pool.h:80). Empty = cpp tasks
    # must carry an explicit binary path.
    "cpp_worker_bin": (str, ""),
}


class _Config:
    def __init__(self):
        self._lock = threading.Lock()
        self._cache: dict[str, Any] = {}

    def __getattr__(self, name: str):
        if name.startswith("_"):
            raise AttributeError(name)
        return self.get(name)

    def get(self, name: str):
        if name not in _DEFS:
            raise AttributeError(f"unknown config {name!r}; known: "
                                 f"{sorted(_DEFS)}")
        with self._lock:
            if name in self._cache:
                return self._cache[name]
            typ, default = _DEFS[name]
            raw = os.environ.get(_ENV_PREFIX + name.upper())
            if raw is None:
                value = default
            elif typ is bool:
                value = _parse_bool(raw)
            else:
                value = typ(raw)
            self._cache[name] = value
            return value

    def override(self, name: str, value) -> None:
        if name not in _DEFS:
            raise AttributeError(f"unknown config {name!r}")
        with self._lock:
            self._cache[name] = value

    def reset(self, name: str | None = None) -> None:
        with self._lock:
            if name is None:
                self._cache.clear()
            else:
                self._cache.pop(name, None)

    def snapshot(self) -> dict:
        return {name: self.get(name) for name in _DEFS}


config = _Config()
