"""User-defined metrics: Counter / Gauge / Histogram + Prometheus text.

Reference parity: ``python/ray/util/metrics.py`` (the user API) and the
Prometheus exposition of ``_private/prometheus_exporter.py``; the OpenCensus
agent pipeline collapses to an in-process registry with a text endpoint.
"""

from __future__ import annotations

import re as _re
import threading
from typing import Dict, List, Optional, Sequence, Tuple

_registry_lock = threading.Lock()
_registry: "List[Metric]" = []

DEFAULT_HISTOGRAM_BOUNDARIES = [
    0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0
]


class Metric:
    metric_type = "untyped"

    def __init__(self, name: str, description: str = "",
                 tag_keys: Optional[Sequence[str]] = None):
        if not name.replace("_", "").replace(":", "").isalnum():
            raise ValueError(f"invalid metric name {name!r}")
        self.name = name
        self.description = description
        self.tag_keys = tuple(tag_keys or ())
        self._default_tags: Dict[str, str] = {}
        self._lock = threading.Lock()
        with _registry_lock:
            _registry.append(self)

    def set_default_tags(self, tags: Dict[str, str]):
        self._default_tags = dict(tags)
        return self

    def _key(self, tags: Optional[Dict[str, str]]) -> Tuple:
        merged = {**self._default_tags, **(tags or {})}
        missing = set(self.tag_keys) - set(merged)
        if missing:
            raise ValueError(f"metric {self.name} missing tags {missing}")
        return tuple(merged.get(k, "") for k in self.tag_keys)

    def remove(self, tags: Optional[Dict[str, str]] = None) -> bool:
        """Drop one tagged series (e.g. a dead worker's gauges) so the
        exposition doesn't accumulate stale children forever. Returns
        whether the series existed."""
        key = self._key(tags)
        removed = False
        with self._lock:
            for table in ("_values", "_counts", "_sums", "_totals"):
                d = getattr(self, table, None)
                if d is not None and d.pop(key, None) is not None:
                    removed = True
        return removed

    def series(self) -> List[Dict[str, str]]:
        """Tag dicts of every live child. Lifecycle sweeps (e.g. a
        trial stopping) enumerate these to retract an entity's series
        without knowing every key the entity ever emitted."""
        keys: List[Tuple] = []
        with self._lock:
            for table in ("_values", "_counts", "_sums", "_totals"):
                d = getattr(self, table, None)
                if d is not None:
                    keys.extend(d.keys())
        return [dict(zip(self.tag_keys, k))
                for k in dict.fromkeys(keys)]

    def _fmt_tags(self, key: Tuple) -> str:
        if not self.tag_keys:
            return ""
        inner = ",".join(
            f'{k}="{v}"' for k, v in zip(self.tag_keys, key)
        )
        return "{" + inner + "}"

    def expose(self) -> List[str]:
        raise NotImplementedError


class Counter(Metric):
    metric_type = "counter"

    def __init__(self, name, description="", tag_keys=None):
        super().__init__(name, description, tag_keys)
        self._values: Dict[Tuple, float] = {}

    def inc(self, value: float = 1.0, tags: Optional[Dict[str, str]] = None):
        if value < 0:
            raise ValueError("counters only increase")
        key = self._key(tags)
        with self._lock:
            self._values[key] = self._values.get(key, 0.0) + value

    def expose(self) -> List[str]:
        out = [f"# HELP {self.name} {self.description}",
               f"# TYPE {self.name} counter"]
        with self._lock:
            for key, v in self._values.items():
                out.append(f"{self.name}{self._fmt_tags(key)} {v}")
        return out


class Gauge(Metric):
    metric_type = "gauge"

    def __init__(self, name, description="", tag_keys=None):
        super().__init__(name, description, tag_keys)
        self._values: Dict[Tuple, float] = {}

    def set(self, value: float, tags: Optional[Dict[str, str]] = None):
        with self._lock:
            self._values[self._key(tags)] = float(value)

    def inc(self, value: float = 1.0, tags=None):
        key = self._key(tags)
        with self._lock:
            self._values[key] = self._values.get(key, 0.0) + value

    def dec(self, value: float = 1.0, tags=None):
        self.inc(-value, tags)

    def expose(self) -> List[str]:
        out = [f"# HELP {self.name} {self.description}",
               f"# TYPE {self.name} gauge"]
        with self._lock:
            for key, v in self._values.items():
                out.append(f"{self.name}{self._fmt_tags(key)} {v}")
        return out


class Histogram(Metric):
    metric_type = "histogram"

    def __init__(self, name, description="", boundaries=None, tag_keys=None):
        super().__init__(name, description, tag_keys)
        self.boundaries = list(boundaries or DEFAULT_HISTOGRAM_BOUNDARIES)
        self._counts: Dict[Tuple, List[int]] = {}
        self._sums: Dict[Tuple, float] = {}
        self._totals: Dict[Tuple, int] = {}

    def observe(self, value: float, tags: Optional[Dict[str, str]] = None):
        key = self._key(tags)
        with self._lock:
            counts = self._counts.setdefault(
                key, [0] * (len(self.boundaries) + 1)
            )
            import bisect

            counts[bisect.bisect_left(self.boundaries, value)] += 1
            self._sums[key] = self._sums.get(key, 0.0) + value
            self._totals[key] = self._totals.get(key, 0) + 1

    def expose(self) -> List[str]:
        out = [f"# HELP {self.name} {self.description}",
               f"# TYPE {self.name} histogram"]
        with self._lock:
            for key, counts in self._counts.items():
                base_tags = list(zip(self.tag_keys, key))
                cumulative = 0
                for bound, c in zip(self.boundaries, counts):
                    cumulative += c
                    tags = base_tags + [("le", str(bound))]
                    inner = ",".join(f'{k}="{v}"' for k, v in tags)
                    out.append(f"{self.name}_bucket{{{inner}}} {cumulative}")
                cumulative += counts[-1]
                inner = ",".join(
                    f'{k}="{v}"' for k, v in base_tags + [("le", "+Inf")]
                )
                out.append(f"{self.name}_bucket{{{inner}}} {cumulative}")
                out.append(
                    f"{self.name}_sum{self._fmt_tags(key)} {self._sums[key]}"
                )
                out.append(
                    f"{self.name}_count{self._fmt_tags(key)} {self._totals[key]}"
                )
        return out


# -- node reporter gauges (reference: dashboard/modules/reporter's
# per-worker cpu/mem stats flowing into the Prometheus exporter). The
# node agent's telemetry loop samples /proc for each worker process and
# sets these; a process that runs no agent just exposes the empty
# families. Tagged per worker so one scrape shows the whole node.
WORKER_CPU_PERCENT = Gauge(
    "ray_tpu_worker_cpu_percent",
    "CPU utilization of a worker process (percent of one core)",
    tag_keys=("node_id", "worker_id", "pid"),
)
WORKER_RSS_BYTES = Gauge(
    "ray_tpu_worker_rss_bytes",
    "Resident set size of a worker process in bytes",
    tag_keys=("node_id", "worker_id", "pid"),
)
WORKER_UPTIME_SECONDS = Gauge(
    "ray_tpu_worker_uptime_seconds",
    "Seconds since the worker process was spawned",
    tag_keys=("node_id", "worker_id", "pid"),
)
NODE_WORKER_COUNT = Gauge(
    "ray_tpu_node_worker_count",
    "Live worker processes on a node",
    tag_keys=("node_id",),
)

# -- task execution phases (fed by the agents from the workers' batched
# task-event reports: each finished task carries wall-ns per phase —
# arg fetch/deserialize, execute, output serialize+store — so p50/p99
# per phase is scrapeable without the state API).
TASK_PHASE_SECONDS = Histogram(
    "ray_tpu_task_phase_seconds",
    "Wall time of one task execution phase (get_args/execute/put_outputs)",
    boundaries=[0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01,
                0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0],
    # node_id like every per-node family: on a real multi-host cluster
    # each agent has its OWN registry, and a phase-only label set would
    # federate as duplicate series (Prometheus rejects the scrape).
    tag_keys=("node_id", "phase"),
)

# -- JAX/XLA device telemetry (util/device_telemetry.py snapshots,
# sampled per worker process and exported by its node agent; stubbed —
# device count 0, no per-device children — when jax never loads).
DEVICE_COUNT = Gauge(
    "ray_tpu_device_count",
    "Accelerator devices visible on a node (0 = no jax-loaded process)",
    tag_keys=("node_id",),
)
DEVICE_MEM_IN_USE = Gauge(
    "ray_tpu_device_memory_bytes_in_use",
    "Device (HBM) bytes in use by a worker process, per device",
    tag_keys=("node_id", "worker_id", "device"),
)
DEVICE_MEM_PEAK = Gauge(
    "ray_tpu_device_memory_peak_bytes",
    "Peak device (HBM) bytes in use by a worker process, per device",
    tag_keys=("node_id", "worker_id", "device"),
)
DEVICE_MEM_LIMIT = Gauge(
    "ray_tpu_device_memory_bytes_limit",
    "Device (HBM) byte capacity visible to a worker process, per device",
    tag_keys=("node_id", "worker_id", "device"),
)
DEVICE_JAX_COMPILES = Gauge(
    "ray_tpu_device_jax_compiles",
    "Cumulative XLA backend compiles in a worker process",
    tag_keys=("node_id", "worker_id"),
)
DEVICE_JAX_COMPILE_SECONDS = Gauge(
    "ray_tpu_device_jax_compile_seconds",
    "Cumulative XLA backend compile wall seconds in a worker process",
    tag_keys=("node_id", "worker_id"),
)
DEVICE_JAX_CACHE_HITS = Gauge(
    "ray_tpu_device_jax_cache_hits",
    "Cumulative JAX compilation-cache hits in a worker process",
    tag_keys=("node_id", "worker_id"),
)
DEVICE_JAX_CACHE_MISSES = Gauge(
    "ray_tpu_device_jax_cache_misses",
    "Cumulative JAX compilation-cache misses in a worker process",
    tag_keys=("node_id", "worker_id"),
)

# -- node drain lifecycle (head-side; the drain coordinator records one
# increment per initiated drain and the wall time from DRAINING to
# deregistration, so preemption churn is visible per reason).
NODE_DRAINS_TOTAL = Counter(
    "ray_tpu_node_drains_total",
    "Node drains initiated, by reason (preemption, autoscaler_idle, ...)",
    tag_keys=("reason",),
)
NODE_DRAIN_DURATION_SECONDS = Histogram(
    "ray_tpu_node_drain_duration_seconds",
    "Wall time from drain start to node deregistration",
    boundaries=[0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 120.0],
    tag_keys=("reason",),
)
NODE_DRAIN_ACTORS_MIGRATED = Counter(
    "ray_tpu_node_drain_actors_migrated_total",
    "Actors proactively reconstructed off draining nodes",
    tag_keys=("reason",),
)
# -- placement-group rescheduling (head-side; the gang-migration half of
# the drain/preemption plane: one increment per completed bundle
# migration, and the wall time from losing a bundle's node to the
# reservation being whole again on healthy nodes).
PG_RESCHEDULES_TOTAL = Counter(
    "ray_tpu_pg_reschedules_total",
    "Completed placement-group reschedules, by trigger cause "
    "(drain = planned departure, node_death = crash-detected loss)",
    tag_keys=("cause",),
)
PG_RESCHEDULE_SECONDS = Histogram(
    "ray_tpu_pg_reschedule_seconds",
    "Wall time from a gang bundle losing its node to the group's "
    "reservation being CREATED again on healthy nodes",
    boundaries=[0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0,
                120.0],
)

# -- fleet autoscaler (execution half, round 17): per-node-type launch /
# failure / quarantine / scale-down counters plus the pending-demand
# gauge the bin-packer planned against — `ray-tpu top` reads these from
# the signal ring as fleet churn. The pending-demand gauge is per-kind
# (task/actor/pg_bundle/slo_burn) and retracted on autoscaler stop so a
# torn-down fleet doesn't linger on the federated scrape.
AUTOSCALER_LAUNCHES_TOTAL = Counter(
    "ray_tpu_autoscaler_launches_total",
    "Provider nodes successfully launched, by node type",
    tag_keys=("node_type",),
)
AUTOSCALER_LAUNCH_FAILURES_TOTAL = Counter(
    "ray_tpu_autoscaler_launch_failures_total",
    "Provider create_node failures/timeouts, by node type",
    tag_keys=("node_type",),
)
AUTOSCALER_QUARANTINES_TOTAL = Counter(
    "ray_tpu_autoscaler_quarantines_total",
    "Node types benched after consecutive boot failures",
    tag_keys=("node_type",),
)
AUTOSCALER_SCALE_DOWNS_TOTAL = Counter(
    "ray_tpu_autoscaler_scale_downs_total",
    "Provider nodes terminated by scale-down (drained first unless the "
    "head was unreachable), by node type",
    tag_keys=("node_type",),
)
AUTOSCALER_LAUNCH_SECONDS = Histogram(
    "ray_tpu_autoscaler_launch_seconds",
    "Wall time of one successful provider create_node call",
    boundaries=[0.01, 0.05, 0.1, 0.5, 1.0, 5.0, 15.0, 60.0, 120.0,
                300.0],
    tag_keys=("node_type",),
)
AUTOSCALER_PENDING_DEMAND = Gauge(
    "ray_tpu_autoscaler_pending_demand",
    "Pending demand entries the bin-packer planned against, by kind "
    "(task, actor, pg_bundle, slo_burn)",
    tag_keys=("kind",),
)

# -- head control plane (head-side; the contention instrumentation the
# 100k-task/1k-actor envelope reads: per-method handler latency on the
# head's RPC server, time spent WAITING on each head lock shard — an
# uncontended acquire observes nothing — and the write-behind
# persistence queue, so "the head is melting" shows up in the federated
# scrape as a named shard/method instead of a vibe).
HEAD_RPC_SECONDS = Histogram(
    "ray_tpu_head_rpc_seconds",
    "Head RPC handler wall time, per method",
    boundaries=[0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01,
                0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5],
    tag_keys=("method",),
)
HEAD_LOCK_WAIT_SECONDS = Histogram(
    "ray_tpu_head_lock_wait_seconds",
    "Time head threads spent blocked acquiring a contended lock shard "
    "(nodes = node/actor/PG tables, objects = object/ref tables, "
    "events = spans/logs)",
    boundaries=[0.0001, 0.0005, 0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1.0],
    tag_keys=("shard",),
)
HEAD_PERSIST_QUEUE_DEPTH = Gauge(
    "ray_tpu_head_persist_queue_depth",
    "Dirty keys waiting in the head's write-behind persistence queue",
)
HEAD_PERSIST_FLUSH_SECONDS = Histogram(
    "ray_tpu_head_persist_flush_seconds",
    "Wall time of one write-behind sqlite batch transaction",
    boundaries=[0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
                0.25, 1.0],
)
HEAD_PERSIST_COALESCED = Counter(
    "ray_tpu_head_persist_coalesced_total",
    "Per-key writes absorbed by the write-behind queue before a flush "
    "(each one was a synchronous fsync'd transaction before round 6)",
)
HEAD_SPANS_DROPPED = Counter(
    "ray_tpu_head_spans_dropped_total",
    "Tracing spans dropped by the head's bounded span ring",
)
TRACING_DROPPED_SPANS = Counter(
    "ray_tpu_tracing_dropped_spans_total",
    "Finished spans a process dropped to its in-memory ring cap before "
    "they could be drained (worker-side drops are re-attributed to "
    "their node by the agent when the event batch ships the count)",
    tag_keys=("node_id",),
)
HEAD_TRACES_DROPPED = Counter(
    "ray_tpu_head_traces_dropped_total",
    "Assembled traces evicted from the head's bounded trace store, "
    "by cause (sampled = tail-sampling declined, evicted = retention "
    "cap, span_cap = per-trace span limit clipped spans)",
    tag_keys=("cause",),
)
TASK_RECORDS_EVICTED = Counter(
    "ray_tpu_task_records_evicted_total",
    "Finished task records evicted from a node agent's bounded ring",
    tag_keys=("node_id",),
)
PUBSUB_COALESCED = Counter(
    "ray_tpu_pubsub_coalesced_total",
    "Pubsub messages absorbed by per-(subscriber,channel,key) "
    "coalescing (subscriber saw latest state instead of history)",
)
PUBSUB_DROPPED = Counter(
    "ray_tpu_pubsub_dropped_total",
    "Pubsub messages dropped on slow-subscriber buffer overflow",
)

# -- Serve request path (the SLO latency plane: replicas, routers and
# batch queues record into ray_tpu/serve/_observability.py, which ships
# the observations over the worker-events plane so they land in the
# scraped (agent) registry; per-replica gauge children are retracted
# when the replica's worker dies, same lifecycle as the /proc gauges).
# Every family is node_id-tagged: on a real multi-host cluster each
# agent has its own registry and a deployment-only label set would
# federate as duplicate series.
SERVE_LATENCY_BOUNDARIES = [
    0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0,
    2.5, 5.0, 10.0, 30.0, 60.0,
]
SERVE_REQUEST_SECONDS = Histogram(
    "ray_tpu_serve_request_seconds",
    "Serve request wall time per phase (route=router assign, "
    "queue_wait=assign to replica execution, batch_wait=time queued in "
    "a @serve.batch queue, execute=user callable, serialize=response "
    "serialize/transfer remainder, total=end to end)",
    boundaries=SERVE_LATENCY_BOUNDARIES,
    tag_keys=("node_id", "deployment", "phase"),
)
SERVE_REQUESTS_TOTAL = Counter(
    "ray_tpu_serve_requests_total",
    "Serve requests by terminal status (ok/error/shed), counted once "
    "at the router",
    tag_keys=("node_id", "deployment", "status"),
)
SERVE_SHED_TOTAL = Counter(
    "ray_tpu_serve_shed_total",
    "Deadline-expired serve requests shed instead of executed, by the "
    "site that shed them (router/replica/batch)",
    tag_keys=("node_id", "deployment", "reason"),
)
SERVE_REPLICA_ONGOING = Gauge(
    "ray_tpu_serve_replica_ongoing",
    "In-flight requests executing on one serve replica",
    tag_keys=("node_id", "deployment", "replica"),
)
SERVE_ROUTER_QUEUE_DEPTH = Gauge(
    "ray_tpu_serve_router_queue_depth",
    "Requests blocked in a router process waiting for replica capacity "
    "(backpressure behind max_concurrent_queries)",
    tag_keys=("node_id", "deployment", "worker"),
)
SERVE_BATCH_SIZE = Histogram(
    "ray_tpu_serve_batch_size",
    "Items per executed @serve.batch batch",
    boundaries=[1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0, 128.0],
    tag_keys=("node_id", "deployment"),
)
SERVE_RECONCILE_SECONDS = Gauge(
    "ray_tpu_serve_reconcile_seconds",
    "Duration of the serve controller's last reconcile pass (health "
    "probes + autoscaling + replica convergence)",
    tag_keys=("node_id",),
)
SERVE_EVENTS_DROPPED = Counter(
    "ray_tpu_serve_events_dropped_total",
    "Serve observations discarded by a worker's bounded ship buffer "
    "before the event flusher drained them (server-side request "
    "counts undercount by this much — no silent caps)",
    tag_keys=("node_id",),
)

# -- continuous-batching LLM decode engine (serve/llm_engine.py): one
# compiled decode step over a fixed slot batch, requests admitted
# between steps. Recorded through the same two-sided serve recorder
# (engine replicas are workers; events replay into the agent registry
# and federate on /metrics/cluster). Read batch occupancy BEFORE
# blaming step latency: a slow tokens/s with full occupancy is a
# kernel problem, with empty occupancy an admission problem.
SERVE_DECODE_STEP_SECONDS = Histogram(
    "ray_tpu_serve_decode_step_seconds",
    "Wall time of one compiled decode iteration of the LLM engine "
    "(device step + host sampling sync)",
    boundaries=[0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01,
                0.025, 0.05, 0.1, 0.25, 0.5, 1.0],
    tag_keys=("node_id", "deployment"),
)
SERVE_DECODE_BATCH_OCCUPANCY = Histogram(
    "ray_tpu_serve_decode_batch_occupancy",
    "Active slots per decode iteration (the continuous-batching "
    "utilization signal: 0-occupancy steps never run; a full batch at "
    "max_batch means admission is the bottleneck)",
    boundaries=[1.0, 2.0, 4.0, 8.0, 16.0, 32.0, 64.0, 128.0, 256.0],
    tag_keys=("node_id", "deployment"),
)
SERVE_DECODE_TTFT_SECONDS = Histogram(
    "ray_tpu_serve_decode_ttft_seconds",
    "Time to first token per admitted stream (submit -> first token "
    "available for delivery, engine-side). Extends past the request "
    "boundaries: under deep admission queues (10k streams on 64 "
    "slots) TTFT IS the queue, minutes not millis",
    boundaries=SERVE_LATENCY_BOUNDARIES + [120.0, 300.0, 600.0],
    tag_keys=("node_id", "deployment"),
)
SERVE_DECODE_TOKENS_TOTAL = Counter(
    "ray_tpu_serve_decode_tokens_total",
    "Tokens produced by the LLM decode engine (prefill first tokens + "
    "decode-step tokens, all streams)",
    tag_keys=("node_id", "deployment"),
)
SERVE_DECODE_ITL_SECONDS = Histogram(
    "ray_tpu_serve_decode_itl_seconds",
    "Inter-token latency (TPOT) per decode-step token: wall time from "
    "a stream's previous token to this one, engine-side (the decode "
    "half of the TTFT/TPOT SLO pair — a full batch with climbing ITL "
    "is a step-latency problem, not an admission problem)",
    boundaries=[0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01,
                0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5],
    tag_keys=("node_id", "deployment"),
)

# -- signal plane (head metrics history ring + SLO burn-rate layer,
# cluster/signals.py): the head self-scrapes its own federated
# /metrics/cluster into a bounded time-series ring and answers windowed
# queries from history — these families are the plane's SELF-overhead
# accounting (the TPU-concurrency-limits lesson: host-side sensing is a
# first-order cost, so the sensor charges itself on the same scrape it
# feeds) plus the SLO layer's exported burn state.
HEAD_SIGNAL_SCRAPE_SECONDS = Histogram(
    "ray_tpu_head_signal_scrape_seconds",
    "Wall time of one head signal-plane self-scrape (federated "
    "cluster_metrics_text render + parse + ring ingest)",
    boundaries=[0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
                0.25, 0.5, 1.0, 2.5],
)
HEAD_SIGNAL_SERIES = Gauge(
    "ray_tpu_head_signal_series",
    "Distinct time series retained in the head's signal-plane history "
    "ring (bounded by signal_max_series)",
)
HEAD_SIGNAL_EVICTIONS_TOTAL = Counter(
    "ray_tpu_head_signal_evictions_total",
    "Series evicted from the head's signal-plane ring, by reason "
    "(series_cap = ring full at signal_max_series, dead_node = node "
    "died, stale = series stopped reporting for a full history window)",
    tag_keys=("reason",),
)
AGENT_METRICS_RENDER_SECONDS = Gauge(
    "ray_tpu_agent_metrics_render_seconds",
    "Wall seconds the node agent spent rendering its previous "
    "metrics_text response (the per-node sensing cost every federated "
    "scrape fan-out pays; one scrape behind by construction — the "
    "cost isn't known until the body is rendered)",
    tag_keys=("node_id",),
)
SLO_STATE = Gauge(
    "ray_tpu_slo_state",
    "Burn-rate state of a registered SLO (0=ok 1=warning 2=burning)",
    tag_keys=("slo",),
)
SLO_VALUE = Gauge(
    "ray_tpu_slo_value",
    "Most recent windowed value of a registered SLO's signal",
    tag_keys=("slo",),
)
SLO_THRESHOLD = Gauge(
    "ray_tpu_slo_threshold",
    "Configured threshold of a registered SLO",
    tag_keys=("slo",),
)

# -- training goodput plane (input-pipeline + per-step train telemetry:
# dataset stages, consumer-loop stall accounting, session-driven step
# phases, the per-rank straggler gauge, and the trainer's downtime
# ledger — recorded two-sided through ray_tpu/train/_observability.py,
# the serve-plane shape: local registry immediately + worker-events
# replay into the agent registry the federated scrape sees; per-rank
# gauge children are retracted when the worker dies). node_id-tagged
# like every per-node family so multi-host federation never duplicates
# series.
DATA_STAGE_SECONDS = Histogram(
    "ray_tpu_data_stage_seconds",
    "Wall time of one executed dataset stage (driver-observed, whole "
    "stage across its blocks)",
    boundaries=[0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1.0, 5.0, 15.0,
                60.0, 300.0],
    tag_keys=("node_id", "stage"),
)
DATA_BLOCK_SECONDS = Histogram(
    "ray_tpu_data_block_seconds",
    "Wall time of one block through one dataset stage (task-measured)",
    boundaries=[0.0005, 0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1.0, 5.0,
                30.0],
    tag_keys=("node_id", "stage"),
)
DATA_BLOCK_ROWS = Histogram(
    "ray_tpu_data_block_rows",
    "Rows per output block of a dataset stage (skew shows up here)",
    boundaries=[1.0, 16.0, 64.0, 256.0, 1024.0, 4096.0, 16384.0,
                65536.0, 262144.0, 1048576.0],
    tag_keys=("node_id", "stage"),
)
DATA_BLOCK_BYTES = Histogram(
    "ray_tpu_data_block_bytes",
    "Bytes per output block of a dataset stage (a 10-GiB skewed block "
    "shows up here before it OOMs the store)",
    boundaries=[1e3, 1e4, 1e5, 1e6, 1e7, 1e8, 1e9, 1e10],
    tag_keys=("node_id", "stage"),
)
DATA_ITER_SECONDS = Histogram(
    "ray_tpu_data_iter_seconds",
    "Consumer-loop time per batch by phase (wait=consumer starved for "
    "the next batch, user=consumer's own time between batches, "
    "transfer=host->device dispatch in iter_device_batches); the "
    "derived stall fraction is wait.sum / (wait.sum + user.sum)",
    boundaries=[0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01,
                0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0],
    tag_keys=("node_id", "phase"),
)
DATA_PREFETCH_OCCUPANCY = Histogram(
    "ray_tpu_data_prefetch_occupancy",
    "Prefetch-buffer occupancy observed as the consumer takes each "
    "block batch (0 = the producer never gets ahead: every batch "
    "starves)",
    boundaries=[0.0, 1.0, 2.0, 4.0, 8.0, 16.0, 32.0],
    tag_keys=("node_id",),
)
TRAIN_STEP_PHASE_SECONDS = Histogram(
    "ray_tpu_train_step_phase_seconds",
    "Wall time of one training-step phase per reported step (data_wait "
    "/ step / report / checkpoint_save / checkpoint_restore), driven "
    "from the session API",
    boundaries=[0.0001, 0.0005, 0.001, 0.005, 0.01, 0.05, 0.1, 0.25,
                0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 120.0],
    tag_keys=("node_id", "trial", "phase"),
)
TRAIN_RANK_STEP_SECONDS = Gauge(
    "ray_tpu_train_rank_step_seconds",
    "Most recent step compute seconds per rank of a gang (the "
    "straggler gauge: rank skew at a glance); retracted when the "
    "worker dies",
    tag_keys=("node_id", "trial", "rank"),
)
TRAIN_REPORTS_TOTAL = Counter(
    "ray_tpu_train_reports_total",
    "session.report calls per trial (all ranks)",
    tag_keys=("node_id", "trial"),
)
TRAIN_DOWNTIME_SECONDS = Counter(
    "ray_tpu_train_downtime_seconds_total",
    "Non-productive trial wall seconds attributed by the trainer's "
    "downtime ledger (cause: drain:<reason> / preemption / failure)",
    tag_keys=("node_id", "trial", "cause"),
)
TRAIN_EVENTS_DROPPED = Counter(
    "ray_tpu_train_events_dropped_total",
    "Goodput observations discarded by a worker's bounded ship buffer "
    "before the event flusher drained them (no silent caps)",
    tag_keys=("node_id",),
)

# -- step anatomy plane (round 19: per-rank phase decomposition, in
# seconds). A per-entity gauge: retracted on worker death and session
# stop via goodput.retract_gauges / retract_trial.
TRAIN_STEP_ANATOMY_SECONDS = Gauge(
    "ray_tpu_step_phase_seconds",
    "Most recent step-anatomy decomposition per rank: data_wait / host "
    "(dispatch until device launch) / compute (synced device wall) / "
    "sync (barrier skew: this rank's wait for the slowest rank); the "
    "four phases partition the instrumented step wall exactly; "
    "retracted on worker death and session stop",
    tag_keys=("node_id", "trial", "phase", "rank"),
)

# -- streaming dataflow (round 14: memory-safe data plane). Block
# splits and pool scaling record two-sided through util/goodput.py
# (tasks/drivers emit events, agents replay them into the federated
# registry); spill traffic records agent-side directly — the agent IS
# the scraped registry for its node.
DATA_BLOCK_SPLITS = Counter(
    "ray_tpu_block_splits_total",
    "Extra output blocks produced by dynamic block splitting (a stage "
    "whose output exceeded target_block_size_bytes; N splits = N "
    "store-friendly objects instead of one oversized block)",
    tag_keys=("node_id", "stage"),
)
DATA_POOL_SIZE = Gauge(
    "ray_tpu_data_pool_size",
    "Live actors in an autoscaling dataset actor pool "
    "(ActorPoolStrategy(min, max): grows on queue depth, shrinks on "
    "idle)",
    tag_keys=("node_id", "pool"),
)
DATA_POOL_QUEUE_DEPTH = Gauge(
    "ray_tpu_data_pool_queue_depth",
    "Blocks queued behind an autoscaling dataset actor pool (the "
    "scale-up pressure signal, sampled at scale decisions)",
    tag_keys=("node_id", "pool"),
)

# -- RPC plane (client-side; one increment per reconnect attempt a
# retry-windowed call makes after losing its connection — a reconnect
# storm against one peer is visible on the federated scrape).
RPC_RECONNECTS_TOTAL = Counter(
    "ray_tpu_rpc_reconnects_total",
    "RPC reconnect attempts after connection loss, by peer address",
    tag_keys=("peer",),
)

# -- daemon-loop survivability (every forever-loop's survival handler
# ticks this when it swallows an exception and re-enters the iteration;
# the DL002 static rule enforces the discipline. A loop stuck in a
# crash-restart cycle shows as a climbing series instead of silently
# burning a core; components retract their loop children on stop so a
# dead node's loops leave the federated scrape).
LOOP_RESTARTS_TOTAL = Counter(
    "ray_tpu_loop_restarts_total",
    "Exceptions a daemon loop survived (swallowed and re-entered the "
    "iteration), by loop name",
    tag_keys=("loop",),
)


def count_loop_restart(loop: str) -> None:
    """One survived daemon-loop exception. Never raises: the survival
    handler calling this is the last line of defense for its loop, and
    a metrics failure must not become the exception that kills it."""
    try:
        LOOP_RESTARTS_TOTAL.inc(tags={"loop": loop})
    except Exception:
        pass


def retract_loop_series(loops: Sequence[str]) -> None:
    """Drop the loop-restart children a stopping component owns (agent
    stop, engine shutdown) so dead nodes' loops vanish from the
    federated scrape. Never raises (stop paths call it)."""
    for loop in loops:
        try:
            LOOP_RESTARTS_TOTAL.remove(tags={"loop": loop})
        except Exception:
            pass

# -- object store / memory observability (agent-side per-node occupancy
# sampled from the shm store's native stats; the head observes object
# lifetimes into the age histogram as the ref-counter frees them, and
# OOM kills count where they happen — on the killing node's agent).
OBJECT_STORE_BYTES_USED = Gauge(
    "ray_tpu_object_store_bytes_used",
    "Bytes resident in a node's shared-memory object store",
    tag_keys=("node_id",),
)
OBJECT_STORE_BYTES_CAPACITY = Gauge(
    "ray_tpu_object_store_bytes_capacity",
    "Byte capacity of a node's shared-memory object store",
    tag_keys=("node_id",),
)
OBJECT_STORE_OBJECTS = Gauge(
    "ray_tpu_object_store_objects",
    "Objects resident in a node's shared-memory object store",
    tag_keys=("node_id",),
)
OBJECT_STORE_EVICTIONS = Counter(
    "ray_tpu_object_store_evictions_total",
    "Objects evicted from a node's object store (LRU or spill-evict)",
    tag_keys=("node_id",),
)
OBJECT_SPILL_DENIED = Counter(
    "ray_tpu_object_spill_denied_total",
    "Spill requests that could not free the requested bytes "
    "(everything left referenced or pinned — a put is about to fail)",
    tag_keys=("node_id",),
)
SPILL_BYTES_TOTAL = Counter(
    "ray_tpu_spill_bytes_total",
    "Bytes written to the node's spill target (local session dir or "
    "the configured spill_uri backend) under memory pressure",
    tag_keys=("node_id",),
)
SPILL_RESTORES_TOTAL = Counter(
    "ray_tpu_spill_restores_total",
    "Spilled objects restored into a node's store (local spill-file "
    "reads plus restore-from-URI recoveries of a dead node's objects)",
    tag_keys=("node_id",),
)
SHM_SWEPT_BYTES = Counter(
    "ray_tpu_shm_swept_bytes_total",
    "Bytes of stale /dev/shm/ray_tpu_* segments (owner process dead — "
    "a SIGKILLed run's leak) removed by the startup sweeper",
)
OBJECT_AGE_SECONDS = Histogram(
    "ray_tpu_object_age_seconds",
    "Lifetime of cluster objects at free time (creation to last-ref)",
    boundaries=[0.1, 0.5, 1.0, 5.0, 15.0, 60.0, 300.0, 1800.0, 7200.0],
)
OOM_KILLS_TOTAL = Counter(
    "ray_tpu_oom_kills_total",
    "Workers killed by the node memory monitor under memory pressure",
    tag_keys=("node_id",),
)


def percentile(sorted_vals: Sequence[float], q: float) -> float:
    """Nearest-rank percentile of an ascending-sorted non-empty
    sequence (shared by state.summarize_tasks and the bench evidence
    writers — one definition, so summaries and committed evidence can
    never disagree)."""
    idx = min(len(sorted_vals) - 1,
              max(0, int(round(q * (len(sorted_vals) - 1)))))
    return sorted_vals[idx]


def latency_dist_ms(vals_ms: Sequence[float]) -> Dict[str, float]:
    """{count, p50_ms, p99_ms, mean_ms} of a non-empty ms sample set."""
    vals = sorted(vals_ms)
    return {
        "count": len(vals),
        "p50_ms": round(percentile(vals, 0.50), 3),
        "p99_ms": round(percentile(vals, 0.99), 3),
        "mean_ms": round(sum(vals) / len(vals), 3),
    }


def registered() -> "List[Metric]":
    """Snapshot of the registry (exporters and dashboard generators)."""
    with _registry_lock:
        return list(_registry)


def prometheus_text() -> str:
    """Full registry in Prometheus exposition format (the /metrics body)."""
    lines: List[str] = []
    for m in registered():
        lines.extend(m.expose())
    return "\n".join(lines) + "\n"


def merge_prometheus(chunks: Sequence[str]) -> str:
    """Merge several exposition bodies into one scrape-able document
    (the head's ``/metrics/cluster`` federation). ``# HELP``/``# TYPE``
    headers are kept once per metric family, and duplicate SERIES
    (same metric name + label set) keep their first-seen sample —
    in-process multi-agent clusters (tests, ``cluster_utils.Cluster``)
    share ONE process registry, so every agent reports the same series
    (possibly re-sampled to a different value between chunk renders —
    identity must be the name+labels, not the whole line, or a gauge
    that moved mid-merge duplicates and Prometheus rejects the body);
    per-node series stay distinct through their ``node_id`` tag."""
    seen_headers: set = set()
    seen_series: set = set()
    out: List[str] = []
    for chunk in chunks:
        for line in (chunk or "").splitlines():
            if not line.strip():
                continue
            if line.startswith("#"):
                parts = line.split(None, 3)
                key = tuple(parts[1:3])  # ("HELP"|"TYPE", metric name)
                if key in seen_headers:
                    continue
                seen_headers.add(key)
            else:
                series = line.rsplit(" ", 1)[0]  # name{labels}
                if series in seen_series:
                    continue
                seen_series.add(series)
            out.append(line)
    return "\n".join(out) + "\n"


# -- reading an exposition back (one parser for serve.stats, the bench
# cross-checks AND the head's signal-plane history ring — the same
# definition everywhere, so a windowed query and a client-side
# measurement can never disagree about what the text says). Moved here
# from serve/_observability.py (which re-exports) when the signal plane
# made the parser cluster infrastructure rather than a serve detail.

_SAMPLE_RE = _re.compile(
    r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(?:\{(.*)\})?\s+([^\s]+)$")
_LABEL_RE = _re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="([^"]*)"')


def parse_prometheus(text: str) -> Dict[str, Dict[tuple, float]]:
    """Exposition text -> {metric_name: {sorted (label, value) tuple:
    sample value}} (comments skipped; NaN-free by construction here)."""
    out: Dict[str, Dict[tuple, float]] = {}
    for line in (text or "").splitlines():
        line = line.strip()
        if not line or line.startswith("#"):
            continue
        m = _SAMPLE_RE.match(line)
        if not m:
            continue
        name, labels_raw, value = m.groups()
        try:
            val = float(value)
        except ValueError:
            continue
        labels = tuple(sorted(_LABEL_RE.findall(labels_raw or "")))
        out.setdefault(name, {})[labels] = val
    return out


def _labels_get(labels: tuple, key: str) -> Optional[str]:
    for k, v in labels:
        if k == key:
            return v
    return None


def sum_counter(parsed: dict, name: str, group_label: str,
                **match: str) -> Dict[str, float]:
    """Sum a family's samples across node_id (and any other untagged
    label), grouped by one label, filtered by exact label matches."""
    out: Dict[str, float] = {}
    for labels, val in (parsed.get(name) or {}).items():
        if any(_labels_get(labels, k) != v for k, v in match.items()):
            continue
        key = _labels_get(labels, group_label) or ""
        out[key] = out.get(key, 0.0) + val
    return out


def histogram_dist(parsed: dict, name: str, **match: str) -> Optional[dict]:
    """One histogram's cumulative buckets/sum/count, summed across
    node_id, filtered by exact label matches (e.g. deployment=...,
    phase=...). Returns {"buckets": [(le, cum)], "sum": s, "count": n}
    or None when no sample matched."""
    buckets: Dict[float, float] = {}
    total = 0.0
    count = 0.0
    seen = False
    for labels, val in (parsed.get(name + "_bucket") or {}).items():
        if any(_labels_get(labels, k) != v for k, v in match.items()):
            continue
        le_raw = _labels_get(labels, "le")
        le = float("inf") if le_raw == "+Inf" else float(le_raw)
        buckets[le] = buckets.get(le, 0.0) + val
        seen = True
    for labels, val in (parsed.get(name + "_sum") or {}).items():
        if not any(_labels_get(labels, k) != v for k, v in match.items()):
            total += val
    for labels, val in (parsed.get(name + "_count") or {}).items():
        if not any(_labels_get(labels, k) != v for k, v in match.items()):
            count += val
    if not seen or count <= 0:
        return None
    return {"buckets": sorted(buckets.items()), "sum": total,
            "count": count}


def quantile_from_buckets(dist: Optional[dict], q: float) -> Optional[float]:
    """Prometheus-style histogram_quantile: linear interpolation inside
    the bucket containing the q-th sample (the +Inf bucket clamps to the
    last finite bound — same convention as PromQL)."""
    if not dist:
        return None
    buckets = dist["buckets"]
    total = dist["count"]
    rank = q * total
    prev_le, prev_cum = 0.0, 0.0
    last_finite = 0.0
    for le, cum in buckets:
        if le != float("inf"):
            last_finite = le
        if cum >= rank and cum > prev_cum:
            if le == float("inf"):
                return last_finite
            frac = (rank - prev_cum) / (cum - prev_cum)
            return prev_le + (le - prev_le) * frac
        prev_le, prev_cum = (0.0 if le == float("inf") else le), cum
    return last_finite


def bucket_width_at(dist: Optional[dict], value: float) -> float:
    """Width of the histogram bucket a value falls in — the resolution
    floor for any client/server latency agreement check."""
    if not dist:
        return float("inf")
    prev = 0.0
    for le, _ in dist["buckets"]:
        if le == float("inf"):
            break
        if value <= le:
            return le - prev
        prev = le
    return float("inf")


def diff_parsed(before: dict, after: dict) -> dict:
    """Per-series ``after - before`` (counters/histogram buckets): lets
    a bench isolate ITS requests from whatever the shared registry
    already accumulated."""
    out: Dict[str, Dict[tuple, float]] = {}
    for name, series in after.items():
        base = before.get(name) or {}
        out[name] = {labels: val - base.get(labels, 0.0)
                     for labels, val in series.items()}
    return out


def file_sd_targets(address: str, labels: Optional[Dict[str, str]] = None,
                    path: str = "/metrics/cluster") -> List[dict]:
    """Prometheus file-SD document pointing one scrape job at the head's
    federated endpoint — one entry covers the whole cluster (write it
    with ``json.dump`` to a file named in a ``file_sd_configs`` block,
    with ``metrics_path: /metrics/cluster``)."""
    return [{
        "targets": [address],
        "labels": {"job": "ray_tpu", "__metrics_path__": path,
                   **(labels or {})},
    }]


PROM_CONTENT_TYPE = "text/plain; version=0.0.4"


def serve_metrics(host: str = "127.0.0.1", port: int = 0,
                  routes: Optional[Dict[str, tuple]] = None):
    """HTTP exposition server. ``routes`` maps a path to
    ``(body_fn, content_type)``; defaults to the process registry at
    ``/metrics``. Returns ``(port, shutdown_fn)``."""
    import http.server

    route_map = dict(routes or {})
    route_map.setdefault("/metrics", (prometheus_text, PROM_CONTENT_TYPE))

    class Handler(http.server.BaseHTTPRequestHandler):
        def do_GET(self):
            path = self.path.split("?", 1)[0].rstrip("/")
            entry = route_map.get(path or "/metrics")
            if entry is None:
                self.send_response(404)
                self.end_headers()
                return
            fn, ctype = entry
            try:
                body = fn().encode()
            except Exception as e:  # scrape must see the failure, not hang
                self.send_response(500)
                self.end_headers()
                self.wfile.write(repr(e).encode())
                return
            self.send_response(200)
            self.send_header("Content-Type", ctype)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def log_message(self, *a):
            pass

    server = http.server.ThreadingHTTPServer((host, port), Handler)
    threading.Thread(target=server.serve_forever, daemon=True).start()

    def shutdown():
        server.shutdown()
        server.server_close()

    return server.server_address[1], shutdown


def start_metrics_server(host: str = "127.0.0.1", port: int = 0) -> int:
    """Serve /metrics for Prometheus scraping; returns the bound port."""
    bound, _shutdown = serve_metrics(host, port)
    return bound
