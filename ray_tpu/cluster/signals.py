"""Cluster signal plane: metrics history ring, windowed queries, SLOs.

The sensing half of the autoscaler (ROADMAP items 1 and 4 consume the
query API built here). Every metric family in the system is a lifetime
total; the only windowed view used to be ``serve.stats(window_s)``
sleeping between two scrapes — banned from the dashboard path since PR
8 because a sleep in a request path stalls every pane. This module
gives the head a memory instead:

* **MetricsRing** — the head's scrape loop feeds each federated
  ``/metrics/cluster`` body through the one parser
  (``util/metrics.parse_prometheus``) into per-series deques of
  ``(ts, value)``. Retention is bounded twice over (PR-6 discipline):
  samples age out past ``signal_history_s`` AND each deque has a hard
  ``maxlen``; distinct series are capped at ``signal_max_series`` with
  least-recently-updated eviction. Dead nodes' series are aged out on
  the death edge (``Head._mark_dead``), stale series a history window
  after they stop reporting; every eviction is counted into
  ``ray_tpu_head_signal_evictions_total{reason}`` — never a silent cap.

* **windowed queries** — ``rate`` / ``delta`` / ``gauge_avg`` /
  ``gauge_max`` / ``gauge_last`` / ``trend`` over counters and gauges,
  and ``quantile_over_window`` over histograms computed from bucket
  deltas between ring snapshots (same interpolation as
  ``quantile_from_buckets`` — one quantile definition everywhere).
  Zero sleeps by construction: a query only ever reads history.

* **SLO layer** — declarative objects (``ttft_p50{deployment="d"} <
  2s over 60s``, ``shed_ratio < 1% over 300s``, ``rate(
  ray_tpu_oom_kills_total) < 1 over 300s``) evaluated by a head loop
  into burn-rate state ok -> warning -> burning with hysteresis
  (``slo_burn_evals`` consecutive breaching evaluations to burn, the
  same count of clean ones to recover; a scrape gap evaluates to None
  and HOLDS state — the evaluator must not flap on missing data).
  Transitions to/from burning publish structured events on the pubsub
  ``SLO`` channel (drain/OOM event shape) and the current state is
  exported as ``ray_tpu_slo_*`` gauges on the same scrape the ring
  ingests.
"""

from __future__ import annotations

import collections
import re
import threading
from typing import Dict, List, Optional, Tuple

from ray_tpu.util import metrics as _metrics
from ray_tpu.util.metrics import (
    _labels_get,
    parse_prometheus,
    quantile_from_buckets,
)

SLO_STATES = ("ok", "warning", "burning")
_STATE_CODE = {"ok": 0.0, "warning": 1.0, "burning": 2.0}

# Signal shorthands the SLO grammar resolves (the serve/train planes'
# SLO-able signals by their operator-facing names; anything else uses
# the generic op(metric) form).
_NAMED_SIGNALS: Dict[str, tuple] = {
    "ttft_p50": ("quantile", "ray_tpu_serve_decode_ttft_seconds",
                 0.50, {}),
    "ttft_p99": ("quantile", "ray_tpu_serve_decode_ttft_seconds",
                 0.99, {}),
    "itl_p50": ("quantile", "ray_tpu_serve_decode_itl_seconds",
                0.50, {}),
    "itl_p99": ("quantile", "ray_tpu_serve_decode_itl_seconds",
                0.99, {}),
    "latency_p50": ("quantile", "ray_tpu_serve_request_seconds",
                    0.50, {"phase": "total"}),
    "latency_p99": ("quantile", "ray_tpu_serve_request_seconds",
                    0.99, {"phase": "total"}),
    "qps": ("rate", "ray_tpu_serve_requests_total", None, {}),
    "shed_ratio": ("ratio", "ray_tpu_serve_shed_total",
                   "ray_tpu_serve_requests_total", {}),
    "error_ratio": ("ratio_match", "ray_tpu_serve_requests_total",
                    "ray_tpu_serve_requests_total",
                    {"status": "error"}),
    "queue_depth": ("gauge_avg", "ray_tpu_serve_router_queue_depth",
                    None, {}),
    "queue_depth_trend": ("trend", "ray_tpu_serve_router_queue_depth",
                          None, {}),
    # Step anatomy plane (round 19). step_p99 is the classic
    # per-report step residual; sync_ratio is the sync phase's share
    # of the per-rank anatomy gauges — the "gang is waiting, not
    # computing" burn signal.
    "step_p99": ("quantile", "ray_tpu_train_step_phase_seconds",
                 0.99, {"phase": "step"}),
    "sync_ratio": ("gauge_ratio", "ray_tpu_step_phase_seconds",
                   {"phase": "sync"}, {}),
}

_GENERIC_OPS = ("rate", "delta", "gauge_avg", "gauge_max", "gauge_last",
                "trend", "p50", "p90", "p95", "p99")

_SLO_RE = re.compile(
    r"^\s*(?P<sig>[a-zA-Z_][a-zA-Z0-9_]*)"
    r"(?:\(\s*(?P<arg>[a-zA-Z_:][a-zA-Z0-9_:]*)\s*\))?"
    r"\s*(?:\{(?P<labels>[^}]*)\})?"
    r"\s*(?P<op><=|>=|<|>)\s*"
    r"(?P<val>\d+(?:\.\d+)?)\s*(?P<unit>ms|s|%)?"
    r"(?:\s+over\s+(?P<win>\d+(?:\.\d+)?)\s*s?)?\s*$")
_LABEL_PAIR_RE = re.compile(
    r'([a-zA-Z_][a-zA-Z0-9_]*)\s*=\s*"?([^",]*)"?')


def parse_slo(expr: str) -> dict:
    """SLO grammar -> spec dict. Examples::

        ttft_p50{deployment="d"} < 2s over 60s
        shed_ratio < 1% over 300s
        p99(ray_tpu_task_phase_seconds) < 0.5s over 120s
        rate(ray_tpu_oom_kills_total) < 1 over 300s
        queue_depth_trend < 5 over 120s

    Raises ``ValueError`` on anything the grammar doesn't cover — a
    typo'd SLO must fail at registration, not evaluate to None forever.
    """
    m = _SLO_RE.match(expr or "")
    if not m:
        raise ValueError(f"unparseable SLO expression {expr!r}")
    sig, arg = m.group("sig"), m.group("arg")
    match = {k: v for k, v in
             _LABEL_PAIR_RE.findall(m.group("labels") or "")}
    threshold = float(m.group("val"))
    unit = m.group("unit")
    window_s = float(m.group("win") or 60.0)
    if arg is not None:
        if sig not in _GENERIC_OPS:
            raise ValueError(
                f"unknown signal op {sig!r} (have {_GENERIC_OPS})")
        if sig.startswith("p") and sig[1:].isdigit():
            signal = ("quantile", arg, int(sig[1:]) / 100.0, {})
        else:
            signal = (sig, arg, None, {})
    else:
        named = _NAMED_SIGNALS.get(sig)
        if named is None:
            raise ValueError(
                f"unknown named signal {sig!r} "
                f"(have {sorted(_NAMED_SIGNALS)})")
        signal = named
    # Unit scaling AFTER signal resolution: a family measured in
    # percent (``..._percent``) takes `< 40%` literally as 40, not
    # 0.4 — `gauge_avg(ray_tpu_worker_cpu_percent) > 90% over 120s`
    # must mean what it says.
    if unit == "ms":
        threshold /= 1e3
    elif unit == "%":
        if not str(signal[1]).endswith("_percent"):
            threshold /= 100.0
    return {
        "expr": expr.strip(),
        "signal": signal,
        "match": match,
        "op": m.group("op"),
        "threshold": threshold,
        "window_s": window_s,
    }


def _compare(value: float, op: str, threshold: float) -> bool:
    """True when the SLO HOLDS."""
    if op == "<":
        return value < threshold
    if op == "<=":
        return value <= threshold
    if op == ">":
        return value > threshold
    return value >= threshold


class _Slo:
    __slots__ = ("name", "spec", "state", "breach_streak", "ok_streak",
                 "last_value", "last_eval_ts", "missed_evals",
                 "transitions")

    def __init__(self, name: str, spec: dict):
        self.name = name
        self.spec = spec
        self.state = "ok"
        self.breach_streak = 0
        self.ok_streak = 0
        self.last_value: Optional[float] = None
        self.last_eval_ts: Optional[float] = None
        self.missed_evals = 0
        self.transitions = 0

    def status(self) -> dict:
        return {
            "name": self.name,
            "expr": self.spec["expr"],
            "state": self.state,
            "value": self.last_value,
            "threshold": self.spec["threshold"],
            "op": self.spec["op"],
            "window_s": self.spec["window_s"],
            "breach_streak": self.breach_streak,
            "missed_evals": self.missed_evals,
            "transitions": self.transitions,
            "last_eval_ts": self.last_eval_ts,
        }


class MetricsRing:
    """Bounded per-series time-series history over parsed expositions.

    Series key = ``(metric_name, sorted label tuple)`` — exactly the
    parser's shape, so ingest is one dict walk. All mutation happens
    under one lock; queries snapshot under the same lock (the scrape
    cadence is seconds, series counts are thousands — contention is
    not a concern at this scale, and a torn read would be)."""

    def __init__(self, history_s: float = 600.0,
                 max_series: int = 50_000,
                 scrape_interval_s: float = 2.0):
        self.history_s = max(1.0, float(history_s))
        self.max_series = max(16, int(max_series))
        # Hard per-series bound: the retention window's worth of
        # samples at the configured cadence, plus slack for jitter.
        self._maxlen = max(
            8, int(self.history_s / max(0.05, scrape_interval_s)) + 8)
        self._lock = threading.Lock()
        self._series: Dict[Tuple[str, tuple], collections.deque] = {}
        self._last_seen: Dict[Tuple[str, tuple], float] = {}
        self._snap_ts: collections.deque = collections.deque(
            maxlen=self._maxlen)
        self.evictions = {"series_cap": 0, "dead_node": 0, "stale": 0}

    # -- ingest ------------------------------------------------------------

    def ingest_text(self, ts: float, text: str) -> int:
        return self.ingest(ts, parse_prometheus(text))

    def ingest(self, ts: float, parsed: dict) -> int:
        """One scrape snapshot into the ring; returns the live series
        count after ingest (the self-overhead gauge's value)."""
        cutoff = ts - self.history_s
        with self._lock:
            self._snap_ts.append(ts)
            for name, series in parsed.items():
                for labels, value in series.items():
                    key = (name, labels)
                    dq = self._series.get(key)
                    if dq is None:
                        dq = collections.deque(maxlen=self._maxlen)
                        self._series[key] = dq
                    dq.append((ts, value))
                    self._last_seen[key] = ts
            # Age out: old samples everywhere, then whole series that
            # stopped reporting a full history window ago (a removed
            # deployment, a retracted gauge child).
            stale = []
            for key, dq in self._series.items():
                while dq and dq[0][0] < cutoff:
                    dq.popleft()
                if not dq or self._last_seen.get(key, 0.0) < cutoff:
                    stale.append(key)
            for key in stale:
                self._drop_locked(key, "stale")
            # Series cap, enforced ONCE per snapshot (a per-insert LRU
            # scan is O(series) per eviction — quadratic under a churn
            # storm, and this runs on the head): one sort, drop the
            # least-recently-updated excess. A single snapshot may
            # overshoot transiently inside this lock; it never returns
            # over cap.
            if len(self._series) > self.max_series:
                excess = len(self._series) - self.max_series
                doomed = sorted(
                    self._series,
                    key=lambda k: self._last_seen.get(k, 0.0))[:excess]
                for key in doomed:
                    self._drop_locked(key, "series_cap")
            return len(self._series)

    def _drop_locked(self, key, reason: str) -> None:
        self._series.pop(key, None)
        self._last_seen.pop(key, None)
        self.evictions[reason] = self.evictions.get(reason, 0) + 1
        try:
            _metrics.HEAD_SIGNAL_EVICTIONS_TOTAL.inc(
                tags={"reason": reason})
        except Exception:
            pass

    def age_out_node(self, node_id: str) -> int:
        """Drop every series labelled with a dead node (called on the
        node-death edge so queries never average a corpse in)."""
        with self._lock:
            doomed = [key for key in self._series
                      if _labels_get(key[1], "node_id") == node_id]
            for key in doomed:
                self._drop_locked(key, "dead_node")
            return len(doomed)

    # -- introspection -----------------------------------------------------

    def series_count(self) -> int:
        with self._lock:
            return len(self._series)

    def latest_ts(self) -> Optional[float]:
        with self._lock:
            return self._snap_ts[-1] if self._snap_ts else None

    def window_span(self, window_s: float) -> float:
        """The actual elapsed seconds the ring can answer for a
        requested window (ring younger than the window answers what it
        has; < 2 snapshots answers 0)."""
        with self._lock:
            if len(self._snap_ts) < 2:
                return 0.0
            latest = self._snap_ts[-1]
            start = latest - float(window_s)
            inside = [t for t in self._snap_ts if t >= start]
            if len(inside) < 2:
                return 0.0
            return inside[-1] - inside[0]

    def _matched(self, name: str, start: float,
                 match: Optional[dict]) -> List[Tuple[tuple, list]]:
        """[(labels, [(ts, v) in window])] for one family, filtered by
        exact label matches, under the lock."""
        out = []
        match = match or {}
        with self._lock:
            for (nm, labels), dq in self._series.items():
                if nm != name:
                    continue
                if any(_labels_get(labels, k) != v
                       for k, v in match.items()):
                    continue
                samples = [s for s in dq if s[0] >= start]
                if samples:
                    out.append((labels, samples))
        return out

    # -- windowed queries --------------------------------------------------

    def _anchor(self, window_s: float) -> Tuple[float, float]:
        latest = self.latest_ts()
        if latest is None:
            return 0.0, 0.0
        return latest, latest - max(0.0, float(window_s))

    def counter_delta(self, name: str, window_s: float,
                      match: Optional[dict] = None,
                      group_by: Optional[str] = None):
        """Sum of per-series increases inside the window (negative
        per-series deltas clamp to 0 — a restarted process's counter
        reset is not negative traffic). Returns ``(value_or_groups,
        elapsed_s)``."""
        _, start = self._anchor(window_s)
        groups: Dict[str, float] = {}
        elapsed = 0.0
        for labels, samples in self._matched(name, start, match):
            delta = max(0.0, samples[-1][1] - samples[0][1])
            span = samples[-1][0] - samples[0][0]
            elapsed = max(elapsed, span)
            key = (_labels_get(labels, group_by) or "") if group_by \
                else ""
            groups[key] = groups.get(key, 0.0) + delta
        if group_by:
            return groups, elapsed
        return groups.get("", 0.0), elapsed

    def rate(self, name: str, window_s: float,
             match: Optional[dict] = None,
             group_by: Optional[str] = None):
        """Per-second increase over the window; (value, elapsed_s)."""
        value, elapsed = self.counter_delta(
            name, window_s, match, group_by)
        if elapsed <= 0:
            return (({} if group_by else None), 0.0)
        if group_by:
            return ({k: v / elapsed for k, v in value.items()},
                    elapsed)
        return value / elapsed, elapsed

    def gauge_over_window(self, name: str, window_s: float,
                          agg: str = "avg",
                          match: Optional[dict] = None,
                          group_by: Optional[str] = None):
        """avg/max/last of a gauge family's samples in the window,
        summed across matched series per group (per-node CPU is the sum
        of its workers' gauges; per-deployment queue depth the sum of
        its routers')."""
        _, start = self._anchor(window_s)
        # group -> ts -> summed value across series
        per_ts: Dict[str, Dict[float, float]] = {}
        for labels, samples in self._matched(name, start, match):
            key = (_labels_get(labels, group_by) or "") if group_by \
                else ""
            bucket = per_ts.setdefault(key, {})
            for ts, v in samples:
                bucket[ts] = bucket.get(ts, 0.0) + v
        out: Dict[str, float] = {}
        for key, bucket in per_ts.items():
            vals = [bucket[t] for t in sorted(bucket)]
            if agg == "max":
                out[key] = max(vals)
            elif agg == "last":
                out[key] = vals[-1]
            else:
                out[key] = sum(vals) / len(vals)
        if group_by:
            return out
        return out.get("")

    def trend(self, name: str, window_s: float,
              match: Optional[dict] = None) -> Optional[float]:
        """Per-second growth of a gauge over the window: (second-half
        mean - first-half mean) / (window/2). Positive = climbing."""
        latest, start = self._anchor(window_s)
        if latest <= 0:
            return None
        mid = (latest + start) / 2.0
        per_ts: Dict[float, float] = {}
        for _labels, samples in self._matched(name, start, match):
            for ts, v in samples:
                per_ts[ts] = per_ts.get(ts, 0.0) + v
        first = [v for t, v in per_ts.items() if t < mid]
        second = [v for t, v in per_ts.items() if t >= mid]
        if not first or not second:
            return None
        half = max(1e-9, (latest - start) / 2.0)
        return (sum(second) / len(second)
                - sum(first) / len(first)) / half

    def quantile_over_window(self, name: str, q: float, window_s: float,
                             match: Optional[dict] = None
                             ) -> Optional[dict]:
        """PromQL-style windowed quantile from bucket deltas between
        ring snapshots: per-bucket-series increase inside the window,
        summed across matched series (cumulative counts stay cumulative
        under per-le subtraction). Returns {"value", "count", "sum",
        "resolution_s", "window_s"} or None when no samples moved."""
        _, start = self._anchor(window_s)
        buckets: Dict[float, float] = {}
        elapsed = 0.0
        for labels, samples in self._matched(
                name + "_bucket", start, match):
            le_raw = _labels_get(labels, "le")
            if le_raw is None:
                continue
            le = float("inf") if le_raw == "+Inf" else float(le_raw)
            delta = max(0.0, samples[-1][1] - samples[0][1])
            buckets[le] = buckets.get(le, 0.0) + delta
            elapsed = max(elapsed, samples[-1][0] - samples[0][0])
        count, _ = self.counter_delta(name + "_count", window_s, match)
        total, _ = self.counter_delta(name + "_sum", window_s, match)
        if not buckets or count <= 0:
            return None
        dist = {"buckets": sorted(buckets.items()), "sum": total,
                "count": count}
        value = quantile_from_buckets(dist, q)
        if value is None:
            return None
        from ray_tpu.util.metrics import bucket_width_at

        return {
            "value": value,
            "count": count,
            "sum": total,
            "resolution_s": bucket_width_at(dist, value),
            "window_s": elapsed,
        }

    def series_deltas(self, name: str, window_s: float,
                      match: Optional[dict] = None):
        """Per-series increase in window as wire-friendly
        ``[[label pairs, delta], ...]`` plus the elapsed span (the
        ``serve.stats`` history path consumes this shape)."""
        _, start = self._anchor(window_s)
        out = []
        for labels, samples in self._matched(name, start, match):
            out.append([[list(kv) for kv in labels],
                        max(0.0, samples[-1][1] - samples[0][1])])
        return out, self.window_span(window_s)


class SignalPlane:
    """MetricsRing + SLO registry + query dispatch (the head owns one;
    everything it exposes is also reachable in-process for tests and
    the bench)."""

    def __init__(self, history_s: float = 600.0,
                 max_series: int = 50_000,
                 scrape_interval_s: float = 2.0,
                 burn_evals: int = 3):
        self.ring = MetricsRing(history_s, max_series, scrape_interval_s)
        self.burn_evals = max(1, int(burn_evals))
        self._slo_lock = threading.Lock()
        self._slos: Dict[str, _Slo] = {}
        # Metrics<->trace exemplars: the trace store's lookup hook
        # (deployment, min_duration_s, limit) -> [{"trace_id", ...}].
        # Optional — a plane without a trace store answers without
        # exemplars, it never fails an SLO surface over them.
        self._exemplar_source = None

    def set_exemplar_source(self, source) -> None:
        self._exemplar_source = source

    def _exemplars_for(self, slo: _Slo, limit: int = 3) -> List[dict]:
        """Sampled trace_ids for the traffic this SLO watches: latency-
        quantile SLOs ask for traces at/over the threshold (the ones IN
        the breaching histogram buckets), everything else takes the
        slowest recent traces for the deployment."""
        if self._exemplar_source is None:
            return []
        kind = slo.spec["signal"][0]
        match = {**slo.spec["signal"][3], **slo.spec["match"]}
        min_s = slo.spec["threshold"] if kind == "quantile" else 0.0
        try:
            return list(self._exemplar_source(
                deployment=match.get("deployment"),
                min_duration_s=min_s, limit=limit) or [])
        except Exception:
            return []

    # -- ingest (head scrape loop) ----------------------------------------

    def ingest_text(self, ts: float, text: str) -> int:
        return self.ring.ingest_text(ts, text)

    def age_out_node(self, node_id: str) -> int:
        return self.ring.age_out_node(node_id)

    def series_count(self) -> int:
        return self.ring.series_count()

    # -- query dispatch (rpc_query_metrics) --------------------------------

    def query(self, spec: dict) -> dict:
        """One windowed query. ``spec``: {"op", "name", "window_s",
        "q"?, "match"?, "group_by"?}. Returns {"ok": bool, ...} — never
        raises on an unknown family (empty ring answers are a normal
        cold-start state the caller handles)."""
        if not isinstance(spec, dict):
            return {"ok": False, "error": "spec must be a dict"}
        op = spec.get("op")
        name = spec.get("name", "")
        window_s = float(spec.get("window_s", 60.0) or 60.0)
        match = spec.get("match") or {}
        group_by = spec.get("group_by")
        try:
            if op == "rate":
                value, elapsed = self.ring.rate(
                    name, window_s, match, group_by)
                return {"ok": True, "op": op, "name": name,
                        "value": value, "window_s": elapsed}
            if op == "delta":
                value, elapsed = self.ring.counter_delta(
                    name, window_s, match, group_by)
                return {"ok": True, "op": op, "name": name,
                        "value": value, "window_s": elapsed}
            if op in ("gauge_avg", "gauge_max", "gauge_last"):
                value = self.ring.gauge_over_window(
                    name, window_s, op[len("gauge_"):], match, group_by)
                return {"ok": True, "op": op, "name": name,
                        "value": value,
                        "window_s": self.ring.window_span(window_s)}
            if op == "trend":
                value = self.ring.trend(name, window_s, match)
                return {"ok": True, "op": op, "name": name,
                        "value": value,
                        "window_s": self.ring.window_span(window_s)}
            if op == "quantile":
                q = float(spec.get("q", 0.5))
                res = self.ring.quantile_over_window(
                    name, q, window_s, match)
                if res is None:
                    return {"ok": True, "op": op, "name": name,
                            "q": q, "value": None, "window_s": 0.0}
                return {"ok": True, "op": op, "name": name, "q": q,
                        **res}
            if op == "series_delta":
                series, elapsed = self.ring.series_deltas(
                    name, window_s, match)
                return {"ok": True, "op": op, "name": name,
                        "series": series, "window_s": elapsed}
            return {"ok": False,
                    "error": f"unknown query op {op!r}"}
        except Exception as e:  # a malformed spec answers, not raises
            return {"ok": False, "error": repr(e)}

    # -- SLO registry ------------------------------------------------------

    def register_slo(self, name: str, expr: str) -> dict:
        """Parse + register (idempotent per name: re-registering
        replaces the spec and resets the burn state)."""
        spec = parse_slo(expr)
        slo = _Slo(name, spec)
        with self._slo_lock:
            self._slos[name] = slo
        try:
            _metrics.SLO_THRESHOLD.set(spec["threshold"],
                                       tags={"slo": name})
            _metrics.SLO_STATE.set(0.0, tags={"slo": name})
        except Exception:
            pass
        return slo.status()

    def remove_slo(self, name: str) -> bool:
        with self._slo_lock:
            existed = self._slos.pop(name, None) is not None
        # Retract the per-SLO gauge children so a removed objective
        # vanishes from the federated scrape (LC001 discipline).
        try:
            _metrics.SLO_STATE.remove(tags={"slo": name})
            _metrics.SLO_VALUE.remove(tags={"slo": name})
            _metrics.SLO_THRESHOLD.remove(tags={"slo": name})
        except Exception:
            pass
        return existed

    def slo_status(self) -> dict:
        with self._slo_lock:
            slos = {name: (slo.status(), slo)
                    for name, slo in self._slos.items()}
        out = {}
        for name, (status, slo) in slos.items():
            if status["state"] in ("burning", "warning"):
                status["exemplar_trace_ids"] = [
                    e["trace_id"] for e in self._exemplars_for(slo)]
            out[name] = status
        return {"slos": out, "burn_evals": self.burn_evals,
                "series": self.ring.series_count(),
                "evictions": dict(self.ring.evictions)}

    def _signal_value(self, slo: _Slo) -> Optional[float]:
        kind, a, b, base_match = slo.spec["signal"]
        match = {**base_match, **slo.spec["match"]}
        window_s = slo.spec["window_s"]
        if kind == "quantile":
            res = self.ring.quantile_over_window(a, b, window_s, match)
            return None if res is None else res["value"]
        if kind == "rate":
            value, elapsed = self.ring.rate(a, window_s, match)
            return None if elapsed <= 0 else value
        if kind == "delta":
            value, elapsed = self.ring.counter_delta(a, window_s, match)
            return None if elapsed <= 0 else value
        if kind in ("gauge_avg", "gauge_max", "gauge_last"):
            return self.ring.gauge_over_window(
                a, window_s, kind[len("gauge_"):], match)
        if kind == "trend":
            return self.ring.trend(a, window_s, match)
        if kind == "gauge_ratio":
            # sync_ratio shape: one phase's share of a gauge family —
            # numerator extra labels ride in `b` (a dict), denominator
            # is the same family with them stripped (all phases, all
            # ranks summed per snapshot), so the value is the gang-wide
            # share of step wall spent in that phase.
            num = self.ring.gauge_over_window(
                a, window_s, "avg", {**match, **b})
            den = self.ring.gauge_over_window(a, window_s, "avg", match)
            if num is None or den is None or den <= 0:
                return None
            return num / den
        if kind == "ratio":
            # shed_ratio shape: numerator family / denominator family,
            # the shared match filtering both (deployment=...).
            num, elapsed = self.ring.counter_delta(a, window_s, match)
            den, _ = self.ring.counter_delta(b, window_s, match)
            if elapsed <= 0:
                return None
            return num / den if den > 0 else 0.0
        if kind == "ratio_match":
            # error_ratio shape: same family, extra labels on the
            # numerator only.
            num, elapsed = self.ring.counter_delta(a, window_s, match)
            den_match = {k: v for k, v in match.items()
                         if k not in base_match}
            den, _ = self.ring.counter_delta(b, window_s, den_match)
            if elapsed <= 0:
                return None
            return num / den if den > 0 else 0.0
        return None

    def evaluate_slos(self, now: float) -> List[dict]:
        """One evaluator pass: update every SLO's burn state and gauges;
        return the transition events to publish (only the burning /
        recovered edges — warning wiggle stays on the gauge)."""
        events: List[dict] = []
        with self._slo_lock:
            slos = list(self._slos.values())
        for slo in slos:
            value = self._signal_value(slo)
            slo.last_eval_ts = now
            if value is None:
                # Scrape gap / cold ring: hold state, never flap.
                slo.missed_evals += 1
                continue
            slo.last_value = value
            holds = _compare(value, slo.spec["op"],
                             slo.spec["threshold"])
            prev = slo.state
            if holds:
                slo.breach_streak = 0
                slo.ok_streak += 1
                if slo.state == "warning":
                    slo.state = "ok"
                elif slo.state == "burning" \
                        and slo.ok_streak >= self.burn_evals:
                    slo.state = "ok"
            else:
                slo.ok_streak = 0
                slo.breach_streak += 1
                if slo.breach_streak >= self.burn_evals:
                    slo.state = "burning"
                elif slo.state == "ok":
                    slo.state = "warning"
            if slo.state != prev:
                slo.transitions += 1
            if (prev != "burning" and slo.state == "burning") or \
                    (prev == "burning" and slo.state == "ok"):
                ev = {
                    "slo": slo.name,
                    "expr": slo.spec["expr"],
                    "state": slo.state,
                    "prev": prev,
                    "value": value,
                    "threshold": slo.spec["threshold"],
                    "window_s": slo.spec["window_s"],
                    "ts": now,
                }
                if slo.state == "burning":
                    # A burn event names concrete traces: the operator
                    # goes straight from "it's burning" to `ray-tpu
                    # trace <id>` without hunting for a repro.
                    ev["exemplar_trace_ids"] = [
                        e["trace_id"] for e in self._exemplars_for(slo)]
                events.append(ev)
            try:
                _metrics.SLO_STATE.set(_STATE_CODE[slo.state],
                                       tags={"slo": slo.name})
                _metrics.SLO_VALUE.set(float(value),
                                       tags={"slo": slo.name})
                _metrics.SLO_THRESHOLD.set(
                    slo.spec["threshold"], tags={"slo": slo.name})
            except Exception:
                pass
        return events

    # -- the `ray-tpu top` rollup ------------------------------------------

    def top_summary(self, window_s: float = 60.0) -> dict:
        """One cluster view from history — per-node CPU/RSS/store
        occupancy, serve QPS/TTFT/shed burn, train goodput — with zero
        sleeps in the path (every number is a ring query)."""
        ring = self.ring
        nodes: Dict[str, dict] = {}
        cpu = ring.gauge_over_window(
            "ray_tpu_worker_cpu_percent", window_s, "avg",
            group_by="node_id") or {}
        rss = ring.gauge_over_window(
            "ray_tpu_worker_rss_bytes", window_s, "last",
            group_by="node_id") or {}
        used = ring.gauge_over_window(
            "ray_tpu_object_store_bytes_used", window_s, "last",
            group_by="node_id") or {}
        cap = ring.gauge_over_window(
            "ray_tpu_object_store_bytes_capacity", window_s, "last",
            group_by="node_id") or {}
        workers = ring.gauge_over_window(
            "ray_tpu_node_worker_count", window_s, "last",
            group_by="node_id") or {}
        for nid in set(cpu) | set(rss) | set(used) | set(workers):
            if not nid:
                continue
            entry = {"cpu_percent": round(cpu.get(nid, 0.0), 1),
                     "rss_bytes": int(rss.get(nid, 0.0)),
                     "workers": int(workers.get(nid, 0.0))}
            if cap.get(nid):
                entry["store_occupancy"] = round(
                    used.get(nid, 0.0) / cap[nid], 4)
            nodes[nid] = entry
        serve: Dict[str, dict] = {}
        qps, _ = self.ring.rate(
            "ray_tpu_serve_requests_total", window_s,
            group_by="deployment")
        shed, _ = self.ring.rate(
            "ray_tpu_serve_shed_total", window_s,
            group_by="deployment")
        for dep, dep_qps in (qps or {}).items():
            if not dep:
                continue
            entry = {"qps": round(dep_qps, 2)}
            total = dep_qps
            if total > 0:
                entry["shed_ratio"] = round(
                    (shed or {}).get(dep, 0.0) / total, 4)
            ttft = ring.quantile_over_window(
                "ray_tpu_serve_decode_ttft_seconds", 0.50, window_s,
                {"deployment": dep})
            if ttft is not None:
                entry["ttft_p50_s"] = round(ttft["value"], 4)
            itl = ring.quantile_over_window(
                "ray_tpu_serve_decode_itl_seconds", 0.50, window_s,
                {"deployment": dep})
            if itl is not None:
                entry["itl_p50_s"] = round(itl["value"], 5)
            lat = ring.quantile_over_window(
                "ray_tpu_serve_request_seconds", 0.50, window_s,
                {"deployment": dep, "phase": "total"})
            if lat is not None:
                entry["latency_p50_s"] = round(lat["value"], 4)
            serve[dep] = entry
        train: Dict[str, dict] = {}
        reports, elapsed = self.ring.rate(
            "ray_tpu_train_reports_total", window_s, group_by="trial")
        downtime, _ = self.ring.counter_delta(
            "ray_tpu_train_downtime_seconds_total", window_s,
            group_by="trial")
        for trial, rps in (reports or {}).items():
            if not trial:
                continue
            entry = {"reports_per_s": round(rps, 3)}
            down = (downtime or {}).get(trial, 0.0)
            if elapsed > 0:
                entry["goodput_pct"] = round(
                    max(0.0, 1.0 - down / elapsed) * 100.0, 1)
            if down:
                entry["downtime_s"] = round(down, 1)
            train[trial] = entry
        # Step anatomy: the straggler verdict from the per-rank phase
        # gauges (the same attributor train_stats uses, so top and
        # stats can never disagree).
        from ray_tpu.util.goodput import (
            ANATOMY_PHASES,
            straggler_attribution,
        )

        anat_trials = set(ring.gauge_over_window(
            "ray_tpu_step_phase_seconds", window_s, "last",
            group_by="trial") or {})
        for trial in sorted(anat_trials - {""}):
            entry = train.setdefault(trial, {})
            rank_phases: Dict[str, Dict[str, float]] = {}
            for phase in ANATOMY_PHASES:
                per_rank = ring.gauge_over_window(
                    "ray_tpu_step_phase_seconds", window_s, "last",
                    {"trial": trial, "phase": phase},
                    group_by="rank") or {}
                for rank, val in per_rank.items():
                    rank_phases.setdefault(rank, {})[phase] = val
            verdict = straggler_attribution(rank_phases)
            if verdict:
                entry["straggler"] = verdict
        # Fleet churn: the autoscaler's counter families (windowed
        # deltas per node type) + the live pending-demand gauge — empty
        # until an autoscaler's registry lands in the ring.
        fleet_types: Dict[str, dict] = {}
        for key, fam in (
                ("launches", "ray_tpu_autoscaler_launches_total"),
                ("launch_failures",
                 "ray_tpu_autoscaler_launch_failures_total"),
                ("quarantines", "ray_tpu_autoscaler_quarantines_total"),
                ("scale_downs", "ray_tpu_autoscaler_scale_downs_total")):
            delta, _ = self.ring.counter_delta(
                fam, window_s, group_by="node_type")
            for t, v in (delta or {}).items():
                if not t or not v:
                    continue
                fleet_types.setdefault(t, {})[key] = int(v)
        pending = ring.gauge_over_window(
            "ray_tpu_autoscaler_pending_demand", window_s, "last",
            group_by="kind") or {}
        return {
            "window_s": window_s,
            "nodes": nodes,
            "serve": serve,
            "train": train,
            "fleet": {
                "types": fleet_types,
                "pending_demand": {k: int(v) for k, v in pending.items()
                                   if k and v},
            },
            "slos": self.slo_status()["slos"],
            "series": ring.series_count(),
            "evictions": dict(ring.evictions),
        }
