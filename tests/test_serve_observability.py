"""Serve request-path observability (PR 8): end-to-end trace
propagation across the HTTP and handle paths, per-phase SLO histograms,
deadline sheds at the router and the batch queue, metrics federation
with dead-replica pruning, and the serve_bench client/server latency
cross-check.

Test order matters (``-p no:randomly`` keeps definition order): the
serve_bench and cluster-federation tests tear down the module's local
runtime, so they run last.
"""

import json
import threading
import time

import pytest

import ray_tpu
from ray_tpu import serve, state
from ray_tpu.serve import _observability as obs
from ray_tpu.util import metrics, tracing


@pytest.fixture(autouse=True, scope="module")
def _runtime():
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=16)
    yield
    try:
        if ray_tpu.is_initialized():
            serve.shutdown()
    except Exception:
        pass
    ray_tpu.shutdown()


@pytest.fixture(autouse=True)
def _clean_between_tests():
    yield
    tracing.disable()
    try:
        if ray_tpu.is_initialized():
            serve.shutdown()
    except Exception:
        pass


def _snapshot():
    return obs.parse_prometheus(metrics.prometheus_text())


def _delta_since(before):
    return obs.diff_parsed(before, _snapshot())


# -- trace propagation ------------------------------------------------------


def test_trace_propagation_handle_path_one_trace():
    """One trace id covers client -> router -> replica -> NESTED handle
    call, with parent/child nesting intact (the tentpole's acceptance
    shape, on the handle path)."""

    @serve.deployment(name="TraceInner")
    class Inner:
        def __call__(self, x):
            return x * 2

    @serve.deployment(name="TraceOuter")
    class Outer:
        def __init__(self, inner):
            self.inner = inner

        def __call__(self, x):
            return ray_tpu.get(self.inner.remote(x), timeout=30) + 1

    handle = serve.run(Outer.bind(Inner.bind()))
    tracing.enable()
    with tracing.span("client-root") as root:
        assert ray_tpu.get(handle.remote(5), timeout=60) == 11
        trace_id = root["trace_id"]

    spans = {s["span_id"]: s for s in tracing.collect()
             if s["trace_id"] == trace_id and s.get("cat") == "serve"}
    by_name = {}
    for s in spans.values():
        by_name.setdefault(s["name"], []).append(s)
    assert "serve.route:TraceOuter" in by_name
    assert "serve.replica:TraceOuter.__call__" in by_name
    assert "serve.route:TraceInner" in by_name
    assert "serve.replica:TraceInner.__call__" in by_name

    route_outer = by_name["serve.route:TraceOuter"][0]
    rep_outer = by_name["serve.replica:TraceOuter.__call__"][0]
    route_inner = by_name["serve.route:TraceInner"][0]
    rep_inner = by_name["serve.replica:TraceInner.__call__"][0]
    # Parenting: client root -> route(outer) -> replica(outer) ->
    # route(inner) -> replica(inner).
    assert route_outer["parent_id"] == root["span_id"]
    assert rep_outer["parent_id"] == route_outer["span_id"]
    assert route_inner["parent_id"] == rep_outer["span_id"]
    assert rep_inner["parent_id"] == route_inner["span_id"]

    # The merged timeline carries the serve spans under cat "serve".
    serve_events = [e for e in state.timeline()
                    if e.get("cat") == "serve"]
    ids = {e["args"].get("span_id") for e in serve_events}
    assert route_outer["span_id"] in ids and rep_inner["span_id"] in ids


def test_trace_propagation_http_traceparent():
    """A W3C traceparent header at the HTTP proxy joins the caller's
    trace: http ingress span -> route -> replica all carry the header's
    trace id."""
    import http.client

    @serve.deployment(name="HttpTraced", route_prefix="/traced")
    def traced(payload):
        return {"ok": True}

    serve.run(traced.bind())
    port = serve.start_http_proxy()
    # Server-side opt-in: a traceparent header joins a trace only when
    # tracing is already enabled here (the proxy shares this process on
    # the local backend) — the header alone must not switch tracing on.
    conn0 = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    conn0.request("POST", "/traced", body=b"null", headers={
        "Content-Type": "application/json",
        "traceparent": f"00-{'ef' * 16}-{'01' * 8}-01",
    })
    assert conn0.getresponse().status == 200
    conn0.close()
    assert not tracing.is_enabled()
    assert not any(s["trace_id"] == "ef" * 16 for s in tracing.collect())

    tracing.enable()
    trace_id = "ab" * 16
    parent_span = "cd" * 8
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    conn.request("POST", "/traced", body=b"null", headers={
        "Content-Type": "application/json",
        "traceparent": f"00-{trace_id}-{parent_span}-01",
    })
    resp = conn.getresponse()
    assert resp.status == 200
    resp.read()
    conn.close()

    # The proxy finishes its http span after it has sent the reply, so
    # the client can be here first (it was, in a whole run under load):
    # wait for the span, do not race it.
    deadline = time.monotonic() + 10
    while True:
        spans = [s for s in tracing.collect()
                 if s["trace_id"] == trace_id and s.get("cat") == "serve"]
        names = {s["name"] for s in spans}
        if "serve.http:/traced" in names or time.monotonic() > deadline:
            break
        time.sleep(0.02)
    assert "serve.http:/traced" in names
    assert "serve.route:HttpTraced" in names
    assert any(n.startswith("serve.replica:HttpTraced") for n in names)
    http_span = next(s for s in spans if s["name"] == "serve.http:/traced")
    assert http_span["parent_id"] == parent_span
    route_span = next(s for s in spans
                      if s["name"] == "serve.route:HttpTraced")
    assert route_span["parent_id"] == http_span["span_id"]


# -- SLO latency plane ------------------------------------------------------


def test_phase_histograms_populated_per_phase():
    before = _snapshot()

    @serve.deployment(name="PhaseDep")
    def phased(x):
        time.sleep(0.002)
        return x

    handle = serve.run(phased.bind())
    for i in range(6):
        assert ray_tpu.get(handle.remote(i), timeout=30) == i

    delta = _delta_since(before)
    for phase in ("route", "queue_wait", "execute", "serialize", "total"):
        dist = obs.histogram_dist(
            delta, "ray_tpu_serve_request_seconds",
            deployment="PhaseDep", phase=phase)
        assert dist is not None, f"phase {phase} unobserved"
        assert dist["count"] == 6, (phase, dist)
    # Status counted once per request, router-side.
    statuses = obs.sum_counter(delta, "ray_tpu_serve_requests_total",
                               "status", deployment="PhaseDep")
    assert statuses == {"ok": 6.0}


def test_batch_wait_phase_and_batch_size_histogram():
    before = _snapshot()

    @serve.deployment(name="BatchDep", max_concurrent_queries=32)
    class BatchModel:
        @serve.batch(max_batch_size=8, batch_wait_timeout_s=0.05)
        def handle_batch(self, items):
            return [i * 2 for i in items]

        def __call__(self, x):
            return self.handle_batch(x)

    handle = serve.run(BatchModel.bind())
    refs = [handle.remote(i) for i in range(12)]
    assert sorted(ray_tpu.get(refs, timeout=60)) == \
        [2 * i for i in range(12)]

    delta = _delta_since(before)
    wait = obs.histogram_dist(delta, "ray_tpu_serve_request_seconds",
                              deployment="BatchDep", phase="batch_wait")
    assert wait is not None and wait["count"] == 12
    sizes = obs.histogram_dist(delta, "ray_tpu_serve_batch_size",
                               deployment="BatchDep")
    assert sizes is not None and sizes["count"] >= 1
    # Batching actually batched: fewer batches than items.
    assert sizes["count"] < 12


def test_deadline_shed_at_router():
    """A request whose deadline expires while the router waits for
    replica capacity is shed (typed error, counted) instead of executed
    late."""
    before = _snapshot()
    executed = []

    @serve.deployment(name="ShedRouter", num_replicas=1,
                      max_concurrent_queries=1)
    class Slow:
        def __call__(self, x):
            executed.append(x)
            time.sleep(0.4)
            return x

    handle = serve.run(Slow.bind())
    blocker = handle.remote("blocker")
    time.sleep(0.1)  # in flight, capacity now 0
    ref = handle.options(deadline_s=0.05).remote("victim")
    with pytest.raises(Exception) as ei:
        ray_tpu.get(ref, timeout=30)
    assert "RequestShedError" in repr(ei.value) or "shed" in repr(ei.value)
    assert ray_tpu.get(blocker, timeout=30) == "blocker"
    time.sleep(0.1)
    assert "victim" not in executed  # dead work was NOT executed

    delta = _delta_since(before)
    sheds = obs.sum_counter(delta, "ray_tpu_serve_shed_total", "reason",
                            deployment="ShedRouter")
    assert sheds.get("router", 0) >= 1
    statuses = obs.sum_counter(delta, "ray_tpu_serve_requests_total",
                               "status", deployment="ShedRouter")
    assert statuses.get("shed", 0) >= 1


def test_deadline_shed_at_batch_queue():
    """A batched request whose deadline expires while queued behind a
    slow batch is shed by the batch loop, not executed."""
    before = _snapshot()
    seen = []

    @serve.deployment(name="ShedBatch", max_concurrent_queries=32)
    class SlowBatch:
        @serve.batch(max_batch_size=2, batch_wait_timeout_s=0.01)
        def handle_batch(self, items):
            seen.extend(items)
            time.sleep(0.4)
            return [i for i in items]

        def __call__(self, x):
            return self.handle_batch(x)

    handle = serve.run(SlowBatch.bind())
    first = handle.remote("first")
    time.sleep(0.15)  # first batch is mid-execution (0.4s)
    victim = handle.options(deadline_s=0.1).remote("victim")
    with pytest.raises(Exception) as ei:
        ray_tpu.get(victim, timeout=30)
    assert "RequestShedError" in repr(ei.value) or "shed" in repr(ei.value)
    assert ray_tpu.get(first, timeout=30) == "first"
    time.sleep(0.1)
    assert "victim" not in seen

    delta = _delta_since(before)
    sheds = obs.sum_counter(delta, "ray_tpu_serve_shed_total", "reason",
                            deployment="ShedBatch")
    assert sheds.get("batch", 0) >= 1


def test_http_deadline_header_returns_503():
    import http.client

    @serve.deployment(name="Shed503", route_prefix="/shed503")
    def slow(payload):
        time.sleep(0.2)
        return {"ok": True}

    serve.run(slow.bind())
    port = serve.start_http_proxy()
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    conn.request("POST", "/shed503", body=b"null", headers={
        "Content-Type": "application/json",
        serve.DEADLINE_HEADER: "0",
    })
    resp = conn.getresponse()
    body = json.loads(resp.read())
    conn.close()
    assert resp.status == 503
    assert body.get("shed") == "router"


# -- probe exclusion + reconcile gauge --------------------------------------


def test_probes_excluded_from_metrics_and_traces():
    """Controller health probes / autoscaling / long-polls run every
    250ms — they must appear in NEITHER the request counters NOR the
    trace stream; the reconcile pass exports its duration gauge."""
    before = _snapshot()

    @serve.deployment(name="ProbeDep", num_replicas=2)
    def probed(x):
        return x

    handle = serve.run(probed.bind())
    for i in range(5):
        assert ray_tpu.get(handle.remote(i), timeout=30) == i

    tracing.enable()
    tracing.collect(clear=True)  # only spans from here on
    time.sleep(1.2)  # ~5 reconcile ticks of probes + long-polls
    spans = tracing.collect(clear=True)
    polluters = [s["name"] for s in spans
                 if any(k in s["name"] for k in (
                     "get_num_ongoing", "check_health",
                     "listen_for_change", "get_routing_table"))]
    assert polluters == [], polluters

    delta = _delta_since(before)
    statuses = obs.sum_counter(delta, "ray_tpu_serve_requests_total",
                               "status", deployment="ProbeDep")
    # EXACTLY the 5 user requests — probes counted nothing.
    assert statuses == {"ok": 5.0}
    parsed = _snapshot()
    assert parsed.get("ray_tpu_serve_reconcile_seconds"), \
        "reconcile duration gauge never exported"


# -- stats surfaces ---------------------------------------------------------


def test_serve_stats_and_cli(capsys):
    @serve.deployment(name="StatsDep", num_replicas=2)
    def stats_dep(x):
        time.sleep(0.002)
        return x

    handle = serve.run(stats_dep.bind())
    for i in range(4):
        ray_tpu.get(handle.remote(i), timeout=30)

    st = serve.stats()
    entry = st["deployments"]["StatsDep"]
    assert entry["replicas"] == 2
    assert entry["count"] >= 4
    assert entry["requests"]["ok"] >= 4
    assert entry["p50_ms"] is not None and entry["p99_ms"] is not None
    assert set(entry["phases"]) >= {"route", "queue_wait", "execute"}

    from ray_tpu.scripts import cli

    cli.main(["serve", "stats", "--window", "0", "--phases"])
    out = capsys.readouterr().out
    assert "StatsDep" in out and "p99" in out

    cli.main(["serve", "stats", "--window", "0", "--json"])
    out = capsys.readouterr().out
    assert json.loads(out)["deployments"]["StatsDep"]["replicas"] == 2


def test_grafana_dashboard_has_serve_panels():
    from ray_tpu.util.grafana import generate_dashboard

    titles = [p["title"] for p in generate_dashboard()["panels"]]
    for family in ("ray_tpu_serve_request_seconds",
                   "ray_tpu_serve_requests_total",
                   "ray_tpu_serve_shed_total",
                   "ray_tpu_serve_replica_ongoing"):
        assert any(family in t for t in titles), family


# -- evidence lint ----------------------------------------------------------


def test_handle_options_deadline_semantics():
    from ray_tpu.serve._private import DeploymentHandle

    h = DeploymentHandle("D")
    h5 = h.options(deadline_s=5.0)
    assert h5.deadline_s == 5.0 and h.deadline_s is None
    assert h5.options().deadline_s == 5.0  # omitted: inherited
    assert h5.options(deadline_s=None).deadline_s is None  # explicit: clears
    assert h5.method.deadline_s == 5.0  # method access preserves it
    # Round-trips through pickle (handles ride into replicas).
    import pickle

    assert pickle.loads(pickle.dumps(h5)).deadline_s == 5.0


def test_traceparent_helpers_roundtrip():
    ctx = {"trace_id": "ab" * 16, "span_id": "12" * 8}
    hdr = tracing.format_traceparent(ctx)
    assert hdr == f"00-{'ab' * 16}-{'12' * 8}-01"
    assert tracing.parse_traceparent(hdr) == ctx
    for bad in (None, "", "00-short-bad-01", "garbage",
                f"00-{'0' * 32}-{'12' * 8}-01",  # zero trace id
                f"00-{'zz' * 16}-{'12' * 8}-01"):  # non-hex
        assert tracing.parse_traceparent(bad) is None


# -- cross-check + cluster federation (these re-init the runtime: last) ----


def test_serve_bench_client_server_crosscheck():
    """Small in-process serve_bench run: the client-side latencies and
    the server-side histograms must agree (count exact, quantiles
    within bucket resolution)."""
    from ray_tpu.scripts import serve_bench

    res = serve_bench.run(mode="handle", connections=3,
                          requests_per_conn=6, sleep_ms=1.0,
                          shed_probes=2, trace_check=True)
    assert res["agreement"]["ok"], res["agreement"]
    assert res["client"]["count"] == 18
    assert res["server"]["count"] == 18
    assert res["shed"]["client_seen"] == 2
    assert res["trace"]["one_trace"]
    assert set(res["phases_observed"]) >= {
        "route", "queue_wait", "execute", "serialize", "total"}


def test_federation_one_scrape_and_dead_replica_pruned():
    """Cluster backend: serve observations ship over the worker-events
    plane into the agent registry, federate on ONE /metrics/cluster
    scrape, and a deleted deployment's replica gauges are retracted
    when its workers die."""
    from ray_tpu.cluster.cluster_utils import Cluster
    from ray_tpu.cluster.gcs_client import GcsClient

    ray_tpu.shutdown()
    c = Cluster()
    c.add_node(num_cpus=8)
    c.wait_for_nodes()
    ray_tpu.init(c.address)
    gcs = GcsClient(c.address)
    try:
        @serve.deployment(name="FedDep", num_replicas=2,
                          max_concurrent_queries=8)
        class Echo:
            def __call__(self, x):
                time.sleep(0.01)
                return x

        handle = serve.run(Echo.bind())
        refs = [handle.remote(i) for i in range(12)]
        assert sorted(ray_tpu.get(refs, timeout=120)) == list(range(12))

        # One scrape of the federated endpoint must carry the serve
        # series (worker flush 0.25s + agent apply: poll).
        deadline = time.monotonic() + 30
        dist = None
        parsed = {}
        while time.monotonic() < deadline:
            parsed = obs.parse_prometheus(gcs.metrics.cluster_text())
            dist = obs.histogram_dist(
                parsed, "ray_tpu_serve_request_seconds",
                deployment="FedDep", phase="total")
            if dist and dist["count"] >= 12:
                break
            time.sleep(0.5)
        assert dist and dist["count"] >= 12
        statuses = obs.sum_counter(
            parsed, "ray_tpu_serve_requests_total", "status",
            deployment="FedDep")
        assert statuses.get("ok", 0) >= 12

        def ongoing_series(p):
            return [labels for labels in
                    (p.get("ray_tpu_serve_replica_ongoing") or {})
                    if dict(labels).get("deployment") == "FedDep"]

        # Replica gauges present while the deployment lives...
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline and not ongoing_series(parsed):
            time.sleep(0.5)
            parsed = obs.parse_prometheus(gcs.metrics.cluster_text())
        assert ongoing_series(parsed)

        # ...and retracted once its replicas die.
        serve.delete("FedDep")
        deadline = time.monotonic() + 60
        leftover = ongoing_series(parsed)
        while time.monotonic() < deadline:
            parsed = obs.parse_prometheus(gcs.metrics.cluster_text())
            leftover = ongoing_series(parsed)
            if not leftover:
                break
            time.sleep(1.0)
        assert not leftover, f"dead replica series survived: {leftover}"
    finally:
        gcs.close()
        try:
            serve.shutdown()
        except Exception:
            pass
        ray_tpu.shutdown()
        c.shutdown()


@pytest.mark.slow
def test_serve_bench_smoke_slow():
    """Standing harness gate (test_scalebench_smoke pattern): the full
    serve_bench shape — HTTP mode, batching, sheds, trace check — runs
    end to end and the client/server cross-check holds."""
    from ray_tpu.scripts import serve_bench

    res = serve_bench.run(mode="http", connections=6,
                          requests_per_conn=15, sleep_ms=2.0,
                          batch=True, shed_probes=4, trace_check=True)
    assert res["agreement"]["ok"], res["agreement"]
    assert res["trace"]["one_trace"]
    assert "batch_wait" in res["phases_observed"]
    assert res["shed"]["client_seen"] == 4
