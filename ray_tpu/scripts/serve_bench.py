"""Serve load harness: N concurrent streams + client/server cross-check.

Drives ``--connections`` concurrent client streams (each a thread
issuing sequential requests) through a deployment — over the HTTP proxy
by default, or the handle path — and records client-side p50/p99/QPS.
Then it reads the server-side ``ray_tpu_serve_request_seconds``
histograms back from the metrics plane and REQUIRES the two views to
agree: exact request-count match, and p50/p99/mean agreement within the
histogram's bucket resolution. If client and server disagree, the
metrics are lying (a phase is unobserved, double-counted, or
mis-tagged) and the bench exits non-zero — the latency plane itself is
under test, not just the deployment.

Also exercised per run: deadline sheds (requests sent with an
already-expired budget must come back 503/shed and land in
``ray_tpu_serve_shed_total``) and — when tracing — one end-to-end
traced request whose ingress/route/replica spans must share a trace id.

What it checks is machine-independent (counts, agreement booleans,
phases observed); the latencies it prints ride along for context only
and are written nowhere.

Run: python -m ray_tpu.scripts.serve_bench
     [--mode http|handle] [--connections 8] [--requests 25] [--cluster]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

DEPLOYMENT = "serve_bench_echo"


class _Stream:
    """One persistent client connection (HTTP keep-alive — the shape of
    a real load client; a fresh TCP handshake per request would measure
    the OS, not the serving path). ``post`` returns (status, body) for
    ANY status — a 503 shed is data here, not an exception."""

    def __init__(self, port: int):
        import http.client

        self._conn = http.client.HTTPConnection(
            "127.0.0.1", port, timeout=60.0)

    def post(self, path: str, payload, headers=None):
        body = json.dumps(payload).encode()
        self._conn.request(
            "POST", path, body=body,
            headers={"Content-Type": "application/json",
                     **(headers or {})})
        resp = self._conn.getresponse()
        data = resp.read()
        return resp.status, (json.loads(data) if data else None)

    def close(self):
        try:
            self._conn.close()
        except Exception:
            pass


def _percentile_ms(vals_s, q):
    from ray_tpu.util.metrics import percentile

    return round(percentile(sorted(vals_s), q) * 1e3, 3)


def run(mode: str = "http", connections: int = 8,
        requests_per_conn: int = 25, sleep_ms: float = 2.0,
        batch: bool = False, shed_probes: int = 4,
        cluster: bool = False, trace_check: bool = True) -> dict:
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve import _observability as obs
    from ray_tpu.util import tracing

    ray_tpu.shutdown()
    cluster_obj = None
    prev_trace_env = os.environ.get("RAY_TPU_TRACING_ENABLED")
    if trace_check:
        # Operator opt-in BEFORE the cluster spawns: worker processes
        # (proxy, routers, replicas) read the env at import — an
        # unauthenticated traceparent header alone no longer enables
        # tracing server-side.
        os.environ["RAY_TPU_TRACING_ENABLED"] = "1"
    if cluster:
        from ray_tpu.cluster.cluster_utils import Cluster

        cluster_obj = Cluster()
        cluster_obj.add_node(num_cpus=8)
        cluster_obj.wait_for_nodes()
        ray_tpu.init(cluster_obj.address)
    else:
        ray_tpu.init(num_cpus=max(8, connections))

    sleep_s = sleep_ms / 1e3

    if batch:
        @serve.deployment(name=DEPLOYMENT, num_replicas=2,
                          max_concurrent_queries=64,
                          route_prefix="/bench")
        class Echo:  # noqa: F811 — bench-local deployment
            @serve.batch(max_batch_size=8, batch_wait_timeout_s=0.005)
            def handle(self, items):
                time.sleep(sleep_s)
                return [{"x": i.get("x") if isinstance(i, dict) else i}
                        for i in items]

            def __call__(self, payload):
                return self.handle(payload)
    else:
        @serve.deployment(name=DEPLOYMENT, num_replicas=2,
                          max_concurrent_queries=64,
                          route_prefix="/bench")
        class Echo:
            def __call__(self, payload):
                time.sleep(sleep_s)
                return {"x": payload.get("x")
                        if isinstance(payload, dict) else payload}

    try:
        handle = serve.run(Echo.bind())
        port = serve.start_http_proxy() if mode == "http" else None
        before = obs.parse_prometheus(obs.metrics_text())

        latencies: list = []
        errors: list = []
        lat_lock = threading.Lock()

        def stream(conn_id: int):
            conn = _Stream(port) if mode == "http" else None
            try:
                for i in range(requests_per_conn):
                    t0 = time.perf_counter()
                    try:
                        if mode == "http":
                            status, body = conn.post(
                                "/bench", {"x": conn_id * 1000 + i})
                            ok = (status == 200
                                  and body.get("x") == conn_id * 1000 + i)
                        else:
                            out = ray_tpu.get(
                                handle.remote({"x": conn_id * 1000 + i}),
                                timeout=60.0)
                            ok = out.get("x") == conn_id * 1000 + i
                        dt = time.perf_counter() - t0
                        with lat_lock:
                            if ok:
                                latencies.append(dt)
                            else:
                                errors.append("wrong result")
                    except Exception as e:  # noqa: BLE001
                        with lat_lock:
                            errors.append(repr(e))
            finally:
                if conn is not None:
                    conn.close()

        t_start = time.perf_counter()
        threads = [threading.Thread(target=stream, args=(c,))
                   for c in range(connections)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall_s = time.perf_counter() - t_start

        # Server side: wait for the stream observations to settle (the
        # cluster backend ships them over the 0.25s worker-event
        # cadence), then diff against the pre-run snapshot so ONLY the
        # streams' requests enter the cross-check — the shed and trace
        # probes below come after this window on purpose.
        n_ok = len(latencies)
        delta = None
        after = before
        deadline = time.monotonic() + 20.0
        while time.monotonic() < deadline:
            after = obs.parse_prometheus(obs.metrics_text())
            delta = obs.diff_parsed(before, after)
            dist = obs.histogram_dist(
                delta, "ray_tpu_serve_request_seconds",
                deployment=DEPLOYMENT, phase="total")
            if dist and dist["count"] >= n_ok:
                break
            time.sleep(0.25)

        dist = obs.histogram_dist(delta, "ray_tpu_serve_request_seconds",
                                  deployment=DEPLOYMENT, phase="total")
        statuses = obs.sum_counter(delta, "ray_tpu_serve_requests_total",
                                   "status", deployment=DEPLOYMENT)
        phases_observed = sorted(
            p for p in obs.PHASES
            if obs.histogram_dist(delta, "ray_tpu_serve_request_seconds",
                                  deployment=DEPLOYMENT, phase=p))

        # Deadline sheds: an already-expired budget must come back as a
        # clean 503/shed, never execute, and count in the shed family.
        shed_seen = 0
        probe_conn = _Stream(port) if mode == "http" else None
        for _ in range(shed_probes):
            try:
                if mode == "http":
                    status, body = probe_conn.post(
                        "/bench", {"x": 1},
                        headers={serve.DEADLINE_HEADER: "0"})
                    if status == 503:
                        shed_seen += 1
                else:
                    ray_tpu.get(
                        handle.options(deadline_s=0.0).remote({"x": 1}),
                        timeout=60.0)
            except Exception as e:  # noqa: BLE001 — handle path sheds
                if "RequestShedError" in repr(e) or "shed" in repr(e):
                    shed_seen += 1
        sheds = {}
        if shed_probes:
            shed_deadline = time.monotonic() + 20.0
            while time.monotonic() < shed_deadline:
                shed_delta = obs.diff_parsed(
                    after, obs.parse_prometheus(obs.metrics_text()))
                sheds = obs.sum_counter(
                    shed_delta, "ray_tpu_serve_shed_total", "reason",
                    deployment=DEPLOYMENT)
                if sum(sheds.values()) >= shed_seen:
                    break
                time.sleep(0.25)

        # One traced request: ingress -> route -> replica must share a
        # trace id (the end-to-end propagation claim, checked live).
        trace = {}
        if trace_check:
            tracing.enable()
            trace_id = None
            if mode == "http":
                want = "aa" * 16
                if probe_conn is not None:
                    probe_conn.post(
                        "/bench", {"x": 0},
                        headers={"traceparent":
                                 f"00-{want}-{'bb' * 8}-01"})
                trace_id = want
            else:
                with tracing.span("serve_bench.client") as s:
                    ray_tpu.get(handle.remote({"x": 0}), timeout=60.0)
                    trace_id = s["trace_id"]
            deadline = time.monotonic() + 15.0
            names: set = set()
            while time.monotonic() < deadline:
                spans = [s for s in _collect_spans(ray_tpu)
                         if s["trace_id"] == trace_id
                         and s.get("cat") == "serve"]
                names = {s["name"].split(":")[0] for s in spans}
                want_names = {"serve.route", "serve.replica"} | (
                    {"serve.http"} if mode == "http" else set())
                if want_names <= names:
                    break
                time.sleep(0.25)
            trace = {"trace_id": trace_id,
                     "span_kinds": sorted(names),
                     "one_trace": {"serve.route", "serve.replica"}
                     <= names}
        if probe_conn is not None:
            probe_conn.close()

        client = {
            "count": n_ok,
            "errors": len(errors),
            "p50_ms": _percentile_ms(latencies, 0.50) if latencies else None,
            "p99_ms": _percentile_ms(latencies, 0.99) if latencies else None,
            "mean_ms": round(sum(latencies) / n_ok * 1e3, 3)
            if n_ok else None,
            "qps": round((n_ok + len(errors)) / wall_s, 1),
        }
        server = {"count": int(dist["count"]) if dist else 0}
        if dist:
            server["mean_ms"] = round(dist["sum"] / dist["count"] * 1e3, 3)
            for q, key in ((0.50, "p50_ms"), (0.99, "p99_ms")):
                v = obs.quantile_from_buckets(dist, q)
                server[key] = round(v * 1e3, 3) if v is not None else None

        # Client latency = server-observed total + ingress overhead the
        # server cannot see (HTTP parse, event-loop scheduling, the
        # executor hop). That overhead is ~constant per request, so it
        # is measured from the means and subtracted before comparing
        # quantile SHAPES; the server claiming MORE time than the
        # client saw, or a count mismatch, is unconditionally lying.
        ingress_ms = 0.0
        if client["mean_ms"] is not None and "mean_ms" in server:
            ingress_ms = max(0.0, client["mean_ms"] - server["mean_ms"])

        def within(client_ms, server_ms):
            """Histogram agreement: a bucket estimate can only be as
            precise as the bucket the sample fell in."""
            if client_ms is None or server_ms is None or not dist:
                return False
            tol_ms = max(
                obs.bucket_width_at(dist, client_ms / 1e3) * 1e3,
                0.35 * client_ms, 5.0)
            return abs((client_ms - ingress_ms) - server_ms) <= tol_ms

        agreement = {
            "count_exact": server["count"] == n_ok,
            "p50_within_tol": within(client["p50_ms"],
                                     server.get("p50_ms")),
            "p99_within_tol": within(client["p99_ms"],
                                     server.get("p99_ms")),
            "server_not_exceeding": (
                "mean_ms" in server and client["mean_ms"] is not None
                and server["mean_ms"]
                <= client["mean_ms"] * 1.1 + 5.0),
            "status_ok_match": int(statuses.get("ok", 0)) == n_ok,
            "shed_counted": (shed_probes == 0
                             or sum(sheds.values()) >= shed_seen > 0),
        }
        agreement["ok"] = all(agreement.values())
        client["ingress_overhead_ms"] = round(ingress_ms, 3)

        result = {
            "mode": mode,
            "backend": "cluster" if cluster else "local",
            "connections": connections,
            "requests_per_conn": requests_per_conn,
            "batch": batch,
            "client": client,
            "server": server,
            "statuses": {k: int(v) for k, v in statuses.items()},
            "shed": {"probes": shed_probes, "client_seen": shed_seen,
                     "server_counted": {k: int(v)
                                        for k, v in sheds.items()}},
            "phases_observed": phases_observed,
            "agreement": agreement,
        }
        if trace:
            result["trace"] = trace
        return result
    finally:
        try:
            serve.shutdown()
        except Exception:
            pass
        ray_tpu.shutdown()
        if cluster_obj is not None:
            cluster_obj.shutdown()
        if trace_check:
            if prev_trace_env is None:
                os.environ.pop("RAY_TPU_TRACING_ENABLED", None)
            else:
                os.environ["RAY_TPU_TRACING_ENABLED"] = prev_trace_env


def _collect_spans(ray_tpu):
    """This process's spans + the backend's span store (cluster: spans
    ship over the worker-events plane to the head)."""
    from ray_tpu._private import worker as _worker
    from ray_tpu.util import tracing

    spans = {s["span_id"]: s for s in tracing.collect()}
    try:
        backend = _worker.backend()
        if hasattr(backend, "list_spans"):
            for s in backend.list_spans():
                spans.setdefault(s["span_id"], s)
    except Exception:
        pass
    return list(spans.values())


def main() -> None:
    ap = argparse.ArgumentParser(
        description="Serve concurrent-stream load harness with "
                    "client/server latency cross-check")
    ap.add_argument("--mode", choices=["http", "handle"], default="http")
    ap.add_argument("--connections", type=int, default=8)
    ap.add_argument("--requests", type=int, default=25)
    ap.add_argument("--sleep-ms", type=float, default=2.0)
    ap.add_argument("--batch", action="store_true",
                    help="serve through a @serve.batch deployment "
                         "(exercises the batch_wait phase + batch shed)")
    ap.add_argument("--cluster", action="store_true",
                    help="run against a real multiprocess cluster "
                         "backend (events ship over the worker plane)")
    args = ap.parse_args()

    res = run(mode=args.mode, connections=args.connections,
              requests_per_conn=args.requests, sleep_ms=args.sleep_ms,
              batch=args.batch, cluster=args.cluster)
    print(json.dumps(res, indent=1, default=str))
    if not res["agreement"]["ok"]:
        print("serve_bench: CLIENT/SERVER DISAGREE — the serve metrics "
              "are lying; see 'agreement'", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
