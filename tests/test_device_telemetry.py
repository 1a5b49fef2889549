"""Device-aware execution telemetry: per-task phase breakdown,
JAX/XLA device snapshots, remote profiler capture, and cluster-wide
metrics federation (one /metrics/cluster scrape covering every agent).

Local-backend tests run first (they re-init the backend per test); the
cluster tests share one module-scoped 2-node cluster and are defined
after, so the fixtures never fight over the process-wide backend.
"""

import json
import os
import signal
import sys
import time
import urllib.request

import cloudpickle
import pytest

import ray_tpu
from ray_tpu import state
from ray_tpu.util import device_telemetry, metrics

# Cluster workers unpickle test functions by value.
cloudpickle.register_pickle_by_value(sys.modules[__name__])


def _wait_for(cond, timeout=20.0, interval=0.2):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if cond():
            return True
        time.sleep(interval)
    return cond()


# -- unit: device_telemetry ------------------------------------------------


def test_snapshot_stub_without_jax(monkeypatch):
    monkeypatch.setattr(device_telemetry, "jax_loaded", lambda: False)
    snap = device_telemetry.snapshot()
    assert snap["available"] is False
    assert snap["devices"] == []
    assert "backend_compiles" in snap["compile"]


def test_snapshot_on_cpu_backend():
    """JAX_PLATFORMS=cpu: real devices, no memory stats, no crash."""
    snap = device_telemetry.snapshot(force=True)
    assert snap["available"] is True
    assert len(snap["devices"]) >= 1
    d = snap["devices"][0]
    assert {"id", "platform", "device_kind", "memory_stats"} <= set(d)
    # CPU backend reports no allocator stats — the stub contract.
    if d["platform"] == "cpu":
        assert d["memory_stats"] is False


def test_compile_counters_advance():
    import jax
    import jax.numpy as jnp

    device_telemetry.snapshot(force=True)  # installs the listeners
    before = device_telemetry.compile_counts()["backend_compiles"]
    shape = int(time.time() * 1000) % 1000 + 2  # always a fresh jit key
    jax.jit(lambda x: x * 3)(jnp.ones(shape)).block_until_ready()
    after = device_telemetry.compile_counts()["backend_compiles"]
    assert after > before


def test_capture_stack_fallback_forced(tmp_path):
    res = device_telemetry.capture(0.1, force_stack=True, worker_id="w-x")
    assert res["kind"] == "stack_sampler"
    assert "stack_trace.json" in res["files"]
    written = device_telemetry.write_capture(res, str(tmp_path))
    assert len(written) == len(res["files"])
    # An idle process may sample to an empty flame graph; the report
    # always has its header.
    assert os.path.getsize(
        str(tmp_path / "stack_report.txt")) > 0


def test_capture_jax_profiler_and_broken_profiler_fallback(monkeypatch):
    import jax

    res = device_telemetry.capture(0.1)
    assert res["kind"] == "jax_profiler"
    assert res["files"]  # trace dir shipped as {relpath: bytes}
    # jax present but its profiler broken: must degrade, not raise.
    monkeypatch.setattr(
        jax.profiler, "trace",
        lambda *a, **kw: (_ for _ in ()).throw(RuntimeError("no tpu")))
    res = device_telemetry.capture(0.1)
    assert res["kind"] == "stack_sampler"


# -- unit: grafana satellites --------------------------------------------


def test_merge_prometheus_series_identity():
    """The same series re-sampled to a DIFFERENT value between chunk
    renders (shared in-process registry) must keep one sample — dedup
    is by name+labels, not the whole line."""
    a = '# HELP m x\n# TYPE m gauge\nm{n="1"} 5.0\n'
    b = '# HELP m x\n# TYPE m gauge\nm{n="1"} 6.0\nm{n="2"} 7.0\n'
    merged = metrics.merge_prometheus([a, b])
    lines = [l for l in merged.splitlines() if l.startswith("m{")]
    assert lines == ['m{n="1"} 5.0', 'm{n="2"} 7.0']
    assert merged.count("# HELP m x") == 1


def test_grafana_panels_track_registry():
    """Every registered metric — including the new device gauges and
    the phase histogram — gets a panel whose query hits its exported
    series name; units/legends come from the metric itself."""
    from ray_tpu.util.grafana import generate_dashboard

    dash = generate_dashboard()
    exprs = [p["targets"][0]["expr"] for p in dash["panels"]]
    for m in metrics.registered():
        assert any(m.name in e for e in exprs), m.name
    by_expr = {p["targets"][0]["expr"]: p for p in dash["panels"]}
    dev = by_expr["ray_tpu_device_memory_bytes_in_use"]
    assert dev["fieldConfig"]["defaults"]["unit"] == "bytes"
    assert "{{device}}" in dev["targets"][0]["legendFormat"]
    phase = next(e for e in exprs if "ray_tpu_task_phase_seconds" in e)
    assert "histogram_quantile(0.99" in phase


# -- local backend ---------------------------------------------------------


@pytest.fixture()
def local():
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=4)
    yield
    ray_tpu.shutdown()


def test_local_phase_breakdown_and_summary(local):
    @ray_tpu.remote
    def work(x):
        time.sleep(0.005)
        return x

    @ray_tpu.remote
    class Probe:
        def ping(self):
            return "pong"

    ray_tpu.get([work.remote(i) for i in range(3)])
    probe = Probe.remote()
    ray_tpu.get(probe.ping.remote())

    def have_phases():
        recs = [r for r in state.list_tasks() if r.get("phases")]
        return (sum(1 for r in recs if r["name"] == "work") >= 3
                and any(r["name"] == "ping" for r in recs))

    assert _wait_for(have_phases), state.list_tasks()
    summary = state.summarize_tasks()
    for name in ("work", "ping"):
        phases = summary[name]["phases"]
        assert {"get_args", "execute", "put_outputs"} <= set(phases)
        assert phases["execute"]["p50_ms"] <= phases["execute"]["p99_ms"]
    # The task slice carries nested phase slices on its own track.
    events = state.timeline()
    parents = [e for e in events if e["name"] == "work"]
    assert parents
    tid = parents[0]["tid"]
    nested = [e for e in events
              if e["cat"] == "phase" and e["tid"] == tid]
    assert {"phase:get_args", "phase:execute", "phase:put_outputs"} <= {
        e["name"] for e in nested}
    lo, hi = parents[0]["ts"], parents[0]["ts"] + parents[0]["dur"]
    assert all(lo <= e["ts"] <= hi + 1000 for e in nested)


def test_local_timeline_merges_spans(local, tmp_path):
    from ray_tpu.util import tracing

    tracing.enable()
    try:
        @ray_tpu.remote
        def traced():
            return 1

        with tracing.span("driver-step"):
            ray_tpu.get(traced.remote())
        assert _wait_for(lambda: any(
            r["name"] == "traced" and r["start_time"] is not None
            for r in state.list_tasks()))
        out = tmp_path / "trace.json"
        state.timeline(str(out))
        events = json.loads(out.read_text())
        # ONE chrome trace holds the task slice, its phase slices, AND
        # the tracing span (satellite: no separate span export needed).
        assert any(e["name"] == "traced" and e["cat"] != "span"
                   for e in events)
        assert any(e["name"] == "driver-step" and e["cat"] == "span"
                   for e in events)
        assert any(e["cat"] == "phase" for e in events)
    finally:
        tracing.disable()
        tracing.collect(clear=True)


def test_local_cli_metrics_and_targets(local, capsys):
    from ray_tpu.scripts.cli import main

    main(["metrics"])
    out = capsys.readouterr().out
    assert "# TYPE ray_tpu_task_phase_seconds histogram" in out
    # Local backend exposes no scrape endpoint: targets must fail loud.
    with pytest.raises(SystemExit):
        main(["metrics", "--targets-json", "/tmp/_sd.json"])


# -- cluster ---------------------------------------------------------------


@pytest.fixture(scope="module")
def cluster():
    ray_tpu.shutdown()
    from ray_tpu.cluster.cluster_utils import Cluster

    c = Cluster()
    c.add_node(num_cpus=2)
    c.add_node(num_cpus=2)
    c.wait_for_nodes()
    ray_tpu.init(c.address)
    yield c
    ray_tpu.shutdown()
    c.shutdown()


def test_cluster_phase_breakdown(cluster):
    @ray_tpu.remote
    def crunch(x):
        time.sleep(0.005)
        return x + 1

    @ray_tpu.remote
    class Probe:
        def ping(self):
            return "pong"

    ray_tpu.get([crunch.remote(i) for i in range(4)])
    probe = Probe.remote()
    ray_tpu.get(probe.ping.remote())

    def have_phases():
        recs = [r for r in state.list_tasks() if r.get("phases")]
        return (sum(1 for r in recs if r["name"] == "crunch") >= 4
                and any(r["name"] == "ping" for r in recs))

    assert _wait_for(have_phases), [
        (r["name"], r.get("phases")) for r in state.list_tasks()]
    summary = state.summarize_tasks()
    for name in ("crunch", "ping"):  # plain task AND actor call
        phases = summary[name]["phases"]
        assert {"get_args", "execute", "put_outputs"} <= set(phases)
    assert summary["crunch"]["phases"]["execute"]["p50_ms"] >= 4.0
    events = state.timeline()
    assert any(e["cat"] == "phase" and e["name"] == "phase:execute"
               for e in events)


def test_cluster_timeline_merges_driver_and_worker_spans(cluster):
    """Cluster mode: one trace holds the DRIVER's submit/user spans
    (local buffer — they never reach the head) and the WORKER's run
    span (head store), so a request is followable end to end."""
    from ray_tpu.util import tracing

    tracing.enable()
    try:
        @ray_tpu.remote
        def spanned():
            return 1

        with tracing.span("driver-step"):
            ray_tpu.get(spanned.remote())

        def merged():
            names = {e["name"] for e in state.timeline()
                     if e["cat"] == "span"}
            return ("driver-step" in names
                    and "run:spanned" in names
                    and "submit:spanned" in names)

        assert _wait_for(merged, timeout=15.0), sorted(
            e["name"] for e in state.timeline() if e["cat"] == "span")
    finally:
        tracing.disable()
        tracing.collect(clear=True)


def test_cluster_metrics_federation(cluster):
    """GET /metrics/cluster on the head exposes worker, device, and
    phase series from every alive agent in ONE scrape; the file-SD
    document points at it."""
    from ray_tpu.cluster.gcs_client import GcsClient

    gcs = GcsClient(cluster.address)
    ep = gcs.metrics.endpoint()
    assert ep is not None and ep["cluster_path"] == "/metrics/cluster"
    url = f"http://{ep['address']}/metrics/cluster"
    node_ids = [n.node_id for n in cluster.nodes]

    def scrape():
        with urllib.request.urlopen(url, timeout=10) as resp:
            return resp.read().decode()

    def federated():
        body = scrape()
        return all(f'ray_tpu_device_count{{node_id="{nid}"}}' in body
                   for nid in node_ids) and \
            "ray_tpu_worker_cpu_percent" in body and \
            "ray_tpu_task_phase_seconds_bucket" in body

    assert _wait_for(federated, timeout=25.0), scrape()[:2000]
    # Exactly one HELP header per family after the merge.
    body = scrape()
    helps = [ln for ln in body.splitlines()
             if ln.startswith("# HELP ray_tpu_device_count ")]
    assert len(helps) == 1
    # The RPC surface serves the same body (CLI `ray-tpu metrics`).
    assert "ray_tpu_device_count" in gcs.metrics.cluster_text()
    # file-SD: one target, pointed at the cluster path.
    with urllib.request.urlopen(
            f"http://{ep['address']}/metrics/targets", timeout=10) as r:
        doc = json.loads(r.read().decode())
    assert doc[0]["targets"] == [ep["address"]]
    assert doc[0]["labels"]["__metrics_path__"] == "/metrics/cluster"
    gcs.close()


def test_dead_worker_pruned_from_federated_endpoint(cluster):
    """Series of a dead worker disappear from /metrics/cluster too,
    not just from the agent-local registry."""
    from ray_tpu.cluster.gcs_client import GcsClient

    @ray_tpu.remote
    def touch():
        return os.getpid()

    ray_tpu.get([touch.remote() for _ in range(4)])
    stats = state.worker_stats(fresh=True)
    victim = next(s for s in stats if not s["is_actor"])
    gcs = GcsClient(cluster.address)
    needle = f'worker_id="{victim["worker_id"]}"'
    assert _wait_for(
        lambda: needle in gcs.metrics.cluster_text(), timeout=15.0)
    os.kill(victim["pid"], signal.SIGKILL)
    assert _wait_for(
        lambda: needle not in gcs.metrics.cluster_text(), timeout=20.0), \
        "dead worker's series still federated"
    gcs.close()


def test_cluster_capture_profile_stack_fallback(cluster, tmp_path):
    """Workers import jax lazily; a worker that never touched jax must
    fall back to the stack sampler — files still stream back whole."""
    @ray_tpu.remote
    def busy():
        t0 = time.time()
        while time.time() - t0 < 0.5:
            sum(i * i for i in range(500))
        return "done"

    ref = busy.remote()
    stats = state.worker_stats(fresh=True)
    assert stats, "no live workers"
    wid = stats[0]["worker_id"]
    res = state.capture_profile(
        wid, duration_s=0.3, out_dir=str(tmp_path / "cap"))
    assert res["kind"] == "stack_sampler"  # jax.profiler unavailable
    assert res["worker_id"] == wid
    assert res["files"] and all(
        os.path.getsize(p) > 0 for p in res["files"])
    assert any(p.endswith("stack_trace.json") for p in res["files"])
    ray_tpu.get(ref)


def test_cluster_device_stats_stub(cluster):
    """JAX_PLATFORMS=cpu, workers never import jax: device_stats is a
    clean (possibly empty) stub list — no crashes anywhere in the
    worker → agent → head → state chain."""
    snaps = state.device_stats(fresh=True)
    assert isinstance(snaps, list)
    for snap in snaps:  # any reporting worker must carry the full shape
        assert {"available", "devices", "compile",
                "worker_id", "node_id"} <= set(snap)
