"""Cluster signal plane (PR 16): metrics history ring retention and
eviction accounting, windowed queries (rate/delta/gauge/trend/quantile)
agreeing with a client-side ledger, the SLO grammar + burn-rate
hysteresis with pubsub events on both edges, and the RPC/CLI/dashboard
surfaces over a live cluster.

Unit tests drive ``MetricsRing``/``SignalPlane`` with synthetic
timestamps — zero sleeps, fully deterministic. The cluster tests run a
fast scrape cadence (50ms) so windowed queries converge in test time.
"""

import contextlib
import io
import json
import time
import urllib.request

import pytest

import ray_tpu
from ray_tpu.cluster import Cluster
from ray_tpu.cluster.signals import MetricsRing, SignalPlane, parse_slo
from ray_tpu.serve import _observability as obs
from ray_tpu.util import metrics


def _lbl(**kv):
    """Labels in the parser's shape: sorted (k, v) tuple."""
    return tuple(sorted(kv.items()))


def _hist(name, labels, by_le):
    """One histogram family snapshot (cumulative bucket counts) in the
    parser's {family: {labels: value}} shape."""
    out = {name + "_bucket": {}, name + "_count": {}, name + "_sum": {}}
    running = 0.0
    total_sum = 0.0
    for le, n in sorted(by_le.items()):
        running += n
        total_sum += n * (le if le != float("inf") else 0.0)
        le_s = "+Inf" if le == float("inf") else repr(le)
        out[name + "_bucket"][labels + (("le", le_s),)] = running
    out[name + "_count"][labels] = running
    out[name + "_sum"][labels] = total_sum
    return out


# -- ring: retention, eviction accounting, windowed counters ---------------


def test_ring_windowed_delta_and_rate_exact():
    ring = MetricsRing(history_s=100.0, scrape_interval_s=1.0)
    lbl = _lbl(node_id="n1", deployment="d")
    for t in range(11):  # counter grows 5/s
        ring.ingest(float(t), {"reqs_total": {lbl: 5.0 * t}})
    value, elapsed = ring.counter_delta("reqs_total", 10.0)
    assert value == 50.0 and elapsed == 10.0
    rate, _ = ring.rate("reqs_total", 10.0)
    assert rate == pytest.approx(5.0)
    # Narrower window: only the increases inside it.
    value, elapsed = ring.counter_delta("reqs_total", 4.0)
    assert value == 20.0 and elapsed == 4.0
    # Label match filters; unknown family answers empty, not raises.
    assert ring.counter_delta("reqs_total", 10.0,
                              match={"deployment": "x"})[0] == 0.0
    assert ring.counter_delta("nope_total", 10.0)[0] == 0.0


def test_ring_counter_reset_clamps_to_zero():
    """A restarted process's counter reset must not read as negative
    traffic (per-series deltas clamp at 0)."""
    ring = MetricsRing(history_s=100.0, scrape_interval_s=1.0)
    lbl = _lbl(node_id="n1")
    for t, v in enumerate([100.0, 120.0, 5.0, 10.0]):
        ring.ingest(float(t), {"reqs_total": {lbl: v}})
    value, _ = ring.counter_delta("reqs_total", 10.0)
    assert value == 0.0  # 10 - 100 clamped, never -90


def test_ring_parses_real_exposition_text():
    """ingest_text goes through the one shared parser — same series
    keys the scrape loop produces."""
    ring = MetricsRing(history_s=60.0, scrape_interval_s=1.0)
    for t in range(3):
        ring.ingest_text(float(t), (
            '# TYPE ray_tpu_worker_cpu_percent gauge\n'
            f'ray_tpu_worker_cpu_percent{{node_id="a",worker_id="w0"}}'
            f' {10.0 * t}\n'
            f'ray_tpu_worker_cpu_percent{{node_id="b",worker_id="w1"}}'
            f' {20.0 + t}\n'))
    per_node = ring.gauge_over_window(
        "ray_tpu_worker_cpu_percent", 60.0, "avg", group_by="node_id")
    assert per_node["a"] == pytest.approx(10.0)  # (0+10+20)/3
    assert per_node["b"] == pytest.approx(21.0)
    assert ring.gauge_over_window(
        "ray_tpu_worker_cpu_percent", 60.0, "max",
        match={"node_id": "a"}) == 20.0


def test_ring_retention_and_series_cap_evictions_counted():
    ring = MetricsRing(history_s=5.0, max_series=20,
                       scrape_interval_s=1.0)
    # Churning label values push past the cap: LRU series evicted and
    # counted — never a silent cap.
    for t in range(40):
        ring.ingest(float(t), {"g": {_lbl(worker_id=f"w{t}"): 1.0}})
    assert ring.series_count() <= 20
    assert ring.evictions["series_cap"] > 0 or \
        ring.evictions["stale"] > 0
    # Stale series (stopped reporting a full window ago) age out even
    # when the cap is never hit.
    ring2 = MetricsRing(history_s=5.0, scrape_interval_s=1.0)
    ring2.ingest(0.0, {"g": {_lbl(worker_id="old"): 1.0}})
    for t in range(1, 10):
        ring2.ingest(float(t), {"g": {_lbl(worker_id="new"): 1.0}})
    assert ring2.series_count() == 1
    assert ring2.evictions["stale"] == 1


def test_ring_memory_plateaus_past_retention_and_cap():
    """Bounded, not merely slow-growing: a many-node scrape ingested far
    past the retention window and over the series cap, 5 % of its
    series churning a label value each snapshot (restarting workers),
    holds no more traced memory at the end than 1.5x what it held once
    warm, and what it let go is counted by reason."""
    import tracemalloc

    nodes, per_node, max_series = 16, 40, 400
    ring = MetricsRing(history_s=10.0, max_series=max_series,
                       scrape_interval_s=0.5)

    def exposition(snap):
        return "\n".join(
            f'ray_tpu_worker_cpu_percent{{node_id="n{n:02d}",'
            f'worker_id="w{s}g{snap if s % 20 == 0 else 0}"}} '
            f'{float(snap + s)}'
            for n in range(nodes) for s in range(per_node))

    tracemalloc.start()
    try:
        for snap in range(120):  # 60 s of scrapes through a 10 s window
            ring.ingest_text(1_000_000.0 + 0.5 * snap, exposition(snap))
            if snap == 40:
                warm = tracemalloc.get_traced_memory()[0]
        end = tracemalloc.get_traced_memory()[0]
    finally:
        tracemalloc.stop()
    assert ring.series_count() <= max_series
    assert end < warm * 1.5, (warm, end)
    assert ring.evictions["series_cap"] > 0
    assert ring.age_out_node("n15") > 0


def test_ring_dead_node_age_out():
    ring = MetricsRing(history_s=60.0, scrape_interval_s=1.0)
    ring.ingest(0.0, {"g": {_lbl(node_id="a", w="1"): 1.0,
                            _lbl(node_id="a", w="2"): 2.0,
                            _lbl(node_id="b", w="3"): 3.0}})
    assert ring.age_out_node("a") == 2
    assert ring.evictions["dead_node"] == 2
    assert ring.series_count() == 1
    assert ring.gauge_over_window("g", 60.0, "last",
                                  group_by="node_id") == {"b": 3.0}


def test_ring_quantile_from_bucket_deltas_windowed():
    """The windowed quantile sees ONLY the window's observations: old
    traffic outside the window must not drag the estimate."""
    name = "ray_tpu_serve_decode_ttft_seconds"
    lbl = _lbl(deployment="d", node_id="n1")
    ring = MetricsRing(history_s=600.0, scrape_interval_s=1.0)
    les = {0.05: 0.0, 0.25: 0.0, 1.0: 0.0, float("inf"): 0.0}
    # ts 0..5: slow traffic (all observations in the (0.25, 1.0]
    # bucket).
    for t in range(6):
        les[1.0] = 10.0 * t
        ring.ingest(float(t), _hist(name, lbl, les))
    # ts 6..12: fast traffic only ((0, 0.05] bucket).
    for t in range(6, 13):
        les[0.05] = 20.0 * (t - 5)
        ring.ingest(float(t), _hist(name, lbl, les))
    # Full window: both phases; p50 lands in the fast bucket (140 fast
    # vs 50 slow), p99 in the slow one.
    res = ring.quantile_over_window(name, 0.5, 600.0)
    assert res is not None and res["value"] <= 0.05
    assert res["count"] == 190.0
    res99 = ring.quantile_over_window(name, 0.99, 600.0)
    assert 0.25 < res99["value"] <= 1.0
    # Window covering only the fast phase: slow buckets contribute no
    # delta — p99 is now fast too.
    res_fast = ring.quantile_over_window(name, 0.99, 6.0)
    assert res_fast["value"] <= 0.05
    # First in-window sample (ts=6) already counts 20: delta = 140-20.
    assert res_fast["count"] == 120.0
    # resolution_s is the bucket width at the estimate — the agreement
    # tolerance the bench asserts against.
    assert res_fast["resolution_s"] == pytest.approx(0.05)
    # No movement in window -> None (cold ring answers, not raises).
    assert ring.quantile_over_window(name, 0.5, 600.0,
                                     {"deployment": "x"}) is None


def test_ring_trend_and_gauge_last():
    ring = MetricsRing(history_s=600.0, scrape_interval_s=1.0)
    lbl = _lbl(node_id="n1")
    for t in range(11):  # gauge climbing 2/s
        ring.ingest(float(t), {"depth": {lbl: 2.0 * t}})
    tr = ring.trend("depth", 10.0)
    assert tr == pytest.approx(2.0, rel=0.3)
    assert ring.gauge_over_window("depth", 10.0, "last") == 20.0


# -- SLO grammar + burn-rate hysteresis ------------------------------------


def test_parse_slo_grammar():
    s = parse_slo('ttft_p50{deployment="d"} < 2s over 60s')
    assert s["signal"][0] == "quantile" and s["signal"][2] == 0.50
    assert s["match"] == {"deployment": "d"}
    assert s["threshold"] == 2.0 and s["window_s"] == 60.0
    assert parse_slo("shed_ratio < 1% over 300s")["threshold"] == 0.01
    assert parse_slo("ttft_p99 < 500ms")["threshold"] == 0.5
    assert parse_slo("ttft_p99 < 500ms")["window_s"] == 60.0  # default
    g = parse_slo("p95(ray_tpu_task_phase_seconds) < 0.5s over 120s")
    assert g["signal"] == ("quantile", "ray_tpu_task_phase_seconds",
                           0.95, {})
    r = parse_slo("rate(ray_tpu_oom_kills_total) < 1 over 300s")
    assert r["signal"][0] == "rate"
    for bad in ("", "ttft_p50", "nonsense_signal < 1s",
                "frobnicate(x) < 1s", "ttft_p50 ~ 2s"):
        with pytest.raises(ValueError):
            parse_slo(bad)


def _drive_plane(plane, name, lbl, les, t0, n, value_le, per_snap):
    """Advance a SignalPlane n snapshots, growing one histogram
    bucket."""
    t = t0
    for _ in range(n):
        les[value_le] += per_snap
        plane.ring.ingest(t, _hist(name, lbl, les))
        t += 1.0
    return t


def test_slo_burn_and_recovery_edges_exactly_once():
    """ok -> warning -> burning emits ONE burning event; recovery emits
    ONE ok event after the same hysteresis; warning wiggle stays off
    the event channel."""
    name = "ray_tpu_serve_decode_ttft_seconds"
    lbl = _lbl(deployment="d", node_id="n1")
    plane = SignalPlane(history_s=600.0, burn_evals=2)
    plane.register_slo("ttft", 'ttft_p50{deployment="d"} < 0.1s over 5s')
    les = {0.05: 0.0, 0.5: 0.0, float("inf"): 0.0}
    events = []
    t = _drive_plane(plane, name, lbl, les, 0.0, 2, 0.05, 10.0)
    events += plane.evaluate_slos(t)
    assert plane.slo_status()["slos"]["ttft"]["state"] == "ok"
    # Slow traffic: first breaching eval -> warning (no event), second
    # -> burning (one event).
    t = _drive_plane(plane, name, lbl, les, t, 6, 0.5, 50.0)
    events += plane.evaluate_slos(t - 1)
    assert plane.slo_status()["slos"]["ttft"]["state"] == "warning"
    assert events == []
    events += plane.evaluate_slos(t - 0.5)
    assert plane.slo_status()["slos"]["ttft"]["state"] == "burning"
    assert [e["state"] for e in events] == ["burning"]
    assert events[0]["prev"] == "warning"
    assert events[0]["threshold"] == 0.1
    # Fast traffic flushes the slow deltas out of the 5s window; two
    # clean evals recover -> exactly one ok event.
    t = _drive_plane(plane, name, lbl, les, t, 8, 0.05, 500.0)
    ok_events = []
    ok_events += plane.evaluate_slos(t - 1)
    ok_events += plane.evaluate_slos(t - 0.5)
    assert [e["state"] for e in ok_events] == ["ok"]
    assert ok_events[0]["prev"] == "burning"
    st = plane.slo_status()["slos"]["ttft"]
    assert st["state"] == "ok" and st["transitions"] == 3


def test_slo_holds_state_on_scrape_gap_no_flap():
    """A window with no samples evaluates to None: the state HOLDS and
    missed_evals counts it — the evaluator must not flap on gaps."""
    name = "ray_tpu_serve_decode_ttft_seconds"
    lbl = _lbl(deployment="d", node_id="n1")
    plane = SignalPlane(history_s=600.0, burn_evals=2)
    plane.register_slo("ttft", 'ttft_p50{deployment="d"} < 0.1s over 5s')
    les = {0.05: 0.0, 0.5: 0.0, float("inf"): 0.0}
    t = _drive_plane(plane, name, lbl, les, 0.0, 6, 0.5, 50.0)
    plane.evaluate_slos(t - 1)
    events = plane.evaluate_slos(t - 0.5)
    assert [e["state"] for e in events] == ["burning"]
    # Gap: snapshots keep arriving (flat counters) but nothing moves in
    # the window -> None -> hold burning, count the misses, no events.
    for _ in range(8):
        plane.ring.ingest(t, _hist(name, lbl, les))
        events = plane.evaluate_slos(t)
        assert events == []
        t += 1.0
    # Early gap evals still see the slow tail inside the 5s window
    # (value computed, still breaching); once it drains the evals go
    # None and are counted as misses — state held either way.
    st = plane.slo_status()["slos"]["ttft"]
    assert st["state"] == "burning" and st["missed_evals"] >= 1


def test_query_dispatch_answers_never_raises():
    plane = SignalPlane()
    assert plane.query({"op": "bogus"})["ok"] is False
    assert plane.query("not a dict")["ok"] is False
    res = plane.query({"op": "rate", "name": "nope", "window_s": 10})
    assert res["ok"] is True and res["value"] is None
    # remove_slo of an unknown name answers False, not raises.
    assert plane.remove_slo("ghost") is False


# -- registry sync: new families reach grafana/export ----------------------


def test_grafana_panels_cover_signal_families():
    """The generator is registry-driven: the ITL histogram, the head
    self-overhead families, and the SLO gauges each get a panel."""
    from ray_tpu.util.grafana import generate_dashboard

    exprs = [p["targets"][0]["expr"]
             for p in generate_dashboard()["panels"]]
    for fam in ("ray_tpu_serve_decode_itl_seconds",
                "ray_tpu_head_signal_scrape_seconds",
                "ray_tpu_head_signal_series",
                "ray_tpu_head_signal_evictions_total",
                "ray_tpu_slo_state", "ray_tpu_slo_value"):
        assert any(fam in e for e in exprs), fam


# -- live cluster: scrape loop, RPCs, pubsub edges, CLI, dashboard ---------


@pytest.fixture(scope="module")
def cluster():
    from ray_tpu.core.config import config

    overrides = {"signal_scrape_interval_s": 0.05,
                 "slo_eval_interval_s": 0.05,
                 "slo_burn_evals": 2}
    for k, v in overrides.items():
        config.override(k, v)
    ray_tpu.shutdown()
    c = Cluster()
    c.add_node(num_cpus=2)
    c.wait_for_nodes()
    ray_tpu.init(c.address)
    yield c
    ray_tpu.shutdown()
    c.shutdown()
    for k in overrides:
        config.reset(k)


def _wait(pred, timeout=15.0, interval=0.05):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        v = pred()
        if v:
            return v
        time.sleep(interval)
    return None


def test_windowed_queries_agree_with_client_ledger(cluster):
    """The acceptance agreement in miniature: seeded traffic through
    the real recorder -> head scrape -> ring; the windowed delta is
    count-exact and the windowed TTFT p50 matches the client percentile
    within the returned bucket resolution."""
    from ray_tpu import state

    # Warm the series into the ring at value 1: a windowed delta is
    # last - FIRST in-window sample, so the ring must hold a snapshot
    # of the counter's starting value for later deltas to be exact.
    obs.record_status("sigdep", "ok")
    obs.record_ttft("sigdep", 0.05)
    assert _wait(lambda: state.query_metrics(
        {"op": "series_delta", "name": "ray_tpu_serve_requests_total",
         "window_s": 300.0, "match": {"deployment": "sigdep"}})
        .get("series") and state.query_metrics(
        {"op": "series_delta",
         "name": "ray_tpu_serve_decode_ttft_seconds_count",
         "window_s": 300.0, "match": {"deployment": "sigdep"}})
        .get("series"))

    import random

    rng = random.Random(7)
    ledger = []
    for _ in range(120):
        v = rng.uniform(0.01, 0.2)
        obs.record_status("sigdep", "ok")
        obs.record_ttft("sigdep", v)
        ledger.append(v)
    # Ring catches up to the exact count: 121 total minus the warmup
    # sample the window's first snapshot already held.
    assert _wait(lambda: state.query_metrics(
        {"op": "delta", "name": "ray_tpu_serve_requests_total",
         "window_s": 300.0, "match": {"deployment": "sigdep"}})
        .get("value") == 120.0)
    q = state.query_metrics(
        {"op": "quantile", "name": "ray_tpu_serve_decode_ttft_seconds",
         "q": 0.5, "window_s": 300.0, "match": {"deployment": "sigdep"}})
    assert q["ok"] and q["value"] is not None
    client_p50 = sorted(ledger)[len(ledger) // 2]
    assert abs(q["value"] - client_p50) <= q["resolution_s"] + 1e-9
    # Self-overhead families export on the head's own scrape.
    text = metrics.prometheus_text()
    assert "ray_tpu_head_signal_series" in text
    assert "ray_tpu_head_signal_scrape_seconds_count" in text


def test_serve_stats_history_window_no_stall(cluster):
    """serve.stats(window_s) answers from the ring — wall time far
    under the window (the old implementation slept the whole window)."""
    from ray_tpu import serve

    obs.record_status("sigdep", "ok")
    time.sleep(0.2)  # let a scrape land (test cadence, not the path)
    t0 = time.monotonic()
    st = serve.stats(window_s=5.0, allow_sleep=False)
    wall = time.monotonic() - t0
    # The sleep fallback stalls the full window; the ring path is one
    # RPC.  Bound by the window, not an absolute: on a saturated
    # single-CPU box the RPC itself can take seconds, and the real
    # proof is allow_sleep=False + the windowed keys below (the
    # fallback is skipped entirely when sleeping is forbidden, so
    # "qps" can only come from the history ring).
    assert wall < 5.0, f"stats(window_s=5) slept the window ({wall:.2f}s)"
    assert "sigdep" in st["deployments"]
    assert "qps" in st["deployments"]["sigdep"]
    assert "window_count" in st["deployments"]["sigdep"]


def test_slo_burn_and_recovery_via_pubsub_and_cli(cluster):
    """End to end: register over RPC, burn with slow TTFT, recover with
    fast TTFT; pubsub delivers exactly one burning and one ok event
    (SLO channel is NOT coalesced); CLI renders both surfaces."""
    from ray_tpu import state
    from ray_tpu.cluster.gcs_client import GcsClient
    from ray_tpu.scripts import cli

    gcs = GcsClient(cluster.address)
    gcs.pubsub.subscribe("t-slo", "SLO")
    try:
        bad = state.register_slo("t-burn", "definitely not a grammar")
        assert bad["ok"] is False
        reg = state.register_slo(
            "t-burn", 'ttft_p50{deployment="burndep"} < 50ms over 1s')
        assert reg["ok"] and reg["slo"]["state"] == "ok"

        events = []

        def drain(until_state, deadline_s=15.0):
            def step():
                res = gcs.pubsub.poll("t-slo", timeout=0.2)
                for m in (res[0] if res else []):
                    ev = m.get("data") or {}
                    if ev.get("slo") == "t-burn":
                        events.append(ev)
                return any(e["state"] == until_state for e in events)
            return _wait(step, timeout=deadline_s)

        def pump(value):
            obs.record_status("burndep", "ok")
            obs.record_ttft("burndep", value)

        # Slow TTFT until the burn edge fires.
        deadline = time.monotonic() + 15.0
        burned = False
        while time.monotonic() < deadline and not burned:
            pump(0.5)
            burned = bool(drain("burning", deadline_s=0.2))
        assert burned, "burning event never arrived"
        # Fast TTFT flushes the window; recovery edge fires once.
        deadline = time.monotonic() + 20.0
        recovered = False
        while time.monotonic() < deadline and not recovered:
            for _ in range(20):
                pump(0.005)
            recovered = bool(drain("ok", deadline_s=0.3))
        assert recovered, "recovery event never arrived"
        assert [e["state"] for e in events] == ["burning", "ok"], events
        st = state.slo_status()
        assert st["ok"] and st["slos"]["t-burn"]["state"] == "ok"

        # CLI surfaces: `ray-tpu slo --json` and `ray-tpu top` read the
        # same head (same-address init is idempotent).
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            cli.main(["--address", cluster.address, "slo", "--json"])
        view = json.loads(buf.getvalue())
        assert view["slos"]["t-burn"]["state"] == "ok"
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            cli.main(["--address", cluster.address, "top",
                      "--window", "300"])
        out = buf.getvalue()
        assert "series" in out and "burndep" in out
    finally:
        state.remove_slo("t-burn")
        gcs.pubsub.unsubscribe("t-slo")


def test_dashboard_signals_and_windowed_serve_stats(cluster):
    """/api/signals answers SLO + top from the ring; /api/serve_stats
    honors ?window= without stalling the single-threaded server."""
    from ray_tpu.dashboard import Dashboard

    dash = Dashboard(cluster.address, port=0)
    try:
        t0 = time.monotonic()
        with urllib.request.urlopen(
                dash.url + "/api/signals?window=60", timeout=10) as r:
            sig = json.loads(r.read())
        with urllib.request.urlopen(
                dash.url + "/api/serve_stats?window=30", timeout=10) as r:
            st = json.loads(r.read())
        wall = time.monotonic() - t0
        assert wall < 5.0, f"dashboard stalled {wall:.2f}s"
        assert sig["slo"]["ok"] and sig["top"]["ok"]
        assert sig["top"]["series"] > 0
        assert "deployments" in st
        with urllib.request.urlopen(
                dash.url + "/api/signals?op=rate&name="
                "ray_tpu_serve_requests_total&window=300", timeout=10) \
                as r:
            q = json.loads(r.read())
        assert q["ok"] and q["value"] is not None
    finally:
        dash.shutdown()
