"""Continuous-batching LLM serving (PR 13): deadline-shed-mid-decode,
admission under a full batch, TTFT histogram exactness, the failpoints, and
token streaming through handle + HTTP + the ``ray://`` proxy. The rest of
what this file held until PR 65 is its neighbours': the cache contract
against an oracle (``test_llm_cache_oracle.py``), the engine's contract for
every family (``test_llm_engine_contract.py``, ``test_llm_steps_ahead.py``)
and the wake-ups and pollers (``test_llm_pollers.py``).

Test order matters (``-p no:randomly`` keeps definition order): the
cluster/ray:// test tears down the module's local runtime, so it runs
last.
"""

import json
import time

import pytest

import ray_tpu
from ray_tpu import serve
from ray_tpu.serve import _observability as obs
from ray_tpu.serve._observability import RequestShedError
from ray_tpu.serve.llm_engine import LLMEngine
from ray_tpu.util import failpoints
from llm_engine_helpers import (_clean_between_tests, _engine,
                                _engine_settings, _runtime, _snapshot)
from served_families import PROMPT


def test_deadline_shed_mid_decode_frees_slot():
    """A deadline dying mid-decode sheds TYPED (reason=decode) at the
    next step boundary, frees the slot, and the engine keeps serving."""
    before = _snapshot()
    eng = _engine(max_batch=2, max_new_tokens=500, max_new_cap=1000,
                  step_throttle_s=0.02)
    try:
        # both programs compiled: a first token is read a turn after its
        # chunks are dispatched, and a budget that dies before that read
        # (in a compile) sheds the request with no token at all
        eng.generate(PROMPT, 2)
        rid = eng.llm_submit(PROMPT, 500,
                             deadline_ts=time.time() + 0.3)
        got_tokens = 0
        deadline = time.monotonic() + 30.0
        shed = None
        while time.monotonic() < deadline:
            resp = eng.llm_next(rid, timeout_s=1.0)
            got_tokens += sum(len(c) for c in resp["chunks"])
            if resp["done"]:
                shed = resp["shed"]
                break
        assert shed == "decode"
        assert 0 < got_tokens < 500  # decoded some, then evicted
        st = eng.llm_stats()
        assert st["active"] == 0  # slot freed at the step boundary
        assert st["shed"] == 1
        # The slot is reusable: a fresh request completes.
        assert len(eng.generate(PROMPT, 4)) == 4
        delta = obs.diff_parsed(before, _snapshot())
        sheds = obs.sum_counter(delta, "ray_tpu_serve_shed_total",
                                "reason", deployment="llm")
        assert sheds.get("decode") == 1
    finally:
        eng.shutdown_engine()


def test_queued_deadline_shed_and_slack_admission():
    """A request whose budget dies IN the queue sheds typed without
    ever taking a slot; admission prefers tighter deadlines."""
    eng = _engine(max_batch=1, prefill_rows=1, max_new_tokens=50,
                  max_new_cap=100, step_throttle_s=0.01)
    try:
        # Occupy the only slot.
        busy = eng.llm_submit(PROMPT, 50)
        time.sleep(0.1)
        dead = eng.llm_submit(PROMPT, 4,
                              deadline_ts=time.time() + 0.05)
        time.sleep(0.3)  # budget dies while queued behind `busy`
        resp = eng.llm_next(dead, timeout_s=5.0)
        assert resp["done"] and resp["shed"] == "decode"
        # Drain the busy stream so teardown is clean.
        while not eng.llm_next(busy, timeout_s=2.0)["done"]:
            pass
    finally:
        eng.shutdown_engine()


def test_admission_full_queue_sheds_typed():
    eng = _engine(max_batch=1, max_queue=2, max_new_tokens=50,
                  max_new_cap=100, step_throttle_s=0.01)
    try:
        eng.llm_submit(PROMPT, 50)
        time.sleep(0.2)  # first request admitted to the slot
        eng.llm_submit(PROMPT, 50)
        eng.llm_submit(PROMPT, 50)
        with pytest.raises(RequestShedError) as ei:
            eng.llm_submit(PROMPT, 4)
        assert ei.value.reason == "decode"
    finally:
        eng.shutdown_engine()


def test_ttft_histogram_exact_counts():
    """Every admitted stream observes EXACTLY one TTFT sample, and the
    token counter matches the delivered tokens exactly."""
    before = _snapshot()
    eng = _engine(deployment="ttft_test")
    try:
        total = 0
        for i in range(5):
            total += len(eng.generate([i + 1, 3, 5], 4))
        delta = obs.diff_parsed(before, _snapshot())
        ttft = obs.histogram_dist(
            delta, "ray_tpu_serve_decode_ttft_seconds",
            deployment="ttft_test")
        assert ttft and int(ttft["count"]) == 5
        toks = obs.sum_counter(
            delta, "ray_tpu_serve_decode_tokens_total", "deployment",
            deployment="ttft_test")
        assert int(sum(toks.values())) == total == 20
        occ = obs.histogram_dist(
            delta, "ray_tpu_serve_decode_batch_occupancy",
            deployment="ttft_test")
        steps = obs.histogram_dist(
            delta, "ray_tpu_serve_decode_step_seconds",
            deployment="ttft_test")
        assert occ and steps and occ["count"] == steps["count"]
    finally:
        eng.shutdown_engine()


def test_failpoint_step_raise_fails_streams_fast():
    """A persistently raise-armed before_step trips the 3-strike
    fail-fast: active streams ERROR out quickly instead of waiting out
    the armed site — fail fast, never hang."""
    eng = _engine(max_new_tokens=50, max_new_cap=100,
                  step_throttle_s=0.01)
    try:
        rid = eng.llm_submit(PROMPT, 50)
        deadline = time.monotonic() + 30.0
        while eng.llm_stats()["active"] < 1 \
                and time.monotonic() < deadline:
            time.sleep(0.05)
        failpoints.arm("serve.llm.before_step", "raise")
        resp = {}
        deadline = time.monotonic() + 15.0
        while time.monotonic() < deadline:
            resp = eng.llm_next(rid, timeout_s=1.0)
            if resp["done"]:
                break
        assert resp.get("done"), "stream hung behind an armed failpoint"
        assert resp["error"], resp
        failpoints.reset()
        assert len(eng.generate(PROMPT, 3)) == 3  # engine recovered
    finally:
        failpoints.reset()
        eng.shutdown_engine()


def test_failpoint_admission_raise_recovers():
    """An armed serve.llm.before_admit raise interrupts the admission
    batch; the engine requeues and the stream still completes once the
    site disarms (raise,once) — crash the scheduler mid-iteration,
    never lose the request."""
    assert "serve.llm.before_admit" in failpoints.SITES
    assert "serve.llm.before_step" in failpoints.SITES
    eng = _engine()
    try:
        failpoints.arm("serve.llm.before_admit", "raise,once")
        assert len(eng.generate(PROMPT, 4)) == 4
        st = eng.llm_stats()
        assert st["completed"] == 1
    finally:
        failpoints.reset()
        eng.shutdown_engine()


# -- streaming transports ---------------------------------------------------


def _deploy_engine(**kw):
    # No explicit deployment= label: the engine must ADOPT the serve
    # deployment's name via Replica's set_deployment_name hook.
    eng = serve.deployment(name="llm", max_concurrent_queries=32,
                           route_prefix="/llm")(LLMEngine)
    return serve.run(eng.bind(**_engine_settings(**kw)))


def test_streaming_handle_and_http_local():
    """Order + completeness through the real transports: handle.stream
    chunks and chunked-HTTP ndjson both concatenate to exactly the
    blocking lane's tokens, and serve.stats() grows a decode section."""
    handle = _deploy_engine()
    want = ray_tpu.get(
        handle.remote({"tokens": PROMPT, "max_tokens": 5}), timeout=120)
    assert len(want["tokens"]) == 5

    chunks = list(handle.stream(PROMPT, 5))
    assert [t for ch in chunks for t in ch] == want["tokens"]
    assert all(len(ch) >= 1 for ch in chunks)  # per-step chunking

    port = serve.start_http_proxy()
    import http.client

    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
    try:
        body = json.dumps({"tokens": PROMPT, "max_tokens": 5}).encode()
        conn.request("POST", "/llm", body=body,
                     headers={"Content-Type": "application/json",
                              serve.STREAM_HEADER: "1"})
        resp = conn.getresponse()
        assert resp.status == 200
        assert resp.getheader("Transfer-Encoding") == "chunked"
        lines = []
        while True:
            line = resp.readline()
            if not line:
                break
            lines.append(json.loads(line))
        toks = [t for ln in lines if "tokens" in ln
                for t in ln["tokens"]]
        assert toks == want["tokens"]
        assert lines[-1].get("done") is True
        # Keep-alive survives a chunked response: a plain request on
        # the same connection still answers.
        conn.request("POST", "/llm", body=body,
                     headers={"Content-Type": "application/json"})
        r2 = conn.getresponse()
        assert r2.status == 200
        assert json.loads(r2.read())["tokens"] == want["tokens"]
    finally:
        conn.close()

    stats = serve.stats()
    decode = stats["deployments"]["llm"].get("decode")
    assert decode and decode["streams"] >= 2
    assert decode.get("tokens", 0) >= 15


def test_stream_deadline_shed_typed_through_handle():
    handle = _deploy_engine()
    with pytest.raises(RequestShedError):
        list(handle.options(deadline_s=0.0).stream(PROMPT, 4))


def test_blocking_lane_deadline_shed_mid_decode():
    """The BLOCKING lane (handle.remote -> __call__) inherits the serve
    request context's deadline: a budget dying mid-decode sheds typed
    and frees the slot, same as the streaming lane."""
    handle = _deploy_engine(max_new_tokens=500, max_new_cap=1000,
                            step_throttle_s=0.02)
    t0 = time.monotonic()
    with pytest.raises(Exception) as ei:
        ray_tpu.get(handle.options(deadline_s=0.6).remote(
            {"tokens": PROMPT, "max_tokens": 500}), timeout=120)
    assert "shed" in repr(ei.value).lower(), repr(ei.value)
    assert time.monotonic() - t0 < 60.0  # shed, not a 500-token wait


# -- cluster backend + ray:// proxy (runs LAST: tears down the module
# runtime) ------------------------------------------------------------------


def test_cluster_stream_and_ray_client_proxy():
    """Streaming order/completeness on the CLUSTER backend (replica in a
    worker process, events federate over the worker plane), then the
    same stream forwarded chunk-by-chunk through the ``ray://`` client
    proxy — including the zero-copy shm handoff lane for big prompts."""
    from ray_tpu.cluster import Cluster
    from ray_tpu.util.client import ClientProxyServer

    ray_tpu.shutdown()
    cluster = Cluster()
    cluster.add_node(num_cpus=4)
    cluster.wait_for_nodes()
    ray_tpu.init(cluster.address)
    proxy = None
    try:
        handle = _deploy_engine()
        want = ray_tpu.get(
            handle.remote({"tokens": PROMPT, "max_tokens": 5}),
            timeout=300)
        chunks = list(handle.stream(PROMPT, 5))
        assert [t for ch in chunks for t in ch] == want["tokens"]

        # TTFT federates from the replica worker to the cluster scrape.
        deadline = time.monotonic() + 30.0
        decode = {}
        while time.monotonic() < deadline:
            parsed = obs.parse_prometheus(obs.metrics_text())
            decode = obs.decode_stats(parsed, "llm")
            if decode.get("streams", 0) >= 2:
                break
            time.sleep(0.5)
        assert decode.get("streams", 0) >= 2, decode

        proxy = ClientProxyServer(cluster.address)
        ray_tpu.shutdown()
        ray_tpu.init(address=f"ray://{proxy.address}")
        h2 = serve.get_deployment_handle("llm")
        toks2 = [t for ch in h2.stream(PROMPT, 5) for t in ch]
        assert toks2 == want["tokens"]
        # Big prompt rides the shm store proxy->replica (the handoff
        # threshold), and the stream still completes in order.
        big = PROMPT + [1] * 600
        toks3 = [t for ch in h2.stream(big, 4) for t in ch]
        assert len(toks3) == 4
        # Typed shed crosses the RPC stream boundary.
        with pytest.raises(RequestShedError):
            list(h2.options(deadline_s=0.0).stream(PROMPT, 4))
    finally:
        try:
            ray_tpu.shutdown()
            ray_tpu.init(cluster.address)
            serve.shutdown()
        except Exception:
            pass
        ray_tpu.shutdown()
        if proxy is not None:
            proxy.shutdown()
        cluster.shutdown()
