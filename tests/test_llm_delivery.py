"""The token's way out of the engine (PR 41): the delivery counters of
``llm_stats()`` exact for scripted runs, ``held_ns`` beside a poll's
chunks, the poll tally on the ``serve.stream`` span of a traced stream
(and no span for an untraced one), and the pollers' ``llm.next.drain``
annotations in a profile, on the caller's thread and never over its wait.

Profiles here are taken on the CPU: they show that the annotations exist
and where. They say nothing about a device.
"""

import threading
import time

import pytest

import ray_tpu
from ray_tpu import serve
from ray_tpu.serve.llm_engine import (DELIVER_LAG_EDGES_MS, LLMEngine,
                                      _Stream)
from ray_tpu.util import failpoints, tracing

from test_device_spans import CTX, TINY, _engine, _profiled

DELIVERY = ("next_calls", "next_empty", "deliver_chunks", "deliver_lag_ns",
            "deliver_lag_hist", "wake_defer_ns")
TALLY = {"polls", "rpc_ns", "held_ns"}


@pytest.fixture(autouse=True, scope="module")
def _runtime():
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=8)
    yield
    try:
        if ray_tpu.is_initialized():
            serve.shutdown()
    except Exception:
        pass
    ray_tpu.shutdown()


@pytest.fixture(autouse=True)
def _clean():
    tracing.disable()
    tracing.drain()
    yield
    tracing.disable()
    tracing.drain()
    failpoints.reset()
    try:
        if ray_tpu.is_initialized():
            serve.shutdown()
    except Exception:
        pass


def _deploy(**kw):
    kw = {"model": "gpt2", "config": TINY, "max_batch": 4, "cache_len": 32,
          "max_prompt_len": 8, "max_new_tokens": 6, "max_new_cap": 64, **kw}
    eng = serve.deployment(name="llm", max_concurrent_queries=32)(LLMEngine)
    return serve.run(eng.bind(**kw))


def _stats(handle):
    return ray_tpu.get(handle.llm_stats.remote(), timeout=60)


def _delta(after, before):
    out = {k: after[k] - before[k] for k in DELIVERY
           if k != "deliver_lag_hist"}
    out["deliver_lag_hist"] = [b - a for a, b in zip(
        before["deliver_lag_hist"], after["deliver_lag_hist"])]
    return out


# -- counters -------------------------------------------------------------------


def test_counters_are_exact_for_streams_through_the_handle():
    """Six streams of 5 to 10 tokens over four slots, a client thread
    each: every chunk a client got was drained once and counted once, by
    the long-poll lane alone."""
    handle = _deploy()
    ray_tpu.get(handle.remote({"tokens": [5, 9, 2], "max_tokens": 2}),
                timeout=120)                     # both programs compiled
    before = _stats(handle)
    assert set(DELIVERY) <= set(before)
    asked = {i: ([i + 1, 7, 11], 5 + i) for i in range(6)}
    got, errors = {}, []

    def client(i):
        try:
            got[i] = list(handle.stream(*asked[i]))
        except BaseException as e:  # noqa: BLE001
            errors.append(repr(e))

    threads = [threading.Thread(target=client, args=(i,)) for i in asked]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not errors and not any(t.is_alive() for t in threads), errors
    d = _delta(_stats(handle), before)
    chunks = sum(len(g) for g in got.values())
    assert chunks == sum(n for _, n in asked.values())   # a token a chunk
    assert d["deliver_chunks"] == chunks
    # a poll is empty, or it took at least one chunk
    assert d["next_calls"] - d["next_empty"] <= d["deliver_chunks"]
    assert d["next_calls"] >= len(asked)
    # what the loop put off is part of what a chunk waited
    assert d["deliver_lag_ns"] >= d["wake_defer_ns"] >= 0
    assert d["deliver_lag_ns"] > 0
    assert len(d["deliver_lag_hist"]) == len(DELIVER_LAG_EDGES_MS) + 1 == 8
    assert sum(d["deliver_lag_hist"]) == chunks
    assert all(n >= 0 for n in d["deliver_lag_hist"])


def test_the_batched_lane_counts_its_chunks_and_no_long_poll():
    eng = _engine(max_batch=2)
    try:
        eng.generate([1, 2, 3], 2)
        before = eng.llm_stats()
        rids = eng.llm_submit_many([
            {"tokens": [1, 2, 3], "max_tokens": 4},
            {"tokens": [4, 5, 6, 7], "max_tokens": 6}])
        out = {rid: [] for rid in rids}
        live = set(rids)
        deadline = time.monotonic() + 60
        while live and time.monotonic() < deadline:
            for rid, resp in eng.llm_poll(sorted(live)).items():
                out[rid].extend(t for ch in resp["chunks"] for t in ch)
                assert "held_ns" not in resp     # the long poll's alone
                if resp["done"]:
                    live.discard(rid)
            time.sleep(0.001)
        d = _delta(eng.llm_stats(), before)
    finally:
        eng.shutdown_engine()
    assert [len(out[rid]) for rid in rids] == [4, 6]
    assert d["deliver_chunks"] == 10 == sum(d["deliver_lag_hist"])
    assert d["deliver_lag_ns"] > 0
    assert d["next_calls"] == d["next_empty"] == 0


@pytest.mark.parametrize("lag_ms, bucket", [
    (0.0, 0), (0.249, 0), (0.25, 1), (0.499, 1), (0.5, 2), (0.99, 2),
    (1.0, 3), (3.9, 4), (4.0, 5), (8.0, 6), (15.9, 6), (16.0, 7),
    (900.0, 7)])
def test_a_lag_goes_to_the_bucket_its_edges_name(lag_ms, bucket):
    """Two chunks of a stream of the test's own, the older made visible
    ``lag_ms`` before their drain (each is charged the older one's lag):
    the buckets are < 0.25, 0.5, 1, 2, 4, 8, 16 and >= 16 ms."""
    eng = _engine(max_batch=2)
    try:
        before = eng.llm_stats()
        st = _Stream()
        st.pending = [[7], [8]]
        with eng._lock:
            eng._streams["mine"] = st
            now = time.perf_counter_ns()
            st.visible_ns = now - int(lag_ms * 1e6)
            resp = eng._drain_locked("mine", st, now)
        d = _delta(eng.llm_stats(), before)
    finally:
        eng.shutdown_engine()
    assert resp["chunks"] == [[7], [8]] and st.last_poll == now
    want = [0] * 8
    want[bucket] = 2
    assert d["deliver_lag_hist"] == want
    assert d["deliver_chunks"] == 2
    assert d["deliver_lag_ns"] == 2 * int(lag_ms * 1e6)
    assert sorted(DELIVER_LAG_EDGES_MS) == list(DELIVER_LAG_EDGES_MS)
    assert (bucket == 7) or lag_ms < DELIVER_LAG_EDGES_MS[bucket]
    assert (bucket == 0) or lag_ms >= DELIVER_LAG_EDGES_MS[bucket - 1]


def test_no_snapshot_reads_the_two_wake_counters_a_step_apart():
    """``wakes_deferred`` and ``wakes_after_dispatch`` are written together
    where the wake-ups are set: however a snapshot falls between a step's
    fan-out and the next enqueue (stretched here), it reads them equal in
    a run whose every wake-up follows an enqueue."""
    eng = _engine(max_batch=2, max_new_cap=64)
    seen, stop = [], threading.Event()

    def watch():
        while not stop.is_set():
            st = eng.llm_stats()
            seen.append((st["wakes_deferred"], st["wakes_after_dispatch"]))

    try:
        eng.generate([1, 2, 3], 2)
        real = eng._step_fn

        def slow(*a):
            time.sleep(0.002)        # fan-out ... enqueue: the old gap
            return real(*a)

        eng._step_fn = slow
        watcher = threading.Thread(target=watch)
        watcher.start()
        rids = [eng.llm_submit([i + 2, 5, 8], 30) for i in (0, 1)]
        for rid in rids:
            while not eng.llm_next(rid, timeout_s=5.0)["done"]:
                pass
        stop.set()
        watcher.join(timeout=30)
        st = eng.llm_stats()
    finally:
        stop.set()
        eng.shutdown_engine()
    assert len(seen) > 20
    assert all(a == b for a, b in seen), [s for s in seen if s[0] != s[1]][:5]
    assert st["wakes_deferred"] == st["wakes_after_dispatch"] == 2 * 28
    assert st["wake_defer_ns"] > 0


# -- held_ns and the stream's span ----------------------------------------------


def test_a_poll_says_what_it_held_and_never_more_than_its_round_trip():
    eng = _engine(max_batch=2)
    try:
        eng.generate([1, 2, 3], 2)
        rid = eng.llm_submit([1, 2, 3], 5)
        n = 0
        while True:
            t0 = time.perf_counter_ns()
            resp = eng.llm_next(rid, timeout_s=10.0)
            trip = time.perf_counter_ns() - t0
            assert 0 < resp["held_ns"] <= trip
            n += len(resp["chunks"])
            if resp["done"]:
                break
        assert n == 5
        # a stream the engine does not know: nothing was held
        assert eng.llm_next("llm-0-0")["held_ns"] == 0
    finally:
        eng.shutdown_engine()


def test_a_traced_stream_carries_its_poll_tally_and_an_untraced_makes_none():
    handle = _deploy()
    ray_tpu.get(handle.remote({"tokens": [5, 9, 2], "max_tokens": 2}),
                timeout=120)
    tracing.drain()
    assert not tracing.is_enabled()
    # no context: no span of any kind, as before
    assert len(list(handle.stream([1, 2, 3], 4))) == 4
    assert tracing.collect(clear=True) == []
    # a caller's context: ONE serve.stream span, with the tally
    t0 = time.perf_counter_ns()
    with tracing.span("t.client", parent=CTX):
        got = list(handle.stream([1, 2, 3], 6))
    whole = time.perf_counter_ns() - t0
    spans = tracing.collect(clear=True)
    [s] = [s for s in spans if s["name"] == "serve.stream:llm"]
    at = s["attributes"]
    assert TALLY <= set(at) and "ttft_s" in at
    assert len(got) == 6 and at["polls"] >= 1
    # durations, each on its own end: what the engine held is inside what
    # the client waited, and that inside the stream
    assert 0 < at["held_ns"] <= at["rpc_ns"] <= whole
    assert not tracing.is_enabled()


def test_a_consumer_that_leaves_early_still_leaves_the_tally():
    handle = _deploy()
    ray_tpu.get(handle.remote({"tokens": [5, 9, 2], "max_tokens": 2}),
                timeout=120)
    tracing.drain()
    with tracing.span("t.client", parent=CTX):
        stream = handle.stream([1, 2, 3], 40)
        first = next(stream)
        stream.close()
    [s] = [s for s in tracing.collect(clear=True)
           if s["name"] == "serve.stream:llm"]
    assert len(first) == 1
    assert TALLY <= set(s["attributes"])
    assert s["attributes"]["polls"] >= 1
    assert 0 < s["attributes"]["held_ns"] <= s["attributes"]["rpc_ns"]


# -- the annotations ------------------------------------------------------------


def test_drains_are_on_the_pollers_threads_and_never_over_a_wait(tmp_path):
    """Two pollers, a thread each, long-polling an engine whose step takes
    30 ms: each ``llm.next.drain`` is on its poller's line (not the
    loop's), there is one a call, and a call that waited a step out left
    an annotation of well under a step. The annotation is entered only
    while a profile is taken (``tracing.profiling``)."""
    eng = _engine(max_batch=2, max_new_cap=64)
    took = {0: [], 1: []}
    try:
        eng.generate([1, 2, 3], 2)
        real = eng._step_fn

        def slow(*a):
            time.sleep(0.03)
            return real(*a)

        eng._step_fn = slow

        def poller(i, rid):
            while True:
                t0 = time.perf_counter()
                resp = eng.llm_next(rid, timeout_s=20.0)
                took[i].append(time.perf_counter() - t0)
                if resp["done"]:
                    return

        def body():
            rids = [eng.llm_submit([i + 2, 5, 8], 6) for i in (0, 1)]
            threads = [threading.Thread(target=poller, args=(i, rid))
                       for i, rid in enumerate(rids)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
            assert tracing.profiling()

        assert not tracing.profiling()
        events = _profiled(tmp_path, body)
        assert not tracing.profiling()
    finally:
        eng.shutdown_engine()
    loop_lines = {e[4] for e in events if e[0].startswith("llm.step.")}
    drains = [e for e in events if e[0] == "llm.next.drain"]
    assert len(loop_lines) == 1
    lines = {e[4] for e in drains}
    assert len(lines) == 2 and not lines & loop_lines
    assert len(drains) == len(took[0]) + len(took[1])
    assert sum(int(e[3]["chunks"]) for e in drains) == 12
    # most calls waited a 30 ms step out; no annotation is that long
    assert sum(t >= 0.02 for t in took[0] + took[1]) >= 6
    assert max(e[2] - e[1] for e in drains) < 15e6, \
        sorted(e[2] - e[1] for e in drains)[-3:]
