"""The token's way out of the engine (PR 41): the delivery counters of
``llm_stats()`` exact for scripted runs, ``held_ns`` beside a poll's
chunks, the poll tally on the ``serve.stream`` span of a traced stream
(and no span for an untraced one), and the pollers' ``llm.next.drain``
annotations in a profile, on the caller's thread and never over its wait.
Since PR 49 a stream through the handle is its process's ONE poller's
(``llm_poll(poller=...)``, a blocking call for all of them): the counters
and the tally are held for that lane (``next_batched`` says which of the
long-polls were a poller's), and for ``llm_next``'s own.

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

DELIVERY = ("next_calls", "next_empty", "next_batched", "deliver_chunks",
            "deliver_lag_ns", "deliver_lag_hist", "wake_defer_ns")
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
    the process's one poller alone: not one ``llm_next``."""
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
    assert d["next_calls"] == d["next_batched"]     # and not one its own
    # a poll is empty, or it took at least a chunk (an end comes with one)
    assert 1 <= d["next_calls"] - d["next_empty"] <= d["deliver_chunks"]
    assert d["next_empty"] >= 0
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
    assert d["next_calls"] == d["next_empty"] == d["next_batched"] == 0


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
    # the poller's calls that brought this stream something: none brings
    # two steps' tokens here, and none is counted that brought it nothing
    assert len(got) == 6 and 1 <= at["polls"] <= 6
    # durations, each on its own end: what the engine held is inside what
    # the poller waited, and a lone stream's calls lie inside the stream
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
    # what it read before it left, and no call it did not wait for
    assert s["attributes"]["polls"] == 1
    assert 0 < s["attributes"]["held_ns"] <= s["attributes"]["rpc_ns"]


# -- the annotations ------------------------------------------------------------


@pytest.mark.parametrize("lane", ["batched", "one_stream"])
def test_drains_are_on_the_pollers_threads_and_never_over_a_wait(
        tmp_path, lane):
    """Two pollers, a thread each, long-polling an engine whose step takes
    30 ms, with the batched call a client process makes
    (``llm_poll(poller=...)``) or with ``llm_next``: each
    ``llm.next.drain`` is on its poller's line (not the loop's), there is
    one a call, and a call that waited a step out left an annotation of
    well under a step. The annotation is entered only while a profile is
    taken (``tracing.profiling``)."""
    eng = _engine(max_batch=2, max_new_cap=64)
    took = {0: [], 1: []}
    try:
        eng.generate([1, 2, 3], 2)
        real = eng._step_fn

        def slow(*a):
            time.sleep(0.03)
            return real(*a)

        eng._step_fn = slow
        before = eng.llm_stats()

        def poller(i, rid):
            while True:
                t0 = time.perf_counter()
                if lane == "batched":
                    resp = eng.llm_poll(poller=f"p{i}", timeout_s=20.0)
                    assert set(resp) <= {rid, "held_ns"}
                    resp = resp.get(rid, {"done": False})
                else:
                    resp = eng.llm_next(rid, timeout_s=20.0)
                took[i].append(time.perf_counter() - t0)
                if resp["done"]:
                    return

        def body():
            rids = [eng.llm_submit(
                [i + 2, 5, 8], 6,
                poller=f"p{i}" if lane == "batched" else None)
                for i in (0, 1)]
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
        st = eng.llm_stats()
    finally:
        eng.shutdown_engine()
    loop_lines = {e[4] for e in events if e[0].startswith("llm.step.")}
    drains = [e for e in events if e[0] == "llm.next.drain"]
    assert len(loop_lines) == 1
    lines = {e[4] for e in drains}
    assert len(lines) == 2 and not lines & loop_lines
    assert len(drains) == len(took[0]) + len(took[1])
    assert len(drains) == st["next_calls"] - before["next_calls"]
    assert st["next_batched"] - before["next_batched"] == (
        len(drains) if lane == "batched" else 0)
    assert sum(int(e[3]["chunks"]) for e in drains) == 12
    # most calls waited a 30 ms step out; no annotation is that long
    assert sum(t >= 0.02 for t in took[0] + took[1]) >= 6
    assert max(e[2] - e[1] for e in drains) < 15e6, \
        sorted(e[2] - e[1] for e in drains)[-3:]


# -- one poller a client process -------------------------------------------------


class _Recorded(LLMEngine):
    """The engine of a deployment, where the test can reach it (the local
    backend runs the replica in this process)."""

    made: list = []

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        _Recorded.made.append(self)


def _deploy_recorded(**kw):
    """(handle, the replica's engine), both programs compiled."""
    from ray_tpu.serve import _private as sp

    del _Recorded.made[:]
    kw = {"model": "gpt2", "config": TINY, "max_batch": 4, "cache_len": 64,
          "max_prompt_len": 8, "max_new_tokens": 6, "max_new_cap": 64, **kw}
    dep = serve.deployment(name="llm", max_concurrent_queries=32)(_Recorded)
    handle = serve.run(dep.bind(**kw))
    ray_tpu.get(handle.remote({"tokens": [5, 9, 2], "max_tokens": 2}),
                timeout=120)
    [eng] = _Recorded.made
    return handle, eng, sp


def _pollers_alive():
    return [t for t in threading.enumerate()
            if t.name == "serve-stream-poller" and t.is_alive()]


def _in_threads(calls):
    """Run each ``name -> callable`` on a thread of its own; returns
    ``(threads, results)``, a result being the callable's list of chunks
    or the exception it raised."""
    results = {}

    def run(name, fn):
        try:
            results[name] = fn()
        except BaseException as e:  # noqa: BLE001 — the test reads it
            results[name] = e

    threads = [threading.Thread(target=run, args=item)
               for item in calls.items()]
    for t in threads:
        t.start()
    return threads, results


def _wait_for(what, timeout=30.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if what():
            return
        time.sleep(0.002)
    raise AssertionError("timed out")


def test_six_streams_from_six_threads_are_one_poller():
    """Six client threads, one process: one poller's thread and one
    poller at the engine, every stream's chunks the blocking lane's
    tokens, once each and in order, in fewer calls than chunks."""
    handle, eng, sp = _deploy_recorded(step_throttle_s=0.002)
    asked = {i: ([i + 1, 7, 11], 5 + i) for i in range(6)}
    want = {i: ray_tpu.get(handle.remote(
        {"tokens": p, "max_tokens": n}), timeout=120)["tokens"]
        for i, (p, n) in asked.items()}
    before = eng.llm_stats()
    seen = []

    def client(i):
        def run():
            out = []
            for chunk in handle.stream(*asked[i]):
                out.append(chunk)
                seen.append((len(_pollers_alive()), len(eng._pollers),
                             len(sp._stream_pollers)))
            return out
        return run

    threads, got = _in_threads({i: client(i) for i in asked})
    for t in threads:
        t.join(timeout=120)
    assert not any(t.is_alive() for t in threads)
    for i in asked:
        assert [t for ch in got[i] for t in ch] == want[i], (i, got[i])
        assert all(len(ch) == 1 for ch in got[i])
    # (the engine's is forgotten as its last stream's last chunk leaves)
    assert {(1, 1, 1)} <= set(seen) <= {(1, 1, 1), (1, 0, 1)}, set(seen)
    d = _delta(eng.llm_stats(), before)
    chunks = sum(n for _, n in asked.values())
    assert d["deliver_chunks"] == chunks
    # four slots step together: a call brings several streams' tokens
    assert 1 <= d["next_calls"] == d["next_batched"] < chunks
    # the engine forgets a poller with its last stream
    assert not eng._pollers and not eng._streams


def test_many_threads_streams_back_to_back_lose_and_repeat_nothing():
    """Twelve client threads (more than cores), three streams each, one
    after the other, the interpreter switching every 10 us: submits,
    registrations and the poller's hand-over interleave every way (a first
    chunk may come back before its stream has a queue), and every stream
    still gets exactly its tokens, in order."""
    import sys

    handle, eng, sp = _deploy_recorded()
    prompts = {i: [i + 1, 7, 11] for i in range(12)}
    want = {i: ray_tpu.get(handle.remote(
        {"tokens": p, "max_tokens": 8}), timeout=120)["tokens"]
        for i, p in prompts.items()}
    before = eng.llm_stats()

    def client(i):
        return lambda: [[t for ch in handle.stream(prompts[i], 3 + 2 * k)
                         for t in ch] for k in range(3)]

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads, got = _in_threads({i: client(i) for i in prompts})
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    for i in prompts:
        assert got[i] == [want[i][:3 + 2 * k] for k in range(3)], (i, got[i])
    d = _delta(eng.llm_stats(), before)
    assert d["deliver_chunks"] == 12 * (3 + 5 + 7)
    assert d["next_calls"] == d["next_batched"]
    with sp._stream_pollers_lock:
        assert all(not p.queues and not p.early and not p.submitting
                   for p in sp._stream_pollers.values())
    assert not eng._pollers and not eng._streams


@pytest.mark.parametrize("what", ["shed_at_admission", "deadline", "cancel",
                                  "engine_error"])
def test_what_ends_one_stream_ends_no_other_of_its_poller(what):
    """Two streams of one poller, the second ended early, each way a
    stream can be: the first still gets every token and no error."""
    handle, eng, sp = _deploy_recorded(step_throttle_s=0.01)
    threads, got = _in_threads(
        {"bystander": lambda: list(handle.stream([1, 2, 3], 30))})
    _wait_for(lambda: eng.llm_stats()["active"] == 1)
    [mine] = list(eng._streams)
    victim = handle.options(deadline_s=0.15) if what == "deadline" \
        else handle
    if what == "shed_at_admission":
        eng.max_queue = 0        # a full queue sheds at the submit
    more, got2 = _in_threads(
        {"victim": lambda: list(victim.stream([4, 5, 6], 40))})
    if what in ("cancel", "engine_error"):
        _wait_for(lambda: eng.llm_stats()["active"] == 2)
        [rid] = [r for r in eng._streams if r != mine]
        if what == "cancel":
            assert eng.llm_cancel(rid)
        else:
            with eng._lock:
                [(slot, req)] = [(i, r) for i, r in enumerate(eng._slot_req)
                                 if r is not None and r.rid == rid]
                eng._finish_locked(req, error="injected", slot=slot)
    for t in threads + more:
        t.join(timeout=120)
    assert not any(t.is_alive() for t in threads + more)
    assert [len(ch) for ch in got["bystander"]] == [1] * 30, got
    end = got2["victim"]
    if what in ("shed_at_admission", "deadline"):
        assert isinstance(end, serve.RequestShedError), end
        assert end.reason == "decode"
    else:
        assert isinstance(end, RuntimeError), end
        assert ("cancelled" if what == "cancel" else "injected") in str(end)
    _wait_for(lambda: not eng._pollers)
    assert not sp._stream_pollers or all(
        not p.queues and not p.submitting
        for p in sp._stream_pollers.values())


def test_a_killed_replica_fails_all_of_a_pollers_streams_fast():
    from ray_tpu.core.object_ref import ActorError

    handle, eng, sp = _deploy_recorded(step_throttle_s=0.02)
    try:
        threads, got = _in_threads({
            i: (lambda i=i: list(handle.stream([i + 1, 2, 3], 60)))
            for i in range(3)})
        _wait_for(lambda: eng.llm_stats()["active"] == 3)
        _, table = ray_tpu.get(
            sp.get_or_create_controller().get_routing_table.remote(),
            timeout=30)
        t0 = time.monotonic()
        ray_tpu.kill(table["llm"]["replicas"][0])
        for t in threads:
            t.join(timeout=30)
        took = time.monotonic() - t0
        # the controller replaces the replica: let it, so that the next
        # test's deployment is not joined by a late engine of this one
        _wait_for(lambda: len(_Recorded.made) == 2)
    finally:
        for made in _Recorded.made:
            made.shutdown_engine()  # ``kill`` leaves the loop's thread
    assert not any(t.is_alive() for t in threads)
    assert all(isinstance(got[i], ActorError) for i in range(3)), got
    assert took < 10.0            # one call's failure, not three time-outs
    _wait_for(lambda: not sp._stream_pollers)


def test_a_closed_generator_stalls_no_other_stream():
    """The consumer of a 20-token stream leaves after one chunk and sends
    no cancel: the engine decodes it to its end, the poller drains and
    drops what comes while it has another stream to poll for, and that
    other stream gets its tokens at the engine's pace. (What is left of
    an abandoned stream once the poller has nothing else to poll for
    waits for the engine's reaper, as an unpolled stream always did.)"""
    handle, eng, sp = _deploy_recorded(step_throttle_s=0.005)
    before = eng.llm_stats()
    threads, got = _in_threads(
        {"stays": lambda: list(handle.stream([1, 2, 3], 40))})
    leaver = handle.stream([4, 5, 6], 20)
    assert len(next(leaver)) == 1
    leaver.close()
    t0 = time.monotonic()
    for t in threads:
        t.join(timeout=60)
    assert not threads[0].is_alive()
    assert [len(ch) for ch in got["stays"]] == [1] * 40, got
    assert time.monotonic() - t0 < 20.0
    st = eng.llm_stats()
    assert st["completed"] - before["completed"] == 2    # nobody cancelled
    assert st["errors"] == before["errors"]
    # what came for the stream nobody reads was taken all the same: the
    # poller goes on while the other stream lives, and the engine holds
    # nothing of either once both have ended
    _wait_for(lambda: not eng._streams)
    assert _delta(eng.llm_stats(), before)["deliver_chunks"] == 60


def test_keepalive_frames_for_a_deep_queued_stream():
    """One slot, taken for a while: a second stream yields keep-alive
    frames from its queue's time-out while it waits, then its tokens."""
    handle, eng, sp = _deploy_recorded(max_batch=1, step_throttle_s=0.01)
    threads, got = _in_threads(
        {"first": lambda: list(handle.stream([1, 2, 3], 40))})
    _wait_for(lambda: eng.llm_stats()["active"] == 1)
    frames = list(sp.stream_call("llm", ([4, 5, 6], 3), {}, None,
                                 poll_s=0.02, keepalive_every=0.05))
    threads[0].join(timeout=60)
    alive = [f for f in frames if f is sp.STREAM_KEEPALIVE]
    tokens = [f for f in frames if f is not sp.STREAM_KEEPALIVE]
    assert len(alive) >= 2 and [len(ch) for ch in tokens] == [1] * 3
    assert frames.index(tokens[0]) == len(alive)    # all of them before
    assert len(got["first"]) == 40


def test_the_pollers_thread_is_gone_after_serve_shutdown():
    handle, eng, sp = _deploy_recorded()
    assert not _pollers_alive()
    assert len(list(handle.stream([1, 2, 3], 4))) == 4
    # it lingers for the process's next stream ...
    assert len(_pollers_alive()) == 1 == len(sp._stream_pollers)
    mine = _pollers_alive()[0]
    assert len(list(handle.stream([1, 2, 3], 4))) == 4
    assert _pollers_alive() == [mine]
    # ... and an open stream hears of the shutdown
    held = handle.stream([1, 2, 3], 40)
    next(held)
    serve.shutdown()
    assert not _pollers_alive() and not sp._stream_pollers
    with pytest.raises(RuntimeError, match="shut down"):
        list(held)


def test_an_idle_pollers_thread_retires(monkeypatch):
    from ray_tpu.serve import _private as sp

    monkeypatch.setattr(sp, "_POLLER_LINGER_S", 0.05)
    handle, eng, sp = _deploy_recorded()
    assert len(list(handle.stream([1, 2, 3], 4))) == 4
    _wait_for(lambda: not _pollers_alive() and not sp._stream_pollers)
    # the next stream starts another
    assert len(list(handle.stream([1, 2, 3], 4))) == 4


def test_get_num_ongoing_reads_the_open_streams():
    """Three open streams are three requests to the autoscaling probe, as
    when each held a long-poll of its own: the poller's one call is in the
    replica's count of calls and out of the engine's count of streams."""
    handle, eng, sp = _deploy_recorded(step_throttle_s=0.02)
    _, table = ray_tpu.get(
        sp.get_or_create_controller().get_routing_table.remote(), timeout=30)
    [replica] = table["llm"]["replicas"]

    def ongoing():
        return ray_tpu.get(replica.get_num_ongoing.remote(), timeout=30)

    assert ongoing() == 0
    threads, got = _in_threads({
        i: (lambda i=i: list(handle.stream([i + 1, 2, 3], 50)))
        for i in range(3)})
    _wait_for(lambda: eng.llm_stats()["active"] == 3)
    seen = {ongoing() for _ in range(20)}
    for t in threads:
        t.join(timeout=60)
    assert all(len(got[i]) == 50 for i in range(3)), got
    # between two calls of the poller the count reads one more for a moment
    assert seen <= {3, 4} and 3 in seen, seen
    _wait_for(lambda: ongoing() == 0)
