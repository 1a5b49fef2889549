"""Enqueue first, wake later (PR 40), and one poller for many streams
(``llm_poll(poller=...)``). Split off ``tests/test_llm_serving.py`` in
PR 65.
"""

import threading
import time

import pytest

from ray_tpu.serve import llm_engine
from ray_tpu.util import failpoints
from llm_engine_helpers import (_clean_between_tests, _drain, _engine, _Gate,
                                _LoggedEvent, _log_sets, _poll_to_the_end,
                                _runtime, _stop_before_flush,
                                _stop_before_read)
from served_families import PROMPT, generated_alone


@pytest.mark.parametrize("poll_s", [0.001, 2.0],
                         ids=["polls_time_out", "polls_are_woken"])
def test_concurrent_streams_get_their_own_tokens_once_in_order(poll_s):
    """Twice as many streams as slots, a poller thread each, the
    interpreter switching threads every 10 us: each stream's tokens are
    the ones it would get alone, in order, none lost, none twice —
    whether its polls are woken (late) or time out and drain first."""
    import sys

    eng = _engine(max_batch=4, max_new_cap=16)
    asked = {i: ([i + 1, 7, 11, i + 2], 5 + i) for i in range(8)}
    got, errors = {}, []

    def one(i, rid):
        try:
            got[i], last = _drain(eng, rid, timeout_s=poll_s)
            assert not last["error"] and not last["shed"], last
        except BaseException as e:  # noqa: BLE001
            errors.append(repr(e))

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        eng.generate(PROMPT, 2)
        threads = [threading.Thread(
            target=one, args=(i, eng.llm_submit(prompt, n)))
            for i, (prompt, n) in asked.items()]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads)
        st = eng.llm_stats()
        params = eng.params
    finally:
        sys.setswitchinterval(interval)
        eng.shutdown_engine()
    assert not errors, errors
    for i, (prompt, n) in asked.items():
        assert got[i] == generated_alone("gpt2", params, prompt, n), i
    assert st["completed"] == 9 and st["errors"] == 0
    # every token but a request's first and last was a put-off wake-up
    assert st["wakes_deferred"] == sum(n - 2 for _, n in asked.values())


def test_a_steps_streams_are_woken_after_the_next_enqueue(monkeypatch):
    """Step n's wake-ups come at the tail of its fan-out and nowhere else,
    and step n + 1 was enqueued before step n was read: a turn is enqueue,
    read, fan out, wake; an admission's chunks go out behind all of it."""
    log = []
    _log_sets(monkeypatch, log)
    eng = _engine(max_batch=2, prefill_chunk=4, cache_len=64,
                  max_new_cap=64)
    try:
        eng.generate(PROMPT, 2)          # both programs compiled
        step, chunk, host = eng._step_fn, eng._prefill_fn, eng._sync

        def logged_step(*a):
            time.sleep(0.005)            # the second request arrives mid-decode
            out = step(*a)
            log.append(("enqueued", "step"))
            return out

        def logged_chunk(*a):
            out = chunk(*a)
            log.append(("enqueued", "chunk"))
            return out

        def logged_sync(d):
            log.append(("read", type(d).__name__))
            return host(d)

        eng._step_fn, eng._prefill_fn = logged_step, logged_chunk
        eng._sync = logged_sync
        gate = _Gate()
        gate.open()
        _stop_before_flush(eng, gate, log)   # (logs what is owed, never stops)
        del log[:]
        a = eng.llm_submit(PROMPT, 40)
        had = 0
        while had < 3:                   # decoding now: steps' tokens came
            had += len(eng.llm_next(a, timeout_s=30.0)["chunks"])
        b = eng.llm_submit([3, 1, 4, 1, 5, 9, 2, 6], 6)    # two chunks
        assert len(_drain(eng, b)[0]) == 6
        assert had + len(_drain(eng, a)[0]) == 40
        st = eng.llm_stats()
    finally:
        eng.shutdown_engine()
    # only the loop's thread wrote the log, so it is in program order
    owed_at = [i for i, e in enumerate(log) if e[0] == "owed"]
    assert len(owed_at) >= 38            # a's steps but its last
    chunks_behind = 0
    for n, i in enumerate(owed_at):
        owed = log[i][1]
        # the wake-ups, all of them, at once
        assert log[i + 1:i + 1 + len(owed)] == [("set", st_) for st_ in owed]
        # since the last flush: the next step enqueued, THEN this one read
        turn = [e[:2] for e in log[owed_at[n - 1] if n else 0:i]
                if e[0] in ("enqueued", "read")]
        turn = [e for e in turn if e != ("enqueued", "chunk")
                and e != ("read", "_Firsts")]
        # (the first flush's stretch also holds the turn that enqueued
        # step 1 with no step to read)
        assert turn[-2:] == [("enqueued", "step"), ("read", "_Step")], turn
        assert n == 0 or len(turn) == 2, turn
        # an admission's chunks follow the wake-ups, back to back
        rest = [e[:2] for e in log[i + 1 + len(owed):i + 3 + len(owed)]]
        if rest == [("enqueued", "chunk")] * 2:
            chunks_behind += 1
    assert chunks_behind == 1
    assert st["wakes_deferred"] == st["wakes_after_dispatch"] \
        == sum(len(log[i][1]) for i in owed_at) == 38 + 4
    # ... and no stream was told anywhere else: beside those, only the
    # two first tokens and the two terminal transitions set an event
    assert sum(e[0] == "set" for e in log) == 38 + 4 + 2 + 2


@pytest.mark.parametrize("what", ["last_step", "failpoint", "step_fn",
                                  "cancel", "shutdown"])
def test_no_poller_waits_out_its_timeout(what):
    """Whatever ends or interrupts a step, every ``llm_next`` comes back
    within a second with tokens or the terminal state: no wake-up is
    left on the list for a poll's ``timeout_s`` (20 s here) to find."""
    eng = _engine(max_batch=2, max_new_cap=64)
    took = {0: [], 1: []}
    ends, errors = {}, []
    try:
        eng.generate(PROMPT, 2)
        real, calls = eng._step_fn, []

        def slow(*a):
            calls.append(1)
            time.sleep(0.003)            # keeps the streams in mid-flight
            if what == "step_fn" and len(calls) == 6:
                raise RuntimeError("injected")
            return real(*a)

        eng._step_fn = slow
        rids = [eng.llm_submit([i + 2, 5, 8], 40) for i in (0, 1)]

        def one(i):
            try:
                ends[i] = _drain(eng, rids[i], timeout_s=20.0, took=took[i])
            except BaseException as e:  # noqa: BLE001
                errors.append(repr(e))

        threads = [threading.Thread(target=one, args=(i,)) for i in (0, 1)]
        for t in threads:
            t.start()
        deadline = time.monotonic() + 30
        while len(calls) < 4 and time.monotonic() < deadline:
            time.sleep(0.001)
        if what == "failpoint":
            failpoints.arm("serve.llm.before_step", "raise,once")
        elif what == "cancel":
            assert eng.llm_cancel(rids[0])
        elif what == "shutdown":
            assert eng.shutdown_engine()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
        st = eng.llm_stats()
    finally:
        failpoints.reset()
        eng.shutdown_engine()
    assert not errors, errors
    assert max(took[0] + took[1]) < 1.0, (took, st)
    for i in (0, 1):
        tokens, last = ends[i]
        if what == "shutdown":
            assert last["error"] == "engine stopped" and len(tokens) < 40
        elif what == "cancel" and i == 0:
            assert last["error"] == "cancelled" and len(tokens) < 40
        else:
            assert len(tokens) == 40 and not last["error"], last
    assert st["errors"] == {"last_step": 0, "failpoint": 1, "step_fn": 1,
                            "cancel": 1, "shutdown": 2}[what]


def test_a_late_wake_up_finds_nothing_and_harms_nothing():
    """A poll that times out between a token's append and its wake-up
    drains the token; the late wake-up then ends the next poll at once
    with no chunk and no error; every token arrives once."""
    eng = _engine(max_batch=2)
    before_flush, before_read = _Gate(), _Gate()
    try:
        eng.generate(PROMPT, 2)
        _stop_before_flush(eng, before_flush)
        _stop_before_read(eng, before_read)
        rid = eng.llm_submit(PROMPT, 8)
        first = eng.llm_next(rid, timeout_s=30.0)
        before_read.reached()             # step 2 enqueued, step 1 unread
        before_read.let()                 # step 1 fans out: token 2 pending
        before_flush.reached()            # ... and its wake-up not yet set
        t0 = time.monotonic()
        second = eng.llm_next(rid, timeout_s=0.05)
        assert time.monotonic() - t0 >= 0.05          # it was not woken
        before_flush.let()                # the late set
        before_read.reached()             # ... and step 2 not fanned out
        t0 = time.monotonic()
        third = eng.llm_next(rid, timeout_s=20.0)
        assert time.monotonic() - t0 < 1.0            # woken, for nothing
        before_flush.open()
        before_read.open()
        rest, last = _drain(eng, rid)
        st = eng.llm_stats()
        params = eng.params
    finally:
        before_flush.open()
        before_read.open()
        eng.shutdown_engine()
    want = generated_alone("gpt2", params, PROMPT, 8)
    assert first["chunks"] == [want[:1]] and second["chunks"] == [want[1:2]]
    assert third == {"chunks": [], "done": False, "shed": None,
                     "error": None, "held_ns": third["held_ns"]}
    assert 0 < third["held_ns"] < 1e9     # what the poll spent in the engine
    assert rest == want[2:] and not last["error"]
    assert st["wakes_deferred"] == st["wakes_after_dispatch"] == 6


@pytest.mark.parametrize("how, after_dispatch", [
    ("plain", 9), ("throttled", 0), ("a_step_fails", 7)])
def test_wake_counters_count_exactly(how, after_dispatch):
    """Two requests of 5 and 8 tokens in two slots: every token but a
    request's first (the prefill's) and last (the terminal transition's)
    is a put-off wake-up, 3 + 6; all of them follow an enqueue unless
    the engine sleeps between steps (none does) or a step fails before
    its enqueue (the step before it is read all the same, and its two
    streams are woken with nothing behind their step)."""
    eng = _engine(max_batch=2, prefill_rows=2,
                  step_throttle_s=0.001 if how == "throttled" else 0.0)
    try:
        eng.generate(PROMPT, 2)
        before = eng.llm_stats()
        if how == "a_step_fails":
            real, raised = eng._step_fn, []

            def flaky(*a):
                # once, with a step for both streams dispatched and unread
                if not raised and [len(d.rows) for d in eng._outstanding
                                   if isinstance(d, llm_engine._Step)] == [2]:
                    raised.append(1)
                    raise RuntimeError("injected")
                return real(*a)

            eng._step_fn = flaky
        rids = eng.llm_submit_many([
            {"tokens": [1, 2, 3], "max_tokens": 5},
            {"tokens": [4, 5, 6, 7], "max_tokens": 8}])
        assert [len(_drain(eng, rid)[0]) for rid in rids] == [5, 8]
        st = eng.llm_stats()
    finally:
        eng.shutdown_engine()
    assert st["wakes_deferred"] - before["wakes_deferred"] == 9
    assert st["wakes_after_dispatch"] - before["wakes_after_dispatch"] \
        == after_dispatch


@pytest.mark.parametrize("poll_s", [0.001, 2.0],
                         ids=["polls_time_out", "polls_are_woken"])
def test_two_pollers_get_their_own_streams_tokens_once_in_order(poll_s):
    """Eight streams over four slots, four to a poller, a thread a poller
    and the interpreter switching every 10 us: each stream's tokens are
    the ones it would get alone, whether the batched polls are woken or
    time out and drain first, and a poller never sees the other's."""
    import sys

    eng = _engine(max_batch=4, max_new_cap=16)
    asked = {i: ([i + 1, 7, 11, i + 2], 5 + i) for i in range(8)}
    got, calls, errors = {}, {}, []

    def one(pid, rids):
        try:
            out, last, calls[pid] = _poll_to_the_end(
                eng, pid, list(rids), timeout_s=poll_s)
            assert not any(r["error"] or r["shed"] for r in last.values())
            for rid, i in rids.items():
                got[i] = out[rid]
        except BaseException as e:  # noqa: BLE001
            errors.append(repr(e))

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        eng.generate(PROMPT, 2)
        before = eng.llm_stats()
        mine = {pid: {eng.llm_submit(*asked[i], poller=pid): i
                      for i in asked if i % 2 == k}
                for k, pid in enumerate(("even", "odd"))}
        assert set(eng._pollers) == {"even", "odd"}
        threads = [threading.Thread(target=one, args=item)
                   for item in mine.items()]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads)
        st = eng.llm_stats()
        params = eng.params
    finally:
        sys.setswitchinterval(interval)
        eng.shutdown_engine()
    assert not errors, errors
    for i, (prompt, n) in asked.items():
        assert got[i] == generated_alone("gpt2", params, prompt, n), i
    chunks = sum(n for _, n in asked.values())
    assert st["next_calls"] - before["next_calls"] == sum(calls.values()) \
        == st["next_batched"] - before["next_batched"]
    assert st["deliver_chunks"] - before["deliver_chunks"] == chunks
    if poll_s == 2.0:
        # woken calls: four streams step together, a call takes several
        assert sum(calls.values()) < chunks
    # a wake-up is still counted a stream
    assert st["wakes_deferred"] == sum(n - 2 for _, n in asked.values())
    assert not eng._pollers and not eng._streams


@pytest.mark.parametrize("beside", ["an_idle_poller", "a_slowed_stream"])
def test_a_stream_submitted_under_a_blocked_call_is_that_calls(beside):
    """The poller's call is inside its wait (20 s) when the stream is
    submitted: its first token ends THAT call, as soon as the prefill has
    it: no first token waits for a time-out. Beside a stream whose steps
    take 0.4 s the call may end a moment sooner, for that stream's token
    (the put-off wake-up of the step before, set as the next step or the
    new prompt's first chunk is enqueued), and a call right after it
    brings the first token, before the next step."""
    eng = _engine(max_batch=2, max_new_cap=64)
    got = []
    try:
        eng.generate(PROMPT, 2)
        old = None
        if beside == "a_slowed_stream":
            real = eng._step_fn

            def slow(*a):
                time.sleep(0.4)
                return real(*a)

            eng._step_fn = slow
            old = eng.llm_submit(PROMPT, 40, poller="p")
            while old not in eng.llm_poll(poller="p", timeout_s=30.0):
                pass                       # its first token: decoding now
            # the next call would be woken for the old stream's tokens
            # too: wait one out, so what follows starts after a wake-up
            assert eng.llm_poll(poller="p", timeout_s=30.0)[old]["chunks"]

        def call():
            t0 = time.monotonic()
            while True:
                resp = eng.llm_poll(poller="p", timeout_s=20.0)
                got.append((resp, time.monotonic() - t0))
                if len(got) == 6 or any(r != old for r in resp
                                        if r != "held_ns"):
                    return

        t = threading.Thread(target=call)
        t.start()
        deadline = time.monotonic() + 30
        while not (eng._pollers.get("p") and eng._pollers["p"].waiting) \
                and time.monotonic() < deadline:
            time.sleep(0.001)
        assert eng._pollers["p"].waiting == 1
        new = eng.llm_submit([3, 1, 4], 8, poller="p")
        t.join(timeout=30)
        assert not t.is_alive()
        assert len(got) == 1 or (old and len(got) <= 3), got
        resp, took = got[-1]
        assert took < 5.0                  # nowhere near the 20 s
        assert len(resp[new]["chunks"]) == 1 and not resp[new]["done"]
        rest, last, _ = _poll_to_the_end(
            eng, "p", [r for r in (old, new) if r])
        assert len(rest[new]) == 7 and not last[new]["error"]
    finally:
        eng.shutdown_engine()


def test_a_poller_is_told_once_a_flush_however_many_streams(monkeypatch):
    """Two streams of one poller decoding side by side, nobody polling:
    the prefill's first tokens and the two ends tell the poller a stream
    each, a decode step's put-off wake-ups tell it ONCE for both, and
    ``wakes_deferred`` still counts a stream a wake-up."""
    log = []
    real = llm_engine._Poller.__init__

    def init(p, pid):
        real(p, pid)
        p.event = _LoggedEvent(log, pid)

    monkeypatch.setattr(llm_engine._Poller, "__init__", init)
    eng = _engine(max_batch=2, prefill_rows=2)
    try:
        eng.generate(PROMPT, 2)
        before = eng.llm_stats()
        rids = [eng.llm_submit([i + 1, 2, 3], 6, poller="p")
                for i in (0, 1)]
        deadline = time.monotonic() + 60
        while eng.llm_stats()["completed"] - before["completed"] < 2 \
                and time.monotonic() < deadline:
            time.sleep(0.005)
        st = eng.llm_stats()
        resp = eng.llm_poll(poller="p", timeout_s=5.0)
    finally:
        eng.shutdown_engine()
    # 2 first tokens + 4 flushes (tokens 2 to 5 of both) + 2 ends
    assert log == [("set", "p")] * 8, log
    assert st["wakes_deferred"] - before["wakes_deferred"] == 8
    assert st["wakes_after_dispatch"] - before["wakes_after_dispatch"] == 8
    assert [len(resp[rid]["chunks"]) for rid in rids] == [6, 6]
    assert all(resp[rid]["done"] for rid in rids)


def test_a_flush_says_nothing_of_tokens_an_earlier_call_took():
    """A call woken for one stream's end takes its neighbour's put-off
    token with it: the flush that follows finds nothing pending and does
    not wake the poller for nothing (``next_empty`` stays 0)."""
    eng = _engine(max_batch=2, prefill_rows=2, max_new_cap=64)
    before_flush = _Gate()
    try:
        eng.generate(PROMPT, 2)
        _stop_before_flush(eng, before_flush)
        before = eng.llm_stats()
        short = eng.llm_submit([1, 2, 3], 2, poller="p")
        long_ = eng.llm_submit([4, 5, 6], 4, poller="p")
        first = {}
        while not {short, long_} <= set(first):
            first.update(eng.llm_poll(poller="p", timeout_s=30.0))
        before_flush.reached()             # step 1: short ended, long_ owed
        second = eng.llm_poll(poller="p", timeout_s=30.0)
        assert second[short]["done"] and second[long_]["chunks"]
        before_flush.let()                 # the flush: step 1's token is gone
        before_flush.reached()             # step 2 fanned out, its flush not
        # step 1's flush found its token taken and set nothing; step 2's
        # token is pending and not yet announced
        assert not eng._pollers["p"].event.is_set()
        st = eng.llm_stats()
        before_flush.open()
        rest, last, _ = _poll_to_the_end(eng, "p", [long_])
    finally:
        before_flush.open()
        eng.shutdown_engine()
    assert len(rest[long_]) == 2 and not last[long_]["error"]
    assert st["next_empty"] == before["next_empty"]
    # it was counted all the same: the token's wake-up was owed and put off
    assert st["wakes_deferred"] - before["wakes_deferred"] >= 1


def test_the_engine_forgets_a_poller_with_its_last_stream(monkeypatch):
    eng = _engine(max_batch=2)
    try:
        eng.generate(PROMPT, 2)
        # a call with no stream: known while it waits, gone when it ends
        assert set(eng.llm_poll(poller="p", timeout_s=0.01)) == {"held_ns"}
        assert not eng._pollers
        rid = eng.llm_submit(PROMPT, 3, poller="p")
        assert list(eng._pollers) == ["p"]
        assert eng.open_streams() == 1
        _poll_to_the_end(eng, "p", [rid])
        assert not eng._pollers and not eng._streams
        assert eng.open_streams() == 0
        # a vanished client's ended stream is reaped, its poller with it
        rid = eng.llm_submit(PROMPT, 2, poller="gone")
        deadline = time.monotonic() + 30
        while not eng._streams[rid].done and time.monotonic() < deadline:
            time.sleep(0.005)
        assert eng.open_streams() == 0     # ended: no longer a request
        monkeypatch.setattr(llm_engine, "_STREAM_TTL_S", 0.0)
        eng._reap_streams()
        assert not eng._pollers and not eng._streams
        # the one-stream lane on an unknown poller's stream id
        assert eng.llm_next(rid)["error"].startswith("unknown stream")
    finally:
        eng.shutdown_engine()
