"""The engine loop's own record of its turns (PR 57): sums that add up,
the two classes of turn, a sync made to wait found as ONE plain turn with
the sync of the turn after it, the one warning, the eight kept turns and
their minute, and what the record may not cost: no thread, hook or callback
beside the loop's own thread, integers and flat lists of integers only,
under 2 kB of ``llm_stats()``. The readers of the record are held by
``tests/benchmark/test_benchmark_turns.py``. Everything here runs on the
CPU at the tiny preset: lengths are this host's, and say nothing about a
device."""

import gc
import logging
import os
import pickle
import signal
import sys
import threading
import time

import pytest

from ray_tpu.serve import llm_engine
from ray_tpu.serve.llm_engine import (SLOW_TURN_FIELDS, SLOW_TURNS,
                                      TURN_EDGES_MS, TURN_PHASES, _Step)

from test_device_spans import _engine

RECORD = ("turns", "turn_ns", "turn_phase_ns", "turn_hist_plain",
          "turn_hist_plain_ns", "turn_hist_prefill", "turn_hist_prefill_ns",
          "slow_turns")
N = len(SLOW_TURN_FIELDS)


@pytest.fixture
def eng():
    e = _engine(max_new_tokens=8)
    e.generate([1, 2, 3], 2)        # both programs have run once
    yield e
    e.shutdown_engine()


def kept(st) -> list:
    """``slow_turns`` as dicts, by start."""
    flat = st["slow_turns"]
    assert len(flat) % N == 0
    return sorted((dict(zip(SLOW_TURN_FIELDS, flat[i:i + N]))
                   for i in range(0, len(flat), N)),
                  key=lambda t: t["start_ns"])


def settled(e) -> dict:
    """The record once the loop has nothing left to do: a generate returns
    when its last token is drained, a moment before that turn is counted."""
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        a = e.llm_stats()
        time.sleep(0.06)
        b = e.llm_stats()
        if a["turns"] == b["turns"] and not b["outstanding"]:
            return b
    raise AssertionError("the loop never came to rest")


def bucket_of(ns: int) -> int:
    return min((ns // 1_000_000).bit_length(), len(TURN_EDGES_MS))


def test_the_phases_sum_to_the_turns_and_turns_counts_what_did_something(eng):
    before = settled(eng)
    for _ in range(3):
        assert len(eng.generate([5, 6, 7, 8], 5)) == 5
    st = settled(eng)
    assert len(st["turn_phase_ns"]) == len(TURN_PHASES) == 9
    assert sum(st["turn_phase_ns"]) == st["turn_ns"]
    assert all(ns >= 0 for ns in st["turn_phase_ns"])
    # every pass of the loop is in turn_ns, the idle ones as llm.loop.wait
    assert st["turn_phase_ns"][TURN_PHASES.index("llm.loop.wait")] > 0
    # a request alone: the turn that admits it reads its first token, then
    # one plain turn a decode step
    d = {k: st[k] - before[k] for k in ("turns", "steps", "admitted")}
    assert d == {"turns": 15, "steps": 12, "admitted": 3}
    plain = sum(st["turn_hist_plain"]) - sum(before["turn_hist_plain"])
    prefill = sum(st["turn_hist_prefill"]) - sum(before["turn_hist_prefill"])
    assert (plain, prefill) == (12, 3)
    assert sum(st["turn_hist_plain"]) + sum(st["turn_hist_prefill"]) \
        == st["turns"]
    # the turns' own time is part of the loop's, which also waited
    in_turns = sum(st["turn_hist_plain_ns"]) + sum(st["turn_hist_prefill_ns"])
    assert 0 < in_turns < st["turn_ns"]
    for cls in ("turn_hist_plain", "turn_hist_prefill"):
        for b, (n, ns) in enumerate(zip(st[cls], st[cls + "_ns"])):
            assert (n == 0) == (ns == 0)
            if n:   # each sum lies inside its bucket's edges
                lo = TURN_EDGES_MS[b - 1] if b else 0
                assert lo * 1e6 * n <= ns < TURN_EDGES_MS[b] * 1e6 * n


def test_a_turn_that_admitted_a_request_is_a_prefill_turn(eng):
    mark = time.perf_counter_ns()
    eng.generate([9, 8, 7, 6, 5], 3)
    turns = [t for t in kept(settled(eng)) if t["start_ns"] >= mark]
    assert len(turns) == 3
    first, *rest = turns
    assert (first["chunks"], first["admitted"], first["firsts_read"]) \
        == (1, 1, 1)
    assert first["llm.prefill.dispatch"] > 0 and first["steps_read"] == 0
    for t in rest:      # plain: a step read, no chunk, no first token
        assert (t["chunks"], t["admitted"], t["firsts_read"],
                t["steps_read"], t["rows"] <= 1) == (0, 0, 0, 1, True)
        assert t["llm.prefill.dispatch"] == 0
    for t in turns:
        assert sum(t[p] for p in TURN_PHASES) == t["turn_ns"]
        assert t["outstanding"] in (0, 1)
    # each kept turn holds the sync of the turn after it
    for t, nxt in zip(turns, turns[1:]):
        assert t["next_sync_ns"] == nxt["llm.step.sync"]


@pytest.mark.parametrize("warn_s", [0.2, 1.0], ids=["patched", "as-shipped"])
def test_a_sync_made_to_wait_is_one_plain_turn_with_the_next_turns_sync(
        eng, monkeypatch, caplog, warn_s):
    """``_sync`` waits 0.3 s on the third decode step it reads: ONE plain
    turn holds it, with the sync of the turn after it; it is logged where
    0.3 s is a stall (a patched threshold) and not at the shipped 1 s.
    (Seven turns in all, the fixture's two among them: the list keeps
    every one, so the turn after the stall is the next one kept.)"""
    monkeypatch.setattr(llm_engine, "SLOW_TURN_WARN_NS", int(warn_s * 1e9))
    real, seen = eng._sync, []

    def slow_once(d):
        if isinstance(d, _Step):
            seen.append(d)
            if len(seen) == 3:
                time.sleep(0.3)
        return real(d)

    monkeypatch.setattr(eng, "_sync", slow_once)
    before = settled(eng)
    mark = time.perf_counter_ns()
    with caplog.at_level(logging.WARNING, logger=llm_engine.__name__):
        assert len(eng.generate([4, 5, 6], 5)) == 5
        st = settled(eng)
    long_from = bucket_of(300_000_000)      # 256 ms to 512
    assert sum(st["turn_hist_plain"][long_from:]) \
        - sum(before["turn_hist_plain"][long_from:]) == 1
    assert sum(st["turn_hist_prefill"][long_from:]) \
        == sum(before["turn_hist_prefill"][long_from:])
    assert st["turns"] == 7 < SLOW_TURNS
    turns = [t for t in kept(st) if t["start_ns"] >= mark]
    assert len(turns) == 5
    [stall] = [t for t in turns if t["turn_ns"] >= 300_000_000]
    assert (stall["steps_read"], stall["chunks"], stall["firsts_read"]) \
        == (1, 0, 0)
    assert 300_000_000 <= stall["llm.step.sync"] <= stall["turn_ns"]
    after = turns[turns.index(stall) + 1]
    assert stall["next_sync_ns"] == after["llm.step.sync"] >= 0
    logged = [r for r in caplog.records if "stalled" in r.getMessage()]
    assert len(logged) == (1 if warn_s < 0.3 else 0)
    if logged:
        assert logged[0].levelno == logging.WARNING
        assert f"{stall['turn_ns'] * 1e-6:.1f} ms" in logged[0].getMessage()


def test_the_list_keeps_eight_and_forgets_after_its_minute(eng, monkeypatch):
    for _ in range(4):
        eng.generate([1, 2, 3, 4], 8)
    st = settled(eng)
    turns = kept(st)
    assert st["turns"] > 30 and len(turns) == SLOW_TURNS == 8
    # the eight LONGEST: no more than seven turns lie in a bucket past
    # the shortest kept one's
    shortest = min(t["turn_ns"] for t in turns)
    past = sum(sum(st[c][bucket_of(shortest) + 1:])
               for c in ("turn_hist_plain", "turn_hist_prefill"))
    assert past <= SLOW_TURNS - 1
    # a full list is entered by beating its shortest turn only
    assert eng._slow_floor == shortest
    # The reap forgets what is older than its minute (cut to 0.2 s here;
    # it comes every 5 s, and at the loop's next pass once ``_last_reap``
    # is wound back): the next turns enter without beating anything.
    monkeypatch.setattr(llm_engine, "SLOW_TURN_AGE_NS", 200_000_000)
    time.sleep(0.25)
    eng._last_reap = 0.0
    deadline = time.monotonic() + 10
    while eng.llm_stats()["slow_turns"] and time.monotonic() < deadline:
        time.sleep(0.02)
    assert eng.llm_stats()["slow_turns"] == [] and eng._slow_floor == -1
    reaped = eng.llm_stats()["turn_phase_ns"][
        TURN_PHASES.index("llm.loop.reap")]
    assert reaped > 0
    mark = time.perf_counter_ns()
    eng.generate([1, 2, 3], 3)
    turns = kept(settled(eng))
    assert len(turns) == 3 and all(t["start_ns"] >= mark for t in turns)


def test_the_engine_adds_no_thread_hook_or_callback_to_its_process():
    hooks = lambda: (list(gc.callbacks), sys.gettrace(), sys.getprofile(),  # noqa: E731
                     threading.gettrace(), threading.getprofile(),
                     sys.excepthook, threading.excepthook,
                     [signal.getsignal(s) for s in (
                         signal.SIGINT, signal.SIGTERM, signal.SIGALRM,
                         signal.SIGUSR1, signal.SIGUSR2, signal.SIGPROF)])
    names = lambda: {t.name for t in threading.enumerate()}  # noqa: E731
    before, before_hooks = names(), hooks()
    e = _engine()
    try:
        e.generate([1, 2, 3], 4)
        running = names()
        assert e.llm_stats()["turns"] >= 4
        assert hooks() == before_hooks
    finally:
        e.shutdown_engine()
    # (an earlier test's thread may end meanwhile: what counts is what came)
    assert running - before == {"llm-engine-loop"}
    assert not names() - before


def flat_ints(value) -> bool:
    if isinstance(value, list):
        return all(type(v) is int for v in value)
    return type(value) is int


def test_the_record_is_integers_and_under_2_kb_of_llm_stats(eng):
    for _ in range(3):
        eng.generate([3, 1, 4, 1, 5], 8)
    st = settled(eng)
    assert "queue_peak" not in st       # nothing read it (PR 57)
    for key in RECORD:
        assert flat_ints(st[key]), key
    without = {k: v for k, v in st.items() if k not in RECORD}
    grown = len(pickle.dumps(st)) - len(pickle.dumps(without))
    assert 0 < grown < 2048
    assert len(kept(st)) == SLOW_TURNS
    # at a chip's magnitudes (13 GB in use, turns of seconds, a window of
    # some thousand turns): the snapshots the readers' tests are given
    from benchmark.loading import load_json

    fx = load_json(os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "benchmark", "metrics", "fixtures", "turn_counters_two_stalls.json"))
    for run in fx["runs"].values():
        close = {k: run["close"][k] for k in RECORD}
        assert all(flat_ints(v) for v in close.values())
        assert len(close["slow_turns"]) == SLOW_TURNS * N
        assert len(pickle.dumps({**without, **close})) \
            - len(pickle.dumps(without)) < 2048


def test_a_copy_of_the_stats_is_one_turns_state(eng):
    """The loop puts its lists into ``stats_counters`` whole, in one
    ``update`` with the sums: a copy taken while it runs never holds a
    phase list that does not sum to its ``turn_ns``."""
    stop, bad = threading.Event(), []

    def watch():
        while not stop.is_set():
            st = eng.llm_stats()
            if sum(st["turn_phase_ns"]) != st["turn_ns"] or \
                    sum(st["turn_hist_plain"]) + \
                    sum(st["turn_hist_prefill"]) != st["turns"]:
                bad.append(st)

    t = threading.Thread(target=watch, name="watcher")
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    t.start()
    try:
        for _ in range(4):
            eng.generate([2, 7, 1, 8], 8)
    finally:
        sys.setswitchinterval(old)
        stop.set()
        t.join(timeout=10)
    assert not t.is_alive() and not bad
