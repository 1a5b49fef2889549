"""The closed loop's one order of hand-out (``kinds/serve_closed.HandOut``)
against a fake engine whose streams end in a scripted order, the two checks
and the two lines that say a window was a replay, on records made by hand,
and the rule PR 33's bounds were set by. CPU, no engine: these say nothing
about a device."""

import os
import threading
import time

import pytest

from benchmark import stats
from benchmark.loading import load_module

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
closed = load_module(os.path.join(REPO, "benchmark", "kinds",
                                  "serve_closed.py"))
MS = 1_000_000


class FakeEngine:
    """Streams that reach the queue after a scripted delay (the race
    between client threads) and end when the script says so. A request's
    first chunk comes 10 ms after its predecessor's IN THE QUEUE, so
    ``admitted_in_order`` reads the order the queue received."""

    def __init__(self, n: int, delay_s: dict):
        self.queue, self.lock = [], threading.Lock()
        self.delay_s = delay_s
        self.end = [threading.Event() for _ in range(n)]
        self.in_flight, self.most_in_flight = 0, 0

    def serve(self, req: dict) -> dict:
        sent = time.perf_counter_ns()
        time.sleep(self.delay_s.get(req["i"], 0.0))
        with self.lock:
            self.queue.append(req["i"])
            first = 10 * MS * len(self.queue)
            self.in_flight += 1
            self.most_in_flight = max(self.most_in_flight, self.in_flight)
        assert self.end[req["i"]].wait(20), "the script never ended it"
        with self.lock:
            self.in_flight -= 1
        return {"i": req["i"], "sent_ns": sent, "first_ns": first,
                "chunk_ns": [first, first + 5 * MS], "error": None}

    def received(self) -> int:
        with self.lock:
            return len(self.queue)

    def wait_for(self, n: int) -> None:
        deadline = time.time() + 20
        while self.received() < n:
            assert time.time() < deadline, f"the queue never got {n}"
            time.sleep(0.001)


def drive(script, delay_s, confirm=True, n_clients=4, pool=14):
    """Run the hand-out over ``pool`` requests; ``script`` is a list of
    groups of indices whose streams end together, released in the order
    written. ``confirm=False`` is the control: a request counts as
    received the moment it is handed out. Returns the fake engine and the
    hand-out."""
    engine = FakeEngine(pool, delay_s)
    hand = closed.HandOut([{"i": i} for i in range(pool)], n_clients,
                          engine.serve, engine.received, fill_poll_s=0.0002,
                          poll_s=0.0002, poll_max_s=0.001)
    if not confirm:
        hand.received = lambda: hand.taken
    hand.start()
    sent = n_clients
    engine.wait_for(sent)
    for group in script:
        for i in group:
            engine.end[i].set()
        sent = min(pool, sent + len(group))
        engine.wait_for(sent)
    hand.stop()
    for e in engine.end:
        e.set()
    for t in hand.threads:
        t.join(20)
        assert not t.is_alive()
    assert hand.error is None
    return engine, hand


# Streams 0-3 fill the four clients. Then: one ends; two end at once,
# released in either order; three at once; and so on. The delays make the
# thread that gets the EARLIER request the slower one to reach the queue.
SCRIPTS = {
    "two_at_once_low_first": [[1], [0, 2], [3], [4, 5], [6], [7, 8, 9]],
    "two_at_once_high_first": [[1], [2, 0], [3], [5, 4], [6], [9, 8, 7]],
    "all_at_once": [[3, 2, 1, 0], [7, 6, 5, 4]],
}
DELAYS = {0: 0.02, 2: 0.01, 4: 0.03, 5: 0.0, 6: 0.02, 8: 0.03, 10: 0.02,
          12: 0.03}


@pytest.mark.parametrize("name", sorted(SCRIPTS))
def test_the_queue_receives_the_pool_in_index_order(name):
    engine, hand = drive(SCRIPTS[name], DELAYS)
    assert engine.queue == list(range(len(engine.queue)))
    assert len(engine.queue) >= 4 + sum(len(g) for g in SCRIPTS[name])
    assert engine.most_in_flight == 4          # never more than the clients
    records = hand.snapshot()
    assert closed.sent_in_order(records)[0]
    assert closed.admitted_in_order(records, 5 * MS)[0]


@pytest.mark.parametrize("name", sorted(SCRIPTS))
def test_without_the_engines_receipt_the_race_decides(name):
    """The control: a dispatcher that takes every request as received at
    once hands out in order all the same, but the slower thread's request
    reaches the queue late, and ``admitted_in_order`` says so."""
    engine, hand = drive(SCRIPTS[name], DELAYS, confirm=False)
    assert sorted(engine.queue) == list(range(len(engine.queue)))
    assert engine.queue != sorted(engine.queue)
    records = hand.snapshot()
    assert closed.sent_in_order(records)[0]
    ok, worst_ms, _ = closed.admitted_in_order(records, 5 * MS)
    assert not ok and worst_ms >= 10


def record(i, sent_ms, first_ms, gaps_ms=()):
    chunks = [first_ms * MS]
    for g in gaps_ms:
        chunks.append(chunks[-1] + int(g * MS))
    return {"i": i, "sent_ns": int(sent_ms * MS), "first_ns": chunks[0],
            "chunk_ns": chunks, "chunk_tokens": [1] * len(chunks),
            "error": None}


def in_order():
    return [record(i, sent_ms=i, first_ms=100 + 30 * i) for i in range(6)]


@pytest.mark.parametrize("change, sent_ok, admitted_ok", [
    (lambda rs: None, True, True),
    # request 3 sent before request 2
    (lambda rs: rs[3].update(sent_ns=rs[2]["sent_ns"] - 1), False, True),
    # two requests stamped at the same instant are not "in order" either
    (lambda rs: rs[3].update(sent_ns=rs[2]["sent_ns"]), False, True),
    # an index is missing: the pool was not handed out one after another
    (lambda rs: rs.pop(2), False, True),
    # request 4 admitted a turn before request 3 (30 ms earlier)
    (lambda rs: rs[4].update(first_ns=rs[3]["first_ns"] - 30 * MS),
     True, False),
    # two requests of one turn stamped 2 ms apart the wrong way round:
    # inside the tolerance of 5 ms
    (lambda rs: rs[4].update(first_ns=rs[3]["first_ns"] - 2 * MS),
     True, True),
    # a request still queued when the run ended has no first chunk
    (lambda rs: rs[5].update(first_ns=None, chunk_ns=[]), True, True),
    # a late request is held against the LATEST of all before it
    (lambda rs: rs[5].update(first_ns=rs[1]["first_ns"]), True, False),
])
def test_the_two_order_checks(change, sent_ok, admitted_ok):
    records = in_order()
    change(records)
    assert closed.sent_in_order(records)[0] is sent_ok
    ok, worst_ms, detail = closed.admitted_in_order(records, 5 * MS)
    assert ok is admitted_ok, detail
    assert (worst_ms > 5) is (not admitted_ok)


def window_of_three():
    """Three streams of 20 ms steps. Stream 0 (first chunk at 0) runs
    alone until stream 1's prefill stands in front of its third token
    (gap 45 ms, first chunk of 1 inside at 80); streams 2 and 3 are
    admitted in one turn (first chunks at 200 and 200.4), in front of the
    eighth token of stream 0 and the fifth of stream 1 (gaps 70 ms)."""
    r0 = record(0, 0, 0, [20, 20, 45, 20, 20, 20, 20, 70, 20])
    r1 = record(1, 1, 80, [5, 20, 20, 20, 75.4, 20])
    r2 = record(2, 2, 200, [55, 20])
    r3 = record(3, 3, 200.4, [54.6, 20])
    return [r0, r1, r2, r3]


def test_itl_quantiles_counts_the_prefills_inside_each_gap():
    records = window_of_three()
    q = closed.itl_quantiles(records, (0, 10_000 * MS))
    assert q["gaps"] == 9 + 6 + 2 + 2
    # one prefill inside: stream 0's 45 ms gap (stream 1's first chunk at
    # 80 lies in (40, 85]). Two inside: stream 0's 70 ms gap (165, 235]
    # and stream 1's 75.4 ms gap (145, 220.4]. A stream's own first chunk
    # opens its first gap and is not inside it; stream 2's first gap
    # (200, 255] holds stream 3's first chunk, stream 3's holds no other.
    assert q["prefills_inside"] == {"0": 15, "1": 2, "2+": 2}
    assert q["ms_p50_by_prefills_inside"]["0"] == pytest.approx(20)
    assert q["ms_p50_by_prefills_inside"]["1"] == pytest.approx(50)
    assert q["ms_p50_by_prefills_inside"]["2+"] == pytest.approx(72.7)
    assert set(q["ms"]) == {"p50", "p90", "p95", "p97", "p98", "p99",
                            "p99.5", "p100"}
    assert q["ms"]["p100"] == pytest.approx(75.4)
    assert q["ms"]["p50"] == pytest.approx(20)
    gaps = sorted([20, 20, 45, 20, 20, 20, 20, 70, 20, 5, 20, 20, 20, 75.4,
                   20, 55, 20, 54.6, 20])
    assert q["ms"]["p99"] == pytest.approx(stats.percentile(gaps, 99))
    # the window cuts by the gap's END: of stream 0 the gaps that end at
    # 105, 125, 145, 165 and 235, of stream 1 those at 105, 125, 145 and
    # 220.4, of streams 2 and 3 (first gaps end at 255) none
    cut = closed.itl_quantiles(records, (100 * MS, 240 * MS))
    assert cut["gaps"] == 5 + 4
    assert cut["prefills_inside"] == {"0": 7, "1": 0, "2+": 2}
    # tokens by whole second of a window from 0 to 0.26 s: none whole; of
    # one from 0 to 1.05 s: all 23 chunks in its one whole second
    assert cut["tokens_by_second"] == []
    assert closed.itl_quantiles(records, (0, 1050 * MS))[
        "tokens_by_second"] == [10 + 7 + 3 + 3]


def test_admission_pattern_names_who_joined_a_predecessors_turn():
    records = window_of_three()
    step = closed.step_ns(records)
    assert step == 20 * MS
    p = closed.admission_pattern(records, step)
    assert p["requests"] == 4 and p["count"] == 1 and p["joined"] == [3]
    assert p["joined_max_ms"] == pytest.approx(0.4)
    assert p["apart_min_ms"] == pytest.approx(80)
    # the same turns stamped a little otherwise give the same hash; another
    # grouping gives another
    moved = window_of_three()
    moved[3]["first_ns"] += 3 * MS
    assert closed.admission_pattern(moved, step)["hash"] == p["hash"]
    moved[3]["first_ns"] += 30 * MS
    other = closed.admission_pattern(moved, step)
    assert other["joined"] == [] and other["hash"] != p["hash"]
    # only the pool's head counts, and a request without a first chunk
    # ends the pattern
    assert closed.admission_pattern(records, step, n=3)["joined"] == []
    records[2]["first_ns"] = None
    assert closed.admission_pattern(records, step)["requests"] == 2


# The spreads PR 33's bounds rest on (PERF.md section 2; my chip runs,
# PR 33): two sets of 6 a cell on the same six seeds; the widest decides.
MEASURED = {
    # GPT-2 XL sets 1 and 2, Nemotron sets 1 and 2
    "serve_out_tokens_per_s": ([0.012867, 0.007580, 0.008468, 0.014423],
                               0.04),
    "itl_p99_ms": ([0.014193, 0.007059], 0.04),
}


@pytest.mark.parametrize("spreads, bound", [
    ([0.001, 0.002], 0.01),          # never under 1 %
    ([0.004], 0.01),                 # 2.5 x 0.4 % is the floor itself
    ([0.0041], 0.015),               # just over it: the next half percent
    ([0.0078, 0.0052, 0.006], 0.02),
    ([0.008], 0.02),                 # 2.0 % exactly stays 2.0 %
    ([0.0187, 0.0315], 0.08),        # PR 26's two sets of the decode cell
    *MEASURED.values(),
])
def test_the_bound_rule(spreads, bound):
    got = stats.bound_from_spreads(spreads)
    assert got == pytest.approx(bound)
    # every set's spread is under half of what the bound allows (the
    # driver's test of tightness) and the bound is under eight times the
    # widest (its test of looseness), unless it is the floor
    assert all(s <= got / 2 for s in spreads)
    assert got <= 8 * max(spreads) or got == 0.01


def test_the_drivers_two_readings_of_two_sets():
    # a set's farthest run is left out for tightness, not for looseness
    steady = [100.0, 100.2, 99.8, 100.1, 99.9, 100.0]
    one_far = [100.0, 100.2, 99.8, 100.1, 99.9, 90.0]
    tight, loose = stats.driver_spreads([steady, one_far])
    five = [100.0, 100.2, 99.8, 100.1, 99.9]
    assert tight == pytest.approx(
        (stats.spread([100.0, 99.8, 100.1, 99.9, 100.0])
         + stats.spread(five)) / 2)
    assert loose == pytest.approx(stats.spread(one_far))
    assert loose > 5 * tight


def test_spread_is_the_quartiles_distance_over_the_median():
    values = [373.1, 374.0, 372.2, 375.5, 373.6, 371.9]
    import statistics

    q = statistics.quantiles(values, n=4)
    assert stats.spread(values) == pytest.approx(
        (q[2] - q[0]) / statistics.median(values))
    assert stats.spread([1.0]) is None
