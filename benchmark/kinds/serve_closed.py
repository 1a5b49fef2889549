"""Kind ``serve_closed``: a closed loop of ``clients_per_slot * max_batch``
clients on one pool of requests, so the engine's admission queue is never
empty (offline generation, rollout callers). The pool leaves in its own
order, one request at a time: the first ``n_clients`` in set-up, then each
ended stream is followed at once by the pool's NEXT request, whichever
client ended. Sizes are one fixed sequence and generation is greedy to
exactly ``max_tokens``, so in units of engine steps a window is a replay:
which requests share an admission turn no longer follows a race between
client threads (``HandOut``). The window opens once as many streams as the
engine has slots have had a first token. Tokens and the gaps between
chunks are timed at the client."""

from __future__ import annotations

import bisect
import hashlib
import queue
import threading
import time

from benchmark import stats
from benchmark.loading import sibling

common = sibling(__file__, "serve_common.py")
TOLERANCES = common.TOLERANCES

PATTERN_REQUESTS = 200  # of the pool's head, on the line admission_pattern
QUANTILES = (50, 90, 95, 97, 98, 99, 99.5, 100)


class HandOut:
    """One order of hand-out. ``handle.stream`` returns a generator: the
    request reaches the engine's queue inside the generator's first
    ``next()``, which then waits for the first chunk, so neither a lock
    around ``handle.stream`` nor a sender thread that passes the stream on
    can order the queue. Instead one dispatcher gives request ``n`` to a
    free client thread and hands out nothing more until the engine says it
    has received ``n + 1`` requests (``received()``, from the engine's own
    counters): the queue gets the pool in index order whichever thread
    runs first. ``serve(request)`` is what a client does with a request
    (send it, time its stream, return its record)."""

    def __init__(self, requests, n_clients: int, serve, received,
                 fill_poll_s: float = 0.0005, poll_s: float = 0.001,
                 poll_max_s: float = 0.016):
        self.requests, self.n_clients = requests, n_clients
        self.serve, self.received = serve, received
        # A receipt comes within a millisecond or two, or, where a prefill
        # is running, when it ends (tens of milliseconds). While the first
        # n_clients requests go out the engine is already admitting, up to
        # prefill_rows a turn, and takes what has arrived: the dispatcher
        # has to stay ahead of it, so it looks every fill_poll_s (set-up
        # pays; with looks that backed off the queue ran dry after four
        # turns and every run's pattern differed, PR 33). From then on the
        # queue is deep and a late receipt delays nothing, so the wait
        # between two looks doubles from poll_s up to poll_max_s and a long
        # wait costs the engine's host few looks (each is a routed call).
        self.fill_poll_s = fill_poll_s
        self.poll_s, self.poll_max_s = poll_s, poll_max_s
        self.records: list = []      # of streams that have returned
        self.taken = 0               # requests handed out so far
        self.polls = 0               # calls of received() by the dispatcher
        self.error = None            # what ended the dispatcher early
        self._inbox = queue.SimpleQueue()
        self._ended = threading.Semaphore(0)
        self._stop = threading.Event()
        self._lock = threading.Lock()
        self.threads = [threading.Thread(
            target=self._client, daemon=True, name=f"bench-client-{i}")
            for i in range(n_clients)]
        self.threads.append(threading.Thread(
            target=self._dispatch, daemon=True, name="bench-dispatcher"))

    def start(self) -> None:
        for t in self.threads:
            t.start()

    def stop(self) -> None:
        """No further request leaves; idle clients and the dispatcher end
        (a client inside a stream ends when its stream does)."""
        self._stop.set()
        self._ended.release()
        for _ in range(self.n_clients):
            self._inbox.put(None)

    def snapshot(self) -> list:
        with self._lock:
            return list(self.records)

    def _client(self) -> None:
        while True:
            req = self._inbox.get()
            if req is None or self._stop.is_set():
                return
            rec = self.serve(req)
            with self._lock:
                self.records.append(rec)
            self._ended.release()

    def _dispatch(self) -> None:
        try:
            base = self.received()
            for n, req in enumerate(self.requests):
                if n >= self.n_clients:
                    self._ended.acquire()  # a stream ended: a client is free
                if self._stop.is_set():
                    return
                self.taken = n + 1
                self._inbox.put(req)
                fill = n < self.n_clients
                wait = self.fill_poll_s if fill else self.poll_s
                while True:
                    time.sleep(wait)
                    self.polls += 1
                    if self.received() >= base + n + 1:
                        break
                    if self._stop.is_set():
                        return
                    if not fill:
                        wait = min(2 * wait, self.poll_max_s)
        except Exception as e:  # noqa: BLE001 - said by the check below
            self.error = repr(e)


def received_by(handle) -> int:
    """Requests the engine has taken into its queue so far, never counted
    too high: ``admitted`` rises when a prefill hands its first token out
    and ``queued`` falls when the turn begins, so during a prefill the sum
    lacks the turn's requests and the dispatcher waits the turn out."""
    st = common.stats_now(handle)
    return st["admitted"] + st["queued"]


def gaps_in(records, window_ns) -> list:
    """``(start_ns, end_ns)`` of every gap between two chunks of a stream
    that ended inside the window."""
    lo, hi = window_ns
    return [(a, b) for r in records
            for a, b in zip(r["chunk_ns"], r["chunk_ns"][1:])
            if lo <= b <= hi]


def itl_quantiles(records, window_ns) -> dict:
    """Where the tail lies: the gaps of the window, their percentiles, and
    how many of them had 0, 1 and 2 or more OTHER requests' first chunks
    inside them (a first chunk marks the end of a prefill that stood in
    front of the gap's token), with each class's median; and the tokens
    delivered in each second of the window."""
    gaps = gaps_in(records, window_ns)
    ms = [(b - a) * 1e-6 for a, b in gaps]
    firsts = sorted(r["first_ns"] for r in records
                    if r["first_ns"] is not None)
    classes = {"0": [], "1": [], "2+": []}
    for (a, b), g in zip(gaps, ms):
        # a stream's own first chunk starts its first gap: (a, b] leaves it out
        inside = bisect.bisect_right(firsts, b) - bisect.bisect_right(firsts, a)
        classes["0" if inside == 0 else "1" if inside == 1 else "2+"].append(g)
    return {"gaps": len(ms),
            "ms": {f"p{q:g}": stats.percentile(ms, q) for q in QUANTILES},
            "tokens_by_second": tokens_by_second(records, window_ns),
            "prefills_inside": {k: len(v) for k, v in classes.items()},
            "ms_p50_by_prefills_inside": {
                k: stats.median(v) for k, v in classes.items()}}


def tokens_by_second(records, window_ns) -> list:
    """Tokens that reached the clients in each whole second of the window:
    where a slow run lost its tokens (a stall shows as one low second, a
    slow host as all of them)."""
    lo, hi = window_ns
    out = [0] * int((hi - lo) // 1_000_000_000)
    for r in records:
        for t, k in zip(r["chunk_ns"], r["chunk_tokens"]):
            if lo <= t < lo + len(out) * 1_000_000_000:
                out[int((t - lo) // 1_000_000_000)] += k
    return out


def step_ns(records) -> float | None:
    """The median gap between two chunks of a stream, over every stream
    that has returned: a decode step as the clients see it."""
    return stats.median(b - a for r in records
                        for a, b in zip(r["chunk_ns"], r["chunk_ns"][1:]))


def admission_pattern(records, step, n: int = PATTERN_REQUESTS) -> dict:
    """Which of the pool's first ``n`` requests were admitted in one turn
    with their predecessor: first chunks less than half a step apart (one
    turn's first chunks leave in one fan-out; two turns are a prefill and
    a step apart or more). ``hash`` is of the flags in index order; a
    request with no first chunk yet makes the pattern shorter.
    ``joined_max_ms`` and ``apart_min_ms`` say how far the half step lies
    from the distances on either side of it."""
    first = {r["i"]: r["first_ns"] for r in records}
    joined, near, far = [], [], []
    covered = 0
    for i in range(n):
        if first.get(i) is None:
            break
        covered += 1
        if not i:
            continue
        apart = abs(first[i] - first[i - 1])
        if apart < step / 2:
            joined.append(i)
            near.append(apart)
        else:
            far.append(apart)
    flags = ["0"] * covered
    for i in joined:
        flags[i] = "1"
    return {"requests": covered, "count": len(joined),
            "hash": hashlib.sha256("".join(flags).encode()).hexdigest()[:12],
            "joined": joined,
            "joined_max_ms": max(near) * 1e-6 if near else None,
            "apart_min_ms": min(far) * 1e-6 if far else None}


def sent_in_order(records) -> tuple:
    """Every index from 0 up was sent, and ``sent_ns`` rises with it."""
    by_i = sorted(records, key=lambda r: r["i"])
    if [r["i"] for r in by_i] != list(range(len(by_i))):
        return False, "the records' indices are not 0..n-1"
    bad = [(a["i"], b["i"]) for a, b in zip(by_i, by_i[1:])
           if not a["sent_ns"] < b["sent_ns"]]
    return not bad, f"{len(bad)} requests sent before their predecessor; " \
                    f"first: {bad[:3]}"


def admitted_in_order(records, tolerance_ns: float) -> tuple:
    """No request had its first chunk before a request of smaller index,
    beyond ``tolerance_ns``: the first chunks of one admission turn leave
    the engine together and are stamped by as many client threads, each
    when its long poll returns and it next holds the interpreter (some
    hundreds of microseconds, a few milliseconds under load), while two
    turns lie a prefill and a decode step apart. Half a step separates
    the two. Returns (ok, the largest such lead in ms, detail)."""
    latest, holder, bad, worst = None, None, [], 0.0
    for r in sorted(records, key=lambda r: r["i"]):
        if r["first_ns"] is None:
            continue
        if latest is not None and r["first_ns"] < latest:
            worst = max(worst, (latest - r["first_ns"]) * 1e-6)
            if r["first_ns"] < latest - tolerance_ns:
                bad.append((holder, r["i"], (latest - r["first_ns"]) * 1e-6))
        if latest is None or r["first_ns"] > latest:
            latest, holder = r["first_ns"], r["i"]
    return not bad, worst, (
        f"largest lead of a first chunk over one of smaller index "
        f"{worst:.3f} ms, limit {tolerance_ns * 1e-6:.3f} ms (half a "
        f"step); {len(bad)} over it; first (earlier index, index, ms): "
        f"{bad[:3]}")


def check_replay(run, records) -> None:
    """The two checks that say the replay held, and the two lines a later
    session reads the window's shape from. A CPU rehearsal runs engine,
    clients and XLA's own threads under one interpreter lock, and stamps
    first chunks several of its toy steps late (36 ms seen against a step
    of 5): it says what ``admitted_in_order`` read and is not held to it."""
    ok, detail = sent_in_order(records)
    run.check("sent_in_order", ok, detail)
    step = step_ns(records)
    if step is None:
        run.check("admitted_in_order", False, "no stream had two chunks")
        return
    ok, _, detail = admitted_in_order(records, step / 2)
    if run.rehearsal:
        run.say("admitted_in_order_not_held_in_rehearsal", ok=ok,
                detail=detail)
    else:
        run.check("admitted_in_order", ok, detail)
    run.say("admission_pattern", step_ms=step * 1e-6,
            **admission_pattern(records, step))
    run.say("itl_quantiles", **itl_quantiles(records, run.window_ns))


def run(run) -> None:
    engine = run.params["engine"]
    n_clients = run.traffic["clients_per_slot"] * engine["max_batch"]
    handle, hand = None, None
    try:
        handle = common.start_engine(run)
        requests = common.make_requests(run, run.traffic["pool_requests"])
        hand = HandOut(requests, n_clients,
                       lambda req: common.stream_request(run, handle, req),
                       lambda: received_by(handle))
        run.raw["requests"] = hand.records
        hand.start()
        deadline = time.perf_counter() + 600
        while common.stats_now(handle)["admitted"] < 1 + engine["max_batch"]:
            if time.perf_counter() > deadline or hand.error:
                raise RuntimeError(f"the engine's slots never filled "
                                   f"(dispatcher: {hand.error})")
            time.sleep(0.01)

        run.counters["open"] = common.stats_now(handle)
        run.open_window()
        common.trace_middle(run, handle)
        run.counters["close"] = common.stats_now(handle)
        run.close_window()

        run.check("request_pool_lasted",
                  hand.taken < len(requests) - 2 * n_clients,
                  f"all {len(requests)} requests of the pool were taken "
                  f"before the window closed")
        run.check("dispatcher_alive", hand.error is None, hand.error)
        hand.stop()
        run.raw["abandon"] = True  # streams in flight are not waited for
        lo, hi = run.window_ns
        terminal = [r for r in hand.snapshot()
                    if lo <= r["done_ns"] <= hi
                    or (r["error"] and "abandoned" not in r["error"])]
        common.finish(run, handle, terminal, shed_allowed=False)
        run.say("hand_out", taken=hand.taken, polls=hand.polls)
    finally:
        run.raw["abandon"] = True
        if hand is not None:
            hand.stop()
        common.stop_engine(run, handle, hand.threads if hand else ())
    # every client has returned: the records hold every request sent
    check_replay(run, hand.snapshot())
