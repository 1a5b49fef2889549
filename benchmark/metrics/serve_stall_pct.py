"""Share of the window that the engine's loop spent in STALLED turns, from
the loop's own record in ``llm_stats()``. A turn is stalled where it took
at least 8 times what a turn of its work takes: the window's typical plain
turn (a plain turn read a decode step, dispatched no chunk and read no
first token, and is a step long, every one of them; the typical one is the
mean of the bucket that the median plain turn fell into,
``turn_hist_plain``, close minus open) and, for each chunk the turn
dispatched, the window's mean time a chunk (the prefill turns' time less a
plain turn each, over ``prefill_chunks``). So a long prompt's admission is
no stall, a plain step of seconds is one, and so is an admission of five
chunks that took 3 s. The stalled turns are taken one by one from
``slow_turns``, those that began inside the window: the loop keeps the
eight longest turns of the last minute, so a turn of seconds is always
among them, and a hold-up shorter than eight longer admissions is not
seen. 0 in a run that did not stall; a run that lost 3 s of its 30 in one
piece reads 10.

The earlier line ``stalls`` says the plain turns, the typical one, the
mean chunk, the stalled turns by class, and
``beyond_ms_between_snapshots``: the time in plain turns of the buckets
that lie wholly beyond 8 times the median's bucket, over everything
between the two snapshots. That holds the harness's own collection just
before the window opens (one plain turn of a quarter of a second in most
runs), which is why the value is not taken from the buckets. What a
stalled turn did, and what the device did next, is on the line
``slow_turns`` (``serve_turn_ms_max``). None where the program keeps no
such record or the window had no plain turn."""

from benchmark import program_counters
from benchmark.loading import sibling

turns = sibling(__file__, "serve_turn_ms_max.py")

TIMES = 8


def read(run):
    typical = turns.typical_plain(run)
    if typical is None:
        return None
    m, turn_ms, _ = typical
    d = turns.deltas(run)
    chunks = program_counters.window_delta(run, "prefill_chunks")
    # (a program that counts no chunks has its plain turns judged alone)
    chunk_ms = max(0.0, sum(d["turn_hist_prefill_ns"]) * 1e-6
                   - sum(d["turn_hist_prefill"]) * turn_ms) / chunks \
        if chunks else None
    stalled = [t for t in turns.kept_in_window(run)
               if (chunk_ms is not None or not t["chunks"])
               and t["turn_ns"] * 1e-6
               >= TIMES * (turn_ms + t["chunks"] * (chunk_ms or 0.0))]
    plain = [t["turn_ns"] for t in stalled if turns.is_plain(t)]
    window_ns = run.window_ns[1] - run.window_ns[0]
    stalled_ns = sum(t["turn_ns"] for t in stalled)
    run.say("stalls", plain_turns=sum(d["turn_hist_plain"]),
            plain_turn_ms_typical=turn_ms, chunk_ms_mean=chunk_ms,
            stalled_from_ms=TIMES * turn_ms,
            stalled_turns=len(stalled), stalled_plain_turns=len(plain),
            stalled_ms=stalled_ns * 1e-6, window_ms=window_ns * 1e-6,
            beyond_ms_between_snapshots=sum(
                d["turn_hist_plain_ns"][m + 4:]) * 1e-6,
            plain_hist=d["turn_hist_plain"])
    return 100.0 * stalled_ns / window_ns
