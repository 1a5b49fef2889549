"""A token's way out of the engine, from the fan-out's append to the
client's chunk: the reductions the four delivery metrics share
(``serve_sync_overshoot_ms_p50``, ``serve_deliver_lag_ms_mean``,
``serve_polls_per_chunk``, ``serve_poll_rpc_ms_p50``).

They read what the program keeps where a token leaves it:

* the counters of ``llm_stats()`` (``next_calls``, ``next_empty``,
  ``deliver_*``, ``wake_defer_ns``), window close minus window open;
* the attributes ``stream_call`` writes on its ``serve.stream:<deployment>``
  span when a traced stream ends (``polls``, ``rpc_ns``, ``held_ns``);
* the annotations ``llm.next.drain`` of the pollers' threads beside the
  loop's ``llm.step.sync``, and the decode program's executions on the
  first device, on the profile's one clock.

A program that keeps none of them (the parent of the PR that added them)
gives ``None`` from every function here, and nothing is said.
"""

from __future__ import annotations

import bisect

from benchmark import program_counters, program_trace, stats

PHASES = ("llm.step.select", "llm.step.dispatch", "llm.step.sync",
          "llm.step.fanout")
DRAIN = "llm.next.drain"
STREAM_PREFIX = "serve.stream:"


# -- counters ------------------------------------------------------------------


def counter_deltas(run, *keys):
    """Window deltas of those ``llm_stats()`` counters, or None where the
    program keeps one of them not."""
    out = {k: program_counters.window_delta(run, k) for k in keys}
    return None if any(v is None for v in out.values()) else out


def deliver_lag_ms_mean(run):
    """Mean time a chunk lay in its stream's ``pending`` before a drain
    took it; the earlier line ``delivery`` says the histogram's delta, the
    share of the lag that the put-off wake-ups chose, and the share of the
    long-polls that came back empty."""
    d = counter_deltas(run, "deliver_chunks", "deliver_lag_ns",
                       "wake_defer_ns", "next_calls", "next_empty")
    if d is None or not d["deliver_chunks"]:
        return None
    # a program that keeps the counters names their buckets' edges
    from ray_tpu.serve.llm_engine import DELIVER_LAG_EDGES_MS

    a, b = run.counters["open"], run.counters["close"]
    hist = [y - x for x, y in zip(a.get("deliver_lag_hist", ()),
                                  b.get("deliver_lag_hist", ()))]
    mean_ms = d["deliver_lag_ns"] / d["deliver_chunks"] * 1e-6
    run.say("delivery", chunks=d["deliver_chunks"], lag_ms_mean=mean_ms,
            lag_hist=hist,
            lag_hist_edges_ms=list(DELIVER_LAG_EDGES_MS),
            deferred_share=d["wake_defer_ns"] / d["deliver_lag_ns"]
            if d["deliver_lag_ns"] else None,
            next_empty_share=d["next_empty"] / d["next_calls"]
            if d["next_calls"] else None)
    return mean_ms


def polls_per_chunk(run):
    """Calls of the engine's drain lanes a delivered chunk: ``next_calls``
    (and ``poll_calls``, where a program counts its batched lane's) over
    ``deliver_chunks``. 1.0 is the best a poller a stream can do, a lane
    that drains many streams a call reads below it."""
    d = counter_deltas(run, "next_calls", "deliver_chunks")
    if d is None or not d["deliver_chunks"]:
        return None
    batched = program_counters.window_delta(run, "poll_calls") or 0
    return (d["next_calls"] + batched) / d["deliver_chunks"]


# -- the stream's span -----------------------------------------------------------


def stream_polls(run) -> list:
    """``(polls, milliseconds a poll outside the engine)`` of every
    ``serve.stream:*`` span that ended inside the window and carries the
    poll tally."""
    if run.window_ns is None or not getattr(run, "program_spans", None):
        return []
    lo = run.window_ns[0] + run.epoch_offset_ns
    hi = run.window_ns[1] + run.epoch_offset_ns
    out = []
    for s in run.program_spans:
        at = s.get("attributes") or {}
        if s["name"].startswith(STREAM_PREFIX) and s.get("end_ns") \
                and lo <= s["end_ns"] <= hi and at.get("polls") \
                and "rpc_ns" in at and "held_ns" in at:
            out.append((at["polls"], (at["rpc_ns"] - at["held_ns"])
                        / at["polls"] * 1e-6))
    return out


def poll_rpc_ms_p50(run):
    """Median over the window's streams of one routed poll's way to the
    engine and back: ``(rpc_ns - held_ns) / polls``, two durations, each
    taken on its own end. The earlier line ``stream_polls`` says the
    streams, their polls and the polls a second."""
    streams = stream_polls(run)
    if not streams:
        return None
    ms = stats.median(m for _, m in streams)
    polls = sum(p for p, _ in streams)
    per_s = polls / run.window_s
    run.say("stream_polls", streams=len(streams),
            polls_per_stream=polls / len(streams), polls_per_s=per_s,
            rpc_ms_p50=ms, rpc_ms_p90=stats.percentile(
                [m for _, m in streams], 90))
    return ms


# -- the profile -----------------------------------------------------------------


def turns(pt: dict) -> list:
    """The decode turns of ``program_trace.step_turns`` with their edges
    kept: ``{"dispatch": start, "sync": (start, end)}`` of every turn
    whose four phases lie inside the traced window, by start."""
    by_thread: dict = {}
    for h in pt["host"]:
        if h[0] in PHASES:
            by_thread.setdefault(h[4], []).append(h)
    lo, hi = pt["window"] if pt["window"] else (float("-inf"), float("inf"))
    out = []
    for events in by_thread.values():
        cur: dict = {}
        for name, s, e, _, _ in events:
            if name == PHASES[0]:
                cur = {"start": s}
            cur[name] = (s, e)
            if name == PHASES[-1]:
                if all(k in cur for k in PHASES) and cur["start"] >= lo \
                        and e <= hi:
                    out.append({"dispatch": cur[PHASES[1]][0],
                                "sync": cur[PHASES[2]]})
                cur = {}
    return sorted(out, key=lambda t: t["dispatch"])


def executions(pt: dict, program: str) -> list:
    """``(start, end of its last operation)`` of each execution of
    ``program`` on the first device, by start; the execution's own end
    where the profile holds no operation inside it."""
    runs = [(s, e) for n, s, e in pt["modules"] if program in n]
    out, i, ops = [], 0, pt["ops"]
    for s, e in runs:
        while i < len(ops) and ops[i][1] < s:
            i += 1
        last, j = None, i
        while j < len(ops) and ops[j][1] <= e:
            if ops[j][2] <= e + 1e-9 and (last is None or ops[j][2] > last):
                last = ops[j][2]
            j += 1
        out.append((s, e if last is None else last))
    return out


def sync_overshoot_ms_p50(run):
    """Median, over decode turns, of how long ``llm.step.sync`` went on
    after the device had ended the step that turn dispatched (floored at
    0: a turn whose sync ended first waited for nothing). The loop's
    thread holds nothing in that stretch but the wish for the
    interpreter. The earlier line ``sync_overshoot`` says the tail, and a
    turn the median count and summed milliseconds of the pollers'
    ``llm.next.drain`` annotations (any thread) that began inside it."""
    pt = program_trace.of_run(run)
    program = run.params.get("device_programs", {}).get("decode")
    if pt is None or program is None:
        return None
    runs = executions(pt, program)
    starts = [r[0] for r in runs]
    drains = sorted((h[1], h[2]) for h in pt["host"] if h[0] == DRAIN)
    drain_starts = [s for s, _ in drains]
    over, inside, inside_ms = [], [], []
    for t in turns(pt):
        sync_s, sync_e = t["sync"]
        # the first execution that began after this turn's dispatch did
        i = bisect.bisect_left(starts, t["dispatch"])
        if i == len(runs) or runs[i][0] >= sync_e:
            continue
        done = runs[i][1]
        over.append(max(0.0, sync_e - done) * 1e3)
        a = bisect.bisect_left(drain_starts, max(done, sync_s))
        b = bisect.bisect_left(drain_starts, sync_e)
        inside.append(max(0, b - a))
        inside_ms.append(sum(e - s for s, e in drains[a:b]) * 1e3)
    if not over:
        return None
    run.say("sync_overshoot", turns=len(over),
            ms_p50=stats.median(over), ms_p90=stats.percentile(over, 90),
            ms_p99=stats.percentile(over, 99),
            drains_inside_p50=stats.median(inside),
            drains_inside_ms_p50=stats.median(inside_ms))
    return stats.median(over)
