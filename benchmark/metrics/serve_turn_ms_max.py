"""The longest turn of the engine's loop that BEGAN inside the window,
plain or prefill, from the loop's own record in ``llm_stats()``
(``slow_turns``: the longest turns of the last minute, at most eight, kept
by the loop's thread with ``time.perf_counter_ns``, the clock
``run.window_ns`` is on). A plain decode step's turn is a step long; a turn
that admitted a prompt holds its chunks; a turn of seconds with no chunk
in front of it is a stall. Unlike the profile's five seconds it covers the
whole window of every run.

The earlier line ``slow_turns`` prints the window's kept turns by start:
length, class, phases, what each did (chunks dispatched, requests
admitted, rows, steps and first tokens read, results outstanding),
``next_sync_ms`` (the ``llm.step.sync`` of the turn AFTER it: the step
ahead was enqueued before a long sync began, so near nothing there says
the device ran on time and only this turn's hand-over was late, and a
sync as long as ``plain_sync_ms_typical`` says the device itself stood
still), the allocator's numbers at the turn's end with how far they had
moved since the loop's last reap and, in a traced run, where the turn
lies against the profile and the share of it the first device was busy.

This file also holds what the three readers of the record share
(``serve_stall_pct`` and ``serve_loop_host_pct`` load it as a sibling).
A program that keeps no such record (the parent of the PR that added it)
gives None from every function here, and nothing is said."""

from __future__ import annotations

import bisect

from benchmark import program_trace, stats

HISTS = ("turn_hist_plain", "turn_hist_plain_ns", "turn_hist_prefill",
         "turn_hist_prefill_ns")
DID = ("chunks", "admitted", "rows", "steps_read", "firsts_read",
       "outstanding")
# the loop's own work in a turn without a chunk (``plain_sync_ms_typical``)
HOST_PHASES = ("llm.admit", "llm.step.select", "llm.step.dispatch",
               "llm.step.fanout", "other")


def record(run):
    """``(open, close, names)`` where both snapshots of ``llm_stats()``
    hold the loop's record, ``names`` being the engine's own
    (``TURN_PHASES``, ``TURN_EDGES_MS``, ``SLOW_TURN_FIELDS``,
    ``MEMORY_KEYS``); else None."""
    a, b = run.counters.get("open"), run.counters.get("close")
    if not a or not b or getattr(run, "window_ns", None) is None:
        return None
    keys = ("turns", "turn_ns", "turn_phase_ns", "slow_turns", *HISTS)
    if any(k not in a or k not in b for k in keys):
        return None
    # a program that keeps the record names its phases, edges and fields
    from ray_tpu.serve import llm_engine

    return a, b, llm_engine


def deltas(run):
    """Close minus open of the record's sums: ``{"turns", "turn_ns",
    "phase_ns": {name: ns}, <each of HISTS>: [...]}``; or None."""
    rec = record(run)
    if rec is None:
        return None
    a, b, names = rec
    out = {k: b[k] - a[k] for k in ("turns", "turn_ns")}
    out["phase_ns"] = {p: y - x for p, x, y in zip(
        names.TURN_PHASES, a["turn_phase_ns"], b["turn_phase_ns"])}
    for k in HISTS:
        out[k] = [y - x for x, y in zip(a[k], b[k])]
    return out


def median_bucket(counts) -> int | None:
    """The bucket the median of the counted turns fell into."""
    n, seen = sum(counts), 0
    for i, k in enumerate(counts):
        seen += k
        if k and 2 * seen >= n:
            return i
    return None


def typical_plain(run):
    """``(bucket, turn ms, sync ms)`` of the window's typical plain turn:
    the bucket of the median plain turn, the mean of the turns in it, and
    that less the loop's own phases a turn (a plain turn is its sync and
    the host's work around it); or None where the window had no plain
    turn."""
    d = deltas(run)
    m = None if d is None else median_bucket(d["turn_hist_plain"])
    if m is None:
        return None
    turn_ms = d["turn_hist_plain_ns"][m] / d["turn_hist_plain"][m] * 1e-6
    host_ms = sum(d["phase_ns"][p] for p in HOST_PHASES) \
        / d["turns"] * 1e-6
    return m, turn_ms, max(0.0, turn_ms - host_ms)


def kept_in_window(run):
    """The kept turns that began inside the window, by start, each a dict
    of ``SLOW_TURN_FIELDS``; or None."""
    rec = record(run)
    if rec is None:
        return None
    _, b, names = rec
    fields = names.SLOW_TURN_FIELDS
    flat = b["slow_turns"]
    lo, hi = run.window_ns
    turns = [dict(zip(fields, flat[i:i + len(fields)]))
             for i in range(0, len(flat), len(fields))]
    return sorted((t for t in turns if lo <= t["start_ns"] < hi),
                  key=lambda t: t["start_ns"])


def is_plain(t) -> bool:
    """It read a step with no prefill in front of it."""
    return bool(t["steps_read"]) and not t["chunks"] \
        and not t["firsts_read"]


def _profile(run):
    """What places a turn in a traced run's profile: the profile's window
    and the first device's busy intervals on the profile's clock, and
    what a ``perf_counter_ns`` reading lacks to be on it (the anchor is
    ``llm.step.dispatch``'s ``epoch_ns``); or None."""
    pt = program_trace.of_run(run)
    trace = getattr(run, "trace", None)
    anchors = None if pt is None else program_trace.clock_anchors(pt)
    if not anchors or pt["window"] is None or not trace \
            or not trace["devices"]:
        return None
    busy = stats.merge((s, e) for _, s, e, _ in trace["devices"][0]["ops"])
    return {"window": pt["window"], "busy": busy,
            "starts": [s for s, _ in busy],
            "to_profile_ns": run.epoch_offset_ns - anchors["offset_ns"]}


def _against_profile(prof, t) -> dict:
    """Where the turn lies against the profile's window, and for one
    inside it the share of the turn the first device was busy."""
    s = (t["start_ns"] + prof["to_profile_ns"]) * 1e-9
    e = s + t["turn_ns"] * 1e-9
    lo, hi = prof["window"]
    if e <= lo or s >= hi:
        return {"profile": "before" if e <= lo else "after"}
    if s < lo or e > hi:
        return {"profile": "across_start" if s < lo else "across_stop"}
    i = max(0, bisect.bisect_right(prof["starts"], s) - 1)
    busy = 0.0
    for bs, be in prof["busy"][i:]:
        if bs >= e:
            break
        busy += max(0.0, min(be, e) - max(bs, s))
    return {"profile": "inside",
            "device_busy_pct": 100.0 * busy / (e - s) if e > s else None}


def _said(t, names, lo, prof) -> dict:
    """One kept turn as the line ``slow_turns`` prints it: milliseconds,
    a phase only where the turn spent a twentieth of a millisecond in
    it, the allocator's numbers only where the backend gave them."""
    out = {"at_s": round((t["start_ns"] - lo) * 1e-9, 3),
           "ms": t["turn_ns"] * 1e-6,
           "class": "plain" if is_plain(t) else "prefill",
           "phases_ms": {p: t[p] * 1e-6 for p in names.TURN_PHASES
                         if t[p] >= 50_000},
           "did": {k: t[k] for k in DID},
           "next_sync_ms": None if t["next_sync_ns"] < 0
           else t["next_sync_ns"] * 1e-6}
    if any(t[k] >= 0 for k in names.MEMORY_KEYS):
        out["memory"] = {k: t[k] for k in names.MEMORY_KEYS}
        out["memory_since_reap"] = {
            k: t[k + "_since_reap"] for k in names.MEMORY_KEYS}
    if prof is not None:
        out.update(_against_profile(prof, t))
    return out


def read(run):
    turns = kept_in_window(run)
    if turns is None:
        return None
    d = deltas(run)
    _, _, names = record(run)
    typical = typical_plain(run)
    prof = _profile(run) if turns else None
    run.say("slow_turns", kept_in_window=len(turns),
            turns_in_window=d["turns"],
            plain_turn_ms_typical=typical and typical[1],
            plain_sync_ms_typical=typical and typical[2],
            turns=[_said(t, names, run.window_ns[0], prof) for t in turns])
    if turns:
        return max(t["turn_ns"] for t in turns) * 1e-6
    # Eight longer turns of the minute before the window hold the list:
    # the mean of the window's highest bucket is all the record says.
    top, cls = max(
        (max((i for i, k in enumerate(d[c]) if k), default=-1), c)
        for c in ("turn_hist_plain", "turn_hist_prefill"))
    return None if top < 0 else d[cls + "_ns"][top] / d[cls][top] * 1e-6
