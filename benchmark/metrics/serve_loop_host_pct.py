"""Of the time the engine's loop spent between the window's two snapshots
of ``llm_stats()``, the share that is neither ``llm.step.sync`` (the loop
waits for the device's result) nor ``llm.loop.wait`` (it waits for work):
the host's own work, admission, the chunks' and the step's dispatch,
selection, fan-out, the reap and what lies between the phases, over ALL
the window's turns (``turn_phase_ns``, which the loop's thread adds up
once a pass with ``time.perf_counter_ns`` at the edges of the device
spans of the same names). ``serve_step_host_ms_p50`` times the same work
over the profile's five seconds, a turn at a time. With the loop one step
ahead the device waits for none of it while the share is under 100; at
100 the device waits for the host whatever runs ahead. The earlier line
``loop_phases`` prints every phase's share, the turns, and how much of the
window the loop's passes cover (the snapshots are taken just outside it).
None where the program keeps no such record."""

from benchmark.loading import sibling

turns = sibling(__file__, "serve_turn_ms_max.py")

WAITS = ("llm.step.sync", "llm.loop.wait")


def read(run):
    d = turns.deltas(run)
    if d is None or not d["turn_ns"]:
        return None
    total = d["turn_ns"]
    window_ns = run.window_ns[1] - run.window_ns[0]
    run.say("loop_phases", turns=d["turns"], loop_ms=total * 1e-6,
            window_ms=window_ns * 1e-6,
            covers_window_pct=100.0 * total / window_ns,
            pct={p: 100.0 * ns / total for p, ns in d["phase_ns"].items()})
    return 100.0 * (total - sum(d["phase_ns"][p] for p in WAITS)) / total
