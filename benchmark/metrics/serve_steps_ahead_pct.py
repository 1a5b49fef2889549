"""Share of the window's decode steps that the engine's loop dispatched
while another step was dispatched and unread: the window's ``steps_ahead``
over its ``steps`` (program counters of ``llm_stats()``, both counted where
a step is read). 100 where every turn enqueues the next step before it
reads the last one, so that the device never waits for the read, the
fan-out and the runtime's hand-over; 0 where each step is read before the
next is enqueued. The earlier line ``steps_ahead`` says what that costs:
``rows_dropped``, the slot-rows a step computed for a request that had
ended, been cancelled or been shed before the step was read, over the
window's slot-rows (those that served a request, ``occupancy_sum``, and
those dropped). None where the program keeps no such counter."""

from benchmark import program_counters


def read(run):
    ahead = program_counters.window_delta(run, "steps_ahead")
    steps = program_counters.window_delta(run, "steps")
    if ahead is None or not steps:
        return None
    dropped = program_counters.window_delta(run, "rows_dropped")
    served = program_counters.window_delta(run, "occupancy_sum")
    if dropped is not None and served is not None and served + dropped:
        run.say("steps_ahead", steps=steps, ahead=ahead,
                rows_dropped=dropped, slot_rows=served + dropped,
                rows_dropped_pct=100.0 * dropped / (served + dropped))
    return 100.0 * ahead / steps
