"""Share of the held experts that a decode step hit: the window's
``experts_hit`` (program counter of ``llm_stats()``: over each step's
expert layers, the held experts that took at least one row) over steps x
``expert_layers`` x ``experts_held``. A step reads the weights of the
experts it hit and of no other. None where the program keeps no such
counter."""

from benchmark import program_counters


def read(run):
    hit = program_counters.window_delta(run, "experts_hit")
    steps = program_counters.window_delta(run, "steps")
    close = run.counters.get("close") or {}
    possible = close.get("expert_layers", 0) * close.get("experts_held", 0)
    if hit is None or not steps or not possible:
        return None
    return 100.0 * hit / (steps * possible)
