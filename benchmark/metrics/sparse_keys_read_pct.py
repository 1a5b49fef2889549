"""Share of the keys a decode step's queries COULD read that they picked:
the window's ``sparse_keys_selected`` (program counter of ``llm_stats()``:
over each step's layers and live slots, the size of the query's set,
``min(topk, context)``) over its ``sparse_keys_eligible`` (the live rows,
the token's own among them). ``topk`` over the mean context, more or less:
what share of a dense step's K and V rows the gather moves. None where the
program keeps no such counter."""

from benchmark import program_counters


def read(run):
    picked = program_counters.window_delta(run, "sparse_keys_selected")
    could = program_counters.window_delta(run, "sparse_keys_eligible")
    if picked is None or not could:
        return None
    return 100.0 * picked / could
