"""Share of the K/V rings' rows that the window's decode steps read: the
window's ``ring_rows_read`` (program counter of ``llm_stats()``: over each
step's ring-holding layers and slots, the rows of the blocks the decode
attention fetched, whole blocks up to each slot's last live one) over its
``ring_rows_held`` (layers x slots x ring length a step). 100 where a step
reads every ring whole, whatever the slots' contexts. None where the
program keeps no such counter."""

from benchmark import program_counters


def read(run):
    read_rows = program_counters.window_delta(run, "ring_rows_read")
    held = program_counters.window_delta(run, "ring_rows_held")
    if read_rows is None or not held:
        return None
    return 100.0 * read_rows / held
