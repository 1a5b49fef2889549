"""Share of the WINDOW layers' rings' rows that the window's decode steps
read: the window's ``window_rows_read`` (program counter of ``llm_stats()``:
over each step's window layers and slots, the rows of the blocks the decode
attention fetched, whole blocks up to each slot's last live one) over its
``window_rows_held`` (window layers x slots x window a step);
``decode_ring_rows_read_pct`` is the same over both stacks. A slot whose
context has passed the window reads its whole ring, so this is the share
of slots past the window, more or less. None where the program keeps no
such counter."""

from benchmark import program_counters


def read(run):
    read_rows = program_counters.window_delta(run, "window_rows_read")
    held = program_counters.window_delta(run, "window_rows_held")
    if read_rows is None or not held:
        return None
    return 100.0 * read_rows / held
