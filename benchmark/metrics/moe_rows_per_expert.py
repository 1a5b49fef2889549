"""Rows a hit expert took in a decode step, on average: the window's
``expert_rows`` (token-expert pairs that landed on experts held here) over
its ``experts_hit`` (program counters of ``llm_stats()``). It says how far
an expert's weights are amortised: at one row an expert's matrices are
read for a single token. None where the program keeps no such counter."""

from benchmark import program_counters


def read(run):
    rows = program_counters.window_delta(run, "expert_rows")
    hit = program_counters.window_delta(run, "experts_hit")
    if rows is None or not hit:
        return None
    return rows / hit
