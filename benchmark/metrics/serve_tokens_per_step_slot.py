"""Tokens a decode step yielded an occupied slot, over the window: the
window's ``tokens_out`` less its ``admitted`` (a stream's first token comes
from its prefill, not from a step) over its ``occupancy_sum`` (program
counters of ``llm_stats()``). 1 for an engine whose step yields one token a
slot; ``1 + a`` at a draft acceptance of ``a`` where a step verifies one
draft a slot, less what ``max_tokens`` cut off inside a pair. The step's
time times this is the cell's rate: at another acceptance the same step
time gives the rate by one multiplication. None where the program keeps no
such counter (``draft_proposed``: the parent of the PR that added
drafting) or no step ran."""

from benchmark import program_counters


def read(run):
    tokens = program_counters.window_delta(run, "tokens_out")
    first = program_counters.window_delta(run, "admitted")
    slots = program_counters.window_delta(run, "occupancy_sum")
    if program_counters.window_delta(run, "draft_proposed") is None \
            or tokens is None or first is None or not slots:
        return None
    return (tokens - first) / slots
