"""Share of the drafts that the window's verify steps accepted: the window's
``draft_accepted`` over its ``draft_proposed`` (program counters of
``llm_stats()``: a drafting engine proposes one draft a slot a step, and a
draft counts as accepted where the DEVICE's comparison of it with the main
stack's greedy token said so and the step yielded two tokens; nothing on
the host decides it). Seeded random weights read near chance, one in the
vocabulary: what a trained checkpoint gives waits for one. None where the
program keeps no such counter or drafted nothing."""

from benchmark import program_counters


def read(run):
    accepted = program_counters.window_delta(run, "draft_accepted")
    proposed = program_counters.window_delta(run, "draft_proposed")
    if accepted is None or not proposed:
        return None
    return 100.0 * accepted / proposed
