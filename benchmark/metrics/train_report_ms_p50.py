"""Median time of ``session.report`` in the trainer's loop (the program's
annotation ``train.report``, profiler trace, host plane)."""

from benchmark import program_trace, stats


def read(run):
    pt = program_trace.of_run(run)
    return stats.median(program_trace.span_ms(pt, "train.report")) \
        if pt else None
