"""Median wall time of one engine decode step: dispatch to tokens on the
host (program span ``llm.step``, steps that started inside the window)."""

from benchmark import program_spans, stats


def read(run):
    return stats.median(ms for _, ms in program_spans.in_window(
        run, "llm.step"))
