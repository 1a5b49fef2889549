"""Median time a request waited in the engine's admission queue (program
span ``llm.queue``, requests of the window)."""

from benchmark import program_spans, stats


def read(run):
    return stats.median(program_spans.by_request(run, "llm.queue").values())
