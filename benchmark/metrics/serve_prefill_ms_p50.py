"""Median time of a request's prefill phase: admission to first token
available (program span ``llm.prefill``, requests of the window)."""

from benchmark import program_spans, stats


def read(run):
    return stats.median(
        program_spans.by_request(run, "llm.prefill").values())
