"""The program's own spans (``ray_tpu.util.tracing``: ``llm.queue``,
``llm.prefill``, ``llm.step``) that started inside the measured window.
They carry the epoch clock; the window is on ``perf_counter``, and the run
recorded the offset between the two."""

from __future__ import annotations


def in_window(run, name: str) -> list:
    """``(trace_id, milliseconds)`` of each finished span of that name."""
    if run.window_ns is None or not getattr(run, "program_spans", None):
        return []
    lo = run.window_ns[0] + run.epoch_offset_ns
    hi = run.window_ns[1] + run.epoch_offset_ns
    return [(s["trace_id"], (s["end_ns"] - s["start_ns"]) * 1e-6)
            for s in run.program_spans
            if s["name"] == name and s.get("end_ns")
            and lo <= s["start_ns"] <= hi]


def by_request(run, name: str) -> dict:
    """trace id -> total milliseconds of the named span (a retried phase
    has more than one span)."""
    out: dict = {}
    for trace_id, ms in in_window(run, name):
        out[trace_id] = out.get(trace_id, 0.0) + ms
    return out
