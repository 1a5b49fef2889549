"""Median time a request spent outside the engine before its first chunk:
the client's send-to-first-chunk time minus that request's ``llm.queue``
and ``llm.prefill`` spans (router, handle, ``stream_call`` and its poll)."""

from benchmark import program_spans, stats


def read(run):
    if "requests" not in run.raw:
        return None
    queue = program_spans.by_request(run, "llm.queue")
    prefill = program_spans.by_request(run, "llm.prefill")
    outside = [(r["first_ns"] - r["sent_ns"]) * 1e-6
               - queue[r["trace_id"]] - prefill[r["trace_id"]]
               for r in run.raw["requests"]
               if r.get("trace_id") in queue and r["trace_id"] in prefill
               and r["first_ns"] is not None]
    return stats.median(outside)
