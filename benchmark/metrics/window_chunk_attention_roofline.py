"""A prompt chunk's attention over the WINDOW layers' rings as a share of
its roofline: the least time the chip could take for what those layers'
attention of one chunk REQUIRES, over the chunk program's busy time under
``attn_window`` an execution (device trace).

The work is the family's ``window_chunk_attention_work``: a layer's scores
and weighted sums of the chunk's queries over a FILLED ring's rows and the
chunk's own, the ring's and the chunk's K and V rows read once, the queries
read and the sums written once. It is the same work whatever implements the
op (a scores array in memory, a float32 copy of a ring and a masked row are
the implementation's, and lower the share), and the work of a chunk whose
ring has filled: a chunk early in a prompt requires less and takes the
program as long, so the share is an upper bound on no execution and cannot
pass 100. None where the family has no such function or the profile holds
no operation of the chunk program under ``attn_window``."""

from benchmark import peaks, shapes
from benchmark.loading import sibling

window = sibling(__file__, "decode_window_attention_time_pct.py")


def read(run):
    work = getattr(run.family, "window_chunk_attention_work", None)
    program = run.params.get("device_programs", {}).get("prefill")
    chunk = (run.counters.get("close") or {}).get("prefill_chunk")
    if work is None or run.trace is None or not chunk:
        return None
    got = window.seconds(run, program)
    if got is None:
        return None
    totals, executions = got
    busy = totals["attn_window"] / executions
    ops, io = work(run.config, chunk)
    least, bound = shapes.roofline_seconds(
        ops, io, peaks.peak(run.device_kind))
    run.say("window_chunk_attention_roofline", ops_per_chunk=ops,
            bytes_per_chunk=io, chunk=chunk, least_ms=least * 1e3,
            device_ms=busy * 1e3, bound_by=bound, executions=executions)
    return 100.0 * least / busy
