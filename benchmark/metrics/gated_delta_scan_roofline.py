"""The delta rule's CHUNKED form as a share of its roofline: the least time
the chip could take for what the linear layers' scans of one chunk's real
rows REQUIRE, over the chunk program's busy time under ``gdn_scan`` an
execution (device trace).

The work is the family's ``gated_delta_scan_work``: a layer's q, k, v, g
and beta read once and its output written once, the slot's state read and
written once a chunk, and the operations of the recurrence itself (the
decay, what the state holds of k, the rank-one write, the readout), NOT of
any blocked form: the block solve, the products inside a block and a
padded row are the implementation's, and lower the share. Real tokens a
chunk are the window's mean from the engine's counters. None where the
family has no such function or the profile holds no operation of the chunk
program under the linear layers' scopes."""

from benchmark import peaks, program_counters, shapes
from benchmark.loading import sibling

linear = sibling(__file__, "decode_linear_attention_time_pct.py")


def read(run):
    work = getattr(run.family, "gated_delta_scan_work", None)
    chunks = program_counters.window_delta(run, "prefill_chunks")
    real = program_counters.window_delta(run, "prefill_tokens_real")
    if work is None or run.trace is None or not chunks or real is None:
        return None
    got = linear.seconds(
        run, run.params.get("device_programs", {}).get("prefill"))
    if got is None:
        return None
    totals, executions = got
    busy = totals.get("gdn_scan", 0.0) / executions
    if not busy:
        return None
    ops, io = work(run.config, real / chunks)
    least, bound = shapes.roofline_seconds(
        ops, io, peaks.peak(run.device_kind))
    run.say("gated_delta_scan_roofline", ops_per_chunk=ops,
            bytes_per_chunk=io, tokens_per_chunk=real / chunks,
            least_ms=least * 1e3, device_ms=busy * 1e3, bound_by=bound,
            executions=executions)
    return 100.0 * least / busy
