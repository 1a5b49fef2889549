"""The prefill chunk program's share of its roofline: the least time the
chip could take for the work one execution REQUIRES, over the median device
busy time of one execution (device trace).

The work is the family's ``prefill_chunk_work``: the bytes a chunk must
read (every stored matrix once, the slot's state) and the operations of its
real tokens (the matrices every token passes, one expert's products for
each token-expert pair that landed here, attention over the keys a query
may see). The window's means come from the engine's counters: real tokens
and ``prefill_expert_rows`` an execution (``llm_stats()``, close minus
open), and the keys a query sees from the clients' records (a prompt of L
tokens: (L + 1) / 2 on average, weighted by its tokens). The head counts
for a prompt's last chunk only (``prefill_rows_real`` over
``prefill_chunks``: the program runs it in every chunk, which nothing
requires). No padding and no un-hit expert's product is counted, so the
share cannot pass 100. None
where the family has no such function or the program keeps no
``prefill_expert_rows`` (the parent of the PR that added them)."""

from benchmark import peaks, program_counters, shapes, stats, trace


def mean_keys(run) -> float:
    """Keys a prompt token's query may see, over the prompts whose first
    token came inside the window."""
    lo, hi = run.window_ns
    lens = [r["prompt_len"] for r in run.raw.get("requests", ())
            if r["first_ns"] is not None and lo <= r["first_ns"] <= hi]
    total = sum(lens)
    return sum(n * (n + 1) / 2.0 for n in lens) / total if total else 0.0


def read(run):
    work = getattr(run.family, "prefill_chunk_work", None)
    program = run.params.get("device_programs", {}).get("prefill")
    chunks = program_counters.window_delta(run, "prefill_chunks")
    real = program_counters.window_delta(run, "prefill_tokens_real")
    pairs = program_counters.window_delta(run, "prefill_expert_rows")
    prompts = program_counters.window_delta(run, "prefill_rows_real")
    if run.trace is None or work is None or program is None or not chunks \
            or real is None or pairs is None or prompts is None \
            or "weight_bytes" not in run.raw:
        return None
    busy = stats.median(trace.per_run_busy(run.trace, program))
    if not busy:
        return None
    keys = mean_keys(run)
    ops, io = work(run.config, run.raw["weight_bytes"], real / chunks,
                   pairs / chunks, keys, prompts / chunks)
    least, bound = shapes.roofline_seconds(
        ops, io, peaks.peak(run.device_kind))
    run.say("prefill_chunk_roofline", ops_per_chunk=ops, bytes_per_chunk=io,
            tokens_per_chunk=real / chunks, expert_rows_per_chunk=pairs
            / chunks, mean_keys=keys, last_chunk_share=prompts / chunks,
            least_ms=least * 1e3,
            device_ms_p50=busy * 1e3, bound_by=bound)
    return 100.0 * least / busy
