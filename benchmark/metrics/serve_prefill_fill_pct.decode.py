"""Real prompt tokens over the tokens the engine's fixed prefill lane
computed for them, over the window (program counters
``prefill_tokens_real`` / ``prefill_tokens_lane`` of ``llm_stats()``,
close minus open; ``program_counters.prefill_fill_pct``). One quantity
under a name per end-to-end metric it moves."""

from benchmark.program_counters import prefill_fill_pct as read  # noqa: F401
