"""Counters the program keeps of its own work, read over the measured
window: the prefill lane's fill (``llm_stats()``: real against computed
tokens) and the compiles of set-up (``device_telemetry.compile_log()``).
A program that keeps no such counter (the parent of the PR that added
them) gives ``None``."""

from __future__ import annotations

import time


def window_delta(run, key: str):
    """Close minus open of one ``llm_stats()`` counter, or None."""
    a, b = run.counters.get("open"), run.counters.get("close")
    if not a or not b or key not in a or key not in b:
        return None
    return b[key] - a[key]


def prefill_fill_pct(run):
    """Real prompt tokens over the tokens the fixed lane computed for
    them, in the prefill batches of the window; says the batches, the
    rows a batch and the tokens on an earlier line."""
    real = window_delta(run, "prefill_tokens_real")
    lane = window_delta(run, "prefill_tokens_lane")
    batches = window_delta(run, "prefill_batches")
    rows = window_delta(run, "prefill_rows_real")
    if not lane or real is None or not batches:
        return None
    run.say("prefill_lane", batches=batches, rows=rows,
            rows_per_batch=rows / batches, tokens_real=real,
            tokens_lane=lane)
    return 100.0 * real / lane


def setup_compiles(run):
    """The compiles the program's listeners saw before the window opened
    (from where its main path first touched JAX: ``ensure_compile_cache``
    or ``ray_tpu.init``): ``{"seconds", "compiles", "hits", "misses",
    "uncached", "slowest"}``, or None."""
    from ray_tpu.util import device_telemetry

    log = getattr(device_telemetry, "compile_log", None)
    if log is None or run.window_ns is None:
        return None
    offset = getattr(run, "epoch_offset_ns", None)
    if offset is None:
        offset = time.time_ns() - time.perf_counter_ns()
    opened = run.window_ns[0] + offset
    before = [e for e in log() if e["epoch_ns"] <= opened]
    slowest = sorted(before, key=lambda e: -e["seconds"])[:5]
    return {"seconds": sum(e["seconds"] for e in before),
            "compiles": len(before),
            "hits": sum(e["cache"] == "hit" for e in before),
            "misses": sum(e["cache"] == "miss" for e in before),
            "uncached": sum(e["cache"] is None for e in before),
            "slowest": [[e["fun_name"], e["cache"], e["seconds"]]
                        for e in slowest]}
