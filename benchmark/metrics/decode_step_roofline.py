"""The decode step's share of its roofline, which memory bandwidth bounds:
bytes one step must read (by the family's ``decode_step_bytes``: for a
dense model every weight once as stored, and the live K/V rows of the
occupied slots) over the chip's peak bytes/s, over the median device busy
time of one execution of the decode program (device trace). Occupancy and
context are the window's means: slots occupied per step from the engine's
counters, context per decoded token from the clients' records."""

from benchmark import peaks, stats, trace


def mean_context(run) -> float | None:
    """Mean cache rows a decoded token attended: prompt length plus its
    index, over tokens decoded inside the window (a stream's first token
    comes from prefill)."""
    lo, hi = run.window_ns
    total, n = 0.0, 0
    for r in run.raw["requests"]:
        k = 0
        for t, c in zip(r["chunk_ns"], r["chunk_tokens"]):
            for _ in range(c):
                if k > 0 and lo <= t <= hi:
                    total += r["prompt_len"] + k
                    n += 1
                k += 1
    return total / n if n else None


def read(run):
    tr = run.trace
    a, b = run.counters.get("open"), run.counters.get("close")
    if tr is None or "requests" not in run.raw or not a or not b \
            or b["steps"] <= a["steps"] or "weight_bytes" not in run.raw:
        return None
    busy = stats.median(trace.per_run_busy(
        tr, run.params["device_programs"]["decode"]))
    context = mean_context(run)
    if not busy or context is None:
        return None
    occupancy = (b["occupancy_sum"] - a["occupancy_sum"]) \
        / (b["steps"] - a["steps"])
    need = run.family.decode_step_bytes(
        run.config, run.raw["weight_bytes"], occupancy, context,
        {"open": a, "close": b})
    least = need / peaks.peak(run.device_kind)["bytes_per_s"]
    run.say("decode_roofline", bytes_per_step=need, occupancy=occupancy,
            mean_context=context, least_ms=least * 1e3,
            device_ms_p50=busy * 1e3, bound_by="memory")
    return 100.0 * least / busy
