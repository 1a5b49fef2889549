"""The decode step's latent attention as a share of its roofline: the least
time the chip could take for what one step REQUIRES of it, over the decode
program's busy time under the scopes ``attn`` and ``absorb`` an execution
(device trace).

The work is the family's ``latent_decode_attention_work``: for the occupied
slots' live rows, in every layer, each row read once and the absorbed
form's scores and values over it. Occupancy and context are the window's
means, as ``decode_step_roofline.py`` takes them. Live rows only and the
absorbed count, whatever the program computes (free slots, rows past a
context, float32 products, a decompressed window), so the share cannot
pass 100 and stays the same yardstick under another program. None where
the family has no such function or the profile holds no operation of the
decode program under either scope."""

from benchmark import peaks, shapes
from benchmark.loading import sibling

latent = sibling(__file__, "prefill_latent_attention_time_pct.py")
step = sibling(__file__, "decode_step_roofline.py")


def read(run):
    work = getattr(run.family, "latent_decode_attention_work", None)
    a, b = run.counters.get("open"), run.counters.get("close")
    if work is None or run.trace is None or "requests" not in run.raw \
            or not a or not b or b["steps"] <= a["steps"]:
        return None
    got = latent.seconds(
        run, run.params.get("device_programs", {}).get("decode"),
        ("attn", "absorb"))
    context = step.mean_context(run)
    if got is None or context is None:
        return None
    under, _, executions = got
    if sum(under.values()) <= 0:
        return None
    busy = sum(under.values()) / executions
    occupancy = (b["occupancy_sum"] - a["occupancy_sum"]) \
        / (b["steps"] - a["steps"])
    ops, io = work(run.config, occupancy, context)
    least, bound = shapes.roofline_seconds(
        ops, io, peaks.peak(run.device_kind))
    run.say("latent_decode_attention_roofline", ops_per_step=ops,
            bytes_per_step=io, occupancy=occupancy, mean_context=context,
            least_ms=least * 1e3, device_ms=busy * 1e3, bound_by=bound,
            executions=executions)
    return 100.0 * least / busy
