"""A verify-and-draft step's attention as a share of its roofline: the
least time the chip could take for what one step REQUIRES of it, over the
decode program's busy time under the scope ``attn`` an execution, the main
stack's layers and the module's block alike (device trace).

The work is the family's ``verify_attention_work``: for the occupied
slots, in every ring, the rows a query must see read ONCE for both of a
slot's query rows, the two new rows written, and two rows' scores and
weighted sums over those keys. Occupancy and context are the window's
means, as ``decode_step_roofline.py`` takes them. Live rows only, whatever
the program reads (free slots, whole blocks past a context, a ring read
once a row), so the share cannot pass 100. None where the family has no
such function or the profile holds no operation of the decode program under
``mtp`` (a program that verifies nothing)."""

from benchmark import peaks, shapes
from benchmark.loading import sibling

draft = sibling(__file__, "decode_draft_time_pct.py")
step = sibling(__file__, "decode_step_roofline.py")


def read(run):
    work = getattr(run.family, "verify_attention_work", None)
    a, b = run.counters.get("open"), run.counters.get("close")
    if work is None or run.trace is None or "requests" not in run.raw \
            or not a or not b or b["steps"] <= a["steps"]:
        return None
    got = draft.seconds(
        run, run.params.get("device_programs", {}).get("decode"))
    context = step.mean_context(run)
    if got is None or context is None or got[0]["attn"] <= 0:
        return None
    totals, executions = got
    busy = totals["attn"] / executions
    occupancy = (b["occupancy_sum"] - a["occupancy_sum"]) \
        / (b["steps"] - a["steps"])
    ops, io = work(run.config, occupancy, context)
    least, bound = shapes.roofline_seconds(
        ops, io, peaks.peak(run.device_kind))
    run.say("verify_attention_roofline", ops_per_step=ops,
            bytes_per_step=io, occupancy=occupancy, mean_context=context,
            least_ms=least * 1e3, device_ms=busy * 1e3, bound_by=bound,
            executions=executions)
    return 100.0 * least / busy
