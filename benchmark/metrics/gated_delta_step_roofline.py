"""The delta rule's STEP form as a share of its roofline: the least time
the chip could take for what the linear layers' updates of one decode step
REQUIRE, over the decode program's busy time under ``gdn_update`` and
``state_write`` an execution (device trace).

The work is the family's ``gated_delta_step_work``: in every linear layer
each occupied slot's state (a float32 matrix a value head) and convolution
tail read and written once, and the recurrence's operations a token.
Occupancy is the window's mean, as ``decode_step_roofline.py`` takes it.
Occupied slots only, whatever the program computes (free slots, a second
pass over the state). The mixers' weights are not among the bytes and their
products not under these two scopes. None where the family has no such
function or the profile holds no operation of the decode program under the
linear layers' scopes."""

from benchmark import peaks, shapes
from benchmark.loading import sibling

linear = sibling(__file__, "decode_linear_attention_time_pct.py")


def read(run):
    work = getattr(run.family, "gated_delta_step_work", None)
    a, b = run.counters.get("open"), run.counters.get("close")
    if work is None or run.trace is None or not a or not b \
            or b["steps"] <= a["steps"]:
        return None
    got = linear.seconds(
        run, run.params.get("device_programs", {}).get("decode"))
    if got is None:
        return None
    totals, executions = got
    busy = (totals.get("gdn_update", 0.0)
            + totals.get("state_write", 0.0)) / executions
    if not busy:
        return None
    occupancy = (b["occupancy_sum"] - a["occupancy_sum"]) \
        / (b["steps"] - a["steps"])
    ops, io = work(run.config, occupancy)
    least, bound = shapes.roofline_seconds(
        ops, io, peaks.peak(run.device_kind))
    run.say("gated_delta_step_roofline", ops_per_step=ops,
            state_bytes_per_step=io, occupancy=occupancy,
            least_ms=least * 1e3, device_ms=busy * 1e3, bound_by=bound,
            executions=executions)
    return 100.0 * least / busy
