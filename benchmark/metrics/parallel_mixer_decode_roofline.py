"""A layer's two mixers (attention and a state-space mixer side by side) in
the decode step, as a share of their roofline: the least time the chip
could take for what one step REQUIRES of their two CACHE passes, over the
decode program's busy time under the two branches' scopes an execution
(device trace).

The work is the family's ``parallel_mixer_decode_work``: in every layer each
occupied slot's live K/V rows read once and each occupied slot's state and
convolution tail read and written once, and the operations of both
branches. Occupancy and context are the window's means, as
``decode_step_roofline.py`` takes them. Live rows and occupied slots only,
whatever the program computes (free slots, rows past a context, a widened
copy of a window).

**The mixers' weights are NOT among the bytes**, though a step must read
them: the compiler brings most of a matrix in with asynchronous copies
beside earlier work, and their waits (``async-done``, ``copy-done``) carry
no scope of ours, so the time under the branches' scopes does not hold
those reads (the cell's first trace: ``ssm_proj`` 0.47 ms a step for
1.23 GB of matrices, which is 1.5 ms of reading). A share that counted
them above and not below would pass 100 on a program whose cache passes are
tight. As it is, the time under ``attn_proj`` and ``ssm_proj`` (whatever of
a matrix is read in place, and the products) stays below with nothing for
it above, which only lowers the share; ``decode_step_roofline`` holds the
weights, over the whole step. None where the family has no such function
or the profile holds no operation of the decode program under the state
branch."""

from benchmark import peaks, shapes
from benchmark.loading import sibling

mixers = sibling(__file__, "decode_parallel_mixer_time_pct.py")
step = sibling(__file__, "decode_step_roofline.py")


def read(run):
    work = getattr(run.family, "parallel_mixer_decode_work", None)
    a, b = run.counters.get("open"), run.counters.get("close")
    if work is None or run.trace is None or "requests" not in run.raw \
            or not a or not b or b["steps"] <= a["steps"]:
        return None
    got = mixers.seconds(
        run, run.params.get("device_programs", {}).get("decode"))
    context = step.mean_context(run)
    if got is None or context is None:
        return None
    totals, executions = got
    busy = sum(totals.get(k, 0.0)
               for k in mixers.ATTENTION + mixers.STATE) / executions
    occupancy = (b["occupancy_sum"] - a["occupancy_sum"]) \
        / (b["steps"] - a["steps"])
    ops, io = work(run.config, occupancy, context)
    least, bound = shapes.roofline_seconds(
        ops, io, peaks.peak(run.device_kind))
    run.say("parallel_mixer_decode_roofline", ops_per_step=ops,
            cache_bytes_per_step=io, occupancy=occupancy,
            mean_context=context, least_ms=least * 1e3,
            device_ms=busy * 1e3, bound_by=bound, executions=executions)
    return 100.0 * least / busy
