"""The flash kernels' share of their roofline: the least time the chip
could take for the attention a step requires (the family's
``attention_calls``: the shape of one causal call on this chip's rows and
how many forward, and as many backward, calls a step makes; each by
``shapes.flash_forward`` / ``flash_backward``, the larger of operations
over peak FLOP/s and bytes over peak bytes/s, call by call) over the
kernels' measured time per step.
A forward recomputed in the backward pass lowers the share: recomputation
is not required work."""

from benchmark import peaks, shapes, trace


def required_seconds(run) -> tuple:
    peak = peaks.peak(run.device_kind)
    call, calls = run.family.attention_calls(
        run.config, run.raw["rows_per_step"] // run.chips)
    fwd, fwd_by = shapes.roofline_seconds(*shapes.flash_forward(*call), peak)
    bwd, bwd_by = shapes.roofline_seconds(*shapes.flash_backward(*call), peak)
    return calls * (fwd + bwd), {"forward": fwd_by, "backward": bwd_by}


def read(run):
    tr = run.trace
    if tr is None or not tr["devices"] or "rows_per_step" not in run.raw:
        return None
    program = run.params["device_programs"]["step"]
    per_step = []
    lo, hi = tr["window"]
    for dev in tr["devices"]:
        for s, e in trace.program_runs(dev, program):
            if s < lo or e > hi:
                continue
            per_step.append(sum(
                b - a for name, a, b, cat in trace.ops_within(dev, s, e)
                if trace.is_custom_call(cat, name)))
    per_step = [t for t in per_step if t > 0]
    if not per_step:
        return None
    need, bound_by = required_seconds(run)
    run.say("flash_roofline_bound", bound_by=bound_by,
            required_ms_per_step=need * 1e3,
            measured_ms_per_step=1e3 * sum(per_step) / len(per_step))
    return 100.0 * need / (sum(per_step) / len(per_step))
