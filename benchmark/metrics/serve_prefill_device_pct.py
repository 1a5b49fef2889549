"""The prefill program's share of the device's busy time in the traced
window: the busy seconds of its whole executions over those of every
operation (device trace, first device counted as the others:
``trace.per_run_busy`` over ``trace.busy_seconds``). It says whether a
serving cell measures its prompts or its decode steps. None where the
cell's deployment names no prefill program or the profile holds no
execution of it.

What the two programs' time went to is said beside it, on the earlier
lines ``prefill_by_scope_hybrid`` and ``decode_by_scope_hybrid`` (the
tables of ``decode_moe_time_pct.py``, by its longer scope list, said once
whichever reader asks first): a cell that reports this share and none of
that file's four still leaves the breakdown behind it in its traced run."""

from benchmark import trace
from benchmark.loading import sibling

hybrid = sibling(__file__, "decode_moe_time_pct.py")


def read(run):
    program = run.params.get("device_programs", {}).get("prefill")
    tr = run.trace
    if tr is None or program is None or tr.get("window") is None:
        return None
    runs = trace.per_run_busy(tr, program)
    busy = trace.busy_seconds(tr)
    if not runs or not busy:
        return None
    share = sum(runs) / len(tr["devices"]) / busy
    for which, name in run.params["device_programs"].items():
        hybrid.table(run, name, f"{which}_by_scope_hybrid")
    run.say("prefill_device_share", program=program, executions=len(runs),
            prefill_busy_s=sum(runs) / len(tr["devices"]), busy_s=busy)
    return 100.0 * share
