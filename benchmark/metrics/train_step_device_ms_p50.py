"""Median busy time of one execution of the step program on a device
(device trace: union of the operations inside each whole execution)."""

from benchmark import stats, trace


def read(run):
    if run.trace is None:
        return None
    med = stats.median(trace.per_run_busy(
        run.trace, run.params["device_programs"]["step"]))
    return None if med is None else med * 1e3
