"""Median gap on the device between one training step's last operation
and the next step's first (device trace, modules line for the step
program, operations line for the edges)."""

from benchmark import stats, trace


def read(run):
    if run.trace is None:
        return None
    gaps = trace.run_gaps(run.trace, run.params["device_programs"]["step"])
    med = stats.median(gaps)
    return None if med is None else med * 1e3
