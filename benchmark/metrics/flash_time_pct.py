"""Share of device busy time spent in the flash attention kernels: device
durations of the Mosaic custom calls (``tpu_custom_call``; the step has no
other) over the union of all operations, averaged over devices."""

from benchmark import stats, trace


def read(run):
    tr = run.trace
    if tr is None or not tr["devices"]:
        return None
    shares = []
    for dev in tr["devices"]:
        busy = stats.union_length([(s, e) for _, s, e, _ in dev["ops"]])
        kern = sum(e - s for name, s, e, cat in dev["ops"]
                   if trace.is_custom_call(cat, name))
        if busy > 0:
            shares.append(100.0 * kern / busy)
    return sum(shares) / len(shares) if shares else None
