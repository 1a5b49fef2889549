"""Share of the traced window in which a collective runs on a chip and no
compute does (device trace: all-gather, reduce-scatter, all-reduce,
all-to-all, collective-permute by HLO opcode, on the core's own line and
on the line of asynchronous operations in flight, minus their overlap with
every other operation of the core's line), averaged over chips."""

from benchmark import stats, trace


def read(run):
    tr = run.trace
    if tr is None or not tr["devices"] or tr["window"] is None:
        return None
    lo, hi = tr["window"]
    shares = []
    for dev in tr["devices"]:
        coll = [(s, e) for name, s, e, cat in dev["ops"] + dev["async"]
                if trace.is_collective(cat, name)]
        rest = [(s, e) for name, s, e, cat in dev["ops"]
                if not trace.is_collective(cat, name)]
        exposed = stats.subtract_length(
            trace.clip(coll, lo, hi), trace.clip(rest, lo, hi))
        shares.append(100.0 * exposed / (hi - lo))
    return sum(shares) / len(shares)
