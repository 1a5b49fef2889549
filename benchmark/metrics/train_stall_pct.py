"""How far the whole-window rate lies under the median-slice pace:
``1 - slices * median slice time / time the whole slices took``. 0 when
every slice is as fast as the median one; a hiccup in one slice shows
here and in the rate, not in the median pace."""

from benchmark import stats


def read(run):
    raw = run.raw
    if not raw.get("slice_seconds"):
        return None
    return stats.stall_pct(raw["slice_seconds"], raw["window_whole_s"])
