"""Tokens in a slice of ``report_every`` steps over the MEDIAN slice wall
time, over chips: the pace between hiccups, a steadier statistic that
stands beside the whole-window rate."""

from benchmark import stats


def read(run):
    raw = run.raw
    if not raw.get("slice_seconds"):
        return None
    return stats.slice_rate(
        raw["slice_seconds"],
        raw["tokens_per_step"] * raw["steps_per_slice"], run.chips)
