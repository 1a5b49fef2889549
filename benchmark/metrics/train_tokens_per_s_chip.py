"""All tokens of the window's whole slices over all the time they took,
over chips. Host clock, loss-on-host to loss-on-host; the window opens on
a slice boundary and the slice its end cuts is dropped, so the time is the
sum of the slice times and every stall inside the window is in it."""

from benchmark import stats


def read(run):
    raw = run.raw
    if not raw.get("slice_seconds"):
        return None
    return stats.window_rate(
        raw["slice_seconds"],
        raw["tokens_per_step"] * raw["steps_per_slice"], run.chips)
