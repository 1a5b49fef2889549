"""A metric added as a file: how many whole slices the window held."""


def read(run):
    slices = run.raw.get("slice_seconds")
    return None if slices is None else float(len(slices))
