"""Mean share of the decode batch's slots that held a request, over the
steps of the window (program counters ``occupancy_sum`` and ``steps`` of
``llm_stats()``, window close minus window open, over ``max_batch``)."""


def read(run):
    a, b = run.counters.get("open"), run.counters.get("close")
    if not a or not b or b["steps"] <= a["steps"]:
        return None
    mean = (b["occupancy_sum"] - a["occupancy_sum"]) \
        / (b["steps"] - a["steps"])
    return 100.0 * mean / b["max_batch"]
