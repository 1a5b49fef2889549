"""Share of the traced window in which no operation ran on the device,
averaged over chips (``trace.read_idle_pct``); this name is the one that
moves ``ttft_p90_ms``."""

from benchmark.trace import read_idle_pct as read  # noqa: F401
