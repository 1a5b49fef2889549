"""Share of the traced window in which no operation ran on the device,
averaged over chips (``trace.read_idle_pct``); this name is the one that
moves ``serve_out_tokens_per_s``."""

from benchmark.trace import read_idle_pct as read  # noqa: F401
