"""Seconds of set-up the program spent compiling or loading programs
from the persistent cache, as its own ``jax.monitoring`` listeners saw
them up to window open (``device_telemetry.compile_log()``, by
``program_counters.setup_compiles``); counts of hits and misses and the
slowest programs go to the earlier line ``setup_compiles``."""

from benchmark import program_counters


def read(run):
    seen = program_counters.setup_compiles(run)
    if seen is None:
        return None
    run.say("setup_compiles", **seen)
    return seen["seconds"]
