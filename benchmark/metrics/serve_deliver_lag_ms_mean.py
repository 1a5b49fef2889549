"""Mean time a chunk lay in its stream's ``pending`` between the fan-out
that appended it and the drain that took it: the window's
``deliver_lag_ns`` over ``deliver_chunks`` (program counters of
``llm_stats()``). The histogram, the share of the lag that the put-off
wake-ups chose (``wake_defer_ns``) and the share of empty long-polls go
to the earlier line ``delivery`` (``benchmark/delivery.py``). None where
the program keeps no such counter."""

from benchmark.delivery import deliver_lag_ms_mean as read  # noqa: F401
