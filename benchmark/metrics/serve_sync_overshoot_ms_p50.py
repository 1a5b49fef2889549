"""Median time a decode turn's ``llm.step.sync`` went on after the device
had ended the step the turn dispatched: end of the sync minus the end of
the last operation of that execution of the decode program on the first
device, floored at 0 (profiler trace: host annotations and device
operations on one clock). What the loop's thread waits for there is the
interpreter. The tail, and the pollers' ``llm.next.drain`` annotations
that began inside that stretch, go to the earlier line ``sync_overshoot``
(``benchmark/delivery.py``)."""

from benchmark.delivery import sync_overshoot_ms_p50 as read  # noqa: F401
