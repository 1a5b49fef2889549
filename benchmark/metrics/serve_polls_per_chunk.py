"""Calls of the engine's drain lanes a delivered chunk: the window's
``next_calls`` (plus ``poll_calls``, where a program counts its batched
lane's) over ``deliver_chunks`` (program counters of ``llm_stats()``;
``benchmark/delivery.py``). 1.0 is the best of a poller a stream; a
delivery that drains many streams a call reads one over the streams a
call. None where the program keeps no such counter."""

from benchmark.delivery import polls_per_chunk as read  # noqa: F401
