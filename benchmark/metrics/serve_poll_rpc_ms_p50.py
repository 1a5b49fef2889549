"""Median, over the streams that ended in the window, of what one routed
poll costs outside the engine: ``(rpc_ns - held_ns) / polls`` from the
attributes ``stream_call`` writes on its ``serve.stream:<deployment>``
span (the client's round trips less what ``llm_next`` said it held each:
``submit_actor_task``, ``Replica.handle_request``, ``backend.get``). The
streams, their polls and the polls a second go to the earlier line
``stream_polls`` (``benchmark/delivery.py``).
None where the span carries no such attributes."""

from benchmark.delivery import poll_rpc_ms_p50 as read  # noqa: F401
