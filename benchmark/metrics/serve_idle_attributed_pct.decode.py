"""Share of the first device's idle seconds (gaps of 20 us and more in
the traced window) that lie under one of the program's ``llm.*``
annotations (``program_trace.read_idle_attributed``; the seconds by span
name go to the earlier line ``idle_by_program_span``). One quantity
under a name per end-to-end metric it moves."""

from benchmark.program_trace import read_idle_attributed as read  # noqa: F401
