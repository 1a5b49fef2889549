"""Share of the decode program's busy time on the first device that ran
under the scopes ``indexer`` and ``select``, where a model picks the keys a
query reads at run time: the indexer's projections and its scores of every
live ring row, and the exact top-k that turns the scores into a set (device
trace, scope path of each operation's metadata). What deciding WHICH rows
to read costs a step, beside reading them (``attn_sparse``).

``program_trace.SCOPES`` knows ``attn`` only, the outer scope of all three
(``decode_attention_time_pct`` reads that), so this file reduces the same
operations (``program_trace.program_ops``) by the inner scopes. The table
goes to the earlier line ``decode_by_sparse_scope`` with each scope's share
and milliseconds an execution. ``seconds`` and ``share`` are shared with
``prefill_indexer_time_pct.py``, ``roofline`` is the two rooflines'
(``sparse_decode_attention_roofline.py``, ``indexer_decode_roofline.py``).
None where the
profile holds no operation of the program under ``attn_sparse`` (a program
that selects nothing, or the parent of the PR that added it)."""

from benchmark import peaks, program_trace, shapes
from benchmark.loading import sibling

SCOPES = ("indexer", "select", "attn_sparse")


def seconds(run, program):
    """(busy seconds of ``program`` under each of ``SCOPES`` and under
    ``all``, its executions in the profile), or None where the profile
    holds none or nothing under ``attn_sparse``."""
    cache = run.raw.setdefault("by_sparse_scope", {})
    if program in cache:
        return cache[program]
    pt = program_trace.of_run(run)
    totals = dict.fromkeys(SCOPES + ("all",), 0.0)
    if pt is not None and program is not None:
        for _, s, e, path in program_trace.program_ops(pt, program):
            parts = program_trace._PART.split((path or "").rstrip(":"))
            totals["all"] += e - s
            # the innermost: the indexer's projections lie under ``attn``
            # and ``indexer`` and under no other of the three
            for part in reversed(parts):
                if part in SCOPES:
                    totals[part] += e - s
                    break
    runs = 0 if pt is None else sum(
        program in name for name, _, _ in pt["modules"])
    got = (totals, runs) if runs and totals["attn_sparse"] > 0 else None
    cache[program] = got
    return got


def share(run, which):
    """Percent of the ``which`` program's busy time under ``indexer`` and
    ``select``, the table said as ``<which>_by_sparse_scope``."""
    program = run.params.get("device_programs", {}).get(which)
    got = seconds(run, program)
    if got is None:
        return None
    totals, runs = got
    run.say(f"{which}_by_sparse_scope", program=program, executions=runs,
            busy_ms_per_execution=1e3 * totals["all"] / runs,
            **{f"{scope}_ms": 1e3 * totals[scope] / runs
               for scope in SCOPES},
            **{f"{scope}_pct": 100.0 * totals[scope] / totals["all"]
               for scope in SCOPES})
    return 100.0 * (totals["indexer"] + totals["select"]) / totals["all"]


def read(run):
    return share(run, "decode")


def roofline(run, work_name: str, scope: str):
    """The least time the chip could take for what the family's
    ``work_name(config, occupancy, context)`` says a decode step REQUIRES,
    over the decode program's busy time under ``scope`` an execution, in
    percent; occupancy and context are the window's means, as
    ``decode_step_roofline.py`` takes them. The line ``<work_name>`` says
    both sides and the bandwidth reached. None where the family has no such
    function or the profile holds nothing to read."""
    work = getattr(run.family, work_name, None)
    a, b = run.counters.get("open"), run.counters.get("close")
    if work is None or run.trace is None or "requests" not in run.raw \
            or not a or not b or b["steps"] <= a["steps"]:
        return None
    got = seconds(run, run.params.get("device_programs", {}).get("decode"))
    context = sibling(__file__, "decode_step_roofline.py").mean_context(run)
    if got is None or context is None or got[0][scope] <= 0:
        return None
    totals, executions = got
    busy = totals[scope] / executions
    occupancy = (b["occupancy_sum"] - a["occupancy_sum"]) \
        / (b["steps"] - a["steps"])
    ops, io = work(run.config, occupancy, context)
    least, bound = shapes.roofline_seconds(
        ops, io, peaks.peak(run.device_kind))
    run.say(work_name, ops_per_step=ops, bytes_per_step=io,
            occupancy=occupancy, mean_context=context, least_ms=least * 1e3,
            device_ms=busy * 1e3, bound_by=bound,
            achieved_gb_per_s=io / busy / 1e9, executions=executions)
    return 100.0 * least / busy
