"""Share of the decode program's busy time on the first device that ran
under the scope ``attn_window``, where a model keeps WINDOW layers beside
global ones: the window layers' ``cached_decode_attention`` over rings as
long as the window (device trace, scope path of each operation's metadata).

``program_trace.SCOPES`` knows ``attn`` only, the outer scope of both kinds
(``decode_attention_time_pct`` reads that), so this file reduces the same
operations (``program_trace.program_ops``) by the two inner scopes: an
operation is the window layers' where ``attn_window`` is on its path, the
global layers' where ``attn_global`` is. The table goes to the earlier line
``decode_by_attention_kind`` with both kinds' share and milliseconds an
execution. ``seconds`` and ``share`` are shared with
``prefill_window_attention_time_pct.py`` and
``window_chunk_attention_roofline.py``. None where the profile holds no
operation of the program under ``attn_window`` (a program with no window
layers, or the parent of the PR that added them)."""

from benchmark import program_trace

KINDS = ("attn_window", "attn_global")


def seconds(run, program):
    """(busy seconds of ``program`` under each of ``KINDS`` and under
    ``all``, its executions in the profile), or None where the profile
    holds none or nothing under ``attn_window``."""
    cache = run.raw.setdefault("by_attention_kind", {})
    if program in cache:
        return cache[program]
    pt = program_trace.of_run(run)
    totals = dict.fromkeys(KINDS + ("all",), 0.0)
    if pt is not None and program is not None:
        for _, s, e, path in program_trace.program_ops(pt, program):
            parts = program_trace._PART.split((path or "").rstrip(":"))
            totals["all"] += e - s
            for kind in KINDS:
                if kind in parts:
                    totals[kind] += e - s
    runs = 0 if pt is None else sum(
        program in name for name, _, _ in pt["modules"])
    got = (totals, runs) if runs and totals["attn_window"] > 0 else None
    cache[program] = got
    return got


def share(run, which):
    """Percent of the ``which`` program's busy time under ``attn_window``,
    the table said as ``<which>_by_attention_kind``."""
    program = run.params.get("device_programs", {}).get(which)
    got = seconds(run, program)
    if got is None:
        return None
    totals, runs = got
    run.say(f"{which}_by_attention_kind", program=program, executions=runs,
            busy_ms_per_execution=1e3 * totals["all"] / runs,
            **{f"{kind}_ms": 1e3 * totals[kind] / runs for kind in KINDS},
            **{f"{kind}_pct": 100.0 * totals[kind] / totals["all"]
               for kind in KINDS})
    return 100.0 * totals["attn_window"] / totals["all"]


def read(run):
    return share(run, "decode")
