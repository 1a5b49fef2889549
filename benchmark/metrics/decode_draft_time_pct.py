"""Share of the decode program's busy time on the first device that ran
under the scope ``mtp``: the prediction module's pass of a verify-and-draft
step (its input projection, one block over the module's own ring, its head)
beside the main stack's two rows under ``verify`` (device trace, scope path
of each operation's metadata). What drafting costs a step over verifying
alone. The table of both goes to the earlier line ``decode_by_draft``;
``seconds`` is shared with ``verify_attention_roofline.py``. None where the
profile holds no operation of the program under ``mtp`` (a program that
drafts nothing, or the parent of the PR that added it)."""

from benchmark import program_trace

PARTS = ("verify", "mtp", "attn")


def seconds(run, program):
    """(busy seconds of ``program`` under each of ``PARTS`` and under
    ``all``, its executions in the profile), or None where the profile
    holds none or nothing under ``mtp``."""
    cache = run.raw.setdefault("by_draft", {})
    if program in cache:
        return cache[program]
    pt = program_trace.of_run(run)
    totals = dict.fromkeys(PARTS + ("all",), 0.0)
    if pt is not None and program is not None:
        for _, s, e, path in program_trace.program_ops(pt, program):
            parts = program_trace._PART.split((path or "").rstrip(":"))
            totals["all"] += e - s
            for part in PARTS:
                if part in parts:
                    totals[part] += e - s
    runs = 0 if pt is None else sum(
        program in name for name, _, _ in pt["modules"])
    got = (totals, runs) if runs and totals["mtp"] > 0 else None
    cache[program] = got
    return got


def read(run):
    program = run.params.get("device_programs", {}).get("decode")
    got = seconds(run, program)
    if got is None:
        return None
    totals, runs = got
    run.say("decode_by_draft", program=program, executions=runs,
            busy_ms_per_execution=1e3 * totals["all"] / runs,
            **{f"{part}_ms": 1e3 * totals[part] / runs for part in PARTS},
            **{f"{part}_pct": 100.0 * totals[part] / totals["all"]
               for part in PARTS})
    return 100.0 * totals["mtp"] / totals["all"]
