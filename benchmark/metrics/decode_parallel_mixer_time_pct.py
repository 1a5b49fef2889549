"""Share of the decode program's busy time on the first device that ran
under the scopes of a layer's TWO mixers, where a layer runs attention and a
state-space mixer side by side: the attention branch's ``attn_proj``,
``rope``, ``attn`` and ``cache_write`` and the state branch's ``ssm_proj``,
``conv``, ``ssm_update`` (the chunk program's ``ssm_scan``), ``ssm_norm``
and ``state_write`` (device trace, scope path of each operation's
metadata).

``program_trace.SCOPES`` names the dense families' scopes only, so this
file keeps the longer list and reduces the same operations
(``program_trace.program_ops``) by it: an operation belongs to the innermost
of these scopes on its path. The whole table goes to the earlier line
``decode_by_scope_parallel`` with the two branches' shares apart, the
program's executions in the profile and each branch's milliseconds an
execution. ``seconds`` and ``share`` are shared with
``parallel_mixer_decode_roofline.py`` and
``prefill_parallel_mixer_time_pct.py``.

A share by scope holds the time of the operations under it and not the
reads the compiler starts ahead of them: it brings most of a matrix in
with asynchronous copies beside earlier work (``async-done`` and
``copy-done`` carry no scope of ours), so ``attn_proj`` and ``ssm_proj``
read less than their weights' bytes would take. None where the family is
not a parallel one (it has no ``parallel_mixer_decode_work``: no reader may
hold a family's name) or the profile holds no operation of the program
under the state branch."""

from benchmark import program_trace

ATTENTION = ("attn_proj", "rope", "attn", "cache_write")
STATE = ("ssm_proj", "conv", "ssm_update", "ssm_scan", "ssm_norm",
         "state_write")
SCOPES = program_trace.SCOPES + ATTENTION + STATE + ("mixer_sum",)


def seconds(run, program):
    """(busy seconds of ``program`` by scope, its executions in the
    profile), or None where the profile holds none or no operation under
    the state branch."""
    cache = run.raw.setdefault("by_scope_parallel", {})
    if program in cache:
        return cache[program]
    pt = program_trace.of_run(run)
    totals: dict = {}
    if pt is not None and program is not None:
        for _, s, e, path in program_trace.program_ops(pt, program):
            parts = [p for p in program_trace._PART.split(
                (path or "").rstrip(":")) if p]
            own = next((p for p in reversed(parts) if p in SCOPES),
                       program_trace.UNSCOPED)
            totals[own] = totals.get(own, 0.0) + e - s
    runs = 0 if pt is None else sum(
        program in name for name, _, _ in pt["modules"])
    got = (totals, runs) if runs and any(
        totals.get(k, 0.0) > 0 for k in STATE) else None
    cache[program] = got
    return got


def share(run, which):
    """Percent of the ``which`` program's busy time under the two branches'
    scopes, the table said as ``<which>_by_scope_parallel``."""
    if getattr(run.family, "parallel_mixer_decode_work", None) is None:
        return None
    program = run.params.get("device_programs", {}).get(which)
    got = seconds(run, program)
    if got is None:
        return None
    totals, runs = got
    busy = sum(totals.values())
    pct = {name: 100.0 * sum(totals.get(k, 0.0) for k in scopes) / busy
           for name, scopes in (("attention", ATTENTION), ("state", STATE))}
    run.say(f"{which}_by_scope_parallel", program=program, executions=runs,
            busy_s=busy, attention_pct=pct["attention"],
            state_pct=pct["state"],
            attention_ms=busy * pct["attention"] * 10.0 / runs,
            state_ms=busy * pct["state"] * 10.0 / runs,
            by_scope_pct={k: 100.0 * v / busy for k, v in sorted(
                totals.items(), key=lambda kv: -kv[1])})
    return pct["attention"] + pct["state"]


def read(run):
    return share(run, "decode")
