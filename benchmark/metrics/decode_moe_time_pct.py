"""Share of the decode program's busy time on the first device that ran
under the latent mixture-of-experts layers' scopes: ``router``,
``latent_proj``, ``moe_dispatch``, ``experts``, ``moe_combine`` and
``shared_expert`` (device trace, scope path of each operation's metadata).

``program_trace.SCOPES`` names the dense families' scopes only, so this
file keeps the longer list (PERF.md section 3) and reduces the same
operations (``program_trace.program_ops``) by it; the whole table goes to
the earlier line ``decode_by_scope_hybrid`` (and the prefill program's to
``prefill_by_scope_hybrid``), once, whichever of the four readers that
share them runs first (``decode_ssm_time_pct``, ``prefill_moe_time_pct``,
``prefill_ssm_time_pct``). The TPU compiler's grouped product carries its
operation's name (``ragged-dot-...``) and no scope of ours: it is the
experts' product and is counted under ``experts``. A program that names
none of these scopes gives None."""

from benchmark import program_trace

MOE = ("router", "latent_proj", "moe_dispatch", "experts", "moe_combine",
       "shared_expert")
SSM = ("ssm_proj", "conv", "ssm_update", "ssm_scan", "ssm_norm",
       "state_write")
SCOPES = program_trace.SCOPES + MOE + SSM


def scope_of(path):
    """``program_trace.scope_of`` by the longer list."""
    if not path:
        return program_trace.NO_PATH
    parts = [p for p in program_trace._PART.split(path.rstrip(":")) if p]
    for part in reversed(parts):
        if part in SCOPES:
            return part
    if parts and parts[-1].startswith("ragged-dot"):
        return "experts"    # ``jax.lax.ragged_dot`` as the TPU lowers it
    return f"{program_trace.UNSCOPED} {parts[-1]}" if parts \
        else program_trace.NO_PATH


def table(run, program, event):
    """Busy seconds of ``program`` by scope, said once as ``event``; None
    where the profile has no operation under a scope of MOE or SSM."""
    cache = run.raw.setdefault("by_scope_hybrid", {})
    if event in cache:
        return cache[event]
    pt = program_trace.of_run(run)
    totals = {}
    if pt is not None:
        for _, s, e, path in program_trace.program_ops(pt, program):
            key = scope_of(path)
            totals[key] = totals.get(key, 0.0) + e - s
    if not any(k in totals for k in MOE + SSM):
        totals = None
    else:
        busy = sum(totals.values())
        run.say(event, program=program, busy_s=busy, by_scope_pct={
            k: 100.0 * v / busy for k, v in
            sorted(totals.items(), key=lambda kv: -kv[1])})
    cache[event] = totals
    return totals


def share(run, scopes, which="decode"):
    """Percent of the ``which`` program's busy time under ``scopes``."""
    program = run.params["device_programs"].get(which)
    if program is None:
        return None
    totals = table(run, program, f"{which}_by_scope_hybrid")
    if not totals:
        return None
    return 100.0 * sum(totals.get(s, 0.0) for s in scopes) \
        / sum(totals.values())


def read(run):
    return share(run, MOE)
