"""Share of the prefill chunk program's busy time on the first device that
ran under the latent attention's scopes: ``kv_up`` (the decompression of a
block of cached rows into every head's keys and values), ``attn`` (scores,
running softmax and weighted values), ``absorb`` (the products into and out
of the latent space, where a program absorbs) and ``cache_write`` (device
trace, scope path of each operation's metadata).

``program_trace.SCOPES`` names neither ``kv_up`` nor ``absorb``, so this
file keeps the list and reduces the same operations
(``program_trace.program_ops``) by it: an operation belongs to the
innermost of these scopes on its path. Where the compiler fuses a
decompression into the product that consumes it, the fusion carries the
product's path and ``kv_up`` holds no time of its own: the share is over
the four scopes together for that reason. ``seconds`` is shared with
``latent_decode_attention_roofline.py``. None where the family is not a
latent one (it has no ``latent_decode_attention_work``: no reader may hold
a family's name) or the profile holds no execution of the program."""

from benchmark import program_trace

LATENT = ("kv_up", "absorb")
SCOPES = ("attn", "cache_write") + LATENT


def seconds(run, program, scopes):
    """(busy seconds of ``program`` under each of ``scopes``, its busy
    seconds, its executions in the profile), or None where the profile
    holds none."""
    pt = program_trace.of_run(run)
    if pt is None or program is None:
        return None
    totals: dict = {}
    for _, s, e, path in program_trace.program_ops(pt, program):
        parts = [p for p in program_trace._PART.split((path or "").rstrip(":"))
                 if p]
        own = next((p for p in reversed(parts) if p in SCOPES
                    or p in program_trace.SCOPES), None)
        totals[own] = totals.get(own, 0.0) + e - s
    total = sum(totals.values())
    runs = sum(program in name for name, _, _ in pt["modules"])
    if total <= 0 or not runs:
        return None
    return {k: totals.get(k, 0.0) for k in scopes}, total, runs


def read(run):
    program = run.params.get("device_programs", {}).get("prefill")
    if getattr(run.family, "latent_decode_attention_work", None) is None:
        return None
    got = seconds(run, program, SCOPES)
    if got is None:
        return None
    under, total, runs = got
    run.say("prefill_latent_attention", program=program, executions=runs,
            busy_s=total, ms_an_execution={
                k: 1e3 * v / runs for k, v in under.items()})
    return 100.0 * sum(under.values()) / total
