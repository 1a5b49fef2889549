"""Share of the decode program's busy time on the first device that ran
under the scopes of the LINEAR-attention layers, where a model mixes
delta-rule layers with attention layers: ``gdn_proj``, ``conv``,
``gdn_update`` (the chunk program's ``gdn_scan``), ``gdn_norm`` and
``state_write`` (device trace, scope path of each operation's metadata).

``program_trace.SCOPES`` names the dense families' scopes only, so this
file keeps the longer list and reduces the same operations
(``program_trace.program_ops``) by it: an operation belongs to the innermost
of these scopes on its path. The whole table goes to the earlier line
``decode_by_scope_linear`` with every scope's share and milliseconds an
execution, and beside the linear layers' sum the experts' (``router`` to
``shared_expert``) and the gated attention's (``attn_proj``, ``qk_norm``,
``rope``, ``attn``, ``attn_gate``, ``cache_write``). ``seconds`` and
``share`` are shared with ``prefill_linear_attention_time_pct.py`` and the
two ``gated_delta_*_roofline.py``.

A share by scope holds the time of the operations under it and not the
reads the compiler starts ahead of them (``async-done`` and ``copy-done``
carry no scope of ours). None where the family has no delta rule (it has no
``gated_delta_step_work``: no reader may hold a family's name) or the
profile holds no operation of the program under the linear layers' scopes."""

from benchmark import program_trace

LINEAR = ("gdn_proj", "conv", "gdn_update", "gdn_scan", "gdn_norm",
          "state_write")
EXPERTS = ("router", "moe_dispatch", "experts", "moe_combine",
           "shared_expert")
ATTENTION = ("attn_proj", "qk_norm", "rope", "attn", "attn_gate",
             "cache_write")
SCOPES = program_trace.SCOPES + LINEAR + EXPERTS + ATTENTION


def seconds(run, program):
    """(busy seconds of ``program`` by scope, its executions in the
    profile), or None where the profile holds none or no operation under
    the linear layers' scopes."""
    cache = run.raw.setdefault("by_scope_linear", {})
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
        totals.get(k, 0.0) > 0 for k in LINEAR) else None
    cache[program] = got
    return got


def share(run, which):
    """Percent of the ``which`` program's busy time under the linear
    layers' scopes, the table said as ``<which>_by_scope_linear``."""
    if getattr(run.family, "gated_delta_step_work", None) is None:
        return None
    program = run.params.get("device_programs", {}).get(which)
    got = seconds(run, program)
    if got is None:
        return None
    totals, runs = got
    busy = sum(totals.values())
    ms = {name: 1e3 * sum(totals.get(k, 0.0) for k in scopes) / runs
          for name, scopes in (("linear", LINEAR), ("experts", EXPERTS),
                               ("attention", ATTENTION))}
    run.say(f"{which}_by_scope_linear", program=program, executions=runs,
            busy_s=busy, busy_ms_per_execution=1e3 * busy / runs,
            linear_ms=ms["linear"], experts_ms=ms["experts"],
            attention_ms=ms["attention"],
            linear_pct=1e-1 * ms["linear"] * runs / busy,
            experts_pct=1e-1 * ms["experts"] * runs / busy,
            attention_pct=1e-1 * ms["attention"] * runs / busy,
            by_scope={k: {"pct": 100.0 * v / busy, "ms": 1e3 * v / runs}
                      for k, v in sorted(totals.items(),
                                         key=lambda kv: -kv[1])})
    return 1e-1 * ms["linear"] * runs / busy


def read(run):
    return share(run, "decode")
