"""Share of the decode program's busy time on the first device that ran
under the scopes ``attn`` (``cached_decode_attention``) and
``cache_write`` (``cache_write_token``); the whole by-scope table goes to
the earlier line ``decode_by_scope``, and the prefill program's (the
same model functions, no metric of its own) to ``prefill_by_scope``
(device trace, scope path of each operation's metadata)."""

from benchmark import program_trace


def read(run):
    programs = run.params["device_programs"]
    if "prefill" in programs:
        program_trace.scope_share(run, (), programs["prefill"],
                                  "prefill_by_scope")
    return program_trace.scope_share(
        run, ("attn", "cache_write"), programs["decode"], "decode_by_scope")
