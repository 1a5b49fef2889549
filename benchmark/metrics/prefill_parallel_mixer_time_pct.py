"""Share of the prefill chunk program's busy time on the first device that
ran under the scopes of a layer's TWO mixers (``decode_parallel_mixer_
time_pct.py`` has the scope lists and the reduction): the attention
branch's ``attn_proj``, ``rope``, ``attn`` and ``cache_write`` and the state
branch's ``ssm_proj``, ``conv``, ``ssm_scan`` (the blocked scan over the
chunk), ``ssm_norm`` and ``state_write`` (device trace). The table goes to
the earlier line ``prefill_by_scope_parallel`` with the two branches apart.
What the compiler leaves without a path of ours (a copy of a whole stack,
the scan's products where it drops the scope) is in neither branch: the
table's ``(no scope)`` says how much that is. None where the family is not
a parallel one or the profile holds no operation of the chunk program under
the state branch."""

from benchmark.loading import sibling

mixers = sibling(__file__, "decode_parallel_mixer_time_pct.py")


def read(run):
    return mixers.share(run, "prefill")
