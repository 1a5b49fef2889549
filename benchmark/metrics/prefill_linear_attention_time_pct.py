"""Share of the prefill chunk program's busy time on the first device that
ran under the scopes of the LINEAR-attention layers
(``decode_linear_attention_time_pct.py`` has the scope lists and the
reduction): ``gdn_proj``, ``conv``, ``gdn_scan`` (the blocked delta-rule
scan over the chunk, its triangular solve a block), ``gdn_norm`` and
``state_write`` (device trace). The table goes to the earlier line
``prefill_by_scope_linear`` with the experts' and the gated attention's sums
beside it. What the compiler leaves without a path of ours is in none of
the three: the table's ``(no scope)`` says how much that is. None where the
family has no delta rule or the profile holds no operation of the chunk
program under the linear layers' scopes."""

from benchmark.loading import sibling

linear = sibling(__file__, "decode_linear_attention_time_pct.py")


def read(run):
    return linear.share(run, "prefill")
