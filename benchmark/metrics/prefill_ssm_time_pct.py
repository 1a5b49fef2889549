"""Share of the prefill program's busy time on the first device that ran
under the Mamba-2 mixers' scopes: ``ssm_proj``, ``conv``, ``ssm_scan`` (the
chunked scan over the lane), ``ssm_norm`` and ``state_write`` (device
trace; the table, ``prefill_by_scope_hybrid``, and the scope list are
``decode_moe_time_pct.py``'s)."""

from benchmark.loading import sibling

hybrid = sibling(__file__, "decode_moe_time_pct.py")


def read(run):
    return hybrid.share(run, hybrid.SSM, "prefill")
