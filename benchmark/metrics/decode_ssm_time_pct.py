"""Share of the decode program's busy time on the first device that ran
under the Mamba-2 mixers' scopes: ``ssm_proj`` (in and out projections),
``conv``, ``ssm_update``, ``ssm_norm`` and ``state_write`` (device trace;
the table and the longer scope list are ``decode_moe_time_pct.py``'s)."""

from benchmark.loading import sibling

hybrid = sibling(__file__, "decode_moe_time_pct.py")


def read(run):
    return hybrid.share(run, hybrid.SSM)
