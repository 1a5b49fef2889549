"""Share of the prefill program's busy time on the first device that ran
under the latent mixture-of-experts layers' scopes (``router``,
``latent_proj``, ``moe_dispatch``, ``experts`` with the TPU's grouped
product, ``moe_combine``, ``shared_expert``): in a decode cell whose fixed
lane takes most of the device's time, this is the lane's largest part
(device trace; the table, ``prefill_by_scope_hybrid``, and the scope list
are ``decode_moe_time_pct.py``'s)."""

from benchmark.loading import sibling

hybrid = sibling(__file__, "decode_moe_time_pct.py")


def read(run):
    return hybrid.share(run, hybrid.MOE, "prefill")
