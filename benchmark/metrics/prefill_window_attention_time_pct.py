"""Share of the prefill chunk program's busy time on the first device that
ran under the scope ``attn_window``: the window layers' chunk attention
over their rings, wrapped or not (``decode_window_attention_time_pct.py``
has the reduction; device trace). The table goes to the earlier line
``prefill_by_attention_kind`` with the global layers' share beside it. None
where the profile holds no operation of the chunk program under
``attn_window``."""

from benchmark.loading import sibling

window = sibling(__file__, "decode_window_attention_time_pct.py")


def read(run):
    return window.share(run, "prefill")
