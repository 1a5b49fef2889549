"""A decode step's attention over the PICKED rows as a share of its roofline:
the least time the chip could take for what one step REQUIRES of it, over
the decode program's busy time under the scope ``attn_sparse`` an execution
(the gather of the picked rows out of the K and V rings, the softmax and the
weighted sum; device trace).

The work is the family's ``sparse_attention_work``: for the occupied slots,
a layer, the ``min(topk, context)`` rows of K and of V a query's set holds
read ONCE, the new rows written, and the scores and weighted sums over
those keys. Occupancy and context are the window's means, as
``decode_step_roofline.py`` takes them. Picked rows of live slots only,
whatever the program reads (free slots, the rows past a short context's
set), so the share cannot pass 100; the time is ``bytes / achieved`` of the
gather more than anything, and the line ``sparse_attention_work`` says the bandwidth it reached. None
where the family has no such function or the profile holds no operation of
the decode program under ``attn_sparse``."""

from benchmark.loading import sibling

scopes = sibling(__file__, "decode_indexer_time_pct.py")


def read(run):
    return scopes.roofline(run, "sparse_attention_work", "attn_sparse")
