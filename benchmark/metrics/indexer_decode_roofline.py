"""A decode step's INDEXER as a share of its roofline: the least time the
chip could take for what one step REQUIRES of it, over the decode
program's busy time under the scope ``indexer`` an execution (the indexer's
three projections and its scores of the ring's rows; device trace).

The work is the family's ``indexer_work``: a layer, the projections'
weights read once, and for each occupied slot its live indexer keys read
ONCE and scored by every indexer head, its new key written. Occupancy and
context are the window's means, as ``decode_step_roofline.py`` takes them.
Live rows of live slots only, whatever the program reads (the whole ring
under a mask, free slots), so the share cannot pass 100. None where the
family has no such function or the profile holds no operation of the decode
program under ``attn_sparse``."""

from benchmark.loading import sibling

scopes = sibling(__file__, "decode_indexer_time_pct.py")


def read(run):
    return scopes.roofline(run, "indexer_work", "indexer")
