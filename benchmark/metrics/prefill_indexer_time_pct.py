"""Share of the prefill chunk program's busy time on the first device that
ran under the scopes ``indexer`` and ``select``: a chunk's index scores
over the keys in sight and its exact top-k a query
(``decode_indexer_time_pct.py`` has the reduction; device trace). The table
goes to the earlier line ``prefill_by_sparse_scope`` with the masked
attention's share (``attn_sparse``) beside them. None where the profile
holds no operation of the chunk program under ``attn_sparse``."""

from benchmark.loading import sibling

indexer = sibling(__file__, "decode_indexer_time_pct.py")


def read(run):
    return indexer.share(run, "prefill")
