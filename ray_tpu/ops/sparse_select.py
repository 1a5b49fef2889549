"""Attention over keys the model picks at run time: a learned indexer's
scores, an EXACT top-k a query row, and attention over the rows picked.

Every other attention path of the serving programs reads a contiguous
stretch of a ring (all live rows, or a window). Here a small indexer scores
every cached position for a query (``index_scores``: ``I[t, s] = sum_j w[t,
j] relu(qI[t, j] . kI[s])``, the "lightning indexer" of DeepSeek-V3.2-Exp),
the ``topk`` largest a query are its key set ``S_t``, one set shared by all
of a token's heads, and the softmax runs over ``S_t`` alone.

The selection is exact. A sort of tens of thousands of candidates a row is
what a TPU does worst, so the k-th largest score is FOUND, not sorted for:
the float32 scores become integers of the same order (``sort_keys``) and a
radix search fixes the threshold's bits from the top, ``RADIX_BITS`` a pass,
each pass one fused compare-and-count over the candidates (``kth_largest``).
Everything above the threshold is picked; of the scores EQUAL to it the
lowest positions, as a stable sort would (``select_mask``; the extra passes
over positions run only where a row really has such ties). Nothing on the
path may return another set (no ``approx_max_k``).

A prompt chunk attends under the mask (``sparse_chunk_attention``: its
queries' sets differ, so the keys in sight are scored for all and masked).
At shapes Mosaic can tile (``chunk_select``) that is two Pallas kernels a
layer: ``ops/sparse_pick.py`` makes the index scores and the exact top-k of
a block of queries without their leaving VMEM and hands the selection on as
a bias, and ``ops/sparse_chunk.py`` attends under it; ``index_scores``,
``select_mask`` and the doubling windows of ``chunk_windows`` are then the
decode step's, the toy widths' and the kernels' reference in the tests.
A decode step turns its mask into positions (``mask_indices``: counts a
block of 128 candidates, then the picked lane inside the block, all dense
products) and GATHERS those rows out of the K/V rings
(``sparse_decode_attention``): the step's traffic is ``topk`` rows a slot a
layer whatever the context.

The K/V rings are ``ops/attention.py``'s merged rows with a token's K row
and its V row SIDE BY SIDE in one ring row, ``[N, S, L, 2 W]``: a gather
costs by the row more than by the byte (on a v5e 2,048 rows a slot of
17 out of K and out of V apart take 1.21 ms a layer, the same picks out of
rows twice as wide 0.68: PERF.md section 6, PR 60). The indexer's keys are a
second stack beside them, ``[N, S, L, dI]``. Both are read before they are
written, by ``ops/attention.py``'s ``cache_write_token`` /
``cache_write_chunk``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.ops import sparse_chunk, sparse_pick
from ray_tpu.ops.attention import (_chunk_query_rows, _heads_merged,
                                   _heads_out_of_group_columns, _kv_heads,
                                   _lane_cols, _lane_groups, _online_softmax,
                                   _slot_rows, _step_own_columns,
                                   _step_query_rows, _whole_ring_sums)

# Bits of the threshold a pass of ``kth_largest`` fixes: a pass compares
# every candidate with ``2 ** RADIX_BITS - 1`` trial thresholds and reads the
# candidates once, so more bits are fewer trips to memory and more
# comparisons (PERF.md section 6, PR 60 has the chip's readings).
RADIX_BITS = 2

# Candidates a block of ``mask_indices``: one lane tile.
_LANES = 128

# Bytes of float32 scores a chunk makes at a time: the indexer's [G, J,
# keys] of a group of G queries and, in the XLA arm of the attention (the
# shapes the kernel turns away), [H, G, K] of a group over a block of
# ``_KEY_BLOCK`` keys. On a v5e scores of 64 MiB go from the product to
# the next operation without a trip to memory and those of 128 MiB do not
# (32 heads over 8,192 keys: 0.90 ms a chunk of 512 in groups of 64 queries,
# 2.36 ms in groups of 128; PERF.md section 6, PR 60).
_GROUP_BYTES = 64 << 20
_KEY_BLOCK = 8192


def sort_keys(scores: jax.Array, eligible: jax.Array) -> jax.Array:
    """float32 scores -> uint32 of the same order (a larger score is a
    larger key, equal scores are equal keys, ``-0.0`` is ``0.0``), and 0
    where ``eligible`` is false: below every score's key."""
    scores = jnp.where(scores == 0, 0.0, scores.astype(jnp.float32))
    bits = jax.lax.bitcast_convert_type(scores, jnp.int32)
    ordered = jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)
    keys = jax.lax.bitcast_convert_type(ordered, jnp.uint32) \
        ^ jnp.uint32(0x80000000)
    return jnp.where(eligible, keys, jnp.uint32(0))


def kth_largest(keys: jax.Array, k: jax.Array) -> jax.Array:
    """The ``k``-th largest of each row of keys [..., n] uint32 (k [...]
    int32, at most the row's length): the largest threshold that at least
    ``k`` of the row reach. ``k == 0`` gives the largest uint32. One
    compare-and-count over the row a pass, ``32 / RADIX_BITS`` passes."""
    trials = jnp.arange(1, 1 << RADIX_BITS, dtype=jnp.uint32)

    def one_pass(i, tau):
        shift = (32 - RADIX_BITS * (i + 1)).astype(jnp.uint32)
        cands = tau[..., None] | (trials << shift)               # [..., T]
        reach = jnp.sum(keys[..., None, :] >= cands[..., None],
                        axis=-1, dtype=jnp.int32)                # [..., T]
        took = jnp.sum(reach >= k[..., None], axis=-1).astype(jnp.uint32)
        return tau | (took << shift)

    return jax.lax.fori_loop(0, 32 // RADIX_BITS, one_pass,
                             jnp.zeros(keys.shape[:-1], jnp.uint32))


def select_mask(keys: jax.Array, k: jax.Array) -> jax.Array:
    """[..., n] bool: the ``min(k, eligible)`` largest keys of each row of
    ``sort_keys``' keys [..., n] (k [...] int32), ties to the LOWER index:
    exactly what a stable descending sort's first ``k`` are. A row with no
    eligible candidate picks nothing."""
    n = keys.shape[-1]
    eligible = keys > 0
    k = jnp.minimum(k, jnp.sum(eligible, axis=-1, dtype=jnp.int32))
    tau = kth_largest(keys, k)[..., None]
    above, equal = keys > tau, (keys == tau) & eligible
    # of the candidates AT the threshold the row still needs this many
    need = k - jnp.sum(above, axis=-1, dtype=jnp.int32)
    at = jnp.arange(n, dtype=jnp.int32)

    def lowest(_):
        # the largest p with fewer than ``need`` equals below it: the
        # need-th equal lies AT p. Fixed a bit a pass, from the top.
        def one_pass(i, p):
            cand = p | (jnp.int32(1) << (n.bit_length() - 1 - i))
            below = jnp.sum(equal & (at < cand[..., None]), axis=-1,
                            dtype=jnp.int32)
            return jnp.where(below < need, cand, p)

        return jax.lax.fori_loop(0, n.bit_length(), one_pass,
                                 jnp.zeros(k.shape, jnp.int32))

    # a row whose equals are all needed (nearly every row: float32 scores
    # seldom tie) takes them all; only real ties pay the passes by position
    tied = jnp.any(jnp.sum(equal, axis=-1, dtype=jnp.int32) > need)
    last = jax.lax.cond(tied, lowest,
                        lambda _: jnp.full(k.shape, n, jnp.int32), None)
    return above | (equal & (at <= last[..., None]) & (need > 0)[..., None])


def mask_indices(mask: jax.Array, k: int) -> tuple[jax.Array, jax.Array]:
    """The positions a mask picks, in rising order: mask [S, n] bool with at
    most ``k`` true a row -> (idx [S, k] int32, count [S] int32); the
    entries past a row's ``count`` are 0. No sort, scatter or search: the
    picks are counted a block of 128 candidates, output j finds its block
    by comparing with the blocks' running counts, takes the block's lanes
    by a one-hot product and its own lane by the lanes' running count
    (small integers: exact in the products' bfloat16 with float32 sums)."""
    s, n = mask.shape
    pad = -n % _LANES
    blocks = jnp.pad(mask, ((0, 0), (0, pad))).reshape(s, -1, _LANES)
    n_blocks = blocks.shape[1]
    per = jnp.sum(blocks, axis=-1, dtype=jnp.int32)              # [S, B]
    ends = jnp.cumsum(per, axis=-1)
    count = ends[:, -1]
    j = jnp.arange(k, dtype=jnp.int32)
    block = jnp.minimum(jnp.sum(ends[:, None, :] <= j[None, :, None],
                                axis=-1, dtype=jnp.int32), n_blocks - 1)
    one_hot = block[..., None] == jnp.arange(n_blocks)           # [S, k, B]
    rank = j[None] - jnp.sum(
        jnp.where(one_hot, (ends - per)[:, None, :], 0), axis=-1)
    lanes = jnp.einsum("skb,sbl->skl", one_hot.astype(jnp.bfloat16),
                       blocks.astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32)       # [S, k, 128]
    upto = jnp.einsum("skl,lm->skm", lanes.astype(jnp.bfloat16),
                      jnp.triu(jnp.ones((_LANES, _LANES), jnp.bfloat16)),
                      preferred_element_type=jnp.float32)
    hit = (lanes > 0) & (upto == (rank + 1)[..., None].astype(jnp.float32))
    lane = jnp.sum(jnp.where(hit, jnp.arange(_LANES), 0), axis=-1)
    idx = block * _LANES + lane.astype(jnp.int32)
    return jnp.where(j[None] < count[:, None], idx, 0), count


def index_scores(q_idx: jax.Array, w_idx: jax.Array,
                 k_idx: jax.Array) -> jax.Array:
    """``I[..., t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])``, float32:
    q_idx [..., T, J, dI], w_idx [..., T, J] float32, k_idx [..., K, dI]
    in the ring's type -> [..., T, K]."""
    dots = jnp.einsum("...tjd,...kd->...tjk", q_idx.astype(k_idx.dtype),
                      k_idx, preferred_element_type=jnp.float32)
    return jnp.sum(jax.nn.relu(dots)
                   * w_idx.astype(jnp.float32)[..., None], axis=-2)


def _halves(rows: jax.Array):
    """A K/V row [..., 2 W] -> its merged K row and its merged V row."""
    w = rows.shape[-1] // 2
    return rows[..., :w], rows[..., w:]


# decode-path  # jax-hot-path: the rings stay in the activation dtype
def sparse_decode_attention(q: jax.Array, kv_all: jax.Array,
                            idx_all: jax.Array, kv_new: jax.Array,
                            idx_new: jax.Array, q_idx: jax.Array,
                            w_idx: jax.Array, layer: int, cursor: jax.Array,
                            valid: jax.Array, topk: int, out_dtype
                            ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One query token a slot over the ``topk`` rows of its ring that its
    indexer scores highest, the token itself a candidate like any other,
    WITHOUT its rows being in the rings yet (``cached_decode_attention``'s
    contract).

    q [S, H, hd]; kv_all [N, S, L, 2 W] the stack of K/V rows (a token's
    merged K row and V row side by side) and idx_all [N, S, L, dI] the
    indexer's keys, as they were before this step (read only), ``layer``
    which of the N; kv_new [S, 2 W] and idx_new [S, dI] the token's own
    rows in the rings' type; q_idx [S, J, dI] and w_idx [S, J] its indexer
    queries and their weights; cursor [S] the ring row the token will
    take, valid [S] the live rows with it (0: a free slot, which picks
    nothing). Three scopes: ``indexer`` scores every ring row (the whole
    ring is read, a mask keeps the live rows; the token's own score stands
    at the cursor), ``select`` picks the ``min(topk, valid)`` largest
    exactly and turns them into positions, ``attn_sparse`` gathers those
    K/V rows out of the stack (one gather: rows of a table of ``N x S x
    L``) and runs ``cached_decode_attention``'s softmax over them, the
    token's own score and value standing where the cursor was picked.
    -> (out [S, H, hd] in ``out_dtype``, rows [S, min(topk, L)] int32: the
    ring rows of each slot's set in rising order, picked [S] int32: how
    many of them count)."""
    s, h, hd = q.shape
    n_layer, _, n_rows, w2 = kv_all.shape
    k_sel = min(topk, n_rows)
    at = jnp.arange(n_rows, dtype=jnp.int32)
    with jax.named_scope("indexer"):
        scores = index_scores(q_idx[:, None], w_idx[:, None],
                              idx_all[layer])[:, 0]              # [S, L]
        own = index_scores(q_idx[:, None], w_idx[:, None],
                           idx_new[:, None])[:, 0, 0]            # [S]
        at_cursor = at[None, :] == cursor[:, None]
        scores = jnp.where(at_cursor, own[:, None], scores)
    with jax.named_scope("select"):
        keys = sort_keys(scores, at[None, :] < valid[:, None])
        mask = select_mask(keys, jnp.full((s,), k_sel, jnp.int32))
        rows, picked = mask_indices(mask, k_sel)                 # [S, k]
    with jax.named_scope("attn_sparse"):
        table = (layer * s + jnp.arange(s)[:, None]) * n_rows + rows
        k_rows, v_rows = _halves(jnp.take(
            kv_all.reshape(n_layer * s * n_rows, w2), table.reshape(-1),
            axis=0).reshape(s, k_sel, w2))
        # where among the picked rows the token's own stands: nowhere
        # (-1: no row of the list) if its own score did not make the set
        mine = (rows == cursor[:, None]) & (
            jnp.arange(k_sel)[None, :] < picked[:, None])
        own_at = jnp.where(jnp.any(mine, axis=-1),
                           jnp.argmax(mine, axis=-1), -1)
        sums = _whole_ring_sums(
            _step_query_rows(q, w2 // 2, kv_all.dtype), k_rows, v_rows,
            *_halves(kv_new), own_at, picked, hd, None)
        return _step_own_columns(sums, hd).astype(out_dtype), rows, picked


def chunk_windows(chunk: int, window: int) -> tuple:
    """The key windows a chunk program holds a branch for: whole chunks,
    doubling, up to ``window`` (the caller's bound on ``start + C``). A
    chunk at ``start`` runs the first that holds ``start + C`` rows, so its
    work follows the keys in sight to within a factor of two."""
    sizes = []
    at = chunk
    while at < window:
        sizes.append(at)
        at *= 2
    return tuple(sizes) + (window,)


def _group(chunk: int, rows: int, keys: int) -> int:
    """Queries of a chunk scored at a time so that ``rows`` float32 scores
    a query over ``keys`` keys stay within ``_GROUP_BYTES``: the chunk,
    halved while that is over the limit, even and more than 8 queries."""
    g = chunk
    while g > 8 and g % 2 == 0 and g * rows * keys * 4 > _GROUP_BYTES:
        g //= 2
    return g


def _masked_softmax(q: jax.Array, rows: jax.Array, parts: list,
                    w: int) -> jax.Array:
    """``ops/attention._chunk_softmax`` as a RUNNING softmax: a group of a
    chunk's queries (``_chunk_query_rows``) over several parts of keys,
    each (keys [R, K, W], values [R, K, W], seen [R, G, K]), a block of
    ``_KEY_BLOCK`` keys at a time, so that one block's float32 scores are
    all that is ever held. Operands in the rings' type, float32 scores,
    statistics and sums, probabilities cast to the values' type before
    their product; a query that sees no key at all (a padded row) gets
    zeros. -> [R, G, H, hd] in q's type."""
    r, c, h, hd = q.shape
    g = _kv_heads(h, hd, w)
    lead = rows.shape[:1] + rows.shape[2:4] + (c,)        # [R, T, g, G]
    carry = (jnp.full(lead, -1e30, jnp.float32), jnp.zeros(lead, jnp.float32),
             jnp.zeros(lead + rows.shape[-1:], jnp.float32))
    for keys, values, seen in parts:
        for at in range(0, keys.shape[1], _KEY_BLOCK):
            of = slice(at, at + _KEY_BLOCK)
            v = _lane_groups(values[:, of], hd)
            scores = jnp.einsum(
                "rqtgw,rktw->rtgqk", rows, _lane_groups(keys[:, of], hd),
                preferred_element_type=jnp.float32) * hd ** -0.5
            carry = _online_softmax(
                carry, scores, seen[:, None, None, :, of],
                lambda p, v=v: jnp.einsum(
                    "rtgqk,rktw->rtgqw", p.astype(v.dtype), v,
                    preferred_element_type=jnp.float32))
    _, total, sums = carry
    # a padded query sees nothing: zeros, not 0 / 0
    out = jnp.moveaxis(sums / jnp.maximum(total, 1e-30)[..., None], 3, 1)
    if g == h:
        out = _heads_merged(out, hd).reshape(r, c, w)[..., :h * hd]
    else:
        out = _heads_out_of_group_columns(
            out, g // (w // _lane_cols(w, hd)))
    return out.reshape(r, c, h, hd).astype(q.dtype)


def chunk_select(c: int, hd: int, w: int, old: int, heads: int,
                 di: int) -> str:
    """Which of ``sparse_chunk_attention``'s two implementations a chunk
    program of these shapes is built with, ``"kernel"`` or ``"xla"``: a
    chunk of c queries with heads of ``hd`` lanes in merged K rows of ``w``
    columns over ``old`` ring rows, ``heads`` indexer heads over keys of
    ``di``. Both kernels or neither: the picking kernel's bias is what the
    attention kernel reads. (The picking kernel holds the window in VMEM:
    past some 171 thousand rows at the published widths it does not fit,
    ``sparse_pick.query_rows``.)"""
    both = sparse_chunk.takes_kernel(c, hd, w, old) \
        and sparse_pick.takes_kernel(c, heads, di, old)
    return "kernel" if both else "xla"


def _chunk_through_kernels(q, kv_all, idx_all, kv_own, idx_own, q_idx, w_idx,
                           layer, slots, start, lengths, old, topk):
    """``sparse_chunk_attention`` at shapes Mosaic can tile: a call of each
    kernel a row, no branch and no window but the whole one (both kernels
    stop at ``start`` by themselves)."""
    rows = range(q.shape[0])
    with jax.named_scope("select"):
        # the slot's indexer keys cut out of their stack and transposed:
        # the stack lies ring-rows-minor, so this is the cut's own copy
        # (a Mosaic operand would have the whole stack re-laid)
        idx_old = jnp.swapaxes(_slot_rows(idx_all, layer, slots, old), 1, 2)
        bias = [sparse_pick.sparse_pick(
            q_idx[i].astype(idx_all.dtype), w_idx[i], idx_own[i], idx_old[i],
            start[i], lengths[i], topk) for i in rows]
    with jax.named_scope("attn_sparse"):
        out = jnp.stack([sparse_chunk.sparse_chunk_attention(
            q[i].astype(kv_all.dtype), kv_all, kv_own[i], *bias[i], layer,
            slots[i], start[i]) for i in rows]).astype(q.dtype)
    return out, jnp.stack([jnp.concatenate(b, axis=-1) for b in bias]) == 0


def sparse_chunk_attention(q: jax.Array, kv_all: jax.Array,
                           idx_all: jax.Array, kv_own: jax.Array,
                           idx_own: jax.Array, q_idx: jax.Array,
                           w_idx: jax.Array, layer: int, slots: jax.Array,
                           start: jax.Array, lengths: jax.Array, window: int,
                           topk: int) -> tuple[jax.Array, jax.Array]:
    """``merged_chunk_attention`` under a selection: a chunk of C prompt
    tokens a row, each over the ``topk`` keys ``<=`` itself that its indexer
    scores highest, WITHOUT the chunk's rows being in the rings yet.

    q [R, C, H, hd] at positions ``start[r] + i``; kv_all [N, S, L, 2 W]
    and idx_all [N, S, L, dI] the stacks as they were before this chunk
    (read only); kv_own [R, C, 2 W], idx_own [R, C, dI] the chunk's own
    rows in the rings' type; q_idx [R, C, J, dI], w_idx [R, C, J] its
    indexer queries and weights; slots, start, lengths [R] int32 (a row's
    first ``lengths`` tokens are real: the others pick nothing). Query i is
    scored against the slot's rows ``< start`` and the chunk's own ``<= i``
    (scope ``indexer``), picks ``min(topk, start + i + 1)`` of them exactly
    (``select``) and attends under that mask (``attn_sparse``). One
    algorithm, two implementations, chosen by whether Mosaic can tile the
    shapes (``chunk_select``), never by a name or a flag. Heads of whole
    lane tiles and an indexer key of whole sublane tiles over a window of
    whole blocks go through two Pallas kernels, a call of each a row
    (``_chunk_through_kernels``): ``ops/sparse_pick.py`` under scope
    ``select``, handed the slot's indexer keys cut out of their stack once
    and transposed, holds a block of queries' index scores in VMEM through
    every pass of the selection and returns it as a bias; ``ops/
    sparse_chunk.py`` under ``attn_sparse``, handed the K/V stack as it
    lies and that bias, keeps the attention's scores in VMEM. Neither
    reads a ring block past ``start``, so the program holds no branch and
    no window but the whole one. Everything else (toy widths, a single
    chunk over no ring) is XLA: ``index_scores`` a group of queries at a
    time (``_group``) under ``indexer``, ``select_mask`` over all of them
    at once (a pass of the selection costs a launch whatever it reads) and
    ``_masked_softmax``, inside one branch a window of ``chunk_windows``,
    of which a chunk runs the least that holds ``start + C`` rows.
    -> (out [R, C, H, hd] in q's type, mask [R, C, window] bool: what each
    query picked, over the slot's first ``window - C`` ring rows (ring row
    = position; none at or past ``start``) and then the chunk's own rows:
    for ``benchmark/tools/serve_check_sparse.py``, and dropped from a
    program that does not ask for it)."""
    r, c, h, hd = q.shape
    w = kv_all.shape[-1] // 2
    old_max = window - c
    if chunk_select(c, hd, w, old_max, *q_idx.shape[2:]) == "kernel":
        return _chunk_through_kernels(q, kv_all, idx_all, kv_own, idx_own,
                                      q_idx, w_idx, layer, slots, start,
                                      lengths, old_max, topk)
    real = jnp.arange(c)[None, :] < lengths[:, None]              # [R, C]
    sees = start[:, None] + jnp.arange(c)[None, :]                # [R, C]
    own_seen = jnp.tril(jnp.ones((c, c), bool))[None] & real[:, :, None]
    # the slots' rows are cut out of the stacks ONCE, before the branches,
    # as long as the longest window: a branch that cut its own would take
    # the stacks as operands, and a conditional's operands are copied
    cut = [_slot_rows(x, layer, slots, old_max) if old_max else None
           for x in (kv_all, idx_all)]

    def picks(old, i_old):
        """-> mask [R, C, old + C] bool over the slot's first ``old`` rows
        and then the chunk's own: among the eligible that is the order of
        their positions."""
        with jax.named_scope("indexer"):
            g = _group(c, q_idx.shape[2], old + c)
            scores = [index_scores(q_idx, w_idx, idx_own)]
            seen = [own_seen]
            if old:
                # every query's scores, a group at a time
                scores.insert(0, jnp.concatenate([index_scores(
                    q_idx[:, at:at + g], w_idx[:, at:at + g], i_old)
                    for at in range(0, c, g)], axis=1))
                seen.insert(0, (jnp.arange(old)[None, None, :]
                                < start[:, None, None]) & real[:, :, None])
        with jax.named_scope("select"):
            return select_mask(
                sort_keys(jnp.concatenate(scores, axis=-1),
                          jnp.concatenate(seen, axis=-1)),
                jnp.minimum(topk, sees + 1))

    def under(window_):
        old = window_ - c

        def attended(cut):
            kv_old, i_old = (x if x is None else x[:, :old] for x in cut)
            mask = picks(old, i_old)
            query_rows = _chunk_query_rows(q, w, kv_all.dtype)
            with jax.named_scope("attn_sparse"):
                g = _group(c, h, min(window_, _KEY_BLOCK))
                out = []
                for at in range(0, c, g):
                    of = slice(at, at + g)
                    # the group's own keys end with its last query
                    parts = [(*_halves(kv_own[:, :at + g]),
                              mask[:, of, old:old + at + g])]
                    if old:
                        parts.insert(0, (*_halves(kv_old),
                                         mask[:, of, :old]))
                    out.append(_masked_softmax(q[:, of], query_rows[:, of],
                                               parts, w))
            return jnp.concatenate(out, axis=1), jnp.concatenate(
                [jnp.pad(mask[..., :old],
                         ((0, 0), (0, 0), (0, old_max - old))),
                 mask[..., old:]], axis=-1)

        return attended

    windows = chunk_windows(c, window)
    need = jnp.max(start) + c
    return jax.lax.switch(
        jnp.sum(need > jnp.asarray(np.array(windows, np.int32))),
        [under(w_) for w_ in windows], cut)
