"""Pallas TPU kernel: which keys each query of a prompt chunk PICKS, over
ONE slot's ring of indexer keys, with the index scores in VMEM from their
products to the last pass of the exact top-k.

``ops/sparse_select.py``'s XLA arm makes the chunk's float32 index scores
[queries, indexer heads, keys] a group of queries at a time, sends them to
memory between the product and the relu-weighted sum, and then reads the
[queries, keys] sort keys from memory again in each of the 16 passes of
``kth_largest``: at 512 queries over tens of thousands of keys those trips,
not the products or the comparisons, are what picking costs (8.0 ms of a
16.7 ms chunk: PERF.md section 6, PR 60). Here a grid step holds a block of
``QUERY_ROWS`` queries (fewer over a window that would not fit VMEM so:
``query_rows``) and walks the key blocks in a loop of its own: the
ring's blocks that hold a row before ``start``, then the chunk's own rows.
A block's float32 products [queries x heads, block] meet relu, the weights
and the sum over the heads and are gone; the scores become integers of the
same order (``sort_keys``' order, signed: the least int32 where a key is
not eligible) in a VMEM scratch of a row a query. The threshold search of
``kth_largest`` then runs over that scratch, a bit a pass, counting
into a partial sum a lane that is added up across lanes once a pass; ties
at the threshold go to the lower positions (ring rows before own rows) by
``select_mask``'s search over positions, which a query block pays only
where one of its queries really has more equals than it needs. What comes
out is what ``ops/sparse_chunk.py`` takes: an additive bias in bfloat16, 0
where query i picked the key and -1e30 where it did not, over ALL of the
window's ring rows (a block past ``start`` is written -1e30 unread) and
over the chunk's own.

The slot's indexer keys come TRANSPOSED, ``[dI, old]``: a block ``[dI,
block]`` is lane-dense and the product ``[queries x heads, dI] x [dI,
block]`` needs no transposition. They stay in memory (``pl.ANY``); the
first grid step copies the blocks before ``start`` into VMEM, once, and the
later query blocks find them there: the ring is read once and only as far
as the chunk may see.

The same selection as the XLA arm: operands in the rings' type into the
product, float32 scores, the exact ``min(topk, eligible)`` largest with
ties to the lower position. The float32 sum over the heads may round
otherwise than XLA's, so a pick AT the threshold may differ; on scores that
float32 holds exactly the masks are equal bit for bit
(``tests/test_sparse_select.py``).

On CPU (tests) the kernel runs in Pallas interpret mode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30
_LANES = 128
# ``sort_keys``' 0, below every score's key, in the signed order
_NOT_ELIGIBLE = -2 ** 31

# Ring rows a block: ``ops/sparse_chunk.py``'s, so that one window tiles for
# both kernels.
BLOCK_ROWS = 512

# Queries a grid step at most, as measured on a v5e at 512 queries of 16
# heads over keys of 64 (PERF.md section 6, PR 61; the kernel alone with
# 9,728 / 31,744 ring rows in sight, where the XLA picks take 1.17 / 2.52
# ms): 128 queries 0.328 / 0.855 ms, 64 queries 0.356 / 0.900. The search
# takes ONE bit of the threshold a pass: a pass is bound by the vector unit
# (a compare, a select and an add a key a trial), not by memory, so 32
# passes of one trial beat 16 of three (2 bits a pass: 0.428 / 1.180 at 128
# queries, 0.396 / 1.055 at 64).
QUERY_ROWS = 128

_VMEM_LIMIT_BYTES = 64 * 2 ** 20


def _vmem_bytes(tq: int, c: int, heads: int, di: int, old: int,
                block: int) -> int:
    """What a grid step of ``tq`` queries holds in VMEM, counted from
    above: a ring row costs its key (as float32, whatever the ring's
    type), ``tq`` sort keys and ``tq`` bfloat16 biases in each of the
    output block's two buffers; beside the rows, the chunk's own keys and
    biases, the queries, the weights a lane and a block's products. (At
    128 queries of 16 heads that is 1,280 B a row and 8.3 MB: the chip's
    compiler takes 55,296 bfloat16 rows and refuses 57,344.)"""
    a_row = 4 * di + 4 * tq + 2 * 2 * tq
    own = 4 * tq * c + 2 * 2 * tq * c + 2 * 4 * di * c
    queries = 2 * 4 * heads * tq * max(di, _LANES)
    weights = 4 * heads * tq * _LANES + 2 * 4 * tq * _LANES
    return a_row * old + own + queries + weights + 4 * heads * tq * block


def query_rows(c: int, heads: int, di: int, old: int,
               block: int = BLOCK_ROWS, rows: int = QUERY_ROWS) -> int:
    """Queries a grid step over a window of ``old`` ring rows: ``rows``,
    halved (down to a bfloat16 tile's 16 sublanes) until a step's scratch
    and blocks fit the VMEM limit, since both grow with the window; 0
    where not even 16 do."""
    tq = min(rows, c)
    while tq >= 16 and (c % tq or _vmem_bytes(
            tq, c, heads, di, old, block) > _VMEM_LIMIT_BYTES):
        tq //= 2
    return tq if tq >= 16 else 0


def takes_kernel(c: int, heads: int, di: int, old: int,
                 block: int = BLOCK_ROWS, rows: int = QUERY_ROWS) -> bool:
    """A chunk of whole lane tiles over a ring window of whole blocks of
    whole lane tiles, an indexer whose key fills whole sublane tiles, and
    a window that some block of queries holds in VMEM (``query_rows``)
    take the kernel, whatever the indexer's ``heads``; the toy widths of
    the tiny presets, and a window too long for 16 queries, keep the XLA
    arm."""
    return old > 0 and old % block == 0 and block % _LANES == 0 \
        and c % _LANES == 0 and di % 16 == 0 \
        and query_rows(c, heads, di, old, block, rows) > 0


def _kernel(meta_ref, q_ref, w_ref, own_ref, old_hbm, bias_old_ref,
            bias_own_ref, idx_scr, keys_scr, own_scr, w_scr, sem, *, heads,
            topk):
    """Grid (query block,), sequential. ``keys_scr[b]`` holds the block's
    queries' sort keys over ring block ``b`` and ``own_scr`` those over the
    chunk's own rows, which count as the positions after the ring's: among
    the eligible that is the order of their positions."""
    n_old, tq, block = keys_scr.shape
    c = own_scr.shape[1]
    start, length = meta_ref[0], meta_ref[1]
    q0 = pl.program_id(0) * tq
    # the ring blocks that hold a row before ``start``
    n_live = jnp.minimum((start + block - 1) // block, n_old)

    def copy(b):
        return pltpu.make_async_copy(
            old_hbm.at[:, pl.ds(pl.multiple_of(b * block, block), block)],
            idx_scr.at[b], sem)

    @pl.when(pl.program_id(0) == 0)
    def _fetch():
        jax.lax.fori_loop(0, n_live, lambda b, _: copy(b).start(), None)
        jax.lax.fori_loop(0, n_live, lambda b, _: copy(b).wait(), None)

    lane = jax.lax.broadcasted_iota(jnp.int32, (tq, _LANES), 1)
    query = q0 + jax.lax.broadcasted_iota(jnp.int32, (tq, _LANES), 0)
    real = query < length
    for j in range(heads):
        w_scr[j] = jnp.broadcast_to(w_ref[:, j:j + 1], (tq, _LANES))

    def lane_tiles(width):
        return [slice(at, at + _LANES) for at in range(0, width, _LANES)]

    # -- the scores, a block at a time, into the scratch as sort keys -----
    def keys_of(k_blk, put, seen, count):
        """k_blk [dI, K] -> ``put(lanes, keys [tq, 128])`` a lane tile of
        its K keys; ``seen(first)`` [tq, 128] bool: which of the 128 keys
        from ``first`` on the queries may pick."""
        dots = jnp.dot(q_ref[...], k_blk,
                       preferred_element_type=jnp.float32)
        for of in lane_tiles(k_blk.shape[1]):
            scores = None
            for j in range(heads):
                part = jnp.maximum(dots[j * tq:(j + 1) * tq, of], 0.0) \
                    * w_scr[j]
                scores = part if scores is None else scores + part
            scores = jnp.where(scores == 0, 0.0, scores)  # -0.0 is 0.0
            as_int = jax.lax.bitcast_convert_type(scores, jnp.int32)
            ordered = jnp.where(as_int < 0, as_int ^ jnp.int32(0x7FFFFFFF),
                                as_int)
            eligible = seen(of.start) & real
            put(of, jnp.where(eligible, ordered, jnp.int32(_NOT_ELIGIBLE)))
            count = count + (eligible & (ordered != _NOT_ELIGIBLE)).astype(
                jnp.int32)
        return count

    def ring(b, count):
        def put(of, keys):
            keys_scr[b, :, of] = keys

        return keys_of(idx_scr[b], put,
                       lambda first: b * block + first + lane < start, count)

    def put_own(of, keys):
        own_scr[:, of] = keys

    count = keys_of(
        own_ref[...], put_own, lambda first: first + lane <= query,
        jax.lax.fori_loop(0, n_live, ring,
                          jnp.zeros((tq, _LANES), jnp.int32)))

    # -- the threshold: the k-th largest key of each query ----------------
    def counted(tests, n):
        """``n`` counts a query over the keys in sight: ``tests(keys [tq,
        128], at)`` -> ``n`` bools, ``at`` the keys' positions. A partial
        sum a lane across the lane tiles, added up across lanes once."""
        def add(sums, keys, at):
            return tuple(s + hit.astype(jnp.int32)
                         for s, hit in zip(sums, tests(keys, at)))

        def one(b, sums):
            for of in lane_tiles(block):
                sums = add(sums, keys_scr[b, :, of],
                           b * block + of.start + lane)
            return sums

        sums = jax.lax.fori_loop(
            0, n_live, one,
            tuple(jnp.zeros((tq, _LANES), jnp.int32) for _ in range(n)))
        for of in lane_tiles(c):
            sums = add(sums, own_scr[:, of], n_old * block + of.start + lane)
        return [jnp.sum(s, axis=1, keepdims=True) for s in sums]

    def across(column):
        return jnp.broadcast_to(column, (tq, _LANES))

    k = jnp.minimum(jnp.minimum(topk, start + query[:, :1] + 1),
                    jnp.sum(count, axis=1, keepdims=True))      # [tq, 1]
    flip = jnp.int32(_NOT_ELIGIBLE)  # unsigned order <-> signed order

    def one_pass(i, tau):
        """``kth_largest``'s pass, a bit of the threshold from the top:
        tau [tq, 1], the bits so far, as the unsigned key's bit pattern."""
        trial = tau | (jnp.int32(1) << (31 - i))
        at_least = across(trial ^ flip)
        reach, = counted(lambda keys, _: [keys >= at_least], 1)
        return jnp.where(reach >= k, trial, tau)

    tau = across(jax.lax.fori_loop(
        0, 32, one_pass, jnp.zeros((tq, 1), jnp.int32)) ^ flip)

    # -- ties: of the keys AT the threshold the lowest positions ----------
    def is_equal(keys):
        return (keys == tau) & (keys != _NOT_ELIGIBLE)

    n_above, n_equal = counted(
        lambda keys, _: [keys > tau, is_equal(keys)], 2)
    need = k - n_above
    n_keys = n_old * block + c

    def lowest():
        # ``select_mask``'s: the largest p with fewer than ``need`` equals
        # below it, a bit a pass from the top
        def one_pass(i, p):
            cand = p | (jnp.int32(1) << (n_keys.bit_length() - 1 - i))
            at_most = across(cand)
            below, = counted(
                lambda keys, at: [is_equal(keys) & (at < at_most)], 1)
            return jnp.where(below < need, cand, p)

        return jax.lax.fori_loop(0, n_keys.bit_length(), one_pass,
                                 jnp.zeros((tq, 1), jnp.int32))

    tied = jnp.max((n_equal > need).astype(jnp.int32)) > 0
    last = jax.lax.cond(tied, lowest,
                        lambda: jnp.full((tq, 1), n_keys, jnp.int32))
    last = across(jnp.where(need > 0, last, -1))

    # -- the bias over every row of the window -----------------------------
    def bias_of(keys, at):
        return jnp.where(
            (keys > tau) | (is_equal(keys) & (at <= last)), 0.0,
            _NEG_INF).astype(bias_old_ref.dtype)

    def write(b, _):
        base = pl.multiple_of(b * block, block)

        @pl.when(b < n_live)
        def _live():
            for of in lane_tiles(block):
                bias_old_ref[:, pl.ds(base + of.start, _LANES)] = bias_of(
                    keys_scr[b, :, of], base + of.start + lane)

        @pl.when(b >= n_live)
        def _dead():
            bias_old_ref[:, pl.ds(base, block)] = jnp.full(
                (tq, block), _NEG_INF, bias_old_ref.dtype)

    jax.lax.fori_loop(0, n_old, write, None)
    for of in lane_tiles(c):
        bias_own_ref[:, of] = bias_of(own_scr[:, of],
                                      n_old * block + of.start + lane)


# jitted so that a program's layers (and rows) share ONE traced and lowered
# copy of the kernel (``ops/latent_chunk.py`` says what a lowering costs)
@functools.partial(jax.jit, static_argnames=("topk", "block", "rows"))
def sparse_pick(q_idx: jax.Array, w_idx: jax.Array, idx_own: jax.Array,
                idx_old_t: jax.Array, start, length, topk: int,
                block: int = BLOCK_ROWS,
                rows: int = QUERY_ROWS) -> tuple[jax.Array, jax.Array]:
    """q_idx [C, J, dI] the indexer queries of positions ``start + i``, in
    the ring's type, w_idx [C, J] float32 their weights; idx_own [C, dI]
    the chunk's own indexer keys; idx_old_t [dI, old] the slot's first
    ``old`` ring rows' keys, transposed (whole blocks; the rows ``>=
    start`` are not read); start, length int32 scalars (the chunk's first
    ``length`` queries are real: the others pick nothing). Query i picks
    the ``min(topk, start + i + 1)`` keys its index scores
    (``sparse_select.index_scores``) rank highest among ring rows ``<
    start`` and own rows ``<= i``, ties to the lower position, ring rows
    before own rows. -> (bias_old [C, old], bias_own [C, C]) bfloat16: 0
    where query i picked the key, -1e30 where it did not."""
    c, heads, di = q_idx.shape
    old = idx_old_t.shape[1]
    tq = query_rows(c, heads, di, old, block, rows)
    n_old = old // block
    # a query block's rows by (head, query): a head's products are a slab
    # of whole sublane tiles, and the sum over heads plain additions
    q_rows = q_idx.reshape(c // tq, tq, heads, di).transpose(
        0, 2, 1, 3).reshape(c // tq, heads * tq, di)
    return pl.pallas_call(
        functools.partial(_kernel, heads=heads, topk=topk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(c // tq,),
            in_specs=[
                pl.BlockSpec((None, heads * tq, di),
                             lambda i, meta: (i, 0, 0)),
                pl.BlockSpec((tq, heads), lambda i, meta: (i, 0)),
                pl.BlockSpec((di, c), lambda i, meta: (0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=[
                pl.BlockSpec((tq, old), lambda i, meta: (i, 0)),
                pl.BlockSpec((tq, c), lambda i, meta: (i, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((n_old, di, block), idx_old_t.dtype),
                pltpu.VMEM((n_old, tq, block), jnp.int32),
                pltpu.VMEM((tq, c), jnp.int32),
                pltpu.VMEM((heads, tq, _LANES), jnp.float32),
                pltpu.SemaphoreType.DMA(()),
            ]),
        out_shape=[jax.ShapeDtypeStruct((c, old), jnp.bfloat16),
                   jax.ShapeDtypeStruct((c, c), jnp.bfloat16)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=jax.default_backend() == "cpu",
        name="sparse_pick",
    )(jnp.stack([jnp.asarray(x, jnp.int32).reshape(())
                 for x in (start, length)]),
      q_rows, w_idx.astype(jnp.float32), idx_own.T, idx_old_t)
