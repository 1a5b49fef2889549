"""Pallas TPU kernel: the held experts' two products over the rows each
expert took, and nothing else.

``ops/moe.dropless_experts`` sorts a lane's token-expert pairs by expert
(scope ``moe_dispatch``) and hands the kernel the sorted pairs' tokens and
routing weights, cut into ROW TILES: a tile is up to ``ROW_TILE`` consecutive
pairs of ONE expert, an expert with ``c`` pairs has ``ceil(c / ROW_TILE)``
tiles and an expert with none has none. The grid is ``(tile, block of F)``
with each tile's expert, first pair and pair count prefetched as scalars:

  * the weight blocks' index maps follow the TILES' experts, so an expert no
    row hit is never named, never fetched and never computed; the tiles past
    the last live one (the grid is as long as the worst routing needs) stand
    still at the last live tile's blocks, issue no copy and do nothing;
  * a tile gathers its own rows from the lane (which lies in VMEM whole, in
    float32, so that a row is a 32-bit sublane the kernel can address),
    multiplies them with its expert's ``w1`` block, applies the family's
    activation on the float32 product, multiplies with the ``w2`` block into
    a float32 accumulator, and adds each pair's row times its routing weight
    to its token's row of the float32 result, which also lies in VMEM whole
    and goes to memory once: neither the sorted rows, nor ``[pairs, F]``,
    nor ``[pairs, D]`` ever exist outside the kernel;
  * where one expert's two matrices fit the block budget whole (three of
    the five serving shapes) F is ONE block, and an expert's second tile
    finds its weights where the first left them.

Operands in the rows' type, float32 accumulation in both products, the
activation and the sum over a token's experts in float32.

On CPU (tests) the kernel runs in Pallas interpret mode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Pairs of one expert a tile (the rows of the kernel's two products).
ROW_TILE = 128

# Rows of a lane a call: the lane and its result lie in VMEM whole, in
# float32, beside the weight blocks, so a longer lane goes through the
# kernel ``LANE_ROWS`` rows at a time (``ops/moe.dropless_experts``).
LANE_ROWS = 512

# What the two weight blocks of a grid step may take of VMEM, both of their
# buffers counted, and what the kernel may take in all (a v5e core has
# 128 MiB).
_WEIGHT_BLOCKS_BYTES = 32 * 2 ** 20
_VMEM_LIMIT_BYTES = 100 * 2 ** 20


def takes_kernel(d: int, f1: int, f: int) -> bool:
    """Experts of whole 128-lane tiles (``w1`` [E, D, f1], ``w2`` [E, F, D],
    f1 = F or, gated, 2 F) take the kernel; toy widths keep the XLA form."""
    return d % 128 == 0 and f % 128 == 0 and f1 in (f, 2 * f)


def f_block(d: int, f1: int, f: int, itemsize: int) -> int:
    """The widest block of F, in whole lane tiles that divide F, whose
    weight blocks fit the budget twice (the pipeline's two buffers)."""
    for n in range(1, f // 128 + 1):
        tf = f // n
        if f % n == 0 and tf % 128 == 0 and \
                2 * (f1 // f + 1) * d * tf * itemsize <= _WEIGHT_BLOCKS_BYTES:
            return tf
    return 128


def row_tiles(counts: jax.Array) -> jax.Array:
    """The tiles of experts that took ``counts`` pairs."""
    return (counts + ROW_TILE - 1) // ROW_TILE


def n_tiles(pairs: int, n_held: int) -> int:
    """The grid's length in tiles: what the worst routing of ``pairs``
    pairs over ``n_held`` experts needs (``sum(ceil(c / ROW_TILE))`` over
    counts that sum to at most ``pairs``)."""
    return pairs // ROW_TILE + n_held


def _kernel(expert_ref, start_ref, rows_ref, tok_ref, wgt_ref, h_ref, *refs,
            activation, gated, dtype):
    """Grid (tile, block of F), both sequential: the result's block is the
    whole lane and stays in VMEM from the first step to the last."""
    del expert_ref  # the index maps' alone
    w1_refs, (w2_ref, o_ref, x_scr, y_scr) = refs[:1 + gated], refs[1 + gated:]
    i, f = pl.program_id(0), pl.program_id(1)
    start, rows = start_ref[i], rows_ref[i]

    @pl.when((i == 0) & (f == 0))
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when((rows > 0) & (f == 0))
    def _gather():
        def row(j, carry):
            x_scr[pl.ds(j, 1), :] = h_ref[pl.ds(tok_ref[start + j], 1), :]
            return carry
        jax.lax.fori_loop(0, rows, row, None)

    @pl.when(rows > 0)
    def _products():
        # rows past the tile's count hold whatever an earlier tile left:
        # they are computed and never added anywhere
        x = x_scr[...].astype(dtype)
        a = activation(jnp.concatenate(
            [jnp.dot(x, w[...], preferred_element_type=jnp.float32)
             for w in w1_refs], axis=-1))
        y = jnp.dot(a.astype(dtype), w2_ref[...],
                    preferred_element_type=jnp.float32)

        @pl.when(f == 0)
        def _first():
            y_scr[...] = y

        @pl.when(f > 0)
        def _more():
            y_scr[...] += y

    @pl.when((rows > 0) & (f == pl.num_programs(1) - 1))
    def _combine():
        def row(j, carry):
            t = pl.ds(tok_ref[start + j], 1)
            o_ref[t, :] = o_ref[t, :] + wgt_ref[start + j] \
                * y_scr[pl.ds(j, 1), :]
            return carry
        jax.lax.fori_loop(0, rows, row, None)


def grouped_experts(h: jax.Array, tile_expert: jax.Array,
                    tile_start: jax.Array, tile_rows: jax.Array,
                    tok: jax.Array, wgt: jax.Array, w1: jax.Array,
                    w2: jax.Array, activation) -> jax.Array:
    """h [T, D] the lane's rows (T at most ``LANE_ROWS``); the pairs sorted
    by expert: tok [P] int32 each pair's token, wgt [P] float32 its routing
    weight; the tiles: tile_expert / tile_start / tile_rows [``n_tiles``]
    int32, a tile's held expert, its first pair in the sorted order and its
    pairs (0: a tile that does nothing, whose expert is the last live
    tile's); w1 [E, D, F or 2 F] (gated: ``[gate, up]`` side by side) and w2
    [E, F, D] in the rows' type. -> [T, D] float32,
    ``sum wgt[p] * expert(h[tok[p]])`` over the tiles' pairs."""
    t, d = h.shape
    f1, f = w1.shape[2], w2.shape[1]
    gated = f1 == 2 * f
    tf = f_block(d, f1, f, w1.dtype.itemsize)
    n_f = f // tf

    def whole(i, f_, *_):
        return (0, 0)

    def block_of_f(i, f_, rows):
        # a tile that does nothing stands still in F too (where the last
        # live tile ended), or each of its steps would fetch a block
        return jnp.where(rows[i] > 0, f_, n_f - 1)

    def w1_block(half):
        return pl.BlockSpec(
            (None, d, tf), lambda i, f_, e, start, rows, *_: (
                e[i], 0, half * n_f + block_of_f(i, f_, rows)))

    return pl.pallas_call(
        functools.partial(_kernel, activation=activation, gated=gated,
                          dtype=h.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(tile_expert.shape[0], n_f),
            in_specs=[
                pl.BlockSpec((t, d), whole, pipeline_mode=pl.Buffered(1)),
                *[w1_block(half) for half in range(1 + gated)],
                pl.BlockSpec((None, tf, d),
                             lambda i, f_, e, start, rows, *_: (
                                 e[i], block_of_f(i, f_, rows), 0)),
            ],
            out_specs=pl.BlockSpec((t, d), whole),
            scratch_shapes=[
                pltpu.VMEM((ROW_TILE, d), jnp.float32),
                pltpu.VMEM((ROW_TILE, d), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((t, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=jax.default_backend() == "cpu",
        name="grouped_experts",
    )(tile_expert, tile_start, tile_rows, tok, wgt.astype(jnp.float32),
      h.astype(jnp.float32), *([w1] * (1 + gated)), w2)
