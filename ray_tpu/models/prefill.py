"""A prompt is prefilled in chunks: what the served families share.

Each family has ONE prefill function the serving engine compiles,
``<family>_prefill_chunk(params, cache, tokens[R, C], slots[R], start[R],
lengths[R], cfg, window=None)``: C prompt tokens a row at positions
``start + i``, of which the first ``lengths`` are real, read what earlier
chunks of the same prompt left in the row's slot, leave their own part
there, and return the logits at the chunk's last real token. A prompt of
n tokens costs ``ceil(n / C)`` executions of that one program
(``serve/llm_engine.py``); ``start == 0`` begins a prompt whatever the slot
held. This module holds what no family owns: the chunk length's rule
(``chunk_len``: as many tokens as bring a chunk's operations to the chip's
ridge for the weights it reads once, 256 where a token multiplies with
every stored matrix and one lane of the experts' kernel, 512, where it
takes ``top_k`` of the held experts) and the whole-window form
``<family>_prefill(params, cache, tokens[R, P], slots, lengths, cfg)`` as a
loop over that same function, so a family has one prefill mathematics and
whoever holds the whole-window form to a reference holds what the engine
runs.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ray_tpu.ops.moe_experts import LANE_ROWS

# A v5e does 197 TFLOP/s over 819 GB/s: 240 operations a byte. A chunk
# reads each stored weight once (2 bytes in bfloat16) and does 2 operations
# a token with each parameter a token multiplies with, so its operations a
# byte are its tokens times the share of the stored parameters one token
# multiplies with: the ridge lies at ``RIDGE x stored / a token's``
# tokens. Shorter chunks pay the weights again for nothing, longer ones pad
# more of the last chunk (what twice the rows cost the experts, +1 to +8 %
# up to one lane of their kernel and twice past it: PERF.md section 6,
# PR 52's layer-alone table; what they cost a whole chunk: PR 53).
RIDGE = 240
# The least chunk: the ridge's power of two where a token multiplies with
# every stored matrix, whatever the model's size.
CHUNK = 256


# The leaves that hold one matrix an expert, under the names every routed
# family stores what it hands ``ops/moe.dropless_experts`` as ``w1``, ``w2``.
ROUTED = ("w1", "w2")


def token_parameters(cfg, params) -> tuple[int, int]:
    """-> (the parameters ``params`` stores, those one token multiplies
    with), from the leaves' shapes (arrays or ``jax.eval_shape``'s) and
    what the configuration says of its routing: of the ``ROUTED`` leaves a
    token takes ``top_k`` of the ``n_experts`` the router scores, whatever
    share of them this chip holds. Every other leaf counts whole, the
    tables a token gathers one row of among them, so a model without
    experts reads 1 : 1 exactly."""
    share = cfg.top_k / cfg.n_experts if hasattr(cfg, "top_k") else 1.0
    stored = a_token = 0.0
    for path, leaf in jax.tree_util.tree_leaves_with_path(params):
        n = math.prod(leaf.shape)
        stored += n
        a_token += n * share if getattr(
            path[-1], "key", None) in ROUTED else n
    return int(stored), round(a_token)


def chunk_len(max_prompt_len: int, stored: int, a_token: int,
              cache_len: int | None = None) -> int:
    """The chunk an engine compiles for prompts of up to ``max_prompt_len``
    of a model that holds ``stored`` parameters of which a token multiplies
    with ``a_token`` (``token_parameters``): the fewest tokens, a power of
    two from ``CHUNK`` up, that reach the ridge; no more than one lane of
    the experts' kernel (past ``LANE_ROWS`` rows the hit experts are read
    again: PERF.md section 6, PR 52's layer-alone table), than the longest
    prompt, or, given ``cache_len``, than the longest power of two (down
    to ``CHUNK``) whose whole chunks up to ``max_prompt_len`` a slot's rows
    hold."""
    chunk = CHUNK
    while chunk < min(RIDGE * stored / a_token, LANE_ROWS):
        chunk *= 2
    while (cache_len is not None and chunk > CHUNK and key_window(
            max_prompt_len, min(chunk, max_prompt_len)) > cache_len):
        chunk //= 2
    return min(chunk, int(max_prompt_len))


def key_window(max_prompt_len: int, chunk: int) -> int:
    """Cache rows a chunk's queries may see: whole chunks up to the longest
    prompt (static; a slot's cache must hold as many)."""
    return -(-int(max_prompt_len) // chunk) * chunk


def whole_prompts(chunk_fn, params, cache, tokens: jax.Array,
                  slots: jax.Array, lengths: jax.Array, cfg,
                  chunk: int | None = None):
    """``chunk_fn`` over padded prompts tokens [R, P], every row at the same
    chunk at a time, from chunk 0 on, in chunks of ``chunk`` or of the
    rule's length for this model and P (the engine's, where P is its
    ``max_prompt_len`` and its cache holds the rule's window): -> (logits
    [R, V] at each prompt's last real token, the cache). A row whose
    prompt ended in an earlier chunk runs on with no real token: it writes
    pad garbage past its prompt, as a padded lane always did, and no
    state. One traced copy of the layers however many chunks (a
    ``fori_loop``)."""
    r, p_len = tokens.shape
    c = chunk or chunk_len(p_len, *token_parameters(cfg, params))
    n = -(-p_len // c)
    window = n * c

    def one(i, cache):
        at = i * c
        return chunk_fn(
            params, cache, jax.lax.dynamic_slice_in_dim(tokens, at, c, 1),
            slots, jnp.full((r,), at, jnp.int32),
            jnp.clip(lengths - at, 0, c).astype(jnp.int32), cfg,
            window=window)

    if n == 1:
        return one(0, cache)
    tokens = jnp.pad(tokens, ((0, 0), (0, window - p_len)))

    def body(i, carry):
        logits, cache = carry
        got, cache = one(i, cache)
        ends_here = (lengths > i * c) & (lengths <= (i + 1) * c)
        return jnp.where(ends_here[:, None], got, logits), cache

    like = jax.eval_shape(lambda held: one(0, held)[0], cache)
    return jax.lax.fori_loop(
        0, n, body, (jnp.zeros(like.shape, like.dtype), cache))
