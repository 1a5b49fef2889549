"""A prompt is prefilled in chunks: what the served families share.

Each family has ONE prefill function the serving engine compiles,
``<family>_prefill_chunk(params, cache, tokens[R, C], slots[R], start[R],
lengths[R], cfg, window=None)``: C prompt tokens a row at positions
``start + i``, of which the first ``lengths`` are real, read what earlier
chunks of the same prompt left in the row's slot, leave their own part
there, and return the logits at the chunk's last real token. A prompt of
n tokens costs ``ceil(n / C)`` executions of that one program
(``serve/llm_engine.py``); ``start == 0`` begins a prompt whatever the slot
held. This module holds what no family owns: the chunk length's rule and
the whole-window form ``<family>_prefill(params, cache, tokens[R, P], slots,
lengths, cfg)`` as a loop over that same function, so a family has one
prefill mathematics and whoever holds the whole-window form to a reference
holds what the engine runs.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# On a v5e (197 TFLOP/s over 819 GB/s: 240 operations a byte) a chunk of
# 256 bfloat16 tokens does about as many operations as the ridge asks for
# the weights it reads once, whatever the model's size; shorter chunks pay
# the weights again for nothing, longer ones pad more of the last chunk.
CHUNK = 256


def chunk_len(max_prompt_len: int) -> int:
    """The chunk an engine compiles for prompts of up to ``max_prompt_len``."""
    return min(CHUNK, int(max_prompt_len))


def key_window(max_prompt_len: int, chunk: int) -> int:
    """Cache rows a chunk's queries may see: whole chunks up to the longest
    prompt (static; a slot's cache must hold as many)."""
    return -(-int(max_prompt_len) // chunk) * chunk


def whole_prompts(chunk_fn, params, cache, tokens: jax.Array,
                  slots: jax.Array, lengths: jax.Array, cfg,
                  chunk: int | None = None):
    """``chunk_fn`` over padded prompts tokens [R, P], every row at the same
    chunk at a time, from chunk 0 on: -> (logits [R, V] at each prompt's
    last real token, the cache). A row whose prompt ended in an earlier
    chunk runs on with no real token: it writes pad garbage past its
    prompt, as a padded lane always did, and no state. One traced copy of
    the layers however many chunks (a ``fori_loop``)."""
    r, p_len = tokens.shape
    c = chunk or chunk_len(p_len)
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
