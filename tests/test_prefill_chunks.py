"""A prompt is prefilled in chunks (PR 31): for every family the engine
serves, ``<family>_prefill_chunk`` run chunk after chunk leaves what one
whole-window pass leaves (first-token logits, K/V rows, and for the hybrid
family the convolution's tail and the SSM state), a first chunk begins
anew whatever the slot held. The sixty cases of chunks against the whole
window are ``tests/test_prefill_chunks_whole_window.py``'s and the engine
built on the chunk program is ``tests/test_prefill_chunks_engine.py``'s
(both split off in PR 64: under ``--dist loadfile`` a file is one
worker's, and this one was the whole run's longest).
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.prefill import key_window, whole_prompts
from served_families import FAMILIES

# Every family but the self-drafting one (its chunk carries the module's
# pass: ``tests/test_exaone_moe.py``), at ``Family.chunked``: the float32
# tiny configuration with what ``Family.in_chunks`` says these files need.
CHUNKED = [name for name in FAMILIES if name != "exaone_moe"]
every_family = pytest.mark.parametrize("family", CHUNKED)
# GPT-2's merged, lane-padded rows at the head counts it is served with: XL's
# 25 heads of 64 (1600 columns padded to 1664, a lane tile shared by two
# heads and the last one half empty) and 124M's 12 of 64 (768, no pad); the
# tiny one's 4 of 16 are half a tile and stay unpadded; 3 heads of 48 (144
# columns padded to 256) do not divide a tile: both run the chunk's products
# over the whole row (``_lane_groups``' other arm).
# Model functions only, no engine.
ROW_WIDTHS = {"gpt2-25x64": dict(n_head=25, d_model=1600),
              "gpt2-12x64": dict(n_head=12, d_model=768),
              "gpt2-3x48": dict(n_head=3, d_model=144)}
every_row_width = pytest.mark.parametrize("family", CHUNKED + list(ROW_WIDTHS))
CHUNK, MAX_PROMPT, CACHE_LEN, SLOTS = 4, 16, 24, 4


def _row(family):
    return FAMILIES[family.split("-")[0]]


def _cfg(family):
    return dataclasses.replace(_row(family).chunked,
                               **ROW_WIDTHS.get(family, {}))


@functools.lru_cache(maxsize=None)
def _params(family):
    return _row(family).init(jax.random.PRNGKey(31), _cfg(family))


def _prompt(n, seed=0):
    return np.random.default_rng(seed).integers(1, 200, n).astype(np.int32)


def _used_cache(family, seed):
    """A cache every part of which holds another request's leavings."""
    rng = np.random.default_rng(seed)
    return jax.tree.map(
        lambda a: jnp.asarray(rng.normal(size=a.shape), a.dtype),
        _row(family).init_cache(_cfg(family), SLOTS, CACHE_LEN))


@functools.lru_cache(maxsize=None)
def _chunk_program(family, chunk):
    """The chunk function at R = 1 and C = ``chunk`` as ONE compiled program
    a (family, chunk) a process, whatever the parameters, slot and test."""
    cfg, chunk_fn = _cfg(family), _row(family).prefill_chunk
    return jax.jit(lambda params, cache, toks, slot, at, n: chunk_fn(
        params, cache, toks, slot, at, n, cfg,
        window=key_window(MAX_PROMPT, chunk)))


def _in_chunks(family, params, cache, prompt, slot, chunk=CHUNK):
    """As the engine runs one request: ``ceil(len / chunk)`` calls of the
    chunk function with R = 1. -> (the last call's logits [V], the cache)."""
    run = _chunk_program(family, chunk)
    for at in range(0, len(prompt), chunk):
        piece = prompt[at:at + chunk]
        toks = np.zeros((1, chunk), np.int32)
        toks[0, :len(piece)] = piece
        logits, cache = run(params, cache, jnp.asarray(toks),
                            jnp.full(1, slot, jnp.int32),
                            jnp.full(1, at, jnp.int32),
                            jnp.full(1, len(piece), jnp.int32))
    return logits[0], cache


def _whole(family, params, cache, prompt, slot):
    """One pass over the whole padded window: the chunk function at
    C = MAX_PROMPT, which is the lane the engine compiled before."""
    return _in_chunks(family, params, cache, prompt, slot, chunk=MAX_PROMPT)


def _holds_rows(path, a):
    """K/V or latent rows [layer, slot, row, head, hd], GPT-2's merged
    [layer, slot, row, W] (SmallThinker's two stacks of them, ``k_full``
    and ``k_win``; Keye-VL-2.0's K/V rows ``kv`` and its indexer's keys
    ``idx``); the rest is Mamba state."""
    return a.ndim == 5 or jax.tree_util.keystr(path) in (
        "['k']", "['v']", "['k_full']", "['v_full']", "['k_win']",
        "['v_win']", "['kv']", "['idx']")


def _assert_same_state(family, got, want, slot, n):
    """The slot's real K/V rows and its whole Mamba state agree; every
    other slot is bit for bit what it was in both."""
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        a, b = np.asarray(a), np.asarray(b)
        name = jax.tree_util.keystr(path)
        if _holds_rows(path, a):  # [layer, slot, row, ...]
            np.testing.assert_allclose(a[:, slot, :n], b[:, slot, :n],
                                       rtol=1e-5, atol=1e-5, err_msg=name)
            others = [s for s in range(SLOTS) if s != slot]
            np.testing.assert_array_equal(a[:, others], b[:, others])
        elif "conv" in name:     # [layer, K-1, slot, channel]
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5,
                                       err_msg=name)
        else:                    # ssm, one array a layer: [slot, H, P, N]
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5,
                                       err_msg=name)


@every_family
@pytest.mark.parametrize("n", [CHUNK + 2, 2 * CHUNK, MAX_PROMPT - 1], ids=[
    "ends-inside-a-chunk", "ends-at-a-boundary", "several-chunks"])
def test_where_a_prompt_is_cut_changes_nothing(family, n):
    """The chunk's length is the rule's to choose (``chunk_len``: C where
    a token multiplies with every stored matrix, 2 C where it takes a share
    of the experts): the same prompt through chunks of C and of 2 C leaves
    the same greedy token, the same logits and the same state."""
    params, prompt = _params(family), _prompt(n, seed=50 + n)
    cache = _used_cache(family, seed=6)
    got, got_cache = _in_chunks(family, params, cache, prompt, slot=1)
    want, want_cache = _in_chunks(family, params, cache, prompt, slot=1,
                                  chunk=2 * CHUNK)
    assert int(jnp.argmax(got)) == int(jnp.argmax(want))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    _assert_same_state(family, got_cache, want_cache, 1, n)


@every_row_width
def test_a_first_chunk_begins_anew_whatever_the_slot_held(family):
    """``start == 0``: the K/V rows a used slot holds are not seen, its
    convolution tail and SSM state are not continued."""
    params, prompt = _params(family), _prompt(2 * CHUNK + 1, seed=3)
    used, _ = _in_chunks(family, params, _used_cache(family, seed=8),
                         prompt, slot=1)
    fresh, _ = _in_chunks(
        family, params,
        _row(family).init_cache(_cfg(family), SLOTS, CACHE_LEN), prompt,
        slot=1)
    np.testing.assert_array_equal(np.asarray(used), np.asarray(fresh))


@every_row_width
def test_the_whole_window_form_is_a_loop_over_the_chunk_function(family):
    """``<family>_prefill`` on [R, P] (what the benchmark's reference check
    calls) cut into chunks gives what it gives in one chunk, rows of
    different lengths and a scratch row together, with ONE traced copy of
    the layers."""
    cfg, row = _cfg(family), _row(family)
    chunk_fn, whole = row.prefill_chunk, row.prefill
    params = _params(family)
    lens = [MAX_PROMPT - 3, CHUNK, 1]
    toks = np.zeros((3, MAX_PROMPT), np.int32)
    for i, n in enumerate(lens):
        toks[i, :n] = _prompt(n, seed=10 + i)
    args = (jnp.asarray(toks), jnp.asarray([3, 0, 2], jnp.int32),
            jnp.asarray(lens, jnp.int32))
    cache = _used_cache(family, seed=2)
    # (op by op on purpose: under one jit each, Qwen3-Next's float32 sums
    # take another order and pass 1e-5 by a third)
    want, want_cache = whole(params, cache, *args, cfg)
    got, got_cache = whole_prompts(chunk_fn, params, cache, *args, cfg,
                                   chunk=CHUNK)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got_cache),
                            jax.tree.leaves(want_cache)):
        a, b = np.asarray(a), np.asarray(b)
        if _holds_rows(path, a):  # a prompt's own rows (past them, garbage)
            a, b = (np.concatenate([x[:, slot, :n] for slot, n in
                                    zip((3, 0, 2), lens)], 1) for x in (a, b))
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)
    jaxpr = jax.make_jaxpr(lambda c: whole_prompts(
        chunk_fn, params, c, *args, cfg, chunk=CHUNK))(cache)
    loops = [e for e in jaxpr.jaxpr.eqns if e.primitive.name in
             ("while", "scan")]
    assert len(loops) == 1  # the chunks; the layers are inside it
