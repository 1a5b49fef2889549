"""The cache contract of the two families whose K/V rings the engine's
first programs were written for (GPT-2's merged, lane-padded rows at the head
counts it is served with, Llama's heads apart), held to a plain oracle: decode
parity against the single-tenant loop, the ring's wrap, pad columns, free
slots' garbage, scratch rows, and the bfloat16 programs within the cells'
limit of float32. Model functions only, no engine (split off
``tests/test_llm_serving.py`` in PR 65).
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import gpt2, llama
from ray_tpu.models.prefill import whole_prompts
from served_families import FAMILIES, PROMPT, generated_alone

GPT2_FP32, LLAMA_FP32 = FAMILIES["gpt2"].cfg, FAMILIES["llama"].cfg


# -- decode parity vs the naive per-request loop ----------------------------


def test_decode_parity_gpt2_vs_naive():
    """prefill + cached decode steps == full-context forward, token for
    token (fp32: identical math modulo reduction order)."""
    params = gpt2.gpt2_init(jax.random.PRNGKey(0), GPT2_FP32)
    want = generated_alone("gpt2", params, PROMPT, 6)
    cache = gpt2.gpt2_init_cache(GPT2_FP32, 4, 32)
    toks = np.zeros((2, 8), np.int32)
    toks[0, :len(PROMPT)] = PROMPT
    logits, cache = gpt2.gpt2_prefill(
        params, cache, jnp.asarray(toks), jnp.asarray([2, 3], jnp.int32),
        jnp.asarray([len(PROMPT), 1], jnp.int32), GPT2_FP32)
    got = [int(jnp.argmax(logits[0]))]
    cur = np.zeros(4, np.int32)
    pos = np.zeros(4, np.int32)
    cur[2], pos[2] = got[0], len(PROMPT)
    for _ in range(5):
        lg, cache = gpt2.gpt2_decode_step(
            params, cache, jnp.asarray(cur), jnp.asarray(pos), GPT2_FP32)
        nxt = int(jnp.argmax(lg[2]))
        got.append(nxt)
        cur[2], pos[2] = nxt, pos[2] + 1
    assert got == want


def test_decode_parity_llama_vs_naive():
    """Same parity for the GQA/RoPE/SwiGLU family — the cache stores
    only n_kv_head heads and the decode path must still match."""
    params = llama.llama_init(jax.random.PRNGKey(1), LLAMA_FP32)
    want = generated_alone("llama", params, PROMPT, 6)
    cache = llama.llama_init_cache(LLAMA_FP32, 4, 32)
    assert cache["k"].shape[3] == LLAMA_FP32.n_kv_head  # GQA layout
    assert cache["k"].dtype == LLAMA_FP32.dtype  # rides activation dtype
    toks = np.zeros((1, 8), np.int32)
    toks[0, :len(PROMPT)] = PROMPT
    logits, cache = llama.llama_prefill(
        params, cache, jnp.asarray(toks), jnp.asarray([0], jnp.int32),
        jnp.asarray([len(PROMPT)], jnp.int32), LLAMA_FP32)
    got = [int(jnp.argmax(logits[0]))]
    cur = np.zeros(4, np.int32)
    pos = np.zeros(4, np.int32)
    cur[0], pos[0] = got[0], len(PROMPT)
    for _ in range(5):
        lg, cache = llama.llama_decode_step(
            params, cache, jnp.asarray(cur), jnp.asarray(pos),
            LLAMA_FP32)
        nxt = int(jnp.argmax(lg[0]))
        got.append(nxt)
        cur[0], pos[0] = nxt, pos[0] + 1
    assert got == want


# -- the cache contract, held to a plain oracle ----------------------------------
#
# The oracle is the form the serving functions had before the cache stopped
# travelling through the layer loop (PR 25): a Python loop over the layers
# that takes the layer's block of the cache, writes the new rows at the
# cursor (or at rows [0, P) of the target slot), attends over the block
# under the mask ``idx < valid``, and stacks the blocks again. Each family
# gives it its own projections; the cache logic is written once.


def _oracle_attention(q, k, v, valid):
    """q [S, H, hd] over k/v [S, L, H, hd], rows idx < valid[s]."""
    scores = jnp.einsum("shd,slhd->shl", q, k) / (q.shape[-1] ** 0.5)
    mask = jnp.arange(k.shape[1])[None, :] < valid[:, None]
    weights = jax.nn.softmax(
        jnp.where(mask[:, None, :], scores, -1e30), axis=-1)
    return jnp.einsum("shl,slhd->shd", weights, v)


class _Gpt2Oracle:
    name, cfg = "gpt2", GPT2_FP32
    init, init_cache = gpt2.gpt2_init, gpt2.gpt2_init_cache
    prefill, decode = gpt2.gpt2_prefill, gpt2.gpt2_decode_step
    prefill_chunk = gpt2.gpt2_prefill_chunk

    @classmethod
    def heads(cls, cache):
        """The merged, lane-padded rows as the oracle's [N, S, L, H, hd]."""
        h, hd = cls.cfg.n_head, cls.cfg.head_dim
        return {n: a[..., :h * hd].reshape(*a.shape[:3], h, hd)
                for n, a in cache.items()}

    @staticmethod
    def embed(params, tokens, pos, cfg):
        return params["wte"][tokens] + params["wpe"][
            jnp.clip(pos, 0, cfg.seq_len - 1)]

    @staticmethod
    def qkv(x, p, pos, cfg):
        y = gpt2._layer_norm(x, p["ln1_scale"], p["ln1_bias"])
        q, k, v = jnp.split(y @ p["attn_qkv_w"] + p["attn_qkv_b"], 3, -1)
        heads = lambda a: a.reshape(*a.shape[:-1], cfg.n_head, cfg.head_dim)
        return heads(q), heads(k), heads(v)

    @staticmethod
    def finish(x, attn, p, cfg):
        x = x + attn @ p["attn_out_w"] + p["attn_out_b"]
        return gpt2._mlp_block(x, p, x.dtype)

    @staticmethod
    def head(x, params):
        x = gpt2._layer_norm(x, params["lnf_scale"], params["lnf_bias"])
        return x @ params["wte"].T


class _LlamaOracle:
    name, cfg = "llama", LLAMA_FP32
    init, init_cache = llama.llama_init, llama.llama_init_cache
    prefill, decode = llama.llama_prefill, llama.llama_decode_step
    prefill_chunk = llama.llama_prefill_chunk
    heads = staticmethod(lambda cache: cache)   # heads apart as it lies

    @staticmethod
    def embed(params, tokens, pos, cfg):
        return params["embed"][tokens]

    @staticmethod
    def qkv(x, p, pos, cfg):
        y = llama._rms_norm(x, p["attn_norm"])
        nh, nkv, hd = cfg.n_head, cfg.n_kv_head, cfg.head_dim
        q = (y @ p["wq"]).reshape(*y.shape[:-1], nh, hd)
        k = (y @ p["wk"]).reshape(*y.shape[:-1], nkv, hd)
        v = (y @ p["wv"]).reshape(*y.shape[:-1], nkv, hd)
        if y.ndim == 2:  # decode: one token a slot, at its own position
            rope = lambda a: llama._rope_at(a, pos, cfg.rope_theta)
        else:            # prefill: positions 0..P-1
            rope = lambda a: llama._rope(a, cfg.rope_theta)
        return rope(q), rope(k), v

    @staticmethod
    def finish(x, attn, p, cfg):
        x = x + attn @ p["wo"]
        y = llama._rms_norm(x, p["mlp_norm"])
        return x + (jax.nn.silu(y @ p["w_gate"]) * (y @ p["w_up"])) \
            @ p["w_down"]

    @staticmethod
    def head(x, params):
        return llama._rms_norm(x, params["final_norm"]) @ params["lm_head"]


def _gpt2_rows_of(n_head, d_model):
    """The GPT-2 oracle at another head count: what the merged, lane-padded
    rows look like changes with it (``_Gpt2Oracle``'s 4 heads of 16 are half
    a lane tile, which ``merged_row_width`` leaves unpadded)."""
    return type(f"_Gpt2Oracle{n_head}x{d_model // n_head}", (_Gpt2Oracle,), {
        "name": f"gpt2-{n_head}x{d_model // n_head}",
        "cfg": dataclasses.replace(GPT2_FP32, n_head=n_head,
                                   d_model=d_model)})


# XL's 25 heads of 64: 1600 columns padded to 1664, the last lane tile half
# a head's and half nobody's; 124M's 12 of 64: 768 columns, no pad.
ORACLES = pytest.mark.parametrize(
    "fam", [_Gpt2Oracle, _gpt2_rows_of(25, 1600), _gpt2_rows_of(12, 768),
            _LlamaOracle], ids=lambda f: f.name)


def _oracle_decode(fam, params, cache, tokens, pos):
    """``cache`` and the cache returned are heads apart (``fam.heads``)."""
    cfg = fam.cfg
    s, cache_len = tokens.shape[0], cache["k"].shape[2]
    cursor, valid = pos % cache_len, jnp.minimum(pos + 1, cache_len)
    rep = cfg.n_head // cache["k"].shape[3]
    x = fam.embed(params, tokens, pos, cfg)
    ks, vs = [], []
    for i in range(cfg.n_layer):
        p = jax.tree.map(lambda a: a[i], params["blocks"])
        q, k_new, v_new = fam.qkv(x, p, pos, cfg)
        k = cache["k"][i].at[jnp.arange(s), cursor].set(k_new)
        v = cache["v"][i].at[jnp.arange(s), cursor].set(v_new)
        attn = _oracle_attention(q, jnp.repeat(k, rep, axis=2),
                                 jnp.repeat(v, rep, axis=2), valid)
        x = fam.finish(x, attn.reshape(s, -1), p, cfg)
        ks.append(k)
        vs.append(v)
    return fam.head(x, params), {"k": jnp.stack(ks), "v": jnp.stack(vs)}


def _oracle_prefill(fam, params, cache, tokens, slots, lengths):
    """``cache`` and the cache returned are heads apart (``fam.heads``)."""
    cfg = fam.cfg
    r, p_len = tokens.shape
    rep = cfg.n_head // cache["k"].shape[3]
    x = fam.embed(params, tokens, jnp.arange(p_len), cfg)
    causal = jnp.tril(jnp.ones((p_len, p_len), bool))
    ks, vs = [], []
    for i in range(cfg.n_layer):
        p = jax.tree.map(lambda a: a[i], params["blocks"])
        q, k_, v_ = fam.qkv(x, p, None, cfg)
        k, v = cache["k"][i], cache["v"][i]
        for row in range(r):
            k = k.at[slots[row], :p_len].set(k_[row])
            v = v.at[slots[row], :p_len].set(v_[row])
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, jnp.repeat(k_, rep, 2)) \
            / (cfg.head_dim ** 0.5)
        weights = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
        attn = jnp.einsum("bhqk,bkhd->bqhd", weights, jnp.repeat(v_, rep, 2))
        x = fam.finish(x, attn.reshape(r, p_len, -1), p, cfg)
        ks.append(k)
        vs.append(v)
    last = x[jnp.arange(r), jnp.clip(lengths - 1, 0, p_len - 1)]
    return fam.head(last, params), {"k": jnp.stack(ks), "v": jnp.stack(vs)}


def _garbage_cache(fam, slots, cache_len, seed):
    """A cache whose every row holds noise, as a recycled slot's does."""
    cache = fam.init_cache(fam.cfg, slots, cache_len)
    keys = jax.random.split(jax.random.PRNGKey(seed), 2)
    return {n: 3.0 * jax.random.normal(k, cache[n].shape, cache[n].dtype)
            for n, k in zip(("k", "v"), keys)}


def _live_rows(cache, live, pos):
    """What a later step may read: rows < min(pos + 1, L) of live slots."""
    cache_len = cache["k"].shape[2]
    return [np.asarray(cache[n][:, s, :min(int(pos[s]) + 1, cache_len)])
            for n in ("k", "v") for s in live]


def _pad_columns(fam, cache):
    """A merged cache's columns that belong to no head (none: empty)."""
    used = fam.cfg.n_head * fam.cfg.head_dim
    return [np.asarray(cache[n][..., used:]) for n in ("k", "v")
            if cache[n].ndim == 4]


@functools.lru_cache(maxsize=None)
def _params(fam):
    return fam.init(jax.random.PRNGKey(3), fam.cfg)


@functools.lru_cache(maxsize=None)
def _programs(fam):
    """The family's step and whole-window prefill and the oracle's, each
    ONE compiled program a (family, shape) for every test of this file: the
    parameters and the cache are arguments."""
    return {
        "decode": jax.jit(lambda p, c, t, n: fam.decode(p, c, t, n, fam.cfg)),
        "oracle_decode": jax.jit(
            lambda p, c, t, n: _oracle_decode(fam, p, c, t, n)),
        "prefill": jax.jit(
            lambda p, c, t, s, n: fam.prefill(p, c, t, s, n, fam.cfg)),
        "oracle_prefill": jax.jit(
            lambda p, c, t, s, n: _oracle_prefill(fam, p, c, t, s, n))}


CACHE_LEN = 8


LIVE = (0, 2)            # slots 1 and 3 are free and hold garbage


START = np.array([5, 0, 2, 0], np.int32)   # slot 0 wraps first, at pos 8


@ORACLES
@pytest.mark.parametrize("steps", [2, 4, 14],
                         ids=["before_wrap", "wrap_step", "ten_after_wrap"])
def test_decode_step_matches_oracle(fam, steps):
    """Logits and the cache's live rows agree with the scan-through
    oracle to 1e-5 before the ring wraps, on the step whose cursor wraps
    to row 0 (slot 0: pos 8 in a cache of 8), and ten steps later, when
    both live slots have wrapped."""
    params = _params(fam)
    got_cache = _garbage_cache(fam, 4, CACHE_LEN, seed=7)
    want_cache = fam.heads(got_cache)
    step, oracle = (_programs(fam)[k] for k in ("decode", "oracle_decode"))
    rng = np.random.default_rng(11)
    advance = np.isin(np.arange(4), LIVE).astype(np.int32)
    # The live slots' earlier rows are whatever the noise is: both sides
    # start from the same cache, so the window is the same on both.
    for i in range(steps):
        pos = START + i * advance   # a new array a step: jax may alias it
        tokens = jnp.asarray(rng.integers(1, 200, 4), jnp.int32)
        got, got_cache = step(params, got_cache, tokens, jnp.asarray(pos))
        want, want_cache = oracle(params, want_cache, tokens,
                                  jnp.asarray(pos))
    assert int(pos[0]) == START[0] + steps - 1
    wrapped = [int(pos[s]) >= CACHE_LEN for s in LIVE]
    assert wrapped == {2: [False, False], 4: [True, False],
                       14: [True, True]}[steps]
    np.testing.assert_allclose(np.asarray(got)[list(LIVE)],
                               np.asarray(want)[list(LIVE)],
                               rtol=1e-5, atol=1e-5)
    for g, w in zip(_live_rows(fam.heads(got_cache), LIVE, pos),
                    _live_rows(want_cache, LIVE, pos)):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-5)


@ORACLES
def test_the_pad_columns_stay_zero_after_every_write(fam):
    """A merged row's pad columns belong to no head: from ``init_cache`` on,
    a prefill in chunks (one from row 0, one from mid-prompt) and decode
    steps up to a wrapped ring write zeros there, in every slot, the free
    ones' garbage rows too. (A cache with the heads apart has no pad.)"""
    from ray_tpu.models.prefill import whole_prompts

    params = _params(fam)
    cache = fam.init_cache(fam.cfg, 4, CACHE_LEN)
    tokens = np.zeros((2, 6), np.int32)
    tokens[0], tokens[1, :2] = [5, 9, 2, 17, 3, 8], [7, 1]
    _, cache = whole_prompts(
        fam.prefill_chunk, params, cache, jnp.asarray(tokens),
        jnp.asarray([0, 2], jnp.int32), jnp.asarray([6, 2], jnp.int32),
        fam.cfg, chunk=3)
    pos = np.array([6, 0, 2, 0], np.int32)
    step = _programs(fam)["decode"]
    for i in range(4):  # slot 0 wraps at pos 8
        _, cache = step(params, cache, jnp.asarray([3, 1, 4, 1]) + i,
                        jnp.asarray(pos + i))
    for pad in _pad_columns(fam, cache):
        assert pad.shape[-1] == cache["k"].shape[-1] \
            - fam.cfg.n_head * fam.cfg.head_dim
        np.testing.assert_array_equal(pad, 0)
    assert float(jnp.abs(cache["k"][:, 0, 0]).max()) > 0  # rows were written


@ORACLES
def test_free_slots_garbage_never_reaches_live_logits(fam):
    """Two caches that differ in every row of the free slots (and in what
    the free slots are fed) give the live slots the same logits."""
    params = _params(fam)
    a = _garbage_cache(fam, 4, CACHE_LEN, seed=7)
    b = _garbage_cache(fam, 4, CACHE_LEN, seed=8)
    live = jnp.asarray(LIVE)
    b = {n: b[n].at[:, live].set(a[n][:, live]) for n in ("k", "v")}
    pos = jnp.asarray(START + np.array([6, 0, 1, 0]), jnp.int32)  # 0 wrapped
    tok_a = jnp.asarray([17, 0, 23, 0], jnp.int32)
    tok_b = jnp.asarray([17, 99, 23, 5], jnp.int32)
    pos_b = pos.at[jnp.asarray([1, 3])].set(jnp.asarray([6, 40]))
    step = _programs(fam)["decode"]
    la, _ = step(params, a, tok_a, pos)
    lb, _ = step(params, b, tok_b, pos_b)
    np.testing.assert_array_equal(np.asarray(la)[list(LIVE)],
                                  np.asarray(lb)[list(LIVE)])


@ORACLES
def test_prefill_scratch_rows_leave_other_slots_untouched(fam):
    """Two real rows and two rows pointed at the scratch slot: the real
    rows' slots hold the oracle's K/V in rows [0, P) and their old rows
    beyond, and every other slot but the scratch one is as it was."""
    params = _params(fam)
    before = _garbage_cache(fam, 6, 16, seed=9)     # slot 5 is the scratch
    tokens = np.zeros((4, 8), np.int32)
    tokens[0, :5], tokens[1, :3] = PROMPT, [7, 1, 4]
    slots = jnp.asarray([3, 1, 5, 5], jnp.int32)
    lengths = jnp.asarray([5, 3, 1, 1], jnp.int32)
    programs = _programs(fam)
    got, after = programs["prefill"](
        params, jax.tree.map(jnp.copy, before), jnp.asarray(tokens), slots,
        lengths)
    want, oracle_after = programs["oracle_prefill"](
        params, fam.heads(before), jnp.asarray(tokens), slots, lengths)
    np.testing.assert_allclose(np.asarray(got)[:2], np.asarray(want)[:2],
                               rtol=1e-5, atol=1e-5)
    for n in ("k", "v"):
        for slot in (0, 2, 4):
            np.testing.assert_array_equal(np.asarray(after[n][:, slot]),
                                          np.asarray(before[n][:, slot]))
        for slot in (3, 1):
            np.testing.assert_allclose(
                np.asarray(fam.heads(after)[n][:, slot, :8]),
                np.asarray(oracle_after[n][:, slot, :8]),
                rtol=1e-5, atol=1e-5)
            np.testing.assert_array_equal(
                np.asarray(after[n][:, slot, 8:]),
                np.asarray(before[n][:, slot, 8:]))


@ORACLES
def test_decode_after_prefill_reads_the_rows_prefill_wrote(fam):
    """Prefill then one decode step agree with the oracle's pair, and the
    step's logits move when a prefilled row of the live slot is changed
    (so it is the cache the step reads, not a copy of the prompt)."""
    params = _params(fam)
    cache = fam.init_cache(fam.cfg, 4, 16)
    tokens = np.zeros((2, 8), np.int32)
    tokens[0, :5] = PROMPT
    args = (jnp.asarray(tokens), jnp.asarray([2, 3], jnp.int32),
            jnp.asarray([5, 1], jnp.int32))
    programs = _programs(fam)
    first, got_cache = programs["prefill"](params, cache, *args)
    want_first, want_cache = programs["oracle_prefill"](
        params, fam.heads(cache), *args)
    cur = jnp.zeros(4, jnp.int32).at[2].set(jnp.argmax(first[0]))
    pos = jnp.zeros(4, jnp.int32).at[2].set(5)
    got, _ = programs["decode"](params, got_cache, cur, pos)
    want, _ = programs["oracle_decode"](params, want_cache, cur, pos)
    np.testing.assert_allclose(np.asarray(first[0]),
                               np.asarray(want_first[0]),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(got[2]), np.asarray(want[2]),
                               rtol=1e-5, atol=1e-5)
    bent = {"k": got_cache["k"].at[:, 2, 1].add(1.0), "v": got_cache["v"]}
    moved, _ = programs["decode"](params, bent, cur, pos)
    assert float(jnp.max(jnp.abs(moved[2] - got[2]))) > 1e-3


@pytest.mark.parametrize("shape", [{}, {"n_head": 25, "d_model": 1600},
                                   {"n_head": 12, "d_model": 768}],
                         ids=["4x16", "25x64", "12x64"])
def test_bfloat16_programs_stay_within_the_cells_limit_of_float32(shape):
    """The two programs as the GPT-2 cells run them (bfloat16, merged
    bfloat16 rows) against the float32 full-context forward on the same
    weights, fed its greedy tokens: a prompt in three chunks (from row 0,
    from mid-prompt, a ragged last one) and six decode steps, every row of
    logits within the relative L2 the benchmark's configuration allows
    (``serve_logits_rel_l2`` 3e-2: today's limit, not a new one)."""
    from ray_tpu.models.prefill import whole_prompts

    served = dataclasses.replace(gpt2.GPT2Config.tiny(), **shape)
    assert served.dtype == jnp.bfloat16
    exact = dataclasses.replace(served, dtype=jnp.float32)
    params = gpt2.gpt2_init(jax.random.PRNGKey(5), served)
    prompt = [5, 9, 2, 17, 3, 11, 60, 7, 1, 4, 33]
    want_tokens = generated_alone("gpt2", params, prompt, 7, cfg=exact)
    toks = prompt + want_tokens
    want = gpt2.gpt2_forward(params, jnp.asarray([toks], jnp.int32), exact)[0]
    cache = gpt2.gpt2_init_cache(served, 3, 32)
    assert cache["k"].dtype == jnp.bfloat16 and cache["k"].ndim == 4
    got, cache = whole_prompts(
        gpt2.gpt2_prefill_chunk, params, cache,
        jnp.asarray([prompt], jnp.int32), jnp.asarray([1], jnp.int32),
        jnp.asarray([len(prompt)], jnp.int32), served, chunk=4)
    rows = [got[0]]
    step = jax.jit(lambda c, t, n: gpt2.gpt2_decode_step(
        params, c, t, n, served))
    for i in range(6):
        at = len(prompt) + i
        lg, cache = step(cache, jnp.asarray([0, toks[at], 0], jnp.int32),
                         jnp.asarray([0, at, 0], jnp.int32))
        rows.append(lg[1])
    for i, row in enumerate(rows):
        ref = np.asarray(want[len(prompt) - 1 + i], np.float64)
        err = np.linalg.norm(np.asarray(row, np.float64) - ref) \
            / np.linalg.norm(ref)
        assert err < 3e-2, (i, err)
