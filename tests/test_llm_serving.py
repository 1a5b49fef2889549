"""Continuous-batching LLM serving (PR 13): decode parity vs the naive
per-request loop, slot recycle/eviction, deadline-shed-mid-decode,
admission under a full batch, token streaming through handle + HTTP +
the ``ray://`` proxy, TTFT histogram exactness, and the
single-compiled-shape (no per-request recompiles) assertion.

Test order matters (``-p no:randomly`` keeps definition order): the
cluster/ray:// test tears down the module's local runtime, so it runs
last.
"""

import contextlib
import dataclasses
import json
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import ray_tpu
from ray_tpu import serve
from ray_tpu.models import (deepseek_v2, exaone_moe, falcon_h1, gpt2,
                            granite_hybrid, keye_vl2, llama, nemotron_h,
                            qwen3_next, smallthinker)
from ray_tpu.serve import _observability as obs
from ray_tpu.serve import llm_engine
from ray_tpu.serve._observability import RequestShedError
from ray_tpu.serve.llm_engine import LLMEngine
from ray_tpu.util import failpoints, metrics, tracing


@pytest.fixture(autouse=True, scope="module")
def _runtime():
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=8)
    yield
    try:
        if ray_tpu.is_initialized():
            serve.shutdown()
    except Exception:
        pass
    ray_tpu.shutdown()


@pytest.fixture(autouse=True)
def _clean_between_tests():
    yield
    failpoints.reset()
    try:
        if ray_tpu.is_initialized():
            serve.shutdown()
    except Exception:
        pass


GPT2_FP32 = dataclasses.replace(gpt2.GPT2Config.tiny(), dtype=jnp.float32)
LLAMA_FP32 = dataclasses.replace(llama.LlamaConfig.tiny(),
                                 dtype=jnp.float32)
_FP32 = dict(dtype=jnp.float32, param_dtype=jnp.float32)
NEMOTRON_FP32 = nemotron_h.NemotronHConfig.tiny(**_FP32)
# Every family the engine serves (the arms of ``_model_bundle``): its
# float32 tiny config and the full-context forward its served tokens are
# held to.
SERVED = {
    "gpt2": (GPT2_FP32, gpt2.gpt2_forward),
    "llama": (LLAMA_FP32, llama.llama_forward),
    "nemotron_h": (NEMOTRON_FP32, nemotron_h.nemotron_h_forward),
    "granite_hybrid": (granite_hybrid.GraniteHybridConfig.tiny(**_FP32),
                       granite_hybrid.granite_hybrid_forward),
    "deepseek_v2": (deepseek_v2.DeepseekV2Config.tiny(**_FP32),
                    deepseek_v2.deepseek_v2_forward),
    "falcon_h1": (falcon_h1.FalconH1Config.tiny(**_FP32),
                  falcon_h1.falcon_h1_forward),
    "qwen3_next": (qwen3_next.Qwen3NextConfig.tiny(**_FP32),
                   qwen3_next.qwen3_next_forward),
    # (window rings of 8 rows beside the global rings of ``cache_len``: the
    # engine's chunk, the longest prompt's 8 tokens, is one whole ring, and
    # every generation here outlives the window)
    "smallthinker": (smallthinker.SmallThinkerConfig.tiny(**_FP32),
                     smallthinker.smallthinker_forward),
    # (served by its verify-and-draft step: two rows a slot a step, one or
    # two tokens a slot; the tokens are the main stack's greedy ones)
    "exaone_moe": (exaone_moe.ExaoneMoeConfig.tiny(**_FP32),
                   lambda params, tokens, cfg: exaone_moe.exaone_moe_forward(
                       params, tokens, cfg)[0]),
    # (a query reads the 16 keys its indexer picks: every generation here
    # runs past 16 positions, so its later steps select)
    "keye_vl2": (keye_vl2.KeyeVL2Config.tiny(**_FP32),
                 keye_vl2.keye_vl2_forward),
}
every_family = pytest.mark.parametrize("model", list(SERVED))
PROMPT = [5, 9, 2, 17, 3]


def _naive_generate(forward, params, prompt, n, cfg):
    """The single-tenant reference loop: full-context forward + argmax
    per token — the thing the engine must match token-for-token."""
    toks = list(prompt)
    for _ in range(n):
        logits = forward(params, jnp.asarray([toks], jnp.int32), cfg)
        toks.append(int(jnp.argmax(logits[0, -1])))
    return toks[len(prompt):]


def _compiled(forward, params, cfg, width):
    """``forward`` as ONE compiled program for ``_naive_generate``: the
    families are causal, so a forward padded to ``width`` serves every
    length up to it."""
    fwd = jax.jit(lambda tokens: forward(params, tokens, cfg))

    def padded(_params, tokens, _cfg):
        n = tokens.shape[1]
        return fwd(jnp.pad(tokens, ((0, 0), (0, width - n))))[:, :n]

    return padded


def _engine(**kw):
    kw.setdefault("model", "gpt2")
    kw.setdefault("config", SERVED[kw["model"]][0])
    kw.setdefault("max_batch", 4)
    kw.setdefault("cache_len", 32)
    kw.setdefault("max_prompt_len", 8)
    kw.setdefault("max_new_tokens", 6)
    return LLMEngine(**kw)


def _snapshot():
    return obs.parse_prometheus(metrics.prometheus_text())


# -- decode parity vs the naive per-request loop ----------------------------


def test_decode_parity_gpt2_vs_naive():
    """prefill + cached decode steps == full-context forward, token for
    token (fp32: identical math modulo reduction order)."""
    params = gpt2.gpt2_init(jax.random.PRNGKey(0), GPT2_FP32)
    want = _naive_generate(gpt2.gpt2_forward, params, PROMPT, 6,
                           GPT2_FP32)
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
    want = _naive_generate(llama.llama_forward, params, PROMPT, 6,
                           LLAMA_FP32)
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


@pytest.mark.parametrize("model", ["gpt2", "llama"])
def test_engine_generate_matches_naive(model):
    """The whole engine (admission -> prefill lane -> batched decode)
    reproduces the naive loop (the hybrid family's engine is held to its
    float32 reference in test_nemotron_h.py)."""
    cfg, fwd = SERVED[model]
    eng = _engine(model=model)
    try:
        want = _naive_generate(fwd, eng.params, PROMPT, 6, cfg)
        assert eng.generate(PROMPT, 6) == want
    finally:
        eng.shutdown_engine()


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
FAMILIES = pytest.mark.parametrize(
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


CACHE_LEN = 8
LIVE = (0, 2)            # slots 1 and 3 are free and hold garbage
START = np.array([5, 0, 2, 0], np.int32)   # slot 0 wraps first, at pos 8


@FAMILIES
@pytest.mark.parametrize("steps", [2, 4, 14],
                         ids=["before_wrap", "wrap_step", "ten_after_wrap"])
def test_decode_step_matches_oracle(fam, steps):
    """Logits and the cache's live rows agree with the scan-through
    oracle to 1e-5 before the ring wraps, on the step whose cursor wraps
    to row 0 (slot 0: pos 8 in a cache of 8), and ten steps later, when
    both live slots have wrapped."""
    params = fam.init(jax.random.PRNGKey(3), fam.cfg)
    got_cache = _garbage_cache(fam, 4, CACHE_LEN, seed=7)
    want_cache = fam.heads(got_cache)
    step = jax.jit(lambda p, c, t, n: fam.decode(p, c, t, n, fam.cfg))
    oracle = jax.jit(lambda p, c, t, n: _oracle_decode(fam, p, c, t, n))
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


@FAMILIES
def test_the_pad_columns_stay_zero_after_every_write(fam):
    """A merged row's pad columns belong to no head: from ``init_cache`` on,
    a prefill in chunks (one from row 0, one from mid-prompt) and decode
    steps up to a wrapped ring write zeros there, in every slot, the free
    ones' garbage rows too. (A cache with the heads apart has no pad.)"""
    from ray_tpu.models.prefill import whole_prompts

    params = fam.init(jax.random.PRNGKey(3), fam.cfg)
    cache = fam.init_cache(fam.cfg, 4, CACHE_LEN)
    tokens = np.zeros((2, 6), np.int32)
    tokens[0], tokens[1, :2] = [5, 9, 2, 17, 3, 8], [7, 1]
    _, cache = whole_prompts(
        fam.prefill_chunk, params, cache, jnp.asarray(tokens),
        jnp.asarray([0, 2], jnp.int32), jnp.asarray([6, 2], jnp.int32),
        fam.cfg, chunk=3)
    pos = np.array([6, 0, 2, 0], np.int32)
    for i in range(4):  # slot 0 wraps at pos 8
        _, cache = fam.decode(params, cache, jnp.asarray([3, 1, 4, 1]) + i,
                              jnp.asarray(pos + i), fam.cfg)
    for pad in _pad_columns(fam, cache):
        assert pad.shape[-1] == cache["k"].shape[-1] \
            - fam.cfg.n_head * fam.cfg.head_dim
        np.testing.assert_array_equal(pad, 0)
    assert float(jnp.abs(cache["k"][:, 0, 0]).max()) > 0  # rows were written


@FAMILIES
def test_free_slots_garbage_never_reaches_live_logits(fam):
    """Two caches that differ in every row of the free slots (and in what
    the free slots are fed) give the live slots the same logits."""
    params = fam.init(jax.random.PRNGKey(3), fam.cfg)
    a = _garbage_cache(fam, 4, CACHE_LEN, seed=7)
    b = _garbage_cache(fam, 4, CACHE_LEN, seed=8)
    live = jnp.asarray(LIVE)
    b = {n: b[n].at[:, live].set(a[n][:, live]) for n in ("k", "v")}
    pos = jnp.asarray(START + np.array([6, 0, 1, 0]), jnp.int32)  # 0 wrapped
    tok_a = jnp.asarray([17, 0, 23, 0], jnp.int32)
    tok_b = jnp.asarray([17, 99, 23, 5], jnp.int32)
    pos_b = pos.at[jnp.asarray([1, 3])].set(jnp.asarray([6, 40]))
    la, _ = fam.decode(params, a, tok_a, pos, fam.cfg)
    lb, _ = fam.decode(params, b, tok_b, pos_b, fam.cfg)
    np.testing.assert_array_equal(np.asarray(la)[list(LIVE)],
                                  np.asarray(lb)[list(LIVE)])


@FAMILIES
def test_prefill_scratch_rows_leave_other_slots_untouched(fam):
    """Two real rows and two rows pointed at the scratch slot: the real
    rows' slots hold the oracle's K/V in rows [0, P) and their old rows
    beyond, and every other slot but the scratch one is as it was."""
    params = fam.init(jax.random.PRNGKey(3), fam.cfg)
    before = _garbage_cache(fam, 6, 16, seed=9)     # slot 5 is the scratch
    tokens = np.zeros((4, 8), np.int32)
    tokens[0, :5], tokens[1, :3] = PROMPT, [7, 1, 4]
    slots = jnp.asarray([3, 1, 5, 5], jnp.int32)
    lengths = jnp.asarray([5, 3, 1, 1], jnp.int32)
    got, after = fam.prefill(params, jax.tree.map(jnp.copy, before),
                             jnp.asarray(tokens), slots, lengths, fam.cfg)
    want, oracle_after = _oracle_prefill(
        fam, params, fam.heads(before), jnp.asarray(tokens), slots, lengths)
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


@FAMILIES
def test_decode_after_prefill_reads_the_rows_prefill_wrote(fam):
    """Prefill then one decode step agree with the oracle's pair, and the
    step's logits move when a prefilled row of the live slot is changed
    (so it is the cache the step reads, not a copy of the prompt)."""
    params = fam.init(jax.random.PRNGKey(3), fam.cfg)
    cache = fam.init_cache(fam.cfg, 4, 16)
    tokens = np.zeros((2, 8), np.int32)
    tokens[0, :5] = PROMPT
    args = (jnp.asarray(tokens), jnp.asarray([2, 3], jnp.int32),
            jnp.asarray([5, 1], jnp.int32))
    first, got_cache = fam.prefill(params, cache, *args, fam.cfg)
    want_first, want_cache = _oracle_prefill(fam, params, fam.heads(cache),
                                             *args)
    cur = jnp.zeros(4, jnp.int32).at[2].set(jnp.argmax(first[0]))
    pos = jnp.zeros(4, jnp.int32).at[2].set(5)
    got, _ = fam.decode(params, got_cache, cur, pos, fam.cfg)
    want, _ = _oracle_decode(fam, params, want_cache, cur, pos)
    np.testing.assert_allclose(np.asarray(first[0]),
                               np.asarray(want_first[0]),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(got[2]), np.asarray(want[2]),
                               rtol=1e-5, atol=1e-5)
    bent = {"k": got_cache["k"].at[:, 2, 1].add(1.0), "v": got_cache["v"]}
    moved, _ = fam.decode(params, bent, cur, pos, fam.cfg)
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
    want_tokens = _naive_generate(
        _compiled(gpt2.gpt2_forward, params, exact, 32), params, prompt, 7,
        exact)
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


# -- scheduler: slots, admission, deadlines ---------------------------------


@every_family
def test_slot_recycle_and_admission_queue(model):
    """More concurrent requests than slots: the overflow QUEUES (never
    errors), slots recycle as streams finish, and every request gets
    its full generation — the one it would get alone, whatever the slot
    held before it (a K/V row or a recurrent state)."""
    cfg, fwd = SERVED[model]
    eng = _engine(model=model, max_batch=2, prefill_rows=2)
    try:
        results: dict = {}
        errors: list = []

        def one(i):
            try:
                results[i] = eng.generate([i + 1, 7, 11], 5)
            except Exception as e:  # noqa: BLE001
                errors.append(repr(e))

        threads = [threading.Thread(target=one, args=(i,))
                   for i in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors, errors
        assert len(results) == 8
        alone = _compiled(fwd, eng.params, cfg, 8)
        for i, got in results.items():
            assert got == _naive_generate(
                alone, None, [i + 1, 7, 11], 5, None), i
        st = eng.llm_stats()
        assert st["admitted"] == 8          # every request held a slot
        assert st["admitted"] > eng.max_batch  # ... by recycling
        assert st["active"] == 0 and st["queued"] == 0
        assert st["completed"] == 8
    finally:
        eng.shutdown_engine()


@every_family
def test_a_freed_slot_steps_on_at_position_0_and_live_tokens_are_the_same(
        model):
    """Where a slot frees, its position goes back to 0, so that the step's
    attention reads one block of the free slot's ring and not the dead
    request's context (PR 48). What a free slot computes no one reads:
    requests of different lengths on two slots, one ending while the other
    goes on and a third taking the freed slot, get the tokens they get
    with the position left where the dead request stood (the engine as it
    was), which are the tokens each would get alone."""
    cfg, fwd = SERVED[model]
    asked = {0: ([3, 7, 11], 2), 1: ([4, 7, 11, 2], 12), 2: ([5, 9], 7),
             3: ([6, 1, 8, 8, 2], 4)}

    def served(reset):
        eng = _engine(model=model, max_batch=2, prefill_rows=1,
                      max_new_cap=16)
        if not reset:
            finish = eng._finish_locked

            def leave_the_position(req, *a, slot=None, **kw):
                was = None if slot is None else int(eng._pos[slot])
                finish(req, *a, slot=slot, **kw)
                if slot is not None:
                    eng._pos[slot] = was

            eng._finish_locked = leave_the_position
        got, errors = {}, []

        def one(i, rid):
            try:
                got[i], last = _drain(eng, rid)
                assert not last["error"] and not last["shed"], last
            except BaseException as e:  # noqa: BLE001
                errors.append(repr(e))

        try:
            threads = [threading.Thread(
                target=one, args=(i, eng.llm_submit(prompt, n)))
                for i, (prompt, n) in asked.items()]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
            assert not errors, errors
            assert eng.llm_stats()["completed"] == len(asked)
            return got, [int(p) for p in eng._pos[:eng.max_batch]], \
                eng.params
        finally:
            eng.shutdown_engine()

    with_reset, free_at, params = served(reset=True)
    as_it_was, stood_at, _ = served(reset=False)
    assert free_at == [0, 0] and min(stood_at) > 0
    assert with_reset == as_it_was
    alone = _compiled(fwd, params, cfg, 32)
    for i, (prompt, n) in asked.items():
        assert with_reset[i] == _naive_generate(alone, None, prompt, n,
                                                None), i


def test_deadline_shed_mid_decode_frees_slot():
    """A deadline dying mid-decode sheds TYPED (reason=decode) at the
    next step boundary, frees the slot, and the engine keeps serving."""
    before = _snapshot()
    eng = _engine(max_batch=2, max_new_tokens=500, max_new_cap=1000,
                  step_throttle_s=0.02)
    try:
        # both programs compiled: a first token is read a turn after its
        # chunks are dispatched, and a budget that dies before that read
        # (in a compile) sheds the request with no token at all
        eng.generate(PROMPT, 2)
        rid = eng.llm_submit(PROMPT, 500,
                             deadline_ts=time.time() + 0.3)
        got_tokens = 0
        deadline = time.monotonic() + 30.0
        shed = None
        while time.monotonic() < deadline:
            resp = eng.llm_next(rid, timeout_s=1.0)
            got_tokens += sum(len(c) for c in resp["chunks"])
            if resp["done"]:
                shed = resp["shed"]
                break
        assert shed == "decode"
        assert 0 < got_tokens < 500  # decoded some, then evicted
        st = eng.llm_stats()
        assert st["active"] == 0  # slot freed at the step boundary
        assert st["shed"] == 1
        # The slot is reusable: a fresh request completes.
        assert len(eng.generate(PROMPT, 4)) == 4
        delta = obs.diff_parsed(before, _snapshot())
        sheds = obs.sum_counter(delta, "ray_tpu_serve_shed_total",
                                "reason", deployment="llm")
        assert sheds.get("decode") == 1
    finally:
        eng.shutdown_engine()


def test_queued_deadline_shed_and_slack_admission():
    """A request whose budget dies IN the queue sheds typed without
    ever taking a slot; admission prefers tighter deadlines."""
    eng = _engine(max_batch=1, prefill_rows=1, max_new_tokens=50,
                  max_new_cap=100, step_throttle_s=0.01)
    try:
        # Occupy the only slot.
        busy = eng.llm_submit(PROMPT, 50)
        time.sleep(0.1)
        dead = eng.llm_submit(PROMPT, 4,
                              deadline_ts=time.time() + 0.05)
        time.sleep(0.3)  # budget dies while queued behind `busy`
        resp = eng.llm_next(dead, timeout_s=5.0)
        assert resp["done"] and resp["shed"] == "decode"
        # Drain the busy stream so teardown is clean.
        while not eng.llm_next(busy, timeout_s=2.0)["done"]:
            pass
    finally:
        eng.shutdown_engine()


def test_admission_full_queue_sheds_typed():
    eng = _engine(max_batch=1, max_queue=2, max_new_tokens=50,
                  max_new_cap=100, step_throttle_s=0.01)
    try:
        eng.llm_submit(PROMPT, 50)
        time.sleep(0.2)  # first request admitted to the slot
        eng.llm_submit(PROMPT, 50)
        eng.llm_submit(PROMPT, 50)
        with pytest.raises(RequestShedError) as ei:
            eng.llm_submit(PROMPT, 4)
        assert ei.value.reason == "decode"
    finally:
        eng.shutdown_engine()


@every_family
def test_cancel_frees_slot_and_queue(model):
    """llm_cancel drops a queued request and evicts an active one (the
    abandoned-caller path generate() uses on timeout): slot freed,
    stream terminates with a 'cancelled' error, engine keeps serving —
    and the next request in that slot starts from a clean state."""
    cfg, fwd = SERVED[model]
    eng = _engine(model=model, max_batch=1, prefill_rows=1,
                  max_new_tokens=100, max_new_cap=200,
                  step_throttle_s=0.01)
    try:
        active = eng.llm_submit(PROMPT, 100)
        deadline = time.monotonic() + 30.0
        while eng.llm_stats()["active"] < 1 \
                and time.monotonic() < deadline:
            time.sleep(0.05)  # first prefill compiles; wait for the slot
        assert eng.llm_stats()["active"] == 1
        queued = eng.llm_submit(PROMPT, 4)
        assert eng.llm_cancel(queued)
        assert eng.llm_cancel(active)
        assert not eng.llm_cancel(active)  # already gone
        resp = eng.llm_next(active, timeout_s=2.0)
        assert resp["done"] and resp["error"] == "cancelled"
        other = [7, 1, 30]  # the one slot, reused mid-generation
        assert eng.generate(other, 3) == _naive_generate(
            fwd, eng.params, other, 3, cfg)
    finally:
        eng.shutdown_engine()


@every_family
def test_ring_cache_wrap(model):
    """Generation past cache_len wraps the ring cursor (sliding-window
    attention) instead of erroring."""
    eng = _engine(model=model, max_batch=2, cache_len=8, max_prompt_len=8,
                  max_new_tokens=20, max_new_cap=64)
    try:
        out = eng.generate([1, 2, 3], 20)
        assert len(out) == 20
        assert eng.llm_stats()["ring_wraps"] > 0
    finally:
        eng.shutdown_engine()


@every_family
def test_compile_counters_single_shape(model):
    """Assorted prompt lengths and generation lengths all ride the SAME
    two compiled shapes — the no-per-request-recompile claim, asserted
    via trace-time counters: the engine owns two programs and no more."""
    eng = _engine(model=model, max_batch=4)
    try:
        for prompt, n in (([1], 1), ([1, 2, 3], 4), (list(range(1, 9)),
                                                     6), ([9, 9], 2)):
            assert len(eng.generate(prompt, n)) == n
        assert eng.llm_stats()["compiles"] == {"decode": 1, "prefill": 1}
    finally:
        eng.shutdown_engine()


# -- one step ahead (PR 55) ----------------------------------------------------
#
# The loop enqueues step n + 1 before it reads step n. These four hold, for
# every served family and against each request generated ALONE, that every
# live slot is still handed the token and position it would be handed by a
# loop that read first: with slots ending by count and by end token under
# an unread step, a slot changing hands under one, a dispatch that raises
# over one, and the compile count after all of it. One engine a family
# serves all four, in this order, so the last one's counts cover the lot.


@pytest.fixture(scope="module")
def ahead_engine():
    engines = {}

    def get(model):
        if model not in engines:
            eng = engines[model] = _engine(
                model=model, max_batch=2, prefill_rows=2, max_new_cap=16)
            # A host array on a 64-byte boundary is handed to the CPU's
            # runtime WITHOUT a copy, and a program that runs later (behind
            # an unread step) reads it as it is then: the engine has to
            # hand over positions that its fan-out will not move.
            raw = np.zeros(eng.max_batch + 1 + 16, np.int32)
            at = (-raw.ctypes.data % 64) // 4
            eng._pos = raw[at:at + eng.max_batch + 1]
            assert eng._pos.ctypes.data % 64 == 0
        return engines[model]

    yield get
    for eng in engines.values():
        eng.shutdown_engine()


def _alone(model, eng, asked):
    """What each ``(prompt, n)`` of ``asked`` gets generated alone."""
    cfg, fwd = SERVED[model]
    alone = _compiled(fwd, eng.params, cfg, 32)
    return {i: _naive_generate(alone, None, prompt, n, None)
            for i, (prompt, n) in asked.items()}


def _serve_all(eng, asked, **submit):
    """Submit all of ``asked`` at once, a poller thread each:
    ``{i: (tokens, last response)}``."""
    got, errors = {}, []

    def one(i, rid):
        try:
            got[i] = _drain(eng, rid)
        except BaseException as e:  # noqa: BLE001
            errors.append(repr(e))

    threads = [threading.Thread(
        target=one, args=(i, eng.llm_submit(prompt, n, **submit)))
        for i, (prompt, n) in asked.items()]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not errors and not any(t.is_alive() for t in threads), errors
    return got


def _settled(eng):
    """The engine's counters once nothing is dispatched and unread."""
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        st = eng.llm_stats()
        if not st["outstanding"] and not st["active"] and not st["queued"]:
            return st
        time.sleep(0.005)
    raise AssertionError(f"the engine did not settle: {eng.llm_stats()}")


@every_family
def test_slots_end_by_count_and_by_end_token_under_an_unread_step(
        model, ahead_engine):
    """Eight requests of staggered lengths on two slots, and an end token
    the model emits: a slot that ends by count is known to the host before
    the step is read, one that ends by its end token is stepped once more
    (the row is dropped); each successor gets the tokens it gets alone."""
    eng = ahead_engine(model)
    asked = {i: ([i + 1, 7, 11][:1 + i % 3] + [2 + i], 2 + (3 * i) % 7)
             for i in range(8)}
    alone = _alone(model, eng, asked)

    def cut(toks, eos):
        return toks[:toks.index(eos) + 1] if eos in toks else toks

    # the end token that cuts some generations short and leaves others
    # their count
    def kinds(eos):
        short = sum(len(cut(t, eos)) < len(t) for t in alone.values())
        return min(short, len(alone) - short)

    eos = max({t for toks in alone.values() for t in toks}, key=kinds)
    assert kinds(eos) >= 1, alone
    before = _settled(eng)
    eng.eos_token = eos
    try:
        got = _serve_all(eng, asked)
    finally:
        eng.eos_token = None
    st = _settled(eng)
    for i, (tokens, last) in got.items():
        assert not last["error"] and not last["shed"], last
        assert tokens == cut(alone[i], eos), (i, eos)
    assert st["completed"] - before["completed"] == len(asked)
    # every generation cut short had a step enqueued for its next token
    short = sum(len(cut(t, eos)) < len(t) for t in alone.values())
    dropped = st["rows_dropped"] - before["rows_dropped"]
    assert dropped >= short if eng._drafting else dropped == short, (
        dropped, short, eos, alone)
    assert st["steps_ahead"] > before["steps_ahead"]


@every_family
def test_a_slot_changes_hands_under_an_unread_step(model, ahead_engine):
    """A cancel, then a deadline's eviction, land between a step's
    dispatch and its read, with a request queued for the slot: it is
    admitted at once, its stream holds its own tokens only, the other
    slot's stream goes on undisturbed, and the rows the steps computed for
    the request that left are counted as dropped."""
    eng = ahead_engine(model)
    asked = {"stays": ([4, 7, 11, 2], 14), "heir": ([9, 1, 8], 5),
             "heir2": ([6, 6, 3, 1, 2], 4)}
    alone = _alone(model, eng, asked)
    before_read = _Gate()
    host = eng._sync
    _stop_before_read(eng, before_read)
    got, errors = {}, []

    def one(i, rid):
        try:
            got[i] = _drain(eng, rid)
        except BaseException as e:  # noqa: BLE001
            errors.append(repr(e))

    def submit(i):
        rid = eng.llm_submit(*asked[i])
        t = threading.Thread(target=one, args=(i, rid))
        t.start()
        return t

    try:
        st0 = _settled(eng)
        threads = [submit("stays")]
        leaves = eng.llm_submit(PROMPT, 16)
        # both decoding: a step for both is dispatched and unread, and the
        # step after it is enqueued
        for _ in range(2):
            before_read.reached()
            before_read.let()
        before_read.reached()
        assert eng.llm_stats()["active"] == 2
        threads.append(submit("heir"))                # queued for a slot
        assert eng.llm_cancel(leaves)
        for _ in range(3):   # the two steps computed a row for it; a third
            before_read.let()                         # holds the heir
            before_read.reached()
        st1 = eng.llm_stats()
        assert st1["rows_dropped"] - st0["rows_dropped"] == 2
        # ... and a deadline that dies under an unread step: the step read
        # now still hands its token out, the next select evicts
        evicted = eng.llm_submit([3, 3, 5], 16)
        threads.append(submit("heir2"))               # queued behind it
        before_read.open()
        threads[1].join(timeout=60)                   # the heir ends
        # (evicted holds the heir's slot now, or will; wait for its token)
        assert eng.llm_next(evicted, timeout_s=30.0)["chunks"]
        before_read.shut()
        before_read.reached()
        [victim] = [r for r in eng._slot_req
                    if r is not None and r.prompt == [3, 3, 5]]
        victim.deadline_ts = time.time() - 1.0
        dropped = eng.llm_stats()["rows_dropped"]
        before_read.open()
        tokens, last = _drain(eng, evicted)
        assert last["shed"] == "decode", last
        for t in threads:
            t.join(timeout=60)
        assert not errors and not any(t.is_alive() for t in threads), errors
        st2 = _settled(eng)
    finally:
        before_read.open()
        eng._sync = host
    for i, (tokens, last) in got.items():
        assert not last["error"] and not last["shed"], (i, last)
        assert tokens == alone[i], i
    # the one step enqueued before the eviction computed a row for it
    assert st2["rows_dropped"] - dropped == 1
    assert st2["shed"] - st0["shed"] == 1


@every_family
def test_a_dispatch_that_raises_loses_no_token_of_the_step_before(
        model, ahead_engine):
    """``serve.llm.before_step`` raises with a step dispatched and unread:
    that step's tokens are delivered. Once, and the stream goes on to its
    end with the tokens it gets alone; armed for good, three in a row fail
    the streams, with a prefix of them, and nothing is left unread."""
    eng = ahead_engine(model)
    asked = {"once": ([2, 9, 4], 9), "for_good": ([8, 1, 1, 6], 12),
             "after": ([5, 5, 2], 4)}
    alone = _alone(model, eng, asked)
    before_read = _Gate()
    host = eng._sync
    _stop_before_read(eng, before_read)
    try:
        st0 = _settled(eng)
        for i, arm in (("once", "raise,once"), ("for_good", "raise")):
            rid = eng.llm_submit(*asked[i])
            before_read.reached()         # a step unread, the next enqueued
            before_read.let()
            before_read.reached()
            failpoints.arm("serve.llm.before_step", arm)
            before_read.open()
            tokens, last = _drain(eng, rid)
            failpoints.reset()
            st = _settled(eng)            # nothing outstanding and unread
            if i == "once":
                assert not last["error"] and tokens == alone[i], last
                assert st["errors"] - st0["errors"] == 1
            else:
                assert "decode step failing repeatedly" in last["error"]
                # the first token, two steps read at the gate, and the step
                # that was on the device when the first dispatch raised
                assert len(tokens) >= 4 and tokens == alone[i][:len(tokens)]
                assert st["errors"] - st0["errors"] == 1 + 3 + 1
            before_read.shut()
        before_read.open()
        assert eng.generate(*asked["after"]) == alone["after"]  # recovered
    finally:
        failpoints.reset()
        before_read.open()
        eng._sync = host


@every_family
def test_nothing_is_outstanding_when_the_last_request_ends(
        model, ahead_engine):
    """After all of the above on this engine (whichever ran): the last
    stream ends by its end token with a step enqueued behind it, the loop
    reads that step before it waits, the steps ahead never outnumber the
    steps, and each program was compiled and cached ONCE, helpers
    included: every call presented the same kinds of argument."""
    eng = ahead_engine(model)
    asked = {0: ([7, 2, 9, 4], 10)}
    alone = _alone(model, eng, asked)[0]
    eng.eos_token = alone[4]
    try:
        tokens = eng.generate(*asked[0])
    finally:
        eng.eos_token = None
    assert tokens == alone[:alone.index(alone[4]) + 1]
    st = _settled(eng)
    assert st["outstanding"] == 0 and st["active"] == 0
    assert 0 < st["steps_ahead"] <= st["steps"]
    assert st["rows_dropped"] >= 1
    assert st["compiles"] == {"decode": 1, "prefill": 1}
    assert [f._cache_size() for f in (
        eng._step_fn, eng._prefill_fn, eng._carry_fn, eng._put_fn)] \
        == [1, 1, 1, 1]


def test_ttft_histogram_exact_counts():
    """Every admitted stream observes EXACTLY one TTFT sample, and the
    token counter matches the delivered tokens exactly."""
    before = _snapshot()
    eng = _engine(deployment="ttft_test")
    try:
        total = 0
        for i in range(5):
            total += len(eng.generate([i + 1, 3, 5], 4))
        delta = obs.diff_parsed(before, _snapshot())
        ttft = obs.histogram_dist(
            delta, "ray_tpu_serve_decode_ttft_seconds",
            deployment="ttft_test")
        assert ttft and int(ttft["count"]) == 5
        toks = obs.sum_counter(
            delta, "ray_tpu_serve_decode_tokens_total", "deployment",
            deployment="ttft_test")
        assert int(sum(toks.values())) == total == 20
        occ = obs.histogram_dist(
            delta, "ray_tpu_serve_decode_batch_occupancy",
            deployment="ttft_test")
        steps = obs.histogram_dist(
            delta, "ray_tpu_serve_decode_step_seconds",
            deployment="ttft_test")
        assert occ and steps and occ["count"] == steps["count"]
    finally:
        eng.shutdown_engine()


def test_failpoint_step_raise_fails_streams_fast():
    """A persistently raise-armed before_step trips the 3-strike
    fail-fast: active streams ERROR out quickly instead of waiting out
    the armed site — fail fast, never hang."""
    eng = _engine(max_new_tokens=50, max_new_cap=100,
                  step_throttle_s=0.01)
    try:
        rid = eng.llm_submit(PROMPT, 50)
        deadline = time.monotonic() + 30.0
        while eng.llm_stats()["active"] < 1 \
                and time.monotonic() < deadline:
            time.sleep(0.05)
        failpoints.arm("serve.llm.before_step", "raise")
        resp = {}
        deadline = time.monotonic() + 15.0
        while time.monotonic() < deadline:
            resp = eng.llm_next(rid, timeout_s=1.0)
            if resp["done"]:
                break
        assert resp.get("done"), "stream hung behind an armed failpoint"
        assert resp["error"], resp
        failpoints.reset()
        assert len(eng.generate(PROMPT, 3)) == 3  # engine recovered
    finally:
        failpoints.reset()
        eng.shutdown_engine()


def test_failpoint_admission_raise_recovers():
    """An armed serve.llm.before_admit raise interrupts the admission
    batch; the engine requeues and the stream still completes once the
    site disarms (raise,once) — crash the scheduler mid-iteration,
    never lose the request."""
    assert "serve.llm.before_admit" in failpoints.SITES
    assert "serve.llm.before_step" in failpoints.SITES
    eng = _engine()
    try:
        failpoints.arm("serve.llm.before_admit", "raise,once")
        assert len(eng.generate(PROMPT, 4)) == 4
        st = eng.llm_stats()
        assert st["completed"] == 1
    finally:
        failpoints.reset()
        eng.shutdown_engine()


# -- enqueue first, wake later (PR 40) ---------------------------------------
#
# A decode step's token is visible from its append under the lock; the
# stream's poller is told after the next enqueue. These hold the order,
# that no order of set, drain and clear loses or repeats a token, and
# that nobody's wake-up is stranded.


class _LoggedEvent(threading.Event):
    """A stream's event that notes every ``set`` in the test's log."""

    def __init__(self, log, stream):
        super().__init__()
        self._log, self._stream = log, stream

    def set(self):
        self._log.append(("set", self._stream))
        super().set()


def _log_sets(monkeypatch, log):
    real = llm_engine._Stream.__init__

    def init(st):
        real(st)
        st.event = _LoggedEvent(log, st)

    monkeypatch.setattr(llm_engine._Stream, "__init__", init)


class _Gate:
    """A point at which the loop's thread stops until the test lets it
    go on (``let``), or for good (``open``)."""

    def __init__(self):
        self._reached = threading.Semaphore(0)
        self._go = threading.Semaphore(0)
        self._open = False

    def stop(self):
        if not self._open:
            self._reached.release()
            assert self._go.acquire(timeout=30)

    def reached(self):
        assert self._reached.acquire(timeout=30)

    def let(self):
        self._go.release()

    def open(self):
        self._open = True
        self._go.release()

    def shut(self):
        """Stop the loop's thread at its next arrival again."""
        self._open = False


def _stop_before_flush(eng, gate, log=None):
    """The loop's thread stops at ``gate`` between a fan-out that owes
    wake-ups (tokens pending under the lock) and their flush; ``log``
    notes what was owed."""
    real = eng._flush_wakes

    def gated(enqueued):
        if eng._wakes:
            if log is not None:
                log.append(("owed", list(eng._wakes)))
            gate.stop()
        return real(enqueued)

    eng._flush_wakes = gated


def _stop_before_read(eng, gate):
    """The loop's thread stops at ``gate`` before it reads a decode step
    (its sync), with the step after it already enqueued."""
    real = eng._sync

    def gated(d):
        if isinstance(d, llm_engine._Step):
            gate.stop()
        return real(d)

    eng._sync = gated


def _drain(eng, rid, timeout_s=2.0, took=None):
    """Poll one stream to its end: (tokens, last response). ``took``
    collects how long each poll lasted."""
    out = []
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        t0 = time.monotonic()
        resp = eng.llm_next(rid, timeout_s=timeout_s)
        if took is not None:
            took.append(time.monotonic() - t0)
        for chunk in resp["chunks"]:
            out.extend(chunk)
        if resp["done"]:
            return out, resp
    raise AssertionError(f"stream {rid} did not end")


@pytest.mark.parametrize("poll_s", [0.001, 2.0],
                         ids=["polls_time_out", "polls_are_woken"])
def test_concurrent_streams_get_their_own_tokens_once_in_order(poll_s):
    """Twice as many streams as slots, a poller thread each, the
    interpreter switching threads every 10 us: each stream's tokens are
    the ones it would get alone, in order, none lost, none twice —
    whether its polls are woken (late) or time out and drain first."""
    import sys

    cfg, fwd = SERVED["gpt2"]
    eng = _engine(max_batch=4, max_new_cap=16)
    asked = {i: ([i + 1, 7, 11, i + 2], 5 + i) for i in range(8)}
    got, errors = {}, []

    def one(i, rid):
        try:
            got[i], last = _drain(eng, rid, timeout_s=poll_s)
            assert not last["error"] and not last["shed"], last
        except BaseException as e:  # noqa: BLE001
            errors.append(repr(e))

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        eng.generate(PROMPT, 2)
        threads = [threading.Thread(
            target=one, args=(i, eng.llm_submit(prompt, n)))
            for i, (prompt, n) in asked.items()]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads)
        st = eng.llm_stats()
        alone = _compiled(fwd, eng.params, cfg, 32)
    finally:
        sys.setswitchinterval(interval)
        eng.shutdown_engine()
    assert not errors, errors
    for i, (prompt, n) in asked.items():
        assert got[i] == _naive_generate(alone, None, prompt, n, None), i
    assert st["completed"] == 9 and st["errors"] == 0
    # every token but a request's first and last was a put-off wake-up
    assert st["wakes_deferred"] == sum(n - 2 for _, n in asked.values())


def test_a_steps_streams_are_woken_after_the_next_enqueue(monkeypatch):
    """Step n's wake-ups come at the tail of its fan-out and nowhere else,
    and step n + 1 was enqueued before step n was read: a turn is enqueue,
    read, fan out, wake; an admission's chunks go out behind all of it."""
    log = []
    _log_sets(monkeypatch, log)
    eng = _engine(max_batch=2, prefill_chunk=4, cache_len=64,
                  max_new_cap=64)
    try:
        eng.generate(PROMPT, 2)          # both programs compiled
        step, chunk, host = eng._step_fn, eng._prefill_fn, eng._sync

        def logged_step(*a):
            time.sleep(0.005)            # the second request arrives mid-decode
            out = step(*a)
            log.append(("enqueued", "step"))
            return out

        def logged_chunk(*a):
            out = chunk(*a)
            log.append(("enqueued", "chunk"))
            return out

        def logged_sync(d):
            log.append(("read", type(d).__name__))
            return host(d)

        eng._step_fn, eng._prefill_fn = logged_step, logged_chunk
        eng._sync = logged_sync
        gate = _Gate()
        gate.open()
        _stop_before_flush(eng, gate, log)   # (logs what is owed, never stops)
        del log[:]
        a = eng.llm_submit(PROMPT, 40)
        had = 0
        while had < 3:                   # decoding now: steps' tokens came
            had += len(eng.llm_next(a, timeout_s=30.0)["chunks"])
        b = eng.llm_submit([3, 1, 4, 1, 5, 9, 2, 6], 6)    # two chunks
        assert len(_drain(eng, b)[0]) == 6
        assert had + len(_drain(eng, a)[0]) == 40
        st = eng.llm_stats()
    finally:
        eng.shutdown_engine()
    # only the loop's thread wrote the log, so it is in program order
    owed_at = [i for i, e in enumerate(log) if e[0] == "owed"]
    assert len(owed_at) >= 38            # a's steps but its last
    chunks_behind = 0
    for n, i in enumerate(owed_at):
        owed = log[i][1]
        # the wake-ups, all of them, at once
        assert log[i + 1:i + 1 + len(owed)] == [("set", st_) for st_ in owed]
        # since the last flush: the next step enqueued, THEN this one read
        turn = [e[:2] for e in log[owed_at[n - 1] if n else 0:i]
                if e[0] in ("enqueued", "read")]
        turn = [e for e in turn if e != ("enqueued", "chunk")
                and e != ("read", "_Firsts")]
        # (the first flush's stretch also holds the turn that enqueued
        # step 1 with no step to read)
        assert turn[-2:] == [("enqueued", "step"), ("read", "_Step")], turn
        assert n == 0 or len(turn) == 2, turn
        # an admission's chunks follow the wake-ups, back to back
        rest = [e[:2] for e in log[i + 1 + len(owed):i + 3 + len(owed)]]
        if rest == [("enqueued", "chunk")] * 2:
            chunks_behind += 1
    assert chunks_behind == 1
    assert st["wakes_deferred"] == st["wakes_after_dispatch"] \
        == sum(len(log[i][1]) for i in owed_at) == 38 + 4
    # ... and no stream was told anywhere else: beside those, only the
    # two first tokens and the two terminal transitions set an event
    assert sum(e[0] == "set" for e in log) == 38 + 4 + 2 + 2


@pytest.mark.parametrize("what", ["last_step", "failpoint", "step_fn",
                                  "cancel", "shutdown"])
def test_no_poller_waits_out_its_timeout(what):
    """Whatever ends or interrupts a step, every ``llm_next`` comes back
    within a second with tokens or the terminal state: no wake-up is
    left on the list for a poll's ``timeout_s`` (20 s here) to find."""
    eng = _engine(max_batch=2, max_new_cap=64)
    took = {0: [], 1: []}
    ends, errors = {}, []
    try:
        eng.generate(PROMPT, 2)
        real, calls = eng._step_fn, []

        def slow(*a):
            calls.append(1)
            time.sleep(0.003)            # keeps the streams in mid-flight
            if what == "step_fn" and len(calls) == 6:
                raise RuntimeError("injected")
            return real(*a)

        eng._step_fn = slow
        rids = [eng.llm_submit([i + 2, 5, 8], 40) for i in (0, 1)]

        def one(i):
            try:
                ends[i] = _drain(eng, rids[i], timeout_s=20.0, took=took[i])
            except BaseException as e:  # noqa: BLE001
                errors.append(repr(e))

        threads = [threading.Thread(target=one, args=(i,)) for i in (0, 1)]
        for t in threads:
            t.start()
        deadline = time.monotonic() + 30
        while len(calls) < 4 and time.monotonic() < deadline:
            time.sleep(0.001)
        if what == "failpoint":
            failpoints.arm("serve.llm.before_step", "raise,once")
        elif what == "cancel":
            assert eng.llm_cancel(rids[0])
        elif what == "shutdown":
            assert eng.shutdown_engine()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
        st = eng.llm_stats()
    finally:
        failpoints.reset()
        eng.shutdown_engine()
    assert not errors, errors
    assert max(took[0] + took[1]) < 1.0, (took, st)
    for i in (0, 1):
        tokens, last = ends[i]
        if what == "shutdown":
            assert last["error"] == "engine stopped" and len(tokens) < 40
        elif what == "cancel" and i == 0:
            assert last["error"] == "cancelled" and len(tokens) < 40
        else:
            assert len(tokens) == 40 and not last["error"], last
    assert st["errors"] == {"last_step": 0, "failpoint": 1, "step_fn": 1,
                            "cancel": 1, "shutdown": 2}[what]


def test_a_late_wake_up_finds_nothing_and_harms_nothing():
    """A poll that times out between a token's append and its wake-up
    drains the token; the late wake-up then ends the next poll at once
    with no chunk and no error; every token arrives once."""
    cfg, fwd = SERVED["gpt2"]
    eng = _engine(max_batch=2)
    before_flush, before_read = _Gate(), _Gate()
    try:
        eng.generate(PROMPT, 2)
        _stop_before_flush(eng, before_flush)
        _stop_before_read(eng, before_read)
        rid = eng.llm_submit(PROMPT, 8)
        first = eng.llm_next(rid, timeout_s=30.0)
        before_read.reached()             # step 2 enqueued, step 1 unread
        before_read.let()                 # step 1 fans out: token 2 pending
        before_flush.reached()            # ... and its wake-up not yet set
        t0 = time.monotonic()
        second = eng.llm_next(rid, timeout_s=0.05)
        assert time.monotonic() - t0 >= 0.05          # it was not woken
        before_flush.let()                # the late set
        before_read.reached()             # ... and step 2 not fanned out
        t0 = time.monotonic()
        third = eng.llm_next(rid, timeout_s=20.0)
        assert time.monotonic() - t0 < 1.0            # woken, for nothing
        before_flush.open()
        before_read.open()
        rest, last = _drain(eng, rid)
        st = eng.llm_stats()
        alone = _compiled(fwd, eng.params, cfg, 16)
    finally:
        before_flush.open()
        before_read.open()
        eng.shutdown_engine()
    want = _naive_generate(alone, None, PROMPT, 8, None)
    assert first["chunks"] == [want[:1]] and second["chunks"] == [want[1:2]]
    assert third == {"chunks": [], "done": False, "shed": None,
                     "error": None, "held_ns": third["held_ns"]}
    assert 0 < third["held_ns"] < 1e9     # what the poll spent in the engine
    assert rest == want[2:] and not last["error"]
    assert st["wakes_deferred"] == st["wakes_after_dispatch"] == 6


@pytest.mark.parametrize("how, after_dispatch", [
    ("plain", 9), ("throttled", 0), ("a_step_fails", 7)])
def test_wake_counters_count_exactly(how, after_dispatch):
    """Two requests of 5 and 8 tokens in two slots: every token but a
    request's first (the prefill's) and last (the terminal transition's)
    is a put-off wake-up, 3 + 6; all of them follow an enqueue unless
    the engine sleeps between steps (none does) or a step fails before
    its enqueue (the step before it is read all the same, and its two
    streams are woken with nothing behind their step)."""
    eng = _engine(max_batch=2, prefill_rows=2,
                  step_throttle_s=0.001 if how == "throttled" else 0.0)
    try:
        eng.generate(PROMPT, 2)
        before = eng.llm_stats()
        if how == "a_step_fails":
            real, raised = eng._step_fn, []

            def flaky(*a):
                # once, with a step for both streams dispatched and unread
                if not raised and [len(d.rows) for d in eng._outstanding
                                   if isinstance(d, llm_engine._Step)] == [2]:
                    raised.append(1)
                    raise RuntimeError("injected")
                return real(*a)

            eng._step_fn = flaky
        rids = eng.llm_submit_many([
            {"tokens": [1, 2, 3], "max_tokens": 5},
            {"tokens": [4, 5, 6, 7], "max_tokens": 8}])
        assert [len(_drain(eng, rid)[0]) for rid in rids] == [5, 8]
        st = eng.llm_stats()
    finally:
        eng.shutdown_engine()
    assert st["wakes_deferred"] - before["wakes_deferred"] == 9
    assert st["wakes_after_dispatch"] - before["wakes_after_dispatch"] \
        == after_dispatch


# -- one poller for many streams (``llm_poll(poller=...)``) -------------------


def _poll_to_the_end(eng, pid, rids, timeout_s=2.0, took=None):
    """Drain a poller's streams to their ends with its batched long-poll:
    ``{rid: tokens}``, ``{rid: last response}``, the calls made."""
    out = {rid: [] for rid in rids}
    last, calls = {}, 0
    deadline = time.monotonic() + 60
    while len(last) < len(rids) and time.monotonic() < deadline:
        t0 = time.monotonic()
        resp = eng.llm_poll(poller=pid, timeout_s=timeout_s)
        if took is not None:
            took.append(time.monotonic() - t0)
        calls += 1
        assert resp.pop("held_ns") > 0
        for rid, r in resp.items():
            assert rid not in last, "a stream spoke after its end"
            assert r["chunks"] or r["done"]    # only those with something
            for chunk in r["chunks"]:
                out[rid].extend(chunk)
            if r["done"]:
                last[rid] = r
    assert len(last) == len(rids), "streams did not end"
    return out, last, calls


@pytest.mark.parametrize("poll_s", [0.001, 2.0],
                         ids=["polls_time_out", "polls_are_woken"])
def test_two_pollers_get_their_own_streams_tokens_once_in_order(poll_s):
    """Eight streams over four slots, four to a poller, a thread a poller
    and the interpreter switching every 10 us: each stream's tokens are
    the ones it would get alone, whether the batched polls are woken or
    time out and drain first, and a poller never sees the other's."""
    import sys

    cfg, fwd = SERVED["gpt2"]
    eng = _engine(max_batch=4, max_new_cap=16)
    asked = {i: ([i + 1, 7, 11, i + 2], 5 + i) for i in range(8)}
    got, calls, errors = {}, {}, []

    def one(pid, rids):
        try:
            out, last, calls[pid] = _poll_to_the_end(
                eng, pid, list(rids), timeout_s=poll_s)
            assert not any(r["error"] or r["shed"] for r in last.values())
            for rid, i in rids.items():
                got[i] = out[rid]
        except BaseException as e:  # noqa: BLE001
            errors.append(repr(e))

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        eng.generate(PROMPT, 2)
        before = eng.llm_stats()
        mine = {pid: {eng.llm_submit(*asked[i], poller=pid): i
                      for i in asked if i % 2 == k}
                for k, pid in enumerate(("even", "odd"))}
        assert set(eng._pollers) == {"even", "odd"}
        threads = [threading.Thread(target=one, args=item)
                   for item in mine.items()]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads)
        st = eng.llm_stats()
        alone = _compiled(fwd, eng.params, cfg, 32)
    finally:
        sys.setswitchinterval(interval)
        eng.shutdown_engine()
    assert not errors, errors
    for i, (prompt, n) in asked.items():
        assert got[i] == _naive_generate(alone, None, prompt, n, None), i
    chunks = sum(n for _, n in asked.values())
    assert st["next_calls"] - before["next_calls"] == sum(calls.values()) \
        == st["next_batched"] - before["next_batched"]
    assert st["deliver_chunks"] - before["deliver_chunks"] == chunks
    if poll_s == 2.0:
        # woken calls: four streams step together, a call takes several
        assert sum(calls.values()) < chunks
    # a wake-up is still counted a stream
    assert st["wakes_deferred"] == sum(n - 2 for _, n in asked.values())
    assert not eng._pollers and not eng._streams


@pytest.mark.parametrize("beside", ["an_idle_poller", "a_slowed_stream"])
def test_a_stream_submitted_under_a_blocked_call_is_that_calls(beside):
    """The poller's call is inside its wait (20 s) when the stream is
    submitted: its first token ends THAT call, as soon as the prefill has
    it: no first token waits for a time-out. Beside a stream whose steps
    take 0.4 s the call may end a moment sooner, for that stream's token
    (the put-off wake-up of the step before, set as the next step or the
    new prompt's first chunk is enqueued), and a call right after it
    brings the first token, before the next step."""
    eng = _engine(max_batch=2, max_new_cap=64)
    got = []
    try:
        eng.generate(PROMPT, 2)
        old = None
        if beside == "a_slowed_stream":
            real = eng._step_fn

            def slow(*a):
                time.sleep(0.4)
                return real(*a)

            eng._step_fn = slow
            old = eng.llm_submit(PROMPT, 40, poller="p")
            while old not in eng.llm_poll(poller="p", timeout_s=30.0):
                pass                       # its first token: decoding now
            # the next call would be woken for the old stream's tokens
            # too: wait one out, so what follows starts after a wake-up
            assert eng.llm_poll(poller="p", timeout_s=30.0)[old]["chunks"]

        def call():
            t0 = time.monotonic()
            while True:
                resp = eng.llm_poll(poller="p", timeout_s=20.0)
                got.append((resp, time.monotonic() - t0))
                if len(got) == 6 or any(r != old for r in resp
                                        if r != "held_ns"):
                    return

        t = threading.Thread(target=call)
        t.start()
        deadline = time.monotonic() + 30
        while not (eng._pollers.get("p") and eng._pollers["p"].waiting) \
                and time.monotonic() < deadline:
            time.sleep(0.001)
        assert eng._pollers["p"].waiting == 1
        new = eng.llm_submit([3, 1, 4], 8, poller="p")
        t.join(timeout=30)
        assert not t.is_alive()
        assert len(got) == 1 or (old and len(got) <= 3), got
        resp, took = got[-1]
        assert took < 5.0                  # nowhere near the 20 s
        assert len(resp[new]["chunks"]) == 1 and not resp[new]["done"]
        rest, last, _ = _poll_to_the_end(
            eng, "p", [r for r in (old, new) if r])
        assert len(rest[new]) == 7 and not last[new]["error"]
    finally:
        eng.shutdown_engine()


def test_a_poller_is_told_once_a_flush_however_many_streams(monkeypatch):
    """Two streams of one poller decoding side by side, nobody polling:
    the prefill's first tokens and the two ends tell the poller a stream
    each, a decode step's put-off wake-ups tell it ONCE for both, and
    ``wakes_deferred`` still counts a stream a wake-up."""
    log = []
    real = llm_engine._Poller.__init__

    def init(p, pid):
        real(p, pid)
        p.event = _LoggedEvent(log, pid)

    monkeypatch.setattr(llm_engine._Poller, "__init__", init)
    eng = _engine(max_batch=2, prefill_rows=2)
    try:
        eng.generate(PROMPT, 2)
        before = eng.llm_stats()
        rids = [eng.llm_submit([i + 1, 2, 3], 6, poller="p")
                for i in (0, 1)]
        deadline = time.monotonic() + 60
        while eng.llm_stats()["completed"] - before["completed"] < 2 \
                and time.monotonic() < deadline:
            time.sleep(0.005)
        st = eng.llm_stats()
        resp = eng.llm_poll(poller="p", timeout_s=5.0)
    finally:
        eng.shutdown_engine()
    # 2 first tokens + 4 flushes (tokens 2 to 5 of both) + 2 ends
    assert log == [("set", "p")] * 8, log
    assert st["wakes_deferred"] - before["wakes_deferred"] == 8
    assert st["wakes_after_dispatch"] - before["wakes_after_dispatch"] == 8
    assert [len(resp[rid]["chunks"]) for rid in rids] == [6, 6]
    assert all(resp[rid]["done"] for rid in rids)


def test_a_flush_says_nothing_of_tokens_an_earlier_call_took():
    """A call woken for one stream's end takes its neighbour's put-off
    token with it: the flush that follows finds nothing pending and does
    not wake the poller for nothing (``next_empty`` stays 0)."""
    eng = _engine(max_batch=2, prefill_rows=2, max_new_cap=64)
    before_flush = _Gate()
    try:
        eng.generate(PROMPT, 2)
        _stop_before_flush(eng, before_flush)
        before = eng.llm_stats()
        short = eng.llm_submit([1, 2, 3], 2, poller="p")
        long_ = eng.llm_submit([4, 5, 6], 4, poller="p")
        first = {}
        while not {short, long_} <= set(first):
            first.update(eng.llm_poll(poller="p", timeout_s=30.0))
        before_flush.reached()             # step 1: short ended, long_ owed
        second = eng.llm_poll(poller="p", timeout_s=30.0)
        assert second[short]["done"] and second[long_]["chunks"]
        before_flush.let()                 # the flush: step 1's token is gone
        before_flush.reached()             # step 2 fanned out, its flush not
        # step 1's flush found its token taken and set nothing; step 2's
        # token is pending and not yet announced
        assert not eng._pollers["p"].event.is_set()
        st = eng.llm_stats()
        before_flush.open()
        rest, last, _ = _poll_to_the_end(eng, "p", [long_])
    finally:
        before_flush.open()
        eng.shutdown_engine()
    assert len(rest[long_]) == 2 and not last[long_]["error"]
    assert st["next_empty"] == before["next_empty"]
    # it was counted all the same: the token's wake-up was owed and put off
    assert st["wakes_deferred"] - before["wakes_deferred"] >= 1


def test_the_engine_forgets_a_poller_with_its_last_stream(monkeypatch):
    eng = _engine(max_batch=2)
    try:
        eng.generate(PROMPT, 2)
        # a call with no stream: known while it waits, gone when it ends
        assert set(eng.llm_poll(poller="p", timeout_s=0.01)) == {"held_ns"}
        assert not eng._pollers
        rid = eng.llm_submit(PROMPT, 3, poller="p")
        assert list(eng._pollers) == ["p"]
        assert eng.open_streams() == 1
        _poll_to_the_end(eng, "p", [rid])
        assert not eng._pollers and not eng._streams
        assert eng.open_streams() == 0
        # a vanished client's ended stream is reaped, its poller with it
        rid = eng.llm_submit(PROMPT, 2, poller="gone")
        deadline = time.monotonic() + 30
        while not eng._streams[rid].done and time.monotonic() < deadline:
            time.sleep(0.005)
        assert eng.open_streams() == 0     # ended: no longer a request
        monkeypatch.setattr(llm_engine, "_STREAM_TTL_S", 0.0)
        eng._reap_streams()
        assert not eng._pollers and not eng._streams
        # the one-stream lane on an unknown poller's stream id
        assert eng.llm_next(rid)["error"].startswith("unknown stream")
    finally:
        eng.shutdown_engine()


# -- streaming transports ---------------------------------------------------


def _deploy_engine(**kw):
    kw.setdefault("model", "gpt2")
    kw.setdefault("config", GPT2_FP32)
    kw.setdefault("max_batch", 4)
    kw.setdefault("cache_len", 32)
    kw.setdefault("max_prompt_len", 8)
    kw.setdefault("max_new_tokens", 6)
    # No explicit deployment= label: the engine must ADOPT the serve
    # deployment's name via Replica's set_deployment_name hook.
    eng = serve.deployment(name="llm", max_concurrent_queries=32,
                           route_prefix="/llm")(LLMEngine)
    return serve.run(eng.bind(**kw))


def test_streaming_handle_and_http_local():
    """Order + completeness through the real transports: handle.stream
    chunks and chunked-HTTP ndjson both concatenate to exactly the
    blocking lane's tokens, and serve.stats() grows a decode section."""
    handle = _deploy_engine()
    want = ray_tpu.get(
        handle.remote({"tokens": PROMPT, "max_tokens": 5}), timeout=120)
    assert len(want["tokens"]) == 5

    chunks = list(handle.stream(PROMPT, 5))
    assert [t for ch in chunks for t in ch] == want["tokens"]
    assert all(len(ch) >= 1 for ch in chunks)  # per-step chunking

    port = serve.start_http_proxy()
    import http.client

    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
    try:
        body = json.dumps({"tokens": PROMPT, "max_tokens": 5}).encode()
        conn.request("POST", "/llm", body=body,
                     headers={"Content-Type": "application/json",
                              serve.STREAM_HEADER: "1"})
        resp = conn.getresponse()
        assert resp.status == 200
        assert resp.getheader("Transfer-Encoding") == "chunked"
        lines = []
        while True:
            line = resp.readline()
            if not line:
                break
            lines.append(json.loads(line))
        toks = [t for ln in lines if "tokens" in ln
                for t in ln["tokens"]]
        assert toks == want["tokens"]
        assert lines[-1].get("done") is True
        # Keep-alive survives a chunked response: a plain request on
        # the same connection still answers.
        conn.request("POST", "/llm", body=body,
                     headers={"Content-Type": "application/json"})
        r2 = conn.getresponse()
        assert r2.status == 200
        assert json.loads(r2.read())["tokens"] == want["tokens"]
    finally:
        conn.close()

    stats = serve.stats()
    decode = stats["deployments"]["llm"].get("decode")
    assert decode and decode["streams"] >= 2
    assert decode.get("tokens", 0) >= 15


def test_stream_deadline_shed_typed_through_handle():
    handle = _deploy_engine()
    with pytest.raises(RequestShedError):
        list(handle.options(deadline_s=0.0).stream(PROMPT, 4))


def test_blocking_lane_deadline_shed_mid_decode():
    """The BLOCKING lane (handle.remote -> __call__) inherits the serve
    request context's deadline: a budget dying mid-decode sheds typed
    and frees the slot, same as the streaming lane."""
    handle = _deploy_engine(max_new_tokens=500, max_new_cap=1000,
                            step_throttle_s=0.02)
    t0 = time.monotonic()
    with pytest.raises(Exception) as ei:
        ray_tpu.get(handle.options(deadline_s=0.6).remote(
            {"tokens": PROMPT, "max_tokens": 500}), timeout=120)
    assert "shed" in repr(ei.value).lower(), repr(ei.value)
    assert time.monotonic() - t0 < 60.0  # shed, not a 500-token wait


# -- cluster backend + ray:// proxy (runs LAST: tears down the module
# runtime) ------------------------------------------------------------------


def test_cluster_stream_and_ray_client_proxy():
    """Streaming order/completeness on the CLUSTER backend (replica in a
    worker process, events federate over the worker plane), then the
    same stream forwarded chunk-by-chunk through the ``ray://`` client
    proxy — including the zero-copy shm handoff lane for big prompts."""
    from ray_tpu.cluster import Cluster
    from ray_tpu.util.client import ClientProxyServer

    ray_tpu.shutdown()
    cluster = Cluster()
    cluster.add_node(num_cpus=4)
    cluster.wait_for_nodes()
    ray_tpu.init(cluster.address)
    proxy = None
    try:
        handle = _deploy_engine()
        want = ray_tpu.get(
            handle.remote({"tokens": PROMPT, "max_tokens": 5}),
            timeout=300)
        chunks = list(handle.stream(PROMPT, 5))
        assert [t for ch in chunks for t in ch] == want["tokens"]

        # TTFT federates from the replica worker to the cluster scrape.
        deadline = time.monotonic() + 30.0
        decode = {}
        while time.monotonic() < deadline:
            parsed = obs.parse_prometheus(obs.metrics_text())
            decode = obs.decode_stats(parsed, "llm")
            if decode.get("streams", 0) >= 2:
                break
            time.sleep(0.5)
        assert decode.get("streams", 0) >= 2, decode

        proxy = ClientProxyServer(cluster.address)
        ray_tpu.shutdown()
        ray_tpu.init(address=f"ray://{proxy.address}")
        h2 = serve.get_deployment_handle("llm")
        toks2 = [t for ch in h2.stream(PROMPT, 5) for t in ch]
        assert toks2 == want["tokens"]
        # Big prompt rides the shm store proxy->replica (the handoff
        # threshold), and the stream still completes in order.
        big = PROMPT + [1] * 600
        toks3 = [t for ch in h2.stream(big, 4) for t in ch]
        assert len(toks3) == 4
        # Typed shed crosses the RPC stream boundary.
        with pytest.raises(RequestShedError):
            list(h2.options(deadline_s=0.0).stream(PROMPT, 4))
    finally:
        try:
            ray_tpu.shutdown()
            ray_tpu.init(cluster.address)
            serve.shutdown()
        except Exception:
            pass
        ray_tpu.shutdown()
        if proxy is not None:
            proxy.shutdown()
        cluster.shutdown()
