"""DeepSeek-V2 (``models/deepseek_v2.py``) against its plain reference
(``benchmark/reference/deepseek_v2.py``) at toy widths on the CPU: prefill in
toy chunks then decode steps through the latent cache (several blocks of keys,
a clamped last block, a wrapped ring), the absorbed attention against the
decompressed one on the same cache, the group-limited router on near-ties,
YaRN's angles and scale, the eight groups' shares adding up to the uncut
layer, a float8 and a bfloat16-statistics control, and every other family's
lowered programs held to what they were before this family came. The contracts
every served family holds (sizes, types, the forward pass, the engine against
the reference) are ``tests/test_served_family_contract.py``'s.
"""

import dataclasses
import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import deepseek_v2 as ds
from ray_tpu.models.prefill import whole_prompts
from ray_tpu.ops import attention as attn_ops
from ray_tpu.ops import moe as moe_ops
from served_families import (FAMILIES, contract_params, contract_tokens,
                             contract_want,
                             deepseek_v2_rope_scaling as rope_scaling, rel_l2)

ROW = FAMILIES["deepseek_v2"]
reference, family, CFG = ROW.reference, ROW.family, ROW.cfg
to_ref, ref_kwargs = ROW.to_reference, ROW.reference_kwargs
F32 = jnp.float32


@pytest.fixture(scope="module")
def params():
    return contract_params("deepseek_v2")


@pytest.fixture(scope="module")
def tokens():
    return contract_tokens("deepseek_v2")


@pytest.fixture(scope="module")
def want():
    return contract_want("deepseek_v2")


def moved(params, seed=6):
    """``served_families.moved`` with the draw made in float32 and cast:
    this file moves bfloat16 weights too (float32 ones get the same)."""
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 256))
    return jax.tree.map(
        lambda x: x + (0.05 * jax.random.normal(
            next(keys), x.shape, jnp.float32)).astype(x.dtype), params)


def test_weights_are_stored_in_bfloat16_and_the_two_kinds_of_layer():
    cfg = ds.DeepseekV2Config.tiny()
    params = ds.deepseek_v2_init(jax.random.PRNGKey(0), cfg)
    assert all(x.dtype == jnp.bfloat16 for x in jax.tree.leaves(params))
    assert set(params) == {"embed", "layers", "norm_f", "head"}  # untied
    dense, expert = params["layers"][0], params["layers"][1]
    assert set(dense) ^ set(expert) == {
        "w_in", "w_down", "router", "w1", "w2", "shared_w1", "shared_w2"}
    assert dense["w_in"].shape == (64, 2 * 96)
    assert expert["w1"].shape == (8, 64, 2 * 24)
    assert expert["router"].shape == (64, 16)
    assert expert["w_uk"].shape == expert["w_uv"].shape == (16, 4, 8)
    cache = ds.deepseek_v2_init_cache(cfg, 3, 16)
    # one row of kv_rank + rope_dim numbers a token a layer, all heads'
    assert cache["latent"].shape == (3, 3, 16, 1, 16 + 8)
    assert cache["latent"].dtype == jnp.bfloat16
    assert cfg.serving_dtypes(params) == jax.tree.map(
        lambda x: x.dtype, params)


@pytest.mark.parametrize("term, without", [
    ("softmax_scale", {"rope_scaling": {**rope_scaling(CFG),
                                        "mscale_all_dim": 0.0}}),
    ("yarn", {"rope_scaling": {**rope_scaling(CFG), "factor": 1.0}}),
    ("rope", "rotate"),
    ("routed_scale", {"routed_scale": 1.0}),
    ("topk_group", {"topk_group": 4}),
    ("top_k", {"top_k": 2}),
    ("first_expert", {"first_expert": 8}),
    ("gate", None),
])
def test_the_reference_without_a_term_is_another_model(
        params, tokens, want, monkeypatch, term, without):
    """The controls: each published constant left at what a model without
    it would use (no YaRN magnitude in the scale, no stretching, no
    rotation at all, unscaled or ungrouped routing, other experts),
    and the gate left out, moves the reference by far more than the
    comparisons' 1e-4: a tolerance cannot hide a missing term."""
    if without is None:
        monkeypatch.setattr(reference, "gated", lambda ab: jax.nn.silu(
            ab[..., :ab.shape[-1] // 2]))
        without = {}
    elif without == "rotate":
        monkeypatch.setattr(reference, "rotate", lambda x, cos, sin: x)
        without = {}
    other = ROW.reference_forward(params, CFG, **without)(tokens)
    assert rel_l2(other, want) > 5e-3, term
    got = ds.deepseek_v2_forward(params, tokens, CFG)
    assert rel_l2(got, other) > 5e-3, term


# -- YaRN -----------------------------------------------------------------------


@pytest.mark.parametrize("cfg", [ds.DeepseekV2Config(), CFG],
                         ids=["published", "tiny"])
def test_yarn_angles_and_scale_are_the_references(cfg):
    """The program's frequencies, cos / sin and softmax scale against the
    reference's (the released ``yarn_find_correction_range`` and ramp), at
    the published constants: lanes 0-10 keep theta's frequency, lanes
    23-31 are stretched 40 times, the ramp between."""
    kw = dict(rope=cfg.rope_dim, rope_theta=cfg.rope_theta,
              rope_scaling=rope_scaling(cfg))
    pos = jnp.asarray([0, 1, 17, 4095, 4096, 16383, 16895])
    cos, sin = reference.yarn_angles(pos, **kw)
    x = jax.random.normal(jax.random.PRNGKey(0), (7, cfg.rope_dim))
    np.testing.assert_allclose(
        np.asarray(ds._rotate(x, pos, cfg)),
        np.asarray(reference.rotate(x, cos, sin)), rtol=1e-5, atol=1e-5)
    assert cfg.softmax_scale == pytest.approx(reference.softmax_scale(
        nope=cfg.nope_dim, rope=cfg.rope_dim,
        rope_scaling=rope_scaling(cfg)), rel=1e-12)
    # mscale == mscale_all_dim: cos and sin keep their magnitude
    assert float(jnp.max(cos ** 2 + sin ** 2)) == pytest.approx(1.0, 1e-5)
    if cfg.rope_dim == 64:
        freq = ds.yarn_inv_freq(cfg)
        plain = 10000.0 ** (-np.arange(32) / 32)
        np.testing.assert_allclose(freq[:11], plain[:11], rtol=1e-6)
        np.testing.assert_allclose(freq[23:], plain[23:] / 40, rtol=1e-6)
        assert np.all(freq[11:23] < plain[11:23])
        assert np.all(freq[11:23] > plain[11:23] / 40)


# -- the router -----------------------------------------------------------------


def test_the_group_limited_router_on_near_ties():
    """``route_group_limited`` against the reference's ``gating`` on scores
    built to be hard: groups whose best experts differ in the last bits,
    exact ties between groups (the lower id wins in both) and a strong
    expert in a group that is not kept (it must NOT be chosen)."""
    rng = np.random.default_rng(0)
    e, g = 16, 8
    logits = rng.normal(size=(64, e)).astype(np.float32)
    logits[:16, 3] = logits[:16, 9] + 1e-6          # near-tie across groups
    logits[16:32, 4] = logits[16:32, 12]            # exact tie across groups
    logits[32:, 1] = 3.0                             # group 0 leads ...
    logits[32:, 0] = 2.9                             # ... with two strong
    x = jnp.asarray(logits)          # rows ARE the logits; the gate is I
    w = jnp.eye(e, dtype=F32)
    for top_k, topk_group in ((4, 3), (6, 3), (2, 1)):
        ids, weights = moe_ops.route_group_limited(
            x, w, top_k, g, topk_group, 16.0)
        want = reference.gating(x, w, top_k=top_k, n_group=g,
                                topk_group=topk_group, routed_scale=16.0)
        got = jnp.zeros_like(want).at[
            jnp.arange(64)[:, None], ids].set(weights)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6, atol=0)
        assert ids.dtype == jnp.int32 and weights.dtype == jnp.float32
        # at most topk_group groups a token, top_k experts, not normalised
        groups = np.asarray(ids) // (e // g)
        assert max(len(set(r)) for r in groups) <= topk_group
        assert np.all(np.asarray(weights).sum(-1) < 16.0)
    p = jax.nn.softmax(x, axis=-1)
    ids, weights = moe_ops.route_group_limited(x, w, 2, g, 1, 1.0)
    # one group kept: both choices are the best group's two experts,
    # their weights the softmax scores unchanged
    assert np.all(np.asarray(ids[32:]) == [1, 0])
    np.testing.assert_allclose(np.asarray(weights[32:]),
                               np.asarray(p[32:, [1, 0]]), rtol=1e-6)


# -- the latent cache and its two attentions ------------------------------------


def _latent_case(seed=0, s=3, rows=50, h=4, rank=16, rope=8, nope=8, v=8,
                 ring=64):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    cache = jnp.zeros((2, s, ring, 1, rank + rope)).at[1, :, :rows, 0].set(
        jax.random.normal(ks[0], (s, rows, rank + rope)))
    return (cache, jax.random.normal(ks[1], (s, h, nope)),
            jax.random.normal(ks[2], (s, h, rope)),
            jax.random.normal(ks[3], (rank, h, nope)) * 0.3,
            jax.random.normal(ks[4], (rank, h, v)) * 0.3,
            jax.random.normal(ks[5], (s, rank + rope)))


def _plain_latent_attention(cache_rows, q_nope, q_pe, w_uk, w_uv, scale):
    """One query a slot over rows [S, N, W], every key and value
    decompressed: the equations, nothing else. -> [S, H, v]."""
    rank = w_uk.shape[0]
    k = jnp.einsum("snr,rhd->snhd", cache_rows[..., :rank], w_uk)
    v = jnp.einsum("snr,rhd->snhd", cache_rows[..., :rank], w_uv)
    scores = (jnp.einsum("shd,snhd->shn", q_nope, k)
              + jnp.einsum("shp,snp->shn", q_pe, cache_rows[..., rank:])) \
        * scale
    return jnp.einsum("shn,snhd->shd", jax.nn.softmax(scores, -1), v)


@pytest.mark.parametrize("block", [64, 16, 24])
def test_the_absorbed_path_is_the_decompressed_one_on_the_same_cache(block):
    """``latent_decode_attention`` (absorbed, the new row beside the ring)
    against ``latent_chunk_attention`` (decompressed, the row written
    first) and against the plain equations, in one block, in four and in
    three of which the last is moved back inside the ring."""
    cache, q_nope, q_pe, w_uk, w_uv, row_new = _latent_case()
    s, rows, scale = 3, 50, 0.3
    pos = jnp.full(s, rows)
    q_lat = jnp.einsum("shd,rhd->shr", q_nope, w_uk)
    o_lat = attn_ops.latent_decode_attention(
        q_lat, q_pe, cache, 1, row_new, pos, pos + 1, scale, F32,
        block=block)
    absorbed = jnp.einsum("shr,rhd->shd", o_lat, w_uv)
    written = attn_ops.cache_write_token(
        cache, jnp.stack([row_new, row_new])[:, :, None], pos)
    decompressed = attn_ops.latent_chunk_attention(
        q_nope[:, None], q_pe[:, None], written, 1, jnp.arange(s), pos,
        w_uk, w_uv, scale, block=block)[:, 0]
    plain = _plain_latent_attention(
        written[1, :, :rows + 1, 0], q_nope, q_pe, w_uk, w_uv, scale)
    np.testing.assert_allclose(np.asarray(absorbed), np.asarray(plain),
                               rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(np.asarray(decompressed), np.asarray(plain),
                               rtol=2e-5, atol=2e-6)
    # layer 0 of the stack holds zeros: the layer index is honoured
    other = attn_ops.latent_decode_attention(
        q_lat, q_pe, cache, 0, row_new, pos, pos + 1, scale, F32,
        block=block)
    assert float(jnp.abs(other - o_lat).max()) > 1e-2


def test_slots_of_different_lengths_and_a_wrapped_ring():
    """Every slot masks its own rows (contexts 5, 50 and a ring of 64 that
    has wrapped: all rows live but the cursor's, which the new row
    replaces), whatever block the longest context makes the loop read."""
    cache, q_nope, q_pe, w_uk, w_uv, row_new = _latent_case(rows=64)
    pos = jnp.asarray([5, 50, 64 + 9])
    cursor, valid = jnp.mod(pos, 64), jnp.minimum(pos + 1, 64)
    q_lat = jnp.einsum("shd,rhd->shr", q_nope, w_uk)
    got = jnp.einsum("shr,rhd->shd", attn_ops.latent_decode_attention(
        q_lat, q_pe, cache, 1, row_new, cursor, valid, 0.3, F32, block=16),
        w_uv)
    written = attn_ops.cache_write_token(
        cache, jnp.stack([row_new, row_new])[:, :, None], cursor)[1, :, :, 0]
    for i, n in enumerate([6, 51, 64]):
        plain = _plain_latent_attention(
            written[i:i + 1, :n], q_nope[i:i + 1], q_pe[i:i + 1], w_uk, w_uv,
            0.3)
        np.testing.assert_allclose(np.asarray(got[i]), np.asarray(plain[0]),
                                   rtol=2e-5, atol=2e-6)


def test_a_chunks_attention_follows_the_keys_it_can_see():
    """The chunk's loop stops at the block that holds ``start + C``: rows
    past it may hold anything (here NaN) and are never read, in a ring far
    longer than the prompt so far; and the jaxpr holds no scores array over
    the whole ring."""
    cache, _, _, w_uk, w_uv, _ = _latent_case(rows=40, ring=256)
    cache = cache.at[:, :, 48:].set(jnp.nan)
    q_nope = jax.random.normal(jax.random.PRNGKey(7), (1, 8, 4, 8))
    q_pe = jax.random.normal(jax.random.PRNGKey(8), (1, 8, 4, 8))
    fn = lambda c, at: attn_ops.latent_chunk_attention(
        q_nope, q_pe, c, 1, jnp.asarray([2]), at, w_uk, w_uv, 0.3, block=16)
    got = fn(cache, jnp.asarray([32]))
    assert bool(jnp.isfinite(got).all())
    for i in range(8):
        plain = _plain_latent_attention(
            cache[1, 2:3, :32 + i + 1, 0], q_nope[:, i], q_pe[:, i], w_uk,
            w_uv, 0.3)
        np.testing.assert_allclose(np.asarray(got[0, i]),
                                   np.asarray(plain[0]), rtol=2e-5,
                                   atol=2e-6)
    text = str(jax.make_jaxpr(fn)(cache, jnp.asarray([32])))
    assert "while" in text
    assert not re.search(r"f32\[[0-9,]*\b256\]", text)  # no [.., ring] array


CHUNK = 4


@pytest.mark.parametrize("chunks, length", [(1, 4), (2, 7), (9, 35),
                                            (33, 130)])
def test_prefill_in_toy_chunks_then_decode_through_the_cache(chunks,
                                                             length):
    """A prompt of 1, 2, 9 and 33 chunks (the second and third end inside a
    chunk) through the chunk program, then five decode steps through the
    latent cache, against the reference's full forward: logits at the
    prompt's last token and after every step."""
    assert -(-length // CHUNK) == chunks
    params = moved(ds.deepseek_v2_init(jax.random.PRNGKey(2), CFG))
    steps = 5
    row = jnp.asarray(np.random.default_rng(length).integers(
        0, CFG.vocab_size, (1, length + steps), dtype=np.int32))
    want = ROW.reference_forward(params, CFG)(row)
    cache = ds.deepseek_v2_init_cache(CFG, 2, 144)
    chunk = jax.jit(lambda c, t, at, n: ds.deepseek_v2_prefill_chunk(
        params, c, t, jnp.ones(1, jnp.int32), at, n, CFG, window=136))
    for at in range(0, length, CHUNK):
        piece = np.zeros((1, CHUNK), np.int32)
        n = min(CHUNK, length - at)
        piece[0, :n] = np.asarray(row)[0, at:at + n]
        logits, cache = chunk(
            cache, jnp.asarray(piece), jnp.full(1, at, jnp.int32),
            jnp.full(1, n, jnp.int32))
    out = [logits[0]]
    step = jax.jit(lambda c, t, n: ds.deepseek_v2_decode_step(
        params, c, t, n, CFG))
    for i in range(steps):
        toks = jnp.zeros(2, jnp.int32).at[1].set(row[0, length + i])
        pos = jnp.zeros(2, jnp.int32).at[1].set(length + i)
        logits, cache, counted = step(cache, toks, pos)
        out.append(logits[1])
    assert rel_l2(jnp.stack(out), want[0, length - 1:]) < 2e-4
    # the chunks' pairs, in the cache the steps handed on; padding is
    # routed nowhere: at most top_k pairs a real token an expert layer
    pairs = int(cache["counted"]["prefill_expert_rows"])
    assert 0 < pairs <= length * CFG.top_k * 2
    # a step's two rows through two expert layers: each row with a pair
    # here is counted once a layer, and takes at most top_k pairs
    here = int(counted["expert_tokens_here"])
    assert here <= 4 and here <= int(counted["expert_rows"]) \
        <= here * CFG.top_k
    assert int(counted["experts_hit"]) <= int(counted["expert_rows"])


@pytest.mark.parametrize("chunk, ring", [(256, 1300), (512, 1600)])
def test_long_rows_cross_the_ops_own_blocks(chunk, ring):
    """At the blocks the ops really use (256 keys a chunk block, 1024 a
    decode block): a prompt of 1100 tokens in chunks of 256 in a ring of
    1300 rows (up to six chunk blocks and two decode blocks, the last of
    each moved back inside the ring) against the reference, in float32;
    and in the expert families' chunks of 512, whose queries meet a
    decompressed block in two groups of ``CHUNK_QUERIES``."""
    cfg = ds.DeepseekV2Config.tiny(dtype=F32, param_dtype=F32, n_layer=2,
                                   vocab_size=64)
    params = moved(ds.deepseek_v2_init(jax.random.PRNGKey(4), cfg))
    length, window = 1100, -(-1100 // chunk) * chunk
    assert ring > attn_ops.LATENT_DECODE_BLOCK > attn_ops.LATENT_CHUNK_BLOCK
    assert chunk // attn_ops.CHUNK_QUERIES == chunk // 256
    row = jnp.asarray(np.random.default_rng(9).integers(
        0, cfg.vocab_size, (1, length + 2), dtype=np.int32))
    want = ROW.reference_forward(params, cfg)(row)
    cache = ds.deepseek_v2_init_cache(cfg, 2, ring)
    prompt = jnp.pad(row[:, :length], ((0, 0), (0, window - length)))
    logits, cache = jax.jit(lambda c: whole_prompts(
        ds.deepseek_v2_prefill_chunk, params, c, prompt, jnp.ones(
            1, jnp.int32), jnp.asarray([length]), cfg, chunk=chunk))(cache)
    out = [logits[0]]
    for i in range(2):
        toks = jnp.zeros(2, jnp.int32).at[1].set(row[0, length + i])
        pos = jnp.zeros(2, jnp.int32).at[1].set(length + i)
        logits, cache, _ = ds.deepseek_v2_decode_step(
            params, cache, toks, pos, cfg)
        out.append(logits[1])
    assert rel_l2(jnp.stack(out), want[0, length - 1:]) < 2e-4


def test_the_whole_window_form_serves_rows_of_different_lengths(params,
                                                                tokens,
                                                                want):
    """``deepseek_v2_prefill``'s loop (what the benchmark's reference check
    calls; here in four chunks of 8): three rows of different lengths in
    one window, then decode."""
    lens = jnp.asarray([17, 32, 5], jnp.int32)
    prompts = np.zeros((3, 32), np.int32)
    for i, n in enumerate(np.asarray(lens)):
        prompts[i, :n] = np.asarray(tokens)[i, :n]
    cache = ds.deepseek_v2_init_cache(CFG, 4, 64)
    logits, cache = whole_prompts(
        ds.deepseek_v2_prefill_chunk, params, cache, jnp.asarray(prompts),
        jnp.arange(3), lens, CFG, chunk=8)
    rows = jnp.arange(3)
    out = [logits]
    for s in range(4):
        pos = jnp.zeros(4, jnp.int32).at[:3].set(lens + s)
        toks = jnp.zeros(4, jnp.int32).at[:3].set(tokens[rows, lens + s])
        logits, cache, _ = ds.deepseek_v2_decode_step(
            params, cache, toks, pos, CFG)
        out.append(logits[:3])
    got = jnp.stack(out, axis=1)
    ref = jnp.stack([want[rows, lens - 1 + s] for s in range(5)], axis=1)
    assert rel_l2(got, ref) < 2e-4


# -- the share and the model ----------------------------------------------------


def test_the_eight_groups_shares_add_up_to_the_uncut_layer():
    """The guide's test that ties the share to the model: one expert layer
    with 16 experts in 8 groups of 2, a group a chip. Each chip's hidden
    state is ``x + attention + shared + its group's routed part``; the
    eight routed parts, with what every chip computes alike (the residual,
    attention, the shared experts) counted once, add up to what the
    reference gives with every expert held."""
    cfg = ds.DeepseekV2Config.tiny(
        dtype=F32, param_dtype=F32, n_layer=1, first_dense=0, n_group=8,
        topk_group=3, top_k=4, experts_held=(0, 16))
    params = moved(ds.deepseek_v2_init(jax.random.PRNGKey(5), cfg))
    tokens = jnp.asarray(np.random.default_rng(3).integers(
        0, cfg.vocab_size, (2, 24), dtype=np.int32))
    lengths = jnp.full(2, 24, jnp.int32)

    def hidden(first, count, routed=1.0):
        layer = params["layers"][0]
        share = {**params, "layers": [{
            **layer, "w1": layer["w1"][first:first + count],
            "w2": layer["w2"][first:first + count] * routed}]}
        return ds._rows(share, tokens, lengths, dataclasses.replace(
            cfg, experts_held=(first, count)))[0]

    alike = hidden(0, 2, routed=0.0)  # residual + attention + shared
    parts = [hidden(2 * g, 2) - alike for g in range(8)]
    assert all(float(jnp.abs(p).max()) > 0 for p in parts)  # every group
    total = alike + sum(parts)
    uncut = ROW.reference_forward(params, cfg)(tokens)
    assert rel_l2(ds._head(total, params, cfg), uncut) < 1e-4
    # and the uncut program is that sum too
    assert rel_l2(hidden(0, 16), total) < 1e-5
    # one share alone is another model: the tolerance would see a lost group
    assert rel_l2(ds._head(alike + parts[0], params, cfg), uncut) > 1e-2


def _fp8(params):
    """Every matrix rounded to float8 (e4m3, a scale an output channel)."""
    def one(x):
        if x.ndim < 2:
            return x
        scale = jnp.max(jnp.abs(x.astype(F32)), axis=-2,
                        keepdims=True) / 240.0 + 1e-30
        low = jax.lax.reduce_precision(x.astype(F32) / scale,
                                       exponent_bits=4, mantissa_bits=3)
        return (low * scale).astype(x.dtype)
    return jax.tree.map(one, params)


def test_bfloat16_against_the_reference_and_the_float8_control():
    """As the benchmark compares: the programs in bfloat16 (chunks, then
    steps through the cache) against the float32 reference on the same
    bfloat16 weights. Tolerance 4e-2: bfloat16 activations through three
    layers of two branches read about 1e-2 here (a routing choice that
    rounding turns reads higher, which is what the room is for). The
    control, every matrix in float8 (the nearest matmul precision below
    the one the file states), must fail it twice over."""
    cfg = ds.DeepseekV2Config.tiny()
    params = moved(ds.deepseek_v2_init(jax.random.PRNGKey(8), cfg))
    length, steps = 37, 4
    row = jnp.asarray(np.random.default_rng(2).integers(
        0, cfg.vocab_size, (1, length + steps), dtype=np.int32))
    want = ROW.reference_forward(params, cfg)(row)[
        0, length - 1:]

    @jax.jit
    def served(p):
        cache = ds.deepseek_v2_init_cache(cfg, 2, 64)
        prompt = jnp.pad(row[:, :length], ((0, 0), (0, 40 - length)))
        logits, cache = whole_prompts(
            ds.deepseek_v2_prefill_chunk, p, cache, prompt,
            jnp.ones(1, jnp.int32), jnp.asarray([length]), cfg, chunk=8)
        out = [logits[0]]
        for i in range(steps):
            toks = jnp.zeros(2, jnp.int32).at[1].set(row[0, length + i])
            pos = jnp.zeros(2, jnp.int32).at[1].set(length + i)
            logits, cache, _ = ds.deepseek_v2_decode_step(
                p, cache, toks, pos, cfg)
            out.append(logits[1])
        return jnp.stack(out)

    tolerance = 4e-2
    sound = rel_l2(served(params), want)
    assert 1e-3 < sound < tolerance, sound
    assert rel_l2(served(_fp8(params)), want) > 2 * tolerance


def _bf16_statistics(carry, scores, seen, product):
    """``ops/attention._online_softmax`` with its running maximum, sum and
    weighted values kept in bfloat16: the precision below the float32 the
    configuration states for them."""
    b16 = jnp.bfloat16
    m, l, acc = (c.astype(b16) for c in carry)
    scores = jnp.where(seen, scores, -1e30).astype(b16)
    m_new = jnp.maximum(m, jnp.max(scores, axis=-1))
    alpha = jnp.exp(m - m_new)
    p = jnp.where(seen, jnp.exp(scores - m_new[..., None]), 0)
    return (m_new.astype(F32), (l * alpha + jnp.sum(p, -1)).astype(F32),
            (acc * alpha[..., None] + product(p).astype(b16)).astype(F32))


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_bfloat16_statistics_fail_the_attentions_own_tolerance(seed,
                                                               monkeypatch):
    """The absorbed attention on a bfloat16 ring of 2000 live rows, read
    in eight blocks, against the plain softmax in float32 on the same
    values. Tolerance 2e-3: bfloat16 operands and probabilities with
    float32 scores, statistics and sums read 3.7e-4 to 7.0e-4 over these
    seeds; with the statistics in bfloat16 the same attention reads 5.0e-3
    to 1.1e-2 and fails on every seed."""
    b16 = jnp.bfloat16
    cache, q_nope, q_pe, w_uk, _, row_new = (
        x.astype(b16) for x in _latent_case(seed=seed, s=2, rows=2000,
                                            ring=2048))
    pos = jnp.full(2, 2000)
    q_lat = jnp.einsum("shd,rhd->shr", q_nope, w_uk)
    attend = lambda: attn_ops.latent_decode_attention(
        q_lat, q_pe, cache, 1, row_new, pos, pos + 1, 0.3, F32, block=256)
    rows = attn_ops.cache_write_token(
        cache, jnp.stack([row_new, row_new])[:, :, None],
        pos)[1, :, :2001, 0].astype(F32)
    scores = jnp.einsum(
        "shw,snw->shn", jnp.concatenate([q_lat, q_pe], -1).astype(F32),
        rows, precision="highest") * 0.3
    plain = jnp.einsum("shn,snr->shr", jax.nn.softmax(scores, -1),
                       rows[..., :16], precision="highest")
    tolerance = 2e-3
    assert rel_l2(attend(), plain) < tolerance / 2
    monkeypatch.setattr(attn_ops, "_online_softmax", _bf16_statistics)
    assert rel_l2(attend(), plain) > 2 * tolerance



# -- every family's two programs ------------------------------------------------

# sha256 of the lowered text (StableHLO, no locations) of each family's two
# engine programs at its tiny preset: the one table every family's pair is
# pinned in (PR 50 merged ``tests/test_falcon_h1.py``'s four lines into it
# and added Qwen3-Next's two, all values as they stood). A PR that changes
# a program ON PURPOSE replaces its line here and says so; one that did not
# mean to has found out. The four older families' were taken on the parent
# of the PR that added latent attention (PR 38), which only ADDED functions
# to ``ops/attention.py`` and ``ops/moe.py``. PR 42 replaced GPT-2's two ON
# PURPOSE (its cache holds merged rows, written after both layer loops);
# the six others held through it. PR 48 replaced GPT-2's decode program ON
# PURPOSE (its scan runs over the layer's index with the stacked cache
# closed over, which the attention is handed whole with that index, and the
# step returns what it read of the rings); its chunk program and the six
# others held through it, Granite's and Nemotron's among them.
# DeepSeek-V2's two were taken on the parent of the PR that added Falcon-H1
# (PR 43: the mixer's column multipliers and the rotary helper were ADDED).
# Falcon-H1's own two are the programs of PR 44, whose rings hold merged
# rows that the step reads as they lie and the chunk reads before it
# writes: that PR changed them ON PURPOSE (and no other family's). PR 48
# replaced Falcon-H1's decode program ON PURPOSE (the attention is handed
# the stacked cache and the layer's index, and the step returns what it
# read of the rings); its chunk program and DeepSeek-V2's two held through
# it. Qwen3-Next's two were taken on PR 50's parent (PR 49), the programs
# of PR 48. PR 51 cut ``merged_chunk_attention`` into the helpers it now
# shares with ``wrapped_chunk_attention`` and moved no line of any of these
# fourteen; SmallThinker's two were taken on PR 51's own tree. PR 52 moved
# the five expert families' DECODE programs ON PURPOSE (``held_counters``
# returns ``expert_row_tiles`` beside its two counters; at the tiny
# presets' toy widths the experts keep the batched XLA product, so their
# chunk programs, which return no such counter, hold): GPT-2's, Llama's
# and Falcon-H1's six hold untouched, which is the proof that those
# families' cells bypass that PR's change.
LOWERED = {
    ("gpt2", "decode"):
        "2b81ed566a4807464776b4b17fb93f366083f68456236ee2eeb73d0f4b83d1e2",
    ("gpt2", "prefill"):
        "24b60a2b1b87975cd1b66868e898c07b090f46e70752fa53cddc28402c283ecf",
    ("llama", "decode"):
        "abe772a595d67a9932bebf3eeb2f47246da5b42fb7aa82b7884c79b3f419aa42",
    ("llama", "prefill"):
        "0908d512ab3b1f21515a16fb91d278375cac144643fe808beabd8bb3205a3864",
    ("nemotron_h", "decode"):
        "36ece1d026205db44ecc6085bf11ee4829b8003592ae17566cae87c41f5fbc7b",
    ("nemotron_h", "prefill"):
        "5bebe758fc42f853e3942938dddd959f23addd672477f8b5761523e093d7b282",
    ("granite_hybrid", "decode"):
        "fdefc26172085099b2802ef7ff2078f0528e2613c1803ebe3a0f2e8270142e17",
    ("granite_hybrid", "prefill"):
        "75223bed40a6807b543fa8559dfab0c621e3691d3403d64521cd23710aabe7c0",
    ("deepseek_v2", "decode"):
        "c29434c331078ad37cfdd22ab929691ecd37e9be5062b3fb9da4e923e6b1e3ee",
    ("deepseek_v2", "prefill"):
        "52a5ec015c69fe816fa3668bc8bb7a33010803b7015750ae1fa51f9b931e67d0",
    ("falcon_h1", "decode"):
        "12254346a7750fae85518cd98839101153ec8b1c10b65ef6b911c45a458fd552",
    ("falcon_h1", "prefill"):
        "0e302753a3af60ac69badd51fccf8994c05d93eb66f0c8b4624a87981698c5c1",
    ("qwen3_next", "decode"):
        "710ae3869418dc164ec59191b166b6d29f2ca448efc30b532bceec4691bc3a7a",
    ("qwen3_next", "prefill"):
        "7652be0b12c44a4698adcd67e1e99dad4412f86d60108e1d4c4cf6475f8b871f",
    ("smallthinker", "decode"):
        "f51bfa1a6e6b246ee6b4732d5732f6b31b5f7973ea2481160110a2bdff97084e",
    ("smallthinker", "prefill"):
        "4b49b998b731ec92566cd01523ded5c351bb0ee26ee1801526436068bb183c4b",
    # PR 54: the ninth family; its undrafted step, its verify-and-draft
    # step (what its engine runs) and its chunk (the module's pass in it)
    ("exaone_moe", "decode"):
        "3a4b199002ab32e6c5fce00b9aabf1e2a8cf97651785ab6ed7bca63e6eca43af",
    ("exaone_moe", "verify"):
        "db132a14a010f4d5bace24c22505d6fb25cbed8246ddeb6e467f111047bdd3bc",
    ("exaone_moe", "prefill"):
        "845f58142694409faac7e39d5f7448b2822bab95606b6b9fac193c4796b4b92b",
    # PR 60: the tenth family (its step gathers the rows its indexer
    # picks; its chunk's toy widths keep the XLA arm)
    ("keye_vl2", "decode"):
        "4e080fe1d920c5f1483c176d2b57892096201b2aa98fd1b01b2bc94e79d9cfdc",
    ("keye_vl2", "prefill"):
        "00874abaafcb7d620379a1362ee8f8793a36cb85eae52b6db3b7644c44e94a95",
}


@pytest.mark.parametrize("model, program", sorted(LOWERED))
def test_every_familys_programs_are_what_they_were(model, program):
    from ray_tpu.serve.llm_engine import _model_bundle

    cfg, init, init_cache, chunk, step, *verify = _model_bundle(
        model, None, "tiny")
    params = jax.eval_shape(lambda: init(jax.random.PRNGKey(0), cfg))
    cache = jax.eval_shape(lambda: init_cache(cfg, 3, 16))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    if program == "decode":
        text = jax.jit(lambda p, c, t, n: step(p, c, t, n, cfg)).lower(
            params, cache, i32(3), i32(3)).as_text()
    elif program == "verify":  # a self-drafting family's sixth element
        text = jax.jit(lambda p, c, t, n: verify[0](p, c, t, n, cfg)).lower(
            params, cache, i32(3, 2), i32(3)).as_text()
    else:
        text = jax.jit(lambda p, c, t, s, a, n: chunk(
            p, c, t, s, a, n, cfg, window=8)).lower(
                params, cache, i32(1, 4), i32(1), i32(1), i32(1)).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() \
        == LOWERED[model, program], (
            f"{model}'s {program} program is not the one it was")
