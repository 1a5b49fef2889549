"""Granite 4.0-H (``models/granite_hybrid.py``) against its plain reference
(``benchmark/reference/granite_hybrid.py``) at toy widths on the CPU: prefill
in 1, 2, 17 and 32 toy chunks then decode steps through the cache, each
published multiplier and the gate as a control (left out of the reference in
turn, the comparison fails). The contracts every served family holds (sizes,
types, the forward pass, the engine against the reference) are
``tests/test_served_family_contract.py``'s.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import granite_hybrid as gh
from ray_tpu.models.prefill import whole_prompts
from served_families import (FAMILIES, contract_params, contract_tokens,
                             contract_want, moved, rel_l2)

ROW = FAMILIES["granite_hybrid"]
reference, family, CFG = ROW.reference, ROW.family, ROW.cfg
to_ref, ref_kwargs = ROW.to_reference, ROW.reference_kwargs
F32 = jnp.float32


@pytest.fixture(scope="module")
def params():
    return contract_params("granite_hybrid")


@pytest.fixture(scope="module")
def tokens():
    return contract_tokens("granite_hybrid")


@pytest.fixture(scope="module")
def want():
    return contract_want("granite_hybrid")


def test_weights_are_stored_in_bfloat16_and_the_head_is_the_embedding():
    cfg = gh.GraniteHybridConfig.tiny()
    params = gh.granite_hybrid_init(jax.random.PRNGKey(0), cfg)
    assert all(x.dtype == jnp.bfloat16 for x in jax.tree.leaves(params))
    assert set(params) == {"embed", "layers", "norm_f"}  # no lm_head
    assert params["layers"][0]["w1"].shape == (4, 64, 2 * 48)
    assert params["layers"][0]["router"].shape == (64, 8)
    cache = gh.granite_hybrid_init_cache(cfg, 3, 16)
    # K/V rings for the one attention layer only; a tail and a float32
    # state for each of the two Mamba layers
    assert cache["k"].shape == cache["v"].shape == (1, 3, 16, 2, 16)
    assert cache["conv"].shape == (2, 3, 3, cfg.mamba.conv_dim)
    assert [(s.shape, s.dtype) for s in cache["ssm"]] \
        == [((3, 8, 16, 16), jnp.float32)] * 2
    assert cfg.serving_dtypes(params) == jax.tree.map(
        lambda x: x.dtype, params)


@pytest.mark.parametrize("term, without", [
    ("embedding_multiplier", {"embedding_multiplier": 1.0}),
    ("attention_multiplier", {"attention_multiplier": CFG.head_dim ** -0.5}),
    ("residual_multiplier", {"residual_multiplier": 1.0}),
    ("logits_scaling", {"logits_scaling": 1.0}),
    ("gate", None),
    ("top_k", {"top_k": 2}),
    ("first_expert", {"first_expert": 4}),
])
def test_the_reference_without_a_term_is_another_model(
        params, tokens, want, monkeypatch, term, without):
    """The controls: each published multiplier left at what a model
    without it would use, and the experts' gate left out, moves the
    reference by far more than the comparisons' 1e-4: a tolerance cannot
    hide a missing term."""
    if without is None:
        monkeypatch.setattr(reference, "gated", lambda ab: jax.nn.silu(
            ab[..., :ab.shape[-1] // 2]))
        without = {}
    other = ROW.reference_forward(params, CFG, **without)(tokens)
    assert rel_l2(other, want) > 2e-2, term
    got = gh.granite_hybrid_forward(params, tokens, CFG)
    assert rel_l2(got, other) > 2e-2, term


CHUNK = 4  # a toy chunk; the scan blocks by 4 too


@pytest.mark.parametrize("chunks, length", [(1, 4), (2, 7), (17, 66),
                                            (32, 128)])
def test_prefill_in_toy_chunks_then_decode_through_the_cache(chunks,
                                                             length):
    """A prompt of 1, 2, 17 and 32 chunks (the second and third end
    inside a chunk) through the chunk program, then five decode steps
    through the cache, against the reference's full forward: logits at
    the prompt's last token and after every step."""
    assert -(-length // CHUNK) == chunks
    cfg = gh.GraniteHybridConfig.tiny(dtype=F32, param_dtype=F32,
                                      chunk_size=4)
    params = moved(gh.granite_hybrid_init(jax.random.PRNGKey(2), cfg))
    steps, window = 5, 128
    row = jnp.asarray(np.random.default_rng(length).integers(
        0, cfg.vocab_size, (1, length + steps), dtype=np.int32))
    want = ROW.reference_forward(params, cfg)(row)
    cache = gh.granite_hybrid_init_cache(cfg, 2, window + 8)
    chunk = jax.jit(lambda c, t, at, n: gh.granite_hybrid_prefill_chunk(
        params, c, t, jnp.ones(1, jnp.int32), at, n, cfg, window=window))
    for at in range(0, length, CHUNK):
        piece = np.zeros((1, CHUNK), np.int32)
        n = min(CHUNK, length - at)
        piece[0, :n] = np.asarray(row)[0, at:at + n]
        logits, cache = chunk(
            cache, jnp.asarray(piece), jnp.full(1, at, jnp.int32),
            jnp.full(1, n, jnp.int32))
    out = [logits[0]]
    step = jax.jit(lambda c, t, n: gh.granite_hybrid_decode_step(
        params, c, t, n, cfg))
    for i in range(steps):
        toks = jnp.zeros(2, jnp.int32).at[1].set(row[0, length + i])
        pos = jnp.zeros(2, jnp.int32).at[1].set(length + i)
        logits, cache, _ = step(cache, toks, pos)
        out.append(logits[1])
    assert rel_l2(jnp.stack(out), want[0, length - 1:]) < 2e-4
    # the chunks' pairs, in the cache the steps handed on; padding is
    # routed nowhere: at most top_k pairs a real token a layer
    pairs = int(cache["counted"]["prefill_expert_rows"])
    assert 0 < pairs <= length * cfg.top_k * len(cfg.layer_types)


def test_the_whole_window_form_serves_rows_of_different_lengths(params,
                                                                tokens,
                                                                want):
    """``granite_hybrid_prefill``'s loop (what the benchmark's reference
    check calls; here in four chunks of 8): three rows of different lengths
    in one window, then decode."""
    lens = jnp.asarray([17, 32, 5], jnp.int32)
    prompts = np.zeros((3, 32), np.int32)
    for i, n in enumerate(np.asarray(lens)):
        prompts[i, :n] = np.asarray(tokens)[i, :n]
    cache = gh.granite_hybrid_init_cache(CFG, 4, 64)
    logits, cache = whole_prompts(
        gh.granite_hybrid_prefill_chunk, params, cache, jnp.asarray(prompts),
        jnp.arange(3), lens, CFG, chunk=8)
    rows = jnp.arange(3)
    out = [logits]
    for s in range(4):
        pos = jnp.zeros(4, jnp.int32).at[:3].set(lens + s)
        toks = jnp.zeros(4, jnp.int32).at[:3].set(tokens[rows, lens + s])
        logits, cache, _ = gh.granite_hybrid_decode_step(
            params, cache, toks, pos, CFG)
        out.append(logits[:3])
    got = jnp.stack(out, axis=1)
    ref = jnp.stack([want[rows, lens - 1 + s] for s in range(5)], axis=1)
    assert rel_l2(got, ref) < 2e-4


# -- the attention ops' ``scale`` ---------------------------------------------


def _attention_case(seed=0, s=2, t=12, g=2, rep=2, hd=16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    d = g * rep * hd
    x = jax.random.normal(ks[0], (s, t, d))
    p = {"q_proj": jax.random.normal(ks[1], (d, d)) * 0.3,
         "k_proj": jax.random.normal(ks[2], (d, g * hd)) * 0.3,
         "v_proj": jax.random.normal(ks[3], (d, g * hd)) * 0.3,
         "o_proj": jnp.eye(d)}
    q = (x @ p["q_proj"]).reshape(s, t, g * rep, hd)
    k = (x @ p["k_proj"]).reshape(s, t, g, hd)
    v = (x @ p["v_proj"]).reshape(s, t, g, hd)
    return x, p, q, k, v


def _through_the_ops(q, k, v, scale, expand=False):
    """The first ``t - 1`` positions as one chunk over the slot's rows,
    the last as a decode step over the ring. -> [S, T, H * hd]."""
    from ray_tpu.ops import attention as ops

    s, t, h, hd = q.shape
    if expand:  # as many K/V heads as query heads: the ungrouped form
        k, v = (jnp.repeat(a, h // a.shape[2], axis=2) for a in (k, v))
    g = k.shape[2]
    kw = {} if scale is None else {"scale": scale}
    cache_k = jnp.zeros((1, s, 16, g, hd)).at[0, :, :t - 1].set(k[:, :t - 1])
    cache_v = jnp.zeros((1, s, 16, g, hd)).at[0, :, :t - 1].set(v[:, :t - 1])
    rows = ops.cached_chunk_attention(
        q[:, :t - 1], cache_k, cache_v, 0, jnp.arange(s),
        jnp.zeros(s, jnp.int32), 16, **kw)
    last = ops.cached_decode_attention(
        q[:, t - 1], cache_k[0], cache_v[0], k[:, t - 1], v[:, t - 1],
        jnp.full(s, t - 1), jnp.full(s, t), jnp.float32, **kw)
    return jnp.concatenate([rows, last[:, None]], 1).reshape(s, t, h * hd)


@pytest.mark.parametrize("expand", [False, True], ids=["grouped", "full"])
def test_the_default_scale_is_bit_for_bit_what_it_was(expand):
    """No ``scale``: ``hd ** -0.5``, computed as the compiled steps have
    always computed it (the decode ops divide by ``hd ** 0.5``, the chunk
    op multiplies), so every other family's programs are the ones they
    were. At ``hd`` 16 both are exact, and a given 0.25 is the same bits."""
    from ray_tpu.ops import attention as ops

    _, _, q, k, v = _attention_case()
    default = _through_the_ops(q, k, v, None, expand)
    np.testing.assert_array_equal(
        np.asarray(default),
        np.asarray(_through_the_ops(q, k, v, 0.25, expand)))
    step = lambda **kw: str(jax.make_jaxpr(
        lambda q, k, v: ops.cached_decode_attention(
            q[:, -1], k, v, k[:, -1], v[:, -1], jnp.full(2, 11),
            jnp.full(2, 12), jnp.float32, **kw))(
                q, *((jnp.repeat(a, 2, axis=2) for a in (k, v))
                     if expand else (k, v))))
    # (the softmax divides too: the default holds one division more)
    assert step().count(" div ") == step(scale=0.25).count(" div ") + 1


@pytest.mark.parametrize("expand", [False, True], ids=["grouped", "full"])
def test_a_published_scale_is_the_references(expand):
    """1 / 128 on heads of 16: the chunk op and the decode op against
    the reference's attention with ``attention_multiplier``; the default
    scale is then another model."""
    x, p, q, k, v = _attention_case(seed=3)
    want = reference.attention(p, x, n_head=4, n_kv_head=2, head_dim=16,
                               attention_multiplier=1 / 128)
    got = _through_the_ops(q, k, v, 1 / 128, expand)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    other = _through_the_ops(q, k, v, None, expand)
    assert float(jnp.abs(other - want).max()) > 1e-2

