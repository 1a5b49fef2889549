"""Granite 4.0-H (``models/granite_hybrid.py``) against its plain reference
(``benchmark/reference/granite_hybrid.py``) at toy widths on the CPU: the
forward pass, prefill in 1, 2, 17 and 32 toy chunks then decode steps through
the cache, each published multiplier and the gate as a control (left out of
the reference in turn, the comparison fails), the types the programs
compute in, and the engine on the normal path with its counters.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.loading import load_json, load_module
from ray_tpu.models import granite_hybrid as gh
from ray_tpu.models.prefill import whole_prompts

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
reference = load_module(os.path.join(REPO, "benchmark", "reference",
                                     "granite_hybrid.py"))
family = load_module(os.path.join(REPO, "benchmark", "families",
                                  "granite_hybrid.py"))
F32 = jnp.float32
CFG = gh.GraniteHybridConfig.tiny(dtype=F32, param_dtype=F32)


def ref_kwargs(cfg, **over):
    kw = dict(layer_types=cfg.layer_types, eps=cfg.eps, n_head=cfg.n_head,
              n_kv_head=cfg.n_kv_head, head_dim=cfg.head_dim,
              mamba_heads=cfg.mamba_heads,
              mamba_head_dim=cfg.mamba_head_dim, n_groups=cfg.ssm_groups,
              ssm_state=cfg.ssm_state, top_k=cfg.top_k,
              first_expert=cfg.experts_held[0],
              embedding_multiplier=cfg.embedding_multiplier,
              attention_multiplier=cfg.attention_multiplier,
              residual_multiplier=cfg.residual_multiplier,
              logits_scaling=cfg.logits_scaling)
    kw.update(over)
    return kw


def to_ref(params, cfg):
    return {"embed_tokens": params["embed"], "norm": params["norm_f"],
            "layers": [{ref: p[name] for name, ref in {
                **family.LAYER_NAMES, **family.MIXER_NAMES[kind]}.items()}
                for kind, p in zip(cfg.layer_types, params["layers"])]}


def moved(params, seed=6):
    """Every weight moved off its initial value: the norm scales start at
    one, and a dropped or swapped scale would go unseen."""
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 256))
    return jax.tree.map(
        lambda x: x + 0.05 * jax.random.normal(next(keys), x.shape, x.dtype),
        params)


def rel_l2(got, want):
    return float(jnp.max(jnp.linalg.norm(got - want, axis=-1)
                         / jnp.linalg.norm(want, axis=-1)))


def weighty(params):
    """At their seeded scale the routed experts and attention add a
    hundredth of what a Mamba mixer adds: make them count, so that a
    fault in either is seen."""
    big = {"w2": 6.0, "wo": 6.0}
    return {**params, "layers": [
        {k: v * big.get(k, 1.0) for k, v in p.items()}
        for p in params["layers"]]}


@pytest.fixture(scope="module")
def params():
    return weighty(moved(gh.granite_hybrid_init(jax.random.PRNGKey(0), CFG)))


@pytest.fixture(scope="module")
def tokens():
    return jnp.asarray(np.random.default_rng(1).integers(
        0, CFG.vocab_size, (3, 40), dtype=np.int32))


@pytest.fixture(scope="module")
def want(params, tokens):
    # (jitted: op by op the reference costs several times as much, D19)
    return jax.jit(lambda t: reference.forward(
        to_ref(params, CFG), t, **ref_kwargs(CFG)))(tokens)


def test_the_published_layers_and_the_tiny_preset():
    types = gh.GraniteHybridConfig().layer_types
    assert len(types) == 40
    assert [types.count(k) for k in ("mamba", "attention")] == [36, 4]
    assert all(types[i:i + 10] == types[:10] for i in range(0, 40, 10))
    assert types[:10].index("attention") == 5
    assert set(CFG.layer_types) == {"mamba", "attention"}
    assert CFG.attention_multiplier != CFG.head_dim ** -0.5
    assert gh.GraniteHybridConfig().attention_multiplier == 1 / 128
    assert CFG.serving_stats() == {"expert_layers": 3, "experts_held": 4}
    with pytest.raises(ValueError, match="layer_types"):
        gh.GraniteHybridConfig.tiny(layer_types=("mamba", "moe"))
    with pytest.raises(ValueError, match="experts_held"):
        gh.GraniteHybridConfig.tiny(experts_held=(6, 4))


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


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_the_programs_hold_the_types_the_file_states(program):
    """``computes_in`` of the benchmark's configuration file, held by the
    programs' own types: weights and products in bfloat16 and nothing
    narrower anywhere, float32 beside them (router, softmax, dt / A, norms'
    statistics), and a float32 state in and out."""
    config = load_json(os.path.join(
        REPO, "benchmark", "configs", "granite-4.0-h-small.json"))
    stated = family.system_config(config)
    assert config["assumed"]["ssm_state_dtype"] == "float32"
    assert "bfloat16 weights" in config["computes_in"]
    assert (stated.param_dtype, stated.dtype, stated.ssm_state_dtype) \
        == (jnp.bfloat16, jnp.bfloat16, jnp.float32)
    cfg = gh.GraniteHybridConfig.tiny()  # the same defaults, a CPU's size
    assert (cfg.param_dtype, cfg.dtype, cfg.ssm_state_dtype) \
        == (stated.param_dtype, stated.dtype, stated.ssm_state_dtype)
    params = jax.eval_shape(
        lambda: gh.granite_hybrid_init(jax.random.PRNGKey(0), cfg))
    cache = jax.eval_shape(lambda: gh.granite_hybrid_init_cache(cfg, 3, 16))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    if program == "decode":
        fn = lambda p, c, t, n: gh.granite_hybrid_decode_step(
            p, c, t, n, cfg)
        args = (params, cache, i32(3), i32(3))
    else:
        fn = lambda p, c, t, s, n: gh.granite_hybrid_prefill_chunk(
            p, c, t, s, jnp.zeros_like(s), n, cfg)
        args = (params, cache, i32(1, 16), i32(1), i32(1))
    text = str(jax.make_jaxpr(fn)(*args))
    types = set(re.findall(r"\b([a-z]+[0-9]+[a-z0-9_]*)\[", text))
    assert {"bf16", "f32"} <= types
    assert not {t for t in types if t.startswith(("f8", "f16", "i8", "u8",
                                                  "i4", "u4"))}, types
    logits, new_cache, *counted = jax.eval_shape(fn, *args)
    assert logits.dtype == jnp.float32
    assert [s.dtype for s in new_cache["ssm"]] == [jnp.float32] * 2
    # the step returns its counters third; the chunk program counts in
    # the cache, which both hand on
    counted = [*counted, new_cache["counted"]]
    assert all(v.dtype == jnp.int32 and v.shape == ()
               for c in counted for v in c.values())
    assert [set(c) for c in counted] == (
        [{"experts_hit", "expert_rows", "expert_row_tiles"}]
        if program == "decode" else []) \
        + [{"prefill_expert_rows"}]


def test_forward_agrees_with_the_reference(params, tokens, want):
    forward = jax.jit(lambda p, t: gh.granite_hybrid_forward(p, t, CFG))
    got = forward(params, tokens)
    assert got.shape == want.shape == (3, 40, CFG.vocab_size)
    assert rel_l2(got, want) < 1e-4
    # a row longer than one block of the scan, and not a multiple of it
    assert tokens.shape[1] > 2 * CFG.chunk_size
    odd = forward(params, tokens[:, :37])
    assert rel_l2(odd, want[:, :37]) < 1e-4


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
    other = reference.forward(to_ref(params, CFG), tokens,
                              **ref_kwargs(CFG, **without))
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
    want = reference.forward(to_ref(params, cfg), row, **ref_kwargs(cfg))
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


# -- the engine ---------------------------------------------------------------


@pytest.fixture
def runtime():
    import ray_tpu
    from ray_tpu import serve

    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=4)
    yield serve
    try:
        serve.shutdown()
    except Exception:
        pass
    ray_tpu.shutdown()


def test_the_engine_serves_the_references_greedy_tokens(runtime):
    """``LLMEngine(model="granite_hybrid", preset="tiny")``'s sizes
    through ``serve.run`` / ``handle.stream`` in float32: token for token
    the reference's greedy choice, two compiled programs, and the step's
    and the chunks' counters in ``llm_stats()``."""
    import dataclasses

    import ray_tpu
    from ray_tpu.serve.llm_engine import LLMEngine

    # With the tied head and the published multiplier 12, seeded weights
    # answer every token with itself (the embedding's own row leads its
    # logits by several spreads: on the chip too, PERF.md section 7), and
    # a greedy continuation would then say nothing of state or cache. At
    # 0.3 the continuation depends on the whole context.
    cfg = dataclasses.replace(CFG, embedding_multiplier=0.3)
    dep = runtime.deployment(name="llm", max_concurrent_queries=16)(LLMEngine)
    handle = runtime.run(dep.bind(
        model="granite_hybrid", config=cfg, seed=3, max_batch=3,
        cache_len=32, max_prompt_len=16, prefill_rows=2, prefill_chunk=4))
    params = gh.granite_hybrid_init(jax.random.PRNGKey(3), cfg)
    ref, kw = to_ref(params, cfg), ref_kwargs(cfg)
    prompts = [[5, 9, 2, 17, 3], [11, 200, 4, 4, 8, 1, 99, 23, 54]]
    forward = jax.jit(lambda t: reference.forward(ref, t, **kw))
    for prompt in prompts:
        toks = list(prompt)
        for _ in range(6):  # causal: one padded shape serves every length
            padded = jnp.asarray([toks + [0] * (16 - len(toks))])
            toks.append(int(jnp.argmax(forward(padded)[0, len(toks) - 1])))
        served = [t for chunk in handle.stream(prompt, 6) for t in chunk]
        assert served == toks[len(prompt):]
        assert len(set(served)) > 3  # no fixed point
    stats = ray_tpu.get(handle.llm_stats.remote(), timeout=30)
    assert stats["compiles"] == {"decode": 1, "prefill": 1}
    assert stats["model"] == "granite_hybrid"
    assert stats["expert_layers"] == 3 and stats["experts_held"] == 4
    steps = stats["steps"]
    assert steps >= 10
    # every step runs max_batch + 1 rows through 3 expert layers, top 3
    assert 0 < stats["experts_hit"] <= steps * 3 * 4
    assert stats["experts_hit"] <= stats["expert_rows"] <= steps * 3 * 12
    # the chunks: 2 + 3 executions, 14 real tokens, their pairs counted
    assert stats["prefill_chunks"] == 5
    assert stats["prefill_tokens_real"] == 14
    assert 0 < stats["prefill_expert_rows"] <= 14 * 3 * 3
    ray_tpu.get(handle.shutdown_engine.remote(), timeout=30)


def test_the_tiny_preset_engine_and_the_bundles_error_text():
    from ray_tpu.serve.llm_engine import LLMEngine, _model_bundle

    eng = LLMEngine(model="granite_hybrid", preset="tiny", max_batch=2,
                    cache_len=16, max_prompt_len=8)
    try:
        assert len(eng.generate([1, 2, 3], 4)) == 4
        assert eng._step_counters == ("expert_row_tiles", "expert_rows",
                                      "experts_hit")
        assert eng.llm_stats()["prefill_expert_rows"] == int(
            eng._cache["counted"]["prefill_expert_rows"]) > 0
    finally:
        eng.shutdown_engine()
    with pytest.raises(ValueError,
                       match=r"gpt2\|llama\|nemotron_h\|granite_hybrid"):
        _model_bundle("mamba", None, "tiny")
