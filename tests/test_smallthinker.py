"""SmallThinker (``models/smallthinker.py``) against its plain reference
(``benchmark/reference/smallthinker.py``) at toy widths on the CPU: the
forward pass, prefill in toy chunks then decode steps through BOTH stacks
of rings with prompts that end before the window rings' first wrap, exactly
at it and several wraps on, in slots other than 0 beside a scratch row, the
controls that must fail the limit the benchmark's configuration states, the
cache's two stacks and a slot's bytes, the types the programs compute in,
the scopes the readers read, the counters, and the engine on the normal
path with a generation that outlives the window. Every family's two
programs, this one's among them, are held bit for bit by
``tests/test_deepseek_v2.py``'s one table.
"""

import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.loading import load_json, load_module
from ray_tpu.models import smallthinker as st
from ray_tpu.ops import attention

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
reference = load_module(os.path.join(REPO, "benchmark", "reference",
                                     "smallthinker.py"))
family = load_module(os.path.join(REPO, "benchmark", "families",
                                  "smallthinker.py"))
check_tool = load_module(os.path.join(REPO, "benchmark", "tools",
                                      "serve_check_many.py"))
CONFIG = load_json(os.path.join(REPO, "benchmark", "configs",
                                "smallthinker-21b-a3b-instruct.json"))
F32 = jnp.float32
CFG = st.SmallThinkerConfig.tiny(dtype=F32, param_dtype=F32)


def toy_file(cfg):
    """The keys of a configuration file that ``families/smallthinker.py``
    reads, for ``cfg``'s sizes."""
    return {"hidden_size": cfg.d_model, "num_hidden_layers": cfg.n_layer,
            "rope_layout": list(cfg.window_layout),
            "sliding_window_layout": list(cfg.window_layout),
            "sliding_window_size": cfg.window,
            "num_attention_heads": cfg.n_head,
            "num_key_value_heads": cfg.n_kv_head, "head_dim": cfg.head_dim,
            "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.eps,
            "moe_num_primary_experts": cfg.n_experts,
            "moe_num_active_primary_experts": cfg.top_k,
            "moe_ffn_hidden_size": cfg.expert_ff,
            "vocab_size": cfg.vocab_size, "max_position_embeddings": 64,
            "assumed": {"init_gains": dict(cfg.gains)}}


def to_ref(params, cfg=CFG):
    return family.to_reference(params, toy_file(cfg))


def ref_kwargs(cfg=CFG, **turned):
    return {**family.reference_kwargs(toy_file(cfg)), **turned}


def moved(params, seed=6):
    """Every weight moved off its initial value: the norms start at 1, and
    a dropped or swapped scale would go unseen."""
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 512))
    return jax.tree.map(
        lambda x: x + 0.05 * jax.random.normal(next(keys), x.shape, x.dtype),
        params)


def rel_l2(got, want):
    return float(jnp.max(jnp.linalg.norm(got - want, axis=-1)
                         / jnp.linalg.norm(want, axis=-1)))


def through_the_cache(cfg, params, tokens, lengths, steps, chunk=4,
                      cache_len=64, padded=48, slots=None, n_slots=None):
    """The serving functions: the prompts (``tokens[r, :lengths[r]]``) in
    chunks through ``smallthinker_prefill_chunk``, every row run on to the
    end of the padded window as ``whole_prompts`` runs it, then ``steps``
    decode steps fed ``tokens``' continuation, the rows in ``slots`` (the
    first ones by default) of ``n_slots`` (one more than the rows: a
    scratch row that every step computes). -> logits [R, 1 + steps, V]."""
    r = tokens.shape[0]
    n_slots = n_slots or r + 1
    slots = jnp.arange(r) if slots is None else jnp.asarray(slots)
    prompts = jnp.where(jnp.arange(padded)[None] < lengths[:, None],
                        tokens[:, :padded], 0)
    cache = st.smallthinker_init_cache(cfg, n_slots, cache_len)
    logits, cache = jax.jit(lambda c: st.smallthinker_prefill(
        params, c, prompts, slots, lengths, cfg, chunk=chunk))(cache)
    out, rows = [logits], jnp.arange(r)
    step = jax.jit(lambda c, t, n: st.smallthinker_decode_step(
        params, c, t, n, cfg)[:2])
    for i in range(steps):
        toks = jnp.zeros((n_slots,), jnp.int32).at[slots].set(
            tokens[rows, lengths + i])
        pos = jnp.zeros((n_slots,), jnp.int32).at[slots].set(lengths + i)
        logits, cache = step(cache, toks, pos)
        out.append(logits[slots])
    return jnp.stack(out, axis=1)


def reference_rows(params, cfg, tokens, lengths, steps, **turned):
    full = jax.jit(lambda t: reference.forward(
        to_ref(params, cfg), t, **ref_kwargs(cfg, **turned)))(tokens)
    rows = jnp.arange(tokens.shape[0])
    return jnp.stack([full[rows, lengths - 1 + i]
                      for i in range(steps + 1)], axis=1)


@pytest.fixture(scope="module")
def params():
    return moved(st.smallthinker_init(jax.random.PRNGKey(0), CFG))


@pytest.fixture(scope="module")
def tokens():
    return jnp.asarray(np.random.default_rng(1).integers(
        0, CFG.vocab_size, (3, 56), dtype=np.int32))


@pytest.fixture(scope="module")
def want(params, tokens):
    # (jitted: op by op the reference costs several times as much, D19)
    return jax.jit(lambda t: reference.forward(
        to_ref(params), t, **ref_kwargs()))(tokens)


# -- sizes, the cache and types -----------------------------------------------


def test_the_published_sizes_and_the_tiny_preset():
    cfg = st.SmallThinkerConfig()
    assert (cfg.n_layer, cfg.n_global, cfg.n_window) == (52, 13, 39)
    assert cfg.window_layout[:8] == (0, 1, 1, 1, 0, 1, 1, 1)
    assert (cfg.d_model, cfg.n_head, cfg.n_kv_head, cfg.head_dim) \
        == (2560, 28, 4, 128)
    assert (cfg.n_experts, cfg.top_k, cfg.expert_ff) == (64, 6, 768)
    assert (cfg.window, cfg.rope_theta, cfg.row_width) == (4096, 1.5e6, 512)
    stated = family.system_config(CONFIG)
    assert dataclasses.replace(
        cfg, window_layout=cfg.window_layout[:8], vocab_size=18992,
        gains=stated.gains) == stated
    tiny = st.SmallThinkerConfig.tiny()
    assert tiny.window in (8, 16) and tiny.n_kv_head < tiny.n_head
    assert tiny.top_k < tiny.n_experts and tiny.window_layout \
        == (0, 1, 1, 1) * 2
    for bad in (dict(window_layout=(0, 2)), dict(window_layout=()),
                dict(n_head=3), dict(top_k=9), dict(window=0),
                dict(gains=(("embed", 1.0),))):
        with pytest.raises(ValueError):
            st.SmallThinkerConfig.tiny(**bad)


@pytest.mark.parametrize("which", ["tiny", "published"])
def test_the_cache_is_two_stacks_of_the_lengths_the_file_states(which):
    """A slot's bytes are ``2 * n_global * cache_len * W + 2 * n_window *
    window * W`` elements whatever ``cache_len`` is: the window stack does
    not grow with it."""
    cfg, slots, cache_len = (st.SmallThinkerConfig.tiny(), 3, 40) \
        if which == "tiny" else (family.system_config(CONFIG), 49, 16384)
    cache = jax.eval_shape(
        lambda: st.smallthinker_init_cache(cfg, slots, cache_len))
    w = cfg.row_width
    assert cache["k_full"].shape == cache["v_full"].shape \
        == (cfg.n_global, slots, cache_len, w)
    assert cache["k_win"].shape == cache["v_win"].shape \
        == (cfg.n_window, slots, cfg.window, w)
    rings = [cache[k] for k in ("k_full", "v_full", "k_win", "v_win")]
    assert {a.dtype for a in rings} == {jnp.dtype(jnp.bfloat16)}
    elements = sum(int(np.prod(a.shape)) for a in rings)
    assert elements == slots * (2 * cfg.n_global * cache_len * w
                                + 2 * cfg.n_window * cfg.window * w)
    if which == "published":
        assert w == 512 and elements * 2 == 49 * 117_440_512 \
            == family.cache_bytes(CONFIG, 49, 16384)
        stats = cfg.serving_stats()
        assert (stats["kv_bytes_per_token"],
                stats["window_kv_bytes_per_token"],
                stats["window_rows"]) == (2 * 2048, 6 * 2048, 4096)
        # the engine's chunk and key window, and an engine with no ring
        assert [cfg.serving_stats(*at)["chunk_attention_arm"]
                for at in ((512, 14336), (0, 0))] == ["kernel", "xla"]
    else:
        assert cfg.serving_stats(512, 14336)["chunk_attention_arm"] == "xla"


def _programs(cfg, chunk=4):
    params = jax.eval_shape(
        lambda: st.smallthinker_init(jax.random.PRNGKey(0), cfg))
    cache = jax.eval_shape(lambda: st.smallthinker_init_cache(cfg, 3, 16))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    return {
        "decode": (lambda p, c, t, n: st.smallthinker_decode_step(
            p, c, t, n, cfg), (params, cache, i32(3), i32(3))),
        "prefill": (lambda p, c, t, s, a, n: st.smallthinker_prefill_chunk(
            p, c, t, s, a, n, cfg, window=8),
            (params, cache, i32(1, chunk), i32(1), i32(1), i32(1)))}


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_the_programs_hold_the_types_the_file_states(program):
    """``computes_in`` of the benchmark's configuration file, held by the
    programs' own types: weights and products in bfloat16 and nothing
    narrower anywhere, float32 beside them, bfloat16 rings in and out."""
    stated = family.system_config(CONFIG)
    assert "bfloat16 weights" in CONFIG["computes_in"]
    assert (stated.param_dtype, stated.dtype) == (jnp.bfloat16, jnp.bfloat16)
    cfg = st.SmallThinkerConfig.tiny()  # the same defaults, a CPU's size
    assert (cfg.param_dtype, cfg.dtype) == (stated.param_dtype, stated.dtype)
    fn, args = _programs(cfg)[program]
    text = str(jax.make_jaxpr(fn)(*args))
    types = set(re.findall(r"\b([a-z]+[0-9]+[a-z0-9_]*)\[", text))
    assert {"bf16", "f32"} <= types
    assert not {t for t in types if t.startswith(("f8", "f16", "i8", "u8",
                                                  "i4", "u4"))}, types
    logits, new_cache, *_ = jax.eval_shape(fn, *args)
    assert logits.dtype == jnp.float32
    assert new_cache["k_full"].shape == (2, 3, 16, 32)  # merged rows, 2 x 16
    assert new_cache["k_win"].shape == (6, 3, 8, 32)
    assert jax.tree.structure(new_cache) == jax.tree.structure(args[1])
    assert jax.tree.map(lambda a: (a.shape, a.dtype), new_cache) \
        == jax.tree.map(lambda a: (a.shape, a.dtype), args[1])


def test_the_programs_name_the_scopes_the_readers_read():
    texts = {name: jax.jit(fn).lower(*args).as_text(debug_info=True)
             for name, (fn, args) in _programs(
                 st.SmallThinkerConfig.tiny()).items()}
    reader = load_module(os.path.join(
        REPO, "benchmark", "metrics", "decode_window_attention_time_pct.py"))
    for scope in ("embed", "ln", "router", "attn_proj", "rope", "attn",
                  "cache_write", "moe_dispatch", "experts", "moe_combine",
                  "head") + reader.KINDS:
        for name, text in texts.items():
            assert f"/{scope}/" in text, (name, scope)
    # both kinds under the outer scope that ``decode_attention_time_pct``
    # reads; only the window layers turn anything
    for text in texts.values():
        assert "/attn/attn_window/" in text and "/attn/attn_global/" in text
        assert text.count("/rope/") > 0


# -- against the reference ----------------------------------------------------


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_forward_agrees_with_the_reference(dtype, params, tokens, want):
    """Rows of 56 tokens: seven windows of eight."""
    if dtype == "float32":
        got = jax.jit(lambda p, t: st.smallthinker_forward(p, t, CFG))(
            params, tokens)
        assert got.dtype == jnp.float32 and got.shape == want.shape
        assert rel_l2(got, want) < 2e-5
        return
    cfg = st.SmallThinkerConfig.tiny()
    cast = jax.tree.map(lambda x: x.astype(jnp.bfloat16), params)
    got = st.smallthinker_forward(cast, tokens, cfg)
    exact = reference.forward(to_ref(cast, cfg), tokens, **ref_kwargs(cfg))
    # (the largest of 168 positions, where the served fixture has 14)
    assert got.dtype == jnp.float32 and rel_l2(got, exact) < 2 * TINY_SOUND


# Where a prompt ends, against window rings of 8 rows written in chunks of
# 4: before the first wrap, exactly at it, a token past it, off a chunk
# boundary several wraps on (the last chunk's padded rows must keep what the
# ring held), and on one.
ENDS = {"before_the_first_wrap": [5, 3, 7], "exactly_at_it": [8, 8, 6],
        "a_token_past_it": [9, 4, 8], "several_wraps_on": [37, 22, 11],
        "on_a_chunk_boundary_wraps_on": [40, 16, 24]}


@pytest.mark.parametrize("chunk", [4, 8])
@pytest.mark.parametrize("ends", sorted(ENDS))
def test_prefill_in_toy_chunks_then_decode_through_both_stacks(
        ends, chunk, params, tokens):
    """Three prompts of different lengths in slots 3, 1 and 2 of five (slot
    0 free, slot 4 the scratch row), every row run on to the end of the
    padded window, then eight decode steps: a window ring's cursor goes
    round once more."""
    lengths = jnp.asarray(ENDS[ends], jnp.int32)
    got = through_the_cache(CFG, params, tokens, lengths, steps=8,
                            chunk=chunk, slots=[3, 1, 2], n_slots=5)
    assert rel_l2(got, reference_rows(params, CFG, tokens, lengths, 8)) \
        < 2e-5


def test_a_recycled_slots_window_ring_holds_the_last_tenants_rows(params,
                                                                  tokens):
    """A slot that served a long prompt serves a short one next: the window
    rings still hold the last tenant's rows past the new prompt's end, and
    nothing of them is seen (a ring row whose position is negative for this
    prompt is nobody's)."""
    cache = st.smallthinker_init_cache(CFG, 2, 64)
    slot = jnp.zeros((1,), jnp.int32)
    prefill = jax.jit(lambda c, t, n: st.smallthinker_prefill(
        params, c, t, slot, n, CFG, chunk=4))
    step = jax.jit(lambda c, t, n: st.smallthinker_decode_step(
        params, c, t, n, CFG)[:2])
    _, cache = prefill(cache, tokens[:1, :40], jnp.asarray([37]))
    assert float(jnp.abs(cache["k_win"][:, 0]).min(axis=-1).min()) > 0
    lengths = jnp.asarray([6], jnp.int32)
    logits, cache = prefill(cache, tokens[1:2, :8], lengths)
    out = [logits]
    for i in range(4):
        got, cache = step(cache, jnp.asarray([tokens[1, 6 + i], 0]),
                          jnp.asarray([6 + i, 0]))
        out.append(got[:1])
    assert rel_l2(jnp.stack(out, axis=1), reference_rows(
        params, CFG, tokens[1:2], lengths, 4)) < 2e-5


def test_the_counters_count_the_pairs_and_the_rows_of_both_stacks(params,
                                                                  tokens):
    cache = st.smallthinker_init_cache(CFG, 3, 32)
    lengths = jnp.asarray([13, 6], jnp.int32)
    _, cache = jax.jit(lambda c: st.smallthinker_prefill(
        params, c, tokens[:2, :16], jnp.arange(2), lengths, CFG, chunk=4))(
            cache)
    # every real token's three experts in each of eight layers, no padding
    assert int(cache["counted"]["prefill_expert_rows"]) == (13 + 6) * 3 * 8
    _, _, counted = jax.jit(lambda c: st.smallthinker_decode_step(
        params, c, jnp.zeros((3,), jnp.int32), jnp.asarray([13, 6, 0]),
        CFG))(cache)
    got = {k: int(v) for k, v in counted.items()}
    assert got["expert_rows"] == 3 * 3 * 8  # every row is routed
    assert 0 < got["experts_hit"] <= 8 * 8
    # toy rings take the XLA arm, which reads every ring whole
    assert got["window_rows_read"] == got["window_rows_held"] == 6 * 3 * 8
    assert got["ring_rows_read"] == got["ring_rows_held"] \
        == 6 * 3 * 8 + 2 * 3 * 32


# -- the limit and its controls -----------------------------------------------


@pytest.fixture(scope="module")
def served():
    """The tiny preset AS THE CELL COMPUTES (bfloat16 weights, activations
    and matmuls) through the cache, and the float32 reference's rows of the
    same seeded weights: prompts that have wrapped the window rings five
    times and twice and end off a chunk boundary."""
    cfg = st.SmallThinkerConfig.tiny()
    params = st.smallthinker_init(jax.random.PRNGKey(4), cfg)
    tokens = jnp.asarray(np.random.default_rng(4).integers(
        0, cfg.vocab_size, (2, 60), dtype=np.int32))
    lens = jnp.asarray([43, 21], jnp.int32)
    return cfg, params, tokens, lens, reference_rows(
        params, cfg, tokens, lens, 6)


# What bfloat16 reads at the TINY preset: 0.008-0.021 over three seeds (48
# lanes, three of eight experts a token), under the stated limit as the
# published widths are on the chip (the configuration file's
# ``tolerance.reason``). The tiny preset's own sound bound stands beside the
# stated limit, and a control must pass the larger of the two twice over.
TINY_SOUND = 0.04


def test_the_stated_limit_holds_the_sound_program(served):
    cfg, params, tokens, lens, want = served
    limit = CONFIG["tolerance"]["serve_logits_rel_l2"]
    got = through_the_cache(cfg, params, tokens, lens, steps=6)
    assert rel_l2(got, want) < min(limit, TINY_SOUND)


def _ring_as_if_not_wrapped(start, n_rows):
    """``ring_positions`` of a ring read as a straight cache: row j holds
    position j where the prompt has got that far."""
    rows = jnp.arange(n_rows)
    return jnp.where(rows < start[..., None], rows, -1)


def _padded_rows_written(cache, rows, slots, start, lengths):
    """``cache_write_ring_chunk`` that writes a chunk's padded rows too."""
    return attention.cache_write_ring_chunk(
        cache, rows, slots, start, jnp.full_like(lengths, rows.shape[2]))


# each control, and where it is turned: the REFERENCE's argument (the sound
# system is then held to another model) or the SYSTEM
TURNED_IN_THE_REFERENCE = {
    "window_ignored_on_window_layers": dict(windows=(False,) * 8),
    "global_layers_rotated": dict(rotates=(True,) * 8),
    "window_layers_not_rotated": dict(rotates=(False,) * 8),
    "router_fed_the_normed_input": dict(router_reads="normed_input"),
    "router_fed_the_post_attention_stream":
        dict(router_reads="post_attention"),
    "silu_for_relu": dict(activation="silu"),
}
CONTROLS = sorted(TURNED_IN_THE_REFERENCE) + [
    "chunk_reads_the_ring_as_if_not_wrapped",
    "padded_rows_overwrite_the_ring", "float8_weights"]


@pytest.mark.parametrize("control", CONTROLS)
def test_the_stated_limit_refuses_each_control(served, control, monkeypatch):
    """``serve_logits_rel_l2`` of the benchmark's configuration, at the
    tiny preset in the cell's precision. Each control is one function's
    difference from another, put where it is shortest to write: into the
    REFERENCE the sound system is then held to (the window ignored, the
    global layers rotated, the window layers not rotated, the router fed
    ``N1(x)`` or the post-attention stream, SiLU for ReLU) or into the
    SYSTEM (a chunk that reads a wrapped ring as if it had not wrapped, a
    last chunk whose padded rows overwrite the ring, float8 weights as
    ``tools/serve_check_many.py --fault fp8_weights`` rounds them). Every
    one reads over the limit, by a wide margin."""
    cfg, params, tokens, lens, want = served
    limit = CONFIG["tolerance"]["serve_logits_rel_l2"]
    if control == "router_fed_the_normed_input":
        # at w = 1 and a stream of rms one N1(x) IS x: the norms' weights
        # drawn well away from 1, as a trained checkpoint's are
        keys = iter(jax.random.split(jax.random.PRNGKey(9), 16))
        params = {**params, "layers": [
            {**p, "norm": (1 + 0.5 * jax.random.normal(
                next(keys), p["norm"].shape)).astype(p["norm"].dtype)}
            for p in params["layers"]]}
    if control in TURNED_IN_THE_REFERENCE:
        want = reference_rows(params, cfg, tokens, lens, 6,
                              **TURNED_IN_THE_REFERENCE[control])
    elif control == "chunk_reads_the_ring_as_if_not_wrapped":
        monkeypatch.setattr(attention, "ring_positions",
                            _ring_as_if_not_wrapped)
    elif control == "padded_rows_overwrite_the_ring":
        monkeypatch.setattr(st, "cache_write_ring_chunk",
                            _padded_rows_written)
    else:
        params = check_tool.rounded(jax.tree.map(jnp.copy, params), 2)
    got = through_the_cache(cfg, params, tokens, lens, steps=6)
    assert rel_l2(got, want) > 2 * max(limit, TINY_SOUND), control


def test_the_router_reads_the_unnormed_input_before_attention(params, tokens):
    """The system's choice of experts in a layer is the reference's gating
    of ``x W_r`` of the layer's own un-normed input: with the input's norm
    weight moved, a router fed ``N1(x)`` would choose otherwise."""
    p = params["layers"][0]
    x = params["embed"][tokens[0]]
    ids, weights = st._route(p, x, CFG)
    dense = reference.gating(x @ p["router"], CFG.top_k)
    np.testing.assert_allclose(
        np.take_along_axis(np.asarray(dense), np.asarray(ids), axis=-1),
        np.asarray(weights), rtol=1e-5)
    assert np.allclose(np.asarray(weights).sum(-1), 1.0, atol=1e-5)
    normed = st._norm(x, p["norm"], CFG.eps)
    other, _ = st._route(p, normed, CFG)
    assert (np.sort(np.asarray(other)) != np.sort(np.asarray(ids))).any()


# -- the engine ---------------------------------------------------------------


@pytest.fixture
def runtime():
    import ray_tpu
    from ray_tpu import serve

    ray_tpu.init()
    yield serve
    serve.shutdown()
    ray_tpu.shutdown()


def test_the_engine_serves_the_references_greedy_tokens(runtime):
    """Through ``serve.run(LLMEngine)``: a prompt of three windows and a
    generation of three more, against the float32 reference's own greedy
    continuation of the same float32 weights; one decode program and one
    chunk program whatever the lengths."""
    import ray_tpu
    from ray_tpu.serve.llm_engine import LLMEngine

    dep = runtime.deployment(name="llm", max_concurrent_queries=16)(LLMEngine)
    handle = runtime.run(dep.bind(
        model="smallthinker", config=CFG, seed=0, max_batch=2, cache_len=64,
        max_prompt_len=32, prefill_chunk=4, max_new_cap=24))
    params = st.smallthinker_init(jax.random.PRNGKey(0), CFG)
    ref, kw = to_ref(params), ref_kwargs()
    forward = jax.jit(lambda t: reference.forward(ref, t, **kw))
    rng = np.random.default_rng(3)
    for n in (26, 7):
        prompt = rng.integers(0, CFG.vocab_size, n).tolist()
        row = list(prompt)
        for _ in range(24):  # causal: one padded shape serves every length
            padded = jnp.asarray([row + [0] * (50 - len(row))])
            row.append(int(jnp.argmax(forward(padded)[0, len(row) - 1])))
        served = [t for chunk in handle.stream(prompt, 24) for t in chunk]
        assert served == row[n:], n
        assert len(set(served)) > 2  # no fixed point: it follows its context
    stats = ray_tpu.get(handle.llm_stats.remote(), timeout=30)
    assert stats["compiles"] == {"decode": 1, "prefill": 1}
    assert stats["prefill_chunks"] == 7 + 2 and stats["window_rows"] == 8
    for key in ("ring_rows_read", "ring_rows_held", "window_rows_read",
                "window_rows_held", "experts_hit", "expert_rows",
                "expert_row_tiles", "prefill_expert_rows"):
        assert stats[key] > 0, key
    assert stats["prefill_expert_rows"] == (26 + 7) * 3 * 8
    ray_tpu.get(handle.shutdown_engine.remote(), timeout=30)


def test_the_tiny_preset_engine_and_the_bundles_error_text():
    from ray_tpu.serve.llm_engine import LLMEngine, _model_bundle

    cfg, init, init_cache, chunk, step = _model_bundle(
        "smallthinker", None, "tiny")
    assert cfg == st.SmallThinkerConfig.tiny()
    assert (init, init_cache, chunk, step) == (
        st.smallthinker_init, st.smallthinker_init_cache,
        st.smallthinker_prefill_chunk, st.smallthinker_decode_step)
    with pytest.raises(ValueError) as err:
        _model_bundle("smallthinker2", None, "tiny")
    for name in ("gpt2", "llama", "nemotron_h", "granite_hybrid",
                 "deepseek_v2", "falcon_h1", "qwen3_next", "smallthinker"):
        assert name in str(err.value)
    # a prompt three and a half times the window passes the engine's check:
    # cache_len bounds a context and the GLOBAL rings, not the window rings
    engine = LLMEngine(model="smallthinker", preset="tiny", max_batch=2,
                       cache_len=32, max_prompt_len=28, prefill_chunk=4)
    try:
        assert engine._cache["k_win"].shape[2] == 8
        assert engine._cache["k_full"].shape[2] == 32
        assert len(engine.generate(list(range(1, 29)), 4)) == 4
    finally:
        engine.shutdown_engine()
