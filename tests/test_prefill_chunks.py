"""A prompt is prefilled in chunks (PR 31): for every family the engine
serves, ``<family>_prefill_chunk`` run chunk after chunk leaves what one
whole-window pass leaves (first-token logits, K/V rows, and for the hybrid
family the convolution's tail and the SSM state), a first chunk begins
anew whatever the slot held, and the engine built on it serves the
full-context forward's tokens from its two compiled programs, counting its
chunks.
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.loading import load_json, load_module
from ray_tpu.models import (deepseek_v2, falcon_h1, gpt2, granite_hybrid,
                            keye_vl2, llama, nemotron_h, qwen3_next,
                            smallthinker)
from ray_tpu.models.prefill import (chunk_len, key_window, token_parameters,
                                    whole_prompts)
from ray_tpu.serve.llm_engine import LLMEngine, _model_bundle

F32 = jnp.float32
# (float32 tiny config, init, init_cache, prefill_chunk, whole-window
# prefill, full-context forward); the hybrid's scan blocks by 4 so that a
# chunk of 4 and a window of 16 block alike.
def _gpt2(**shape):
    return (dataclasses.replace(gpt2.GPT2Config.tiny(), dtype=F32, **shape),
            gpt2.gpt2_init, gpt2.gpt2_init_cache, gpt2.gpt2_prefill_chunk,
            gpt2.gpt2_prefill, gpt2.gpt2_forward)


FAMILIES = {
    "gpt2": _gpt2(),
    "llama": (dataclasses.replace(llama.LlamaConfig.tiny(), dtype=F32),
              llama.llama_init, llama.llama_init_cache,
              llama.llama_prefill_chunk, llama.llama_prefill,
              llama.llama_forward),
    "nemotron_h": (nemotron_h.NemotronHConfig.tiny(
        dtype=F32, param_dtype=F32, chunk_size=4),
        nemotron_h.nemotron_h_init, nemotron_h.nemotron_h_init_cache,
        nemotron_h.nemotron_h_prefill_chunk, nemotron_h.nemotron_h_prefill,
        nemotron_h.nemotron_h_forward),
    "granite_hybrid": (granite_hybrid.GraniteHybridConfig.tiny(
        dtype=F32, param_dtype=F32, chunk_size=4),
        granite_hybrid.granite_hybrid_init,
        granite_hybrid.granite_hybrid_init_cache,
        granite_hybrid.granite_hybrid_prefill_chunk,
        granite_hybrid.granite_hybrid_prefill,
        granite_hybrid.granite_hybrid_forward),
    "deepseek_v2": (deepseek_v2.DeepseekV2Config.tiny(
        dtype=F32, param_dtype=F32),
        deepseek_v2.deepseek_v2_init, deepseek_v2.deepseek_v2_init_cache,
        deepseek_v2.deepseek_v2_prefill_chunk,
        deepseek_v2.deepseek_v2_prefill, deepseek_v2.deepseek_v2_forward),
    "falcon_h1": (falcon_h1.FalconH1Config.tiny(
        dtype=F32, param_dtype=F32, chunk_size=4),
        falcon_h1.falcon_h1_init, falcon_h1.falcon_h1_init_cache,
        falcon_h1.falcon_h1_prefill_chunk, falcon_h1.falcon_h1_prefill,
        falcon_h1.falcon_h1_forward),
    # (one period, L L L F: this file compares states at 1e-5, and two
    # periods of float32 sums in another order pass that by a third)
    "qwen3_next": (qwen3_next.Qwen3NextConfig.tiny(
        dtype=F32, param_dtype=F32, scan_block=4, n_layer=4),
        qwen3_next.qwen3_next_init, qwen3_next.qwen3_next_init_cache,
        qwen3_next.qwen3_next_prefill_chunk, qwen3_next.qwen3_next_prefill,
        qwen3_next.qwen3_next_forward),
    # (window rings of 16 rows: this file's whole-window pass is ONE chunk
    # of MAX_PROMPT rows, which must divide a ring; the wraps are
    # tests/test_smallthinker.py's)
    "smallthinker": (smallthinker.SmallThinkerConfig.tiny(
        dtype=F32, param_dtype=F32, window=16),
        smallthinker.smallthinker_init, smallthinker.smallthinker_init_cache,
        smallthinker.smallthinker_prefill_chunk,
        smallthinker.smallthinker_prefill, smallthinker.smallthinker_forward),
    # (a query reads the 8 keys its indexer picks: prompts of up to
    # MAX_PROMPT tokens select from their ninth token on, across chunks)
    "keye_vl2": (keye_vl2.KeyeVL2Config.tiny(
        dtype=F32, param_dtype=F32, index_topk=8),
        keye_vl2.keye_vl2_init, keye_vl2.keye_vl2_init_cache,
        keye_vl2.keye_vl2_prefill_chunk, keye_vl2.keye_vl2_prefill,
        keye_vl2.keye_vl2_forward),
}
every_family = pytest.mark.parametrize("family", list(FAMILIES))
# GPT-2's merged, lane-padded rows at the head counts it is served with: XL's
# 25 heads of 64 (1600 columns padded to 1664, a lane tile shared by two
# heads and the last one half empty) and 124M's 12 of 64 (768, no pad); the
# tiny one's 4 of 16 are half a tile and stay unpadded; 3 heads of 48 (144
# columns padded to 256) do not divide a tile: both run the chunk's products
# over the whole row (``_lane_groups``' other arm).
# Model functions only, no engine.
FAMILIES.update({"gpt2-25x64": _gpt2(n_head=25, d_model=1600),
                 "gpt2-12x64": _gpt2(n_head=12, d_model=768),
                 "gpt2-3x48": _gpt2(n_head=3, d_model=144)})
every_row_width = pytest.mark.parametrize("family", list(FAMILIES))
CHUNK, MAX_PROMPT, CACHE_LEN, SLOTS = 4, 16, 24, 4


def _params(family):
    cfg, init = FAMILIES[family][:2]
    return init(jax.random.PRNGKey(31), cfg)


def _prompt(n, seed=0):
    return np.random.default_rng(seed).integers(1, 200, n).astype(np.int32)


def _used_cache(family, seed):
    """A cache every part of which holds another request's leavings."""
    cfg, _, init_cache = FAMILIES[family][:3]
    rng = np.random.default_rng(seed)
    return jax.tree.map(
        lambda a: jnp.asarray(rng.normal(size=a.shape), a.dtype),
        init_cache(cfg, SLOTS, CACHE_LEN))


def _in_chunks(family, params, cache, prompt, slot, chunk=CHUNK):
    """As the engine runs one request: ``ceil(len / chunk)`` calls of the
    chunk function with R = 1. -> (the last call's logits [V], the cache)."""
    cfg, chunk_fn = FAMILIES[family][0], FAMILIES[family][3]
    run = jax.jit(lambda c, t, at, n: chunk_fn(
        params, c, t, jnp.full(1, slot, jnp.int32), at, n, cfg,
        window=key_window(MAX_PROMPT, chunk)))
    for at in range(0, len(prompt), chunk):
        piece = prompt[at:at + chunk]
        toks = np.zeros((1, chunk), np.int32)
        toks[0, :len(piece)] = piece
        logits, cache = run(cache, jnp.asarray(toks),
                            jnp.full(1, at, jnp.int32),
                            jnp.full(1, len(piece), jnp.int32))
    return logits[0], cache


def _whole(family, params, cache, prompt, slot):
    """One pass over the whole padded window: the chunk function at
    C = MAX_PROMPT, which is the lane the engine compiled before."""
    cfg, chunk_fn = FAMILIES[family][0], FAMILIES[family][3]
    toks = np.zeros((1, MAX_PROMPT), np.int32)
    toks[0, :len(prompt)] = prompt
    logits, cache = chunk_fn(
        params, cache, jnp.asarray(toks), jnp.full(1, slot, jnp.int32),
        jnp.zeros(1, jnp.int32), jnp.full(1, len(prompt), jnp.int32), cfg,
        window=MAX_PROMPT)
    return logits[0], cache


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


@every_row_width
@pytest.mark.parametrize("n", [CHUNK - 1, CHUNK, CHUNK + 1, 2 * CHUNK,
                               MAX_PROMPT])
def test_chunks_leave_what_the_whole_window_leaves(family, n):
    params, prompt = _params(family), _prompt(n, seed=n)
    cache = _used_cache(family, seed=5)
    got, got_cache = _in_chunks(family, params, cache, prompt, slot=2)
    want, want_cache = _whole(family, params, cache, prompt, slot=2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    _assert_same_state(family, got_cache, want_cache, 2, n)
    # and the whole-window logits are the full-context forward's
    cfg, forward = FAMILIES[family][0], FAMILIES[family][5]
    full = forward(params, jnp.asarray(prompt)[None], cfg)[0, -1]
    np.testing.assert_allclose(np.asarray(got), np.asarray(full),
                               rtol=2e-4, atol=2e-4)


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
    cfg, _, init_cache = FAMILIES[family][:3]
    params, prompt = _params(family), _prompt(2 * CHUNK + 1, seed=3)
    used, _ = _in_chunks(family, params, _used_cache(family, seed=8),
                         prompt, slot=1)
    fresh, _ = _in_chunks(family, params,
                          init_cache(cfg, SLOTS, CACHE_LEN), prompt, slot=1)
    np.testing.assert_array_equal(np.asarray(used), np.asarray(fresh))


@every_row_width
def test_the_whole_window_form_is_a_loop_over_the_chunk_function(family):
    """``<family>_prefill`` on [R, P] (what the benchmark's reference check
    calls) cut into chunks gives what it gives in one chunk, rows of
    different lengths and a scratch row together, with ONE traced copy of
    the layers."""
    cfg, _, _, chunk_fn, whole, _ = FAMILIES[family]
    params = _params(family)
    lens = [MAX_PROMPT - 3, CHUNK, 1]
    toks = np.zeros((3, MAX_PROMPT), np.int32)
    for i, n in enumerate(lens):
        toks[i, :n] = _prompt(n, seed=10 + i)
    args = (jnp.asarray(toks), jnp.asarray([3, 0, 2], jnp.int32),
            jnp.asarray(lens, jnp.int32))
    cache = _used_cache(family, seed=2)
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


def _engine(family, **kw):
    kw.setdefault("max_batch", 2)
    return LLMEngine(model=family, config=FAMILIES[family][0], seed=31,
                     cache_len=CACHE_LEN, max_prompt_len=MAX_PROMPT,
                     prefill_chunk=CHUNK, **kw)


def _naive(family, params, prompt, n):
    cfg, forward = FAMILIES[family][0], FAMILIES[family][5]
    fwd = jax.jit(lambda t: forward(params, t, cfg))
    toks = [int(t) for t in prompt]
    for _ in range(n):
        padded = np.zeros((1, CACHE_LEN), np.int32)
        padded[0, :len(toks)] = toks
        toks.append(int(jnp.argmax(fwd(jnp.asarray(padded))[0,
                                                            len(toks) - 1])))
    return toks[len(prompt):]


@every_family
@pytest.mark.parametrize("n", [CHUNK + 2, 2 * CHUNK + 3])
def test_the_engine_serves_the_full_forward_across_chunk_boundaries(
        family, n):
    """Prompts that cross one and two chunk boundaries: greedy tokens of
    the deployed loop equal the full-context forward's, first token and
    decode steps after it (which read the rows every chunk wrote)."""
    eng = _engine(family)
    try:
        prompt = _prompt(n, seed=20 + n).tolist()
        assert eng.generate(prompt, 5) == _naive(family, eng.params,
                                                 prompt, 5)
        assert eng.llm_stats()["prefill_chunks"] == -(-n // CHUNK)
    finally:
        eng.shutdown_engine()


@every_family
def test_one_two_and_three_chunks_run_one_program_and_add_up(family):
    """``prefill_rows`` 2 bounds a turn's admissions and shapes nothing:
    two compiled programs whatever the chunk count, a chunk counted per
    execution and CHUNK lane tokens each."""
    eng = _engine(family, prefill_rows=2)
    try:
        lens = [CHUNK - 1, 2 * CHUNK, 2 * CHUNK + 1, MAX_PROMPT + 5]
        for i, n in enumerate(lens):
            assert len(eng.generate(_prompt(n, seed=i).tolist(), 2)) == 2
        st = eng.llm_stats()
    finally:
        eng.shutdown_engine()
    assert st["compiles"] == {"decode": 1, "prefill": 1}
    assert st["prefill_chunk"] == CHUNK and st["prefill_rows"] == 2
    assert st["prefill_rows_real"] == st["prefill_batches"] == 4
    assert st["prefill_chunks"] == 1 + 2 + 3 + MAX_PROMPT // CHUNK
    assert st["prefill_tokens_lane"] == st["prefill_chunks"] * CHUNK
    assert st["prefill_tokens_real"] == sum(lens[:3]) + MAX_PROMPT


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# model -> (its configuration as a cell runs it, that cell's deployment)
PUBLISHED = {
    "gpt2": ("gpt2-xl-1.5b", "gpt2xl_1chip_b8"),
    "falcon_h1": ("falcon-h1-34b-instruct", "falconh1_1chip_b32"),
    "nemotron_h": ("nemotron3-super-120b-a12b", "nemotron3s_1chip_b64"),
    "granite_hybrid": ("granite-4.0-h-small", "granite4hs_1chip_b32"),
    "deepseek_v2": ("deepseek-v2", "dsv2_1chip_b64"),
    "qwen3_next": ("qwen3-next-80b-a3b-instruct", "qwen3next_1chip_b64"),
    "smallthinker": ("smallthinker-21b-a3b-instruct",
                     "smallthinker_1chip_b48"),
    "exaone_moe": ("k-exaone-236b-a23b", "kexaone_1chip_b64"),
    "keye_vl2": ("keye-vl-2.0-30b-a3b", "keyevl2_1chip_b16"),
}


def _published(model):
    """(the program's configuration at the published widths, ``top_k`` and
    held counts, the deployment's engine settings) of ``model``'s cell."""
    config, deployment = (os.path.join(REPO, "benchmark", kind, name + ".json")
                          for kind, name in zip(("configs", "deployments"),
                                                PUBLISHED[model]))
    family = load_module(os.path.join(REPO, "benchmark", "families",
                                      model + ".py"))
    return (family.system_config(load_json(config)),
            load_json(deployment)["engine"])


def _case(model, chunk, sizes="published", **engine):
    """``sizes``: the cell's configuration and deployment (``engine``
    changes some of its settings), the family's own ``default``
    configuration, or this file's ``tiny`` one, which is built too."""
    return model, sizes, engine, chunk


@pytest.mark.parametrize("model, sizes, engine, chunk", [pytest.param(
    *case, id=name) for name, case in {
        # a token multiplies with every stored matrix: 256
        "dense-gpt2-xl": _case("gpt2", 256),
        "dense-falcon-h1": _case("falcon_h1", 256),
        "dense-llama": _case("llama", 256, "default", max_prompt_len=1024,
                             cache_len=1024),
        # ... with top_k of the held experts: one lane of their kernel
        "experts-nemotron-h": _case("nemotron_h", 512),
        "experts-granite": _case("granite_hybrid", 512),
        "experts-deepseek-v2": _case("deepseek_v2", 512),
        "experts-qwen3-next": _case("qwen3_next", 512),
        "experts-smallthinker": _case("smallthinker", 512),
        "experts-exaone-moe": _case("exaone_moe", 512),
        "experts-keye-vl2": _case("keye_vl2", 512),
        "experts-tiny": _case("granite_hybrid", 512, "tiny",
                              max_prompt_len=700, cache_len=1024),
        # no longer than the longest prompt
        "short-prompts-dense": _case("gpt2", 16, "tiny", max_prompt_len=16,
                                     cache_len=32),
        "short-prompts-experts": _case("granite_hybrid", 300,
                                       max_prompt_len=300),
        # whole chunks of 256 fit a slot's rows and of 512 do not: 256, and
        # the engine does not raise where it did not before (key_window of
        # 700 tokens in chunks of 256 is 768 rows)
        "cache-of-768-rows": _case("deepseek_v2", 256, max_prompt_len=700,
                                   cache_len=768),
        "cache-of-768-rows-tiny": _case("qwen3_next", 256, "tiny",
                                        max_prompt_len=700, cache_len=768),
        "cache-of-768-rows-dense": _case("gpt2", 256, max_prompt_len=768,
                                         cache_len=768),
        # ... and whole chunks that fit no way are refused
        "must-fit-the-cache": _case("gpt2", None, "tiny", cache_len=18,
                                    max_prompt_len=MAX_PROMPT + 1,
                                    prefill_chunk=CHUNK),
    }.items()])
def test_the_chunk_is_the_engines_by_rule_and_must_fit_the_cache(
        model, sizes, engine, chunk):
    """The fewest tokens, a power of two from 256 up, at which a chunk's
    operations reach the ridge for the weights it reads once, read from the
    stored leaves' shapes and the configuration's ``top_k`` of
    ``n_experts``: 256 for the families without experts, one lane of the
    experts' kernel for the five with them at their cells' own sizes; the
    longest prompt if shorter; what a slot's rows hold in whole chunks;
    and ``llm_stats()`` says what the rule read."""
    assert key_window(768, 256) == key_window(700, 256) == 768
    cfg = None
    if sizes == "published":
        cfg, deployment = _published(model)
        engine = {**deployment, **engine}
    elif sizes == "tiny":
        cfg = FAMILIES[model][0]
    cfg, init = _model_bundle(model, cfg, "full")[:2]
    longest, rows = engine["max_prompt_len"], engine["cache_len"]
    read = token_parameters(cfg, jax.eval_shape(
        lambda: init(jax.random.PRNGKey(0), cfg)))
    assert (read[1] < read[0]) == hasattr(cfg, "top_k")
    got = engine.get("prefill_chunk") or chunk_len(longest, *read, rows)
    if chunk is None:
        assert key_window(longest, got) > rows
        with pytest.raises(ValueError, match="must fit the cache"):
            LLMEngine(model=model, config=cfg, **engine)
        return
    assert got == chunk and key_window(longest, got) <= rows
    if sizes == "tiny":
        eng = LLMEngine(model=model, config=cfg, **engine)
        try:
            st = eng.llm_stats()
        finally:
            eng.shutdown_engine()
        assert st["prefill_chunk"] == chunk
        assert (st["params_stored"], st["params_a_token"]) == read
        assert read[0] == sum(a.size for a in jax.tree.leaves(eng.params))


def test_what_a_cache_counts_adds_up_in_llm_stats():
    """A family's cache may carry ``counted``, int32 scalars its programs
    add to in place (``prefill_expert_rows``): the engine reads them once
    an admission turn and says in ``llm_stats()`` by how much each rose,
    across a wrap of the 32 bits too; a family whose cache carries none
    reports no such key."""
    cfg = FAMILIES["granite_hybrid"][0]
    eng = _engine("granite_hybrid")
    try:
        lens = [CHUNK - 1, 2 * CHUNK + 1, MAX_PROMPT]
        prompts = [_prompt(n, seed=40 + n) for n in lens]
        for prompt in prompts[:2]:
            assert len(eng.generate(prompt.tolist(), 2)) == 2
        # the counter is about to wrap
        seen = eng.llm_stats()["prefill_expert_rows"]
        eng._cache["counted"]["prefill_expert_rows"] = jnp.int32(2 ** 31 - 5)
        eng._counted_seen = {"prefill_expert_rows": 2 ** 31 - 5}
        assert len(eng.generate(prompts[2].tolist(), 2)) == 2
        assert int(eng._cache["counted"]["prefill_expert_rows"]) < 0
        st = eng.llm_stats()
        # what the chunk function itself counts for the same prompts
        cache = granite_hybrid.granite_hybrid_init_cache(cfg, SLOTS,
                                                         CACHE_LEN)
        for prompt in prompts:
            for at in range(0, len(prompt), CHUNK):
                piece = prompt[at:at + CHUNK]
                toks = np.zeros((1, CHUNK), np.int32)
                toks[0, :len(piece)] = piece
                _, cache = granite_hybrid.granite_hybrid_prefill_chunk(
                    eng.params, cache, jnp.asarray(toks),
                    jnp.zeros(1, jnp.int32), jnp.full(1, at, jnp.int32),
                    jnp.full(1, len(piece), jnp.int32), cfg,
                    window=key_window(MAX_PROMPT, CHUNK))
        want = int(cache["counted"]["prefill_expert_rows"])
    finally:
        eng.shutdown_engine()
    assert st["prefill_chunks"] == 1 + 3 + 4
    assert st["prefill_tokens_real"] == sum(lens)
    assert 0 < seen < st["prefill_expert_rows"] == want
    assert want <= sum(lens) * cfg.top_k * len(cfg.layer_types)
    dense = _engine("gpt2")
    try:
        assert len(dense.generate(_prompt(6).tolist(), 2)) == 2
        assert "counted" not in dense._cache
        assert "prefill_expert_rows" not in dense.llm_stats()
    finally:
        dense.shutdown_engine()
