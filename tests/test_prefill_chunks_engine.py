"""The engine on its chunk program (PR 31; split off
``tests/test_prefill_chunks.py`` in PR 64 so that neither file is the
whole run's longest): for every family it serves the full-context
forward's tokens across chunk boundaries from its two compiled programs and
counts its chunks; the chunk length is the engine's by rule and must fit
the cache; what a cache counts adds up in ``llm_stats()``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import granite_hybrid
from ray_tpu.models.prefill import chunk_len, key_window, token_parameters
from ray_tpu.serve.llm_engine import LLMEngine, _model_bundle
from served_families import FAMILIES, generated_alone
from test_prefill_chunks import (CACHE_LEN, CHUNK, MAX_PROMPT, SLOTS,
                                 _prompt, every_family)


def _engine(family, **kw):
    kw.setdefault("max_batch", 2)
    return LLMEngine(model=family, config=FAMILIES[family].chunked, seed=31,
                     cache_len=CACHE_LEN, max_prompt_len=MAX_PROMPT,
                     prefill_chunk=CHUNK, **kw)


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
        assert eng.generate(prompt, 5) == generated_alone(
            family, eng.params, prompt, 5, cfg=FAMILIES[family].chunked,
            width=CACHE_LEN)
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
        cfg, deployment = FAMILIES[model].cell()
        engine = {**deployment, **engine}
    elif sizes == "tiny":
        cfg = FAMILIES[model].chunked
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
    cfg = FAMILIES["granite_hybrid"].chunked
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
