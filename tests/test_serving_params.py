"""The serving engine stores its weights as its programs read them (PR 29).

A family says, beside its two serving programs, in which type they consume
each parameter leaf (its config's ``serving_dtypes``); ``LLMEngine`` stores
each leaf so, once, and keeps nothing of what it was cast from. Held here,
on the CPU at toy widths: the stored leaves give both programs bit for bit what
the float32 leaves gave them; no program casts a parameter it was handed
(so a weight that joins a step without joining its family's rule fails
here, not on the chip); a family that stores what it multiplies with gets
its own arrays back; and ``llm_stats()`` says the bytes held by type.
"""

import gc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.serve.llm_engine import LLMEngine, _model_bundle, _stored_params
from served_families import FAMILIES, PROMPT

# The configurations are the table's ``Family.serving``: the tiny presets
# (bfloat16 programs), but GPT-2's and Llama's at sizes no other test uses,
# so that `jax.live_arrays()` can be asked for a float32 array of a weight's
# shape (the workers run many files a process). Not a sequence of 40:
# Qwen3-Next's tiny preset has a float32 [40, 48] shared expert, which
# `tests/test_qwen3_next.py` keeps alive in its worker.
NORMS = {"gpt2": {"ln1_scale", "ln1_bias", "ln2_scale", "ln2_bias",
                  "lnf_scale", "lnf_bias"},
         "llama": {"attn_norm", "mlp_norm", "final_norm"}}
MAX_BATCH, CACHE_LEN, PROMPT_LEN, ROWS = 2, 32, 8, 2


def _family(name):
    """(cfg, init, init_cache, prefill_chunk, decode), as the engine gets
    them."""
    return _model_bundle(name, FAMILIES[name].serving, "tiny")


def _stored(params, cfg):
    """What the engine stores, without letting go of ``params``."""
    return jax.tree.map(lambda x, dt: x.astype(dt), params,
                        cfg.serving_dtypes(params))


def _names(tree):
    return {path[-1].key: leaf
            for path, leaf in jax.tree_util.tree_leaves_with_path(tree)}


def _lane(prompt):
    """The engine's prefill arguments for one request in slot 0: its one
    chunk (tokens, slot, start, real tokens)."""
    toks = np.zeros((1, PROMPT_LEN), np.int32)
    toks[0, :len(prompt)] = prompt
    return (jnp.asarray(toks), jnp.zeros(1, jnp.int32),
            jnp.zeros(1, jnp.int32), jnp.full(1, len(prompt), jnp.int32))


def _run(name, params, steps):
    """Prefill, then ``steps`` greedy decode steps, in the engine's shapes:
    every logits array and every cache on the way, and slot 0's tokens."""
    cfg, _, init_cache, prefill, decode = _family(name)
    cache = init_cache(cfg, MAX_BATCH + 1, CACHE_LEN)
    logits, cache = prefill(params, cache, *_lane(PROMPT), cfg)
    seen = [logits, cache]
    tokens = [int(jnp.argmax(logits[0]))]
    cur = np.zeros(MAX_BATCH + 1, np.int32)
    pos = np.zeros(MAX_BATCH + 1, np.int32)
    cur[0], pos[0] = tokens[0], len(PROMPT)
    for _ in range(steps):
        logits, cache = decode(params, cache, jnp.asarray(cur),
                               jnp.asarray(pos), cfg)[:2]
        seen += [logits, cache]
        tokens.append(int(jnp.argmax(logits[0])))
        cur[0], pos[0] = tokens[-1], pos[0] + 1
    return seen, tokens


@pytest.mark.parametrize("name", ["gpt2", "llama"])
def test_stored_parameters_give_the_programs_the_same_bits(name):
    """bfloat16 programs over float32 ``param_dtype``, as the GPT-2 cells
    run: logits and cache of the prefill and of three decode steps on the
    stored parameters are, bit for bit, those on the float32 parameters
    they were made from; and an engine built from a seed serves what the
    plain loop over that seed's float32 weights chooses."""
    cfg, init, *_ = _family(name)
    assert cfg.dtype == jnp.bfloat16 and cfg.param_dtype == jnp.float32
    params = init(jax.random.PRNGKey(7), cfg)
    stored = _stored(params, cfg)
    assert {str(x.dtype) for x in jax.tree.leaves(stored)} \
        == {"bfloat16", "float32"}
    want, tokens = _run(name, params, 3)
    got, _ = _run(name, stored, 3)
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
    eng = LLMEngine(model=name, config=cfg, seed=7, max_batch=MAX_BATCH,
                    cache_len=CACHE_LEN, max_prompt_len=PROMPT_LEN,
                    prefill_rows=ROWS)
    try:
        assert eng.generate(PROMPT, 4) == tokens
    finally:
        eng.shutdown_engine()


# -- the family's rule is tied to its programs ---------------------------------


def _casts_of_inputs(jaxpr, handed):
    """Shapes of the variables among ``handed`` (inputs of ``jaxpr``) that
    a ``convert_element_type`` takes as its operand, here or in what it
    calls. An input stays itself through a call's arguments and through a
    layer loop's ``xs``, where the body sees one layer's slice of it."""
    found = []
    for eqn in jaxpr.eqns:
        ins = [v for v in eqn.invars if not hasattr(v, "val")]
        if eqn.primitive.name == "convert_element_type":
            found += [v.aval.shape for v in ins if v in handed]
        for value in eqn.params.values():
            inner = getattr(value, "jaxpr", value)
            if hasattr(inner, "eqns"):
                assert len(inner.invars) == len(eqn.invars), eqn.primitive
                found += _casts_of_inputs(inner, {
                    b for a, b in zip(eqn.invars, inner.invars)
                    if not hasattr(a, "val") and a in handed})
    return found


def _programs(name, params):
    """The two programs as the engine traces them, over ``params``: the
    closed jaxprs and, of each, the input variables that are parameters."""
    cfg, _, init_cache, prefill, decode = _family(name)
    cache = init_cache(cfg, MAX_BATCH + 1, CACHE_LEN)
    n = len(jax.tree.leaves(params))
    i32 = jnp.zeros(MAX_BATCH + 1, jnp.int32)
    for closed in (
            jax.make_jaxpr(lambda p, c, t, at: decode(p, c, t, at, cfg)[:2])(
                params, cache, i32, i32),
            jax.make_jaxpr(
                lambda p, c, t, s, at, m: prefill(p, c, t, s, at, m, cfg))(
                params, cache, *_lane(PROMPT))):
        yield closed.jaxpr, set(closed.jaxpr.invars[:n])


@pytest.mark.parametrize("name", ["gpt2", "llama"])
def test_no_program_casts_a_parameter_the_engine_stored(name):
    """In both programs traced on the engine's parameters no
    ``convert_element_type`` takes a whole parameter (or a layer's slice
    of one): what the family's rule stores is what the program reads. On
    the float32 tree the same reading finds every leaf of the rule, so it
    would find one the rule forgot. The norms stay float32."""
    cfg, init, *_ = _family(name)
    eng = LLMEngine(model=name, config=cfg, seed=3, max_batch=MAX_BATCH,
                    cache_len=CACHE_LEN, max_prompt_len=PROMPT_LEN,
                    prefill_rows=ROWS)
    try:
        stored = eng.params
    finally:
        eng.shutdown_engine()
    made = init(jax.random.PRNGKey(3), cfg)
    for jaxpr, handed in _programs(name, stored):
        assert _casts_of_inputs(jaxpr, handed) == []
    cast = {k: v for k, v in _names(stored).items() if k not in NORMS[name]}
    assert all(v.dtype == jnp.bfloat16 for v in cast.values())
    assert all(v.dtype == jnp.float32 for k, v in _names(stored).items()
               if k in NORMS[name])
    as_read = {x.shape[1:] if path[0].key == "blocks" else x.shape
               for path, x in jax.tree_util.tree_leaves_with_path(made)
               if path[-1].key in cast}  # the loop's body sees one layer
    for jaxpr, handed in _programs(name, made):
        assert set(_casts_of_inputs(jaxpr, handed)) == as_read


@pytest.mark.parametrize("name", ["nemotron_h", "smallthinker",
                                  "exaone_moe", "keye_vl2"])
def test_a_family_that_stores_what_it_multiplies_with_gets_its_arrays_back(
        name):
    """The family is handed back leaf for leaf: the very arrays its
    ``init`` returned, none cast, none copied."""
    cfg, init, *_ = _family(name)
    made = []

    def recording(key, cfg):
        params = init(key, cfg)
        made.extend(jax.tree.leaves(params))
        return params

    stored, held = _stored_params(recording, jax.random.PRNGKey(0), cfg)
    leaves = jax.tree.leaves(stored)
    assert len(leaves) == len(made) > 20
    assert all(a is b for a, b in zip(made, leaves))
    assert held == {"bfloat16": sum(x.nbytes for x in made)}


# -- the counter ---------------------------------------------------------------


def test_llm_stats_says_the_parameter_bytes_by_stored_type():
    """Two bytes for each element of a leaf the programs cast, four for
    each of a norm's; and once the engine stands, no float32 array of a
    weight's shape is alive in the process: one copy of the weights."""
    cfg, init, *_ = _family("gpt2")
    shapes = jax.eval_shape(lambda: init(jax.random.PRNGKey(0), cfg))
    cast = {k: v for k, v in _names(shapes).items() if k not in NORMS["gpt2"]}
    norms = {k: v for k, v in _names(shapes).items() if k in NORMS["gpt2"]}
    assert len(cast) == 10 and len(norms) == 6
    eng = LLMEngine(model="gpt2", config=cfg, seed=1, max_batch=MAX_BATCH,
                    cache_len=CACHE_LEN, max_prompt_len=PROMPT_LEN)
    try:
        assert eng.llm_stats()["param_bytes"] == {
            "bfloat16": 2 * sum(v.size for v in cast.values()),
            "float32": 4 * sum(v.size for v in norms.values())}
        assert sum(eng.llm_stats()["param_bytes"].values()) \
            == sum(x.nbytes for x in jax.tree.leaves(eng.params))
        gc.collect()
        weights = {v.shape for v in cast.values()} \
            - {v.shape for v in norms.values()}
        assert len(weights) == 8
        alive = [a.shape for a in jax.live_arrays()
                 if a.dtype == jnp.float32 and a.shape in weights]
        assert alive == []
    finally:
        eng.shutdown_engine()
