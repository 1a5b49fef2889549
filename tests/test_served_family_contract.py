"""The six contracts every served family's own file used to spell out for
itself, once each, over the one table of ``tests/served_families.py``: the
published sizes and what the tiny preset keeps of them, the types the
programs compute in against what the benchmark's configuration file states,
the scopes the benchmark's readers sum over, the forward pass against the
family's plain reference, the engine against that reference's greedy tokens,
and the tiny preset's engine beside ``_model_bundle``'s error text. What is
one family's alone (the YaRN angles, the eight shares, a router's gating,
the draft's verify step) is in that family's file.
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.serve.llm_engine import LLMEngine, _model_bundle
from served_families import (BF16, FAMILIES, abstract_programs,
                             contract_params, contract_tokens, contract_want,
                             families_with, forward_fn, greedy)


@families_with("sizes")
def test_the_published_sizes_and_the_tiny_preset(model):
    """The published configuration's sizes, what the tiny preset keeps of
    what makes the family, and the sizes its configuration refuses."""
    FAMILIES[model].sizes(FAMILIES[model])


@families_with("types")
@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_the_programs_hold_the_types_the_file_states(model, program):
    """``computes_in`` of the benchmark's configuration file, held by the
    programs' own types: weights and products in bfloat16 and nothing
    narrower anywhere, float32 beside them (routers, softmax, norms'
    statistics, a state in and out: the row says which)."""
    row = FAMILIES[model]
    stated = row.family.system_config(row.CONFIG)
    assert "bfloat16" in row.CONFIG["computes_in"]
    want = tuple(BF16 if name in ("param_dtype", "dtype") else jnp.float32
                 for name in row.stated)
    assert tuple(getattr(stated, name) for name in row.stated) == want
    cfg = row.config.tiny()  # the same defaults, at a CPU's size
    assert tuple(getattr(cfg, name) for name in row.stated) == want
    fn, args = abstract_programs(row, cfg)[program]
    text = str(jax.make_jaxpr(fn)(*args))
    types = set(re.findall(r"\b([a-z]+[0-9]+[a-z0-9_]*)\[", text))
    assert {"bf16", "f32"} <= types
    assert not {t for t in types if t.startswith(("f8", "f16", "i8", "u8",
                                                  "i4", "u4"))}, types
    out = jax.eval_shape(fn, *args)
    assert out[0].dtype == jnp.float32
    row.types(row, program, args, out, text)


@families_with("scopes")
def test_the_programs_name_the_scopes_the_readers_read(model):
    row = FAMILIES[model]
    row.scopes(row, {
        name: jax.jit(fn).lower(*args).as_text(debug_info=True)
        for name, (fn, args) in abstract_programs(
            row, row.config.tiny()).items()})


@families_with("agrees")
def test_forward_agrees_with_the_reference(model):
    row, params = FAMILIES[model], contract_params(model)
    fwd = forward_fn(model, row.cfg)
    row.agrees(row, lambda tokens: fwd(params, tokens),
               contract_tokens(model), contract_want(model))


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


@families_with("serves")
def test_the_engine_serves_the_references_greedy_tokens(model, runtime):
    """``LLMEngine(model=...)`` at the tiny preset's sizes in float32,
    through ``serve.run`` / ``handle.stream`` (or on the normal path, where
    the row says so): token for token the reference's greedy choice, two
    compiled programs whatever the lengths, and what the model says of
    itself in ``llm_stats()``."""
    import ray_tpu

    row = FAMILIES[model]
    case = row.serves
    cfg = dataclasses.replace(row.cfg, **case.get("cfg", {}))
    bind = dict(model=model, config=cfg, seed=case["seed"], **case["engine"])
    if case.get("through") == "engine":
        engine = LLMEngine(**bind)
        generate, params = engine.generate, engine.params
        stats, stop = engine.llm_stats, engine.shutdown_engine
    else:
        dep = runtime.deployment(name="llm",
                                 max_concurrent_queries=16)(LLMEngine)
        handle = runtime.run(dep.bind(**bind))
        generate = lambda prompt, n: [
            t for chunk in handle.stream(prompt, n) for t in chunk]
        params = row.init(jax.random.PRNGKey(case["seed"]), cfg)
        stats = lambda: ray_tpu.get(handle.llm_stats.remote(), timeout=30)
        stop = lambda: ray_tpu.get(handle.shutdown_engine.remote(),
                                   timeout=30)
    try:
        reference = row.reference_forward(params, cfg)
        prompts = case["prompts"]
        for prompt in prompts(cfg) if callable(prompts) else prompts:
            served = generate(prompt, case["new"])
            assert served == greedy(reference, prompt, case["new"],
                                    case["width"]), len(prompt)
            if "distinct" in case:  # no fixed point: it follows its context
                assert len(set(served)) > case["distinct"]
        said = stats()
        assert said["compiles"] == {"decode": 1, "prefill": 1}
        case["stats"](said)
    finally:
        stop()


@families_with("preset_engine")
def test_the_tiny_preset_engine_and_the_bundles_error_text(model):
    """``preset="tiny"`` builds the family's own configuration and serves;
    the bundle is the module's functions; a name the engine does not serve
    is refused with every name it does."""
    row = FAMILIES[model]
    case = row.preset_engine
    cfg, init, init_cache, chunk, step, *verify = _model_bundle(
        model, None, "tiny")
    assert cfg == row.config.tiny()
    # (GPT-2's step is its counted one: the rows its rings' kernel reads)
    assert [fn.__name__.startswith(f"{model}_{part}") for fn, part in zip(
        (init, init_cache, chunk, step, *verify),
        ("init", "init_cache", "prefill_chunk", "decode_step",
         "verify_step"))] == [True] * (4 + len(verify))
    assert all(fn.__module__ == row.module.__name__
               for fn in (init, init_cache, chunk, step, *verify))
    with pytest.raises(ValueError) as err:
        _model_bundle(model + "2", None, "tiny")
    assert "|".join(FAMILIES) in str(err.value)
    eng = LLMEngine(model=model, preset="tiny", **case["engine"])
    try:
        prompt, n = case.get("prompt", ([1, 2, 3], 4))
        assert len(eng.generate(prompt, n)) == n
        if "holds" in case:
            case["holds"](eng)
    finally:
        eng.shutdown_engine()
