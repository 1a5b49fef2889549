"""The family files: every count the kinds and readers ask a family for.

``families/gpt2.py`` is fed the benchmark's two configuration files and
held to the figures the old call sites gave (the formulas stayed in
``benchmark/shapes.py``; the kinds and readers used to call them with
GPT-2's keys themselves). Every family file that is found, the toy family
added as files and one that no test names among them, is held to the whole
interface that ``benchmark/families/README.md`` lists, and its
``param_count`` and ``cache_bytes`` to the arrays the system really makes.
The toy family's plain reference is held to ``ray_tpu/models/llama.py`` in
float32."""

import dataclasses
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import benchmark_toy
from benchmark import shapes, trace
from benchmark.loading import load_json, load_module

REPO = benchmark_toy.REPO
INTERFACE = ["ASSUMED", "shape", "system_config", "reference_kwargs",
             "to_reference", "init_params", "build_train", "engine_bind",
             "serve_logits", "param_count", "cache_bytes",
             "decode_step_bytes", "train_flops_per_token", "attention_calls"]
REFERENCE = ["forward", "loss_and_grad_norm"]


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    return benchmark_toy.make_root(str(tmp_path_factory.mktemp("families")))


def family_of(root, config_name):
    config = load_json(os.path.join(root, "benchmark", "configs",
                                    config_name + ".json"))
    return config, load_module(os.path.join(
        root, "benchmark", "families", config["family"] + ".py"))


@pytest.mark.parametrize("name, params, gflop", [
    ("gpt2-124m", 124_439_808, 0.798), ("gpt2-xl-1.5b", 1_557_611_200, 9.80)])
def test_gpt2_counts_from_the_configuration_files(name, params, gflop):
    config, family = family_of(REPO, name)
    # As held: the published sizes plus the 47 padding rows of the table.
    assert family.param_count(config) \
        == params + 47 * config["n_embd"] \
        == shapes.gpt2_param_count(config["n_layer"], config["n_embd"],
                                   50304, config["n_positions"])
    assert family.param_count({**config, "assumed": {}}) == params
    # Required work is counted at the published vocabulary.
    assert family.train_flops_per_token(config) / 1e9 == pytest.approx(
        gflop, abs=0.005)
    assert family.train_flops_per_token(config) \
        == shapes.train_flops_per_token(
            config["n_layer"], config["n_embd"], 50257, 1024)
    heads = config["n_head"]
    assert family.attention_calls(config, 16) == (
        (16, heads, 1024, 64), config["n_layer"])
    assert family.shape(config)["vocab"] == 50257
    assert family.shape(config)["n_positions"] == 1024


def test_gpt2_cache_and_decode_bytes_as_the_old_call_sites_gave_them():
    config, family = family_of(REPO, "gpt2-xl-1.5b")
    slot = family.cache_bytes(config, 1, 1024)
    assert slot == 2 * 48 * 1024 * 1600 * 2
    assert slot / 1e6 == pytest.approx(315, abs=1)
    # serve_common._weight_bytes: (max_batch + 1) slots of cache_len rows
    assert family.cache_bytes(config, 9, 1024) \
        == 9 * shapes.kv_bytes_per_slot(48, 1600, 1024, 2)
    # decode_step_roofline: float32 weights, 8 slots at 600 rows of context
    weights = 4.0 * family.param_count(config)
    assert family.decode_step_bytes(config, weights, 8.0, 600.0, {}) \
        == shapes.decode_step_bytes(weights, 8.0, 600.0, 48, 1600, 2) \
        == weights + 8 * 600 * 2 * 48 * 1600 * 2


def family_files(*dirs):
    return sorted({f[:-3] for d in dirs for f in os.listdir(d)
                   if f.endswith(".py")})


def hold_to_the_interface(root, name):
    families = os.path.join(root, "benchmark", "families")
    family = load_module(os.path.join(families, name + ".py"))
    reference = load_module(os.path.join(
        root, "benchmark", "reference", name + ".py"))
    assert [n for n in INTERFACE if not hasattr(family, n)] == [], name
    assert [n for n in REFERENCE if not hasattr(reference, n)] == [], name
    assert isinstance(family.ASSUMED, frozenset), name


# Whatever family files the tree holds when the tests are collected: the
# benchmark's own and the toy's. A PR that adds ``families/<new>.py`` gets a
# case here without an edit; none is pinned.
@pytest.mark.parametrize("name", family_files(
    os.path.join(REPO, "benchmark", "families"),
    os.path.join(benchmark_toy.HERE, "toy", "families")))
def test_every_family_file_defines_the_whole_interface(toy_root, name):
    hold_to_the_interface(toy_root, name)


def test_a_family_that_no_test_names_is_held_like_the_rest(tmp_path):
    """What the next ``model_config`` PR does, done here: the files of one
    more family (``third_toy``) dropped into the copy. Every file then found
    in ``families/`` is held to the interface, the new one too, and nothing
    here lists what may be found."""
    root = benchmark_toy.make_root(str(tmp_path), third_family=True)
    found = family_files(os.path.join(root, "benchmark", "families"))
    assert {"gpt2", "llama_toy", "third_toy"} <= set(found)
    for name in found:
        hold_to_the_interface(root, name)
    config, family = family_of(root, "third-toy")
    assert family.__file__.endswith("third_toy.py")
    assert family.param_count(config) == 155_968


def test_the_readme_lists_the_whole_interface():
    # the list the next family's writer reads names every one of them
    with open(os.path.join(REPO, "benchmark", "families", "README.md")) as f:
        readme = f.read()
    assert [n for n in INTERFACE + REFERENCE if f"`{n}" not in readme] == []


def test_the_window_counters_reach_the_family_as_two_stats():
    """``decode_step_bytes``'s last argument: the reader hands over the
    engine's ``llm_stats()`` at the window's two ends, whole, so that a
    sparse family can take a counter's difference over the steps'. Neither
    family here reads it (both are dense); this stands in for one that
    does: it counts ``experts_hit`` a step, a counter the reader has never
    heard of, at a million bytes an expert."""
    ms = 1_000_000

    def sparse_bytes(config, weight_bytes, occupancy, mean_context, counters):
        a, b = counters["open"], counters["close"]
        hit = (b["experts_hit"] - a["experts_hit"]) / (b["steps"] - a["steps"])
        return 1e6 * hit + occupancy * mean_context

    said = []
    run = types.SimpleNamespace(
        trace=trace.from_json(os.path.join(
            REPO, "benchmark", "metrics", "fixtures",
            "decode_three_steps.json")),
        params={"device_programs": {"decode": "jit_step_fn"}},
        raw={"requests": [{"prompt_len": 100, "chunk_tokens": [1] * 5,
                           "chunk_ns": [10 * ms, 20 * ms, 30 * ms, 40 * ms,
                                        50 * ms]}],
             "weight_bytes": 4e9},
        counters={"open": {"steps": 5, "occupancy_sum": 0, "experts_hit": 40},
                  "close": {"steps": 15, "occupancy_sum": 30,
                            "experts_hit": 520}},
        window_ns=(0, 600 * ms), device_kind="TPU v5 lite", config={},
        family=types.SimpleNamespace(decode_step_bytes=sparse_bytes),
        say=lambda event, **f: said.append((event, f)))
    reader = load_module(os.path.join(
        REPO, "benchmark", "metrics", "decode_step_roofline.py"))
    # 48 experts a step, 3 slots at 102.5 rows; the decode program is busy
    # 0.08 s an execution in the fixture.
    need = 1e6 * 48 + 3 * 102.5
    assert reader.read(run) == pytest.approx(100 * need / 819e9 / 0.08)
    assert said[0][1]["bytes_per_step"] == need


@pytest.mark.parametrize("name", ["gpt2-toy", "llama-toy"])
def test_counts_agree_with_the_arrays_the_system_makes(toy_root, name):
    """``engine_memory`` divides what the engine put on the device, less
    the cache by shape, by the parameters by shape: with the engine's own
    arrays (float32 weights, as ``*_init`` makes them) it must read 4
    bytes a parameter exactly, for a cache of every K/V head (gpt2) and
    for a grouped-query one (llama_toy: 2 of 4 heads)."""
    config, family = family_of(toy_root, name)
    common = load_module(os.path.join(
        toy_root, "benchmark", "kinds", "serve_common.py"))
    engine = load_json(os.path.join(
        toy_root, "benchmark", "deployments", "toy_engine.json"))["engine"]
    bind = family.engine_bind(config, engine, 3)
    from ray_tpu.serve.llm_engine import _model_bundle

    cfg, init, init_cache, _, _ = _model_bundle(
        bind["model"], bind["config"], "tiny")
    params = init(jax.random.PRNGKey(3), cfg)
    cache = init_cache(cfg, engine["max_batch"] + 1, engine["cache_len"])
    n_params = sum(x.size for x in jax.tree.leaves(params))
    cache_bytes = sum(x.nbytes for x in jax.tree.leaves(cache))
    assert family.param_count(config) == n_params
    assert family.cache_bytes(config, engine["max_batch"] + 1,
                              engine["cache_len"]) == cache_bytes
    said = []
    run = types.SimpleNamespace(
        family=family, config=config,
        say=lambda event, **f: said.append((event, f)))
    held = sum(x.nbytes for x in jax.tree.leaves(params)) + cache_bytes
    assert common._weight_bytes(run, held, engine) == 4.0 * n_params
    assert said == [("engine_memory", {
        "engine_bytes": held, "cache_bytes": cache_bytes,
        "bytes_per_param_measured": 4.0, "bytes_per_param": 4})]
    # and what a decode step reads beyond the weights is that cache's rows
    rows = family.decode_step_bytes(config, 0.0, 2.0, 10.0, {})
    assert rows == 2 * 10 * cache_bytes / (
        (engine["max_batch"] + 1) * engine["cache_len"])


def test_the_toy_family_counts_its_own_shape(toy_root):
    config, family = family_of(toy_root, "llama-toy")
    # L=2, d=64, 4 heads of 16, 2 K/V heads, ff 256, V=256, T=64, by hand:
    # a block's matrices 2*64*64 (q, o) + 2*64*32 (k, v) + 3*64*256.
    per_layer = 8192 + 4096 + 49152
    assert family.param_count(config) \
        == 2 * 256 * 64 + 2 * (per_layer + 128) + 64 == 155_968
    assert family.train_flops_per_token(config) \
        == 6.0 * (2 * per_layer + 64 * 256) + 6.0 * 2 * 64 * 64
    assert family.attention_calls(config, 4) == ((4, 4, 64, 16), 2)
    assert family.shape(config) == {"vocab": 256, "n_positions": 64}
    # a file that states sizes the program does not run is refused
    with pytest.raises(ValueError, match="MLP of 256"):
        family.system_config({**config, "intermediate_size": 172})


def test_the_toy_reference_agrees_with_the_system_in_float32(toy_root):
    """Loss, gradient norm and every logit of ``reference/llama_toy.py``
    against ``models/llama.py`` with its compute type set to float32: two
    independent writings of one architecture (rotary embedding, grouped
    queries, SwiGLU, RMSNorm, untied head) must agree to rounding."""
    from ray_tpu.models.llama import (LlamaConfig, llama_forward, llama_init,
                                      llama_loss)

    config, family = family_of(toy_root, "llama-toy")
    reference = load_module(os.path.join(
        toy_root, "benchmark", "reference", "llama_toy.py"))
    cfg = dataclasses.replace(family.system_config(config),
                              dtype=jnp.float32, remat=False,
                              use_flash=False)
    assert dataclasses.replace(cfg, dtype=jnp.bfloat16, remat="dots",
                               use_flash=None) == LlamaConfig.tiny()
    params = llama_init(jax.random.PRNGKey(5), cfg)
    # The norm scales start at one; move every weight, or a dropped or
    # swapped scale would go unseen.
    keys = iter(jax.random.split(jax.random.PRNGKey(6), 32))
    params = jax.tree.map(
        lambda x: x + 0.05 * jax.random.normal(next(keys), x.shape, x.dtype),
        params)
    ref = family.to_reference(params, config)
    assert set(family.BLOCK_NAMES) == set(params["blocks"])
    assert sum(x.size for x in jax.tree.leaves(ref)) \
        == sum(x.size for x in jax.tree.leaves(params))
    kwargs = family.reference_kwargs(config)
    tokens = jnp.asarray(np.random.default_rng(7).integers(
        0, 256, (3, 33), dtype=np.int32))
    want = reference.forward(ref, tokens[:, :-1], **kwargs)
    got = llama_forward(params, tokens[:, :-1], cfg)
    assert float(jnp.abs(got - want).max() / jnp.abs(want).max()) < 1e-5
    ref_loss, ref_gnorm = reference.loss_and_grad_norm(ref, tokens, **kwargs)
    sys_loss, grads = jax.value_and_grad(
        lambda p: llama_loss(p, {"tokens": tokens}, cfg))(params)
    sys_gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                             for g in jax.tree.leaves(grads)))
    assert float(sys_loss) == pytest.approx(float(ref_loss), rel=1e-5)
    assert float(sys_gnorm) == pytest.approx(float(ref_gnorm), rel=1e-4)
    again, _ = reference.loss_and_grad_norm(ref, tokens, remat=True, **kwargs)
    assert float(again) == pytest.approx(float(ref_loss), rel=1e-6)
    # The control: the reference without its rotary embedding is another
    # model, and the comparison says so.
    flat = reference.forward(ref, tokens[:, :-1],
                             **{**kwargs, "theta": 1e30})
    assert float(jnp.abs(flat - want).max() / jnp.abs(want).max()) > 1e-2
