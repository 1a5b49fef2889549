"""How the system under test is built from a ``gpt2``-family configuration
file, and how its weights map onto the reference's names.

The configuration file holds the released ``config.json``'s keys
(``n_layer``, ``n_embd`` ...) and, under ``assumed``, what the file had to
set beyond them. Nothing here sets ``param_dtype`` or a path flag the file
does not name: how the program stores and runs the model is its business.

This file is also the one place that knows the family's arithmetic: the
kinds and the metric readers ask it for parameters, cache bytes, the bytes
of a decode step, the operations of a trained token and the attention
calls of a step, and hold no formula of their own. The formulas are
``benchmark/shapes.py``'s. ``README.md`` beside this file lists the whole
interface and who calls what.
"""

from __future__ import annotations

from benchmark import shapes

# The keys a configuration file of this family may carry under ``assumed``
# (beside notes whose key ends in ``why``).
ASSUMED = frozenset({"vocab_rows", "remat", "scan_layers", "use_flash"})

# Block weights: the system's name -> the released checkpoint's name.
BLOCK_NAMES = {
    "ln1_scale": "ln_1_g", "ln1_bias": "ln_1_b",
    "attn_qkv_w": "c_attn_w", "attn_qkv_b": "c_attn_b",
    "attn_out_w": "attn_c_proj_w", "attn_out_b": "attn_c_proj_b",
    "ln2_scale": "ln_2_g", "ln2_bias": "ln_2_b",
    "mlp_in_w": "c_fc_w", "mlp_in_b": "c_fc_b",
    "mlp_out_w": "mlp_c_proj_w", "mlp_out_b": "mlp_c_proj_b",
}


def shape(config: dict) -> dict:
    """The sizes the shape arithmetic needs, from the file."""
    a = config.get("assumed", {})
    return {
        "n_layer": config["n_layer"], "d_model": config["n_embd"],
        "n_head": config["n_head"],
        "head_dim": config["n_embd"] // config["n_head"],
        "vocab": config["vocab_size"],
        "vocab_rows": a.get("vocab_rows", config["vocab_size"]),
        "n_positions": config["n_positions"],
        "kv_dtype_bytes": 2,
    }


def param_count(config: dict) -> int:
    """Parameters as the system holds them (padding rows included)."""
    sh = shape(config)
    return shapes.gpt2_param_count(
        sh["n_layer"], sh["d_model"], sh["vocab_rows"], sh["n_positions"])


def cache_bytes(config: dict, slots: int, cache_len: int) -> float:
    """Bytes of a K/V cache of ``slots`` slots, by shape."""
    sh = shape(config)
    return slots * shapes.kv_bytes_per_slot(
        sh["n_layer"], sh["d_model"], cache_len, sh["kv_dtype_bytes"])


def decode_step_bytes(config: dict, weight_bytes: float, occupancy: float,
                      mean_context: float, counters: dict) -> float:
    """Bytes one decode step must read. A dense model reads every weight
    once whatever was decoded, so the window's ``counters`` (the engine's
    ``llm_stats()`` at ``open`` and ``close``) are not looked at."""
    sh = shape(config)
    return shapes.decode_step_bytes(
        weight_bytes, occupancy, mean_context, sh["n_layer"], sh["d_model"],
        sh["kv_dtype_bytes"])


def train_flops_per_token(config: dict) -> float:
    """Forward + backward operations one trained token requires."""
    sh = shape(config)
    return shapes.train_flops_per_token(
        sh["n_layer"], sh["d_model"], sh["vocab"], sh["n_positions"])


def attention_calls(config: dict, rows: int) -> tuple:
    """The causal attention a training step of ``rows`` rows requires:
    the ``(batch, heads, seq, head_dim)`` of one call, and how many
    forward (and as many backward) calls a step makes."""
    sh = shape(config)
    return (rows, sh["n_head"], sh["n_positions"], sh["head_dim"]), \
        sh["n_layer"]


def system_config(config: dict):
    from ray_tpu.models.gpt2 import GPT2Config

    a = config.get("assumed", {})
    kwargs = {"vocab_size": a.get("vocab_rows", config["vocab_size"]),
              "n_layer": config["n_layer"], "n_head": config["n_head"],
              "d_model": config["n_embd"], "seq_len": config["n_positions"]}
    for key in ("remat", "scan_layers", "use_flash"):
        if key in a:
            kwargs[key] = a[key]
    return GPT2Config(**kwargs)


def reference_kwargs(config: dict) -> dict:
    return {"n_head": config["n_head"],
            "eps": config["layer_norm_epsilon"]}


def to_reference(params, config: dict):
    """The system's parameter tree under the reference's names, in
    float32, without the vocabulary's padding rows (pure jax.numpy, so it
    can run inside a jit on sharded weights)."""
    import jax.numpy as jnp

    f32 = lambda x: x.astype(jnp.float32)
    return {
        "wte": f32(params["wte"][:config["vocab_size"]]),
        "wpe": f32(params["wpe"]),
        "h": {ref: f32(params["blocks"][sys_name])
              for sys_name, ref in BLOCK_NAMES.items()},
        "ln_f_g": f32(params["lnf_scale"]),
        "ln_f_b": f32(params["lnf_bias"]),
    }


def init_params(config: dict, seed: int):
    """Seeded weights exactly as the engine makes its own."""
    import jax

    from ray_tpu.models.gpt2 import gpt2_init

    return gpt2_init(jax.random.PRNGKey(seed), system_config(config))


def build_train(config: dict, mesh) -> dict:
    """The training path: ``make_init_fn`` / ``make_train_step`` on
    ``mesh``, as ``chip_smoke.py`` and the README drive them."""
    from ray_tpu.models.gpt2 import gpt2_init, gpt2_loss, gpt2_shardings
    from ray_tpu.train import make_init_fn, make_train_step
    from ray_tpu.train.train_step import batch_sharding

    cfg = system_config(config)
    shardings = gpt2_shardings(cfg, mesh)
    return {
        "init": make_init_fn(lambda r: gpt2_init(r, cfg), shardings, mesh),
        "step": make_train_step(
            lambda p, b: gpt2_loss(p, b, cfg), shardings, mesh),
        "batch_sharding": batch_sharding(mesh),
        "row_tokens": cfg.seq_len + 1,
        "params_of": lambda state: state["params"],
    }


def engine_bind(config: dict, engine: dict, seed: int) -> dict:
    """Arguments of ``LLMEngine`` for this configuration."""
    return {"model": "gpt2", "config": system_config(config),
            "seed": seed, **engine}


def serve_logits(config: dict, params, prompts, lengths, follow, slots: int,
                 cache_len: int):
    """Logits of the serving path's own functions: ``gpt2_prefill`` of
    the padded ``prompts`` [R, P], then one ``gpt2_decode_step`` per column
    of ``follow`` [R, N] through a fresh cache. -> [R, 1 + N, V]."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.gpt2 import (gpt2_decode_step, gpt2_init_cache,
                                     gpt2_prefill)

    cfg = system_config(config)
    r = prompts.shape[0]
    cache = gpt2_init_cache(cfg, slots, cache_len)
    slot_idx = jnp.arange(r, dtype=jnp.int32)
    prefill = jax.jit(lambda p, c, t, s, n: gpt2_prefill(p, c, t, s, n, cfg))
    step = jax.jit(lambda p, c, t, n: gpt2_decode_step(p, c, t, n, cfg),
                   donate_argnums=(1,))
    logits, cache = prefill(params, cache, prompts, slot_idx, lengths)
    out = [logits]
    pad = slots - r
    for i in range(follow.shape[1]):
        toks = jnp.concatenate(
            [follow[:, i], jnp.zeros((pad,), jnp.int32)])
        pos = jnp.concatenate(
            [lengths + i, jnp.zeros((pad,), jnp.int32)])
        logits, cache = step(params, cache, toks, pos)
        out.append(logits[:r])
    return jnp.stack(out, axis=1)
