"""A second family, added AS A FILE: how the system under test is built
from a ``llama_toy`` configuration file (``ray_tpu/models/llama.py`` at toy
sizes), how its weights map onto the reference's names, and the family's
arithmetic. It exists to prove that the harness takes a family of another
shape (grouped-query K/V, no position table, untied head) with no edit;
it is never a benchmark configuration.

The configuration file holds the keys of a released Llama ``config.json``
(``hidden_size``, ``num_key_value_heads`` ...). ``LlamaConfig`` derives
its MLP width and fixes its RMSNorm epsilon, so ``system_config`` refuses
a file that states others than the program runs.
"""

from __future__ import annotations

ASSUMED = frozenset({"remat", "scan_layers", "use_flash"})

# Block weights: the system's name -> the released checkpoints' name.
BLOCK_NAMES = {
    "attn_norm": "input_layernorm", "wq": "q_proj", "wk": "k_proj",
    "wv": "v_proj", "wo": "o_proj", "mlp_norm": "post_attention_layernorm",
    "w_gate": "gate_proj", "w_up": "up_proj", "w_down": "down_proj",
}
KV_DTYPE_BYTES = 2  # the cache is held in the activation type, bfloat16


def shape(config: dict) -> dict:
    return {"vocab": config["vocab_size"],
            "n_positions": config["max_position_embeddings"]}


def _sizes(config: dict) -> tuple:
    """(layers, d, heads, kv heads, head_dim, ff, vocabulary)."""
    d, h = config["hidden_size"], config["num_attention_heads"]
    return (config["num_hidden_layers"], d, h,
            config["num_key_value_heads"], d // h,
            config["intermediate_size"], config["vocab_size"])


def _matmul_params_per_layer(config: dict) -> int:
    _, d, h, kv, hd, ff, _ = _sizes(config)
    return 2 * d * h * hd + 2 * d * kv * hd + 3 * d * ff


def param_count(config: dict) -> int:
    """Embedding, blocks (matrices and two norm scales), final norm, and
    the untied head."""
    layers, d, _, _, _, _, vocab = _sizes(config)
    return (vocab * d + layers * (_matmul_params_per_layer(config) + 2 * d)
            + d + d * vocab)


def _kv_bytes_per_token(config: dict) -> float:
    layers, _, _, kv, hd, _, _ = _sizes(config)
    return 2.0 * layers * kv * hd * KV_DTYPE_BYTES


def cache_bytes(config: dict, slots: int, cache_len: int) -> float:
    """K and V of the key/value heads only: what grouped queries save."""
    return slots * cache_len * _kv_bytes_per_token(config)


def decode_step_bytes(config: dict, weight_bytes: float, occupancy: float,
                      mean_context: float, counters: dict) -> float:
    return weight_bytes + occupancy * mean_context * _kv_bytes_per_token(
        config)


def train_flops_per_token(config: dict) -> float:
    """6 operations a matmul parameter a token (the head is one, the
    embedding lookup is not), and causal attention at half the square."""
    layers, d, _, _, _, _, vocab = _sizes(config)
    matmul = layers * _matmul_params_per_layer(config) + d * vocab
    return 6.0 * matmul + 6.0 * layers * d * config["max_position_embeddings"]


def attention_calls(config: dict, rows: int) -> tuple:
    """The kernel sees the key/value heads repeated to the query heads."""
    layers, _, h, _, hd, _, _ = _sizes(config)
    return (rows, h, config["max_position_embeddings"], hd), layers


def system_config(config: dict):
    from ray_tpu.models.llama import LlamaConfig

    layers, d, h, kv, _, ff, vocab = _sizes(config)
    cfg = LlamaConfig(
        vocab_size=vocab, n_layer=layers, n_head=h, n_kv_head=kv, d_model=d,
        seq_len=config["max_position_embeddings"],
        rope_theta=config["rope_theta"], **{
            k: v for k, v in config.get("assumed", {}).items()
            if k in ASSUMED})
    if cfg.d_ff != ff or config["rms_norm_eps"] != 1e-6 \
            or config["tie_word_embeddings"]:
        raise ValueError(
            f"models/llama.py runs an MLP of {cfg.d_ff}, RMSNorm epsilon "
            f"1e-6 and an untied head; the file states "
            f"{ff}, {config['rms_norm_eps']}, tied "
            f"{config['tie_word_embeddings']}")
    return cfg


def reference_kwargs(config: dict) -> dict:
    return {"n_head": config["num_attention_heads"],
            "n_kv_head": config["num_key_value_heads"],
            "eps": config["rms_norm_eps"], "theta": config["rope_theta"]}


def to_reference(params, config: dict):
    import jax.numpy as jnp

    f32 = lambda x: x.astype(jnp.float32)
    return {"embed_tokens": f32(params["embed"]),
            "layers": {ref: f32(params["blocks"][sys_name])
                       for sys_name, ref in BLOCK_NAMES.items()},
            "norm": f32(params["final_norm"]),
            "lm_head": f32(params["lm_head"])}


def init_params(config: dict, seed: int):
    import jax

    from ray_tpu.models.llama import llama_init

    return llama_init(jax.random.PRNGKey(seed), system_config(config))


def build_train(config: dict, mesh) -> dict:
    from ray_tpu.models.llama import llama_init, llama_loss, llama_shardings
    from ray_tpu.train import make_init_fn, make_train_step
    from ray_tpu.train.train_step import batch_sharding

    cfg = system_config(config)
    shardings = llama_shardings(cfg, mesh)
    return {
        "init": make_init_fn(lambda r: llama_init(r, cfg), shardings, mesh),
        "step": make_train_step(
            lambda p, b: llama_loss(p, b, cfg), shardings, mesh),
        "batch_sharding": batch_sharding(mesh),
        "row_tokens": cfg.seq_len + 1,
        "params_of": lambda state: state["params"],
    }


def engine_bind(config: dict, engine: dict, seed: int) -> dict:
    return {"model": "llama", "config": system_config(config),
            "seed": seed, **engine}


def serve_logits(config: dict, params, prompts, lengths, follow, slots: int,
                 cache_len: int):
    """``llama_prefill`` of the padded prompts, then one
    ``llama_decode_step`` per column of ``follow``. -> [R, 1 + N, V]."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.llama import (llama_decode_step, llama_init_cache,
                                      llama_prefill)

    cfg = system_config(config)
    r = prompts.shape[0]
    cache = llama_init_cache(cfg, slots, cache_len)
    prefill = jax.jit(lambda p, c, t, s, n: llama_prefill(p, c, t, s, n, cfg))
    step = jax.jit(lambda p, c, t, n: llama_decode_step(p, c, t, n, cfg),
                   donate_argnums=(1,))
    logits, cache = prefill(params, cache, prompts,
                            jnp.arange(r, dtype=jnp.int32), lengths)
    out = [logits]
    pad = jnp.zeros((slots - r,), jnp.int32)
    for i in range(follow.shape[1]):
        logits, cache = step(params, cache,
                             jnp.concatenate([follow[:, i], pad]),
                             jnp.concatenate([lengths + i, pad]))
        out.append(logits[:r])
    return jnp.stack(out, axis=1)
