"""How the system under test is built from a ``granite_hybrid``-family
configuration file (``model_type: granitemoehybrid``: a Mamba-2 mixer or
attention AND THEN routed experts plus a shared expert in every layer), how
its weights map onto the reference's names, and the family's arithmetic.
``README.md`` beside this file lists the interface; what differs here:

* **Bytes from counters**, state read and written, and bfloat16 leaves
  handed to the reference unconverted: as ``families/nemotron_h.py``.
* **The head is the embedding.** A step reads the table once, for the head;
  the rows the lookup reads are in it.
* **``prefill_chunk_work``**, beside the README's table: the bytes one
  execution of the prefill chunk program must read and the operations its
  real tokens require (``metrics/prefill_chunk_roofline.py``).
* **The training functions refuse**: no training cell of this family exists
  (16 bytes a parameter do not fit one chip at the guide's floors).

The configuration file holds the released ``config.json``'s keys.
``num_hidden_layers`` and ``layer_types`` are the layers that run;
``num_local_experts`` is the number of experts HELD,
``num_local_experts_published`` the router's width. The step runs every
one of ``max_batch + 1`` rows, free slots too, so the counters count what
the step really routed; the cell's slots are full.
"""

from __future__ import annotations

# The keys a configuration file of this family may carry under ``assumed``
# (beside notes whose key ends in ``why``).
ASSUMED = frozenset({"experts_held", "ssm_state_dtype", "init_std",
                     "init_embed_std"})

# A layer's weights: the system's name -> the reference's (the released
# checkpoint's, shortened).
MIXER_NAMES = {
    "mamba": {"in_proj": "in_proj", "conv_w": "conv_w", "conv_b": "conv_b",
              "dt_bias": "dt_bias", "a_log": "A_log", "d_skip": "D",
              "gate_norm": "norm_w", "out_proj": "out_proj"},
    "attention": {"wq": "q_proj", "wk": "k_proj", "wv": "v_proj",
                  "wo": "o_proj"},
}
LAYER_NAMES = {"norm": "input_layernorm", "norm2": "post_attention_layernorm",
               "router": "router", "w1": "experts_in", "w2": "experts_out",
               "shared_w1": "shared_in", "shared_w2": "shared_out"}


def shape(config: dict) -> dict:
    """The sizes the arithmetic needs, from the file."""
    a = config.get("assumed", {})
    kinds = list(config["layer_types"])
    heads, head_dim = config["mamba_n_heads"], config["mamba_d_head"]
    d_inner = heads * head_dim
    gn = config["mamba_n_groups"] * config["mamba_d_state"]
    conv_dim = d_inner + 2 * gn
    first, held = a.get("experts_held", [0, config["num_local_experts"]])
    kv_width = config["num_key_value_heads"] * (
        config["hidden_size"] // config["num_attention_heads"])
    return {
        "vocab": config["vocab_size"],
        "n_positions": config["max_position_embeddings"],
        "layer_types": kinds, "d_model": config["hidden_size"],
        "n_mamba": kinds.count("mamba"), "n_attn": kinds.count("attention"),
        "head_dim": config["hidden_size"] // config["num_attention_heads"],
        "d_inner": d_inner, "conv_dim": conv_dim, "mamba_heads": heads,
        "in_width": d_inner + conv_dim + heads,
        "first_expert": first, "experts_held": held,
        "router_width": config.get("num_local_experts_published",
                                   config["num_local_experts"]),
        # gated: [a, b] = W1 h and W2, three matrices' worth
        "expert_params": 3 * config["hidden_size"]
        * config["intermediate_size"],
        "shared_params": 3 * config["hidden_size"]
        * config["shared_intermediate_size"],
        "kv_bytes_per_token": 2 * kinds.count("attention") * kv_width * 2,
        # a slot's state: float32 SSM state and bfloat16 convolution tail
        "state_bytes_per_slot": kinds.count("mamba") * (
            d_inner * config["mamba_d_state"] * 4
            + (config["mamba_d_conv"] - 1) * conv_dim * 2),
    }


def _mixer_params(config: dict, kind: str) -> int:
    sh = shape(config)
    d = sh["d_model"]
    if kind == "mamba":
        return (d * sh["in_width"] + (config["mamba_d_conv"] + 1)
                * sh["conv_dim"] + 3 * sh["mamba_heads"] + sh["d_inner"]
                + sh["d_inner"] * d)
    q = config["num_attention_heads"] * sh["head_dim"]
    kv = config["num_key_value_heads"] * sh["head_dim"]
    return 2 * d * q + 2 * d * kv


def _token_params(config: dict) -> int:
    """Parameters of the matrices every prompt token passes: the mixers,
    routers and shared experts (not the norms' scales, the convolution or
    the head, which takes a chunk's last token only)."""
    sh = shape(config)
    d = sh["d_model"]
    per_mamba = d * sh["in_width"] + sh["d_inner"] * d
    return sh["n_mamba"] * per_mamba \
        + sh["n_attn"] * _mixer_params(config, "attention") \
        + len(sh["layer_types"]) * (d * sh["router_width"]
                                    + sh["shared_params"])


def param_count(config: dict) -> int:
    """Parameters as the system holds them: the experts held, the
    vocabulary's slice (the head is the embedding), the layers that run."""
    sh = shape(config)
    d = sh["d_model"]
    every = 2 * d + d * sh["router_width"] + sh["shared_params"] \
        + sh["experts_held"] * sh["expert_params"]
    return sum(_mixer_params(config, kind) + every
               for kind in sh["layer_types"]) + sh["vocab"] * d + d


def cache_bytes(config: dict, slots: int, cache_len: int) -> float:
    """Bytes of the engine's cache, by shape: the attention layers' K/V
    rows and, for every Mamba layer, the convolution tail and the state."""
    sh = shape(config)
    return slots * (cache_len * sh["kv_bytes_per_token"]
                    + sh["state_bytes_per_slot"])


def decode_step_bytes(config: dict, weight_bytes: float, occupancy: float,
                      mean_context: float, counters: dict) -> float:
    """Bytes one decode step must move: every weight outside the experts
    once (the embedding table as the head reads it), one expert's bytes for
    each held expert the step hit (``experts_hit`` a step, from the
    window's two ``llm_stats()``; every held expert where there are none),
    and for the occupied slots the K/V rows read and the state read and
    written."""
    sh = shape(config)
    per_param = weight_bytes / param_count(config)
    experts = len(sh["layer_types"]) * sh["experts_held"]
    a, b = counters.get("open") or {}, counters.get("close") or {}
    if "experts_hit" in a and "experts_hit" in b and b["steps"] > a["steps"]:
        hit = (b["experts_hit"] - a["experts_hit"]) \
            / (b["steps"] - a["steps"])
    else:
        hit = float(experts)
    dense = param_count(config) - experts * sh["expert_params"]
    return per_param * (dense + hit * sh["expert_params"]) \
        + occupancy * (mean_context * sh["kv_bytes_per_token"]
                       + 2 * sh["state_bytes_per_slot"])


def prefill_chunk_work(config: dict, weight_bytes: float, real_tokens: float,
                       expert_rows: float, mean_keys: float = 0.0,
                       last_share: float = 1.0) -> tuple:
    """(operations, bytes) one execution of the prefill chunk program
    requires for ``real_tokens`` real tokens of one request of which
    ``expert_rows`` token-expert pairs landed on the experts held here.
    Bytes: every stored matrix once (at some hundreds of tokens a chunk
    every held expert is hit), the slot's state read and written, the K/V
    rows of the ``mean_keys`` keys a query may see. Operations: 2 a
    parameter of every matrix a token passes, 2 x one expert's parameters a
    pair, the scores and the weighted sum over ``mean_keys`` keys a query.
    Only a prompt's LAST chunk needs logits: the head's pass over the tied
    table counts for the ``last_share`` of executions that are one (the
    program runs it in every chunk; that is not required work), and the
    others read the table's rows of their tokens. No padding, no un-hit
    expert's product, and the scan's own products are left out."""
    sh = shape(config)
    d = sh["d_model"]
    table = weight_bytes / param_count(config) * d  # bytes a row
    io = weight_bytes + 2 * sh["state_bytes_per_slot"] \
        + mean_keys * sh["kv_bytes_per_token"] \
        - (1.0 - last_share) * table * (sh["vocab"] - real_tokens)
    ops = 2.0 * real_tokens * _token_params(config) \
        + 2.0 * expert_rows * sh["expert_params"] \
        + real_tokens * sh["n_attn"] * 4.0 * d * mean_keys \
        + last_share * 2.0 * sh["vocab"] * d
    return ops, io


def _no_training(what: str):
    raise NotImplementedError(
        f"{what}: no training cell of the granite_hybrid family exists (16 "
        f"bytes a parameter do not fit one chip at the guide's floors); "
        f"the family is served only")


def train_flops_per_token(config: dict) -> float:
    _no_training("train_flops_per_token")


def attention_calls(config: dict, rows: int) -> tuple:
    _no_training("attention_calls")


def build_train(config: dict, mesh) -> dict:
    _no_training("build_train")


def system_config(config: dict):
    """The program's configuration; refuses a file that states what the
    program does not run."""
    from ray_tpu.models.granite_hybrid import GraniteHybridConfig

    a = config.get("assumed", {})
    sh = shape(config)
    want = {"hidden_act": "silu", "normalization_function": "rmsnorm",
            "position_embedding_type": "nope", "tie_word_embeddings": True,
            "mamba_conv_bias": True, "mamba_proj_bias": False,
            "attention_bias": False}
    for key, value in want.items():
        if config.get(key, value) != value:
            raise ValueError(f"{key} = {config[key]!r}: the program runs "
                             f"{value!r} only")
    if config["mamba_expand"] * config["hidden_size"] != sh["d_inner"]:
        raise ValueError("mamba_expand * hidden_size is not mamba_n_heads * "
                         "mamba_d_head")
    if len(sh["layer_types"]) != config["num_hidden_layers"]:
        raise ValueError("layer_types does not hold num_hidden_layers "
                         "entries")
    if a.get("ssm_state_dtype", "float32") != "float32" \
            or a.get("init_std", 0.02) != 0.02:
        raise ValueError(f"assumed {a}: the program keeps a float32 SSM "
                         f"state and draws its matrices at 0.02 (but the "
                         f"embedding, which has a key of its own)")
    if sh["experts_held"] != config["num_local_experts"]:
        raise ValueError("assumed.experts_held does not hold "
                         "num_local_experts experts")
    return GraniteHybridConfig(
        vocab_size=sh["vocab"], d_model=sh["d_model"],
        layer_types=tuple(sh["layer_types"]), eps=config["rms_norm_eps"],
        n_head=config["num_attention_heads"],
        n_kv_head=config["num_key_value_heads"], head_dim=sh["head_dim"],
        attention_multiplier=float(config["attention_multiplier"]),
        mamba_heads=sh["mamba_heads"], mamba_head_dim=config["mamba_d_head"],
        ssm_groups=config["mamba_n_groups"],
        ssm_state=config["mamba_d_state"],
        conv_kernel=config["mamba_d_conv"],
        chunk_size=config["mamba_chunk_size"],
        n_experts=sh["router_width"],
        experts_held=(sh["first_expert"], sh["experts_held"]),
        top_k=config["num_experts_per_tok"],
        expert_ff=config["intermediate_size"],
        shared_ff=config["shared_intermediate_size"],
        embedding_multiplier=float(config["embedding_multiplier"]),
        residual_multiplier=float(config["residual_multiplier"]),
        logits_scaling=float(config["logits_scaling"]),
        # the seeded draw's one departure from 0.02, where the file
        # assumes it
        embed_std=float(a.get("init_embed_std", 0.02)))


def reference_kwargs(config: dict) -> dict:
    sh = shape(config)
    return {"layer_types": tuple(sh["layer_types"]),
            "eps": config["rms_norm_eps"],
            "n_head": config["num_attention_heads"],
            "n_kv_head": config["num_key_value_heads"],
            "head_dim": sh["head_dim"],
            "mamba_heads": sh["mamba_heads"],
            "mamba_head_dim": config["mamba_d_head"],
            "n_groups": config["mamba_n_groups"],
            "ssm_state": config["mamba_d_state"],
            "top_k": config["num_experts_per_tok"],
            "first_expert": sh["first_expert"],
            "embedding_multiplier": float(config["embedding_multiplier"]),
            "attention_multiplier": float(config["attention_multiplier"]),
            "residual_multiplier": float(config["residual_multiplier"]),
            "logits_scaling": float(config["logits_scaling"])}


def to_reference(params, config: dict):
    """The system's parameter tree under the reference's names. The leaves
    go over as they are stored (bfloat16 widens exactly, and the reference
    widens each where it uses it): no float32 copy of the weights is made.
    Nothing is padded, so nothing is cut."""
    return {
        "embed_tokens": params["embed"], "norm": params["norm_f"],
        "layers": [{ref: p[name] for name, ref in
                    {**LAYER_NAMES, **MIXER_NAMES[kind]}.items()}
                   for kind, p in zip(shape(config)["layer_types"],
                                      params["layers"])],
    }


def init_params(config: dict, seed: int):
    """Seeded weights exactly as the engine makes its own."""
    import jax

    from ray_tpu.models.granite_hybrid import granite_hybrid_init

    return granite_hybrid_init(jax.random.PRNGKey(seed),
                               system_config(config))


def engine_bind(config: dict, engine: dict, seed: int) -> dict:
    """Arguments of ``LLMEngine`` for this configuration."""
    return {"model": "granite_hybrid", "config": system_config(config),
            "seed": seed, **engine}


def serve_logits(config: dict, params, prompts, lengths, follow, slots: int,
                 cache_len: int):
    """Logits of the serving path's own functions:
    ``granite_hybrid_prefill`` of the padded ``prompts`` [R, P] (the chunk
    program over every chunk of the window), then one
    ``granite_hybrid_decode_step`` per column of ``follow`` [R, N] through
    a fresh cache (K/V rows and both states). -> [R, 1 + N, V]."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.granite_hybrid import (granite_hybrid_decode_step,
                                               granite_hybrid_init_cache,
                                               granite_hybrid_prefill)

    cfg = system_config(config)
    r = prompts.shape[0]
    cache = granite_hybrid_init_cache(cfg, slots, cache_len)
    slot_idx = jnp.arange(r, dtype=jnp.int32)
    prefill = jax.jit(
        lambda p, c, t, s, n: granite_hybrid_prefill(p, c, t, s, n, cfg),
        donate_argnums=(1,))
    step = jax.jit(
        lambda p, c, t, n: granite_hybrid_decode_step(p, c, t, n, cfg)[:2],
        donate_argnums=(1,))
    logits, cache = prefill(params, cache, prompts, slot_idx, lengths)
    out = [logits]
    pad = slots - r
    for i in range(follow.shape[1]):
        toks = jnp.concatenate([follow[:, i], jnp.zeros((pad,), jnp.int32)])
        pos = jnp.concatenate([lengths + i, jnp.zeros((pad,), jnp.int32)])
        logits, cache = step(params, cache, toks, pos)
        out.append(logits[:r])
    return jnp.stack(out, axis=1)
