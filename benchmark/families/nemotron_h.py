"""How the system under test is built from a ``nemotron_h``-family
configuration file (Mamba-2 mixers, attention and latent mixture-of-experts
layers in one model), how its weights map onto the reference's names, and
the family's arithmetic. ``README.md`` beside this file lists the interface;
what differs for a sparse, hybrid family:

* **Bytes from counters.** A decode step reads only the experts it hit.
  ``decode_step_bytes`` takes ``experts_hit`` a step from the window's two
  ``llm_stats()`` (the engine adds up what each step's program counted) and
  charges one expert's bytes for each; without counters it charges every
  held expert.
* **State read and written.** Beside the K/V rows a step reads, every
  occupied slot's convolution tail and float32 SSM state are read AND
  written each step, in every ``M`` layer: both directions are counted.
  ``cache_bytes`` counts K/V and both states.
* **The embedding's rows, not the table**, are read by a step; the untied
  head is read whole.
* **``to_reference`` hands bfloat16 leaves over unconverted.** The weights
  are stored in bfloat16 as published; bfloat16 to float32 is exact, and
  the reference widens each leaf where it uses it, an expert at a time, so
  no float32 copy of all 4.65 B parameters is ever made.
* **The training functions refuse.** No training cell of this family
  exists (16 bytes a parameter do not fit one chip at the guide's floors):
  ``build_train``, ``train_flops_per_token`` and ``attention_calls`` raise.

The configuration file holds the released ``config.json``'s keys. The depth
that runs is the length of ``hybrid_override_pattern`` (the released config
class holds ``num_hidden_layers`` equal to it); ``n_routed_experts`` is the
number of experts HELD, ``n_routed_experts_published`` the router's width.
The step runs every one of ``max_batch + 1`` rows, free slots too, so the
counters count what the step really routed; the cell's slots are full.
"""

from __future__ import annotations

# The keys a configuration file of this family may carry under ``assumed``
# (beside notes whose key ends in ``why``).
ASSUMED = frozenset({"attention_position_embedding", "ssm_state_dtype",
                     "mtp", "experts_held"})

# A layer's weights: the system's name -> the reference's (the released
# checkpoint's, shortened).
LAYER_NAMES = {
    "M": {"norm": "norm", "in_proj": "in_proj", "conv_w": "conv_w",
          "conv_b": "conv_b", "dt_bias": "dt_bias", "a_log": "A_log",
          "d_skip": "D", "gate_norm": "norm_w", "out_proj": "out_proj"},
    "*": {"norm": "norm", "wq": "q_proj", "wk": "k_proj", "wv": "v_proj",
          "wo": "o_proj"},
    "E": {"norm": "norm", "router": "gate_w",
          "router_bias": "e_score_correction_bias",
          "w_down": "fc1_latent_proj", "w_up": "fc2_latent_proj",
          "w1": "experts_up", "w2": "experts_down",
          "shared_w1": "shared_up", "shared_w2": "shared_down"},
}


def shape(config: dict) -> dict:
    """The sizes the arithmetic needs, from the file."""
    a = config.get("assumed", {})
    pattern = config["hybrid_override_pattern"]
    heads, head_dim = config["mamba_num_heads"], config["mamba_head_dim"]
    d_inner = heads * head_dim
    conv_dim = d_inner + 2 * config["n_groups"] * config["ssm_state_size"]
    first, held = a.get("experts_held", [0, config["n_routed_experts"]])
    return {
        "vocab": config["vocab_size"],
        "n_positions": config["max_position_embeddings"],
        "pattern": pattern, "d_model": config["hidden_size"],
        "n_m": pattern.count("M"), "n_attn": pattern.count("*"),
        "n_e": pattern.count("E"),
        "d_inner": d_inner, "conv_dim": conv_dim, "mamba_heads": heads,
        "first_expert": first, "experts_held": held,
        "router_width": config.get("n_routed_experts_published",
                                   config["n_routed_experts"]),
        "expert_params": 2 * config["moe_latent_size"]
        * config["moe_intermediate_size"],
        "kv_bytes_per_token": 2 * pattern.count("*")
        * config["num_key_value_heads"] * config["head_dim"] * 2,
        # a slot's state: float32 SSM state and bfloat16 convolution tail
        "state_bytes_per_slot": pattern.count("M") * (
            heads * head_dim * config["ssm_state_size"] * 4
            + (config["conv_kernel"] - 1) * conv_dim * 2),
    }


def _layer_params(config: dict, kind: str) -> int:
    sh = shape(config)
    d = sh["d_model"]
    if kind == "M":
        in_width = 2 * sh["d_inner"] + 2 * config["n_groups"] \
            * config["ssm_state_size"] + sh["mamba_heads"]
        return (d + d * in_width + (config["conv_kernel"] + 1)
                * sh["conv_dim"] + 3 * sh["mamba_heads"] + sh["d_inner"]
                + sh["d_inner"] * d)
    if kind == "*":
        q = config["num_attention_heads"] * config["head_dim"]
        kv = config["num_key_value_heads"] * config["head_dim"]
        return d + 2 * d * q + 2 * d * kv
    return (d + d * sh["router_width"] + sh["router_width"]
            + 2 * d * config["moe_latent_size"]
            + sh["experts_held"] * sh["expert_params"]
            + 2 * d * config["moe_shared_expert_intermediate_size"])


def param_count(config: dict) -> int:
    """Parameters as the system holds them: the experts held, the
    vocabulary's slice, the layers of the pattern."""
    sh = shape(config)
    return sum(_layer_params(config, kind) for kind in sh["pattern"]) \
        + 2 * sh["vocab"] * sh["d_model"] + sh["d_model"]


def cache_bytes(config: dict, slots: int, cache_len: int) -> float:
    """Bytes of the engine's cache, by shape: the ``*`` layers' K/V rows
    and, for every ``M`` layer, the convolution tail and the SSM state."""
    sh = shape(config)
    return slots * (cache_len * sh["kv_bytes_per_token"]
                    + sh["state_bytes_per_slot"])


def decode_step_bytes(config: dict, weight_bytes: float, occupancy: float,
                      mean_context: float, counters: dict) -> float:
    """Bytes one decode step must move: every weight outside the experts
    once (of the embedding only the rows read), one expert's bytes for each
    held expert the step hit (``experts_hit`` a step, from the window's two
    ``llm_stats()``; every held expert where there are none), and for the
    occupied slots the K/V rows read and the state read and written."""
    sh = shape(config)
    per_param = weight_bytes / param_count(config)
    experts = sh["n_e"] * sh["experts_held"]
    a, b = counters.get("open") or {}, counters.get("close") or {}
    if "experts_hit" in a and "experts_hit" in b and b["steps"] > a["steps"]:
        hit = (b["experts_hit"] - a["experts_hit"]) \
            / (b["steps"] - a["steps"])
    else:
        hit = float(experts)
    dense = param_count(config) - experts * sh["expert_params"] \
        - (sh["vocab"] - occupancy) * sh["d_model"]
    return per_param * (dense + hit * sh["expert_params"]) \
        + occupancy * (mean_context * sh["kv_bytes_per_token"]
                       + 2 * sh["state_bytes_per_slot"])


def _no_training(what: str):
    raise NotImplementedError(
        f"{what}: no training cell of the nemotron_h family exists (16 "
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
    from ray_tpu.models.nemotron_h import NemotronHConfig

    a = config.get("assumed", {})
    sh = shape(config)
    want = {"mamba_hidden_act": "silu", "mlp_hidden_act": "relu2",
            "n_group": 1, "topk_group": 1, "norm_topk_prob": True,
            "n_shared_experts": 1, "tie_word_embeddings": False,
            "use_conv_bias": True, "use_bias": False,
            "mamba_proj_bias": False, "attention_bias": False,
            "mlp_bias": False}
    for key, value in want.items():
        if config.get(key, value) != value:
            raise ValueError(f"{key} = {config[key]!r}: the program runs "
                             f"{value!r} only")
    if config["expand"] * config["hidden_size"] != sh["d_inner"]:
        raise ValueError("expand * hidden_size is not mamba_num_heads * "
                         "mamba_head_dim")
    if a.get("attention_position_embedding", "none") != "none" \
            or a.get("ssm_state_dtype", "float32") != "float32" \
            or a.get("mtp", "not served") != "not served":
        raise ValueError(f"assumed {a}: the program applies no position "
                         f"embedding, keeps a float32 SSM state and does "
                         f"not serve the multi-token head")
    if sh["experts_held"] != config["n_routed_experts"]:
        raise ValueError("assumed.experts_held does not hold "
                         "n_routed_experts experts")
    return NemotronHConfig(
        vocab_size=sh["vocab"], d_model=sh["d_model"],
        pattern=sh["pattern"], eps=config["layer_norm_epsilon"],
        n_head=config["num_attention_heads"],
        n_kv_head=config["num_key_value_heads"],
        head_dim=config["head_dim"], mamba_heads=sh["mamba_heads"],
        mamba_head_dim=config["mamba_head_dim"],
        ssm_groups=config["n_groups"], ssm_state=config["ssm_state_size"],
        conv_kernel=config["conv_kernel"], chunk_size=config["chunk_size"],
        n_experts=sh["router_width"],
        experts_held=(sh["first_expert"], sh["experts_held"]),
        top_k=config["num_experts_per_tok"],
        latent=config["moe_latent_size"],
        expert_ff=config["moe_intermediate_size"],
        shared_ff=config["moe_shared_expert_intermediate_size"],
        routed_scale=float(config["routed_scaling_factor"]))


def reference_kwargs(config: dict) -> dict:
    sh = shape(config)
    return {"pattern": sh["pattern"], "eps": config["layer_norm_epsilon"],
            "n_head": config["num_attention_heads"],
            "n_kv_head": config["num_key_value_heads"],
            "head_dim": config["head_dim"],
            "mamba_heads": sh["mamba_heads"],
            "mamba_head_dim": config["mamba_head_dim"],
            "n_groups": config["n_groups"],
            "ssm_state": config["ssm_state_size"],
            "top_k": config["num_experts_per_tok"],
            "routed_scale": float(config["routed_scaling_factor"]),
            "first_expert": sh["first_expert"]}


def to_reference(params, config: dict):
    """The system's parameter tree under the reference's names. The leaves
    go over as they are stored (bfloat16 widens exactly, and the reference
    widens each where it uses it): no float32 copy of the weights is made.
    Nothing is padded, so nothing is cut."""
    return {
        "embeddings": params["embed"], "lm_head": params["lm_head"],
        "norm_f": params["norm_f"],
        "layers": [{ref: p[name] for name, ref in LAYER_NAMES[kind].items()}
                   for kind, p in zip(shape(config)["pattern"],
                                      params["layers"])],
    }


def init_params(config: dict, seed: int):
    """Seeded weights exactly as the engine makes its own."""
    import jax

    from ray_tpu.models.nemotron_h import nemotron_h_init

    return nemotron_h_init(jax.random.PRNGKey(seed), system_config(config))


def engine_bind(config: dict, engine: dict, seed: int) -> dict:
    """Arguments of ``LLMEngine`` for this configuration."""
    return {"model": "nemotron_h", "config": system_config(config),
            "seed": seed, **engine}


def serve_logits(config: dict, params, prompts, lengths, follow, slots: int,
                 cache_len: int):
    """Logits of the serving path's own functions: ``nemotron_h_prefill``
    of the padded ``prompts`` [R, P], then one ``nemotron_h_decode_step``
    per column of ``follow`` [R, N] through a fresh cache (K/V rows and
    both states). -> [R, 1 + N, V]."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.nemotron_h import (nemotron_h_decode_step,
                                           nemotron_h_init_cache,
                                           nemotron_h_prefill)

    cfg = system_config(config)
    r = prompts.shape[0]
    cache = nemotron_h_init_cache(cfg, slots, cache_len)
    slot_idx = jnp.arange(r, dtype=jnp.int32)
    prefill = jax.jit(
        lambda p, c, t, s, n: nemotron_h_prefill(p, c, t, s, n, cfg),
        donate_argnums=(1,))
    step = jax.jit(
        lambda p, c, t, n: nemotron_h_decode_step(p, c, t, n, cfg)[:2],
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
