"""How the system under test is built from a ``deepseek_v2``-family
configuration file (``model_type: deepseek_v2``: latent attention over a
latent cache, a leading dense gated MLP, then group-limited routed experts
beside shared experts), how its weights map onto the reference's names, and
the family's arithmetic. ``README.md`` beside this file lists the
interface; what differs here:

* **The cache is latent rows**: ``kv_lora_rank + qk_rope_head_dim`` numbers a
  token a layer, shared by every head (``latent_bytes_per_token``), and no
  state beside them.
* **Bytes from counters** (one expert for each held expert a step hit) and
  bfloat16 leaves handed to the reference unconverted, as
  ``families/granite_hybrid.py``; the head is its own matrix, so a step
  reads the head's table whole and of the embedding's only its tokens' rows.
* **``prefill_chunk_work``** with ``metrics/prefill_chunk_roofline.py``'s
  signature, and **``latent_decode_attention_work``**, the operations and
  bytes the decode step's attention requires over its live rows
  (``metrics/latent_decode_attention_roofline.py``).
* **The training functions refuse**: no training cell of this family exists
  (16 bytes a parameter do not fit one chip at the guide's floors).

The configuration file holds the released ``config.json``'s keys.
``num_hidden_layers`` is the layers that run (the leading
``first_k_dense_replace`` dense ones first), ``n_routed_experts`` the number
of experts HELD, ``n_routed_experts_published`` the router's width. The step
runs every one of ``max_batch + 1`` rows, free slots too, so the counters
count what the step really routed; the cell's slots are full.
"""

from __future__ import annotations

# The keys a configuration file of this family may carry under ``assumed``
# (beside notes whose key ends in ``why``).
ASSUMED = frozenset({"experts_held", "init_std", "init_embed_std",
                     "init_routed_out_std", "rope_lanes"})

# A layer's weights: the system's name -> the reference's (the released
# checkpoint's, shortened). ``kv_b_proj`` is made of ``w_uk`` and ``w_uv``.
ATTENTION_NAMES = {"norm": "input_layernorm",
                   "norm2": "post_attention_layernorm", "wq_a": "q_a_proj",
                   "q_norm": "q_a_layernorm", "wkv_a": "kv_a_proj_with_mqa",
                   "kv_norm": "kv_a_layernorm", "wo": "o_proj"}
DENSE_NAMES = {"w_in": "mlp_in", "w_down": "mlp_down"}
EXPERT_NAMES = {"router": "router", "w1": "experts_in", "w2": "experts_out",
                "shared_w1": "shared_in", "shared_w2": "shared_out"}


def shape(config: dict) -> dict:
    """The sizes the arithmetic needs, from the file."""
    a = config.get("assumed", {})
    d, h = config["hidden_size"], config["num_attention_heads"]
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    rank, q_rank = config["kv_lora_rank"], config["q_lora_rank"]
    v_dim = config["v_head_dim"]
    first, held = a.get("experts_held", [0, config["n_routed_experts"]])
    n_layer = config["num_hidden_layers"]
    n_dense = min(config["first_k_dense_replace"], n_layer)
    return {
        "vocab": config["vocab_size"],
        "n_positions": config["max_position_embeddings"],
        "n_layer": n_layer, "n_dense": n_dense,
        "n_expert_layers": n_layer - n_dense, "d_model": d, "n_head": h,
        "row_width": rank + rope,
        "first_expert": first, "experts_held": held,
        "router_width": config.get("n_routed_experts_published",
                                   config["n_routed_experts"]),
        # the matrices of the latent attention, without its two norms
        "attention_params": d * q_rank + q_rank * h * (nope + rope)
        + d * (rank + rope) + rank * h * (nope + v_dim) + h * v_dim * d,
        "attention_norms": q_rank + rank,
        # gated: [a, b] = W_in h and W_down, three matrices' worth
        "dense_params": 3 * d * config["intermediate_size"],
        "expert_params": 3 * d * config["moe_intermediate_size"],
        "shared_params": 3 * d * config["n_shared_experts"]
        * config["moe_intermediate_size"],
        "latent_bytes_per_token": n_layer * (rank + rope) * 2,
        # one query against one cached row, a layer: scores and values in
        # the latent space (absorbed) or on decompressed heads
        "absorbed_ops_per_row": 2 * h * (rank + rope + rank),
        "decompressed_ops_per_row": 2 * h * (nope + rope + v_dim),
    }


def _token_params(config: dict) -> int:
    """Parameters of the matrices every prompt token passes: attention's
    (its keys' and values' decompression once a token among them), the
    dense MLPs, routers and shared experts (not the norms' scales, nor the
    head, which takes a chunk's last token only)."""
    sh = shape(config)
    return sh["n_layer"] * sh["attention_params"] \
        + sh["n_dense"] * sh["dense_params"] \
        + sh["n_expert_layers"] * (sh["d_model"] * sh["router_width"]
                                   + sh["shared_params"])


def param_count(config: dict) -> int:
    """Parameters as the system holds them: the experts held, the
    vocabulary's slice in both tables, the layers that run."""
    sh = shape(config)
    d = sh["d_model"]
    every = 2 * d + sh["attention_params"] + sh["attention_norms"]
    expert_layer = d * sh["router_width"] + sh["shared_params"] \
        + sh["experts_held"] * sh["expert_params"]
    return sh["n_layer"] * every + sh["n_dense"] * sh["dense_params"] \
        + sh["n_expert_layers"] * expert_layer + 2 * sh["vocab"] * d + d


def cache_bytes(config: dict, slots: int, cache_len: int) -> float:
    """Bytes of the engine's cache, by shape: a ring of latent rows a slot
    a layer."""
    return slots * cache_len * shape(config)["latent_bytes_per_token"]


def _hit_per_step(config: dict, counters: dict) -> float:
    """Held experts a step hit, from the window's two ``llm_stats()``;
    every held expert where there are none."""
    sh = shape(config)
    a, b = counters.get("open") or {}, counters.get("close") or {}
    if "experts_hit" in a and "experts_hit" in b and b["steps"] > a["steps"]:
        return (b["experts_hit"] - a["experts_hit"]) \
            / (b["steps"] - a["steps"])
    return float(sh["n_expert_layers"] * sh["experts_held"])


def decode_step_bytes(config: dict, weight_bytes: float, occupancy: float,
                      mean_context: float, counters: dict) -> float:
    """Bytes one decode step must move: every weight outside the experts
    and the embedding once (the head's table whole; of the embedding the
    occupied slots' rows), one expert's bytes for each held expert the
    step hit, and the occupied slots' live latent rows."""
    sh = shape(config)
    d = sh["d_model"]
    per_param = weight_bytes / param_count(config)
    experts = sh["n_expert_layers"] * sh["experts_held"]
    dense = param_count(config) - experts * sh["expert_params"] \
        - sh["vocab"] * d
    return per_param * (dense + occupancy * d
                        + _hit_per_step(config, counters)
                        * sh["expert_params"]) \
        + occupancy * mean_context * sh["latent_bytes_per_token"]


def latent_decode_attention_work(config: dict, occupancy: float,
                                 mean_context: float) -> tuple:
    """(operations, bytes) the attention of ONE decode step requires: for
    each occupied slot's live rows, in every layer, the row read once and
    the absorbed form's scores and values over it (one query a slot leaves
    no cheaper form: decompressing a row costs more than scoring it).
    Free slots' rows, rows past a context and whatever a program computes
    beyond this are not required work."""
    sh = shape(config)
    rows = occupancy * mean_context * sh["n_layer"]
    return rows * sh["absorbed_ops_per_row"], \
        rows * sh["latent_bytes_per_token"] / sh["n_layer"]


def prefill_chunk_work(config: dict, weight_bytes: float, real_tokens: float,
                       expert_rows: float, mean_keys: float = 0.0,
                       last_share: float = 1.0) -> tuple:
    """(operations, bytes) one execution of the prefill chunk program
    requires for ``real_tokens`` real tokens of one request of which
    ``expert_rows`` token-expert pairs landed on the experts held here.
    Bytes: every stored matrix once (at some hundreds of tokens a chunk
    every held expert is hit) but the embedding, of which the tokens' rows;
    the latent rows of the ``mean_keys`` keys a query may see, and the
    chunk's own written. Operations: 2 a parameter of every matrix a token
    passes (a token's keys and values decompressed ONCE: what a chunk
    decompresses again of earlier chunks is the program's choice), 2 x one
    expert's parameters a pair, and a query's scores and values over
    ``mean_keys`` decompressed keys (the cheaper form at a chunk's
    length). Only a prompt's LAST chunk needs logits: the head counts for
    the ``last_share`` of executions that are one. No padding, no un-hit
    expert's product."""
    sh = shape(config)
    d = sh["d_model"]
    per_param = weight_bytes / param_count(config)
    io = weight_bytes - per_param * d * (
        sh["vocab"] - real_tokens + (1.0 - last_share) * sh["vocab"]) \
        + (mean_keys + real_tokens) * sh["latent_bytes_per_token"]
    ops = 2.0 * real_tokens * _token_params(config) \
        + 2.0 * expert_rows * sh["expert_params"] \
        + real_tokens * sh["n_layer"] * sh["decompressed_ops_per_row"] \
        * mean_keys + last_share * 2.0 * sh["vocab"] * d
    return ops, io


def _no_training(what: str):
    raise NotImplementedError(
        f"{what}: no training cell of the deepseek_v2 family exists (16 "
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
    from ray_tpu.models.deepseek_v2 import DeepseekV2Config

    a = config.get("assumed", {})
    sh = shape(config)
    want = {"hidden_act": "silu", "attention_bias": False,
            "tie_word_embeddings": False, "scoring_func": "softmax",
            "topk_method": "group_limited_greedy", "norm_topk_prob": False,
            "moe_layer_freq": 1, "rope_lanes": "split_halves"}
    for key, value in want.items():
        if {**a, **config}.get(key, value) != value:
            raise ValueError(f"{key} = {({**a, **config})[key]!r}: the "
                             f"program runs {value!r} only")
    rs = config["rope_scaling"]
    if rs.get("type") != "yarn":
        raise ValueError(f"rope_scaling {rs}: the program runs yarn only")
    if config["num_key_value_heads"] != config["num_attention_heads"]:
        raise ValueError("num_key_value_heads is not num_attention_heads: "
                         "a latent cache has no K/V heads to group")
    if a.get("init_std", 0.02) != 0.02:
        raise ValueError(f"assumed {a}: the program draws its matrices at "
                         f"0.02 (but the embedding and the routed experts' "
                         f"output, which have keys of their own)")
    if sh["experts_held"] != config["n_routed_experts"]:
        raise ValueError("assumed.experts_held does not hold "
                         "n_routed_experts experts")
    return DeepseekV2Config(
        vocab_size=sh["vocab"], d_model=sh["d_model"],
        n_layer=sh["n_layer"], first_dense=sh["n_dense"],
        dense_ff=config["intermediate_size"], eps=config["rms_norm_eps"],
        n_head=sh["n_head"], q_rank=config["q_lora_rank"],
        kv_rank=config["kv_lora_rank"],
        nope_dim=config["qk_nope_head_dim"],
        rope_dim=config["qk_rope_head_dim"], v_dim=config["v_head_dim"],
        rope_theta=float(config["rope_theta"]),
        yarn_factor=float(rs["factor"]),
        yarn_original=rs["original_max_position_embeddings"],
        beta_fast=float(rs["beta_fast"]), beta_slow=float(rs["beta_slow"]),
        mscale=float(rs["mscale"]),
        mscale_all_dim=float(rs["mscale_all_dim"]),
        n_experts=sh["router_width"],
        experts_held=(sh["first_expert"], sh["experts_held"]),
        n_group=config["n_group"], topk_group=config["topk_group"],
        top_k=config["num_experts_per_tok"],
        routed_scale=float(config["routed_scaling_factor"]),
        expert_ff=config["moe_intermediate_size"],
        shared_ff=config["n_shared_experts"]
        * config["moe_intermediate_size"],
        # the seeded draw's departures from 0.02, where the file assumes
        # them
        embed_std=float(a.get("init_embed_std", 0.02)),
        routed_out_std=float(a.get("init_routed_out_std", 0.02)))


def reference_kwargs(config: dict) -> dict:
    sh = shape(config)
    return {"n_head": sh["n_head"], "nope": config["qk_nope_head_dim"],
            "rope": config["qk_rope_head_dim"],
            "v_dim": config["v_head_dim"], "eps": config["rms_norm_eps"],
            "rope_theta": float(config["rope_theta"]),
            "rope_scaling": dict(config["rope_scaling"]),
            "top_k": config["num_experts_per_tok"],
            "n_group": config["n_group"],
            "topk_group": config["topk_group"],
            "routed_scale": float(config["routed_scaling_factor"]),
            "first_expert": sh["first_expert"]}


def to_reference(params, config: dict):
    """The system's parameter tree under the reference's names. The leaves
    go over as they are stored (bfloat16 widens exactly, and the reference
    widens each where it uses it): no float32 copy of the weights is made.
    ``q_b_proj`` and ``kv_b_proj`` get their heads back side by side in
    one axis, a head's key part before its value part."""
    import jax.numpy as jnp

    layers = []
    for p in params["layers"]:
        names = {**ATTENTION_NAMES,
                 **(DENSE_NAMES if "w_in" in p else EXPERT_NAMES)}
        layer = {ref: p[name] for name, ref in names.items()}
        layer["q_b_proj"] = p["wq_b"].reshape(p["wq_b"].shape[0], -1)
        layer["kv_b_proj"] = jnp.concatenate(
            [p["w_uk"], p["w_uv"]], axis=-1).reshape(p["w_uk"].shape[0], -1)
        layers.append(layer)
    return {"embed_tokens": params["embed"], "norm": params["norm_f"],
            "lm_head": params["head"], "layers": layers}


def init_params(config: dict, seed: int):
    """Seeded weights exactly as the engine makes its own."""
    import jax

    from ray_tpu.models.deepseek_v2 import deepseek_v2_init

    return deepseek_v2_init(jax.random.PRNGKey(seed), system_config(config))


def engine_bind(config: dict, engine: dict, seed: int) -> dict:
    """Arguments of ``LLMEngine`` for this configuration."""
    return {"model": "deepseek_v2", "config": system_config(config),
            "seed": seed, **engine}


def serve_logits(config: dict, params, prompts, lengths, follow, slots: int,
                 cache_len: int):
    """Logits of the serving path's own functions: ``deepseek_v2_prefill``
    of the padded ``prompts`` [R, P] (the chunk program over every chunk of
    the window), then one ``deepseek_v2_decode_step`` per column of
    ``follow`` [R, N] through a fresh latent cache. -> [R, 1 + N, V]."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.deepseek_v2 import (deepseek_v2_decode_step,
                                            deepseek_v2_init_cache,
                                            deepseek_v2_prefill)

    cfg = system_config(config)
    r = prompts.shape[0]
    cache = deepseek_v2_init_cache(cfg, slots, cache_len)
    slot_idx = jnp.arange(r, dtype=jnp.int32)
    prefill = jax.jit(
        lambda p, c, t, s, n: deepseek_v2_prefill(p, c, t, s, n, cfg),
        donate_argnums=(1,))
    step = jax.jit(
        lambda p, c, t, n: deepseek_v2_decode_step(p, c, t, n, cfg)[:2],
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
