"""How the system under test is built from an ``exaone_moe``-family
configuration file (``model_type: exaone_moe``: three rotary window layers
of 128 keys to one global layer without a position embedding, QK-norm, each
sublayer's norm after it, a sigmoid router over 128 experts beside a shared
expert behind a leading dense layer, and a multi-token-prediction module
served as the model's own draft), how its weights map onto the reference's
names, and the family's arithmetic. ``README.md`` beside this file lists the
interface; what differs here:

* **A decode step is a verify-and-draft step**: two rows a slot through
  every layer and then through the module. ``serve_logits`` drives exactly
  that (``exaone_moe_verify_step``), fed the reference's follow tokens as
  drafts, so accepted, and a WRONG draft at every ``REJECT_EVERY``-th step,
  so rejected; what it returns is the main stack's logits alone, as the
  interface says. ``_verify_steps``, which it calls, also returns the
  module's logits and the device's counts, for
  ``tools/serve_check_draft.py``.
* **Two ring lengths in one cache**, as ``families/smallthinker.py``: a
  global layer and the module count ``cache_len`` rows a slot, a window
  layer ``sliding_window`` rows whatever ``cache_len`` is.
* **The chip's share of the experts**: ``num_experts`` is how many of the
  router's ``num_experts_published`` are held here (ids 0 up); the router
  keeps its published width and ``num_experts_per_tok``.
* **Bytes from counters** (the experts a step really hit), bfloat16 leaves
  handed to the reference unconverted: as ``families/qwen3_next.py``.
* **``prefill_chunk_work``** (``metrics/prefill_chunk_roofline.py``) and
  **``verify_attention_work``** (``metrics/verify_attention_roofline.py``),
  beside the README's table: what a chunk and a verify step's attention
  REQUIRE, whatever implements them.
* **The training functions refuse**: no training cell of this family exists
  (the dropless share layer has no gradients).

The configuration file holds the released ``config.json``'s keys.
``num_hidden_layers`` is the layers that run (with ``layer_types``,
``mlp_layer_types`` and ``sliding_windows`` cut to as many entries),
``num_experts`` the experts held and ``vocab_size`` the rows of both tables
held here; the published values stand beside them. The step runs every one
of ``max_batch + 1`` slots, free ones too, so the counters count what the
step really routed; the cell's slots are full.
"""

from __future__ import annotations

# The keys a configuration file of this family may carry under ``assumed``
# (beside notes whose key ends in ``why``).
ASSUMED = frozenset({"norm_placement", "window_keys_with_own", "mtp",
                     "mtp_block", "mtp_combine", "init_gains"})

# ``serve_logits`` hands every REJECT_EVERY-th verify step a wrong draft
# (the second, the fourth, ...): the step must reject it and the steps
# after it must read nothing of its row.
REJECT_EVERY = 2

BLOCK_NAMES = {"wq": "q_proj", "wk": "k_proj", "wv": "v_proj", "wo": "o_proj",
               "q_norm": "q_norm", "k_norm": "k_norm",
               "norm_attn": "post_attention_layernorm",
               "norm_ff": "post_feedforward_layernorm"}


def shape(config: dict) -> dict:
    """The sizes the arithmetic needs, from the file."""
    d = config["hidden_size"]
    layout = [int(kind == "sliding_attention")
              for kind in config["layer_types"]]
    q = config["num_attention_heads"] * config["head_dim"]
    kv = config["num_key_value_heads"] * config["head_dim"]
    dense = sum(kind == "dense" for kind in config["mlp_layer_types"])
    width = config.get("num_experts_published", config["num_experts"])
    return {
        "vocab": config["vocab_size"],
        "n_positions": config["max_position_embeddings"],
        "d_model": d, "layout": layout, "dense_layers": dense,
        "sparse_layers": len(layout) - dense,
        "n_window": sum(layout), "n_global": len(layout) - sum(layout),
        "window": config["sliding_window"],
        "q_width": q, "held": config["num_experts"],
        "router_width": width,
        "attention_params": 2 * d * q + 2 * d * kv,
        "router_params": d * width,
        # gated: gate, up and down, three matrices' worth
        "expert_params": 3 * d * config["moe_intermediate_size"],
        "shared_params": 3 * d * config["moe_intermediate_size"]
        * config["num_shared_experts"],
        "dense_ff_params": 3 * d * config["intermediate_size"],
        "module_params": 2 * d * d,  # eh_proj, beside one dense block
        # what a block holds beside its matrices: two sublayer norms, two
        # head norms
        "block_norms": 2 * d + 2 * config["head_dim"],
        # bfloat16 merged K and V rows of ONE layer, a token
        "kv_bytes_per_layer_token": 2 * kv * 2,
    }


def param_count(config: dict) -> int:
    """Parameters as the system holds them: the layers that run with the
    experts held, the module (its projection, one dense block, three
    norms), both vocabulary tables' slices, the last norm, each sparse
    layer's selection bias. No table is padded."""
    sh = shape(config)
    block = sh["attention_params"] + sh["block_norms"]
    dense = block + sh["dense_ff_params"]
    sparse = block + sh["router_params"] + sh["router_width"] \
        + sh["held"] * sh["expert_params"] + sh["shared_params"]
    module = sh["module_params"] + dense + 3 * sh["d_model"]
    return sh["dense_layers"] * dense + sh["sparse_layers"] * sparse \
        + module + 2 * sh["vocab"] * sh["d_model"] + sh["d_model"]


def cache_bytes(config: dict, slots: int, cache_len: int) -> float:
    """Bytes of the engine's cache, by shape: the global layers' rings and
    the module's of ``cache_len`` rows, the window layers' of ``window``
    rows."""
    sh = shape(config)
    return slots * sh["kv_bytes_per_layer_token"] * (
        (sh["n_global"] + 1) * cache_len + sh["n_window"] * sh["window"])


def _ring_rows(sh: dict, mean_context: float) -> float:
    """Ring rows a query at ``mean_context`` must see, over every ring a
    slot holds: the global layers' and the module's live rows, the window
    layers' ``min(context, window)``."""
    return (sh["n_global"] + 1) * mean_context \
        + sh["n_window"] * min(mean_context, sh["window"])


def decode_step_bytes(config: dict, weight_bytes: float, occupancy: float,
                      mean_context: float, counters: dict) -> float:
    """Bytes one VERIFY-AND-DRAFT step must move: every held matrix outside
    the routed experts once, the module's among them, but the embedding's
    table (a step looks up four rows a slot: two tokens for the main stack,
    two for the module); one expert's bytes for each expert the step hit
    (``experts_hit`` a step, from the window's two ``llm_stats()``; every
    held expert where there are none); and for the occupied slots the live
    rows of the global rings and the module's and ``min(context, window)``
    rows of the window rings, each read ONCE for a slot's two rows."""
    sh = shape(config)
    per_param = weight_bytes / param_count(config)
    experts = sh["sparse_layers"] * sh["held"]
    a, b = counters.get("open") or {}, counters.get("close") or {}
    if "experts_hit" in a and "experts_hit" in b and b["steps"] > a["steps"]:
        hit = (b["experts_hit"] - a["experts_hit"]) \
            / (b["steps"] - a["steps"])
    else:
        hit = float(experts)
    dense = param_count(config) - experts * sh["expert_params"] \
        - sh["vocab"] * sh["d_model"]
    return per_param * (dense + hit * sh["expert_params"]
                        + 4 * occupancy * sh["d_model"]) \
        + occupancy * _ring_rows(sh, mean_context) \
        * sh["kv_bytes_per_layer_token"]


def verify_attention_work(config: dict, occupancy: float,
                          mean_context: float) -> tuple:
    """(operations, bytes) the attention of one verify-and-draft step
    REQUIRES, the module's included, whatever implements it: for each
    occupied slot, in every ring, the rows a query at ``mean_context`` must
    see read once for BOTH of the slot's query rows, the two new rows of K
    and V written once, and the scores and weighted sums of two query rows
    over those keys (2 operations a multiply-add, two products, every query
    head)."""
    sh = shape(config)
    keys = _ring_rows(sh, mean_context)
    rings = sh["n_global"] + 1 + sh["n_window"]
    ops = occupancy * 2 * 4.0 * sh["q_width"] * keys
    io = occupancy * (keys + 2 * rings) * sh["kv_bytes_per_layer_token"]
    return ops, io


def prefill_chunk_work(config: dict, weight_bytes: float, real_tokens: float,
                       expert_rows: float, mean_keys: float = 0.0,
                       last_share: float = 1.0) -> tuple:
    """(operations, bytes) one execution of the prefill chunk program
    requires for ``real_tokens`` real tokens of one request that made
    ``expert_rows`` token-expert pairs, the module's pass over them
    included. Bytes: every stored matrix once (at some hundreds of tokens a
    chunk every held expert is hit), but of the embedding's table the rows
    looked up (a token's own, for the main stack and, one on, for the
    module) and the head's table only in the ``last_share`` of executions
    that end a prompt; the K/V rows of the keys a query may see
    (``_ring_rows``). Operations: 2 a parameter of every matrix a token
    passes, 2 x one expert's parameters a pair, the scores and the weighted
    sum over those keys a query, the head TWICE (the main stack's token and
    the first draft) for the last token of a last chunk. No padding, no
    un-hit expert's product."""
    sh = shape(config)
    d = sh["d_model"]
    row = weight_bytes / param_count(config) * d  # bytes a table row
    keys = _ring_rows(sh, mean_keys)
    io = weight_bytes - row * (sh["vocab"] - 2 * real_tokens) \
        - (1.0 - last_share) * row * sh["vocab"] \
        + keys * sh["kv_bytes_per_layer_token"]
    a_token = (len(sh["layout"]) + 1) * sh["attention_params"] \
        + (sh["dense_layers"] + 1) * sh["dense_ff_params"] \
        + sh["sparse_layers"] * (sh["router_params"] + sh["shared_params"]) \
        + sh["module_params"]
    ops = 2.0 * real_tokens * a_token \
        + 2.0 * expert_rows * sh["expert_params"] \
        + real_tokens * 4.0 * sh["q_width"] * keys \
        + last_share * 2 * 2.0 * sh["vocab"] * d
    return ops, io


def _no_training(what: str):
    raise NotImplementedError(
        f"{what}: no training cell of the exaone_moe family exists (the "
        f"dropless share layer has no gradients); the family is served only")


def train_flops_per_token(config: dict) -> float:
    _no_training("train_flops_per_token")


def attention_calls(config: dict, rows: int) -> tuple:
    _no_training("attention_calls")


def build_train(config: dict, mesh) -> dict:
    _no_training("build_train")


def system_config(config: dict):
    """The program's configuration; refuses a file that states what the
    program does not run."""
    from ray_tpu.models.exaone_moe import GAINS, ExaoneMoeConfig

    a = config.get("assumed", {})
    sh = shape(config)
    want = {"tie_word_embeddings": False, "norm_topk_prob": True,
            "scoring_func": "sigmoid", "n_group": 1, "topk_group": 1,
            "hidden_act": "silu", "num_nextn_predict_layers": 1,
            "mtp_layer_types": ["full_attention"],
            "first_k_dense_replace": sh["dense_layers"]}
    for key, value in want.items():
        if config.get(key, value) != value:
            raise ValueError(f"{key} = {config[key]!r}: the program runs "
                             f"{value!r} only")
    if a.get("norm_placement", "post") != "post":
        raise ValueError(f"assumed norm_placement {a['norm_placement']!r}: "
                         f"the program norms each sublayer's OUTPUT")
    if a.get("window_keys_with_own", sh["window"]) != sh["window"]:
        raise ValueError("assumed window_keys_with_own: the program's "
                         "window is sliding_window keys with the query's "
                         "own")
    kinds = config["mlp_layer_types"]
    if len(sh["layout"]) != config["num_hidden_layers"] \
            or len(kinds) != len(sh["layout"]) \
            or kinds != ["dense"] * sh["dense_layers"] \
            + ["sparse"] * sh["sparse_layers"] \
            or [int(w > 0) for w in config["sliding_windows"]] \
            != sh["layout"]:
        raise ValueError("layer_types, mlp_layer_types and sliding_windows "
                         "must be lists of num_hidden_layers entries, the "
                         "dense layers leading, a window where the layer "
                         "slides")
    return ExaoneMoeConfig(
        vocab_size=sh["vocab"], d_model=sh["d_model"],
        window_layout=tuple(sh["layout"]), dense_layers=sh["dense_layers"],
        window=sh["window"], eps=config["rms_norm_eps"],
        n_head=config["num_attention_heads"],
        n_kv_head=config["num_key_value_heads"], head_dim=config["head_dim"],
        rope_theta=float(config["rope_parameters"]["rope_theta"]),
        dense_ff=config["intermediate_size"], n_experts=sh["router_width"],
        top_k=config["num_experts_per_tok"], experts_held=(0, sh["held"]),
        expert_ff=config["moe_intermediate_size"],
        shared_ff=config["moe_intermediate_size"]
        * config["num_shared_experts"],
        routed_scale=float(config["routed_scaling_factor"]),
        gains=tuple(a.get("init_gains", dict(GAINS)).items()))


def reference_kwargs(config: dict) -> dict:
    sh = shape(config)
    slides = tuple(bool(v) for v in sh["layout"])
    return {"rotates": slides, "windows": slides,
            "window": config["sliding_window"],
            "eps": config["rms_norm_eps"],
            "n_head": config["num_attention_heads"],
            "n_kv_head": config["num_key_value_heads"],
            "head_dim": config["head_dim"],
            "rope_theta": float(config["rope_parameters"]["rope_theta"]),
            "top_k": config["num_experts_per_tok"],
            "routed_scale": float(config["routed_scaling_factor"]),
            "first_expert": 0,
            "norm_placement": config.get("assumed", {}).get(
                "norm_placement", "post")}


def _block_to_reference(p, config: dict) -> dict:
    out = {ref: p[name] for name, ref in BLOCK_NAMES.items()}
    if "w_in" in p:
        ff = config["intermediate_size"]
        out.update(gate_proj=p["w_in"][:, :ff], up_proj=p["w_in"][:, ff:],
                   down_proj=p["w_down"])
        return out
    ff = config["moe_intermediate_size"]
    sf = ff * config["num_shared_experts"]
    out.update(router=p["router"],
               e_score_correction_bias=p["router_bias"],
               experts_gate=p["w1"][..., :ff], experts_up=p["w1"][..., ff:],
               experts_down=p["w2"],
               shared_gate=p["shared_w1"][:, :sf],
               shared_up=p["shared_w1"][:, sf:], shared_down=p["shared_w2"])
    return out


def to_reference(params, config: dict):
    """The system's parameter tree under the reference's names. The leaves
    go over as they are stored (bfloat16 widens exactly, and the reference
    widens each where it uses it): no float32 copy of the weights is made.
    Nothing is padded, so nothing is cut; a gated MLP's ``[gate, up]``
    halves, side by side in the system, are taken apart."""
    m = params["mtp"]
    return {"embed_tokens": params["embed"], "lm_head": params["lm_head"],
            "norm": params["norm_f"],
            "layers": [_block_to_reference(p, config)
                       for p in params["layers"]],
            "module": {"hnorm": m["norm_h"], "enorm": m["norm_e"],
                       "eh_proj": m["w_eh"], "norm": m["norm_m"],
                       "block": _block_to_reference(m["block"], config)}}


def init_params(config: dict, seed: int):
    """Seeded weights exactly as the engine makes its own."""
    import jax

    from ray_tpu.models.exaone_moe import exaone_moe_init

    return exaone_moe_init(jax.random.PRNGKey(seed), system_config(config))


def engine_bind(config: dict, engine: dict, seed: int) -> dict:
    """Arguments of ``LLMEngine`` for this configuration. The family's
    bundle holds a verify-and-draft step, so the engine serves with it:
    there is nothing to pass for that."""
    return {"model": "exaone_moe", "config": system_config(config),
            "seed": seed, **engine}


def _serving_programs(cfg, prefill=None):
    """The two jitted programs ``_verify_steps`` runs, the cache donated:
    the prefill (``prefill``: the model's own, or a test's at a toy chunk)
    and the verify-and-draft step. A caller that runs ``_verify_steps``
    more than once over one configuration keeps the pair: a compile each."""
    import jax

    from ray_tpu.models.exaone_moe import (exaone_moe_prefill,
                                           exaone_moe_verify_step)

    prefill = prefill or exaone_moe_prefill

    def one(p, c, t, k):
        logits, c, _, served, drafts = exaone_moe_verify_step(p, c, t, k, cfg)
        return logits, c, served, drafts

    return (jax.jit(lambda p, c, t, s, k: prefill(p, c, t, s, k, cfg),
                    donate_argnums=(1,)),
            jax.jit(one, donate_argnums=(1,)))


def _verify_steps(config: dict, params, prompts, lengths, follow, slots: int,
                  cache_len: int, wrong_every: int = REJECT_EVERY, cfg=None,
                  programs=None):
    """The serving path's own functions through a fresh cache:
    ``exaone_moe_prefill`` of the padded ``prompts`` [R, P] (the chunk
    program over every chunk, the module's pass included), then
    ``exaone_moe_verify_step`` until every column of ``follow`` [R, N] has
    been the first of a slot's two rows. A step's draft is the NEXT follow
    token, so the device accepts it (the host takes both rows' logits and
    moves two on), but at every ``wrong_every``-th step (0: never) another
    token, so the device rejects it (the host takes the first row's and
    moves one on; the step after it runs over the rejected row's place).
    -> (main logits [R, 1 + N, V], module logits [R, 1 + N, V]: row i the
    module's prediction made beside main row i, for the token after the
    one main row i predicts; counts [steps, R] the device's own count a
    step, wrong [steps] which steps were handed a wrong draft). ``cfg``:
    the program's configuration where it is not the file's (a test's
    float32 one); ``programs``: ``_serving_programs(cfg)`` kept from an
    earlier call."""
    import jax.numpy as jnp

    from ray_tpu.models.exaone_moe import exaone_moe_init_cache

    cfg = cfg or system_config(config)
    r, n = follow.shape
    cache = exaone_moe_init_cache(cfg, slots, cache_len)
    slot_idx = jnp.arange(r, dtype=jnp.int32)
    prefill, step = programs or _serving_programs(cfg)
    logits, cache, drafts = prefill(params, cache, prompts, slot_idx, lengths)
    main, module, counts, wrong = [logits], [drafts], [], []
    pad = slots - r
    at, steps = 0, 0
    while at < n:
        steps += 1
        bad = bool(wrong_every) and steps % wrong_every == 0
        last = at + 1 >= n
        draft = follow[:, at] if last else follow[:, at + 1]
        if bad or last:
            draft = jnp.mod(draft + 1, shape(config)["vocab"])
        toks = jnp.concatenate([
            jnp.stack([follow[:, at], draft], axis=1),
            jnp.zeros((pad, 2), jnp.int32)])
        pos = jnp.concatenate([lengths + at, jnp.zeros((pad,), jnp.int32)])
        logits, cache, served, drafts = step(params, cache, toks, pos)
        take = 1 if bad or last else 2
        main.extend(logits[:r, i] for i in range(take))
        module.extend(drafts[:r, i] for i in range(take))
        counts.append(served[:r, 0])
        wrong.append(bad or last)
        at += take
    return (jnp.stack(main, axis=1), jnp.stack(module, axis=1),
            jnp.stack(counts), wrong)


def serve_logits(config: dict, params, prompts, lengths, follow, slots: int,
                 cache_len: int):
    """Logits of the serving path's own functions (``_verify_steps``): the
    main stack's, through prefill chunks and then verify steps with
    accepted and rejected drafts interleaved. -> [R, 1 + N, V]."""
    return _verify_steps(config, params, prompts, lengths, follow, slots,
                         cache_len)[0]
