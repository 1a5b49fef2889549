"""How the system under test is built from a ``qwen3_next``-family
configuration file (``model_type: qwen3_next``: three Gated DeltaNet layers
to one gated-attention layer, routed experts and a gated shared expert in
every layer), how its weights map onto the reference's names, and the
family's arithmetic. ``README.md`` beside this file lists the interface;
what differs here:

* **Three kinds of slot state**: a linear layer counts a float32 delta
  state (a matrix a value head) and a convolution tail a slot, a full layer
  a K/V ring's rows a token.
* **Bytes from counters** (the experts a step really hit), state read and
  written, bfloat16 leaves handed to the reference unconverted: as
  ``families/granite_hybrid.py``.
* **The head is a table of its own**: a step reads the head's table once
  and, of the embedding's, only the rows it looks up.
* **The released layout taken apart**: the program keeps ``in_qkvz`` and
  ``in_ba`` interleaved per key head as the checkpoint has them;
  ``to_reference`` hands the reference six plain matrices.
* **``prefill_chunk_work``** (``metrics/prefill_chunk_roofline.py``),
  **``gated_delta_scan_work``** and **``gated_delta_step_work``**
  (``metrics/gated_delta_scan_roofline.py``, ``gated_delta_step_
  roofline.py``), beside the README's table: what the delta rule's two
  forms REQUIRE, whatever implements them.
* **``branch_readings``**: what the seeded draw (``assumed.init_gains``)
  makes of each kind of layer's branches, and in how many tokens a head's
  state halves; the configuration file quotes its readings.
* **The training functions refuse**: no training cell of this family exists
  (the blocked scan has no backward, and the share layer no gradients).

The configuration file holds the released ``config.json``'s keys.
``num_hidden_layers`` is the layers that run, ``num_experts`` the experts
HELD (``num_experts_published`` the router's width) and ``vocab_size`` the
rows of both tables held here; the published values stand beside them. The
step runs every one of ``max_batch + 1`` rows, free slots too, so the
counters count what the step really routed; the cell's slots are full.
"""

from __future__ import annotations

# The keys a configuration file of this family may carry under ``assumed``
# (beside notes whose key ends in ``why``).
ASSUMED = frozenset({"experts_held", "delta_state_dtype", "init_gains",
                     "scan_block"})

LINEAR, FULL = "linear_attention", "full_attention"

# A layer's weights that go over as they are: the system's name -> the
# reference's (the released checkpoint's, shortened). ``in_qkvz`` and
# ``in_ba`` are taken apart in ``to_reference``.
MIXER_NAMES = {
    LINEAR: {"conv_w": "conv_w", "dt_bias": "dt_bias", "a_log": "A_log",
             "gate_norm": "norm_w", "out_proj": "out_proj"},
    FULL: {"wq": "q_proj", "wk": "k_proj", "wv": "v_proj", "wo": "o_proj",
           "q_norm": "q_norm", "k_norm": "k_norm"},
}
LAYER_NAMES = {"norm": "input_layernorm", "norm2": "post_attention_layernorm",
               "router": "router", "w1": "experts_in", "w2": "experts_out",
               "shared_w1": "shared_in", "shared_w2": "shared_out",
               "shared_gate": "shared_gate"}


def layer_types(config: dict) -> list:
    every = config["full_attention_interval"]
    return [FULL if (i + 1) % every == 0 else LINEAR
            for i in range(config["num_hidden_layers"])]


def shape(config: dict) -> dict:
    """The sizes the arithmetic needs, from the file."""
    a = config.get("assumed", {})
    d = config["hidden_size"]
    kinds = layer_types(config)
    hk, hv = config["linear_num_key_heads"], config["linear_num_value_heads"]
    dk, dv = config["linear_key_head_dim"], config["linear_value_head_dim"]
    conv_dim = 2 * hk * dk + hv * dv
    q = config["num_attention_heads"] * config["head_dim"]
    kv = config["num_key_value_heads"] * config["head_dim"]
    first, held = a.get("experts_held", [0, config["num_experts"]])
    linear_matrices = d * (2 * hk * dk + 2 * hv * dv) + d * 2 * hv \
        + hv * dv * d
    full_matrices = d * 2 * q + 2 * d * kv + q * d
    return {
        "vocab": config["vocab_size"],
        "n_positions": config["max_position_embeddings"],
        "layer_types": kinds, "d_model": d,
        "n_linear": kinds.count(LINEAR), "n_full": kinds.count(FULL),
        "q_width": q, "key_heads": hk, "value_heads": hv, "key_dim": dk,
        "value_dim": dv, "conv_dim": conv_dim,
        "first_expert": first, "experts_held": held,
        "router_width": config.get("num_experts_published",
                                   config["num_experts"]),
        "linear_matrices": linear_matrices, "full_matrices": full_matrices,
        "linear_params": linear_matrices
        + config["linear_conv_kernel_dim"] * conv_dim + 2 * hv + dv,
        "full_params": full_matrices + 2 * config["head_dim"],
        # gated: [a, b] = W1 h and W2, three matrices' worth
        "expert_params": 3 * d * config["moe_intermediate_size"],
        "shared_params": 3 * d * config["shared_expert_intermediate_size"],
        # bfloat16 merged K and V rows of the full layers
        "kv_bytes_per_token": 2 * kinds.count(FULL) * kv * 2,
        # a slot's state: a float32 matrix a value head and the bfloat16
        # convolution tail, in every linear layer
        "state_bytes_per_slot": kinds.count(LINEAR) * (
            hv * dk * dv * 4
            + (config["linear_conv_kernel_dim"] - 1) * conv_dim * 2),
    }


def _token_params(config: dict) -> int:
    """Parameters of the matrices every prompt token passes: the mixers'
    projections, routers, shared experts and their gates (not the norms'
    scales, the convolution or the head, which takes a chunk's last token
    only)."""
    sh = shape(config)
    d = sh["d_model"]
    return sh["n_linear"] * sh["linear_matrices"] \
        + sh["n_full"] * sh["full_matrices"] \
        + len(sh["layer_types"]) * (d * sh["router_width"]
                                    + sh["shared_params"] + d)


def param_count(config: dict) -> int:
    """Parameters as the system holds them: the layers that run, the
    experts held, both vocabulary tables' slices, the last norm. No table
    is padded."""
    sh = shape(config)
    d = sh["d_model"]
    every = 2 * d + d * sh["router_width"] + sh["shared_params"] + d \
        + sh["experts_held"] * sh["expert_params"]
    return sh["n_linear"] * sh["linear_params"] \
        + sh["n_full"] * sh["full_params"] \
        + len(sh["layer_types"]) * every + 2 * sh["vocab"] * d + d


def cache_bytes(config: dict, slots: int, cache_len: int) -> float:
    """Bytes of the engine's cache, by shape: the full layers' K/V rings
    and, for every linear layer, the delta state and the convolution tail."""
    sh = shape(config)
    return slots * (cache_len * sh["kv_bytes_per_token"]
                    + sh["state_bytes_per_slot"])


def decode_step_bytes(config: dict, weight_bytes: float, occupancy: float,
                      mean_context: float, counters: dict) -> float:
    """Bytes one decode step must move: every weight outside the experts
    once but the embedding's table (a step reads the rows it looks up), one
    expert's bytes for each held expert the step hit (``experts_hit`` a
    step, from the window's two ``llm_stats()``; every held expert where
    there are none), and for the occupied slots the live K/V rows read and
    the delta state and tail read and written."""
    sh = shape(config)
    per_param = weight_bytes / param_count(config)
    experts = len(sh["layer_types"]) * sh["experts_held"]
    a, b = counters.get("open") or {}, counters.get("close") or {}
    if "experts_hit" in a and "experts_hit" in b and b["steps"] > a["steps"]:
        hit = (b["experts_hit"] - a["experts_hit"]) \
            / (b["steps"] - a["steps"])
    else:
        hit = float(experts)
    dense = param_count(config) - experts * sh["expert_params"] \
        - sh["vocab"] * sh["d_model"]
    return per_param * (dense + hit * sh["expert_params"]
                        + occupancy * sh["d_model"]) \
        + occupancy * (mean_context * sh["kv_bytes_per_token"]
                       + 2 * sh["state_bytes_per_slot"])


def _delta_ops_per_token(sh: dict) -> float:
    """Operations of the recurrence itself for one token over one layer's
    value heads: the decay (one an element of the state), what the state
    holds of k, the rank-one write and the readout (a multiply-add each an
    element): ``7 dk dv`` a head. No blocked form's products."""
    return 7.0 * sh["value_heads"] * sh["key_dim"] * sh["value_dim"]


def gated_delta_scan_work(config: dict, real_tokens: float) -> tuple:
    """(operations, bytes) the linear layers' scans of ONE chunk of
    ``real_tokens`` real tokens REQUIRE, whatever implements the rule.
    Bytes, a layer: q and k (a key head's lanes each), v, g and beta read
    once and the output written once, in the types the configuration
    states (bfloat16 activations, float32 g and beta), and the slot's state
    read and written once a chunk. Operations: the recurrence's own
    (``_delta_ops_per_token``). No padded row."""
    sh = shape(config)
    row = 2 * (2 * sh["key_heads"] * sh["key_dim"]
               + 2 * sh["value_heads"] * sh["value_dim"]) \
        + 2 * 4 * sh["value_heads"]
    state = sh["value_heads"] * sh["key_dim"] * sh["value_dim"] * 4
    io = sh["n_linear"] * (real_tokens * row + 2 * state)
    ops = sh["n_linear"] * real_tokens * _delta_ops_per_token(sh)
    return ops, io


def gated_delta_step_work(config: dict, occupancy: float) -> tuple:
    """(operations, bytes) the linear layers' updates of one decode step
    REQUIRE: each occupied slot's state and convolution tail read and
    written once a layer, the recurrence's operations a token. No free
    slot."""
    sh = shape(config)
    return (occupancy * sh["n_linear"] * _delta_ops_per_token(sh),
            occupancy * 2 * sh["state_bytes_per_slot"])


def prefill_chunk_work(config: dict, weight_bytes: float, real_tokens: float,
                       expert_rows: float, mean_keys: float = 0.0,
                       last_share: float = 1.0) -> tuple:
    """(operations, bytes) one execution of the prefill chunk program
    requires for ``real_tokens`` real tokens of one request of which
    ``expert_rows`` token-expert pairs landed on the experts held here.
    Bytes: every stored matrix once (at some hundreds of tokens a chunk
    every held expert is hit), but of the embedding's table the rows looked
    up and the head's table only in the ``last_share`` of executions that
    end a prompt; the slot's state read and written; the K/V rows of the
    ``mean_keys`` keys a query may see. Operations: 2 a parameter of every
    matrix a token passes, 2 x one expert's parameters a pair, the scores
    and the weighted sum over ``mean_keys`` keys a query in the full
    layers, the recurrence's own in the linear ones, the head for the last
    token of a last chunk. No padding, no un-hit expert's product."""
    sh = shape(config)
    d = sh["d_model"]
    row = weight_bytes / param_count(config) * d  # bytes a table row
    io = weight_bytes - row * (sh["vocab"] - real_tokens) \
        - (1.0 - last_share) * row * sh["vocab"] \
        + 2 * sh["state_bytes_per_slot"] \
        + mean_keys * sh["kv_bytes_per_token"]
    ops = 2.0 * real_tokens * _token_params(config) \
        + 2.0 * expert_rows * sh["expert_params"] \
        + real_tokens * sh["n_full"] * 4.0 * sh["q_width"] * mean_keys \
        + gated_delta_scan_work(config, real_tokens)[0] \
        + last_share * 2.0 * sh["vocab"] * d
    return ops, io


def _no_training(what: str):
    raise NotImplementedError(
        f"{what}: no training cell of the qwen3_next family exists (the "
        f"blocked delta-rule scan has no backward and the dropless share "
        f"layer no gradients); the family is served only")


def train_flops_per_token(config: dict) -> float:
    _no_training("train_flops_per_token")


def attention_calls(config: dict, rows: int) -> tuple:
    _no_training("attention_calls")


def build_train(config: dict, mesh) -> dict:
    _no_training("build_train")


def system_config(config: dict):
    """The program's configuration; refuses a file that states what the
    program does not run."""
    import jax.numpy as jnp

    from ray_tpu.models.qwen3_next import GAINS, Qwen3NextConfig

    a = config.get("assumed", {})
    sh = shape(config)
    want = {"hidden_act": "silu", "tie_word_embeddings": False,
            "norm_topk_prob": True, "decoder_sparse_step": 1,
            "mlp_only_layers": [], "rope_scaling": None,
            "use_sliding_window": False, "attention_bias": False}
    for key, value in want.items():
        if config.get(key, value) != value:
            raise ValueError(f"{key} = {config[key]!r}: the program runs "
                             f"{value!r} only")
    if a.get("delta_state_dtype", "float32") != "float32":
        raise ValueError(f"assumed delta_state_dtype "
                         f"{a['delta_state_dtype']}: the program keeps a "
                         f"float32 delta state")
    if sh["experts_held"] != config["num_experts"]:
        raise ValueError("assumed.experts_held does not hold num_experts "
                         "experts")
    return Qwen3NextConfig(
        # the file's statement builds the program, not the class's default
        delta_state_dtype=getattr(jnp, a.get("delta_state_dtype",
                                             "float32")),
        vocab_size=sh["vocab"], d_model=sh["d_model"],
        n_layer=config["num_hidden_layers"],
        full_attention_interval=config["full_attention_interval"],
        eps=config["rms_norm_eps"], n_head=config["num_attention_heads"],
        n_kv_head=config["num_key_value_heads"], head_dim=config["head_dim"],
        partial_rotary_factor=float(config["partial_rotary_factor"]),
        rope_theta=float(config["rope_theta"]),
        linear_key_heads=sh["key_heads"], linear_value_heads=sh["value_heads"],
        linear_key_dim=sh["key_dim"], linear_value_dim=sh["value_dim"],
        conv_kernel=config["linear_conv_kernel_dim"],
        scan_block=int(a.get("scan_block", 64)),
        n_experts=sh["router_width"],
        experts_held=(sh["first_expert"], sh["experts_held"]),
        top_k=config["num_experts_per_tok"],
        expert_ff=config["moe_intermediate_size"],
        shared_ff=config["shared_expert_intermediate_size"],
        gains=tuple(a.get("init_gains", dict(GAINS)).items()))


def reference_kwargs(config: dict) -> dict:
    sh = shape(config)
    return {"layer_types": tuple(sh["layer_types"]),
            "eps": config["rms_norm_eps"],
            "n_head": config["num_attention_heads"],
            "n_kv_head": config["num_key_value_heads"],
            "head_dim": config["head_dim"],
            "rope_theta": float(config["rope_theta"]),
            "rotary_dim": int(config["head_dim"]
                              * config["partial_rotary_factor"]),
            "key_heads": sh["key_heads"], "value_heads": sh["value_heads"],
            "key_dim": sh["key_dim"], "value_dim": sh["value_dim"],
            "top_k": config["num_experts_per_tok"],
            "first_expert": sh["first_expert"]}


def split_released(p: dict, sh: dict) -> dict:
    """``in_qkvz`` [d, Hk (2 dk + 2 per dv)] and ``in_ba`` [d, Hk 2 per],
    interleaved per KEY head as the released checkpoint has them (q dk | k
    dk | v per x dv | z per x dv; b per | a per), -> the reference's six
    plain matrices, every head's columns side by side in head order."""
    d = p["in_qkvz"].shape[0]
    hk, dk = sh["key_heads"], sh["key_dim"]
    vw = sh["value_heads"] // hk * sh["value_dim"]
    per = sh["value_heads"] // hk
    qkvz = p["in_qkvz"].reshape(d, hk, 2 * dk + 2 * vw)
    ba = p["in_ba"].reshape(d, hk, 2 * per)
    flat = lambda x: x.reshape(d, -1)
    return {"q_proj": flat(qkvz[..., :dk]),
            "k_proj": flat(qkvz[..., dk:2 * dk]),
            "v_proj": flat(qkvz[..., 2 * dk:2 * dk + vw]),
            "z_proj": flat(qkvz[..., 2 * dk + vw:]),
            "b_proj": flat(ba[..., :per]), "a_proj": flat(ba[..., per:])}


def to_reference(params, config: dict):
    """The system's parameter tree under the reference's names. The leaves
    go over as they are stored (bfloat16 widens exactly, and the reference
    widens each where it uses it): no float32 copy of the weights is made.
    Nothing is padded, so nothing is cut; the two interleaved projections
    of a linear layer are taken apart (``split_released``)."""
    sh = shape(config)
    layers = []
    for kind, p in zip(sh["layer_types"], params["layers"]):
        layer = {ref: p[name] for name, ref in
                 {**LAYER_NAMES, **MIXER_NAMES[kind]}.items()}
        if kind == LINEAR:
            layer.update(split_released(p, sh))
        layers.append(layer)
    return {"embed_tokens": params["embed"], "lm_head": params["lm_head"],
            "norm": params["norm_f"], "layers": layers}


def init_params(config: dict, seed: int):
    """Seeded weights exactly as the engine makes its own."""
    import jax

    from ray_tpu.models.qwen3_next import qwen3_next_init

    return qwen3_next_init(jax.random.PRNGKey(seed), system_config(config))


def engine_bind(config: dict, engine: dict, seed: int) -> dict:
    """Arguments of ``LLMEngine`` for this configuration."""
    return {"model": "qwen3_next", "config": system_config(config),
            "seed": seed, **engine}


def serve_logits(config: dict, params, prompts, lengths, follow, slots: int,
                 cache_len: int):
    """Logits of the serving path's own functions: ``qwen3_next_prefill`` of
    the padded ``prompts`` [R, P] (the chunk program over every chunk of the
    window), then one ``qwen3_next_decode_step`` per column of ``follow``
    [R, N] through a fresh cache (rings, tails and delta states).
    -> [R, 1 + N, V]."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.qwen3_next import (qwen3_next_decode_step,
                                           qwen3_next_init_cache,
                                           qwen3_next_prefill)

    cfg = system_config(config)
    r = prompts.shape[0]
    cache = qwen3_next_init_cache(cfg, slots, cache_len)
    slot_idx = jnp.arange(r, dtype=jnp.int32)
    prefill = jax.jit(
        lambda p, c, t, s, n: qwen3_next_prefill(p, c, t, s, n, cfg),
        donate_argnums=(1,))
    step = jax.jit(
        lambda p, c, t, n: qwen3_next_decode_step(p, c, t, n, cfg)[:2],
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


def branch_readings(config: dict, params, tokens) -> dict:
    """What the seeded draw makes of the first PERIOD's layers, by the
    reference's own functions in float32, over tokens [R, T]: for the first
    linear layer and the first full layer the rms of the stream the layer
    receives and of each branch as it is added (the mixer, the routed
    experts held here, the shared expert behind its gate), the spread of
    the attention's scores, the share of a token's ten experts that are
    held, and in how many tokens each linear head's state halves under its
    mean decay (``ln 2 / -mean(g)``). Each branch of some tenths of the
    stream says that a comparison of logits holds all of them."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.loading import sibling

    ref = sibling(__file__, "../reference/qwen3_next.py")
    kw = reference_kwargs(config)
    p = to_reference(params, config)

    def rms(x):
        return float(jnp.sqrt(jnp.mean(jnp.square(x))))

    out = {}
    with jax.default_matmul_precision("highest"):
        x = ref._w(p["embed_tokens"][tokens])
        seen = set()
        for kind, layer in zip(kw["layer_types"], p["layers"]):
            y = ref.zero_centred_norm(x, layer["input_layernorm"], kw["eps"])
            if kind == LINEAR:
                mixer = ref.gated_delta_net(
                    layer, y, eps=kw["eps"], key_heads=kw["key_heads"],
                    value_heads=kw["value_heads"], key_dim=kw["key_dim"],
                    value_dim=kw["value_dim"])
            else:
                mixer = ref.gated_attention(
                    layer, y, eps=kw["eps"], n_head=kw["n_head"],
                    n_kv_head=kw["n_kv_head"], head_dim=kw["head_dim"],
                    rope_theta=kw["rope_theta"], rotary_dim=kw["rotary_dim"])
            x1 = x + mixer
            h = ref.zero_centred_norm(
                x1, layer["post_attention_layernorm"], kw["eps"])
            flat = h.reshape(-1, h.shape[-1])
            routed = ref.routed_experts(layer, flat, top_k=kw["top_k"],
                                        first_expert=kw["first_expert"])
            shared = ref.shared_expert(layer, flat)
            if kind not in seen:
                seen.add(kind)
                got = {"stream_rms": rms(x), "mixer_rms": rms(mixer),
                       "routed_rms": rms(routed), "shared_rms": rms(shared)}
                held = layer["experts_in"].shape[0]
                weights = ref.gating(flat, layer["router"], kw["top_k"])
                got["held_share_of_chosen"] = float(jnp.mean(jnp.sum(
                    weights[:, kw["first_expert"]:kw["first_expert"] + held]
                    > 0, axis=-1)) / kw["top_k"])
                if kind == LINEAR:
                    g = -jnp.exp(ref._w(layer["A_log"])) * jax.nn.softplus(
                        y @ ref._w(layer["a_proj"]) + ref._w(layer["dt_bias"]))
                    halves = np.log(2.0) / -np.asarray(
                        jnp.mean(g, axis=(0, 1)))
                    got["state_halves_in_tokens"] = [
                        float(v) for v in np.quantile(halves, (0, 0.5, 1))]
                else:
                    q = ref.zero_centred_norm(
                        (y @ ref._w(layer["q_proj"])).reshape(
                            *y.shape[:-1], kw["n_head"], -1)[
                                ..., :kw["head_dim"]],
                        layer["q_norm"], kw["eps"])
                    k = ref.zero_centred_norm(
                        (y @ ref._w(layer["k_proj"])).reshape(
                            *y.shape[:-1], kw["n_kv_head"], -1),
                        layer["k_norm"], kw["eps"])
                    got["score_spread"] = rms(q) * rms(k)
                out[kind] = got
            x = x1 + (routed + shared).reshape(x.shape)
            if len(seen) == 2:
                break
    return out
