"""How the system under test is built from a ``smallthinker``-family
configuration file (``model_name: smallthinker_*``: one global attention
layer without a position embedding to three rotary window layers, a router
read before attention, gated-ReLU experts in every layer), how its weights
map onto the reference's names, and the family's arithmetic. ``README.md``
beside this file lists the interface; what differs here:

* **Two ring lengths in one cache**: a global layer counts ``cache_len``
  rows a slot, a window layer ``sliding_window_size`` rows whatever
  ``cache_len`` is; a decode step must read a global ring's live rows and
  of a window ring ``min(context, window)``.
* **Bytes from counters** (the experts a step really hit), bfloat16 leaves
  handed to the reference unconverted: as ``families/qwen3_next.py``. Every
  expert of the router is held.
* **The head is a table of its own**: a step reads the head's table once
  and, of the embedding's, only the rows it looks up.
* **``prefill_chunk_work``** (``metrics/prefill_chunk_roofline.py``) and
  **``window_chunk_attention_work``** (``metrics/window_chunk_attention_
  roofline.py``), beside the README's table: what a chunk and a window
  layer's chunk attention REQUIRE, whatever implements them.
* **``branch_readings``**: what the seeded draw (``assumed.init_gains``)
  makes of a global and a window layer's branches and of the router's
  scores; the configuration file quotes its readings.
* **The training functions refuse**: no training cell of this family exists
  (the dropless share layer has no gradients).

The configuration file holds the released ``config.json``'s keys.
``num_hidden_layers`` is the layers that run (with ``rope_layout`` and
``sliding_window_layout`` cut to as many entries) and ``vocab_size`` the
rows of both tables held here; the published values stand beside them. The
step runs every one of ``max_batch + 1`` rows, free slots too, so the
counters count what the step really routed; the cell's slots are full.
"""

from __future__ import annotations

# The keys a configuration file of this family may carry under ``assumed``
# (beside notes whose key ends in ``why``).
ASSUMED = frozenset({"router_reads", "window_keys_with_own", "init_gains"})

LAYER_NAMES = {"norm": "input_layernorm", "norm2": "post_attention_layernorm",
               "wq": "q_proj", "wk": "k_proj", "wv": "v_proj", "wo": "o_proj",
               "router": "router", "w2": "experts_down"}


def shape(config: dict) -> dict:
    """The sizes the arithmetic needs, from the file."""
    d = config["hidden_size"]
    layout = [int(v) for v in config["sliding_window_layout"]]
    q = config["num_attention_heads"] * config["head_dim"]
    kv = config["num_key_value_heads"] * config["head_dim"]
    experts = config["moe_num_primary_experts"]
    return {
        "vocab": config["vocab_size"],
        "n_positions": config["max_position_embeddings"],
        "d_model": d, "layout": layout,
        "n_window": sum(layout), "n_global": len(layout) - sum(layout),
        "window": config["sliding_window_size"],
        "q_width": q, "experts": experts,
        "attention_params": 2 * d * q + 2 * d * kv,
        "router_params": d * experts,
        # gated: [a, b] = W1 m and W2, three matrices' worth
        "expert_params": 3 * d * config["moe_ffn_hidden_size"],
        # bfloat16 merged K and V rows of ONE layer, a token
        "kv_bytes_per_layer_token": 2 * kv * 2,
    }


def param_count(config: dict) -> int:
    """Parameters as the system holds them: the layers that run, every
    expert, both vocabulary tables' slices, the last norm. No table is
    padded."""
    sh = shape(config)
    d = sh["d_model"]
    layer = sh["attention_params"] + sh["router_params"] \
        + sh["experts"] * sh["expert_params"] + 2 * d
    return len(sh["layout"]) * layer + 2 * sh["vocab"] * d + d


def cache_bytes(config: dict, slots: int, cache_len: int) -> float:
    """Bytes of the engine's cache, by shape: the global layers' rings of
    ``cache_len`` rows and the window layers' of ``window`` rows."""
    sh = shape(config)
    return slots * sh["kv_bytes_per_layer_token"] * (
        sh["n_global"] * cache_len + sh["n_window"] * sh["window"])


def decode_step_bytes(config: dict, weight_bytes: float, occupancy: float,
                      mean_context: float, counters: dict) -> float:
    """Bytes one decode step must move: every weight outside the experts
    once but the embedding's table (a step reads the rows it looks up), one
    expert's bytes for each expert the step hit (``experts_hit`` a step,
    from the window's two ``llm_stats()``; every expert where there are
    none), and for the occupied slots the live rows of the global rings and
    ``min(context, window)`` rows of the window rings."""
    sh = shape(config)
    per_param = weight_bytes / param_count(config)
    experts = len(sh["layout"]) * sh["experts"]
    a, b = counters.get("open") or {}, counters.get("close") or {}
    if "experts_hit" in a and "experts_hit" in b and b["steps"] > a["steps"]:
        hit = (b["experts_hit"] - a["experts_hit"]) \
            / (b["steps"] - a["steps"])
    else:
        hit = float(experts)
    dense = param_count(config) - experts * sh["expert_params"] \
        - sh["vocab"] * sh["d_model"]
    rows = sh["n_global"] * mean_context \
        + sh["n_window"] * min(mean_context, sh["window"])
    return per_param * (dense + hit * sh["expert_params"]
                        + occupancy * sh["d_model"]) \
        + occupancy * rows * sh["kv_bytes_per_layer_token"]


def window_chunk_attention_work(config: dict, chunk: int) -> tuple:
    """(operations, bytes) the WINDOW layers' attention of one chunk of
    ``chunk`` queries REQUIRES, whatever implements it, over a ring that has
    filled: a layer's scores and weighted sums over the ring's ``window``
    rows and the chunk's own (2 operations a multiply-add, two products:
    ``4 x heads x chunk x (window + chunk) x head_dim``), the ring's rows
    and the chunk's of K and V read once, the queries read and the sums
    written once, in bfloat16."""
    sh = shape(config)
    keys = sh["window"] + chunk
    ops = 4.0 * sh["q_width"] * chunk * keys
    io = keys * sh["kv_bytes_per_layer_token"] + 2 * chunk * sh["q_width"] * 2
    return sh["n_window"] * ops, sh["n_window"] * io


def prefill_chunk_work(config: dict, weight_bytes: float, real_tokens: float,
                       expert_rows: float, mean_keys: float = 0.0,
                       last_share: float = 1.0) -> tuple:
    """(operations, bytes) one execution of the prefill chunk program
    requires for ``real_tokens`` real tokens of one request that made
    ``expert_rows`` token-expert pairs. Bytes: every stored matrix once (at
    some hundreds of tokens a chunk every expert is hit), but of the
    embedding's table the rows looked up and the head's table only in the
    ``last_share`` of executions that end a prompt; the K/V rows of the
    ``mean_keys`` keys a query may see in a global layer and of
    ``min(mean_keys, window)`` in a window layer. Operations: 2 a parameter
    of every matrix a token passes, 2 x one expert's parameters a pair, the
    scores and the weighted sum over those keys a query, the head for the
    last token of a last chunk. No padding, no un-hit expert's product."""
    sh = shape(config)
    d = sh["d_model"]
    row = weight_bytes / param_count(config) * d  # bytes a table row
    keys = sh["n_global"] * mean_keys \
        + sh["n_window"] * min(mean_keys, sh["window"])
    io = weight_bytes - row * (sh["vocab"] - real_tokens) \
        - (1.0 - last_share) * row * sh["vocab"] \
        + keys * sh["kv_bytes_per_layer_token"]
    ops = 2.0 * real_tokens * len(sh["layout"]) * (
        sh["attention_params"] + sh["router_params"]) \
        + 2.0 * expert_rows * sh["expert_params"] \
        + real_tokens * 4.0 * sh["q_width"] * keys \
        + last_share * 2.0 * sh["vocab"] * d
    return ops, io


def _no_training(what: str):
    raise NotImplementedError(
        f"{what}: no training cell of the smallthinker family exists (the "
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
    from ray_tpu.models.smallthinker import GAINS, SmallThinkerConfig

    a = config.get("assumed", {})
    sh = shape(config)
    want = {"tie_word_embeddings": False, "norm_topk_prob": True,
            "moe_primary_router_apply_softmax": True, "rope_scaling": None}
    for key, value in want.items():
        if config.get(key, value) != value:
            raise ValueError(f"{key} = {config[key]!r}: the program runs "
                             f"{value!r} only")
    if a.get("router_reads", "input") != "input":
        raise ValueError(f"assumed router_reads {a['router_reads']!r}: the "
                         f"program's router reads the layer's un-normed "
                         f"input")
    if a.get("window_keys_with_own", sh["window"]) != sh["window"]:
        raise ValueError("assumed window_keys_with_own: the program's "
                         "window is sliding_window_size keys with the "
                         "query's own")
    if [int(v) for v in config["rope_layout"]] != sh["layout"] \
            or len(sh["layout"]) != config["num_hidden_layers"]:
        raise ValueError("rope_layout and sliding_window_layout must be the "
                         "same list of num_hidden_layers entries: the "
                         "program rotates exactly its window layers")
    return SmallThinkerConfig(
        vocab_size=sh["vocab"], d_model=sh["d_model"],
        window_layout=tuple(sh["layout"]), window=sh["window"],
        eps=config["rms_norm_eps"], n_head=config["num_attention_heads"],
        n_kv_head=config["num_key_value_heads"], head_dim=config["head_dim"],
        rope_theta=float(config["rope_theta"]), n_experts=sh["experts"],
        top_k=config["moe_num_active_primary_experts"],
        expert_ff=config["moe_ffn_hidden_size"],
        gains=tuple(a.get("init_gains", dict(GAINS)).items()))


def reference_kwargs(config: dict) -> dict:
    return {"rotates": tuple(bool(v) for v in config["rope_layout"]),
            "windows": tuple(bool(v) for v in config["sliding_window_layout"]),
            "window": config["sliding_window_size"],
            "eps": config["rms_norm_eps"],
            "n_head": config["num_attention_heads"],
            "n_kv_head": config["num_key_value_heads"],
            "head_dim": config["head_dim"],
            "rope_theta": float(config["rope_theta"]),
            "top_k": config["moe_num_active_primary_experts"],
            "router_reads": config.get("assumed", {}).get("router_reads",
                                                          "input")}


def to_reference(params, config: dict):
    """The system's parameter tree under the reference's names. The leaves
    go over as they are stored (bfloat16 widens exactly, and the reference
    widens each where it uses it): no float32 copy of the weights is made.
    Nothing is padded, so nothing is cut; ``w1``, the gate's halves side by
    side, is taken apart."""
    ff = config["moe_ffn_hidden_size"]
    layers = []
    for p in params["layers"]:
        layer = {ref: p[name] for name, ref in LAYER_NAMES.items()}
        layer["experts_gate"] = p["w1"][..., :ff]
        layer["experts_up"] = p["w1"][..., ff:]
        layers.append(layer)
    return {"embed_tokens": params["embed"], "lm_head": params["lm_head"],
            "norm": params["norm_f"], "layers": layers}


def init_params(config: dict, seed: int):
    """Seeded weights exactly as the engine makes its own."""
    import jax

    from ray_tpu.models.smallthinker import smallthinker_init

    return smallthinker_init(jax.random.PRNGKey(seed), system_config(config))


def engine_bind(config: dict, engine: dict, seed: int) -> dict:
    """Arguments of ``LLMEngine`` for this configuration."""
    return {"model": "smallthinker", "config": system_config(config),
            "seed": seed, **engine}


def serve_logits(config: dict, params, prompts, lengths, follow, slots: int,
                 cache_len: int):
    """Logits of the serving path's own functions: ``smallthinker_prefill``
    of the padded ``prompts`` [R, P] (the chunk program over every chunk of
    the window), then one ``smallthinker_decode_step`` per column of
    ``follow`` [R, N] through a fresh cache (both stacks of rings).
    -> [R, 1 + N, V]."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.smallthinker import (smallthinker_decode_step,
                                             smallthinker_init_cache,
                                             smallthinker_prefill)

    cfg = system_config(config)
    r = prompts.shape[0]
    cache = smallthinker_init_cache(cfg, slots, cache_len)
    slot_idx = jnp.arange(r, dtype=jnp.int32)
    prefill = jax.jit(
        lambda p, c, t, s, n: smallthinker_prefill(p, c, t, s, n, cfg),
        donate_argnums=(1,))
    step = jax.jit(
        lambda p, c, t, n: smallthinker_decode_step(p, c, t, n, cfg)[:2],
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
    global and the first window layer the rms of the stream the layer
    receives and of each branch as it is added (attention, the routed
    experts), the spread of the router's logits (which grow with the
    un-normed stream) and the weight of a token's largest and smallest
    chosen expert. Each branch of some tenths of the stream says that a
    comparison of logits holds both."""
    import jax
    import jax.numpy as jnp

    from benchmark.loading import sibling

    ref = sibling(__file__, "../reference/smallthinker.py")
    kw = reference_kwargs(config)
    p = to_reference(params, config)

    def rms(x):
        return float(jnp.sqrt(jnp.mean(jnp.square(x))))

    out = {}
    with jax.default_matmul_precision("highest"):
        x = ref._w(p["embed_tokens"][tokens])
        for layer, turns, windowed in zip(p["layers"], kw["rotates"],
                                          kw["windows"]):
            a = ref.rms_norm(x, layer["input_layernorm"], kw["eps"])
            attn = ref.attention(
                layer, a, n_head=kw["n_head"], n_kv_head=kw["n_kv_head"],
                head_dim=kw["head_dim"], rope_theta=kw["rope_theta"],
                rotates=turns, window=kw["window"] if windowed else None)
            h = x + attn
            logits = x.reshape(-1, x.shape[-1]) @ ref._w(layer["router"])
            weights = ref.gating(logits, kw["top_k"])
            m = ref.rms_norm(h, layer["post_attention_layernorm"], kw["eps"])
            routed = ref.experts(layer, m.reshape(-1, m.shape[-1]), weights)
            kind = "window" if windowed else "global"
            if kind not in out:
                top = jnp.sort(weights, axis=-1)[:, -kw["top_k"]:]
                out[kind] = {
                    "stream_rms": rms(x), "attention_rms": rms(attn),
                    "routed_rms": rms(routed),
                    "router_logit_spread": float(jnp.mean(
                        jnp.std(logits, axis=-1))),
                    "largest_weight_mean": float(jnp.mean(top[:, -1])),
                    "smallest_weight_mean": float(jnp.mean(top[:, 0]))}
            x = h + routed.reshape(x.shape)
            if len(out) == 2:
                break
    return out
