"""How the system under test is built from a ``keye_vl2``-family
configuration file (``model_type: KeyeVL2``, the language model: grouped
queries over the keys a learned indexer picks, top-k experts in every
layer), how its weights map onto the reference's names, and the family's
arithmetic. ``README.md`` beside this file lists the interface; what
differs here:

* **Two stacks of rings**: a token takes one K/V row (its merged K row and
  V row side by side) and one narrow indexer key a layer (``cache_bytes``).
* **A step reads what it PICKS**: ``decode_step_bytes`` counts, a slot a
  layer, the ``min(topk, context)`` K and V rows a query's set holds and
  every live indexer key, not the live K/V rows.
* **``sparse_attention_work``** and **``indexer_work``**
  (``metrics/sparse_decode_attention_roofline.py``,
  ``metrics/indexer_decode_roofline.py``): what a step's attention over the
  picked rows and its indexer REQUIRE, whatever implements them, so no
  share can pass 100.
* **``prefill_chunk_work``** (``metrics/prefill_chunk_roofline.py``): a
  query attends ``min(topk, keys in sight)`` keys and is scored by the
  indexer against all in sight.
* **Bytes from counters**, bfloat16 leaves handed to the reference
  unconverted, the experts held a share of the router's: as
  ``families/qwen3_next.py``.
* **``branch_readings``**: what the seeded draw (``assumed.init_gains``)
  makes of a layer's branches, of the router's scores and of the index
  scores' margin at the ``topk``-th; the configuration file quotes it.
* **``serve_logits`` hands out the sets** the programs picked (``sets``:
  a few queries of every prefill chunk, every step) and **``forced_picks``**
  what their indexer picks from the reference's own stream: for
  ``tools/serve_check_sparse.py``.
* **The training functions refuse**: no training cell of this family exists.

The configuration file holds the released ``config.json``'s language-model
keys. ``num_hidden_layers`` is the layers that run, ``num_experts`` (and
``num_local_experts``) the experts held here, the first of the router's
``assumed.router_experts``, and ``vocab_size`` the rows of both tables held
here; the published values stand beside them.
"""

from __future__ import annotations

# The keys a configuration file of this family may carry under ``assumed``
# (beside notes whose key ends in ``why``).
ASSUMED = frozenset({"indexer_reads", "indexer_key_norm", "indexer_rotary",
                     "sa_tiling", "vision_tower", "router_experts",
                     "init_gains"})

LAYER_NAMES = {"norm": "input_layernorm", "norm2": "post_attention_layernorm",
               "wq": "q_proj", "wk": "k_proj", "wv": "v_proj", "wo": "o_proj",
               "q_norm": "q_norm", "k_norm": "k_norm",
               "idx_wq": "indexer_q_proj", "idx_wk": "indexer_k_proj",
               "idx_ww": "indexer_weights", "idx_k_norm": "indexer_k_norm",
               "idx_k_bias": "indexer_k_bias", "router": "router",
               "w2": "experts_down"}


def shape(config: dict) -> dict:
    """The sizes the arithmetic needs, from the file."""
    d = config["hidden_size"]
    hd = config["head_dim"]
    q = config["num_attention_heads"] * hd
    kv = config["num_key_value_heads"] * hd
    sa = config["sa_config"]
    j, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    router = config.get("assumed", {}).get("router_experts",
                                           config["num_experts"])
    return {
        "vocab": config["vocab_size"],
        "n_positions": config["max_position_embeddings"],
        "d_model": d, "layers": config["num_hidden_layers"],
        "q_width": q, "experts": config["num_experts"],
        "router_experts": router, "topk": sa["topk"],
        "index_heads": j, "index_dim": di,
        # q, k, v, o and the two head norms
        "attention_params": 2 * d * q + 2 * d * kv + 2 * hd,
        # the indexer's three projections and its LayerNorm
        "indexer_params": d * j * di + d * di + d * j + 2 * di,
        "router_params": d * router,
        # gated: [a, b] = W1 y and W2, three matrices' worth
        "expert_params": 3 * d * config["moe_intermediate_size"],
        # bfloat16 merged K and V rows of ONE layer, a token; its index key
        "kv_bytes_per_layer_token": 2 * kv * 2,
        "index_bytes_per_layer_token": di * 2,
    }


def param_count(config: dict) -> int:
    """Parameters as the system holds them: the layers that run, the
    experts held, both vocabulary tables' slices, the last norm. No table
    is padded."""
    sh = shape(config)
    d = sh["d_model"]
    layer = sh["attention_params"] + sh["indexer_params"] \
        + sh["router_params"] + sh["experts"] * sh["expert_params"] + 2 * d
    return sh["layers"] * layer + 2 * sh["vocab"] * d + d


def cache_bytes(config: dict, slots: int, cache_len: int) -> float:
    """Bytes of the engine's cache, by shape: K, V and indexer rings of
    ``cache_len`` rows in every layer."""
    sh = shape(config)
    return slots * cache_len * sh["layers"] * (
        sh["kv_bytes_per_layer_token"] + sh["index_bytes_per_layer_token"])


def _rows_a_slot(sh: dict, context: float) -> float:
    """Ring bytes one slot's step must read in ONE layer at ``context``
    live rows: the K and V rows of its set, every live indexer key."""
    return min(context, sh["topk"]) * sh["kv_bytes_per_layer_token"] \
        + context * sh["index_bytes_per_layer_token"]


def decode_step_bytes(config: dict, weight_bytes: float, occupancy: float,
                      mean_context: float, counters: dict) -> float:
    """Bytes one decode step must move: every weight outside the experts
    once but the embedding's table (a step reads the rows it looks up), one
    expert's bytes for each expert the step hit (``experts_hit`` a step,
    from the window's two ``llm_stats()``; every held expert where there
    are none), and for the occupied slots, a layer, the K and V rows a set
    holds (``min(topk, context)``) and every live indexer key."""
    sh = shape(config)
    per_param = weight_bytes / param_count(config)
    experts = sh["layers"] * sh["experts"]
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
        + occupancy * sh["layers"] * _rows_a_slot(sh, mean_context)


def sparse_attention_work(config: dict, occupancy: float,
                          context: float) -> tuple:
    """(operations, bytes) the attention of one decode step REQUIRES over
    the picked rows, whatever implements it: for ``occupancy`` slots at
    ``context`` live rows, a layer, the ``min(topk, context)`` rows of K
    and of V of the set read ONCE, the new K and V row written, the queries
    read and the sums written, in bfloat16; the scores and the weighted
    sums over those keys for every query head (2 operations a
    multiply-add, two products)."""
    sh = shape(config)
    picked = min(context, sh["topk"])
    ops = 4.0 * sh["q_width"] * picked
    io = (picked + 1) * sh["kv_bytes_per_layer_token"] + 2 * sh["q_width"] * 2
    return occupancy * sh["layers"] * ops, occupancy * sh["layers"] * io


def indexer_work(config: dict, occupancy: float, context: float) -> tuple:
    """(operations, bytes) the indexer of one decode step REQUIRES: a
    layer, its three projections' weights read once for all slots and
    multiplied with every occupied slot's row; for each slot its live
    indexer keys read ONCE and scored by every indexer head (a product and
    a weighted sum a head a key), its new key written."""
    sh = shape(config)
    proj = sh["indexer_params"]
    ops = occupancy * (2.0 * proj + context * sh["index_heads"]
                       * (2.0 * sh["index_dim"] + 2.0))
    io = proj * 2 + occupancy * (context + 1) \
        * sh["index_bytes_per_layer_token"]
    return sh["layers"] * ops, sh["layers"] * io


def prefill_chunk_work(config: dict, weight_bytes: float, real_tokens: float,
                       expert_rows: float, mean_keys: float = 0.0,
                       last_share: float = 1.0) -> tuple:
    """(operations, bytes) one execution of the prefill chunk program
    requires for ``real_tokens`` real tokens of one request that made
    ``expert_rows`` token-expert pairs. Bytes: every stored matrix once,
    but of the embedding's table the rows looked up and the head's table
    only in the ``last_share`` of executions that end a prompt; the K, V
    and indexer rows of the ``mean_keys`` keys in sight. Operations: 2 a
    parameter of every matrix a token passes, 2 x one expert's parameters a
    pair, the index scores over the keys in sight, attention's scores and
    weighted sums over ``min(topk, keys in sight)`` keys a query, the head
    for the last token of a last chunk. No padding, no un-hit expert's
    product, and nothing for the selection itself."""
    sh = shape(config)
    d = sh["d_model"]
    row = weight_bytes / param_count(config) * d  # bytes a table row
    io = weight_bytes - row * (sh["vocab"] - real_tokens) \
        - (1.0 - last_share) * row * sh["vocab"] \
        + sh["layers"] * mean_keys * (sh["kv_bytes_per_layer_token"]
                                      + sh["index_bytes_per_layer_token"])
    ops = 2.0 * real_tokens * sh["layers"] * (
        sh["attention_params"] + sh["indexer_params"]
        + sh["router_params"]) \
        + 2.0 * expert_rows * sh["expert_params"] \
        + real_tokens * sh["layers"] * (
            4.0 * sh["q_width"] * min(mean_keys, sh["topk"])
            + mean_keys * sh["index_heads"] * (2.0 * sh["index_dim"] + 2.0)) \
        + last_share * 2.0 * sh["vocab"] * d
    return ops, io


def _no_training(what: str):
    raise NotImplementedError(
        f"{what}: no training cell of the keye_vl2 family exists (the "
        f"dropless share layer and the selection have no gradients); the "
        f"family is served only")


def train_flops_per_token(config: dict) -> float:
    _no_training("train_flops_per_token")


def attention_calls(config: dict, rows: int) -> tuple:
    _no_training("attention_calls")


def build_train(config: dict, mesh) -> dict:
    _no_training("build_train")


# What the program runs of each ``assumed`` key that names a reading.
_RUNS = {"indexer_reads": "normed_input", "indexer_key_norm": "layernorm",
         "indexer_rotary": "all", "sa_tiling": "tiles_only",
         "vision_tower": "not served"}


def system_config(config: dict):
    """The program's configuration; refuses a file that states what the
    program does not run."""
    from ray_tpu.models.keye_vl2 import GAINS, KeyeVL2Config

    a = config.get("assumed", {})
    sh = shape(config)
    want = {"tie_word_embeddings": False, "norm_topk_prob": True,
            "attention_bias": False, "sliding_window": None,
            "use_sliding_window": False, "decoder_sparse_step": 1,
            "mlp_only_layers": [], "hidden_act": "silu"}
    for key, value in want.items():
        if config.get(key, value) != value:
            raise ValueError(f"{key} = {config[key]!r}: the program runs "
                             f"{value!r} only")
    for key, value in _RUNS.items():
        if a.get(key, value) != value:
            raise ValueError(f"assumed {key} {a[key]!r}: the program runs "
                             f"{value!r}")
    scaling = config.get("rope_scaling") or {}
    if scaling.get("rope_type", "default") != "default" \
            or 2 * sum(scaling.get("mrope_section", [])) \
            not in (0, config["head_dim"]):
        raise ValueError(f"rope_scaling {scaling!r}: the program runs the "
                         f"default M-RoPE over the whole head, which for "
                         f"text is the ordinary rotation")
    if config.get("num_local_experts", sh["experts"]) != sh["experts"]:
        raise ValueError("num_local_experts and num_experts must both "
                         "count the experts held")
    return KeyeVL2Config(
        vocab_size=sh["vocab"], d_model=sh["d_model"], n_layer=sh["layers"],
        eps=config["rms_norm_eps"], n_head=config["num_attention_heads"],
        n_kv_head=config["num_key_value_heads"], head_dim=config["head_dim"],
        rope_theta=float(config["rope_theta"]),
        index_heads=sh["index_heads"], index_dim=sh["index_dim"],
        index_topk=sh["topk"], n_experts=sh["router_experts"],
        experts_held=(0, sh["experts"]),
        top_k=config["num_experts_per_tok"],
        expert_ff=config["moe_intermediate_size"],
        gains=tuple(a.get("init_gains", dict(GAINS)).items()))


def reference_kwargs(config: dict) -> dict:
    a = config.get("assumed", {})
    sa = config["sa_config"]
    hd = config["head_dim"]
    return {"eps": config["rms_norm_eps"],
            "n_head": config["num_attention_heads"],
            "n_kv_head": config["num_key_value_heads"], "head_dim": hd,
            "rope_theta": float(config["rope_theta"]),
            "mrope_section": tuple((config.get("rope_scaling") or {}).get(
                "mrope_section", [hd // 2])),
            "indexer_heads": sa["indexer_num_heads"],
            "indexer_dim": sa["indexer_head_dim"], "topk": sa["topk"],
            "top_k": config["num_experts_per_tok"], "first_expert": 0,
            "indexer_rotary": a.get("indexer_rotary", "all"),
            "indexer_key_norm": a.get("indexer_key_norm", "layernorm")}


def to_reference(params, config: dict):
    """The system's parameter tree under the reference's names. The leaves
    go over as they are stored (bfloat16 widens exactly, and the reference
    widens each where it uses it): no float32 copy of the weights is made.
    Nothing is padded, so nothing is cut; ``w1``, the gate's halves side by
    side, is taken apart."""
    ff = config["moe_intermediate_size"]
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

    from ray_tpu.models.keye_vl2 import keye_vl2_init

    return keye_vl2_init(jax.random.PRNGKey(seed), system_config(config))


def engine_bind(config: dict, engine: dict, seed: int) -> dict:
    """Arguments of ``LLMEngine`` for this configuration."""
    return {"model": "keye_vl2", "config": system_config(config),
            "seed": seed, **engine}


# Queries of a chunk whose sets ``serve_logits`` keeps for the tool.
CHUNK_SAMPLE = 4


def _prefill_with_sets(cfg, params, cache, prompts, lengths):
    """``whole_prompts``' loop in Python, over the chunks that hold a real
    token, through ``keye_vl2_chunk_with_sets``: the chunk program (the
    rule's chunk length, the window of the padded prompts' width) and what
    its queries picked. Of each chunk ``CHUNK_SAMPLE`` evenly spaced
    queries are kept. -> (logits at each prompt's last real token, the
    cache, positions [Q] and picked [n_layer, R, Q, T] bool BY POSITION:
    nothing for a query past its prompt's end)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models.keye_vl2 import keye_vl2_chunk_with_sets
    from ray_tpu.models.prefill import chunk_len, token_parameters

    r, p_len = prompts.shape
    c = chunk_len(p_len, *token_parameters(cfg, params))
    window = -(-p_len // c) * c
    top = -(-int(np.max(np.asarray(lengths))) // c) * c
    chunk = jax.jit(
        lambda p, held, t, at, n: keye_vl2_chunk_with_sets(
            p, held, t, jnp.arange(r, dtype=jnp.int32), at, n, cfg,
            window=window), donate_argnums=(1,))
    offsets = np.unique(np.linspace(0, c - 1, CHUNK_SAMPLE).astype(int))
    padded = jnp.pad(prompts, ((0, 0), (0, window - p_len)))
    logits, positions, picked = None, [], []
    for at in range(0, top, c):
        got, cache, masks = chunk(
            params, cache, padded[:, at:at + c], jnp.full((r,), at, jnp.int32),
            jnp.clip(lengths - at, 0, c).astype(jnp.int32))
        ends_here = (lengths > at) & (lengths <= at + c)
        logits = got if logits is None else jnp.where(
            ends_here[:, None], got, logits)
        masks = np.asarray(masks[:, :, offsets])       # [N, R, Q, window]
        by_position = np.zeros(masks.shape[:3] + (top,), bool)
        by_position[..., :at] = masks[..., :at]        # ring row = position
        by_position[..., at:at + c] = masks[..., window - c:]
        positions.append(at + offsets)
        picked.append(by_position)
    return logits, cache, (np.concatenate(positions),
                           np.concatenate(picked, axis=2))


def serve_logits(config: dict, params, prompts, lengths, follow, slots: int,
                 cache_len: int, sets=None, cache_fault=None):
    """Logits of the serving path's own functions: ``keye_vl2_prefill`` of
    the padded ``prompts`` [R, P] (the chunk program over every chunk of
    the window), then one decode step per column of ``follow`` [R, N]
    through a fresh cache (both stacks of rings). -> [R, 1 + N, V].
    ``sets``, a dict, is given what the programs picked: under ``prefill``
    ``_prefill_with_sets``' positions and sets (the prefill then runs a
    chunk at a time, to hand them out), under ``steps`` each step's picked
    rows (``[n_layer, R, topk]`` positions and ``[n_layer, R]`` sizes);
    ``cache_fault`` is applied to the cache between prefill and the steps
    (the tool's control)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.keye_vl2 import (keye_vl2_init_cache,
                                         keye_vl2_prefill,
                                         keye_vl2_step_with_sets)

    cfg = system_config(config)
    r = prompts.shape[0]
    cache = keye_vl2_init_cache(cfg, slots, cache_len)
    step = jax.jit(
        lambda p, c, t, n: keye_vl2_step_with_sets(p, c, t, n, cfg),
        donate_argnums=(1,))
    if sets is None:
        logits, cache = jax.jit(
            lambda p, c, t, s, n: keye_vl2_prefill(p, c, t, s, n, cfg),
            donate_argnums=(1,))(params, cache, prompts,
                                 jnp.arange(r, dtype=jnp.int32), lengths)
    else:
        logits, cache, sets["prefill"] = _prefill_with_sets(
            cfg, params, cache, prompts, lengths)
        sets["steps"] = []
    if cache_fault is not None:
        cache = cache_fault(cache)
    out = [logits]
    pad = slots - r
    for i in range(follow.shape[1]):
        toks = jnp.concatenate([follow[:, i], jnp.zeros((pad,), jnp.int32)])
        pos = jnp.concatenate([lengths + i, jnp.zeros((pad,), jnp.int32)])
        logits, cache, _, rows, sizes = step(params, cache, toks, pos)
        if sets is not None:
            sets["steps"].append((rows[:, :r], sizes[:, :r]))
        out.append(logits[:r])
    return jnp.stack(out, axis=1)


def forced_picks(config: dict, params, streams, queries):
    """What the programs' indexer and selection pick when every layer is
    fed the REFERENCE's stream (streams [n_layer, R, T, d], what each layer
    received) for the queries at positions ``queries`` [R, Q]: the
    arithmetic of ``keye_vl2_layer_picks``, apart from what bfloat16 did
    to the stream before the layer. -> [n_layer, R, Q, T] bool."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.keye_vl2 import keye_vl2_layer_picks

    cfg = system_config(config)
    one = jax.jit(lambda p, x, at: keye_vl2_layer_picks(p, x, at, cfg))
    return jnp.stack([one(p, x, queries)
                      for p, x in zip(params["layers"], streams)])


def branch_readings(config: dict, params, tokens) -> dict:
    """What the seeded draw makes of the FIRST layer, by the reference's
    own functions in float32, over tokens [R, T]: the rms of the stream the
    layer receives and of each branch as it is added (attention over the
    picked keys, the held experts), the spread of the router's logits and
    the weight of a token's largest and smallest chosen expert; and of the
    last row's index scores their spread and the gap between the
    ``topk``-th and the next, in units of that spread (what bfloat16
    rounding must move a score by to turn a pick). Each branch of some
    tenths of the stream says that a comparison of logits holds both."""
    import jax
    import jax.numpy as jnp

    from benchmark.loading import sibling

    ref = sibling(__file__, "../reference/keye_vl2.py")
    kw = reference_kwargs(config)
    p = to_reference(params, config)
    layer = p["layers"][0]

    def rms(x):
        return float(jnp.sqrt(jnp.mean(jnp.square(x))))

    with jax.default_matmul_precision("highest"):
        x = ref._w(p["embed_tokens"][tokens])
        a = ref.rms_norm(x, layer["input_layernorm"], kw["eps"])
        scores = ref.index_scores(
            layer, a, indexer_heads=kw["indexer_heads"],
            indexer_dim=kw["indexer_dim"], rope_theta=kw["rope_theta"],
            eps=kw["eps"])
        sets = ref.key_sets(scores, kw["topk"])
        attn = ref.attention(
            layer, a, sets, n_head=kw["n_head"], n_kv_head=kw["n_kv_head"],
            head_dim=kw["head_dim"], rope_theta=kw["rope_theta"],
            eps=kw["eps"], mrope_section=kw["mrope_section"])
        h = x + attn
        y = ref.rms_norm(h, layer["post_attention_layernorm"], kw["eps"])
        flat = y.reshape(-1, y.shape[-1])
        logits = flat @ ref._w(layer["router"])
        weights = ref.gating(logits, kw["top_k"])
        held = layer["experts_gate"].shape[0]
        routed = ref.experts(layer, flat, weights[:, :held])
        top = jnp.sort(weights, axis=-1)[:, -kw["top_k"]:]
        last = jnp.sort(scores[:, -1], axis=-1)[:, ::-1]        # descending
        out = {"stream_rms": rms(x), "attention_rms": rms(attn),
               "routed_rms": rms(routed),
               "router_logit_spread": float(jnp.mean(
                   jnp.std(logits, axis=-1))),
               "largest_weight_mean": float(jnp.mean(top[:, -1])),
               "smallest_weight_mean": float(jnp.mean(top[:, 0])),
               "index_score_spread": float(jnp.mean(jnp.std(last, axis=-1)))}
        k = kw["topk"]
        if last.shape[-1] > k:
            out["index_gap_at_topk"] = float(jnp.mean(
                (last[:, k - 1] - last[:, k]) / jnp.std(last, axis=-1)))
    return out
