"""How the system under test is built from a ``falcon_h1``-family
configuration file (``model_type: falcon_h1``: attention AND a Mamba-2 mixer
side by side in every layer, then a gated MLP, fourteen muP multipliers),
how its weights map onto the reference's names, and the family's
arithmetic. ``README.md`` beside this file lists the interface; what
differs here:

* **No layer pattern and no experts**: every layer holds both mixers, so
  every layer counts a K/V ring's rows AND a state.
* **The head is a table of its own** (``tie_word_embeddings`` false): a step
  reads the head's table once and, of the embedding's, only the rows it
  looks up.
* **``prefill_chunk_work``** (``metrics/prefill_chunk_roofline.py``) and
  **``parallel_mixer_decode_work``** (``metrics/
  parallel_mixer_decode_roofline.py``), beside the README's table.
* **``branch_readings``**: what the seeded draw (``assumed.init_gains``)
  makes of the first layer's three branches; the configuration file quotes
  its readings and a test holds them at a toy size.
* **The training functions refuse**: no training cell of this family exists
  (16 bytes a parameter do not fit one chip at the guide's floors, and the
  blocked scan has no backward on the chip).

The configuration file holds the released ``config.json``'s keys.
``num_hidden_layers`` is the layers that run and ``vocab_size`` the rows of
both tables held here; the published values stand beside them.
"""

from __future__ import annotations

# The keys a configuration file of this family may carry under ``assumed``
# (beside notes whose key ends in ``why``).
ASSUMED = frozenset({"ssm_state_dtype", "init_gains"})

# A layer's weights: the system's name -> the reference's (the released
# checkpoint's, shortened).
LAYER_NAMES = {
    "norm": "input_layernorm", "norm2": "pre_ff_layernorm",
    "wq": "q_proj", "wk": "k_proj", "wv": "v_proj", "wo": "o_proj",
    "in_proj": "in_proj", "conv_w": "conv_w", "conv_b": "conv_b",
    "dt_bias": "dt_bias", "a_log": "A_log", "d_skip": "D",
    "gate_norm": "norm_w", "out_proj": "out_proj",
    "w_gate": "gate_proj", "w_up": "up_proj", "w_down": "down_proj"}

MULTIPLIERS = ("embedding_multiplier", "lm_head_multiplier",
               "key_multiplier", "attention_in_multiplier",
               "attention_out_multiplier", "ssm_in_multiplier",
               "ssm_out_multiplier")  # and ssm_multipliers, mlp_multipliers


def shape(config: dict) -> dict:
    """The sizes the arithmetic needs, from the file."""
    d = config["hidden_size"]
    heads, head_dim = config["mamba_n_heads"], config["mamba_d_head"]
    d_inner = heads * head_dim
    gn = config["mamba_n_groups"] * config["mamba_d_state"]
    conv_dim = d_inner + 2 * gn
    q = config["num_attention_heads"] * config["head_dim"]
    kv = config["num_key_value_heads"] * config["head_dim"]
    layers = config["num_hidden_layers"]
    attn = 2 * d * q + 2 * d * kv
    mixer = d * (d_inner + conv_dim + heads) \
        + (config["mamba_d_conv"] + 1) * conv_dim + 3 * heads + d_inner \
        + d_inner * d
    return {
        "vocab": config["vocab_size"],
        "n_positions": config["max_position_embeddings"],
        "layers": layers, "d_model": d, "q_width": q, "d_inner": d_inner,
        "state": config["mamba_d_state"],
        "attn_params": attn, "mixer_params": mixer,
        "mlp_params": 3 * d * config["intermediate_size"],
        # the matrices every prompt token passes, a layer (not the norms'
        # scales, the convolution or the mixer's per-head scalars)
        "token_params": attn + d * (d_inner + conv_dim + heads)
        + d_inner * d + 3 * d * config["intermediate_size"],
        # bfloat16 K and V rows of every layer
        "kv_bytes_per_token": 2 * layers * kv * 2,
        # a slot's state: float32 SSM state and bfloat16 convolution tail,
        # in every layer
        "state_bytes_per_slot": layers * (
            d_inner * config["mamba_d_state"] * 4
            + (config["mamba_d_conv"] - 1) * conv_dim * 2),
    }


def param_count(config: dict) -> int:
    """Parameters as the system holds them: the layers that run, both
    vocabulary tables' slices, the last norm."""
    sh = shape(config)
    d = sh["d_model"]
    return sh["layers"] * (sh["attn_params"] + sh["mixer_params"]
                           + sh["mlp_params"] + 2 * d) \
        + 2 * sh["vocab"] * d + d


def cache_bytes(config: dict, slots: int, cache_len: int) -> float:
    """Bytes of the engine's cache, by shape: in every layer a K/V ring,
    a convolution tail and a state."""
    sh = shape(config)
    return slots * (cache_len * sh["kv_bytes_per_token"]
                    + sh["state_bytes_per_slot"])


def decode_step_bytes(config: dict, weight_bytes: float, occupancy: float,
                      mean_context: float, counters: dict) -> float:
    """Bytes one decode step must move: every weight once but the
    embedding's table (a step reads the rows it looks up), and for the
    occupied slots the live K/V rows read and the state read and
    written."""
    sh = shape(config)
    per_param = weight_bytes / param_count(config)
    table = sh["vocab"] * sh["d_model"]
    return per_param * (param_count(config) - table
                        + occupancy * sh["d_model"]) \
        + occupancy * (mean_context * sh["kv_bytes_per_token"]
                       + 2 * sh["state_bytes_per_slot"])


def parallel_mixer_decode_work(config: dict, occupancy: float,
                               mean_context: float) -> tuple:
    """(operations, cache bytes) the two mixers of every layer REQUIRE of
    one decode step, whatever implements them. Bytes: each occupied slot's
    live K/V rows read once, each occupied slot's state and tail read and
    written once. The mixers' weights are left out on purpose: the compiler
    reads them ahead, under no scope the reader's time holds
    (``metrics/parallel_mixer_decode_roofline.py`` says why);
    ``decode_step_bytes`` counts them over the whole step. Operations: 2 a
    parameter a row, the scores and the weighted sum over the live rows,
    and the state's update and readout (a multiply-add each an element,
    and the decay). No free slot, no dead ring row and no widened copy is
    counted."""
    sh = shape(config)
    params = sh["layers"] * (sh["attn_params"] + sh["mixer_params"])
    io = occupancy * (mean_context * sh["kv_bytes_per_token"]
                      + 2 * sh["state_bytes_per_slot"])
    ops = occupancy * (
        2.0 * params
        + sh["layers"] * (4.0 * sh["q_width"] * mean_context
                          + 5.0 * sh["d_inner"] * sh["state"]))
    return ops, io


def prefill_chunk_work(config: dict, weight_bytes: float, real_tokens: float,
                       expert_rows: float, mean_keys: float = 0.0,
                       last_share: float = 1.0) -> tuple:
    """(operations, bytes) one execution of the prefill chunk program
    requires for ``real_tokens`` real tokens of one request. Bytes: every
    stored matrix once, but of the embedding's table the rows looked up and
    the head's table only in the ``last_share`` of executions that end a
    prompt (only a prompt's LAST chunk needs logits; the program runs the
    head in every chunk, which nothing requires); the slot's state read and
    written; the K/V rows of the ``mean_keys`` keys a query may see.
    Operations: 2 a parameter of every matrix a token passes, the scores
    and the weighted sum over ``mean_keys`` keys a query in every layer,
    the head for the last token of a last chunk. No padding; the scan's own
    products are left out. ``expert_rows`` is the interface's (the family
    has no experts: it is 0 and unused)."""
    sh = shape(config)
    d = sh["d_model"]
    row = weight_bytes / param_count(config) * d  # bytes a table row
    io = weight_bytes - row * (sh["vocab"] - real_tokens) \
        - (1.0 - last_share) * row * sh["vocab"] \
        + 2 * sh["state_bytes_per_slot"] \
        + mean_keys * sh["kv_bytes_per_token"]
    ops = 2.0 * real_tokens * sh["layers"] * sh["token_params"] \
        + real_tokens * sh["layers"] * 4.0 * sh["q_width"] * mean_keys \
        + last_share * 2.0 * sh["vocab"] * d
    return ops, io


def _no_training(what: str):
    raise NotImplementedError(
        f"{what}: no training cell of the falcon_h1 family exists (16 bytes "
        f"a parameter do not fit one chip at the guide's floors, and the "
        f"blocked scan has no backward on the chip); the family is served "
        f"only")


def train_flops_per_token(config: dict) -> float:
    _no_training("train_flops_per_token")


def attention_calls(config: dict, rows: int) -> tuple:
    _no_training("attention_calls")


def build_train(config: dict, mesh) -> dict:
    _no_training("build_train")


def system_config(config: dict):
    """The program's configuration; refuses a file that states what the
    program does not run."""
    from ray_tpu.models.falcon_h1 import GAINS, FalconH1Config

    a = config.get("assumed", {})
    want = {"hidden_act": "silu", "tie_word_embeddings": False,
            "attention_bias": False, "mlp_bias": False,
            "projectors_bias": False, "mamba_conv_bias": True,
            "mamba_proj_bias": False, "mamba_rms_norm": True,
            "mamba_norm_before_gate": False, "rope_scaling": None,
            "attn_layer_indices": None}
    for key, value in want.items():
        if config.get(key, value) != value:
            raise ValueError(f"{key} = {config[key]!r}: the program runs "
                             f"{value!r} only")
    if config["mamba_d_ssm"] != config["mamba_n_heads"] \
            * config["mamba_d_head"]:
        raise ValueError("mamba_d_ssm is not mamba_n_heads * mamba_d_head")
    if a.get("ssm_state_dtype", "float32") != "float32":
        raise ValueError(f"assumed ssm_state_dtype {a['ssm_state_dtype']}: "
                         f"the program keeps a float32 SSM state")
    import jax.numpy as jnp

    return FalconH1Config(
        # the file's statement builds the program, not the class's default
        ssm_state_dtype=getattr(jnp, a.get("ssm_state_dtype", "float32")),
        vocab_size=config["vocab_size"], d_model=config["hidden_size"],
        n_layer=config["num_hidden_layers"], eps=config["rms_norm_eps"],
        n_head=config["num_attention_heads"],
        n_kv_head=config["num_key_value_heads"],
        head_dim=config["head_dim"], rope_theta=float(config["rope_theta"]),
        mamba_heads=config["mamba_n_heads"],
        mamba_head_dim=config["mamba_d_head"],
        ssm_groups=config["mamba_n_groups"],
        ssm_state=config["mamba_d_state"],
        conv_kernel=config["mamba_d_conv"],
        chunk_size=config["mamba_chunk_size"],
        d_ff=config["intermediate_size"],
        ssm_multipliers=tuple(config["ssm_multipliers"]),
        mlp_multipliers=tuple(config["mlp_multipliers"]),
        gains=tuple(a.get("init_gains", dict(GAINS)).items()),
        **{key: float(config[key]) for key in MULTIPLIERS})


def reference_kwargs(config: dict) -> dict:
    return {"eps": config["rms_norm_eps"],
            "n_head": config["num_attention_heads"],
            "n_kv_head": config["num_key_value_heads"],
            "head_dim": config["head_dim"],
            "rope_theta": float(config["rope_theta"]),
            "mamba_heads": config["mamba_n_heads"],
            "mamba_head_dim": config["mamba_d_head"],
            "n_groups": config["mamba_n_groups"],
            "ssm_state": config["mamba_d_state"],
            "ssm_multipliers": tuple(config["ssm_multipliers"]),
            "mlp_multipliers": tuple(config["mlp_multipliers"]),
            **{key: float(config[key]) for key in MULTIPLIERS}}


def to_reference(params, config: dict):
    """The system's parameter tree under the reference's names. The leaves
    go over as they are stored (bfloat16 widens exactly, and the reference
    widens each where it uses it): no float32 copy of the weights is made.
    Nothing is padded and no multiplier is folded, so nothing is undone."""
    return {
        "embed_tokens": params["embed"], "lm_head": params["lm_head"],
        "final_layernorm": params["norm_f"],
        "layers": [{ref: p[name] for name, ref in LAYER_NAMES.items()}
                   for p in params["layers"]],
    }


def init_params(config: dict, seed: int):
    """Seeded weights exactly as the engine makes its own."""
    import jax

    from ray_tpu.models.falcon_h1 import falcon_h1_init

    return falcon_h1_init(jax.random.PRNGKey(seed), system_config(config))


def engine_bind(config: dict, engine: dict, seed: int) -> dict:
    """Arguments of ``LLMEngine`` for this configuration."""
    return {"model": "falcon_h1", "config": system_config(config),
            "seed": seed, **engine}


def serve_logits(config: dict, params, prompts, lengths, follow, slots: int,
                 cache_len: int):
    """Logits of the serving path's own functions: ``falcon_h1_prefill`` of
    the padded ``prompts`` [R, P] (the chunk program over every chunk of the
    window), then one ``falcon_h1_decode_step`` per column of ``follow``
    [R, N] through a fresh cache (rings, tails and states of every layer).
    -> [R, 1 + N, V]."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.falcon_h1 import (falcon_h1_decode_step,
                                          falcon_h1_init_cache,
                                          falcon_h1_prefill)

    cfg = system_config(config)
    r = prompts.shape[0]
    cache = falcon_h1_init_cache(cfg, slots, cache_len)
    slot_idx = jnp.arange(r, dtype=jnp.int32)
    prefill = jax.jit(
        lambda p, c, t, s, n: falcon_h1_prefill(p, c, t, s, n, cfg),
        donate_argnums=(1,))
    step = jax.jit(
        lambda p, c, t, n: falcon_h1_decode_step(p, c, t, n, cfg)[:2],
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
    """What the seeded draw makes of the FIRST layer, by the reference's own
    functions in float32, over tokens [R, T]: the rms of the stream the
    layer receives and of each of the three branches as it is added (its
    multiplier included), the spread of the attention's scores, and how far
    the mixer's output moves when the skip ``D x_t`` is left out, as a
    share of its rms (near 0: the state's readout ``S_t C_t`` alone makes
    the output; near 1.4: the skip alone does, and the output holds nothing
    of the state). Each branch of the stream's own order says that a
    comparison of logits holds all three."""
    import jax
    import jax.numpy as jnp

    from benchmark.loading import sibling

    ref = sibling(__file__, "../reference/falcon_h1.py")
    kw = reference_kwargs(config)
    p = to_reference(params, config)
    layer = p["layers"][0]

    def rms(x):
        return float(jnp.sqrt(jnp.mean(jnp.square(x))))

    with jax.default_matmul_precision("highest"):
        x = ref._w(p["embed_tokens"][tokens]) * kw["embedding_multiplier"]
        y = ref.rms_norm(x, layer["input_layernorm"], kw["eps"])
        attn = ref.attention(
            layer, y * kw["attention_in_multiplier"], n_head=kw["n_head"],
            n_kv_head=kw["n_kv_head"], head_dim=kw["head_dim"],
            rope_theta=kw["rope_theta"],
            key_multiplier=kw["key_multiplier"]) \
            * kw["attention_out_multiplier"]
        mixer = dict(eps=kw["eps"], mamba_heads=kw["mamba_heads"],
                     mamba_head_dim=kw["mamba_head_dim"],
                     n_groups=kw["n_groups"], ssm_state=kw["ssm_state"],
                     ssm_multipliers=kw["ssm_multipliers"])
        u = y * kw["ssm_in_multiplier"]
        ssm = ref.mamba2(layer, u, **mixer) * kw["ssm_out_multiplier"]
        # the same mixer without the skip D x: the state's readout alone
        no_skip = ref.mamba2({**layer, "D": jnp.zeros_like(layer["D"])}, u,
                             **mixer) * kw["ssm_out_multiplier"]
        x1 = x + attn + ssm
        mlp = ref.mlp(layer, ref.rms_norm(x1, layer["pre_ff_layernorm"],
                                          kw["eps"]), kw["mlp_multipliers"])
        q = (y * kw["attention_in_multiplier"]) @ ref._w(layer["q_proj"])
        k = (y * kw["attention_in_multiplier"]) @ ref._w(layer["k_proj"]) \
            * kw["key_multiplier"]
    return {"stream_rms": rms(x), "attention_rms": rms(attn),
            "ssm_rms": rms(ssm), "mlp_rms": rms(mlp),
            "ssm_moved_by_skip": rms(ssm - no_skip) / rms(ssm),
            "score_spread": rms(q) * rms(k)}
