"""Falcon-H1 (``model_type: falcon_h1``): attention AND a Mamba-2 mixer side
by side in EVERY layer, served through ``LLMEngine``.

A layer runs both mixers on the same normed input and adds them, then a
gated MLP; fourteen published muP multipliers scale activations where the
released ``modeling_falcon_h1`` applies them (e = ``embedding_multiplier``,
``m`` = ``mlp_multipliers``):

  ``x0 = E[token] * e``
  ``y  = RMSNorm(x)``
  ``A  = Attn(y * attention_in_multiplier)``: grouped queries, no bias,
         ``k = (y Wk) * key_multiplier``, q and k rotated over the whole
         head (``ops/rotary.py``, the halves against each other), causal
         ``softmax(q k^T / sqrt(head_dim)) v``, then ``Wo``;
  ``S  = Mamba2(y * ssm_in_multiplier)``: ``ops/mamba2.py``'s mixer, the
         one both other hybrids call, with ``in_proj``'s output columns
         times ``ssm_multipliers`` by part (z, x, B, C, dt);
  ``x  = x + A * attention_out_multiplier + S * ssm_out_multiplier``
  ``x  = x + down(up(y2) * silu(gate(y2) * m[0])) * m[1]``, ``y2 =
         RMSNorm(x)``
  ``logits = (RMSNorm(x) W_head) * lm_head_multiplier`` (untied head).

No multiplier is folded into a stored weight: each is applied to the
activation, in float32, and the product rounded once to the activations'
type. ``benchmark/reference/falcon_h1.py`` writes the equations out
plainly; the tests hold this file to it.

The cache: there is no layer pattern, so EVERY layer owns a K/V ring
(rotated keys at their true positions: a wrapped ring is a window), a
convolution tail and a float32 SSM state. The rings are one stacked array
[layer, slot, row, W] for K and one for V, a token's K/V heads MERGED in
one row (GPT-2's layout, ``ops/attention.py``'s rank 4; at the published
4 heads of 128 a row is four whole lane tiles and has no pad), the tails
one array and the state one array a layer (``ops/mamba2.init_state``). Both
programs read the rings as they were and write their new rows once a stack
after the layer loop: with a ring in EVERY layer, windows re-laid out for
the heads-apart products were half of a step and a write inside the loop
cost a chunk two copies of whole stacks (PERF.md section 6, PR 44). The
two hybrids with an attention layer in ten keep their rings heads apart. In
a decode step a layer's ring read and its state rewrite depend on nothing
of each other.

Seeded weights (``falcon_h1_init``) are drawn by ``cfg.gains``: see there.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from ray_tpu.models.common import (_normal,
                                   merged_row as _merged_row,
                                   rms_norm as _rms_norm)
from ray_tpu.models.prefill import whole_prompts
from ray_tpu.ops import mamba2
from ray_tpu.ops.attention import (cache_write_chunk, cache_write_token,
                                   cached_decode_attention, causal_attention,
                                   chunk_attention_arm, merged_chunk_attention,
                                   merged_row_width, ring_rows_counted)
from ray_tpu.ops.rotary import rotate

Params = dict[str, Any]

# How ``falcon_h1_init`` draws a matrix: normal at ``gain / (sqrt(fan_in) *
# multiplier)``, ``multiplier`` the published factor the released code
# applies to the matrix's output (``in_proj``: ``ssm_in_multiplier *
# ssm_multipliers[1]``, the x columns), so that ``gain`` is the rms of that
# output, multiplier included, for an input of rms one. At 0.02 throughout
# the multipliers (made for a checkpoint trained under muP) leave the stream
# the token's own row times 5.66 beside branches of 1e-3: a seeded model
# answers every token with itself and a comparison with a reference holds
# nothing of the mixers. With these gains each branch is of the stream's
# order after the first layer, the scores spread over more than a unit, and
# the state's readout weighs what the skip ``D x`` weighs
# (``branch_readings``). Nothing a released checkpoint would need.
GAINS = (("embed", 1.0), ("q", 1.2), ("k", 1.2), ("v", 1.0), ("o", 4.0),
         ("ssm_in", 1.25), ("ssm_out", 0.6), ("gate", 1.0), ("up", 1.0),
         ("down", 1.2), ("head", 1.0))


@dataclasses.dataclass(frozen=True)
class FalconH1Config:
    vocab_size: int = 261120
    d_model: int = 5120
    n_layer: int = 72
    eps: float = 1e-5
    # attention: grouped queries, rotary over the whole head
    n_head: int = 20
    n_kv_head: int = 4
    head_dim: int = 128
    rope_theta: float = 1e11
    # Mamba-2 mixer: d_inner = mamba_heads * mamba_head_dim, which is NOT
    # ``mamba_expand * d_model`` here
    mamba_heads: int = 32
    mamba_head_dim: int = 128
    ssm_groups: int = 2
    ssm_state: int = 256
    conv_kernel: int = 4
    chunk_size: int = 128  # how a prefill blocks the scan; no result moves
    d_ff: int = 21504
    # the fourteen muP multipliers
    embedding_multiplier: float = 5.656854249492381
    lm_head_multiplier: float = 0.0078125
    key_multiplier: float = 0.011048543456039804
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 0.0375
    ssm_in_multiplier: float = 0.25
    ssm_out_multiplier: float = 0.08838834764831845
    ssm_multipliers: tuple = (0.3535533905932738, 0.25, 0.1767766952966369,
                              0.5, 0.3535533905932738)  # z, x, B, C, dt
    mlp_multipliers: tuple = (0.1767766952966369, 0.011160714285714284)
    dtype: Any = jnp.bfloat16        # activations and matmuls
    param_dtype: Any = jnp.bfloat16  # as the checkpoint stores them
    ssm_state_dtype: Any = jnp.float32
    gains: tuple = GAINS

    def __post_init__(self):
        object.__setattr__(self, "ssm_multipliers",
                           tuple(float(m) for m in self.ssm_multipliers))
        object.__setattr__(self, "mlp_multipliers",
                           tuple(float(m) for m in self.mlp_multipliers))
        object.__setattr__(self, "gains", tuple(
            (str(k), float(v)) for k, v in dict(self.gains).items()))
        if len(self.ssm_multipliers) != 5 or len(self.mlp_multipliers) != 2:
            raise ValueError("ssm_multipliers holds five factors (z, x, B, "
                             "C, dt) and mlp_multipliers two (gate, down)")
        if dict(self.gains).keys() != dict(GAINS).keys():
            raise ValueError(f"gains {self.gains}: want the keys "
                             f"{sorted(dict(GAINS))}")
        if self.n_head % self.n_kv_head or \
                self.mamba_heads % self.ssm_groups or self.head_dim % 2:
            raise ValueError("heads must divide into their groups, and a "
                             "head into rotary pairs")
        if self.n_layer < 1:
            raise ValueError("n_layer must be at least 1")

    @property
    def mamba(self) -> mamba2.Mamba2Dims:
        """The mixers' sizes, as ``ops/mamba2.py`` takes them."""
        return mamba2.Mamba2Dims(
            heads=self.mamba_heads, head_dim=self.mamba_head_dim,
            groups=self.ssm_groups, state=self.ssm_state,
            kernel=self.conv_kernel, block=self.chunk_size, eps=self.eps,
            dtype=self.dtype, state_dtype=self.ssm_state_dtype,
            in_multipliers=self.ssm_multipliers)

    def serving_dtypes(self, params: Params) -> Params:
        """How an engine stores ``params``: as ``falcon_h1_init`` made them
        (see ``NemotronHConfig.serving_dtypes``)."""
        return jax.tree.map(lambda x: x.dtype, params)

    def serving_stats(self, chunk: int = 0, window: int = 0) -> dict:
        """What ``llm_stats()`` says of the model beside the engine's own
        counters: the token-expert pairs its chunks make, none (the family
        has no experts). The benchmark's reader of the chunk program takes
        a program without this counter for one that cannot be read
        (PERF.md section 7 asks for its repair); a constant costs the
        programs nothing. And which implementation a chunk program of
        ``chunk`` tokens over a key window of ``window`` rows (the engine's)
        attends through (``ops/attention.chunk_attention_arm``: static, by
        shapes alone)."""
        return {"prefill_expert_rows": 0,
                "chunk_attention_arm": chunk_attention_arm(
                    chunk, self.head_dim,
                    merged_row_width(self.n_kv_head, self.head_dim), window)}

    @classmethod
    def tiny(cls, **kw) -> "FalconH1Config":
        """Both mixers at a size a CPU test runs: two groups, a state that
        is not the head size, ``d_inner`` (64) that is not twice the hidden
        size, fewer K/V heads than query heads, and every multiplier a
        value that is neither 1 nor a power of two."""
        base = dict(
            vocab_size=256, d_model=48, n_layer=3, n_head=4, n_kv_head=2,
            head_dim=16, rope_theta=1e4, mamba_heads=8, mamba_head_dim=8,
            ssm_groups=2, ssm_state=24, chunk_size=8, d_ff=80,
            embedding_multiplier=2.3, lm_head_multiplier=0.37,
            key_multiplier=0.43, attention_in_multiplier=0.9,
            attention_out_multiplier=0.31, ssm_in_multiplier=0.7,
            ssm_out_multiplier=0.27, ssm_multipliers=(0.6, 0.45, 0.35, 0.8,
                                                      0.55),
            mlp_multipliers=(0.65, 0.21))
        base.update(kw)
        return cls(**base)


# -- parameters ---------------------------------------------------------------


def init_stds(cfg: FalconH1Config) -> dict:
    """The standard deviation each matrix is drawn at (``GAINS`` says
    why): ``gain / (sqrt(fan_in) * multiplier)``."""
    g = dict(cfg.gains)
    d = cfg.d_model ** 0.5
    return {
        "embed": g["embed"] / cfg.embedding_multiplier,
        "wq": g["q"] / (d * cfg.attention_in_multiplier),
        "wk": g["k"] / (d * cfg.attention_in_multiplier
                        * cfg.key_multiplier),
        "wv": g["v"] / (d * cfg.attention_in_multiplier),
        "wo": g["o"] / ((cfg.n_head * cfg.head_dim) ** 0.5
                        * cfg.attention_out_multiplier),
        "in_proj": g["ssm_in"] / (d * cfg.ssm_in_multiplier
                                  * cfg.ssm_multipliers[1]),
        "out_proj": g["ssm_out"] / (cfg.mamba.d_inner ** 0.5
                                    * cfg.ssm_out_multiplier),
        "w_gate": g["gate"] / (d * cfg.mlp_multipliers[0]),
        "w_up": g["up"] / d,
        "w_down": g["down"] / (cfg.d_ff ** 0.5 * cfg.mlp_multipliers[1]),
        "lm_head": g["head"] / (d * cfg.lm_head_multiplier),
    }


def _layer_init(key, cfg: FalconH1Config, std: dict) -> Params:
    d, pd = cfg.d_model, cfg.param_dtype
    q, kv = cfg.n_head * cfg.head_dim, cfg.n_kv_head * cfg.head_dim
    keys = iter(jax.random.split(key, 13))
    p = {"norm": jnp.ones((d,), pd), "norm2": jnp.ones((d,), pd)}
    p.update(wq=_normal(next(keys), (d, q), std["wq"], pd),
             wk=_normal(next(keys), (d, kv), std["wk"], pd),
             wv=_normal(next(keys), (d, kv), std["wv"], pd),
             wo=_normal(next(keys), (q, d), std["wo"], pd))
    p.update(mamba2.mixer_init(keys, d, cfg.mamba, pd, _normal,
                               std["out_proj"], in_std=std["in_proj"]))
    p.update(w_gate=_normal(next(keys), (d, cfg.d_ff), std["w_gate"], pd),
             w_up=_normal(next(keys), (d, cfg.d_ff), std["w_up"], pd),
             w_down=_normal(next(keys), (cfg.d_ff, d), std["w_down"], pd))
    return p


def falcon_h1_init(rng: jax.Array, cfg: FalconH1Config) -> Params:
    """Seeded weights in ``cfg.param_dtype`` (bfloat16 as published), one
    dict a layer, every matrix normal at ``init_stds``'s value, norms at
    one, Mamba-2's ``dt``, ``A``, ``D`` and convolution as
    ``ops/mamba2.mixer_init`` draws them. The head is a table of its own
    (``tie_word_embeddings`` false), stored [V, D] as the embedding."""
    keys = jax.random.split(rng, cfg.n_layer + 2)
    pd, std = cfg.param_dtype, init_stds(cfg)
    return {
        "embed": _normal(keys[0], (cfg.vocab_size, cfg.d_model),
                         std["embed"], pd),
        "layers": [_layer_init(keys[2 + i], cfg, std)
                   for i in range(cfg.n_layer)],
        "norm_f": jnp.ones((cfg.d_model,), pd),
        "lm_head": _normal(keys[1], (cfg.vocab_size, cfg.d_model),
                           std["lm_head"], pd),
    }


# -- the parts ----------------------------------------------------------------


def _times(x: jax.Array, m: float) -> jax.Array:
    """``x * m`` for a published multiplier: in float32 (the factor is not
    rounded to the activations' type), the product in x's type."""
    return x if m == 1.0 else (x.astype(jnp.float32) * m).astype(x.dtype)


def _qkv(p: Params, y: jax.Array, pos: jax.Array, cfg: FalconH1Config):
    """The attention branch's inputs of normed rows y [..., D] at positions
    pos [...]: q [..., H, hd] and k [..., G, hd] rotated, v [..., G, hd]."""
    dt_ = cfg.dtype
    lead = y.shape[:-1]
    with jax.named_scope("attn_proj"):
        y = _times(y, cfg.attention_in_multiplier)
        q = (y @ p["wq"].astype(dt_)).reshape(*lead, cfg.n_head,
                                              cfg.head_dim)
        k = _times(y @ p["wk"].astype(dt_), cfg.key_multiplier).reshape(
            *lead, cfg.n_kv_head, cfg.head_dim)
        v = (y @ p["wv"].astype(dt_)).reshape(*lead, cfg.n_kv_head,
                                              cfg.head_dim)
    with jax.named_scope("rope"):
        q = rotate(q, pos, cfg.rope_theta)
        k = rotate(k, pos, cfg.rope_theta)
    return q, k, v


def _mixer_sum(x, attn, ssm, cfg: FalconH1Config):
    """``x + A * attention_out_multiplier + S * ssm_out_multiplier``: the
    two branches meet the stream in one float32 sum."""
    with jax.named_scope("mixer_sum"):
        return (x.astype(jnp.float32)
                + attn.astype(jnp.float32) * cfg.attention_out_multiplier
                + ssm.astype(jnp.float32) * cfg.ssm_out_multiplier
                ).astype(x.dtype)


def _mlp(p: Params, x: jax.Array, cfg: FalconH1Config) -> jax.Array:
    """``x + down(up(y) * silu(gate(y) * m[0])) * m[1]``, y = RMSNorm(x)."""
    dt_ = cfg.dtype
    with jax.named_scope("ln"):
        y = _rms_norm(x, p["norm2"], cfg.eps)
    with jax.named_scope("mlp"):
        gate = _times(y @ p["w_gate"].astype(dt_), cfg.mlp_multipliers[0])
        out = (y @ p["w_up"].astype(dt_) * jax.nn.silu(gate)) \
            @ p["w_down"].astype(dt_)
        return x + _times(out, cfg.mlp_multipliers[1])


def _head(x: jax.Array, params: Params, cfg: FalconH1Config):
    """``(RMSNorm(x) W_head) * lm_head_multiplier``, float32. x [..., D]."""
    with jax.named_scope("ln"):
        x = _rms_norm(x, params["norm_f"], cfg.eps)
    with jax.named_scope("head"):
        return jnp.einsum(
            "...d,vd->...v", x, params["lm_head"].astype(cfg.dtype),
            preferred_element_type=jnp.float32) * cfg.lm_head_multiplier


def _embed(params: Params, tokens: jax.Array, cfg: FalconH1Config):
    with jax.named_scope("embed"):
        return _times(params["embed"].astype(cfg.dtype)[tokens],
                      cfg.embedding_multiplier)


# -- the cache and the serving functions --------------------------------------


def falcon_h1_init_cache(cfg: FalconH1Config, slots: int,
                         cache_len: int) -> Params:  # decode-path
    """For EVERY layer a K/V ring (one stacked array each for K and V,
    [layer, slot, row, W]: a token's K/V heads merged in one row,
    ``merged_row_width``, which both programs read as it lies; the same
    bytes as heads apart wherever the row needs no pad, as at the published
    and the tiny sizes), the convolution's tail and the float32 SSM state
    (``ops/mamba2.init_state``): one pytree, which the engine donates."""
    kv = (cfg.n_layer, slots, cache_len,
          merged_row_width(cfg.n_kv_head, cfg.head_dim))
    return {"k": jnp.zeros(kv, cfg.dtype), "v": jnp.zeros(kv, cfg.dtype),
            **mamba2.init_state(cfg.mamba, cfg.n_layer, slots)}


# jax-hot-path: traced into the engine's single compiled decode step
def falcon_h1_decode_step(params: Params, cache: Params, tokens: jax.Array,
                          pos: jax.Array, cfg: FalconH1Config
                          ) -> tuple[jax.Array, Params, dict]:
    """One decode iteration for every slot: tokens [S] int32, pos [S]
    int32 -> (logits [S, V] fp32, new cache, counters ``ring_rows_read``
    and ``ring_rows_held``: what the step's attention read of the rings,
    ``ops/attention.ring_rows_counted``). The K/V part keeps
    ``gpt2_decode_step``'s ring contract and its layout (the rings of
    merged rows are read as they were, each layer's block as it lies with
    the five query heads of a K/V head standing in its columns, and every
    layer's new rows written after the loop); the keys are rotated before
    they are stored, so a wrapped ring is a window."""
    s = tokens.shape[0]
    dt_ = cfg.dtype
    cache_len = cache["k"].shape[2]
    cursor = jnp.mod(pos, cache_len)
    valid = jnp.minimum(pos + 1, cache_len)
    x = _embed(params, tokens, cfg)
    conv_all, ssm_all = cache["conv"], list(cache["ssm"])
    k_rows, v_rows = [], []
    for i, p in enumerate(params["layers"]):
        with jax.named_scope("ln"):
            y = _rms_norm(x, p["norm"], cfg.eps)
        q, k_new, v_new = _qkv(p, y, pos, cfg)
        k_new = _merged_row(k_new, cache["k"])
        v_new = _merged_row(v_new, cache["v"])
        with jax.named_scope("attn"):
            attn = cached_decode_attention(
                q, cache["k"], cache["v"], k_new, v_new, cursor, valid,
                dt_, layer=i)
        with jax.named_scope("attn_proj"):
            attn = attn.reshape(s, -1) @ p["wo"].astype(dt_)
        ssm, conv_all, ssm_all[i] = mamba2.step_through_cache(
            p, _times(y, cfg.ssm_in_multiplier), conv_all, ssm_all[i], i,
            cfg.mamba)
        k_rows.append(k_new)
        v_rows.append(v_new)
        x = _mlp(p, _mixer_sum(x, attn, ssm, cfg), cfg)
    with jax.named_scope("cache_write"):
        k_all = cache_write_token(cache["k"], jnp.stack(k_rows), cursor)
        v_all = cache_write_token(cache["v"], jnp.stack(v_rows), cursor)
    return _head(x, params, cfg), {
        "k": k_all, "v": v_all, "conv": conv_all, "ssm": tuple(ssm_all)
    }, ring_rows_counted(cache["k"], valid)


def _rows(params: Params, tokens: jax.Array, lengths: jax.Array,
          cfg: FalconH1Config, cache: Params | None = None,
          slots: jax.Array | None = None, start: jax.Array | None = None,
          window: int | None = None):
    """Rows of T tokens through every layer: tokens [R, T], lengths [R].
    Without a cache, whole rows from nothing. With one, row r is a chunk
    of a prompt at positions ``start[r] + i``: every layer reads
    ``slots[r]``'s rows ``< start`` as earlier chunks left them, BEFORE the
    chunk's own are written, and takes the chunk's own beside them
    (``merged_chunk_attention``: the softmax a write and then a read give);
    the nine layers' rows are written once a stack after the loop
    (``cache_write_chunk``, ``gpt2_prefill_chunk``'s order: a write inside
    the loop made the compiler copy whole stacks). Every layer continues
    the slot's SSM state, leaving there, in place, its state after the
    row's real tokens (``nemotron_h._rows``'s contract).
    -> (hidden [R, T, D] before ``norm_f``, the cache)."""
    r, t = tokens.shape
    dt_ = cfg.dtype
    x = _embed(params, tokens, cfg)
    pos = jnp.arange(t)[None, :] + (0 if start is None else start[:, None])
    if cache is not None:
        conv_all, ssm_all = cache["conv"], list(cache["ssm"])
        k_rows, v_rows = [], []
        window = window or cache["k"].shape[2]
        goes_on = start > 0  # [R]: the slot holds this prompt's state
    for i, p in enumerate(params["layers"]):
        with jax.named_scope("ln"):
            y = _rms_norm(x, p["norm"], cfg.eps)
        q, k_, v_ = _qkv(p, y, pos, cfg)
        y_ssm = _times(y, cfg.ssm_in_multiplier)
        if cache is None:
            with jax.named_scope("attn"):
                rep = cfg.n_head // cfg.n_kv_head
                attn = causal_attention(
                    q, jnp.repeat(k_, rep, axis=2),
                    jnp.repeat(v_, rep, axis=2), use_flash=False)
            ssm, _, _ = mamba2.mamba_rows(p, y_ssm, lengths, cfg.mamba)
        else:
            k_ = _merged_row(k_, cache["k"])
            v_ = _merged_row(v_, cache["v"])
            with jax.named_scope("attn"):
                attn = merged_chunk_attention(
                    q, cache["k"], cache["v"], k_, v_, i, slots, start,
                    window)
            k_rows.append(k_)
            v_rows.append(v_)
            ssm, conv_all, ssm_all[i] = mamba2.rows_through_cache(
                p, y_ssm, lengths, conv_all, ssm_all[i], i, slots, goes_on,
                cfg.mamba)
        with jax.named_scope("attn_proj"):
            attn = attn.reshape(r, t, -1) @ p["wo"].astype(dt_)
        x = _mlp(p, _mixer_sum(x, attn, ssm, cfg), cfg)
    if cache is not None:
        with jax.named_scope("cache_write"):
            k_all = cache_write_chunk(cache["k"], jnp.stack(k_rows), slots,
                                      start)
            v_all = cache_write_chunk(cache["v"], jnp.stack(v_rows), slots,
                                      start)
        cache = {"k": k_all, "v": v_all, "conv": conv_all,
                 "ssm": tuple(ssm_all)}
    return x, cache


# jax-hot-path: traced into the engine's single compiled prefill program
def falcon_h1_prefill_chunk(params: Params, cache: Params,
                            tokens: jax.Array, slots: jax.Array,
                            start: jax.Array, lengths: jax.Array,
                            cfg: FalconH1Config, window: int | None = None
                            ) -> tuple[jax.Array, Params]:
    """A chunk of a prompt (fixed [R, C] shape; the contract of
    ``models/prefill.py`` and, for both kinds of state,
    ``nemotron_h_prefill_chunk``'s). Logits at the chunk's last real
    token."""
    r, c = tokens.shape
    x, cache = _rows(params, tokens, lengths, cfg, cache, slots, start,
                     window)
    last = x[jnp.arange(r), jnp.clip(lengths - 1, 0, c - 1)]
    return _head(last, params, cfg), cache


def falcon_h1_prefill(params: Params, cache: Params, tokens: jax.Array,
                      slots: jax.Array, lengths: jax.Array,
                      cfg: FalconH1Config) -> tuple[jax.Array, Params]:
    """Whole padded prompts tokens [R, P] through
    ``falcon_h1_prefill_chunk`` (``models/prefill.py``). Logits at each
    prompt's last real token."""
    return whole_prompts(falcon_h1_prefill_chunk, params, cache, tokens,
                         slots, lengths, cfg)


def falcon_h1_forward(params: Params, tokens: jax.Array,
                      cfg: FalconH1Config) -> jax.Array:
    """Logits [R, T, V] float32 of whole rows, no cache (tests)."""
    lengths = jnp.full((tokens.shape[0],), tokens.shape[1], jnp.int32)
    x, _ = _rows(params, tokens, lengths, cfg)
    return _head(x, params, cfg)
