"""Granite 4.0-H (``model_type: granitemoehybrid``): a sequence mixer AND
THEN sparse experts in every layer, served through ``LLMEngine``.

A layer is two residual branches, both times ``residual_multiplier``:

  ``x = x + r * Mixer(RMSNorm(x))``, the mixer a Mamba-2 mixer
  (``ops/mamba2.py``, the one ``models/nemotron_h.py`` calls) or
  grouped-query attention without position embedding whose scores are scaled
  by the published ``attention_multiplier`` (not ``head_dim ** -0.5``);
  ``x = x + r * (Routed(h) + Shared(h))``, ``h = RMSNorm(x)``: the router
  takes the ``top_k`` largest of its float32 logits and a softmax over
  those; an expert is gated, ``W2 (silu(a) * b)`` with ``[a, b] = W1 h``;
  the shared expert is the same form, every token.

``layer_types`` holds ``"mamba"`` or ``"attention"`` a layer. The embedding
is multiplied by ``embedding_multiplier``, the head is the embedding
transposed, and the logits are divided by ``logits_scaling``.
``benchmark/reference/granite_hybrid.py`` writes the equations out plainly;
the tests hold this file to it.

The gated expert needs no product of its own: ``ops/moe.dropless_experts``
applies ``activation`` between its two products, and with ``w1`` stored
[E, D, 2 F] the activation ``silu(a) * b`` halves the width on the way.

The cache, the expert share (``experts_held``), the stored types and the
step's counters are as ``models/nemotron_h.py`` has them. New here: the
cache carries ``counted``, int32 scalars that the programs add to in place:
``prefill_expert_rows``, the token-expert pairs the held experts took in
chunks so far. The engine reads what a cache counts once an admission turn
and says it in ``llm_stats()``; the chunk program returns what every
family's returns.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from ray_tpu.models.common import (_normal,
                                   gate as _gate,
                                   rms_norm as _rms_norm)
from ray_tpu.models.prefill import whole_prompts
from ray_tpu.ops import mamba2
from ray_tpu.ops.attention import (cache_write_prompt, cache_write_token,
                                   cached_chunk_attention,
                                   cached_decode_attention, causal_attention)
from ray_tpu.ops.moe import (dropless_experts, held_counters,
                             route_topk_softmax)

Params = dict[str, Any]

PUBLISHED_LAYER_TYPES = (("mamba",) * 5 + ("attention",) + ("mamba",) * 4) \
    * 4


@dataclasses.dataclass(frozen=True)
class GraniteHybridConfig:
    vocab_size: int = 100352
    d_model: int = 4096
    layer_types: tuple = PUBLISHED_LAYER_TYPES
    eps: float = 1e-5
    # attention: grouped queries, no position embedding, a published scale
    n_head: int = 32
    n_kv_head: int = 8
    head_dim: int = 128
    attention_multiplier: float = 0.0078125
    # Mamba-2 mixer
    mamba_heads: int = 128
    mamba_head_dim: int = 64
    ssm_groups: int = 1
    ssm_state: int = 128
    conv_kernel: int = 4
    chunk_size: int = 256  # how a prefill blocks the scan; no result moves
    # experts, in every layer
    n_experts: int = 72       # the router's width: every expert of the model
    experts_held: tuple = (0, 72)  # (first, count) of the experts held here
    top_k: int = 10
    expert_ff: int = 768
    shared_ff: int = 1536
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    logits_scaling: float = 16.0
    dtype: Any = jnp.bfloat16        # activations and matmuls
    param_dtype: Any = jnp.bfloat16  # as the checkpoint stores them
    ssm_state_dtype: Any = jnp.float32
    # How ``granite_hybrid_init`` draws the seeded embedding (every other
    # matrix normal at 0.02): under a tied head times
    # ``embedding_multiplier`` it decides whether a seeded model answers
    # a token with itself (0.02: the token's own row weighs enough that a
    # test sees ``embedding_multiplier`` left out) or with its context
    # (0.002, the benchmark's: what an engine serves then depends on its
    # cache). Nothing a released checkpoint would need.
    embed_std: float = 0.02

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        bad = set(self.layer_types) - {"mamba", "attention"}
        if bad or not self.layer_types:
            raise ValueError(f"layer_types {self.layer_types!r}: want "
                             f"'mamba' and 'attention'")
        first, count = self.experts_held
        if not (0 <= first and count >= 1
                and first + count <= self.n_experts):
            raise ValueError(f"experts_held {self.experts_held} outside "
                             f"the router's {self.n_experts}")
        if self.n_head % self.n_kv_head or \
                self.mamba_heads % self.ssm_groups:
            raise ValueError("heads must divide into their groups")

    @property
    def mamba(self) -> mamba2.Mamba2Dims:
        """The Mamba layers' sizes, as ``ops/mamba2.py`` takes them."""
        return mamba2.Mamba2Dims(
            heads=self.mamba_heads, head_dim=self.mamba_head_dim,
            groups=self.ssm_groups, state=self.ssm_state,
            kernel=self.conv_kernel, block=self.chunk_size, eps=self.eps,
            dtype=self.dtype, state_dtype=self.ssm_state_dtype)

    def count(self, kind: str) -> int:
        return self.layer_types.count(kind)

    def serving_dtypes(self, params: Params) -> Params:
        """How an engine stores ``params``: as ``granite_hybrid_init`` made
        them (see ``NemotronHConfig.serving_dtypes``)."""
        return jax.tree.map(lambda x: x.dtype, params)

    def serving_stats(self, chunk: int = 0, window: int = 0) -> dict:
        """What ``llm_stats()`` says of the model beside its counters."""
        return {"expert_layers": len(self.layer_types),
                "experts_held": self.experts_held[1]}

    @classmethod
    def tiny(cls, **kw) -> "GraniteHybridConfig":
        """Both mixers at a size a CPU test runs. The attention scale is
        not ``head_dim ** -0.5`` here either."""
        base = dict(vocab_size=256, d_model=64,
                    layer_types=("mamba", "attention", "mamba"), n_head=4,
                    n_kv_head=2, head_dim=16, attention_multiplier=0.5,
                    mamba_heads=8, mamba_head_dim=16, ssm_groups=1,
                    ssm_state=16, chunk_size=8, n_experts=8,
                    experts_held=(0, 4), top_k=3, expert_ff=48,
                    shared_ff=96)
        base.update(kw)
        return cls(**base)


# -- parameters ---------------------------------------------------------------


def _layer_init(key, kind: str, cfg: GraniteHybridConfig) -> Params:
    d, pd, std = cfg.d_model, cfg.param_dtype, 0.02
    keys = iter(jax.random.split(key, 12))
    p = {"norm": jnp.ones((d,), pd), "norm2": jnp.ones((d,), pd)}
    if kind == "mamba":
        p.update(mamba2.mixer_init(keys, d, cfg.mamba, pd, _normal, std))
    else:
        q, kv = cfg.n_head * cfg.head_dim, cfg.n_kv_head * cfg.head_dim
        p.update(wq=_normal(next(keys), (d, q), std, pd),
                 wk=_normal(next(keys), (d, kv), std, pd),
                 wv=_normal(next(keys), (d, kv), std, pd),
                 wo=_normal(next(keys), (q, d), std, pd))
    held, ff = cfg.experts_held[1], cfg.expert_ff
    p.update(
        router=_normal(next(keys), (d, cfg.n_experts), std, pd),
        # [a, b] = W1 h side by side: the gate's halves of one product
        w1=_normal(next(keys), (held, d, 2 * ff), std, pd),
        w2=_normal(next(keys), (held, ff, d), std, pd),
        shared_w1=_normal(next(keys), (d, 2 * cfg.shared_ff), std, pd),
        shared_w2=_normal(next(keys), (cfg.shared_ff, d), std, pd))
    return p


def granite_hybrid_init(rng: jax.Array, cfg: GraniteHybridConfig) -> Params:
    """Seeded weights in ``cfg.param_dtype`` (bfloat16 as published), one
    dict a layer, every matrix normal at 0.02 (the embedding at
    ``cfg.embed_std``), Mamba-2's ``dt`` and ``A`` as
    ``ops/mamba2.mixer_init`` draws them. The head is the embedding: there
    is no ``lm_head``."""
    keys = jax.random.split(rng, len(cfg.layer_types) + 1)
    pd = cfg.param_dtype
    return {
        "embed": _normal(keys[0], (cfg.vocab_size, cfg.d_model),
                         cfg.embed_std, pd),
        "layers": [_layer_init(keys[1 + i], kind, cfg)
                   for i, kind in enumerate(cfg.layer_types)],
        "norm_f": jnp.ones((cfg.d_model,), pd),
    }


# -- the parts ----------------------------------------------------------------


def _moe(p: Params, y: jax.Array, cfg: GraniteHybridConfig,
         live: jax.Array | None = None):
    """Routed experts plus the shared expert over rows y [T, D] (normed):
    the held experts' part of the routed output; rows that ``live`` [T]
    says are padding are routed nowhere. -> (out [T, D], the pairs each
    held expert took [count])."""
    dt_ = cfg.dtype
    with jax.named_scope("router"):
        ids, weights = route_topk_softmax(y, p["router"], cfg.top_k)
    routed, counts = dropless_experts(
        y, ids, weights, p["w1"], p["w2"], first=cfg.experts_held[0],
        activation=_gate, live=live)
    with jax.named_scope("shared_expert"):
        shared = _gate(y @ p["shared_w1"].astype(dt_)) \
            @ p["shared_w2"].astype(dt_)
        out = (routed + shared.astype(jnp.float32)).astype(dt_)
    return out, counts


def _head(x: jax.Array, params: Params, cfg: GraniteHybridConfig):
    """``RMSNorm(x) E^T / logits_scaling``, float32. x [..., D]."""
    with jax.named_scope("ln"):
        x = _rms_norm(x, params["norm_f"], cfg.eps)
    with jax.named_scope("head"):
        return jnp.einsum(
            "...d,vd->...v", x, params["embed"].astype(cfg.dtype),
            preferred_element_type=jnp.float32) / cfg.logits_scaling


def _embed(params: Params, tokens: jax.Array, cfg: GraniteHybridConfig):
    with jax.named_scope("embed"):
        return params["embed"].astype(cfg.dtype)[tokens] \
            * jnp.asarray(cfg.embedding_multiplier, cfg.dtype)


# -- the cache and the serving functions --------------------------------------


def granite_hybrid_init_cache(cfg: GraniteHybridConfig, slots: int,
                              cache_len: int) -> Params:  # decode-path
    """K/V rows for the attention layers only (a ring, as the other
    families'), and for every Mamba layer the convolution's tail and the
    float32 SSM state (``ops/mamba2.init_state``), and what the programs
    count (``counted``: int32 scalars, which wrap): one pytree, which the
    engine donates."""
    kv = (cfg.count("attention"), slots, cache_len, cfg.n_kv_head,
          cfg.head_dim)
    return {"k": jnp.zeros(kv, cfg.dtype), "v": jnp.zeros(kv, cfg.dtype),
            **mamba2.init_state(cfg.mamba, cfg.count("mamba"), slots),
            "counted": {"prefill_expert_rows": jnp.zeros((), jnp.int32)}}


# jax-hot-path: traced into the engine's single compiled decode step
def granite_hybrid_decode_step(params: Params, cache: Params,
                               tokens: jax.Array, pos: jax.Array,
                               cfg: GraniteHybridConfig
                               ) -> tuple[jax.Array, Params, dict]:
    """One decode iteration for every slot: tokens [S] int32, pos [S]
    int32 -> (logits [S, V] fp32, new cache, counters ``experts_hit`` and
    ``expert_rows`` over the step's layers). Every row is computed, free
    slots and the scratch one too, so the counters count what the step
    really routed. The K/V part keeps ``gpt2_decode_step``'s ring contract;
    with no position embedding a wrapped ring is a window."""
    s = tokens.shape[0]
    nh, nkv, hd = cfg.n_head, cfg.n_kv_head, cfg.head_dim
    dt_ = cfg.dtype
    res = jnp.asarray(cfg.residual_multiplier, dt_)
    cache_len = cache["k"].shape[2]
    cursor = jnp.mod(pos, cache_len)
    valid = jnp.minimum(pos + 1, cache_len)
    x = _embed(params, tokens, cfg)
    conv_all, ssm_all = cache["conv"], list(cache["ssm"])
    k_rows, v_rows, counts = [], [], []
    i_m = i_a = 0
    for kind, p in zip(cfg.layer_types, params["layers"]):
        with jax.named_scope("ln"):
            y = _rms_norm(x, p["norm"], cfg.eps)
        if kind == "mamba":
            out, conv_all, ssm_all[i_m] = mamba2.step_through_cache(
                p, y, conv_all, ssm_all[i_m], i_m, cfg.mamba)
            i_m += 1
        else:
            with jax.named_scope("attn_proj"):
                q = (y @ p["wq"].astype(dt_)).reshape(s, nh, hd)
                k_new = (y @ p["wk"].astype(dt_)).reshape(
                    s, nkv, hd).astype(cache["k"].dtype)
                v_new = (y @ p["wv"].astype(dt_)).reshape(
                    s, nkv, hd).astype(cache["v"].dtype)
            with jax.named_scope("attn"):
                attn = cached_decode_attention(
                    q, cache["k"][i_a], cache["v"][i_a], k_new, v_new,
                    cursor, valid, dt_, scale=cfg.attention_multiplier)
            with jax.named_scope("attn_proj"):
                out = attn.reshape(s, nh * hd) @ p["wo"].astype(dt_)
            k_rows.append(k_new)
            v_rows.append(v_new)
            i_a += 1
        x = x + out * res
        with jax.named_scope("ln"):
            y = _rms_norm(x, p["norm2"], cfg.eps)
        out, c = _moe(p, y, cfg)
        counts.append(c)
        x = x + out * res
    k_all, v_all = cache["k"], cache["v"]
    if k_rows:
        with jax.named_scope("cache_write"):
            k_all = cache_write_token(k_all, jnp.stack(k_rows), cursor)
            v_all = cache_write_token(v_all, jnp.stack(v_rows), cursor)
    return _head(x, params, cfg), {
        "k": k_all, "v": v_all, "conv": conv_all, "ssm": tuple(ssm_all),
        "counted": cache["counted"]}, held_counters(counts)


def _rows(params: Params, tokens: jax.Array, lengths: jax.Array,
          cfg: GraniteHybridConfig, cache: Params | None = None,
          slots: jax.Array | None = None, start: jax.Array | None = None,
          window: int | None = None):
    """Rows of T tokens through every layer: tokens [R, T], lengths [R].
    Without a cache, whole rows from nothing. With one, row r is a chunk
    of a prompt at positions ``start[r] + i``: every layer continues from
    its part of ``slots[r]``'s state and leaves there, in place, its state
    after the row's real tokens (``nemotron_h._rows``'s contract), and
    the token-expert pairs the held experts took are added to the cache's
    ``prefill_expert_rows``. -> (hidden [R, T, D] before ``norm_f``, the
    cache)."""
    r, t = tokens.shape
    nh, nkv, hd = cfg.n_head, cfg.n_kv_head, cfg.head_dim
    dt_ = cfg.dtype
    res = jnp.asarray(cfg.residual_multiplier, dt_)
    x = _embed(params, tokens, cfg)
    # a padded chunk's other positions are not routed: no expert computes them
    real = (jnp.arange(t)[None, :] < lengths[:, None]).reshape(-1)
    if cache is not None:
        k_all, v_all, conv_all = cache["k"], cache["v"], cache["conv"]
        ssm_all = list(cache["ssm"])
        window = window or k_all.shape[2]
        goes_on = start > 0  # [R]: the slot holds this prompt's state
    pairs = jnp.int32(0)
    i_m = i_a = 0
    for kind, p in zip(cfg.layer_types, params["layers"]):
        with jax.named_scope("ln"):
            y = _rms_norm(x, p["norm"], cfg.eps)
        if kind == "mamba" and cache is None:
            out, _, _ = mamba2.mamba_rows(p, y, lengths, cfg.mamba)
        elif kind == "mamba":
            out, conv_all, ssm_all[i_m] = mamba2.rows_through_cache(
                p, y, lengths, conv_all, ssm_all[i_m], i_m, slots, goes_on,
                cfg.mamba)
            i_m += 1
        else:
            with jax.named_scope("attn_proj"):
                q = (y @ p["wq"].astype(dt_)).reshape(r, t, nh, hd)
                k_ = (y @ p["wk"].astype(dt_)).reshape(r, t, nkv, hd)
                v_ = (y @ p["wv"].astype(dt_)).reshape(r, t, nkv, hd)
            if cache is None:
                with jax.named_scope("attn"):
                    rep = nh // nkv
                    attn = causal_attention(
                        q, jnp.repeat(k_, rep, axis=2),
                        jnp.repeat(v_, rep, axis=2), use_flash=False,
                        softmax_scale=cfg.attention_multiplier)
            else:
                with jax.named_scope("cache_write"):
                    k_all = cache_write_prompt(k_all, i_a, k_, slots, start)
                    v_all = cache_write_prompt(v_all, i_a, v_, slots, start)
                with jax.named_scope("attn"):
                    attn = cached_chunk_attention(
                        q, k_all, v_all, i_a, slots, start, window,
                        scale=cfg.attention_multiplier)
            with jax.named_scope("attn_proj"):
                out = attn.reshape(r, t, nh * hd) @ p["wo"].astype(dt_)
            i_a += 1
        x = x + out * res
        with jax.named_scope("ln"):
            y = _rms_norm(x, p["norm2"], cfg.eps)
        out, c = _moe(p, y.reshape(r * t, -1), cfg, real)
        pairs = pairs + jnp.sum(c, dtype=jnp.int32)
        x = x + out.reshape(r, t, -1) * res
    if cache is not None:
        cache = {"k": k_all, "v": v_all, "conv": conv_all,
                 "ssm": tuple(ssm_all), "counted": {
                     "prefill_expert_rows":
                     cache["counted"]["prefill_expert_rows"] + pairs}}
    return x, cache


# jax-hot-path: traced into the engine's single compiled prefill program
def granite_hybrid_prefill_chunk(params: Params, cache: Params,
                                 tokens: jax.Array, slots: jax.Array,
                                 start: jax.Array, lengths: jax.Array,
                                 cfg: GraniteHybridConfig,
                                 window: int | None = None
                                 ) -> tuple[jax.Array, Params]:
    """A chunk of a prompt (fixed [R, C] shape; the contract of
    ``models/prefill.py`` and, for both kinds of state,
    ``nemotron_h_prefill_chunk``'s). Logits at the chunk's last real
    token; the token-expert pairs that landed on the experts held here,
    over the chunk's layers, go to the cache's ``prefill_expert_rows``."""
    r, c = tokens.shape
    x, cache = _rows(params, tokens, lengths, cfg, cache, slots, start,
                     window)
    last = x[jnp.arange(r), jnp.clip(lengths - 1, 0, c - 1)]
    return _head(last, params, cfg), cache


def granite_hybrid_prefill(params: Params, cache: Params, tokens: jax.Array,
                           slots: jax.Array, lengths: jax.Array,
                           cfg: GraniteHybridConfig
                           ) -> tuple[jax.Array, Params]:
    """Whole padded prompts tokens [R, P] through
    ``granite_hybrid_prefill_chunk`` (``models/prefill.py``). Logits at
    each prompt's last real token."""
    return whole_prompts(granite_hybrid_prefill_chunk, params, cache, tokens,
                         slots, lengths, cfg)


def granite_hybrid_forward(params: Params, tokens: jax.Array,
                           cfg: GraniteHybridConfig) -> jax.Array:
    """Logits [R, T, V] float32 of whole rows, no cache (tests)."""
    lengths = jnp.full((tokens.shape[0],), tokens.shape[1], jnp.int32)
    x, _ = _rows(params, tokens, lengths, cfg)
    return _head(x, params, cfg)
