"""Qwen3-Next (``model_type: qwen3_next``): three Gated DeltaNet layers to
one gated-attention layer, sparse experts and a gated shared expert in
EVERY layer, served through ``LLMEngine``.

Layer ``i`` is full attention where ``(i + 1) % full_attention_interval ==
0``, else linear attention (``layer_types``); ``N(x; w) = x * rsqrt(mean(x^2)
+ eps) * (1 + w)`` is the model's zero-centred RMSNorm:

  ``x0 = E[token]`` (no multiplier; the head is a table of its own)
  ``y  = N(x; norm)``
  linear:  ``M = GatedDeltaNet(y)``, ``ops/gated_delta.py``: a float32
           matrix a value head as state, the delta rule;
  full:    ``[q | gate] = y Wq`` per head, ``k = y Wk``, ``v = y Wv``;
           ``q = N(q; q_norm)``, ``k = N(k; k_norm)`` over each head's
           lanes; the FIRST ``rotary_dim`` lanes of a head rotated
           (``ops/rotary.rotate`` on that slice: its halves against each
           other), the others pass; causal ``softmax(q k^T / sqrt(hd)) v``;
           ``M = Wo(A * sigmoid(gate))``;
  ``x  = x + M``; ``y2 = N(x; norm2)``
  ``x  = x + Routed(y2) + sigmoid(y2 w_sg) * Shared(y2)``: the router a
           softmax over ALL experts, the ``top_k`` largest, renormalised
           over those (``ops/moe.route_topk_softmax``: a softmax over the
           chosen logits is that); an expert ``W2 (silu(a) * b)``, ``[a, b]
           = W1 h``; ONE shared expert behind a scalar sigmoid gate a token;
  ``logits = N(x; norm_f) W_head``.

``benchmark/reference/qwen3_next.py`` writes the equations out plainly; the
tests hold this file to it.

The cache holds three kinds of slot state: for every linear layer the
convolution's tail and a float32 delta state [slot, Hv, dk, dv]
(``ops/gated_delta.init_state``), for every full layer a K/V ring of MERGED
rows [layer, slot, row, W] (``ops/attention.py``'s rank 4: two K/V heads of
256 lanes are four whole lane tiles, no pad; rotated keys at their true
positions, so a wrapped ring is a window), and ``counted``, what the
programs count in place (``models/granite_hybrid.py``). Both programs read
the rings as they were and write their new rows once a stack after the
layer loop. The expert share (``experts_held``), the stored types and the
step's counters are as ``models/granite_hybrid.py`` has them.

Seeded weights (``qwen3_next_init``) are drawn by ``cfg.gains``: see there.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from ray_tpu.models.common import (_normal,
                                   gate as _gate,
                                   merged_row as _merged_row)
from ray_tpu.models.prefill import whole_prompts
from ray_tpu.ops import gated_delta
from ray_tpu.ops.attention import (cache_write_chunk, cache_write_token,
                                   cached_decode_attention, causal_attention,
                                   chunk_attention_arm, merged_chunk_attention,
                                   merged_row_width, ring_rows_counted)
from ray_tpu.ops.moe import (dropless_experts, held_counters,
                             route_topk_softmax)
from ray_tpu.ops.rotary import rotate

Params = dict[str, Any]

LINEAR, FULL = "linear_attention", "full_attention"

# How ``qwen3_next_init`` draws a matrix: normal at ``gain / sqrt(fan_in)``,
# so that ``gain`` is the rms of its output for an input of rms one,
# whatever the width (the embedding: ``gain`` itself, the stream's rms). At
# 0.02 throughout the stream would be the token's row at 0.02 beside
# branches of order one; these put every branch at some tenths of the
# stream (``benchmark/families/qwen3_next.branch_readings``). ``o`` is large
# because attention's output is a mean over hundreds of keys times a gate
# of about a half; ``expert_down`` because a token's ten weights sum to one
# and a quarter of its experts are held. The norms normalise what reaches
# q, k and the delta rule's output, so those gains move nothing but the
# gates. Nothing a released checkpoint would need.
GAINS = (("embed", 1.0), ("gdn_in", 1.0), ("gdn_ba", 0.5), ("gdn_out", 1.0),
         ("q", 1.0), ("k", 1.0), ("v", 1.0), ("o", 16.0), ("router", 4.0),
         ("expert_in", 1.0), ("expert_down", 1.0), ("shared_in", 1.0),
         ("shared_down", 1.5), ("shared_gate", 1.0), ("head", 1.0))


@dataclasses.dataclass(frozen=True)
class Qwen3NextConfig:
    vocab_size: int = 151936
    d_model: int = 2048
    n_layer: int = 48
    full_attention_interval: int = 4
    eps: float = 1e-6
    # gated attention: grouped queries, a query/key norm, a partial rotary
    n_head: int = 16
    n_kv_head: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 1e7
    # Gated DeltaNet
    linear_key_heads: int = 16
    linear_value_heads: int = 32
    linear_key_dim: int = 128
    linear_value_dim: int = 128
    conv_kernel: int = 4
    scan_block: int = 64  # how a prefill blocks the scan; no result moves
    # experts, in every layer
    n_experts: int = 512       # the router's width: every expert of the model
    experts_held: tuple = (0, 512)  # (first, count) of the experts held here
    top_k: int = 10
    expert_ff: int = 512
    shared_ff: int = 512
    dtype: Any = jnp.bfloat16        # activations and matmuls
    param_dtype: Any = jnp.bfloat16  # as the checkpoint stores them
    delta_state_dtype: Any = jnp.float32
    gains: tuple = GAINS

    def __post_init__(self):
        object.__setattr__(self, "experts_held", tuple(self.experts_held))
        object.__setattr__(self, "gains", tuple(
            (str(k), float(v)) for k, v in dict(self.gains).items()))
        if dict(self.gains).keys() != dict(GAINS).keys():
            raise ValueError(f"gains {self.gains}: want the keys "
                             f"{sorted(dict(GAINS))}")
        first, count = self.experts_held
        if not (0 <= first and count >= 1
                and first + count <= self.n_experts):
            raise ValueError(f"experts_held {self.experts_held} outside "
                             f"the router's {self.n_experts}")
        if self.n_head % self.n_kv_head or self.rotary_dim % 2 \
                or not 0 < self.rotary_dim <= self.head_dim:
            raise ValueError("query heads must divide into K/V heads, and "
                             "the rotated lanes of a head into pairs")
        if self.n_layer < 1 or self.full_attention_interval < 1:
            raise ValueError("n_layer and full_attention_interval must be "
                             "at least 1")
        self.delta  # its sizes are checked there

    @property
    def layer_types(self) -> tuple:
        return tuple(FULL if (i + 1) % self.full_attention_interval == 0
                     else LINEAR for i in range(self.n_layer))

    @property
    def rotary_dim(self) -> int:
        """The first lanes of a head that are rotated."""
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def delta(self) -> gated_delta.GatedDeltaDims:
        """The linear layers' sizes, as ``ops/gated_delta.py`` takes them."""
        return gated_delta.GatedDeltaDims(
            key_heads=self.linear_key_heads,
            value_heads=self.linear_value_heads,
            key_dim=self.linear_key_dim, value_dim=self.linear_value_dim,
            kernel=self.conv_kernel, block=self.scan_block, eps=self.eps,
            dtype=self.dtype, state_dtype=self.delta_state_dtype)

    def count(self, kind: str) -> int:
        return self.layer_types.count(kind)

    def serving_dtypes(self, params: Params) -> Params:
        """How an engine stores ``params``: as ``qwen3_next_init`` made them
        (see ``NemotronHConfig.serving_dtypes``)."""
        return jax.tree.map(lambda x: x.dtype, params)

    def serving_stats(self, chunk: int = 0, window: int = 0) -> dict:
        """What ``llm_stats()`` says of the model beside its counters, so
        that a reader holds no shape of its own: a slot's delta state and
        convolution tails over the linear layers, the ring bytes a token
        takes over the full ones, and which implementation a chunk program
        of ``chunk`` tokens over a key window of ``window`` rows (the
        engine's) attends through in the full layers
        (``ops/attention.chunk_attention_arm``: static, by shapes alone)."""
        d = self.delta
        state = jnp.dtype(self.delta_state_dtype).itemsize
        act = jnp.dtype(self.dtype).itemsize
        width = merged_row_width(self.n_kv_head, self.head_dim)
        return {
            "expert_layers": self.n_layer,
            "experts_held": self.experts_held[1],
            "linear_layers": self.count(LINEAR),
            "delta_state_bytes_per_slot": self.count(LINEAR) * (
                d.value_heads * d.key_dim * d.value_dim * state
                + (d.kernel - 1) * d.conv_dim * act),
            "kv_bytes_per_token": 2 * self.count(FULL) * width * act,
            "chunk_attention_arm": chunk_attention_arm(
                chunk, self.head_dim, width, window),
        }

    @classmethod
    def tiny(cls, **kw) -> "Qwen3NextConfig":
        """Two periods at a size a CPU test runs: value heads twice the key
        heads, a key head that is not a value head's size, fewer K/V heads
        than query heads, a rotary share that is neither 0 nor 1, and a
        strict part of the router's experts held."""
        base = dict(
            vocab_size=256, d_model=48, n_layer=8, full_attention_interval=4,
            n_head=4, n_kv_head=2, head_dim=16, partial_rotary_factor=0.5,
            rope_theta=1e4, linear_key_heads=2, linear_value_heads=4,
            linear_key_dim=8, linear_value_dim=12, scan_block=8,
            n_experts=16, experts_held=(4, 8), top_k=3, expert_ff=24,
            shared_ff=40)
        base.update(kw)
        return cls(**base)


# -- parameters ---------------------------------------------------------------


def init_stds(cfg: Qwen3NextConfig) -> dict:
    """The standard deviation each matrix is drawn at (``GAINS`` says
    why): ``gain / sqrt(fan_in)``."""
    g = dict(cfg.gains)
    d = cfg.d_model ** 0.5
    return {
        "embed": g["embed"], "in_qkvz": g["gdn_in"] / d,
        "in_ba": g["gdn_ba"] / d,
        "out_proj": g["gdn_out"] / cfg.delta.value_width ** 0.5,
        "wq": g["q"] / d, "wk": g["k"] / d, "wv": g["v"] / d,
        "wo": g["o"] / (cfg.n_head * cfg.head_dim) ** 0.5,
        "router": g["router"] / d, "w1": g["expert_in"] / d,
        "w2": g["expert_down"] / cfg.expert_ff ** 0.5,
        "shared_w1": g["shared_in"] / d,
        "shared_w2": g["shared_down"] / cfg.shared_ff ** 0.5,
        "shared_gate": g["shared_gate"] / d, "lm_head": g["head"] / d,
    }


def _layer_init(key, kind: str, cfg: Qwen3NextConfig, std: dict) -> Params:
    d, pd = cfg.d_model, cfg.param_dtype
    keys = iter(jax.random.split(key, 12))
    # the zero-centred norms' weights start at 0: N multiplies by 1 + w
    p = {"norm": jnp.zeros((d,), pd), "norm2": jnp.zeros((d,), pd)}
    if kind == LINEAR:
        p.update(gated_delta.mixer_init(
            keys, d, cfg.delta, pd, _normal, std["out_proj"],
            in_std=std["in_qkvz"], ba_std=std["in_ba"]))
    else:
        q, kv = cfg.n_head * cfg.head_dim, cfg.n_kv_head * cfg.head_dim
        p.update(
            # per head: q head_dim | gate head_dim, as released
            wq=_normal(next(keys), (d, 2 * q), std["wq"], pd),
            wk=_normal(next(keys), (d, kv), std["wk"], pd),
            wv=_normal(next(keys), (d, kv), std["wv"], pd),
            wo=_normal(next(keys), (q, d), std["wo"], pd),
            q_norm=jnp.zeros((cfg.head_dim,), pd),
            k_norm=jnp.zeros((cfg.head_dim,), pd))
    held, ff = cfg.experts_held[1], cfg.expert_ff
    p.update(
        router=_normal(next(keys), (d, cfg.n_experts), std["router"], pd),
        # [a, b] = W1 h side by side: the gate's halves of one product
        w1=_normal(next(keys), (held, d, 2 * ff), std["w1"], pd),
        w2=_normal(next(keys), (held, ff, d), std["w2"], pd),
        shared_w1=_normal(next(keys), (d, 2 * cfg.shared_ff),
                          std["shared_w1"], pd),
        shared_w2=_normal(next(keys), (cfg.shared_ff, d), std["shared_w2"],
                          pd),
        shared_gate=_normal(next(keys), (d, 1), std["shared_gate"], pd))
    return p


def qwen3_next_init(rng: jax.Array, cfg: Qwen3NextConfig) -> Params:
    """Seeded weights in ``cfg.param_dtype`` (bfloat16 as published), one
    dict a layer, every matrix normal at ``init_stds``'s value, the
    zero-centred norms at 0 and the delta rule's own norm at 1, its decay
    and convolution as ``ops/gated_delta.mixer_init`` draws them. The head
    is a table of its own (``tie_word_embeddings`` false), stored [V, D] as
    the embedding."""
    keys = jax.random.split(rng, cfg.n_layer + 2)
    pd, std = cfg.param_dtype, init_stds(cfg)
    return {
        "embed": _normal(keys[0], (cfg.vocab_size, cfg.d_model),
                         std["embed"], pd),
        "layers": [_layer_init(keys[2 + i], kind, cfg, std)
                   for i, kind in enumerate(cfg.layer_types)],
        "norm_f": jnp.zeros((cfg.d_model,), pd),
        "lm_head": _normal(keys[1], (cfg.vocab_size, cfg.d_model),
                           std["lm_head"], pd),
    }


# -- the parts ----------------------------------------------------------------


def _norm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    """The zero-centred RMSNorm ``N(x; w)`` over the last axis."""
    xf = x.astype(jnp.float32)
    xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * (1.0 + w.astype(jnp.float32))).astype(x.dtype)


def _qkv(p: Params, y: jax.Array, pos: jax.Array, cfg: Qwen3NextConfig):
    """The attention layer's inputs of normed rows y [..., D] at positions
    pos [...]: q [..., H, hd] and k [..., G, hd] normed and rotated over
    their first ``rotary_dim`` lanes, v [..., G, hd], and the output gate
    [..., H, hd] before its sigmoid."""
    dt_ = cfg.dtype
    lead = y.shape[:-1]
    hd, rot = cfg.head_dim, cfg.rotary_dim
    with jax.named_scope("attn_proj"):
        qg = (y @ p["wq"].astype(dt_)).reshape(*lead, cfg.n_head, 2 * hd)
        q, gate = qg[..., :hd], qg[..., hd:]
        k = (y @ p["wk"].astype(dt_)).reshape(*lead, cfg.n_kv_head, hd)
        v = (y @ p["wv"].astype(dt_)).reshape(*lead, cfg.n_kv_head, hd)
    with jax.named_scope("qk_norm"):
        q = _norm(q, p["q_norm"], cfg.eps)
        k = _norm(k, p["k_norm"], cfg.eps)
    with jax.named_scope("rope"):
        q, k = (jnp.concatenate(
            [rotate(x[..., :rot], pos, cfg.rope_theta), x[..., rot:]], -1)
            for x in (q, k))
    return q, k, v, gate


def _attn_out(p: Params, attn: jax.Array, gate: jax.Array,
              cfg: Qwen3NextConfig) -> jax.Array:
    """``Wo(A * sigmoid(gate))``: attn, gate [..., H, hd] -> [..., D]."""
    with jax.named_scope("attn_gate"):
        gated = (attn.astype(jnp.float32)
                 * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(cfg.dtype)
    with jax.named_scope("attn_proj"):
        return gated.reshape(*gated.shape[:-2], -1) @ p["wo"].astype(cfg.dtype)


def _moe(p: Params, x: jax.Array, cfg: Qwen3NextConfig,
         live: jax.Array | None = None):
    """``x + Routed(y) + sigmoid(y w_sg) * Shared(y)``, y = N(x; norm2),
    over rows x [T, D]: the held experts' part of the routed output; rows
    that ``live`` [T] says are padding are routed nowhere.
    -> (the stream [T, D], the pairs each held expert took [count])."""
    dt_ = cfg.dtype
    with jax.named_scope("ln"):
        y = _norm(x, p["norm2"], cfg.eps)
    with jax.named_scope("router"):
        ids, weights = route_topk_softmax(y, p["router"], cfg.top_k)
    routed, counts = dropless_experts(
        y, ids, weights, p["w1"], p["w2"], first=cfg.experts_held[0],
        activation=_gate, live=live)
    with jax.named_scope("shared_expert"):
        shared = _gate(y @ p["shared_w1"].astype(dt_)) \
            @ p["shared_w2"].astype(dt_)
        share = jax.nn.sigmoid(jnp.einsum(
            "td,do->to", y, p["shared_gate"].astype(dt_),
            preferred_element_type=jnp.float32))  # [T, 1]
        out = (x.astype(jnp.float32) + routed
               + share * shared.astype(jnp.float32)).astype(dt_)
    return out, counts


def _head(x: jax.Array, params: Params, cfg: Qwen3NextConfig):
    """``N(x; norm_f) W_head``, float32. x [..., D]."""
    with jax.named_scope("ln"):
        x = _norm(x, params["norm_f"], cfg.eps)
    with jax.named_scope("head"):
        return jnp.einsum(
            "...d,vd->...v", x, params["lm_head"].astype(cfg.dtype),
            preferred_element_type=jnp.float32)


def _embed(params: Params, tokens: jax.Array, cfg: Qwen3NextConfig):
    with jax.named_scope("embed"):
        return params["embed"].astype(cfg.dtype)[tokens]


# -- the cache and the serving functions --------------------------------------


def qwen3_next_init_cache(cfg: Qwen3NextConfig, slots: int,
                          cache_len: int) -> Params:  # decode-path
    """K/V rings for the full-attention layers only (one stacked array each
    for K and V, [layer, slot, row, W]: a token's K/V heads merged in one
    row, ``merged_row_width``, which both programs read as it lies), for
    every linear layer the convolution's tail and the float32 delta state
    (``ops/gated_delta.init_state``), and what the programs count
    (``counted``: int32 scalars, which wrap): one pytree, which the engine
    donates."""
    kv = (cfg.count(FULL), slots, cache_len,
          merged_row_width(cfg.n_kv_head, cfg.head_dim))
    return {"k": jnp.zeros(kv, cfg.dtype), "v": jnp.zeros(kv, cfg.dtype),
            **gated_delta.init_state(cfg.delta, cfg.count(LINEAR), slots),
            "counted": {"prefill_expert_rows": jnp.zeros((), jnp.int32)}}


# jax-hot-path: traced into the engine's single compiled decode step
def qwen3_next_decode_step(params: Params, cache: Params, tokens: jax.Array,
                           pos: jax.Array, cfg: Qwen3NextConfig
                           ) -> tuple[jax.Array, Params, dict]:
    """One decode iteration for every slot: tokens [S] int32, pos [S]
    int32 -> (logits [S, V] fp32, new cache, counters ``experts_hit`` and
    ``expert_rows`` over the step's layers, and ``ring_rows_read`` and
    ``ring_rows_held``: what the full layers' attention read of the rings,
    ``ops/attention.ring_rows_counted``). Every row is computed, free
    slots and the scratch one too, so the counters count what the step
    really routed. The K/V part keeps ``gpt2_decode_step``'s ring contract
    and its layout (rings of merged rows read as they were, every full
    layer's new rows written after the loop)."""
    dt_ = cfg.dtype
    cache_len = cache["k"].shape[2]
    cursor = jnp.mod(pos, cache_len)
    valid = jnp.minimum(pos + 1, cache_len)
    x = _embed(params, tokens, cfg)
    conv_all, delta_all = cache["conv"], list(cache["delta"])
    k_rows, v_rows, counts = [], [], []
    i_l = i_f = 0
    for kind, p in zip(cfg.layer_types, params["layers"]):
        with jax.named_scope("ln"):
            y = _norm(x, p["norm"], cfg.eps)
        if kind == LINEAR:
            out, conv_all, delta_all[i_l] = gated_delta.step_through_cache(
                p, y, conv_all, delta_all[i_l], i_l, cfg.delta)
            i_l += 1
        else:
            q, k_new, v_new, gate = _qkv(p, y, pos, cfg)
            k_new = _merged_row(k_new, cache["k"])
            v_new = _merged_row(v_new, cache["v"])
            with jax.named_scope("attn"):
                attn = cached_decode_attention(
                    q, cache["k"], cache["v"], k_new, v_new, cursor, valid,
                    dt_, layer=i_f)
            out = _attn_out(p, attn, gate, cfg)
            k_rows.append(k_new)
            v_rows.append(v_new)
            i_f += 1
        x, c = _moe(p, x + out, cfg)
        counts.append(c)
    k_all, v_all = cache["k"], cache["v"]
    if k_rows:
        with jax.named_scope("cache_write"):
            k_all = cache_write_token(k_all, jnp.stack(k_rows), cursor)
            v_all = cache_write_token(v_all, jnp.stack(v_rows), cursor)
    return _head(x, params, cfg), {
        "k": k_all, "v": v_all, "conv": conv_all,
        "delta": tuple(delta_all),
        "counted": cache["counted"]}, {
            **held_counters(counts), **ring_rows_counted(cache["k"], valid)}


def _rows(params: Params, tokens: jax.Array, lengths: jax.Array,
          cfg: Qwen3NextConfig, cache: Params | None = None,
          slots: jax.Array | None = None, start: jax.Array | None = None,
          window: int | None = None):
    """Rows of T tokens through every layer: tokens [R, T], lengths [R].
    Without a cache, whole rows from nothing. With one, row r is a chunk
    of a prompt at positions ``start[r] + i``: a full layer reads
    ``slots[r]``'s rows ``< start`` as earlier chunks left them and takes
    the chunk's own beside them (``merged_chunk_attention``), its rows
    written once a stack after the loop (``falcon_h1._rows``'s order); a
    linear layer continues the slot's delta state and leaves there, in
    place, its state after the row's real tokens (``nemotron_h._rows``'s
    contract); the token-expert pairs the held experts took are added to
    the cache's ``prefill_expert_rows``.
    -> (hidden [R, T, D] before ``norm_f``, the cache)."""
    r, t = tokens.shape
    x = _embed(params, tokens, cfg)
    pos = jnp.arange(t)[None, :] + (0 if start is None else start[:, None])
    # a padded chunk's other positions are not routed: no expert computes them
    real = (jnp.arange(t)[None, :] < lengths[:, None]).reshape(-1)
    if cache is not None:
        conv_all, delta_all = cache["conv"], list(cache["delta"])
        k_rows, v_rows = [], []
        window = window or cache["k"].shape[2]
        goes_on = start > 0  # [R]: the slot holds this prompt's state
    pairs = jnp.int32(0)
    i_l = i_f = 0
    for kind, p in zip(cfg.layer_types, params["layers"]):
        with jax.named_scope("ln"):
            y = _norm(x, p["norm"], cfg.eps)
        if kind == LINEAR and cache is None:
            out, _, _ = gated_delta.delta_rows(p, y, lengths, cfg.delta)
        elif kind == LINEAR:
            out, conv_all, delta_all[i_l] = gated_delta.rows_through_cache(
                p, y, lengths, conv_all, delta_all[i_l], i_l, slots,
                goes_on, cfg.delta)
            i_l += 1
        else:
            q, k_, v_, gate = _qkv(p, y, pos, cfg)
            if cache is None:
                with jax.named_scope("attn"):
                    rep = cfg.n_head // cfg.n_kv_head
                    attn = causal_attention(
                        q, jnp.repeat(k_, rep, axis=2),
                        jnp.repeat(v_, rep, axis=2), use_flash=False)
            else:
                k_ = _merged_row(k_, cache["k"])
                v_ = _merged_row(v_, cache["v"])
                with jax.named_scope("attn"):
                    attn = merged_chunk_attention(
                        q, cache["k"], cache["v"], k_, v_, i_f, slots,
                        start, window)
                k_rows.append(k_)
                v_rows.append(v_)
                i_f += 1
            out = _attn_out(p, attn, gate, cfg)
        x, c = _moe(p, (x + out).reshape(r * t, -1), cfg, real)
        x = x.reshape(r, t, -1)
        pairs = pairs + jnp.sum(c, dtype=jnp.int32)
    if cache is not None:
        k_all, v_all = cache["k"], cache["v"]
        if k_rows:
            with jax.named_scope("cache_write"):
                k_all = cache_write_chunk(k_all, jnp.stack(k_rows), slots,
                                          start)
                v_all = cache_write_chunk(v_all, jnp.stack(v_rows), slots,
                                          start)
        cache = {"k": k_all, "v": v_all, "conv": conv_all,
                 "delta": tuple(delta_all), "counted": {
                     "prefill_expert_rows":
                     cache["counted"]["prefill_expert_rows"] + pairs}}
    return x, cache


# jax-hot-path: traced into the engine's single compiled prefill program
def qwen3_next_prefill_chunk(params: Params, cache: Params,
                             tokens: jax.Array, slots: jax.Array,
                             start: jax.Array, lengths: jax.Array,
                             cfg: Qwen3NextConfig, window: int | None = None
                             ) -> tuple[jax.Array, Params]:
    """A chunk of a prompt (fixed [R, C] shape; the contract of
    ``models/prefill.py`` and, for the three kinds of state,
    ``granite_hybrid_prefill_chunk``'s). Logits at the chunk's last real
    token."""
    r, c = tokens.shape
    x, cache = _rows(params, tokens, lengths, cfg, cache, slots, start,
                     window)
    last = x[jnp.arange(r), jnp.clip(lengths - 1, 0, c - 1)]
    return _head(last, params, cfg), cache


def qwen3_next_prefill(params: Params, cache: Params, tokens: jax.Array,
                       slots: jax.Array, lengths: jax.Array,
                       cfg: Qwen3NextConfig) -> tuple[jax.Array, Params]:
    """Whole padded prompts tokens [R, P] through
    ``qwen3_next_prefill_chunk`` (``models/prefill.py``). Logits at each
    prompt's last real token."""
    return whole_prompts(qwen3_next_prefill_chunk, params, cache, tokens,
                         slots, lengths, cfg)


def qwen3_next_forward(params: Params, tokens: jax.Array,
                       cfg: Qwen3NextConfig) -> jax.Array:
    """Logits [R, T, V] float32 of whole rows, no cache (tests)."""
    lengths = jnp.full((tokens.shape[0],), tokens.shape[1], jnp.int32)
    x, _ = _rows(params, tokens, lengths, cfg)
    return _head(x, params, cfg)
