"""Llama-family decoder (RMSNorm / RoPE / SwiGLU / GQA) in pure JAX.

Second flagship model family next to GPT-2 (``models/gpt2.py``): the same
sharding-annotated, scan-over-layers, remat-able design — parameters are
a plain pytree with a parallel pytree of logical axis names; physical
shardings come from ``ray_tpu.parallel.sharding`` rules (Megatron TP on
head/ff/vocab dims, fsdp on embed, pp over the stacked layer dim).

Architecture differences from GPT-2, all modern-decoder standard:
* RMSNorm (no mean subtraction, no bias) instead of LayerNorm;
* rotary position embeddings applied to q/k per head (no learned wpe);
* SwiGLU MLP (gate ⊙ silu(up) with a 2/3·4d hidden, rounded to 128);
* grouped-query attention: ``n_kv_head <= n_head`` KV heads, each shared
  by ``n_head // n_kv_head`` query heads (KV cache/bandwidth saver);
* untied LM head.

Reference parity note: the reference has no model zoo of its own (torch
owns its compute path); this family exists because on TPU the framework
owns the compute path (SURVEY.md §5.7).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from ray_tpu.ops.attention import causal_attention
from ray_tpu.parallel.sharding import logical_sharding, with_logical_constraint

Params = dict[str, Any]


def _round_to(x: int, mult: int) -> int:
    return ((x + mult - 1) // mult) * mult


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    n_layer: int = 16
    n_head: int = 16
    n_kv_head: int = 4
    d_model: int = 1024
    seq_len: int = 2048
    rope_theta: float = 10000.0
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: Any = "dots"  # same semantics as GPT2Config.remat
    scan_layers: bool = True
    use_flash: bool | None = None
    attention_impl: str = "auto"  # "auto" | "ring" | "ulysses"
    # Fused Pallas RMSNorm kernels (ops/fused_norm.py — same kernel
    # family GPT2Config.fused_norm gates): forward saves only the fp32
    # rstd statistic, one backward kernel per row-block fuses
    # dx/dscale with the residual-add gradient. Odd shapes (D % 128)
    # fall back to the plain-XLA chain.
    fused_norm: bool = False
    mesh: Any = dataclasses.field(default=None, compare=False)

    def __post_init__(self):
        assert self.n_head % self.n_kv_head == 0, "GQA needs even groups"
        assert self.d_model % self.n_head == 0

    def serving_dtypes(self, params: Params) -> Params:
        """For each leaf of ``params``, the type in which ``llama_prefill``
        and ``llama_decode_step`` consume it: what an engine stores
        (``_SERVED_IN_DTYPE``; see ``GPT2Config.serving_dtypes``)."""
        dt = jnp.dtype(self.dtype)
        return jax.tree_util.tree_map_with_path(
            lambda path, x: dt if path[-1].key in _SERVED_IN_DTYPE
            else x.dtype, params)

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_head

    @property
    def d_ff(self) -> int:
        # Llama's 2/3 * 4d SwiGLU hidden, rounded up for MXU tiling.
        return _round_to(int(8 * self.d_model / 3), 128)

    @property
    def n_params(self) -> int:
        d, hd = self.d_model, self.head_dim
        attn = d * (self.n_head * hd) + 2 * d * (self.n_kv_head * hd) \
            + (self.n_head * hd) * d
        mlp = 3 * d * self.d_ff
        per_layer = attn + mlp + 2 * d  # + the two RMSNorm scales
        return (self.vocab_size * d            # embed
                + self.n_layer * per_layer
                + d                            # final norm
                + d * self.vocab_size)         # untied head

    @classmethod
    def tiny(cls) -> "LlamaConfig":
        """CPU-test sized."""
        return cls(vocab_size=256, n_layer=2, n_head=4, n_kv_head=2,
                   d_model=64, seq_len=64)

    @classmethod
    def small(cls) -> "LlamaConfig":
        """~300M for single-chip benchmarking."""
        return cls(n_layer=16, n_head=16, n_kv_head=4, d_model=1024,
                   seq_len=2048)


def llama_param_axes(cfg: LlamaConfig) -> Params:
    return {
        "embed": ("vocab", "embed"),
        "blocks": {
            "attn_norm": ("layers", None),
            "wq": ("layers", "embed", "qkv"),
            "wk": ("layers", "embed", "qkv"),
            "wv": ("layers", "embed", "qkv"),
            "wo": ("layers", "qkv", "embed"),
            "mlp_norm": ("layers", None),
            "w_gate": ("layers", "embed", "mlp"),
            "w_up": ("layers", "embed", "mlp"),
            "w_down": ("layers", "mlp", "embed"),
        },
        "final_norm": (None,),
        "lm_head": ("embed", "vocab"),
    }


def llama_shardings(cfg: LlamaConfig, mesh, rules=None) -> Params:
    return jax.tree.map(
        lambda axes: logical_sharding(mesh, axes, rules),
        llama_param_axes(cfg),
        is_leaf=lambda x: isinstance(x, tuple),
    )


def llama_init(rng: jax.Array, cfg: LlamaConfig) -> Params:
    d, l, v = cfg.d_model, cfg.n_layer, cfg.vocab_size
    hd, nh, nkv, ff = cfg.head_dim, cfg.n_head, cfg.n_kv_head, cfg.d_ff
    pd = cfg.param_dtype
    k = iter(jax.random.split(rng, 16))

    def norm(key, shape, stddev=0.02):
        return (jax.random.normal(key, shape, jnp.float32) * stddev).astype(pd)

    resid = 0.02 / (2 * l) ** 0.5
    return {
        "embed": norm(next(k), (v, d)),
        "blocks": {
            "attn_norm": jnp.ones((l, d), pd),
            "wq": norm(next(k), (l, d, nh * hd)),
            "wk": norm(next(k), (l, d, nkv * hd)),
            "wv": norm(next(k), (l, d, nkv * hd)),
            "wo": norm(next(k), (l, nh * hd, d), resid),
            "mlp_norm": jnp.ones((l, d), pd),
            "w_gate": norm(next(k), (l, d, ff)),
            "w_up": norm(next(k), (l, d, ff)),
            "w_down": norm(next(k), (l, ff, d), resid),
        },
        "final_norm": jnp.ones((d,), pd),
        "lm_head": norm(next(k), (d, v)),
    }


def _rms_norm(x: jax.Array, scale: jax.Array) -> jax.Array:
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + 1e-6)
    return (y * scale).astype(x.dtype)


def _rope(x: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding over [B, T, H, D] (rotate pairs in the head dim)."""
    b, t, h, d = x.shape
    half = d // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos = jnp.cos(angles)[None, :, None, :]  # [1, T, 1, half]
    sin = jnp.sin(angles)[None, :, None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def _norm_residual(x: jax.Array, scale: jax.Array,
                   cfg: LlamaConfig) -> tuple[jax.Array, jax.Array]:
    """(RMSNorm(x), residual-skip x); fused Pallas kernel when enabled —
    the skip's cotangent lands inside the one backward kernel."""
    if cfg.fused_norm:
        from ray_tpu.ops.fused_norm import fused_rms_norm_residual

        return fused_rms_norm_residual(x, scale)
    return _rms_norm(x, scale), x


def _block(x: jax.Array, p: Params, cfg: LlamaConfig) -> jax.Array:
    b, t, d = x.shape
    nh, nkv, hd = cfg.n_head, cfg.n_kv_head, cfg.head_dim
    dt = cfg.dtype

    y, x_skip = _norm_residual(x, p["attn_norm"], cfg)
    q = (y @ p["wq"].astype(dt)).reshape(b, t, nh, hd)
    k = (y @ p["wk"].astype(dt)).reshape(b, t, nkv, hd)
    v = (y @ p["wv"].astype(dt)).reshape(b, t, nkv, hd)
    q = _rope(q, cfg.rope_theta)
    k = _rope(k, cfg.rope_theta)
    if nkv != nh:
        # GQA: each KV head serves n_head//n_kv_head query heads.
        rep = nh // nkv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    if cfg.attention_impl == "ring" and cfg.mesh is not None:
        from ray_tpu.ops.ring_attention import ring_causal_attention

        attn = ring_causal_attention(q, k, v, cfg.mesh, axis="sp")
    elif cfg.attention_impl == "ulysses" and cfg.mesh is not None:
        from ray_tpu.ops.ulysses import ulysses_attention

        attn = ulysses_attention(q, k, v, cfg.mesh, axis="sp")
    else:
        attn = causal_attention(q, k, v, use_flash=cfg.use_flash)
    x = x_skip + attn.reshape(b, t, nh * hd) @ p["wo"].astype(dt)
    x = with_logical_constraint(x, ("batch", "seq", None))

    y, x_skip = _norm_residual(x, p["mlp_norm"], cfg)
    gate = y @ p["w_gate"].astype(dt)
    up = y @ p["w_up"].astype(dt)
    h = jax.nn.silu(gate) * up
    h = with_logical_constraint(h, ("batch", "seq", "mlp"))
    x = x_skip + h @ p["w_down"].astype(dt)
    x = with_logical_constraint(x, ("batch", "seq", None))
    return x


def llama_forward(params: Params, tokens: jax.Array,
                  cfg: LlamaConfig) -> jax.Array:
    """tokens [B, T] int32 -> logits [B, T, V] fp32."""
    dt = cfg.dtype
    x = params["embed"].astype(dt)[tokens]
    x = with_logical_constraint(x, ("batch", "seq", None))

    block_fn = lambda carry, p: (_block(carry, p, cfg), None)
    if cfg.remat == "dots":
        block_fn = jax.checkpoint(
            block_fn, prevent_cse=False,
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
        )
    elif cfg.remat:
        block_fn = jax.checkpoint(block_fn, prevent_cse=False)
    if cfg.scan_layers:
        x, _ = jax.lax.scan(block_fn, x, params["blocks"])
    else:
        for i in range(cfg.n_layer):
            x, _ = block_fn(x, jax.tree.map(lambda a: a[i], params["blocks"]))

    if cfg.fused_norm:
        from ray_tpu.ops.fused_norm import fused_rms_norm

        x = fused_rms_norm(x, params["final_norm"])
    else:
        x = _rms_norm(x, params["final_norm"])
    return jnp.einsum(
        "btd,dv->btv", x, params["lm_head"].astype(dt),
        preferred_element_type=jnp.float32,
    )


def llama_loss(params: Params, batch: dict[str, jax.Array],
               cfg: LlamaConfig) -> jax.Array:
    tokens = batch["tokens"]
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    logits = llama_forward(params, inputs, cfg)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - picked)


# -- autoregressive decoding (serving path) --------------------------------
#
# Same contract as the GPT-2 decode API (``models/gpt2.py``): one jitted
# decode step over a fixed slot batch + one jitted prefill chunk,
# with a slot-indexed ring KV-cache. The cache rides the GQA layout —
# only ``n_kv_head`` heads are cached (``[n_layer, slots, cache_len,
# n_kv_head, head_dim]`` in the activation dtype, bf16 by default), and
# query-head groups re-read the shared KV at attention time, so the GQA
# bandwidth saving carries straight into serving HBM footprint. The
# stacked cache stays in place across the layer loop exactly as there:
# decode's loop reads it and hands out the new rows, written after the
# loop; a prefill chunk's loop carries it, writes each layer's rows in
# place and reads the slot's key window back.


def llama_init_cache(cfg: LlamaConfig, slots: int, cache_len: int) -> Params:  # decode-path
    shape = (cfg.n_layer, slots, cache_len, cfg.n_kv_head, cfg.head_dim)
    return {"k": jnp.zeros(shape, cfg.dtype), "v": jnp.zeros(shape, cfg.dtype)}


def _rope_at(x: jax.Array, pos: jax.Array, theta: float) -> jax.Array:
    """Rotary embedding for ONE token per slot at an absolute position:
    x [S, H, D], pos [S] int32."""
    s, h, d = x.shape
    half = d // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = pos.astype(jnp.float32)[:, None] * freqs[None, :]  # [S, half]
    cos = jnp.cos(angles)[:, None, :]  # [S, 1, half]
    sin = jnp.sin(angles)[:, None, :]
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# What an engine stores in ``cfg.dtype`` (``LlamaConfig.serving_dtypes``): the
# leaves whose every use in ``llama_decode_step`` and ``llama_prefill`` is
# ``.astype(cfg.dtype)`` of the whole leaf (see ``gpt2._SERVED_IN_DTYPE``);
# the RMSNorm scales are multiplied in float32 and are not among them.
_SERVED_IN_DTYPE = frozenset({
    "embed", "lm_head", "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down"})


# jax-hot-path: traced into the engine's single compiled decode step
def llama_decode_step(params: Params, cache: Params, tokens: jax.Array,
                      pos: jax.Array, cfg: LlamaConfig
                      ) -> tuple[jax.Array, Params]:
    """One decode iteration for every slot: tokens [S] int32, pos [S]
    int32 -> (logits [S, V] fp32, new cache). See gpt2_decode_step for
    the ring-cursor/mask contract."""
    s = tokens.shape[0]
    nh, nkv, hd = cfg.n_head, cfg.n_kv_head, cfg.head_dim
    cache_len = cache["k"].shape[2]
    dt = cfg.dtype
    cursor = jnp.mod(pos, cache_len)
    valid = jnp.minimum(pos + 1, cache_len)
    x = params["embed"].astype(dt)[tokens]  # [S, D]
    from ray_tpu.ops.attention import (cache_write_token,
                                       cached_decode_attention)

    def block(x, layer):
        p, k_cache, v_cache = layer  # read only: the rows go out as ys
        y = _rms_norm(x, p["attn_norm"])
        q = _rope_at((y @ p["wq"].astype(dt)).reshape(s, nh, hd),
                     pos, cfg.rope_theta)
        k_new = _rope_at((y @ p["wk"].astype(dt)).reshape(s, nkv, hd),
                         pos, cfg.rope_theta).astype(k_cache.dtype)
        v_new = (y @ p["wv"].astype(dt)).reshape(
            s, nkv, hd).astype(v_cache.dtype)
        # GQA: expand the cached KV heads to the query heads at read
        # time (the cache itself stays n_kv_head wide).
        rep = nh // nkv
        attn = cached_decode_attention(
            q, jnp.repeat(k_cache, rep, axis=2),
            jnp.repeat(v_cache, rep, axis=2),
            jnp.repeat(k_new, rep, axis=1), jnp.repeat(v_new, rep, axis=1),
            cursor, valid, dt)
        x = x + attn.reshape(s, nh * hd) @ p["wo"].astype(dt)
        y = _rms_norm(x, p["mlp_norm"])
        gate = y @ p["w_gate"].astype(dt)
        up = y @ p["w_up"].astype(dt)
        x = x + (jax.nn.silu(gate) * up) @ p["w_down"].astype(dt)
        return x, (k_new, v_new)

    x, (k_rows, v_rows) = jax.lax.scan(
        block, x, (params["blocks"], cache["k"], cache["v"]))
    cache = {"k": cache_write_token(cache["k"], k_rows, cursor),
             "v": cache_write_token(cache["v"], v_rows, cursor)}
    x = _rms_norm(x, params["final_norm"])
    logits = jnp.einsum(
        "sd,dv->sv", x, params["lm_head"].astype(dt),
        preferred_element_type=jnp.float32)
    return logits, cache


# jax-hot-path: traced into the engine's single compiled prefill program
def llama_prefill_chunk(params: Params, cache: Params, tokens: jax.Array,
                        slots: jax.Array, start: jax.Array,
                        lengths: jax.Array, cfg: LlamaConfig,
                        window: int | None = None
                        ) -> tuple[jax.Array, Params]:
    """A chunk of a prompt (fixed [R, C] shape): tokens at positions
    ``start + i``, K/V rows ``[start, start + C)`` written into each row's
    slot, queries over the slot's rows ``<= start + i`` (the K/V heads read
    as they lie), logits at the chunk's last real token. Same contract as
    gpt2_prefill_chunk."""
    r, c = tokens.shape
    nh, nkv, hd = cfg.n_head, cfg.n_kv_head, cfg.head_dim
    dt = cfg.dtype
    window = window or cache["k"].shape[2]
    pos = (start[:, None] + jnp.arange(c)[None, :]).reshape(r * c)
    x = params["embed"].astype(dt)[tokens]
    from ray_tpu.ops.attention import (cache_write_prompt,
                                       cached_chunk_attention)

    def rope(x, heads):
        return _rope_at(x.reshape(r * c, heads, hd), pos,
                        cfg.rope_theta).reshape(r, c, heads, hd)

    def block(carry, layer):
        x, k_all, v_all = carry  # the stacked cache, written in place
        p, i = layer
        y = _rms_norm(x, p["attn_norm"])
        q = rope(y @ p["wq"].astype(dt), nh)
        k_ = rope(y @ p["wk"].astype(dt), nkv)
        v_ = (y @ p["wv"].astype(dt)).reshape(r, c, nkv, hd)
        k_all = cache_write_prompt(k_all, i, k_, slots, start)
        v_all = cache_write_prompt(v_all, i, v_, slots, start)
        attn = cached_chunk_attention(q, k_all, v_all, i, slots, start,
                                      window)
        x = x + attn.reshape(r, c, nh * hd) @ p["wo"].astype(dt)
        y = _rms_norm(x, p["mlp_norm"])
        gate = y @ p["w_gate"].astype(dt)
        up = y @ p["w_up"].astype(dt)
        x = x + (jax.nn.silu(gate) * up) @ p["w_down"].astype(dt)
        return (x, k_all, v_all), None

    (x, k_all, v_all), _ = jax.lax.scan(
        block, (x, cache["k"], cache["v"]),
        (params["blocks"], jnp.arange(cfg.n_layer)))
    x = _rms_norm(x, params["final_norm"])
    last = x[jnp.arange(r), jnp.clip(lengths - 1, 0, c - 1)]
    logits = jnp.einsum(
        "rd,dv->rv", last, params["lm_head"].astype(dt),
        preferred_element_type=jnp.float32)
    return logits, {"k": k_all, "v": v_all}


def llama_prefill(params: Params, cache: Params, tokens: jax.Array,
                  slots: jax.Array, lengths: jax.Array, cfg: LlamaConfig
                  ) -> tuple[jax.Array, Params]:
    """Whole padded prompts tokens [R, P] through ``llama_prefill_chunk``
    (``models/prefill.py``): logits at each prompt's last real token."""
    from ray_tpu.models.prefill import whole_prompts

    return whole_prompts(llama_prefill_chunk, params, cache, tokens, slots,
                         lengths, cfg)
