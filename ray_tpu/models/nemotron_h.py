"""Nemotron-H: a hybrid of Mamba-2 mixers, attention and latent
mixture-of-experts layers, served through ``LLMEngine``.

The layer list is data: ``pattern`` holds one character a layer, ``M`` a
Mamba-2 mixer (``ops/mamba2.py``, the one ``models/granite_hybrid.py`` calls
too), ``*`` grouped-query attention (no position embedding: the
Mamba layers carry order), ``E`` a latent MoE (sigmoid router over every
expert of the model, top-k, experts in a ``latent``-wide space between one
down- and one up-projection, a shared expert beside them at full width).
Every layer is ``x + mixer(RMSNorm(x))``; after the last, ``norm_f`` and an
untied head. ``benchmark/reference/nemotron_h.py`` writes the equations out
plainly; the tests hold this file to it.

What differs from the dense families here:

* **Two kinds of state in one cache.** ``nemotron_h_init_cache`` gives a
  pytree: ``k`` / ``v`` rows for the ``*`` layers (the ring contract of
  ``ops/attention.py``), and for every ``M`` layer a convolution tail
  ``conv`` and a float32 SSM state (``ssm``, one array a layer), which
  have no ring: a prompt's first chunk begins them anew, every chunk
  continues from them and leaves, by slot, exactly the state after its
  row's ``length`` real tokens (padded positions take ``dt = 0``). The
  engine donates the pytree; every layer writes its part in place, and no
  layer-sized block is copied in or out of the layer loop (PR 25's rule).
* **An expert share.** ``experts_held = (first, count)``: the chip holds
  ``count`` of the ``n_experts`` the router scores, and computes its own
  experts' part of each ``E`` layer (``ops/moe.dropless_experts``). What
  the absent experts would add is left out, here and in the reference.
* **Weights stored in bfloat16**, as the checkpoint publishes them; the
  router, softmax statistics, ``dt`` / ``A`` / decays and the SSM state
  are float32.
* **Counters.** ``nemotron_h_decode_step`` returns a third value, a dict
  of int32 scalars (``experts_hit``, ``expert_rows``) over the step's
  ``E`` layers, which the engine fetches with the step's tokens.

Multi-token prediction (the checkpoint's extra ``*E`` head) is not loaded:
a deployment without speculative decoding does not serve it.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp

from ray_tpu.models.common import _normal, rms_norm as _rms_norm
from ray_tpu.models.prefill import whole_prompts
from ray_tpu.ops import mamba2
from ray_tpu.ops.attention import (cache_write_prompt, cache_write_token,
                                   cached_chunk_attention,
                                   cached_decode_attention, causal_attention)
from ray_tpu.ops.moe import dropless_experts, held_counters, route

Params = dict[str, Any]

PUBLISHED_PATTERN = (
    "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
    "EMEMEMEMEM*EMEMEMEMEM*EMEMEMEM*EMEMEMEME")


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    vocab_size: int = 131072
    d_model: int = 4096
    pattern: str = PUBLISHED_PATTERN
    eps: float = 1e-5
    # `*`: grouped-query attention, no position embedding
    n_head: int = 32
    n_kv_head: int = 2
    head_dim: int = 128
    # `M`: Mamba-2 mixer
    mamba_heads: int = 128
    mamba_head_dim: int = 64
    ssm_groups: int = 8
    ssm_state: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128  # how a prefill blocks the scan; no result moves
    # `E`: latent MoE
    n_experts: int = 512       # the router's width: every expert of the model
    experts_held: tuple = (0, 512)  # (first, count) of the experts held here
    top_k: int = 22
    latent: int = 1024
    expert_ff: int = 2688
    shared_ff: int = 5376
    routed_scale: float = 5.0
    dtype: Any = jnp.bfloat16        # activations and matmuls
    param_dtype: Any = jnp.bfloat16  # as the checkpoint stores them
    ssm_state_dtype: Any = jnp.float32

    def __post_init__(self):
        bad = set(self.pattern) - set("M*E")
        if bad or not self.pattern:
            raise ValueError(f"pattern {self.pattern!r}: want M, * and E")
        first, count = self.experts_held
        if not (0 <= first and count >= 1
                and first + count <= self.n_experts):
            raise ValueError(f"experts_held {self.experts_held} outside "
                             f"the router's {self.n_experts}")
        if self.n_head % self.n_kv_head or \
                self.mamba_heads % self.ssm_groups:
            raise ValueError("heads must divide into their groups")

    @property
    def d_inner(self) -> int:
        return self.mamba.d_inner

    @property
    def conv_dim(self) -> int:
        return self.mamba.conv_dim

    @property
    def mamba(self) -> mamba2.Mamba2Dims:
        """The ``M`` layers' sizes, as ``ops/mamba2.py`` takes them."""
        return mamba2.Mamba2Dims(
            heads=self.mamba_heads, head_dim=self.mamba_head_dim,
            groups=self.ssm_groups, state=self.ssm_state,
            kernel=self.conv_kernel, block=self.chunk_size, eps=self.eps,
            dtype=self.dtype, state_dtype=self.ssm_state_dtype)

    def count(self, kind: str) -> int:
        return self.pattern.count(kind)

    def serving_dtypes(self, params: Params) -> Params:
        """How an engine stores ``params``: as ``nemotron_h_init`` made
        them (see ``GPT2Config.serving_dtypes``). The matrices are held in the
        type the two programs multiply with, and the small leaves the
        programs widen (router, ``a_log``, ``dt_bias``, ``d_skip``, norms)
        stay in the type the configuration's file states for them."""
        return jax.tree.map(lambda x: x.dtype, params)

    def serving_stats(self, chunk: int = 0, window: int = 0) -> dict:
        """What ``llm_stats()`` says of the model beside its counters."""
        return {"expert_layers": self.count("E"),
                "experts_held": self.experts_held[1]}

    @classmethod
    def tiny(cls, **kw) -> "NemotronHConfig":
        """All three kinds of layer at a size a CPU test runs."""
        base = dict(vocab_size=256, d_model=64, pattern="ME*EM", n_head=4,
                    n_kv_head=2, head_dim=16, mamba_heads=8,
                    mamba_head_dim=16, ssm_groups=2, ssm_state=16,
                    chunk_size=8, n_experts=8, experts_held=(0, 4), top_k=3,
                    latent=32, expert_ff=48, shared_ff=96)
        base.update(kw)
        return cls(**base)


# -- parameters ---------------------------------------------------------------


def _layer_init(key, kind: str, cfg: NemotronHConfig) -> Params:
    d, pd = cfg.d_model, cfg.param_dtype
    out_std = 0.02 / math.sqrt(len(cfg.pattern))  # rescale_prenorm_residual
    keys = iter(jax.random.split(key, 12))
    p = {"norm": jnp.ones((d,), pd)}
    if kind == "M":
        p.update(mamba2.mixer_init(keys, d, cfg.mamba, pd, _normal, out_std))
    elif kind == "*":
        q, kv = cfg.n_head * cfg.head_dim, cfg.n_kv_head * cfg.head_dim
        p.update(wq=_normal(next(keys), (d, q), 0.02, pd),
                 wk=_normal(next(keys), (d, kv), 0.02, pd),
                 wv=_normal(next(keys), (d, kv), 0.02, pd),
                 wo=_normal(next(keys), (q, d), out_std, pd))
    else:
        held, lat, ff = cfg.experts_held[1], cfg.latent, cfg.expert_ff
        p.update(
            router=_normal(next(keys), (d, cfg.n_experts), 0.02, pd),
            router_bias=_normal(next(keys), (cfg.n_experts,), 0.05, pd),
            w_down=_normal(next(keys), (d, lat), 0.02, pd),
            w_up=_normal(next(keys), (lat, d), out_std, pd),
            w1=_normal(next(keys), (held, lat, ff), 0.02, pd),
            w2=_normal(next(keys), (held, ff, lat), 0.02, pd),
            shared_w1=_normal(next(keys), (d, cfg.shared_ff), 0.02, pd),
            shared_w2=_normal(next(keys), (cfg.shared_ff, d), out_std, pd))
    return p


def nemotron_h_init(rng: jax.Array, cfg: NemotronHConfig) -> Params:
    """Seeded weights in ``cfg.param_dtype`` (bfloat16 as published), one
    dict a layer, built layer by layer: no float32 copy of anything larger
    than the matrix being drawn ever exists."""
    keys = jax.random.split(rng, len(cfg.pattern) + 2)
    pd = cfg.param_dtype
    return {
        "embed": _normal(keys[0], (cfg.vocab_size, cfg.d_model), 0.02, pd),
        "layers": [_layer_init(keys[2 + i], kind, cfg)
                   for i, kind in enumerate(cfg.pattern)],
        "norm_f": jnp.ones((cfg.d_model,), pd),
        "lm_head": _normal(keys[1], (cfg.d_model, cfg.vocab_size), 0.02, pd),
    }


# -- the parts ----------------------------------------------------------------


def _relu2(x: jax.Array) -> jax.Array:
    return jnp.square(jax.nn.relu(x))


def _moe(p: Params, y: jax.Array, cfg: NemotronHConfig,
         live: jax.Array | None = None):
    """The latent MoE over rows y [T, D] (normed): the held experts' part
    of the routed output plus the shared expert; rows that ``live`` [T]
    says are padding are routed nowhere. -> (out [T, D], the pairs each
    held expert took [count])."""
    dt_ = cfg.dtype
    with jax.named_scope("router"):
        ids, weights = route(y, p["router"], p["router_bias"], cfg.top_k,
                             cfg.routed_scale)
    with jax.named_scope("latent_proj"):
        h = y @ p["w_down"].astype(dt_)
    routed, counts = dropless_experts(
        h, ids, weights, p["w1"], p["w2"], first=cfg.experts_held[0],
        activation=_relu2, live=live)
    with jax.named_scope("latent_proj"):
        out = routed.astype(dt_) @ p["w_up"].astype(dt_)
    with jax.named_scope("shared_expert"):
        out = out + _relu2(y @ p["shared_w1"].astype(dt_)) \
            @ p["shared_w2"].astype(dt_)
    return out, counts


# -- the cache and the serving functions --------------------------------------


def nemotron_h_init_cache(cfg: NemotronHConfig, slots: int,
                          cache_len: int) -> Params:  # decode-path
    """K/V rows for the ``*`` layers (a ring, as the other families'),
    stacked over their layers, and for the ``M`` layers the convolution's
    tail and the SSM state, which have no ring
    (``ops/mamba2.init_state`` says how they lie and why)."""
    kv = (cfg.count("*"), slots, cache_len, cfg.n_kv_head, cfg.head_dim)
    return {"k": jnp.zeros(kv, cfg.dtype), "v": jnp.zeros(kv, cfg.dtype),
            **mamba2.init_state(cfg.mamba, cfg.count("M"), slots)}


# jax-hot-path: traced into the engine's single compiled decode step
def nemotron_h_decode_step(params: Params, cache: Params, tokens: jax.Array,
                           pos: jax.Array, cfg: NemotronHConfig
                           ) -> tuple[jax.Array, Params, dict]:
    """One decode iteration for every slot: tokens [S] int32, pos [S]
    int32 -> (logits [S, V] fp32, new cache, counters). Every row is
    computed, free slots and the scratch one too (their states stay
    finite: the recurrence decays), so the counters count what the step
    really routed. The K/V part keeps ``gpt2_decode_step``'s ring
    contract; with no position embedding a wrapped ring is a window."""
    s = tokens.shape[0]
    nh, nkv, hd = cfg.n_head, cfg.n_kv_head, cfg.head_dim
    dt_ = cfg.dtype
    cache_len = cache["k"].shape[2]
    cursor = jnp.mod(pos, cache_len)
    valid = jnp.minimum(pos + 1, cache_len)
    with jax.named_scope("embed"):
        x = params["embed"].astype(dt_)[tokens]
    conv_all, ssm_all = cache["conv"], list(cache["ssm"])
    k_rows, v_rows, counts = [], [], []
    i_m = i_a = 0
    for kind, p in zip(cfg.pattern, params["layers"]):
        with jax.named_scope("ln"):
            y = _rms_norm(x, p["norm"], cfg.eps)
        if kind == "M":
            out, conv_all, ssm_all[i_m] = mamba2.step_through_cache(
                p, y, conv_all, ssm_all[i_m], i_m, cfg.mamba)
            i_m += 1
        elif kind == "*":
            with jax.named_scope("attn_proj"):
                q = (y @ p["wq"].astype(dt_)).reshape(s, nh, hd)
                k_new = (y @ p["wk"].astype(dt_)).reshape(
                    s, nkv, hd).astype(cache["k"].dtype)
                v_new = (y @ p["wv"].astype(dt_)).reshape(
                    s, nkv, hd).astype(cache["v"].dtype)
            with jax.named_scope("attn"):
                attn = cached_decode_attention(
                    q, cache["k"][i_a], cache["v"][i_a], k_new, v_new,
                    cursor, valid, dt_)
            with jax.named_scope("attn_proj"):
                out = attn.reshape(s, nh * hd) @ p["wo"].astype(dt_)
            k_rows.append(k_new)
            v_rows.append(v_new)
            i_a += 1
        else:
            out, c = _moe(p, y, cfg)
            counts.append(c)
        x = x + out
    k_all, v_all = cache["k"], cache["v"]
    if k_rows:
        with jax.named_scope("cache_write"):
            k_all = cache_write_token(k_all, jnp.stack(k_rows), cursor)
            v_all = cache_write_token(v_all, jnp.stack(v_rows), cursor)
    with jax.named_scope("ln"):
        x = _rms_norm(x, params["norm_f"], cfg.eps)
    with jax.named_scope("head"):
        logits = jnp.einsum("sd,dv->sv", x, params["lm_head"].astype(dt_),
                            preferred_element_type=jnp.float32)
    return logits, {"k": k_all, "v": v_all, "conv": conv_all,
                    "ssm": tuple(ssm_all)}, held_counters(counts)


def _rows(params: Params, tokens: jax.Array, lengths: jax.Array,
          cfg: NemotronHConfig, cache: Params | None = None,
          slots: jax.Array | None = None, start: jax.Array | None = None,
          window: int | None = None):
    """Rows of T tokens through every layer: tokens [R, T], lengths [R].
    Without a cache, whole rows from nothing. With one, row r is a chunk
    of a prompt at positions ``start[r] + i``: every layer continues from
    its part of ``slots[r]``'s state (K/V rows ``< start``; the
    convolution's tail and the SSM state, or none where ``start == 0``)
    and leaves there, in place, its state after the row's real tokens.
    -> (hidden [R, T, D] before ``norm_f``, the cache)."""
    r, t = tokens.shape
    nh, nkv, hd = cfg.n_head, cfg.n_kv_head, cfg.head_dim
    dt_ = cfg.dtype
    with jax.named_scope("embed"):
        x = params["embed"].astype(dt_)[tokens]
    # a padded chunk's other positions are not routed: no expert computes them
    real = jnp.arange(t)[None, :] < lengths[:, None]
    if cache is not None:
        k_all, v_all, conv_all = cache["k"], cache["v"], cache["conv"]
        ssm_all = list(cache["ssm"])
        window = window or k_all.shape[2]
        goes_on = start > 0  # [R]: the slot holds this prompt's state
    i_m = i_a = 0
    for kind, p in zip(cfg.pattern, params["layers"]):
        with jax.named_scope("ln"):
            y = _rms_norm(x, p["norm"], cfg.eps)
        if kind == "M" and cache is None:
            out, _, _ = mamba2.mamba_rows(p, y, lengths, cfg.mamba)
        elif kind == "M":
            out, conv_all, ssm_all[i_m] = mamba2.rows_through_cache(
                p, y, lengths, conv_all, ssm_all[i_m], i_m, slots, goes_on,
                cfg.mamba)
            i_m += 1
        elif kind == "*":
            with jax.named_scope("attn_proj"):
                q = (y @ p["wq"].astype(dt_)).reshape(r, t, nh, hd)
                k_ = (y @ p["wk"].astype(dt_)).reshape(r, t, nkv, hd)
                v_ = (y @ p["wv"].astype(dt_)).reshape(r, t, nkv, hd)
            if cache is None:
                with jax.named_scope("attn"):
                    rep = nh // nkv
                    attn = causal_attention(
                        q, jnp.repeat(k_, rep, axis=2),
                        jnp.repeat(v_, rep, axis=2), use_flash=False)
            else:
                with jax.named_scope("cache_write"):
                    k_all = cache_write_prompt(k_all, i_a, k_, slots, start)
                    v_all = cache_write_prompt(v_all, i_a, v_, slots, start)
                with jax.named_scope("attn"):
                    attn = cached_chunk_attention(
                        q, k_all, v_all, i_a, slots, start, window)
            with jax.named_scope("attn_proj"):
                out = attn.reshape(r, t, nh * hd) @ p["wo"].astype(dt_)
            i_a += 1
        else:
            out, _ = _moe(p, y.reshape(r * t, -1), cfg, real.reshape(-1))
            out = out.reshape(r, t, -1)
        x = x + out
    if cache is not None:
        cache = {"k": k_all, "v": v_all, "conv": conv_all,
                 "ssm": tuple(ssm_all)}
    return x, cache


# jax-hot-path: traced into the engine's single compiled prefill program
def nemotron_h_prefill_chunk(params: Params, cache: Params,
                             tokens: jax.Array, slots: jax.Array,
                             start: jax.Array, lengths: jax.Array,
                             cfg: NemotronHConfig, window: int | None = None
                             ) -> tuple[jax.Array, Params]:
    """A chunk of a prompt (fixed [R, C] shape; ``gpt2_prefill_chunk``'s
    contract for the K/V rows): tokens at positions ``start + i``, the
    first ``lengths`` of them real. The Mamba layers carry the slot's state
    across chunks: a chunk takes the slot's ``conv`` tail as the
    convolution's left context and its ``ssm`` state as the scan's first
    state, and leaves both as they stand after its real tokens;
    ``start == 0`` begins from an empty tail and a zero state, whatever
    the slot held. Padded positions take ``dt = 0`` and are routed to no
    expert. Logits at the chunk's last real token."""
    r, c = tokens.shape
    x, cache = _rows(params, tokens, lengths, cfg, cache, slots, start,
                     window)
    last = x[jnp.arange(r), jnp.clip(lengths - 1, 0, c - 1)]
    with jax.named_scope("ln"):
        last = _rms_norm(last, params["norm_f"], cfg.eps)
    with jax.named_scope("head"):
        logits = jnp.einsum(
            "rd,dv->rv", last, params["lm_head"].astype(cfg.dtype),
            preferred_element_type=jnp.float32)
    return logits, cache


def nemotron_h_prefill(params: Params, cache: Params, tokens: jax.Array,
                       slots: jax.Array, lengths: jax.Array,
                       cfg: NemotronHConfig) -> tuple[jax.Array, Params]:
    """Whole padded prompts tokens [R, P] through
    ``nemotron_h_prefill_chunk`` (``models/prefill.py``): each row's slot
    ends with the K/V rows of the whole window and with the ``conv`` tail
    and ``ssm`` state after the row's ``length`` real tokens, whatever it
    held. Logits at each prompt's last real token."""
    return whole_prompts(nemotron_h_prefill_chunk, params, cache, tokens,
                         slots, lengths, cfg)


def nemotron_h_forward(params: Params, tokens: jax.Array,
                       cfg: NemotronHConfig) -> jax.Array:
    """Logits [R, T, V] float32 of whole rows, no cache (tests)."""
    lengths = jnp.full((tokens.shape[0],), tokens.shape[1], jnp.int32)
    x, _ = _rows(params, tokens, lengths, cfg)
    x = _rms_norm(x, params["norm_f"], cfg.eps)
    return jnp.einsum("rtd,dv->rtv", x, params["lm_head"].astype(cfg.dtype),
                      preferred_element_type=jnp.float32)
