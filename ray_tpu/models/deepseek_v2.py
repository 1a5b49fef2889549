"""DeepSeek-V2 (``model_type: deepseek_v2``): latent attention (MLA) over a
latent cache, then a dense gated MLP (the leading ``first_dense`` layers) or
group-limited routed experts beside shared experts, served through
``LLMEngine``.

A layer is two pre-norm residual branches (RMS norms, no bias anywhere):

  ``x = x + Attention(RMSNorm(x))``: ``c_q = RMSNorm(y W_qa)``, ``q = c_q
  W_qb`` in ``n_head`` heads of ``[q_nope | q_pe]``; ``[c_kv | k_pe] = y
  W_kva``, ``c_kv = RMSNorm(c_kv)``; a head's key and value are products of
  the latent, ``k_nope_h = c_kv W_uk,h`` and ``v_h = c_kv W_uv,h``; ``q_pe``
  and the ONE ``k_pe`` all heads share are rotated by position (YaRN);
  ``score_h = (q_nope_h . k_nope_h + q_pe_h . k_pe) * softmax_scale``;
  ``out = concat_h(sum p v_h) W_o``.
  ``x = x + FF(RMSNorm(x))``: ``W_down(silu(W_gate h) * W_up h)`` in the
  leading dense layers; after them ``sum_k w_k E_k(h) + S(h)``, the router
  ``ops/moe.route_group_limited`` (softmax over all experts, the best
  ``topk_group`` of ``n_group`` groups, the ``top_k`` largest, their scores
  unchanged times ``routed_scale``), ``E`` and ``S`` gated MLPs.

The cache holds, a token a layer, ``[c_kv | k_pe]`` after norm and rotation:
``kv_rank + rope_dim`` numbers shared by all heads
(``ops/attention.py``'s latent cache). The decode step runs the ABSORBED
form over it (``q_lat_h = q_nope_h W_uk,h^T`` scored against the latent
rows, the probabilities' sum of latents times ``W_uv,h``: scope ``absorb``
around ``attn``); a prefill chunk DECOMPRESSES the rows it may see, a block
of keys at a time (scope ``kv_up`` inside ``attn``). At the engine's chunk
of 512 queries a key row costs 75 M operations decompressed against 142 M
absorbed (54 M against 71 M at 256 queries: the decompression is paid once
a key row however many queries score it); with one query a slot only the
absorbed one reads the cache once.

Rotary lanes: lane ``i`` of a ``rope_dim`` vector pairs with lane ``i +
rope_dim / 2`` (the split halves of ``rotate_half``). The released code
first de-interleaves the checkpoint's lanes into this layout: a fixed
permutation of columns of ``W_qb`` and ``W_kva`` that seeded weights absorb.

The expert share (``experts_held``), the stored types, the gated expert as
``ops/moe.dropless_experts``' ``activation`` and the counters are as
``models/granite_hybrid.py`` has them; the step counts one thing more,
``expert_tokens_here``: its rows with at least one pair on the experts held
here (what an exchange between the groups' devices would deliver).
``benchmark/reference/deepseek_v2.py`` writes the equations out plainly;
the tests hold this file to it.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models.common import (_normal,
                                   gate as _gate,
                                   rms_norm as _rms_norm)
from ray_tpu.models.prefill import whole_prompts
from ray_tpu.ops.attention import (cache_write_prompt, cache_write_token,
                                   latent_chunk_attention,
                                   latent_decode_attention,
                                   xla_causal_attention)
from ray_tpu.ops.moe import (dropless_experts, held_counters,
                             route_group_limited)

Params = dict[str, Any]


@dataclasses.dataclass(frozen=True)
class DeepseekV2Config:
    vocab_size: int = 102400
    d_model: int = 5120
    n_layer: int = 60
    first_dense: int = 1      # leading layers with a dense MLP
    dense_ff: int = 12288
    eps: float = 1e-6
    # latent attention
    n_head: int = 128
    q_rank: int = 1536
    kv_rank: int = 512
    nope_dim: int = 128
    rope_dim: int = 64
    v_dim: int = 128
    # YaRN rotary
    rope_theta: float = 10000.0
    yarn_factor: float = 40.0
    yarn_original: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 0.707
    mscale_all_dim: float = 0.707
    # experts, in every layer after the dense ones
    n_experts: int = 160      # the router's width: every expert of the model
    experts_held: tuple = (0, 160)  # (first, count) of the experts held here
    n_group: int = 8
    topk_group: int = 3
    top_k: int = 6
    routed_scale: float = 16.0
    expert_ff: int = 1536
    shared_ff: int = 3072     # n_shared_experts x moe_intermediate_size
    dtype: Any = jnp.bfloat16        # activations, matmuls, cache rows
    param_dtype: Any = jnp.bfloat16  # as the checkpoint stores them
    # How ``deepseek_v2_init`` draws the seeded embedding (every other
    # matrix normal at 0.02, the released ``initializer_range``): drawn
    # smaller, the first layer's attention outweighs the token's own row
    # and a seeded model's continuation follows its context, so what an
    # engine serves depends on its cache. Nothing a checkpoint would need.
    embed_std: float = 0.02
    # ... and the routed experts' output matrices ``w2``. The published
    # ``routed_scale`` 16 on un-normalised softmax scores weighs each
    # chosen expert of a SEEDED (flat) router about a half, where training
    # has sized a released checkpoint's experts to that factor; drawn
    # smaller, a routing choice that bfloat16 rounding turns moves a row
    # no further than the rounding itself does.
    routed_out_std: float = 0.02

    def __post_init__(self):
        first, count = self.experts_held
        if not (0 <= first and count >= 1
                and first + count <= self.n_experts):
            raise ValueError(f"experts_held {self.experts_held} outside "
                             f"the router's {self.n_experts}")
        if self.n_experts % self.n_group \
                or not 1 <= self.topk_group <= self.n_group:
            raise ValueError(f"{self.n_experts} experts do not lie in "
                             f"{self.n_group} groups of which "
                             f"{self.topk_group} are kept")
        if self.rope_dim % 2 or not 0 <= self.first_dense <= self.n_layer:
            raise ValueError("rope_dim must be even and first_dense within "
                             "n_layer")

    @property
    def softmax_scale(self) -> float:
        """``(nope + rope) ** -0.5 * m(mscale_all_dim) ** 2``."""
        return (self.nope_dim + self.rope_dim) ** -0.5 \
            * _yarn_mscale(self.yarn_factor, self.mscale_all_dim) ** 2

    def is_dense(self, layer: int) -> bool:
        return layer < self.first_dense

    def serving_dtypes(self, params: Params) -> Params:
        """How an engine stores ``params``: as ``deepseek_v2_init`` made
        them (see ``NemotronHConfig.serving_dtypes``)."""
        return jax.tree.map(lambda x: x.dtype, params)

    def serving_stats(self, chunk: int = 0, window: int = 0) -> dict:
        """What ``llm_stats()`` says of the model beside its counters."""
        return {"expert_layers": self.n_layer - self.first_dense,
                "experts_held": self.experts_held[1]}

    @classmethod
    def tiny(cls, **kw) -> "DeepseekV2Config":
        """Both kinds of layer at a size a CPU test runs: a dense layer and
        two expert layers, 16 experts in 4 groups of which half are held."""
        base = dict(vocab_size=256, d_model=64, n_layer=3, first_dense=1,
                    dense_ff=96, n_head=4, q_rank=24, kv_rank=16,
                    nope_dim=8, rope_dim=8, v_dim=8, yarn_original=16,
                    n_experts=16, experts_held=(0, 8), n_group=4,
                    topk_group=2, top_k=3, routed_scale=4.0, expert_ff=24,
                    shared_ff=48)
        base.update(kw)
        return cls(**base)


# -- YaRN -----------------------------------------------------------------------


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(cfg: DeepseekV2Config) -> np.ndarray:
    """The ``rope_dim / 2`` rotation frequencies, float32: ``theta ** (-2 i
    / rope_dim)``, each blended with itself over ``yarn_factor`` by the
    linear ramp between the lanes at which ``yarn_original`` positions make
    ``beta_fast`` and ``beta_slow`` turns (fast lanes stay, slow ones are
    stretched)."""
    dim, base = cfg.rope_dim, cfg.rope_theta
    extra = base ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)

    def lane(turns):
        return dim * math.log(cfg.yarn_original / (turns * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(lane(cfg.beta_fast)), 0)
    high = min(math.ceil(lane(cfg.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    return (extra / cfg.yarn_factor * ramp
            + extra * (1.0 - ramp)).astype(np.float32)


def _rotate(x: jax.Array, pos: jax.Array, cfg: DeepseekV2Config) -> jax.Array:
    """x [..., rope_dim] at positions ``pos`` (x's leading axes, or ones
    that broadcast to them): float32 angles, the result in x's type."""
    angles = pos[..., None].astype(jnp.float32) * yarn_inv_freq(cfg)
    m = _yarn_mscale(cfg.yarn_factor, cfg.mscale) \
        / _yarn_mscale(cfg.yarn_factor, cfg.mscale_all_dim)
    cos, sin = jnp.cos(angles) * m, jnp.sin(angles) * m
    half = cfg.rope_dim // 2
    a, b = x[..., :half].astype(jnp.float32), x[..., half:].astype(
        jnp.float32)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


# -- parameters ---------------------------------------------------------------


def _layer_init(key, layer: int, cfg: DeepseekV2Config) -> Params:
    d, pd, std = cfg.d_model, cfg.param_dtype, 0.02
    h, rank = cfg.n_head, cfg.kv_rank
    keys = iter(jax.random.split(key, 12))
    p = {
        "norm": jnp.ones((d,), pd), "norm2": jnp.ones((d,), pd),
        "wq_a": _normal(next(keys), (d, cfg.q_rank), std, pd),
        "q_norm": jnp.ones((cfg.q_rank,), pd),
        "wq_b": _normal(next(keys), (cfg.q_rank, h, cfg.nope_dim
                                     + cfg.rope_dim), std, pd),
        "wkv_a": _normal(next(keys), (d, rank + cfg.rope_dim), std, pd),
        "kv_norm": jnp.ones((rank,), pd),
        # ``kv_b_proj`` in its two parts: a head's key and its value
        "w_uk": _normal(next(keys), (rank, h, cfg.nope_dim), std, pd),
        "w_uv": _normal(next(keys), (rank, h, cfg.v_dim), std, pd),
        "wo": _normal(next(keys), (h * cfg.v_dim, d), std, pd),
    }
    if cfg.is_dense(layer):
        # [a, b] = [W_gate h, W_up h] side by side: one product
        p.update(w_in=_normal(next(keys), (d, 2 * cfg.dense_ff), std, pd),
                 w_down=_normal(next(keys), (cfg.dense_ff, d), std, pd))
        return p
    held, ff = cfg.experts_held[1], cfg.expert_ff
    p.update(
        router=_normal(next(keys), (d, cfg.n_experts), std, pd),
        w1=_normal(next(keys), (held, d, 2 * ff), std, pd),
        w2=_normal(next(keys), (held, ff, d), cfg.routed_out_std, pd),
        shared_w1=_normal(next(keys), (d, 2 * cfg.shared_ff), std, pd),
        shared_w2=_normal(next(keys), (cfg.shared_ff, d), std, pd))
    return p


def deepseek_v2_init(rng: jax.Array, cfg: DeepseekV2Config) -> Params:
    """Seeded weights in ``cfg.param_dtype`` (bfloat16 as published), one
    dict a layer (the two kinds hold different leaves and cannot be
    stacked), every matrix normal at 0.02 (the embedding at
    ``cfg.embed_std``, the routed experts' ``w2`` at
    ``cfg.routed_out_std``), norms at one. The head is its own matrix."""
    keys = jax.random.split(rng, cfg.n_layer + 2)
    pd = cfg.param_dtype
    return {
        "embed": _normal(keys[0], (cfg.vocab_size, cfg.d_model),
                         cfg.embed_std, pd),
        "layers": [_layer_init(keys[2 + i], i, cfg)
                   for i in range(cfg.n_layer)],
        "norm_f": jnp.ones((cfg.d_model,), pd),
        "head": _normal(keys[1], (cfg.vocab_size, cfg.d_model), 0.02, pd),
    }


# -- the parts ----------------------------------------------------------------


def _latent_inputs(p: Params, y: jax.Array, pos: jax.Array,
                   cfg: DeepseekV2Config):
    """y [..., D] (normed) at positions ``pos`` [...] -> (q_nope [..., H,
    nope], q_pe [..., H, rope] rotated, row [..., rank + rope]: the
    normalised latent beside the rotated position key, as the cache holds
    it)."""
    dt_ = cfg.dtype
    with jax.named_scope("attn_proj"):
        c_q = _rms_norm(y @ p["wq_a"].astype(dt_), p["q_norm"], cfg.eps)
        q = jnp.einsum("...r,rhd->...hd", c_q, p["wq_b"].astype(dt_))
        kv = y @ p["wkv_a"].astype(dt_)
        c_kv = _rms_norm(kv[..., :cfg.kv_rank], p["kv_norm"], cfg.eps)
    with jax.named_scope("rope"):
        q_pe = _rotate(q[..., cfg.nope_dim:], pos[..., None], cfg)
        k_pe = _rotate(kv[..., cfg.kv_rank:], pos, cfg)
    return q[..., :cfg.nope_dim], q_pe, jnp.concatenate([c_kv, k_pe], -1)


def _feed_forward(p: Params, y: jax.Array, cfg: DeepseekV2Config,
                  live: jax.Array | None = None):
    """The layer's second branch over rows y [T, D] (normed): the dense
    MLP where ``p`` holds one, else the held experts' part of the routed
    output plus the shared experts. -> (out [T, D], the pairs each held
    expert took [count] or None, rows with a pair here [T] or None)."""
    dt_ = cfg.dtype
    if "w_in" in p:
        with jax.named_scope("mlp"):
            return _gate(y @ p["w_in"].astype(dt_)) \
                @ p["w_down"].astype(dt_), None, None
    first, count = cfg.experts_held
    with jax.named_scope("router"):
        ids, weights = route_group_limited(
            y, p["router"], cfg.top_k, cfg.n_group, cfg.topk_group,
            cfg.routed_scale)
        here = jnp.any((ids >= first) & (ids < first + count), axis=-1)
    routed, counts = dropless_experts(
        y, ids, weights, p["w1"], p["w2"], first=first, activation=_gate,
        live=live)
    with jax.named_scope("shared_expert"):
        shared = _gate(y @ p["shared_w1"].astype(dt_)) \
            @ p["shared_w2"].astype(dt_)
        out = (routed + shared.astype(jnp.float32)).astype(dt_)
    return out, counts, here


def _head(x: jax.Array, params: Params, cfg: DeepseekV2Config):
    """``RMSNorm(x) W_head^T``, float32. x [..., D]."""
    with jax.named_scope("ln"):
        x = _rms_norm(x, params["norm_f"], cfg.eps)
    with jax.named_scope("head"):
        return jnp.einsum("...d,vd->...v", x,
                          params["head"].astype(cfg.dtype),
                          preferred_element_type=jnp.float32)


def _embed(params: Params, tokens: jax.Array, cfg: DeepseekV2Config):
    with jax.named_scope("embed"):
        return params["embed"].astype(cfg.dtype)[tokens]


# -- the cache and the serving functions --------------------------------------


def deepseek_v2_init_cache(cfg: DeepseekV2Config, slots: int,
                           cache_len: int) -> Params:  # decode-path
    """A ring of latent rows a slot a layer (``kv_rank + rope_dim`` numbers
    a token, one "head" of that width: ``ops/attention.py``), and what the
    programs count (``counted``: int32 scalars, which wrap): one pytree,
    which the engine donates."""
    return {"latent": jnp.zeros((cfg.n_layer, slots, cache_len, 1,
                                 cfg.kv_rank + cfg.rope_dim), cfg.dtype),
            "counted": {"prefill_expert_rows": jnp.zeros((), jnp.int32)}}


# jax-hot-path: traced into the engine's single compiled decode step
def deepseek_v2_decode_step(params: Params, cache: Params,
                            tokens: jax.Array, pos: jax.Array,
                            cfg: DeepseekV2Config
                            ) -> tuple[jax.Array, Params, dict]:
    """One decode iteration for every slot: tokens [S] int32, pos [S]
    int32 -> (logits [S, V] fp32, new cache, counters ``experts_hit``,
    ``expert_rows`` and ``expert_tokens_here`` over the step's layers).
    Every row is computed, free slots and the scratch one too, so the
    counters count what the step really routed. The ring keeps
    ``gpt2_decode_step``'s contract; a row holds its key already rotated,
    so a wrapped ring is a window over true positions."""
    s = tokens.shape[0]
    dt_ = cfg.dtype
    latent = cache["latent"]
    cache_len = latent.shape[2]
    cursor = jnp.mod(pos, cache_len)
    valid = jnp.minimum(pos + 1, cache_len)
    x = _embed(params, tokens, cfg)
    rows, counts, here = [], [], jnp.int32(0)
    for i, p in enumerate(params["layers"]):
        with jax.named_scope("ln"):
            y = _rms_norm(x, p["norm"], cfg.eps)
        q_nope, q_pe, row = _latent_inputs(p, y, pos, cfg)
        row = row.astype(latent.dtype)
        with jax.named_scope("absorb"):
            q_lat = jnp.einsum("shd,rhd->shr", q_nope,
                               p["w_uk"].astype(dt_))
        with jax.named_scope("attn"):
            o_lat = latent_decode_attention(
                q_lat, q_pe, latent, i, row, cursor, valid,
                cfg.softmax_scale, dt_)
        with jax.named_scope("absorb"):
            o = jnp.einsum("shr,rhd->shd", o_lat, p["w_uv"].astype(dt_))
        with jax.named_scope("attn_proj"):
            out = o.reshape(s, -1) @ p["wo"].astype(dt_)
        rows.append(row)
        x = x + out
        with jax.named_scope("ln"):
            y = _rms_norm(x, p["norm2"], cfg.eps)
        out, c, h = _feed_forward(p, y, cfg)
        if c is not None:
            counts.append(c)
            here = here + jnp.sum(h, dtype=jnp.int32)
        x = x + out
    with jax.named_scope("cache_write"):
        latent = cache_write_token(latent, jnp.stack(rows)[:, :, None],
                                   cursor)
    return _head(x, params, cfg), {
        "latent": latent, "counted": cache["counted"]}, {
            **held_counters(counts), "expert_tokens_here": here}


def _rows(params: Params, tokens: jax.Array, lengths: jax.Array,
          cfg: DeepseekV2Config, cache: Params | None = None,
          slots: jax.Array | None = None, start: jax.Array | None = None):
    """Rows of T tokens through every layer: tokens [R, T], lengths [R].
    Without a cache, whole rows from position 0 (every key and value
    decompressed, plain causal attention). With one, row r is a chunk of a
    prompt at positions ``start[r] + i``: each layer writes the chunk's
    latent rows to ``slots[r]`` and attends over the slot's rows up to
    itself, and the token-expert pairs the held experts took are added to
    the cache's ``prefill_expert_rows``. -> (hidden [R, T, D] before
    ``norm_f``, the cache)."""
    r, t = tokens.shape
    dt_ = cfg.dtype
    x = _embed(params, tokens, cfg)
    # a padded chunk's other positions are not routed: no expert computes them
    real = (jnp.arange(t)[None, :] < lengths[:, None]).reshape(-1)
    pos = jnp.arange(t)[None, :] + (0 if cache is None else start[:, None])
    latent = None if cache is None else cache["latent"]
    pairs = jnp.int32(0)
    for i, p in enumerate(params["layers"]):
        with jax.named_scope("ln"):
            y = _rms_norm(x, p["norm"], cfg.eps)
        q_nope, q_pe, row = _latent_inputs(p, y, pos, cfg)
        if cache is None:
            with jax.named_scope("attn"):
                c_kv, k_pe = row[..., :cfg.kv_rank], row[..., cfg.kv_rank:]
                k = jnp.einsum("rtc,chd->rthd", c_kv, p["w_uk"].astype(dt_))
                v = jnp.einsum("rtc,chd->rthd", c_kv, p["w_uv"].astype(dt_))
                k = jnp.concatenate([k, jnp.broadcast_to(
                    k_pe[:, :, None], (r, t, cfg.n_head, cfg.rope_dim))], -1)
                attn = xla_causal_attention(
                    jnp.concatenate([q_nope, q_pe], -1), k, v,
                    softmax_scale=cfg.softmax_scale)
        else:
            with jax.named_scope("cache_write"):
                latent = cache_write_prompt(latent, i, row[:, :, None],
                                            slots, start)
            with jax.named_scope("attn"):
                attn = latent_chunk_attention(
                    q_nope, q_pe, latent, i, slots, start, p["w_uk"],
                    p["w_uv"], cfg.softmax_scale)
        with jax.named_scope("attn_proj"):
            out = attn.reshape(r, t, -1).astype(dt_) @ p["wo"].astype(dt_)
        x = x + out
        with jax.named_scope("ln"):
            y = _rms_norm(x, p["norm2"], cfg.eps)
        out, c, _ = _feed_forward(p, y.reshape(r * t, -1), cfg, real)
        if c is not None:
            pairs = pairs + jnp.sum(c, dtype=jnp.int32)
        x = x + out.reshape(r, t, -1)
    if cache is not None:
        cache = {"latent": latent, "counted": {
            "prefill_expert_rows":
            cache["counted"]["prefill_expert_rows"] + pairs}}
    return x, cache


# jax-hot-path: traced into the engine's single compiled prefill program
def deepseek_v2_prefill_chunk(params: Params, cache: Params,
                              tokens: jax.Array, slots: jax.Array,
                              start: jax.Array, lengths: jax.Array,
                              cfg: DeepseekV2Config,
                              window: int | None = None
                              ) -> tuple[jax.Array, Params]:
    """A chunk of a prompt (fixed [R, C] shape; the contract of
    ``models/prefill.py``). Logits at the chunk's last real token; the
    token-expert pairs that landed on the experts held here, over the
    chunk's layers, go to the cache's ``prefill_expert_rows``. ``window``
    is taken and not used: the chunk's attention reads the slot's rows in
    blocks up to ``start + C``, whatever the longest prompt
    (``ops/attention.latent_chunk_attention``)."""
    r, c = tokens.shape
    x, cache = _rows(params, tokens, lengths, cfg, cache, slots, start)
    last = x[jnp.arange(r), jnp.clip(lengths - 1, 0, c - 1)]
    return _head(last, params, cfg), cache


def deepseek_v2_prefill(params: Params, cache: Params, tokens: jax.Array,
                        slots: jax.Array, lengths: jax.Array,
                        cfg: DeepseekV2Config
                        ) -> tuple[jax.Array, Params]:
    """Whole padded prompts tokens [R, P] through
    ``deepseek_v2_prefill_chunk`` (``models/prefill.py``). Logits at each
    prompt's last real token."""
    return whole_prompts(deepseek_v2_prefill_chunk, params, cache, tokens,
                         slots, lengths, cfg)


def deepseek_v2_forward(params: Params, tokens: jax.Array,
                        cfg: DeepseekV2Config) -> jax.Array:
    """Logits [R, T, V] float32 of whole rows, no cache (tests)."""
    lengths = jnp.full((tokens.shape[0],), tokens.shape[1], jnp.int32)
    x, _ = _rows(params, tokens, lengths, cfg)
    return _head(x, params, cfg)
