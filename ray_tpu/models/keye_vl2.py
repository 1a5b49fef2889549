"""Keye-VL-2.0's language model (``model_name: keye_vl2_*``): grouped-query
attention that reads only the keys a learned INDEXER picks, and top-k
experts in every layer, served through ``LLMEngine``. Text only: the vision
tower is not served, and for text the three position streams of the
published M-RoPE are equal, which is the ordinary rotation (the tests hold
``benchmark/reference/keye_vl2.py``'s three-stream form to it).

Every layer alike; ``N`` an RMSNorm with weight, ``t`` a query position,
``s`` a key position:

  ``a  = N(x; norm)``; ``q = a Wq`` (H heads), ``k = a Wk``, ``v = a Wv``
         (G heads), no bias; RMSNorm over each head's lanes of q and of k
         (``q_norm``, ``k_norm``); q and k rotated over the whole head;
  indexer, from the same ``a``: ``qI = a W_qI`` (J heads of dI lanes),
         ``kI = LayerNorm(a W_kI)`` (ONE head of dI lanes, weight and
         bias), ``w = a W_w`` (J numbers); qI and kI rotated;
         ``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])`` in float32;
         ``S_t`` = the ``index_topk`` positions ``s <= t`` of largest
         ``I[t, s]`` (all of them while ``t < index_topk``), ties to the
         lower ``s``: one set a token a layer, shared by all H heads;
  ``h  = x + Wo softmax_{s in S_t}(q_t . k_s / sqrt(hd)) v``;
  ``y  = N(h; norm2)``; ``x = h + sum_chosen w_e W2_e (silu(a_e) * b_e)``,
         ``[a_e, b_e] = W1_e y``: a softmax over ALL experts, the ``top_k``
         largest, renormalised (``ops/moe.route_topk_softmax``); no shared
         expert; of the router's experts this chip holds ``experts_held``;
  ``logits = N(x; norm_f) W_head`` (a table of its own).

``benchmark/reference/keye_vl2.py`` writes the equations out plainly; the
tests hold this file to it.

The cache is TWO stacks of rings in one pytree: ``kv`` [n_layer, slot,
cache_len, 2 W], a token's merged K row and its merged V row
(``ops/attention.py``'s rank 4: four K/V heads of 128 lanes are four whole
lane tiles) SIDE BY SIDE in one ring row, so that a step's gather moves one
row a pick and not two (``ops/sparse_select.py`` has the chip's readings),
and ``idx`` [n_layer, slot, cache_len, dI], the indexer's rotated keys, one
narrow row a token a layer.
A ring is as long as a context may be (no wrap inside a request). Both
programs read the rings as they were and write their new rows once a stack
after the layer loop. Which rows a query reads is ``ops/sparse_select.py``'s
business: a step gathers the picked rows, a chunk masks the keys in sight.
The step counts ``sparse_keys_selected`` and ``sparse_keys_eligible`` (over
layers and live slots).

Seeded weights (``keye_vl2_init``) are drawn by ``cfg.gains``: see there.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from ray_tpu.models.common import _normal, gate as _gate, rms_norm as _norm
from ray_tpu.models.prefill import (chunk_len, token_parameters,
                                    whole_prompts)
from ray_tpu.ops.attention import (cache_write_chunk, cache_write_token,
                                   merged_row_width, merged_rows)
from ray_tpu.ops.moe import (dropless_experts, held_counters,
                             route_topk_softmax)
from ray_tpu.ops.rotary import rotate
from ray_tpu.ops.sparse_select import (chunk_select, index_scores,
                                       select_mask, sort_keys,
                                       sparse_chunk_attention,
                                       sparse_decode_attention)

Params = dict[str, Any]

# How ``keye_vl2_init`` draws a matrix: normal at ``gain / sqrt(fan_in)``, so
# that ``gain`` is the rms of its output for an input of rms one
# (``models/qwen3_next.GAINS`` says why 0.02 throughout does not do). ``o`` is
# large because attention's output is a mean over up to ``index_topk`` keys;
# ``expert_down`` because an eighth of a token's experts are held; the router
# is drawn peaked for the reason Qwen3-Next's is (a choice that bfloat16
# rounding turns must move little). The head norms normalise q and k and the
# LayerNorm the indexer's key, so those gains move nothing; ``idx_q`` and
# ``idx_w`` scale every index score of a query alike and move no selection.
# Nothing a released checkpoint would need.
GAINS = (("embed", 1.0), ("q", 1.0), ("k", 1.0), ("v", 1.0), ("o", 8.0),
         ("idx_q", 1.0), ("idx_k", 1.0), ("idx_w", 1.0), ("router", 4.0),
         ("expert_in", 1.0), ("expert_down", 2.0), ("head", 1.0))


@dataclasses.dataclass(frozen=True)
class KeyeVL2Config:
    vocab_size: int = 151936
    d_model: int = 2048
    n_layer: int = 48
    eps: float = 1e-6
    n_head: int = 32
    n_kv_head: int = 4
    head_dim: int = 128
    rope_theta: float = 1e7
    # the indexer (the published ``sa_config``)
    index_heads: int = 16
    index_dim: int = 64
    index_topk: int = 2048
    # experts, in every layer
    n_experts: int = 128       # the router's width: every expert of the model
    experts_held: tuple = (0, 128)  # (first, count) of the experts held here
    top_k: int = 8
    expert_ff: int = 768
    dtype: Any = jnp.bfloat16        # activations and matmuls
    param_dtype: Any = jnp.bfloat16  # as the checkpoint stores them
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
        if self.n_head % self.n_kv_head or self.head_dim % 2 \
                or self.index_dim % 2:
            raise ValueError("query heads must divide into K/V heads, and "
                             "a head's lanes into pairs")
        if self.index_topk < 1 or not 1 <= self.top_k <= self.n_experts:
            raise ValueError("index_topk and top_k must be at least 1, and "
                             "top_k at most n_experts")

    @property
    def row_width(self) -> int:
        """Columns of a merged K or V row."""
        return merged_row_width(self.n_kv_head, self.head_dim)

    def serving_dtypes(self, params: Params) -> Params:
        """How an engine stores ``params``: as ``keye_vl2_init`` made them
        (see ``NemotronHConfig.serving_dtypes``)."""
        return jax.tree.map(lambda x: x.dtype, params)

    def serving_stats(self, chunk: int = 0, window: int = 0) -> dict:
        """What ``llm_stats()`` says of the model beside its counters, so
        that a reader holds no shape of its own: the ring bytes a token
        takes in the K/V stacks and in the indexer's, how many keys a
        query may pick, and which implementation a chunk program of
        ``chunk`` tokens over a key window of ``window`` rows (the
        engine's) picks and attends through
        (``ops/sparse_select.chunk_select``: the choice is static, by
        shapes alone; an engine with no ring to read runs the XLA arm)."""
        act = jnp.dtype(self.dtype).itemsize
        return {
            "expert_layers": self.n_layer,
            "experts_held": self.experts_held[1],
            "sparse_layers": self.n_layer,
            "sparse_topk": self.index_topk,
            "sparse_chunk_select": chunk_select(
                chunk, self.head_dim, self.row_width,
                max(window - chunk, 0), self.index_heads, self.index_dim),
            "kv_bytes_per_token": 2 * self.n_layer * self.row_width * act,
            "index_bytes_per_token": self.n_layer * self.index_dim * act,
        }

    @classmethod
    def tiny(cls, **kw) -> "KeyeVL2Config":
        """Three layers at a size a CPU test runs: fewer K/V heads than
        query heads, several indexer heads over one narrow key, a ``topk``
        a toy prompt passes, and fewer experts a token than experts."""
        base = dict(
            vocab_size=256, d_model=48, n_layer=3, n_head=4, n_kv_head=2,
            head_dim=16, rope_theta=1e4, index_heads=3, index_dim=8,
            index_topk=16, n_experts=8, experts_held=(0, 8), top_k=3,
            expert_ff=24)
        base.update(kw)
        return cls(**base)


# -- parameters ---------------------------------------------------------------


def init_stds(cfg: KeyeVL2Config) -> dict:
    """The standard deviation each matrix is drawn at (``GAINS`` says
    why): ``gain / sqrt(fan_in)``."""
    g = dict(cfg.gains)
    d = cfg.d_model ** 0.5
    return {
        "embed": g["embed"], "wq": g["q"] / d, "wk": g["k"] / d,
        "wv": g["v"] / d,
        "wo": g["o"] / (cfg.n_head * cfg.head_dim) ** 0.5,
        "idx_wq": g["idx_q"] / d, "idx_wk": g["idx_k"] / d,
        "idx_ww": g["idx_w"] / d,
        "router": g["router"] / d, "w1": g["expert_in"] / d,
        "w2": g["expert_down"] / cfg.expert_ff ** 0.5,
        "lm_head": g["head"] / d,
    }


def _layer_init(key, cfg: KeyeVL2Config, std: dict) -> Params:
    d, pd = cfg.d_model, cfg.param_dtype
    keys = iter(jax.random.split(key, 10))
    q, kv = cfg.n_head * cfg.head_dim, cfg.n_kv_head * cfg.head_dim
    held, ff = cfg.experts_held[1], cfg.expert_ff
    return {
        "norm": jnp.ones((d,), pd), "norm2": jnp.ones((d,), pd),
        "wq": _normal(next(keys), (d, q), std["wq"], pd),
        "wk": _normal(next(keys), (d, kv), std["wk"], pd),
        "wv": _normal(next(keys), (d, kv), std["wv"], pd),
        "wo": _normal(next(keys), (q, d), std["wo"], pd),
        "q_norm": jnp.ones((cfg.head_dim,), pd),
        "k_norm": jnp.ones((cfg.head_dim,), pd),
        # the indexer: J query heads, ONE key head, J weights a token
        "idx_wq": _normal(next(keys), (d, cfg.index_heads * cfg.index_dim),
                          std["idx_wq"], pd),
        "idx_wk": _normal(next(keys), (d, cfg.index_dim), std["idx_wk"], pd),
        "idx_ww": _normal(next(keys), (d, cfg.index_heads), std["idx_ww"],
                          pd),
        "idx_k_norm": jnp.ones((cfg.index_dim,), pd),
        "idx_k_bias": jnp.zeros((cfg.index_dim,), pd),
        "router": _normal(next(keys), (d, cfg.n_experts), std["router"], pd),
        # [a, b] = W1 y side by side: the gate's halves of one product
        "w1": _normal(next(keys), (held, d, 2 * ff), std["w1"], pd),
        "w2": _normal(next(keys), (held, ff, d), std["w2"], pd),
    }


def keye_vl2_init(rng: jax.Array, cfg: KeyeVL2Config) -> Params:
    """Seeded weights in ``cfg.param_dtype`` (bfloat16 as published), one
    dict a layer, every matrix normal at ``init_stds``'s value, the norms at
    1 and the LayerNorm's bias at 0. The head is a table of its own
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


def _layer_norm(x: jax.Array, w: jax.Array, b: jax.Array,
                eps: float) -> jax.Array:
    """LayerNorm with weight and bias over the last axis."""
    xf = x.astype(jnp.float32)
    xf = xf - jnp.mean(xf, axis=-1, keepdims=True)
    xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * w.astype(jnp.float32) + b.astype(jnp.float32)).astype(
        x.dtype)


def _qkv(p: Params, a: jax.Array, pos: jax.Array, cfg: KeyeVL2Config):
    """The attention layer's inputs of normed rows a [..., D] at positions
    pos [...]: q [..., H, hd] and k [..., G, hd] normed a head and rotated,
    v [..., G, hd]."""
    dt_ = cfg.dtype
    lead = a.shape[:-1]
    with jax.named_scope("attn_proj"):
        q = (a @ p["wq"].astype(dt_)).reshape(*lead, cfg.n_head, cfg.head_dim)
        k = (a @ p["wk"].astype(dt_)).reshape(*lead, cfg.n_kv_head,
                                              cfg.head_dim)
        v = (a @ p["wv"].astype(dt_)).reshape(*lead, cfg.n_kv_head,
                                              cfg.head_dim)
    with jax.named_scope("qk_norm"):
        q = _norm(q, p["q_norm"], cfg.eps)
        k = _norm(k, p["k_norm"], cfg.eps)
    with jax.named_scope("rope"):
        q = rotate(q, pos, cfg.rope_theta)
        k = rotate(k, pos, cfg.rope_theta)
    return q, k, v


def _indexer(p: Params, a: jax.Array, pos: jax.Array, cfg: KeyeVL2Config):
    """The indexer's inputs of the same normed rows a [..., D]: its queries
    qI [..., J, dI] and its ONE key kI [..., dI] (LayerNorm), both rotated
    over all their lanes, and the heads' weights w [..., J] float32."""
    dt_ = cfg.dtype
    lead = a.shape[:-1]
    with jax.named_scope("indexer"):
        q_idx = (a @ p["idx_wq"].astype(dt_)).reshape(
            *lead, cfg.index_heads, cfg.index_dim)
        k_idx = _layer_norm(a @ p["idx_wk"].astype(dt_), p["idx_k_norm"],
                            p["idx_k_bias"], cfg.eps)
        w_idx = jnp.einsum("...d,dj->...j", a, p["idx_ww"].astype(dt_),
                           preferred_element_type=jnp.float32)
        q_idx = rotate(q_idx, pos, cfg.rope_theta)
        k_idx = rotate(k_idx[..., None, :], pos, cfg.rope_theta)[..., 0, :]
    return q_idx, k_idx, w_idx


def _attn_out(p: Params, attn: jax.Array, cfg: KeyeVL2Config):
    """``Wo`` of attn [..., H, hd] -> [..., D]."""
    with jax.named_scope("attn_proj"):
        return attn.reshape(*attn.shape[:-2], -1) @ p["wo"].astype(cfg.dtype)


def _moe(p: Params, h: jax.Array, cfg: KeyeVL2Config,
         live: jax.Array | None = None):
    """``h + Routed(N(h; norm2))`` over rows h [T, D]: the held experts'
    part of the routed output; rows that ``live`` [T] says are padding are
    routed nowhere. -> (the stream [T, D], the pairs each held expert took
    [count])."""
    with jax.named_scope("ln"):
        y = _norm(h, p["norm2"], cfg.eps)
    with jax.named_scope("router"):
        ids, weights = route_topk_softmax(y, p["router"], cfg.top_k)
    routed, counts = dropless_experts(
        y, ids, weights, p["w1"], p["w2"], first=cfg.experts_held[0],
        activation=_gate, live=live)
    with jax.named_scope("moe_combine"):
        out = (h.astype(jnp.float32) + routed).astype(cfg.dtype)
    return out, counts


def _head(x: jax.Array, params: Params, cfg: KeyeVL2Config):
    """``N(x; norm_f) W_head``, float32. x [..., D]."""
    with jax.named_scope("ln"):
        x = _norm(x, params["norm_f"], cfg.eps)
    with jax.named_scope("head"):
        return jnp.einsum(
            "...d,vd->...v", x, params["lm_head"].astype(cfg.dtype),
            preferred_element_type=jnp.float32)


def _embed(params: Params, tokens: jax.Array, cfg: KeyeVL2Config):
    with jax.named_scope("embed"):
        return params["embed"].astype(cfg.dtype)[tokens]


# -- the cache and the serving functions --------------------------------------


def keye_vl2_init_cache(cfg: KeyeVL2Config, slots: int,
                        cache_len: int) -> Params:  # decode-path
    """Two stacks of rings in ONE pytree, which the engine donates: ``kv``
    [n_layer, slot, cache_len, 2 W], a token's merged K row and V row side
    by side (``merged_row_width`` each, which both programs read as they
    lie), ``idx`` [n_layer, slot, cache_len, dI], the indexer's keys; and
    what the chunk program counts (``counted``: an int32 scalar, which
    wraps)."""
    rings = (cfg.n_layer, slots, cache_len)
    return {"kv": jnp.zeros(rings + (2 * cfg.row_width,), cfg.dtype),
            "idx": jnp.zeros(rings + (cfg.index_dim,), cfg.dtype),
            "counted": {"prefill_expert_rows": jnp.zeros((), jnp.int32)}}


def _kv_row(k: jax.Array, v: jax.Array, cache: jax.Array) -> jax.Array:
    """A token's K and V heads [..., G, hd] each as the cache holds them:
    the K heads side by side, then the V heads, in one row [..., 2 W], in
    its type."""
    w = cache.shape[-1] // 2
    return jnp.concatenate([merged_rows(
        x.reshape(*x.shape[:-2], -1).astype(cache.dtype), w)
        for x in (k, v)], axis=-1)


_STACKS = ("kv", "idx")


def keye_vl2_step_with_sets(params: Params, cache: Params, tokens: jax.Array,
                            pos: jax.Array, cfg: KeyeVL2Config):
    """``keye_vl2_decode_step`` and, behind its three results, what each
    layer picked: rows [n_layer, S, min(topk, cache_len)] int32, the ring
    rows of each slot's set in rising order, and sizes [n_layer, S] int32,
    how many of them count (``benchmark/tools/serve_check_sparse.py`` holds
    them to the reference's sets)."""
    dt_ = cfg.dtype
    n_rows = cache["kv"].shape[2]
    cursor = jnp.mod(pos, n_rows)
    valid = jnp.where(pos > 0, jnp.minimum(pos + 1, n_rows), 0)
    x = _embed(params, tokens, cfg)
    new_rows = {name: [] for name in _STACKS}
    counts, sets, sizes = [], [], []
    for i, p in enumerate(params["layers"]):
        with jax.named_scope("ln"):
            a = _norm(x, p["norm"], cfg.eps)
        q, k_new, v_new = _qkv(p, a, pos, cfg)
        kv_new = _kv_row(k_new, v_new, cache["kv"])
        with jax.named_scope("attn"):
            q_idx, idx_new, w_idx = _indexer(p, a, pos, cfg)
            idx_new = idx_new.astype(cache["idx"].dtype)
            attn, rows, took = sparse_decode_attention(
                q, cache["kv"], cache["idx"], kv_new, idx_new, q_idx, w_idx,
                i, cursor, valid, cfg.index_topk, dt_)
        for name, row in zip(_STACKS, (kv_new, idx_new)):
            new_rows[name].append(row)
        sets.append(rows)
        sizes.append(took)
        x, c = _moe(p, x + _attn_out(p, attn, cfg), cfg)
        counts.append(c)
    new = {"counted": cache["counted"]}
    with jax.named_scope("cache_write"):
        for name in _STACKS:
            new[name] = cache_write_token(
                cache[name], jnp.stack(new_rows[name]), cursor)
    sizes = jnp.stack(sizes)
    return _head(x, params, cfg), new, {
        **held_counters(counts),
        "sparse_keys_selected": jnp.sum(sizes, dtype=jnp.int32),
        "sparse_keys_eligible": (cfg.n_layer * jnp.sum(valid)).astype(
            jnp.int32)}, jnp.stack(sets), sizes


# jax-hot-path: traced into the engine's single compiled decode step
def keye_vl2_decode_step(params: Params, cache: Params, tokens: jax.Array,
                         pos: jax.Array, cfg: KeyeVL2Config
                         ) -> tuple[jax.Array, Params, dict]:
    """One decode iteration for every slot: tokens [S] int32, pos [S]
    int32 -> (logits [S, V] fp32, new cache, counters ``experts_hit``,
    ``expert_rows`` and ``expert_row_tiles`` over the step's layers,
    ``sparse_keys_selected`` and ``sparse_keys_eligible`` over layers and
    LIVE slots: a slot at position 0 is free (a request's first step comes
    after its prompt), picks nothing and counts nothing). Every row is
    computed, free slots and the scratch one too. ``gpt2_decode_step``'s
    ring contract: slot s's new rows land at ``pos[s] % cache_len``; the
    rings are read as they were, every layer's new rows written once a
    stack after the loop."""
    return keye_vl2_step_with_sets(params, cache, tokens, pos, cfg)[:3]


def _rows(params: Params, tokens: jax.Array, lengths: jax.Array,
          cfg: KeyeVL2Config, cache: Params, slots: jax.Array,
          start: jax.Array, window: int | None = None):
    """A chunk of T prompt tokens a row through every layer: tokens [R, T],
    lengths [R], row r at positions ``start[r] + i`` over ``slots[r]``'s
    rings as earlier chunks left them and the chunk's own rows beside them
    (``sparse_chunk_attention``, over the ``window`` rows the caller's
    longest prompt names). The chunk's rows are written once a stack after
    the loop. What the experts took is added to the cache's ``counted``.
    -> (hidden [R, T, D] before ``norm_f``, the cache, what each layer's
    queries picked: [n_layer, R, T, window] bool, ``sparse_chunk_attention``'s
    masks, which a program that does not return them does not make)."""
    r, t = tokens.shape
    x = _embed(params, tokens, cfg)
    pos = jnp.arange(t)[None, :] + start[:, None]
    # a padded chunk's other positions are not routed: no expert computes them
    real = (jnp.arange(t)[None, :] < lengths[:, None]).reshape(-1)
    window = window or cache["kv"].shape[2]
    new_rows = {name: [] for name in _STACKS}
    pairs, sets = jnp.int32(0), []
    for i, p in enumerate(params["layers"]):
        with jax.named_scope("ln"):
            a = _norm(x, p["norm"], cfg.eps)
        q, k_, v_ = _qkv(p, a, pos, cfg)
        kv_ = _kv_row(k_, v_, cache["kv"])
        with jax.named_scope("attn"):
            q_idx, idx_, w_idx = _indexer(p, a, pos, cfg)
            idx_ = idx_.astype(cache["idx"].dtype)
            attn, mask = sparse_chunk_attention(
                q, cache["kv"], cache["idx"], kv_, idx_, q_idx, w_idx, i,
                slots, start, lengths, window, cfg.index_topk)
        for name, rows in zip(_STACKS, (kv_, idx_)):
            new_rows[name].append(rows)
        sets.append(mask)
        x, c = _moe(p, (x + _attn_out(p, attn, cfg)).reshape(r * t, -1), cfg,
                    real)
        x = x.reshape(r, t, -1)
        pairs = pairs + jnp.sum(c, dtype=jnp.int32)
    new = {"counted": {"prefill_expert_rows":
                       cache["counted"]["prefill_expert_rows"] + pairs}}
    with jax.named_scope("cache_write"):
        for name in _STACKS:
            new[name] = cache_write_chunk(
                cache[name], jnp.stack(new_rows[name]), slots, start)
    return x, new, jnp.stack(sets)


def keye_vl2_chunk_with_sets(params: Params, cache: Params, tokens: jax.Array,
                             slots: jax.Array, start: jax.Array,
                             lengths: jax.Array, cfg: KeyeVL2Config,
                             window: int | None = None):
    """``keye_vl2_prefill_chunk`` and, behind its two results, what each
    layer's queries picked: [n_layer, R, C, window] bool over ring rows
    ``0 .. window - C`` (ring row = position) and then the chunk's own
    (``benchmark/tools/serve_check_sparse.py``)."""
    r, c = tokens.shape
    x, cache, sets = _rows(params, tokens, lengths, cfg, cache, slots, start,
                           window)
    last = x[jnp.arange(r), jnp.clip(lengths - 1, 0, c - 1)]
    return _head(last, params, cfg), cache, sets


def keye_vl2_layer_picks(p: Params, x: jax.Array, queries: jax.Array,
                         cfg: KeyeVL2Config) -> jax.Array:
    """What ONE layer's indexer picks for the queries at positions
    ``queries`` [R, Q] of rows whose stream ENTERING the layer is x
    [R, T, D] (positions ``0 .. T - 1``), by the programs' own arithmetic
    (the norm, the indexer's projections and rotation, its keys in the
    rings' type, float32 index scores, the exact selection) with no ring
    and no layer before it -> [R, Q, T] bool.
    ``benchmark/tools/serve_check_sparse.py`` feeds it the reference's
    stream, which holds the indexer's arithmetic apart from what the
    layers before it did to the stream."""
    r, t, _ = x.shape
    pos = jnp.broadcast_to(jnp.arange(t), (r, t))
    a = _norm(x.astype(cfg.dtype), p["norm"], cfg.eps)
    q_idx, k_idx, w_idx = _indexer(p, a, pos, cfg)
    rows = jnp.arange(r)[:, None]
    scores = index_scores(q_idx[rows, queries], w_idx[rows, queries],
                          k_idx.astype(cfg.dtype))
    sees = jnp.arange(t)[None, None, :] <= queries[:, :, None]
    return select_mask(sort_keys(scores, sees),
                       jnp.minimum(cfg.index_topk, queries + 1))


# jax-hot-path: traced into the engine's single compiled prefill program
def keye_vl2_prefill_chunk(params: Params, cache: Params, tokens: jax.Array,
                           slots: jax.Array, start: jax.Array,
                           lengths: jax.Array, cfg: KeyeVL2Config,
                           window: int | None = None
                           ) -> tuple[jax.Array, Params]:
    """A chunk of a prompt (fixed [R, C] shape; the contract of
    ``models/prefill.py``; ``window`` bounds the ring rows a chunk may
    see). Logits at the chunk's last real token."""
    return keye_vl2_chunk_with_sets(params, cache, tokens, slots, start,
                                    lengths, cfg, window)[:2]


def keye_vl2_prefill(params: Params, cache: Params, tokens: jax.Array,
                     slots: jax.Array, lengths: jax.Array,
                     cfg: KeyeVL2Config, chunk: int | None = None
                     ) -> tuple[jax.Array, Params]:
    """Whole padded prompts tokens [R, P] through ``keye_vl2_prefill_chunk``
    (``models/prefill.py``), in chunks of the rule's length. Logits at each
    prompt's last real token."""
    return whole_prompts(
        keye_vl2_prefill_chunk, params, cache, tokens, slots, lengths, cfg,
        chunk=chunk or chunk_len(tokens.shape[1],
                                 *token_parameters(cfg, params)))


def keye_vl2_forward(params: Params, tokens: jax.Array,
                     cfg: KeyeVL2Config) -> jax.Array:
    """Logits [R, T, V] float32 of whole rows, as ONE chunk over rings of
    their own (tests)."""
    r, t = tokens.shape
    x, _, _ = _rows(params, tokens, jnp.full((r,), t, jnp.int32), cfg,
                    keye_vl2_init_cache(cfg, r, t), jnp.arange(r),
                    jnp.zeros((r,), jnp.int32), t)
    return _head(x, params, cfg)
