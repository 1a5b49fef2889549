"""SmallThinker (``model_name: smallthinker_*``): one GLOBAL attention layer
without a position embedding to three WINDOW layers with a rotary one, a
router that reads the layer's input before attention, and gated-ReLU
experts in EVERY layer, served through ``LLMEngine``.

Layer ``i`` is a window layer where ``window_layout[i]`` is 1 (the published
``sliding_window_layout``, which is its ``rope_layout`` too: a global layer
rotates nothing); ``N(x; w) = x * rsqrt(mean(x^2) + eps) * w``:

  ``x0 = E[token]`` (the head is a table of its own)
  ``r  = x W_r``: the router's float32 logits, of the UN-NORMED layer input,
         taken BEFORE attention and carried past it;
  ``a  = N(x; norm)``; ``q = a Wq``, ``k = a Wk``, ``v = a Wv``, grouped
         queries, no bias;
  window: q and k rotated over the whole head (``ops/rotary.py``); the
         query at position t sees keys ``t - window < p <= t``;
  global: nothing rotated; the query sees every key ``<= t``;
  ``h  = x + Wo softmax(q k^T / sqrt(hd)) v``; ``m = N(h; norm2)``
  ``x  = h + sum_chosen w_e W2_e (relu(a_e) * b_e)``, ``[a_e, b_e] = W1_e
         m``: a softmax over ALL experts, the ``top_k`` largest,
         renormalised over those (``ops/moe.route_topk_softmax``: a softmax over
         the chosen logits is that); no shared expert;
  ``logits = N(x; norm_f) W_head``.

``benchmark/reference/smallthinker.py`` writes the equations out plainly;
the tests hold this file to it.

The cache is TWO stacks of rings of MERGED rows (``ops/attention.py``'s rank
4: four K/V heads of 128 lanes are four whole lane tiles, no pad) of
different lengths in one pytree: ``k_full / v_full [n_global, slot,
cache_len, W]`` for the global layers, whose ring is as long as a context
may be, and ``k_win / v_win [n_window, slot, window, W]`` for the window
layers, whose ring IS the window: position p lies at row ``p mod window``,
keys rotated at their true positions, and a ring that has wrapped holds the
last ``window`` keys and nothing else. Each stack has its own cursor (``pos
mod its length``) and its own ``valid``. The decode step needs nothing a
straight ring does not: ``cached_decode_attention`` on a wrapped ring reads
``window - 1`` rows and the new token, the cursor's row (the key that just
left the window) masked. A prompt chunk over a wrapped ring is
``ops/attention.wrapped_chunk_attention``, masked by position. Both programs
read the rings as they were and write their new rows once a stack after the
layer loop. The stored types and the step's counters are as
``models/qwen3_next.py`` has them; every expert of the router is held.

Seeded weights (``smallthinker_init``) are drawn by ``cfg.gains``: see there.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from ray_tpu.models.common import (_normal,
                                   merged_row as _merged_row,
                                   rms_norm as _norm)
from ray_tpu.models.prefill import (chunk_len, token_parameters,
                                    whole_prompts)
from ray_tpu.ops.attention import (cache_write_chunk, cache_write_ring_chunk,
                                   cache_write_token, cached_decode_attention,
                                   chunk_attention_arm, merged_chunk_attention,
                                   merged_row_width, ring_rows_counted,
                                   wrapped_chunk_attention)
from ray_tpu.ops.moe import (dropless_experts, held_counters,
                             route_topk_softmax)
from ray_tpu.ops.rotary import rotate

Params = dict[str, Any]

# How ``smallthinker_init`` draws a matrix: normal at ``gain / sqrt(fan_in)``,
# so that ``gain`` is the rms of its output for an input of rms one,
# whatever the width (the embedding: ``gain`` itself, the stream's rms);
# ``models/qwen3_next.GAINS`` says why 0.02 throughout does not do. ``o`` is
# large because attention's output is a mean over hundreds or thousands of
# keys. The router reads the UN-NORMED stream, whose rms grows with depth, so
# its scores sharpen with depth as a released checkpoint's would; it is
# drawn peaked for the reason Qwen3-Next's is (a choice that bfloat16
# rounding turns must move little), but the routed branch is NOT light:
# it is the layer's only feed-forward part (no shared expert), and at half
# the gain a SiLU put for the ReLU went unseen. Nothing a released
# checkpoint would need.
GAINS = (("embed", 1.0), ("q", 1.0), ("k", 1.0), ("v", 1.0), ("o", 8.0),
         ("router", 2.0), ("expert_in", 1.0), ("expert_down", 2.0),
         ("head", 1.0))


@dataclasses.dataclass(frozen=True)
class SmallThinkerConfig:
    vocab_size: int = 151936
    d_model: int = 2560
    # 1 a window layer (rotary, the last ``window`` keys), 0 a global one
    # (no position embedding, every key): the published 1 : 3, 13 periods
    window_layout: tuple = (0, 1, 1, 1) * 13
    window: int = 4096
    eps: float = 1e-6
    n_head: int = 28
    n_kv_head: int = 4
    head_dim: int = 128
    rope_theta: float = 1.5e6
    # experts, in every layer, all held
    n_experts: int = 64
    top_k: int = 6
    expert_ff: int = 768
    dtype: Any = jnp.bfloat16        # activations and matmuls
    param_dtype: Any = jnp.bfloat16  # as the checkpoint stores them
    gains: tuple = GAINS

    def __post_init__(self):
        object.__setattr__(self, "window_layout",
                           tuple(int(v) for v in self.window_layout))
        object.__setattr__(self, "gains", tuple(
            (str(k), float(v)) for k, v in dict(self.gains).items()))
        if dict(self.gains).keys() != dict(GAINS).keys():
            raise ValueError(f"gains {self.gains}: want the keys "
                             f"{sorted(dict(GAINS))}")
        if not self.window_layout \
                or set(self.window_layout) - {0, 1}:
            raise ValueError(f"window_layout {self.window_layout}: want a "
                             f"0 (global) or a 1 (window) a layer")
        if self.n_head % self.n_kv_head or self.head_dim % 2:
            raise ValueError("query heads must divide into K/V heads, and "
                             "a head's lanes into pairs")
        if self.window < 1 or not 1 <= self.top_k <= self.n_experts:
            raise ValueError("window and top_k must be at least 1, and "
                             "top_k at most n_experts")

    @property
    def n_layer(self) -> int:
        return len(self.window_layout)

    @property
    def n_window(self) -> int:
        return sum(self.window_layout)

    @property
    def n_global(self) -> int:
        return self.n_layer - self.n_window

    @property
    def row_width(self) -> int:
        """Columns of a merged K or V row."""
        return merged_row_width(self.n_kv_head, self.head_dim)

    def serving_dtypes(self, params: Params) -> Params:
        """How an engine stores ``params``: as ``smallthinker_init`` made
        them (see ``NemotronHConfig.serving_dtypes``)."""
        return jax.tree.map(lambda x: x.dtype, params)

    def serving_stats(self, chunk: int = 0, window: int = 0) -> dict:
        """What ``llm_stats()`` says of the model beside its counters, so
        that a reader holds no shape of its own: the ring bytes a token
        takes in a global layer's ring and in a window layer's, how many
        rows a window ring holds, and which implementation a chunk program
        of ``chunk`` tokens over a key window of ``window`` rows (the
        engine's) attends through in the global layers
        (``ops/attention.chunk_attention_arm``: static, by shapes alone)."""
        row = 2 * self.row_width * jnp.dtype(self.dtype).itemsize
        return {
            "expert_layers": self.n_layer,
            "experts_held": self.n_experts,
            "global_layers": self.n_global,
            "window_layers": self.n_window,
            "window_rows": self.window,
            "kv_bytes_per_token": self.n_global * row,
            "window_kv_bytes_per_token": self.n_window * row,
            "chunk_attention_arm": chunk_attention_arm(
                chunk, self.head_dim, self.row_width, window),
        }

    @classmethod
    def tiny(cls, **kw) -> "SmallThinkerConfig":
        """Two periods at a size a CPU test runs: a window of 8 rows, so
        that a toy prompt wraps the window rings several times, fewer K/V
        heads than query heads, and fewer experts a token than experts."""
        base = dict(
            vocab_size=256, d_model=48, window_layout=(0, 1, 1, 1) * 2,
            window=8, n_head=4, n_kv_head=2, head_dim=16, rope_theta=1e4,
            n_experts=8, top_k=3, expert_ff=24)
        base.update(kw)
        return cls(**base)


# -- parameters ---------------------------------------------------------------


def init_stds(cfg: SmallThinkerConfig) -> dict:
    """The standard deviation each matrix is drawn at (``GAINS`` says
    why): ``gain / sqrt(fan_in)``."""
    g = dict(cfg.gains)
    d = cfg.d_model ** 0.5
    return {
        "embed": g["embed"], "wq": g["q"] / d, "wk": g["k"] / d,
        "wv": g["v"] / d,
        "wo": g["o"] / (cfg.n_head * cfg.head_dim) ** 0.5,
        "router": g["router"] / d, "w1": g["expert_in"] / d,
        "w2": g["expert_down"] / cfg.expert_ff ** 0.5,
        "lm_head": g["head"] / d,
    }


def _layer_init(key, cfg: SmallThinkerConfig, std: dict) -> Params:
    d, pd = cfg.d_model, cfg.param_dtype
    keys = iter(jax.random.split(key, 8))
    q, kv = cfg.n_head * cfg.head_dim, cfg.n_kv_head * cfg.head_dim
    return {
        "norm": jnp.ones((d,), pd), "norm2": jnp.ones((d,), pd),
        "wq": _normal(next(keys), (d, q), std["wq"], pd),
        "wk": _normal(next(keys), (d, kv), std["wk"], pd),
        "wv": _normal(next(keys), (d, kv), std["wv"], pd),
        "wo": _normal(next(keys), (q, d), std["wo"], pd),
        "router": _normal(next(keys), (d, cfg.n_experts), std["router"], pd),
        # [a, b] = W1 m side by side: the gate's halves of one product
        "w1": _normal(next(keys), (cfg.n_experts, d, 2 * cfg.expert_ff),
                      std["w1"], pd),
        "w2": _normal(next(keys), (cfg.n_experts, cfg.expert_ff, d),
                      std["w2"], pd),
    }


def smallthinker_init(rng: jax.Array, cfg: SmallThinkerConfig) -> Params:
    """Seeded weights in ``cfg.param_dtype`` (bfloat16 as published), one
    dict a layer (a global and a window layer hold the same matrices),
    every matrix normal at ``init_stds``'s value, the norms at 1. The head
    is a table of its own (``tie_word_embeddings`` false), stored [V, D] as
    the embedding."""
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


def _reglu(ab: jax.Array) -> jax.Array:
    """``relu(a) * b`` of ``[a, b]`` side by side in the last axis."""
    half = ab.shape[-1] // 2
    return jax.nn.relu(ab[..., :half]) * ab[..., half:]


def _route(p: Params, x: jax.Array, cfg: SmallThinkerConfig):
    """The router, read BEFORE attention: float32 logits of the layer's
    UN-NORMED input x [T, D], the ``top_k`` largest and the softmax over
    those. -> ids [T, K] int32, weights [T, K] float32, which the layer
    carries past its attention to its experts."""
    with jax.named_scope("router"):
        return route_topk_softmax(x, p["router"], cfg.top_k)


def _qkv(p: Params, a: jax.Array, pos: jax.Array, windowed: int,
         cfg: SmallThinkerConfig):
    """The attention layer's inputs of normed rows a [..., D] at positions
    pos [...]: q [..., H, hd], k and v [..., G, hd]; q and k rotated in a
    window layer, as they are in a global one."""
    dt_ = cfg.dtype
    lead = a.shape[:-1]
    with jax.named_scope("attn_proj"):
        q = (a @ p["wq"].astype(dt_)).reshape(*lead, cfg.n_head, cfg.head_dim)
        k = (a @ p["wk"].astype(dt_)).reshape(*lead, cfg.n_kv_head,
                                              cfg.head_dim)
        v = (a @ p["wv"].astype(dt_)).reshape(*lead, cfg.n_kv_head,
                                              cfg.head_dim)
    if windowed:
        with jax.named_scope("rope"):
            q = rotate(q, pos, cfg.rope_theta)
            k = rotate(k, pos, cfg.rope_theta)
    return q, k, v


def _attn_out(p: Params, attn: jax.Array, cfg: SmallThinkerConfig):
    """``Wo`` of attn [..., H, hd] -> [..., D]."""
    with jax.named_scope("attn_proj"):
        return attn.reshape(*attn.shape[:-2], -1) @ p["wo"].astype(cfg.dtype)


def _moe(p: Params, h: jax.Array, routed_to: tuple,
         cfg: SmallThinkerConfig, live: jax.Array | None = None):
    """``h + Routed(N(h; norm2))`` over rows h [T, D], to the experts and
    at the weights the layer's router chose from its input (``_route``);
    rows that ``live`` [T] says are padding are routed nowhere.
    -> (the stream [T, D], the pairs each expert took [E])."""
    ids, weights = routed_to
    with jax.named_scope("ln"):
        m = _norm(h, p["norm2"], cfg.eps)
    routed, counts = dropless_experts(
        m, ids, weights, p["w1"], p["w2"], first=0, activation=_reglu,
        live=live)
    with jax.named_scope("moe_combine"):
        out = (h.astype(jnp.float32) + routed).astype(cfg.dtype)
    return out, counts


def _head(x: jax.Array, params: Params, cfg: SmallThinkerConfig):
    """``N(x; norm_f) W_head``, float32. x [..., D]."""
    with jax.named_scope("ln"):
        x = _norm(x, params["norm_f"], cfg.eps)
    with jax.named_scope("head"):
        return jnp.einsum(
            "...d,vd->...v", x, params["lm_head"].astype(cfg.dtype),
            preferred_element_type=jnp.float32)


def _embed(params: Params, tokens: jax.Array, cfg: SmallThinkerConfig):
    with jax.named_scope("embed"):
        return params["embed"].astype(cfg.dtype)[tokens]


# -- the cache and the serving functions --------------------------------------


def smallthinker_init_cache(cfg: SmallThinkerConfig, slots: int,
                            cache_len: int) -> Params:  # decode-path
    """Two stacks of K/V rings of merged rows (``merged_row_width``, which
    both programs read as it lies) in ONE pytree, which the engine donates:
    ``k_full / v_full [n_global, slot, cache_len, W]``, the global layers'
    rings, as long as a context may be, and ``k_win / v_win [n_window, slot,
    window, W]``, the window layers', as long as the window and no longer
    whatever ``cache_len`` is; and what the programs count (``counted``:
    int32 scalars, which wrap)."""
    w = cfg.row_width
    full = (cfg.n_global, slots, cache_len, w)
    win = (cfg.n_window, slots, cfg.window, w)
    return {"k_full": jnp.zeros(full, cfg.dtype),
            "v_full": jnp.zeros(full, cfg.dtype),
            "k_win": jnp.zeros(win, cfg.dtype),
            "v_win": jnp.zeros(win, cfg.dtype),
            "counted": {"prefill_expert_rows": jnp.zeros((), jnp.int32)}}


_STACK = {0: "full", 1: "win"}  # a layer's kind -> its stack's suffix
_SCOPE = {0: "attn_global", 1: "attn_window"}


# jax-hot-path: traced into the engine's single compiled decode step
def smallthinker_decode_step(params: Params, cache: Params, tokens: jax.Array,
                             pos: jax.Array, cfg: SmallThinkerConfig
                             ) -> tuple[jax.Array, Params, dict]:
    """One decode iteration for every slot: tokens [S] int32, pos [S]
    int32 -> (logits [S, V] fp32, new cache, counters ``experts_hit`` and
    ``expert_rows`` over the step's layers, ``ring_rows_read`` and
    ``ring_rows_held`` over BOTH stacks (``ops/attention.ring_rows_counted``
    of each) and ``window_rows_read`` and ``window_rows_held``, the window
    stack's alone). Every row is computed, free slots and the scratch one
    too. Each stack keeps ``gpt2_decode_step``'s ring contract with its OWN
    cursor and ``valid`` (``pos`` mod, and up to, its own length): a window
    layer's wrapped ring is the window, the cursor's row being the key that
    has just left it. Rings of merged rows read as they were, every layer's
    new rows written once a stack after the loop."""
    dt_ = cfg.dtype
    cursor, valid = {}, {}
    for kind, name in _STACK.items():
        n_rows = cache["k_" + name].shape[2]
        cursor[kind] = jnp.mod(pos, n_rows)
        valid[kind] = jnp.minimum(pos + 1, n_rows)
    x = _embed(params, tokens, cfg)
    k_rows, v_rows = {0: [], 1: []}, {0: [], 1: []}
    counts = []
    for kind, p in zip(cfg.window_layout, params["layers"]):
        routed_to = _route(p, x, cfg)
        with jax.named_scope("ln"):
            a = _norm(x, p["norm"], cfg.eps)
        q, k_new, v_new = _qkv(p, a, pos, kind, cfg)
        k_all, v_all = cache["k_" + _STACK[kind]], cache["v_" + _STACK[kind]]
        k_new, v_new = _merged_row(k_new, k_all), _merged_row(v_new, v_all)
        with jax.named_scope("attn"), jax.named_scope(_SCOPE[kind]):
            attn = cached_decode_attention(
                q, k_all, v_all, k_new, v_new, cursor[kind], valid[kind],
                dt_, layer=len(k_rows[kind]))
        k_rows[kind].append(k_new)
        v_rows[kind].append(v_new)
        x, c = _moe(p, x + _attn_out(p, attn, cfg), routed_to, cfg)
        counts.append(c)
    new = {"counted": cache["counted"]}
    with jax.named_scope("cache_write"):
        for kind, name in _STACK.items():
            for kv, rows in (("k_", k_rows), ("v_", v_rows)):
                new[kv + name] = cache[kv + name] if not rows[kind] else \
                    cache_write_token(cache[kv + name],
                                      jnp.stack(rows[kind]), cursor[kind])
    full = ring_rows_counted(cache["k_full"], valid[0])
    win = ring_rows_counted(cache["k_win"], valid[1])
    return _head(x, params, cfg), new, {
        **held_counters(counts),
        **{key: full[key] + win[key] for key in full},
        "window_rows_read": win["ring_rows_read"],
        "window_rows_held": win["ring_rows_held"]}


def _rows(params: Params, tokens: jax.Array, lengths: jax.Array,
          cfg: SmallThinkerConfig, cache: Params | None = None,
          slots: jax.Array | None = None, start: jax.Array | None = None,
          window: int | None = None):
    """Rows of T tokens through every layer: tokens [R, T], lengths [R].
    Without a cache, whole rows from nothing, a window layer's keys masked
    by distance. With one, row r is a chunk of a prompt at positions
    ``start[r] + i``: a global layer reads ``slots[r]``'s rows ``< start``
    as earlier chunks left them and takes the chunk's own beside them
    (``merged_chunk_attention``, over the ``window`` rows the caller's
    longest prompt names); a window layer reads its ring as it lies,
    wrapped or not, by position (``wrapped_chunk_attention``). The chunk's
    rows are written once a stack after the loop: the global stack's at
    ``start``, the window stack's at ``start mod`` the ring, of which the
    rows past the chunk's real tokens keep what the ring held
    (``cache_write_ring_chunk`` says why). The token-expert pairs the
    experts took are added to the cache's ``prefill_expert_rows``.
    -> (hidden [R, T, D] before ``norm_f``, the cache)."""
    r, t = tokens.shape
    x = _embed(params, tokens, cfg)
    pos = jnp.arange(t)[None, :] + (0 if start is None else start[:, None])
    # a padded chunk's other positions are not routed: no expert computes them
    real = (jnp.arange(t)[None, :] < lengths[:, None]).reshape(-1)
    k_rows, v_rows = {0: [], 1: []}, {0: [], 1: []}
    if cache is not None:
        window = window or cache["k_full"].shape[2]
    pairs = jnp.int32(0)
    for kind, p in zip(cfg.window_layout, params["layers"]):
        routed_to = _route(p, x.reshape(r * t, -1), cfg)
        with jax.named_scope("ln"):
            a = _norm(x, p["norm"], cfg.eps)
        q, k_, v_ = _qkv(p, a, pos, kind, cfg)
        with jax.named_scope("attn"), jax.named_scope(_SCOPE[kind]):
            if cache is None:
                attn = _whole_row_attention(q, k_, v_,
                                            cfg.window if kind else None)
            else:
                k_all = cache["k_" + _STACK[kind]]
                v_all = cache["v_" + _STACK[kind]]
                k_, v_ = _merged_row(k_, k_all), _merged_row(v_, v_all)
                at = len(k_rows[kind])
                attn = wrapped_chunk_attention(
                    q, k_all, v_all, k_, v_, at, slots, start) if kind else \
                    merged_chunk_attention(
                        q, k_all, v_all, k_, v_, at, slots, start, window)
                k_rows[kind].append(k_)
                v_rows[kind].append(v_)
        x, c = _moe(p, (x + _attn_out(p, attn, cfg)).reshape(r * t, -1),
                    routed_to, cfg, real)
        x = x.reshape(r, t, -1)
        pairs = pairs + jnp.sum(c, dtype=jnp.int32)
    if cache is not None:
        new = {"counted": {
            "prefill_expert_rows":
            cache["counted"]["prefill_expert_rows"] + pairs}}
        with jax.named_scope("cache_write"):
            for kv, rows in (("k_", k_rows), ("v_", v_rows)):
                new[kv + "full"] = cache[kv + "full"] if not rows[0] else \
                    cache_write_chunk(cache[kv + "full"], jnp.stack(rows[0]),
                                      slots, start)
                new[kv + "win"] = cache[kv + "win"] if not rows[1] else \
                    cache_write_ring_chunk(cache[kv + "win"],
                                           jnp.stack(rows[1]), slots, start,
                                           lengths)
        cache = new
    return x, cache


def _whole_row_attention(q, k, v, window: int | None):
    """Whole rows from nothing (no cache; tests): q [R, T, H, hd], k / v
    [R, T, G, hd], causal, and within ``window`` keys of the query with its
    own where one is given."""
    rep = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    t, hd = q.shape[1], q.shape[-1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * hd ** -0.5
    dist = jnp.arange(t)[:, None] - jnp.arange(t)[None, :]
    seen = dist >= 0 if window is None else (dist >= 0) & (dist < window)
    probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf),
                           axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


# jax-hot-path: traced into the engine's single compiled prefill program
def smallthinker_prefill_chunk(params: Params, cache: Params,
                               tokens: jax.Array, slots: jax.Array,
                               start: jax.Array, lengths: jax.Array,
                               cfg: SmallThinkerConfig,
                               window: int | None = None
                               ) -> tuple[jax.Array, Params]:
    """A chunk of a prompt (fixed [R, C] shape; the contract of
    ``models/prefill.py``; ``window`` bounds the GLOBAL rings' rows a chunk
    may see, the window rings are read whole). Logits at the chunk's last
    real token."""
    r, c = tokens.shape
    x, cache = _rows(params, tokens, lengths, cfg, cache, slots, start,
                     window)
    last = x[jnp.arange(r), jnp.clip(lengths - 1, 0, c - 1)]
    return _head(last, params, cfg), cache


def smallthinker_prefill(params: Params, cache: Params, tokens: jax.Array,
                         slots: jax.Array, lengths: jax.Array,
                         cfg: SmallThinkerConfig, chunk: int | None = None
                         ) -> tuple[jax.Array, Params]:
    """Whole padded prompts tokens [R, P] through
    ``smallthinker_prefill_chunk`` (``models/prefill.py``), in chunks of
    the rule's length or of one window where that is shorter (a chunk has
    to divide a window ring). Logits at each prompt's last real token."""
    return whole_prompts(
        smallthinker_prefill_chunk, params, cache, tokens, slots, lengths,
        cfg, chunk=chunk or min(chunk_len(
            tokens.shape[1], *token_parameters(cfg, params)), cfg.window))


def smallthinker_forward(params: Params, tokens: jax.Array,
                         cfg: SmallThinkerConfig) -> jax.Array:
    """Logits [R, T, V] float32 of whole rows, no cache (tests)."""
    lengths = jnp.full((tokens.shape[0],), tokens.shape[1], jnp.int32)
    x, _ = _rows(params, tokens, lengths, cfg)
    return _head(x, params, cfg)
