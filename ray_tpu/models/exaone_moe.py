"""K-EXAONE (``model_type: exaone_moe``): three rotary WINDOW layers of 128
keys to one GLOBAL layer without a position embedding, QK-norm, each
sublayer's norm AFTER it, a sigmoid router over 128 experts beside one
shared expert behind a leading dense layer, and a multi-token-prediction
module that the engine serves as the model's own DRAFT: a decode step
verifies two rows a slot and yields one or two tokens.

``N(x; w) = x * rsqrt(mean(x^2) + eps) * w``; layer ``i`` is a window layer
where ``window_layout[i]`` is 1 and dense where ``i < dense_layers``:

  ``x0 = E[token]`` (the head is a table of its own)
  ``q = N_head(x Wq; q_norm)``, ``k = N_head(x Wk; k_norm)``, ``v = x Wv``:
         of the UN-NORMED stream, the norm over each head's lanes, grouped
         queries, no bias;
  window: q and k rotated over the whole head (``ops/rotary.py``); the query
         at position t sees keys ``t - window < p <= t``;
  global: nothing rotated; the query sees every key ``<= t``;
  ``h  = x + N(Wo softmax(q k^T / sqrt(hd)) v; norm_attn)``
  dense:  ``x = h + N(W_down (silu(a) * b); norm_ff)``, ``[a, b] = W_in h``
  sparse: ``x = h + N(sum_chosen w_e E_e(h) + S(h); norm_ff)``: ``s =
         sigmoid(h W_r)`` in float32, the ``top_k`` largest of ``s + bias``,
         ``w_e = routed_scale * s_e / sum_chosen s`` (``ops/moe.route``),
         ``E`` and ``S`` gated MLPs as the dense one; of the router's experts
         this chip holds ``experts_held`` (``ops/moe.dropless_experts``);
  ``logits = N(x; norm_f) W_head``.

The module (``mtp``; DeepSeek-V3's, whose key the config uses), for
position i: ``u_i = W_eh [N(x_i; norm_h) ; N(E[t_{i+1}]; norm_e)]`` of the
main stack's stream BEFORE ``norm_f``, one GLOBAL block of the kind above
with a DENSE feed-forward over ``u_0 .. u_i``, then ``N(.; norm_m) W_head``:
logits for ``t_{i+2}``. It changes speed only: what is served is the main
stack's greedy token.

``benchmark/reference/exaone_moe.py`` writes the equations out plainly; the
tests hold this file to it.

The cache is SmallThinker's two stacks of rings of merged rows at other
sizes: ``k_full / v_full [n_global + 1, slot, cache_len, W]``, the global
layers' rings and, LAST, the module's; ``k_win / v_win [n_window, slot,
window, W]``, rings of exactly ``window`` rows. A verify step reads every
ring as it was and scores its two new rows apart
(``ops/attention.cached_verify_attention``): the second row's place in a
wrapped ring still holds the key ``p - window + 1`` that the first row
needs, and is hidden from the second row only. Both rows are written after
the layer loop, accepted or not: a rejected row lies at the next step's
cursor (masked, then overwritten) or past its ``valid``, and the key it
displaced is one the next step no longer needs. So a draft is undone by
the position alone and nothing is snapshotted.

Seeded weights (``exaone_moe_init``) are drawn by ``cfg.gains``: see there.
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
# (whole rows from nothing, causal and within a window: the tests' form, the
# same for both families that keep window layers beside global ones)
from ray_tpu.models.smallthinker import _whole_row_attention
from ray_tpu.ops.attention import (cache_write_chunk, cache_write_ring_chunk,
                                   cache_write_token, cached_verify_attention,
                                   chunk_attention_arm, merged_chunk_attention,
                                   merged_row_width, ring_rows_counted,
                                   wrapped_chunk_attention)
from ray_tpu.ops.moe import dropless_experts, held_counters, route
from ray_tpu.ops.rotary import rotate

Params = dict[str, Any]

# How ``exaone_moe_init`` draws a matrix: normal at ``gain / sqrt(fan_in)``
# (``models/smallthinker.GAINS`` says why 0.02 throughout does not do). With
# every sublayer's norm AFTER it a branch arrives at rms 1 whatever its
# matrices' gains, so most gains are 1 and only three say anything: the
# router reads the un-normed stream, whose rms grows from 1 to about 3 over
# five layers, so its logits spread over 1 to 3 and a sigmoid's scores over
# most of (0, 1); a shared expert at 4 beside routed experts at 1 makes
# the one held expert a token takes on average (8 of 128 chosen, 16 held),
# at weight 2.5 / 8, about a thirteenth of the branch: a choice between
# the eighth and ninth expert that bfloat16 rounding turns (a sigmoid's
# chosen scores are nearly flat, so no draw of the router makes the ninth
# expert light) then moves a row well less than float8 weights do (at 2.5
# a turned choice read 0.042-0.054 against float8's 0.12: PERF.md section
# 6, PR 54); ``eh`` 1 leaves the module's stream at rms 1. Nothing a
# released checkpoint would need.
ROUTER_BIAS_STD = 0.002
GAINS = (("embed", 1.0), ("attn", 1.0), ("ff_in", 1.0), ("ff_down", 1.0),
         ("router", 1.0), ("expert_down", 1.0), ("shared_down", 4.0),
         ("eh", 1.0), ("head", 1.0))


@dataclasses.dataclass(frozen=True)
class ExaoneMoeConfig:
    vocab_size: int = 153600
    d_model: int = 6144
    # 1 a window layer (rotary, the last ``window`` keys), 0 a global one
    # (no position embedding, every key): the published L L L G, 12 periods
    window_layout: tuple = (1, 1, 1, 0) * 12
    dense_layers: int = 1            # first_k_dense_replace
    window: int = 128
    eps: float = 1e-5
    n_head: int = 64
    n_kv_head: int = 8
    head_dim: int = 128
    rope_theta: float = 1e6
    dense_ff: int = 18432
    # the router's width and experts a token, as published; of those this
    # chip holds ids experts_held[0] .. experts_held[1]
    n_experts: int = 128
    top_k: int = 8
    experts_held: tuple = (0, 128)
    expert_ff: int = 2048
    shared_ff: int = 2048            # num_shared_experts x expert width
    routed_scale: float = 2.5
    dtype: Any = jnp.bfloat16        # activations and matmuls
    param_dtype: Any = jnp.bfloat16  # as the checkpoint stores them
    gains: tuple = GAINS

    def __post_init__(self):
        object.__setattr__(self, "window_layout",
                           tuple(int(v) for v in self.window_layout))
        object.__setattr__(self, "experts_held",
                           tuple(int(v) for v in self.experts_held))
        object.__setattr__(self, "gains", tuple(
            (str(k), float(v)) for k, v in dict(self.gains).items()))
        if dict(self.gains).keys() != dict(GAINS).keys():
            raise ValueError(f"gains {self.gains}: want the keys "
                             f"{sorted(dict(GAINS))}")
        if not self.window_layout or set(self.window_layout) - {0, 1}:
            raise ValueError(f"window_layout {self.window_layout}: want a "
                             f"0 (global) or a 1 (window) a layer")
        if self.n_head % self.n_kv_head or self.head_dim % 2:
            raise ValueError("query heads must divide into K/V heads, and "
                             "a head's lanes into pairs")
        lo, hi = self.experts_held
        if self.window < 1 or not 1 <= self.top_k <= self.n_experts \
                or not 0 <= lo < hi <= self.n_experts \
                or not 0 <= self.dense_layers <= len(self.window_layout):
            raise ValueError("window and top_k must be at least 1, top_k at "
                             "most n_experts, experts_held a range of the "
                             "router's ids, dense_layers at most the depth")

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
    def n_held(self) -> int:
        return self.experts_held[1] - self.experts_held[0]

    @property
    def row_width(self) -> int:
        """Columns of a merged K or V row."""
        return merged_row_width(self.n_kv_head, self.head_dim)

    def serving_dtypes(self, params: Params) -> Params:
        """How an engine stores ``params``: as ``exaone_moe_init`` made
        them (see ``NemotronHConfig.serving_dtypes``)."""
        return jax.tree.map(lambda x: x.dtype, params)

    def serving_stats(self, chunk: int = 0, window: int = 0) -> dict:
        """What ``llm_stats()`` says of the model beside its counters
        (``SmallThinkerConfig.serving_stats``); the module's ring counts
        among the global ones."""
        row = 2 * self.row_width * jnp.dtype(self.dtype).itemsize
        return {
            "expert_layers": self.n_layer - self.dense_layers,
            "experts_held": self.n_held,
            "global_layers": self.n_global + 1,
            "window_layers": self.n_window,
            "window_rows": self.window,
            "kv_bytes_per_token": (self.n_global + 1) * row,
            "window_kv_bytes_per_token": self.n_window * row,
            "draft_depth": 1,
            "chunk_attention_arm": chunk_attention_arm(
                chunk, self.head_dim, self.row_width, window),
        }

    @classmethod
    def tiny(cls, **kw) -> "ExaoneMoeConfig":
        """The leading dense layer and one period and a layer more at a size
        a CPU test runs: a window of 8 rows, so that a toy prompt wraps the
        window rings several times, fewer K/V heads than query heads, and
        half of the router's experts held."""
        base = dict(
            vocab_size=256, d_model=48, window_layout=(1, 1, 1, 0, 1, 1),
            window=8, n_head=4, n_kv_head=2, head_dim=16, rope_theta=1e4,
            dense_ff=96, n_experts=8, top_k=3, experts_held=(0, 4),
            expert_ff=24, shared_ff=24)
        base.update(kw)
        return cls(**base)


# -- parameters ---------------------------------------------------------------


def _block_init(key, cfg: ExaoneMoeConfig, dense: bool) -> Params:
    """One block's matrices: attention with its two head norms, the two
    sublayer norms, and a dense or a sparse feed-forward part."""
    d, pd, g = cfg.d_model, cfg.param_dtype, dict(cfg.gains)
    keys = iter(jax.random.split(key, 12))
    q, kv = cfg.n_head * cfg.head_dim, cfg.n_kv_head * cfg.head_dim
    std = g["attn"] / d ** 0.5
    p = {
        "wq": _normal(next(keys), (d, q), std, pd),
        "wk": _normal(next(keys), (d, kv), std, pd),
        "wv": _normal(next(keys), (d, kv), std, pd),
        "wo": _normal(next(keys), (q, d), g["attn"] / q ** 0.5, pd),
        "q_norm": jnp.ones((cfg.head_dim,), pd),
        "k_norm": jnp.ones((cfg.head_dim,), pd),
        "norm_attn": jnp.ones((d,), pd), "norm_ff": jnp.ones((d,), pd),
    }
    w_in = g["ff_in"] / d ** 0.5
    if dense:
        # [a, b] = W_in h side by side: the gate's halves of one product
        p["w_in"] = _normal(next(keys), (d, 2 * cfg.dense_ff), w_in, pd)
        p["w_down"] = _normal(next(keys), (cfg.dense_ff, d),
                              g["ff_down"] / cfg.dense_ff ** 0.5, pd)
        return p
    p.update(
        router=_normal(next(keys), (d, cfg.n_experts),
                       g["router"] / d ** 0.5, pd),
        # the selection bias (``e_score_correction_bias``): it moves the
        # choice, not the weight. Small beside the SPACING of the scores
        # near the eighth (a sigmoid's top scores lie 0.005-0.01 apart): at
        # 0.05 it chose among the twenty best for every row alike, a step's
        # 130 rows hit 40-49 of the 64 held experts by the seed and the
        # step's time followed (PERF.md section 6, PR 54)
        router_bias=_normal(next(keys), (cfg.n_experts,), ROUTER_BIAS_STD,
                            pd),
        w1=_normal(next(keys), (cfg.n_held, d, 2 * cfg.expert_ff), w_in, pd),
        w2=_normal(next(keys), (cfg.n_held, cfg.expert_ff, d),
                   g["expert_down"] / cfg.expert_ff ** 0.5, pd),
        shared_w1=_normal(next(keys), (d, 2 * cfg.shared_ff), w_in, pd),
        shared_w2=_normal(next(keys), (cfg.shared_ff, d),
                          g["shared_down"] / cfg.shared_ff ** 0.5, pd))
    return p


def exaone_moe_init(rng: jax.Array, cfg: ExaoneMoeConfig) -> Params:
    """Seeded weights in ``cfg.param_dtype`` (bfloat16 as published), one
    dict a layer, every matrix normal at ``gain / sqrt(fan_in)``
    (``GAINS``), the norms at 1; the head a table of its own
    (``tie_word_embeddings`` false), stored [V, D] as the embedding; the
    module under ``mtp``: its two input norms, ``w_eh`` [2 D, D] (the
    stream's half first), one dense block and its last norm."""
    keys = jax.random.split(rng, cfg.n_layer + 4)
    pd, g, d = cfg.param_dtype, dict(cfg.gains), cfg.d_model
    return {
        "embed": _normal(keys[0], (cfg.vocab_size, d), g["embed"], pd),
        "layers": [_block_init(keys[4 + i], cfg, i < cfg.dense_layers)
                   for i in range(cfg.n_layer)],
        "norm_f": jnp.ones((d,), pd),
        "lm_head": _normal(keys[1], (cfg.vocab_size, d),
                           g["head"] / d ** 0.5, pd),
        "mtp": {
            "norm_h": jnp.ones((d,), pd), "norm_e": jnp.ones((d,), pd),
            "w_eh": _normal(keys[2], (2 * d, d), g["eh"] / (2 * d) ** 0.5,
                            pd),
            "block": _block_init(keys[3], cfg, dense=True),
            "norm_m": jnp.ones((d,), pd),
        },
    }


# -- the parts ----------------------------------------------------------------


def _swiglu(ab: jax.Array) -> jax.Array:
    """``silu(a) * b`` of ``[a, b]`` side by side in the last axis."""
    half = ab.shape[-1] // 2
    return jax.nn.silu(ab[..., :half]) * ab[..., half:]


def _qkv(p: Params, x: jax.Array, pos: jax.Array, windowed: int,
         cfg: ExaoneMoeConfig):
    """The attention layer's inputs of the UN-NORMED rows x [..., D] at
    positions pos [...]: q [..., H, hd], k and v [..., G, hd]; q and k
    normed over each head's lanes and, in a window layer, rotated."""
    dt_ = cfg.dtype
    lead = x.shape[:-1]
    with jax.named_scope("attn_proj"):
        q = (x @ p["wq"].astype(dt_)).reshape(*lead, cfg.n_head, cfg.head_dim)
        k = (x @ p["wk"].astype(dt_)).reshape(*lead, cfg.n_kv_head,
                                              cfg.head_dim)
        v = (x @ p["wv"].astype(dt_)).reshape(*lead, cfg.n_kv_head,
                                              cfg.head_dim)
    with jax.named_scope("ln"):
        q = _norm(q, p["q_norm"], cfg.eps)
        k = _norm(k, p["k_norm"], cfg.eps)
    if windowed:
        with jax.named_scope("rope"):
            q = rotate(q, pos, cfg.rope_theta)
            k = rotate(k, pos, cfg.rope_theta)
    return q, k, v


def _after_attention(p: Params, x: jax.Array, attn: jax.Array,
                     cfg: ExaoneMoeConfig) -> jax.Array:
    """``x + N(Wo attn; norm_attn)``: attn [..., H, hd], x [..., D]."""
    with jax.named_scope("attn_proj"):
        out = attn.reshape(*attn.shape[:-2], -1) @ p["wo"].astype(cfg.dtype)
    with jax.named_scope("ln"):
        return x + _norm(out, p["norm_attn"], cfg.eps)


def _feed_forward(p: Params, h: jax.Array, cfg: ExaoneMoeConfig,
                  live: jax.Array | None = None):
    """``h + N(FF(h); norm_ff)`` over rows h [T, D]: the dense gated MLP
    where ``p`` holds one, else the held experts' part of the routed sum
    plus the shared expert; rows that ``live`` [T] says are padding are
    routed nowhere. -> (the stream [T, D], the pairs each held expert took
    [E], or None for a dense block)."""
    dt_ = cfg.dtype
    if "w_in" in p:
        with jax.named_scope("mlp"):
            out = _swiglu(h @ p["w_in"].astype(dt_)) \
                @ p["w_down"].astype(dt_)
        counts = None
    else:
        with jax.named_scope("router"):
            ids, weights = route(h, p["router"], p["router_bias"],
                                 cfg.top_k, cfg.routed_scale)
        routed, counts = dropless_experts(
            h, ids, weights, p["w1"], p["w2"], first=cfg.experts_held[0],
            activation=_swiglu, live=live)
        with jax.named_scope("shared_expert"):
            shared = _swiglu(h @ p["shared_w1"].astype(dt_)) \
                @ p["shared_w2"].astype(dt_)
        with jax.named_scope("moe_combine"):
            out = (routed + shared.astype(jnp.float32)).astype(dt_)
    with jax.named_scope("ln"):
        return h + _norm(out, p["norm_ff"], cfg.eps), counts


def _head(x: jax.Array, norm: jax.Array, params: Params,
          cfg: ExaoneMoeConfig, scope: str = "head"):
    """``N(x; norm) W_head``, float32. x [..., D]."""
    with jax.named_scope("ln"):
        x = _norm(x, norm, cfg.eps)
    with jax.named_scope(scope):
        return jnp.einsum(
            "...d,vd->...v", x, params["lm_head"].astype(cfg.dtype),
            preferred_element_type=jnp.float32)


def _embed(params: Params, tokens: jax.Array, cfg: ExaoneMoeConfig):
    with jax.named_scope("embed"):
        return params["embed"].astype(cfg.dtype)[tokens]


def _module_input(params: Params, x: jax.Array, follows: jax.Array,
                  cfg: ExaoneMoeConfig) -> jax.Array:
    """``u = W_eh [N(x; norm_h) ; N(E[follows]; norm_e)]``: x [..., D] the
    main stack's stream before ``norm_f``, follows [...] the token AFTER
    each row's own."""
    m = params["mtp"]
    with jax.named_scope("ln"):
        both = jnp.concatenate(
            [_norm(x, m["norm_h"], cfg.eps),
             _norm(_embed(params, follows, cfg), m["norm_e"], cfg.eps)], -1)
    with jax.named_scope("mtp_proj"):
        return both @ m["w_eh"].astype(cfg.dtype)


# -- the cache and the serving functions --------------------------------------


def exaone_moe_init_cache(cfg: ExaoneMoeConfig, slots: int,
                          cache_len: int) -> Params:  # decode-path
    """Two stacks of K/V rings of merged rows in ONE pytree, which the
    engine donates: ``k_full / v_full [n_global + 1, slot, cache_len, W]``,
    the global layers' rings and last the module's, as long as a context
    may be, and ``k_win / v_win [n_window, slot, window, W]``, the window
    layers', exactly the window whatever ``cache_len`` is; and what the
    programs count (``counted``: int32 scalars, which wrap)."""
    w = cfg.row_width
    full = (cfg.n_global + 1, slots, cache_len, w)
    win = (cfg.n_window, slots, cfg.window, w)
    return {"k_full": jnp.zeros(full, cfg.dtype),
            "v_full": jnp.zeros(full, cfg.dtype),
            "k_win": jnp.zeros(win, cfg.dtype),
            "v_win": jnp.zeros(win, cfg.dtype),
            "counted": {"prefill_expert_rows": jnp.zeros((), jnp.int32)}}


_STACK = {0: "full", 1: "win"}  # a layer's kind -> its stack's suffix
_SCOPE = {0: "attn_global", 1: "attn_window"}


class _Rings:
    """A step's view of the cache: each stack's cursor and ``valid`` for
    the FIRST of a slot's new rows at ``pos``, and the new rows every layer
    leaves, written once a stack after the loops (``written``)."""

    def __init__(self, cache: Params, pos: jax.Array):
        self.cache = cache
        self.cursor, self.valid = {}, {}
        for kind, name in _STACK.items():
            n_rows = cache["k_" + name].shape[2]
            self.cursor[kind] = jnp.mod(pos, n_rows)
            self.valid[kind] = jnp.minimum(pos + 1, n_rows)
        self.k_rows = {0: [], 1: []}
        self.v_rows = {0: [], 1: []}

    def attend(self, p: Params, x: jax.Array, pos: jax.Array, kind: int,
               cfg: ExaoneMoeConfig) -> jax.Array:
        """One block's attention sublayer over rows x [S, R, D] at pos [S,
        R], the layer's ring being the next of its stack."""
        q, k_new, v_new = _qkv(p, x, pos, kind, cfg)
        k_all = self.cache["k_" + _STACK[kind]]
        v_all = self.cache["v_" + _STACK[kind]]
        k_new, v_new = _merged_row(k_new, k_all), _merged_row(v_new, v_all)
        with jax.named_scope("attn"), jax.named_scope(_SCOPE[kind]):
            attn = cached_verify_attention(
                q, k_all, v_all, k_new, v_new, self.cursor[kind],
                self.valid[kind], cfg.dtype, layer=len(self.k_rows[kind]))
        self.k_rows[kind].append(k_new)
        self.v_rows[kind].append(v_new)
        return _after_attention(p, x, attn, cfg)

    def written(self) -> Params:
        """The cache with every layer's new rows in it: row i of a slot at
        ``(cursor + i) mod`` its ring. A full stack that the caller left a
        ring of untouched (the module's, in an undrafted step) keeps it."""
        new = {"counted": self.cache["counted"]}
        with jax.named_scope("cache_write"):
            for kind, name in _STACK.items():
                for kv, rows in (("k_", self.k_rows), ("v_", self.v_rows)):
                    stack = self.cache[kv + name]
                    if rows[kind]:
                        stacked = jnp.stack(rows[kind])  # [n, S, R, W]
                        for i in range(stacked.shape[2]):
                            at = jnp.mod(self.cursor[kind] + i,
                                         stack.shape[2])
                            stack = cache_write_token(
                                stack, stacked[:, :, i], at)
                    new[kv + name] = stack
        return new


def _main_stack(params: Params, rings: _Rings, x: jax.Array, pos: jax.Array,
                cfg: ExaoneMoeConfig):
    """Rows x [S, R, D] at pos [S, R] through every layer over the rings as
    they were. -> (the stream before ``norm_f``, each sparse layer's
    counts)."""
    s, r, _ = x.shape
    counts = []
    for kind, p in zip(cfg.window_layout, params["layers"]):
        h = rings.attend(p, x, pos, kind, cfg)
        x, c = _feed_forward(p, h.reshape(s * r, -1), cfg)
        x = x.reshape(s, r, -1)
        if c is not None:
            counts.append(c)
    return x, counts


def _step_counters(rings: _Rings, counts: list) -> dict:
    cache = rings.cache
    full = ring_rows_counted(cache["k_full"], rings.valid[0])
    win = ring_rows_counted(cache["k_win"], rings.valid[1])
    return {**held_counters(counts),
            **{key: full[key] + win[key] for key in full},
            "window_rows_read": win["ring_rows_read"],
            "window_rows_held": win["ring_rows_held"]}


# jax-hot-path: traced into an engine's single compiled decode step
def exaone_moe_decode_step(params: Params, cache: Params, tokens: jax.Array,
                           pos: jax.Array, cfg: ExaoneMoeConfig
                           ) -> tuple[jax.Array, Params, dict]:
    """One UNDRAFTED decode iteration for every slot (``smallthinker_
    decode_step``'s contract): tokens [S] int32, pos [S] int32 -> (logits
    [S, V] fp32, new cache, counters). The module does not run and its ring
    is not written: what an engine without the sixth element of the bundle
    serves, and what the drafted engine's tokens are held to."""
    rings = _Rings(cache, pos)
    with jax.named_scope("verify"):
        x, counts = _main_stack(params, rings, _embed(params, tokens, cfg)[
            :, None], pos[:, None], cfg)
        logits = _head(x[:, 0], params["norm_f"], params, cfg)
    # the module's ring is the full stack's last: one row fewer is written
    return logits, rings.written(), _step_counters(rings, counts)


# jax-hot-path: traced into the engine's single compiled decode step
def exaone_moe_verify_step(params: Params, cache: Params, tokens: jax.Array,
                           pos: jax.Array, cfg: ExaoneMoeConfig
                           ) -> tuple[jax.Array, Params, dict, jax.Array]:
    """One verify-and-draft iteration for every slot: tokens [S, 2] int32,
    a slot's newest token ``t_p`` (not yet run) and its draft ``d_{p+1}``;
    pos [S] int32 the first's position. -> (logits [S, 2, V] fp32 of the
    main stack at ``p`` and ``p + 1``, new cache, counters as
    ``exaone_moe_decode_step``'s, served [S, 4] int32: how many tokens the
    slot yields (1 or 2), the tokens ``t_{p+1}`` and ``t_{p+2}`` (the main
    stack's greedy ones; the second means something where the count is 2)
    and the NEXT draft, ``d`` for the position after the last yielded
    token, and module logits [S, 2, V] fp32).

    The main stack runs both rows, the second causal on the first; the
    draft is accepted iff ``t_{p+1} == d_{p+1}`` (the device's comparison:
    nothing else decides). The module then runs rows ``(x_p, t_{p+1})`` and
    ``(x'_{p+1}, t_{p+2})``; the next draft is its first row's greedy token
    on a rejection and its second's on an acceptance. Every ring takes both
    rows; the next step's ``pos`` (``p + count``) says which count (the
    module docstring has why that undoes a rejected row). Every slot is
    computed, free ones and the scratch one too."""
    s = tokens.shape[0]
    rings = _Rings(cache, pos)
    pos2 = pos[:, None] + jnp.arange(2, dtype=pos.dtype)[None, :]
    with jax.named_scope("verify"):
        x, counts = _main_stack(params, rings,
                                _embed(params, tokens, cfg), pos2, cfg)
        logits = _head(x, params["norm_f"], params, cfg)
        with jax.named_scope("head"):
            greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [S, 2]
            accepted = greedy[:, 0] == tokens[:, 1]
    with jax.named_scope("mtp"):
        m = params["mtp"]
        u = _module_input(params, x, greedy, cfg)
        h = rings.attend(m["block"], u, pos2, 0, cfg)
        u, _ = _feed_forward(m["block"], h.reshape(s * 2, -1), cfg)
        draft_logits = _head(u.reshape(s, 2, -1), m["norm_m"], params, cfg,
                             scope="mtp_head")
        with jax.named_scope("mtp_head"):
            drafts = jnp.argmax(draft_logits, axis=-1).astype(jnp.int32)
    served = jnp.stack([
        1 + accepted.astype(jnp.int32), greedy[:, 0], greedy[:, 1],
        jnp.where(accepted, drafts[:, 1], drafts[:, 0])], axis=1)
    return (logits, rings.written(), _step_counters(rings, counts), served,
            draft_logits)


class _ChunkRings:
    """``_Rings`` for a prompt chunk: row r's C tokens at ``start[r] + i``
    in ``slots[r]``'s rings (``merged_chunk_attention`` over a global ring's
    first ``window`` rows, ``wrapped_chunk_attention`` over a window ring as
    it lies, a chunk being whole windows long), or whole rows from nothing
    where there is no cache."""

    def __init__(self, cache, slots, start, lengths, window):
        self.cache, self.slots, self.start = cache, slots, start
        self.lengths, self.window = lengths, window
        self.k_rows = {0: [], 1: []}
        self.v_rows = {0: [], 1: []}

    def attend(self, p, x, pos, kind, cfg):
        q, k_, v_ = _qkv(p, x, pos, kind, cfg)
        with jax.named_scope("attn"), jax.named_scope(_SCOPE[kind]):
            if self.cache is None:
                attn = _whole_row_attention(q, k_, v_,
                                            cfg.window if kind else None)
            else:
                k_all = self.cache["k_" + _STACK[kind]]
                v_all = self.cache["v_" + _STACK[kind]]
                k_, v_ = _merged_row(k_, k_all), _merged_row(v_, v_all)
                at = len(self.k_rows[kind])
                attn = wrapped_chunk_attention(
                    q, k_all, v_all, k_, v_, at, self.slots, self.start) \
                    if kind else merged_chunk_attention(
                        q, k_all, v_all, k_, v_, at, self.slots, self.start,
                        self.window)
                self.k_rows[kind].append(k_)
                self.v_rows[kind].append(v_)
        return _after_attention(p, x, attn, cfg)

    def written(self, pairs) -> Params:
        new = {"counted": {
            "prefill_expert_rows":
            self.cache["counted"]["prefill_expert_rows"] + pairs}}
        with jax.named_scope("cache_write"):
            for kv, rows in (("k_", self.k_rows), ("v_", self.v_rows)):
                new[kv + "full"] = cache_write_chunk(
                    self.cache[kv + "full"], jnp.stack(rows[0]), self.slots,
                    self.start)
                new[kv + "win"] = self.cache[kv + "win"] if not rows[1] \
                    else cache_write_ring_chunk(
                        self.cache[kv + "win"], jnp.stack(rows[1]),
                        self.slots, self.start, self.lengths)
        return new


def _rows(params: Params, tokens: jax.Array, lengths: jax.Array,
          cfg: ExaoneMoeConfig, cache: Params | None = None,
          slots: jax.Array | None = None, start: jax.Array | None = None,
          window: int | None = None, follows: jax.Array | None = None):
    """Rows of T tokens through the main stack and the module: tokens [R,
    T], lengths [R]; with a cache, row r is a chunk of a prompt at
    positions ``start[r] + i`` (``_ChunkRings``). ``follows`` [R]: the
    token that follows each row's last real one where the prompt goes on,
    negative where it ends here: the module's row i reads ``(x_i, t_{i+1})``
    and the last real row takes the main stack's own greedy token then.
    -> (main logits [R, V] at the last real token, module logits [R, V]
    there, hidden [R, T, D] before ``norm_f``, module hidden [R, T, D]
    before ``norm_m``, the cache)."""
    r, t = tokens.shape
    pos = jnp.arange(t)[None, :] + (0 if start is None else start[:, None])
    # a padded chunk's other positions are not routed: no expert computes them
    real = (jnp.arange(t)[None, :] < lengths[:, None]).reshape(-1)
    if cache is not None:
        window = window or cache["k_full"].shape[2]
    rings = _ChunkRings(cache, slots, start, lengths, window)
    pairs = jnp.int32(0)
    last = jnp.clip(lengths - 1, 0, t - 1)
    with jax.named_scope("verify"):
        x = _embed(params, tokens, cfg)
        for kind, p in zip(cfg.window_layout, params["layers"]):
            h = rings.attend(p, x, pos, kind, cfg)
            x, c = _feed_forward(p, h.reshape(r * t, -1), cfg, real)
            x = x.reshape(r, t, -1)
            if c is not None:
                pairs = pairs + jnp.sum(c, dtype=jnp.int32)
        logits = _head(x[jnp.arange(r), last], params["norm_f"], params, cfg)
    with jax.named_scope("mtp"):
        m = params["mtp"]
        sampled = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        ends = sampled if follows is None else jnp.where(
            follows < 0, sampled, follows)
        nxt = jnp.concatenate([tokens[:, 1:], tokens[:, :1]], axis=1)
        nxt = jnp.where(jnp.arange(t)[None, :] == last[:, None],
                        ends[:, None], nxt)
        u = _module_input(params, x, nxt, cfg)
        h = rings.attend(m["block"], u, pos, 0, cfg)
        u, _ = _feed_forward(m["block"], h.reshape(r * t, -1), cfg)
        u = u.reshape(r, t, -1)
        draft_logits = _head(u[jnp.arange(r), last], m["norm_m"], params,
                             cfg, scope="mtp_head")
    if cache is not None:
        cache = rings.written(pairs)
    return logits, draft_logits, x, u, cache


# jax-hot-path: traced into the engine's single compiled prefill program
def exaone_moe_prefill_chunk(params: Params, cache: Params,
                             tokens: jax.Array, slots: jax.Array,
                             start: jax.Array, lengths: jax.Array,
                             cfg: ExaoneMoeConfig,
                             window: int | None = None,
                             follows: jax.Array | None = None
                             ) -> tuple[jax.Array, Params, jax.Array]:
    """A chunk of a prompt (fixed [R, C] shape; the contract of
    ``models/prefill.py``; ``window`` bounds the GLOBAL rings' rows a chunk
    may see, the window rings are read whole), through the main stack AND
    the module, whose ring is filled with the rows ``(x_i, t_{i+1})``.
    ``follows`` [R] int32: the prompt's token after this chunk's last real
    one, negative (or no array) where the prompt ends in this chunk: the
    module's last row then takes the main stack's greedy token, as the
    first verify step will. -> (logits [R, V] at the chunk's last real
    token, the cache, the module's logits [R, V] there: the first draft)."""
    logits, draft_logits, _, _, cache = _rows(
        params, tokens, lengths, cfg, cache, slots, start, window, follows)
    return logits, cache, draft_logits


def exaone_moe_prefill(params: Params, cache: Params, tokens: jax.Array,
                       slots: jax.Array, lengths: jax.Array,
                       cfg: ExaoneMoeConfig, chunk: int | None = None):
    """Whole padded prompts tokens [R, P] through
    ``exaone_moe_prefill_chunk`` (``models/prefill.py``), in chunks of the
    rule's length or, where that is no whole number of windows, of one
    window. -> (logits [R, V] at each prompt's last real token, the cache,
    the module's logits [R, V] there)."""
    p_len = tokens.shape[1]
    c = chunk or chunk_len(p_len, *token_parameters(cfg, params))
    if c % cfg.window and cfg.window % c:
        c = min(c, cfg.window)
    padded = jnp.pad(tokens, ((0, 0), (0, c)))

    def chunk_fn(params, cache, piece, slots, start, lens, cfg, window=None):
        # the token after the chunk, where the prompt has one
        after = start + c
        follows = jnp.where(
            lengths > after,
            padded[jnp.arange(tokens.shape[0]), after], -1)
        logits, cache, drafts = exaone_moe_prefill_chunk(
            params, cache, piece, slots, start, lens, cfg, window, follows)
        return jnp.stack([logits, drafts]), cache

    both, cache = whole_prompts(chunk_fn, params, cache, tokens, slots,
                                lengths, cfg, chunk=c)
    return both[0], cache, both[1]


def exaone_moe_forward(params: Params, tokens: jax.Array,
                       cfg: ExaoneMoeConfig) -> tuple[jax.Array, jax.Array]:
    """(logits [R, T, V], the module's logits [R, T - 1, V]) float32 of
    whole rows, no cache (tests): the module's row i reads ``(x_i,
    tokens[i + 1])`` and predicts the token at ``i + 2``."""
    lengths = jnp.full((tokens.shape[0],), tokens.shape[1], jnp.int32)
    _, _, x, u, _ = _rows(params, tokens, lengths, cfg)
    return (_head(x, params["norm_f"], params, cfg),
            _head(u[:, :-1], params["mtp"]["norm_m"], params, cfg,
                  scope="mtp_head"))
