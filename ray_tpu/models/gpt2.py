"""GPT-2 in pure JAX, sharding-annotated, scan-over-layers, remat-able.

This is the flagship training workload: the benchmark's cells
``train_gpt2s_1chip`` and ``train_gpt2xl_4chip`` (``BENCHMARK.json``; what
they measured is in ``PERF_LEDGER.jsonl``). Design choices for TPU:

* Parameters are a plain pytree of arrays plus a parallel pytree of *logical
  axis names* (``gpt2_param_axes``); physical shardings come from
  ``ray_tpu.parallel.sharding`` rules — Megatron TP on mlp/heads/vocab dims,
  ZeRO-3 (fsdp) on the embed dim, pp over the stacked layer dim.
* Transformer blocks are **stacked** ([n_layer, ...] leaves) and iterated
  with `lax.scan` => O(1) compile time in depth, and the block body is
  `jax.checkpoint`-ed so activations are rematerialized in backward
  (HBM-for-FLOPs trade, SURVEY.md §"HBM bandwidth").
* Compute in bf16 (MXU-native), params + optimizer state in fp32, softmax
  and loss in fp32.

Reference parity note: the reference trains GPT-2 through torch DDP wrapped
in Ray Train (``release/air_tests/air_benchmarks``); here the model is owned
by the framework and compiled as one pjit program.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp

from ray_tpu.ops.attention import causal_attention
from ray_tpu.parallel.sharding import logical_sharding, with_logical_constraint

Params = dict[str, Any]


@dataclasses.dataclass(frozen=True)
class GPT2Config:
    vocab_size: int = 50304  # 50257 padded to a multiple of 128 for the MXU
    n_layer: int = 12
    n_head: int = 12
    d_model: int = 768
    seq_len: int = 1024
    dtype: Any = jnp.bfloat16  # activation/compute dtype
    param_dtype: Any = jnp.float32
    # Rematerialization of the block body in backward (HBM-for-FLOPs):
    #   True   — full remat (lowest memory, recomputes the whole forward);
    #   "dots" — selective: save matmul outputs, recompute elementwise only
    #            (jax.checkpoint_policies.dots_with_no_batch_dims_saveable);
    #   False  — save everything (needs flash attention to fit at seq 1024).
    remat: Any = True
    # Iterate the stacked blocks with lax.scan (O(1) compile time in depth)
    # or a Python loop (unrolled: XLA schedules across layer boundaries —
    # measured ~25% faster fwd+bwd on v5e at 12 layers, at higher compile
    # cost; use for the single-slice training hot path).
    scan_layers: bool = True
    use_flash: bool | None = None  # None = auto by seq_len/backend
    # Attention parallelism: "auto" (GSPMD-partitioned dense/flash),
    # "ring" (sp-axis ring attention, ppermute KV), or "ulysses"
    # (sp-axis all_to_all head scatter). ring/ulysses need ``mesh``.
    attention_impl: str = "auto"
    # LM-head matmul output dtype (MaxText-style). None = fp32 logits
    # (stable default). jnp.bfloat16 doubles the head matmul rate on the
    # MXU (measured 59 -> ~120 TF/s for fp32- vs bf16-out on v5e) and
    # halves logits HBM traffic; CE reductions still accumulate in fp32.
    logits_dtype: Any = None
    # Fused Pallas norm/residual/GELU kernels (ops/fused_norm.py): the
    # LayerNorm forward saves only fp32 mean/rstd, and ONE backward
    # kernel per row-block fuses dx/dscale/dbias with the residual-add
    # gradient, so the fp32 LN recompute chain XLA materializes
    # (~15ms/step in an early profile) never reaches HBM. The MLP GELU
    # rides a fused tanh backward epilogue. Shapes the TPU lane layout
    # can't tile (D % 128 != 0) fall back to the plain-XLA chain.
    fused_norm: bool = False
    # Cross-entropy over vocab chunks (>1 enables): the loss runs an
    # online-logsumexp lax.scan over [V/n, D] slices of the tied head so
    # the full [B, T, V] logits tensor is NEVER materialized — fwd or
    # bwd (per-chunk remat recomputes chunk logits in backward). Cuts
    # the loss-path HBM footprint by n_chunks x, unblocking larger
    # batches (fp32 [16,1024,50304] logits forced spills at
    # batch >= 24). Must divide vocab_size.
    ce_vocab_chunks: int = 1
    mesh: Any = dataclasses.field(default=None, compare=False)

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_head

    @property
    def n_params(self) -> int:
        """Parameter count (tied embeddings)."""
        d, l, v, s = self.d_model, self.n_layer, self.vocab_size, self.seq_len
        per_layer = 12 * d * d + 13 * d  # qkv+proj+mlp weights & biases + 2 LN
        return v * d + s * d + l * per_layer + 2 * d

    def serving_dtypes(self, params: Params) -> Params:
        """For each leaf of ``params``, the type in which ``gpt2_prefill``
        and ``gpt2_decode_step`` consume it, as a tree like ``params``:
        what an engine stores (``_SERVED_IN_DTYPE``, beside the programs).
        Training keeps ``param_dtype``: the optimizer needs the master."""
        dt = jnp.dtype(self.dtype)
        return jax.tree_util.tree_map_with_path(
            lambda path, x: dt if path[-1].key in _SERVED_IN_DTYPE
            else x.dtype, params)

    @classmethod
    def small(cls) -> "GPT2Config":
        return cls()  # 124M

    @classmethod
    def medium(cls) -> "GPT2Config":
        return cls(n_layer=24, n_head=16, d_model=1024)

    @classmethod
    def tiny(cls) -> "GPT2Config":
        """CPU-test sized."""
        return cls(vocab_size=256, n_layer=2, n_head=4, d_model=64, seq_len=64)


def gpt2_param_axes(cfg: GPT2Config) -> Params:
    """Logical axis names for every param leaf (same tree structure)."""
    return {
        "wte": ("vocab", "embed"),
        "wpe": (None, "embed"),
        "blocks": {
            # leading dim is the stacked layer dim
            "ln1_scale": ("layers", None),
            "ln1_bias": ("layers", None),
            "attn_qkv_w": ("layers", "embed", "qkv"),
            "attn_qkv_b": ("layers", "qkv"),
            "attn_out_w": ("layers", "qkv", "embed"),
            "attn_out_b": ("layers", None),
            "ln2_scale": ("layers", None),
            "ln2_bias": ("layers", None),
            "mlp_in_w": ("layers", "embed", "mlp"),
            "mlp_in_b": ("layers", "mlp"),
            "mlp_out_w": ("layers", "mlp", "embed"),
            "mlp_out_b": ("layers", None),
        },
        "lnf_scale": (None,),
        "lnf_bias": (None,),
    }


def gpt2_shardings(cfg: GPT2Config, mesh, rules=None) -> Params:
    return jax.tree.map(
        lambda axes: logical_sharding(mesh, axes, rules),
        gpt2_param_axes(cfg),
        is_leaf=lambda x: isinstance(x, tuple),
    )


def gpt2_init(rng: jax.Array, cfg: GPT2Config) -> Params:
    """GPT-2 init: normal(0.02), residual projections scaled by 1/sqrt(2L)."""
    d, l, v, s = cfg.d_model, cfg.n_layer, cfg.vocab_size, cfg.seq_len
    pd = cfg.param_dtype
    k = iter(jax.random.split(rng, 8))
    std = 0.02
    resid_std = std / math.sqrt(2 * l)

    def norm(key, shape, stddev):
        return (jax.random.normal(key, shape, jnp.float32) * stddev).astype(pd)

    return {
        "wte": norm(next(k), (v, d), std),
        "wpe": norm(next(k), (s, d), std),
        "blocks": {
            "ln1_scale": jnp.ones((l, d), pd),
            "ln1_bias": jnp.zeros((l, d), pd),
            "attn_qkv_w": norm(next(k), (l, d, 3 * d), std),
            "attn_qkv_b": jnp.zeros((l, 3 * d), pd),
            "attn_out_w": norm(next(k), (l, d, d), resid_std),
            "attn_out_b": jnp.zeros((l, d), pd),
            "ln2_scale": jnp.ones((l, d), pd),
            "ln2_bias": jnp.zeros((l, d), pd),
            "mlp_in_w": norm(next(k), (l, d, 4 * d), std),
            "mlp_in_b": jnp.zeros((l, 4 * d), pd),
            "mlp_out_w": norm(next(k), (l, 4 * d, d), resid_std),
            "mlp_out_b": jnp.zeros((l, d), pd),
        },
        "lnf_scale": jnp.ones((d,), pd),
        "lnf_bias": jnp.zeros((d,), pd),
    }


def _layer_norm(x: jax.Array, scale: jax.Array, bias: jax.Array) -> jax.Array:
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + 1e-5)
    return (y * scale + bias).astype(x.dtype)


def _norm_residual(x: jax.Array, scale: jax.Array, bias: jax.Array,
                   cfg: GPT2Config) -> tuple[jax.Array, jax.Array]:
    """(LN(x), residual-skip x). With ``cfg.fused_norm`` the skip rides
    through the fused op so the residual-add gradient lands inside the
    one Pallas backward kernel."""
    if cfg.fused_norm:
        from ray_tpu.ops.fused_norm import fused_layer_norm_residual

        return fused_layer_norm_residual(x, scale, bias)
    return _layer_norm(x, scale, bias), x


def _block(x: jax.Array, p: Params, cfg: GPT2Config) -> jax.Array:
    """One transformer block. x: [B, T, D] in cfg.dtype."""
    b, t, d = x.shape
    h, hd = cfg.n_head, cfg.head_dim
    dt = cfg.dtype

    with jax.named_scope("ln"):
        y, x_skip = _norm_residual(x, p["ln1_scale"], p["ln1_bias"], cfg)
    with jax.named_scope("attn_proj"):
        qkv = y @ p["attn_qkv_w"].astype(dt) + p["attn_qkv_b"].astype(dt)
        q, k_, v_ = jnp.split(qkv, 3, axis=-1)
        q = q.reshape(b, t, h, hd)
        k_ = k_.reshape(b, t, h, hd)
        v_ = v_.reshape(b, t, h, hd)
    with jax.named_scope("attn"):
        if cfg.attention_impl == "ring" and cfg.mesh is not None:
            from ray_tpu.ops.ring_attention import ring_causal_attention

            attn = ring_causal_attention(q, k_, v_, cfg.mesh, axis="sp")
        elif cfg.attention_impl == "ulysses" and cfg.mesh is not None:
            from ray_tpu.ops.ulysses import ulysses_attention

            attn = ulysses_attention(q, k_, v_, cfg.mesh, axis="sp")
        else:
            attn = causal_attention(q, k_, v_, use_flash=cfg.use_flash)
    with jax.named_scope("attn_proj"):
        attn = attn.reshape(b, t, d)
        x = x_skip + attn @ p["attn_out_w"].astype(dt) \
            + p["attn_out_b"].astype(dt)
    x = with_logical_constraint(x, ("batch", "seq", None))

    with jax.named_scope("ln"):
        y, x_skip = _norm_residual(x, p["ln2_scale"], p["ln2_bias"], cfg)
    with jax.named_scope("mlp"):
        y = y @ p["mlp_in_w"].astype(dt) + p["mlp_in_b"].astype(dt)
        y = with_logical_constraint(y, ("batch", "seq", "mlp"))
        if cfg.fused_norm:
            from ray_tpu.ops.fused_norm import fused_gelu

            y = fused_gelu(y)
        else:
            y = jax.nn.gelu(y, approximate=True)
        x = x_skip + y @ p["mlp_out_w"].astype(dt) \
            + p["mlp_out_b"].astype(dt)
    x = with_logical_constraint(x, ("batch", "seq", None))
    return x


def gpt2_hidden(params: Params, tokens: jax.Array, cfg: GPT2Config) -> jax.Array:
    """tokens [B, T] int32 -> final-layernormed hidden states [B, T, D]."""
    _, t = tokens.shape
    dt = cfg.dtype
    with jax.named_scope("embed"):
        x = params["wte"].astype(dt)[tokens] + params["wpe"].astype(dt)[:t]
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
            x, _ = block_fn(
                x, jax.tree.map(lambda a: a[i], params["blocks"])
            )

    with jax.named_scope("ln"):
        if cfg.fused_norm:
            from ray_tpu.ops.fused_norm import fused_layer_norm

            return fused_layer_norm(
                x, params["lnf_scale"], params["lnf_bias"])
        return _layer_norm(x, params["lnf_scale"], params["lnf_bias"])


def _head_dtype(cfg: GPT2Config):
    return cfg.logits_dtype if cfg.logits_dtype is not None else jnp.float32


def gpt2_forward(params: Params, tokens: jax.Array, cfg: GPT2Config) -> jax.Array:
    """tokens [B, T] int32 -> logits [B, T, V] (fp32 unless cfg.logits_dtype)."""
    x = gpt2_hidden(params, tokens, cfg)
    # Tied LM head; fp32 logits by default for a stable loss.
    with jax.named_scope("head"):
        return _tied_head(x, params["wte"], cfg)


def _tied_head(x: jax.Array, wte: jax.Array, cfg: GPT2Config) -> jax.Array:
    return jnp.einsum(
        "btd,vd->btv", x, wte.astype(cfg.dtype),
        preferred_element_type=_head_dtype(cfg),
    )


def _chunked_ce(x: jax.Array, wte: jax.Array, targets: jax.Array,
                cfg: GPT2Config) -> jax.Array:
    """Online-logsumexp cross-entropy over vocab chunks.

    The head matmul + reductions run chunk-at-a-time under ``lax.scan``
    with per-chunk remat, so peak logits memory is [B, T, V/n] in both
    forward AND backward (the reference analog materializes the full
    fp32 [B, T, V] twice; cf. flash attention's online-softmax trick,
    applied to the vocab axis)."""
    n = cfg.ce_vocab_chunks
    v, d = wte.shape
    if v % n:
        raise ValueError(f"ce_vocab_chunks={n} must divide vocab_size={v}")
    vc = v // n
    w_chunks = wte.reshape(n, vc, d).astype(cfg.dtype)
    bases = jnp.arange(n, dtype=targets.dtype) * vc

    def body(carry, inp):
        m, s, picked = carry
        wc, base = inp
        logits = jnp.einsum(
            "btd,vd->btv", x, wc, preferred_element_type=_head_dtype(cfg)
        ).astype(jnp.float32)
        cmax = logits.max(axis=-1)
        new_m = jnp.maximum(m, cmax)
        s = s * jnp.exp(m - new_m) + jnp.exp(
            logits - new_m[..., None]).sum(axis=-1)
        idx = jnp.clip(targets - base, 0, vc - 1)
        p = jnp.take_along_axis(logits, idx[..., None], axis=-1)[..., 0]
        picked = jnp.where((targets >= base) & (targets < base + vc),
                           p, picked)
        return (new_m, s, picked), None

    bt = targets.shape
    init = (
        jnp.full(bt, -jnp.inf, jnp.float32),   # running max
        jnp.zeros(bt, jnp.float32),            # running sum(exp(l - max))
        jnp.zeros(bt, jnp.float32),            # picked target logit
    )
    (m, s, picked), _ = jax.lax.scan(
        jax.checkpoint(body, prevent_cse=False), init, (w_chunks, bases))
    return jnp.mean(m + jnp.log(s) - picked)


def gpt2_loss(params: Params, batch: dict[str, jax.Array], cfg: GPT2Config) -> jax.Array:
    """Next-token cross-entropy. batch: {'tokens': [B, T+1] or [B, T] int32}.

    If only [B, T] is given, inputs are tokens[:, :-1], targets tokens[:, 1:].
    """
    tokens = batch["tokens"]
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    x = gpt2_hidden(params, inputs, cfg)
    with jax.named_scope("head_loss"):
        if cfg.ce_vocab_chunks > 1:
            return _chunked_ce(x, params["wte"], targets, cfg)
        # CE via logsumexp - picked logit: one reduction pass over
        # [B,T,V] instead of materializing log_softmax (measured ~2x
        # faster fwd on v5e at V=50k; the softmax only appears in the
        # backward). The reductions run in fp32 even when
        # cfg.logits_dtype is bf16.
        logits = _tied_head(x, params["wte"], cfg).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(
            logits, targets[..., None], axis=-1)[..., 0]
        return jnp.mean(lse - picked)


# -- autoregressive decoding (serving path) --------------------------------
#
# The serving engine (``ray_tpu/serve/llm_engine.py``) owns ONE jitted
# decode step over a fixed ``[max_batch, ...]`` state and admits requests
# between steps, so these functions are shape-stable by construction:
#
# * ``gpt2_init_cache``   — slot-indexed ring KV-cache in device memory,
#   ``[n_layer, slots, cache_len, W]`` in the activation dtype (bf16 by
#   default — no fp32 cache copy ever materializes): a token's row is its
#   heads MERGED and padded with zero columns to whole 128-lane tiles
#   (``ops/attention.merged_row_width``; XL: 1600 -> 1664), because only
#   then does the TPU's compiler keep a row contiguous, and a row written
#   is 13 tiles and not 4,800 (PR 42);
# * ``gpt2_prefill_chunk`` — the second jitted shape: ``[rows, C]`` tokens
#   of a prompt at a start offset, attending over what earlier chunks left
#   in the slot's cache rows and over the chunk's own K/V, writing those
#   rows there, and giving the logits of the chunk's last real position (the
#   last chunk's sample the FIRST token); ``gpt2_prefill`` is whole prompts
#   through it;
# * ``gpt2_decode_step``  — one token for every slot: attend over the
#   valid cache window and this token's own K/V, next-token logits, and
#   this token's K/V written at the slot's ring cursor.
#
# The stacked cache never travels through the layer loop as ``lax.scan``'s
# ``xs -> ys``; a step writes only its new rows, in place in the buffer
# the engine donates (the contract and its reasons: ``ops/attention.py``).
# BOTH programs read the cache as it was and write after the layer loop,
# which is what lets the compiler keep the row-minor layout from the entry
# through the loop to the donated output in both, so that neither re-lays
# the shared buffer out (``tests/test_serving_programs_v5e.py`` reads what
# the TPU's compiler makes of each):
#
# * decode's loop runs over the layer's index with the stacked cache
#   closed over, read only (a layer's block as the scan's ``xs`` would be
#   copied out for the attention's kernel), the loop's ``ys`` are only the
#   new rows ``[n_layer, S, W]``, and one ``cache_write`` after the loop
#   puts them at the cursors; attention takes the stack, the layer's index
#   and the new token's score and value beside the old window
#   (``cached_decode_attention``), so it is the same softmax over the same
#   keys as a write-then-read.
# * a prefill chunk's loop cuts the slot's rows ``< start`` (a window, not
#   a layer's block) out of the stack, takes the chunk's own ``[C, W]``
#   rows beside them, causal among themselves, in one softmax over both
#   (``merged_chunk_attention``), hands the rows out as ``ys`` ``[n_layer,
#   R, C, W]``, and one ``cache_write`` after the loop puts the blocks at
#   ``start``. (A loop that wrote the rows into the cache it carried and
#   then cut the window out of the same carry made the compiler re-lay the
#   whole cache out, four cache-sized copies a chunk.)
#
# Ring semantics: the write cursor is ``pos % cache_len`` and the
# attention mask covers ``min(pos + 1, cache_len)`` entries — a
# generation longer than the cache degrades to sliding-window attention
# instead of erroring. Positions (wpe rows) use the absolute position,
# clamped to ``seq_len``.


def gpt2_init_cache(cfg: GPT2Config, slots: int, cache_len: int) -> Params:  # decode-path
    """Ring KV-cache for ``slots`` concurrent sequences (bf16 by default:
    the cache rides ``cfg.dtype``, never fp32), a token's row merged and
    lane-padded; the pad columns are zeros and stay zeros."""
    from ray_tpu.ops.attention import merged_row_width

    shape = (cfg.n_layer, slots, cache_len,
             merged_row_width(cfg.n_head, cfg.head_dim))
    return {"k": jnp.zeros(shape, cfg.dtype), "v": jnp.zeros(shape, cfg.dtype)}


# What an engine stores in ``cfg.dtype`` (``GPT2Config.serving_dtypes``): the
# leaves whose every use in ``gpt2_decode_step`` and ``gpt2_prefill`` is
# ``.astype(cfg.dtype)`` of the whole leaf. An engine never updates a weight,
# so that cast has the same operand and the same result at every step for as
# long as it lives: it stores the result once (the very cast the step
# applied, so every product sees the operands it saw) and the ``.astype(dt)``
# below are then no-ops. A weight the programs come to use that way belongs
# here too (tests/test_serving_params.py reads their jaxprs for a cast of an
# input this list leaves out); the LayerNorm scales and biases are multiplied
# in float32 as they are held, and do not.
_SERVED_IN_DTYPE = frozenset({
    "wte", "wpe", "attn_qkv_w", "attn_qkv_b", "attn_out_w", "attn_out_b",
    "mlp_in_w", "mlp_in_b", "mlp_out_w", "mlp_out_b"})


def gpt2_decode_step(params: Params, cache: Params, tokens: jax.Array,
                     pos: jax.Array, cfg: GPT2Config
                     ) -> tuple[jax.Array, Params]:
    """``gpt2_decode_step_counted`` without its counters: (logits, new
    cache), what the step has always returned and its callers outside the
    engine unpack."""
    return gpt2_decode_step_counted(params, cache, tokens, pos, cfg)[:2]


# jax-hot-path: traced into the engine's single compiled decode step
def gpt2_decode_step_counted(params: Params, cache: Params,
                             tokens: jax.Array, pos: jax.Array,
                             cfg: GPT2Config
                             ) -> tuple[jax.Array, Params, dict]:
    """One decode iteration for every slot.

    tokens [S] int32 (the slot's current token), pos [S] int32 (its
    absolute position). Attends over the valid window with each token's
    own K/V in the place its ring cursor names, writes those rows there
    after the layer loop, and returns (logits [S, V] fp32, new cache:
    the given one with S rows a layer changed, in place when donated,
    counters ``ring_rows_read`` and ``ring_rows_held``: what the step's
    attention read of the rings, ``ops/attention.ring_rows_counted``).
    Free slots simply compute garbage into their own cache rows — the
    fixed shape is the point."""
    s = tokens.shape[0]
    d, h, hd = cfg.d_model, cfg.n_head, cfg.head_dim
    cache_len = cache["k"].shape[2]
    dt = cfg.dtype
    cursor = jnp.mod(pos, cache_len)
    valid = jnp.minimum(pos + 1, cache_len)
    wpe_pos = jnp.clip(pos, 0, cfg.seq_len - 1)
    with jax.named_scope("embed"):
        x = params["wte"].astype(dt)[tokens] \
            + params["wpe"].astype(dt)[wpe_pos]

    from ray_tpu.ops.attention import (cache_write_token,
                                       cached_decode_attention,
                                       ring_rows_counted)

    counted = ring_rows_counted(cache["k"], valid)

    def block(x, layer):
        # the stacked cache is read as it was, a layer by its index (a
        # layer's slice as the scan's ``xs`` would be copied out for the
        # attention's kernel): the rows go out as ys
        p, i = layer
        with jax.named_scope("ln"):
            y = _layer_norm(x, p["ln1_scale"], p["ln1_bias"])
        with jax.named_scope("attn_proj"):
            qkv = y @ p["attn_qkv_w"].astype(dt) + p["attn_qkv_b"].astype(dt)
            q, k_new, v_new = jnp.split(qkv, 3, axis=-1)
            k_new = _merged_row(k_new, cache["k"])
            v_new = _merged_row(v_new, cache["v"])
        with jax.named_scope("attn"):
            attn = cached_decode_attention(
                q.reshape(s, h, hd), cache["k"], cache["v"], k_new, v_new,
                cursor, valid, dt, layer=i)
        with jax.named_scope("attn_proj"):
            x = x + attn.reshape(s, d) @ p["attn_out_w"].astype(dt) \
                + p["attn_out_b"].astype(dt)
        x = _mlp_block(x, p, dt)
        return x, (k_new, v_new)

    x, (k_rows, v_rows) = jax.lax.scan(
        block, x, (params["blocks"], jnp.arange(cfg.n_layer)))
    with jax.named_scope("cache_write"):
        cache = {"k": cache_write_token(cache["k"], k_rows, cursor),
                 "v": cache_write_token(cache["v"], v_rows, cursor)}
    with jax.named_scope("ln"):
        x = _layer_norm(x, params["lnf_scale"], params["lnf_bias"])
    with jax.named_scope("head"):
        logits = jnp.einsum(
            "sd,vd->sv", x, params["wte"].astype(dt),
            preferred_element_type=jnp.float32)
    return logits, cache, counted


def _merged_row(rows: jax.Array, cache: jax.Array) -> jax.Array:
    """A projection's K or V rows [..., D] as the cache holds them: its
    type, zeros in the pad columns."""
    from ray_tpu.ops.attention import merged_rows

    return merged_rows(rows.astype(cache.dtype), cache.shape[-1])


def _mlp_block(x: jax.Array, p: Params, dt) -> jax.Array:
    """Second half of a serving block: norm, MLP, residual."""
    with jax.named_scope("ln"):
        y = _layer_norm(x, p["ln2_scale"], p["ln2_bias"])
    with jax.named_scope("mlp"):
        y = y @ p["mlp_in_w"].astype(dt) + p["mlp_in_b"].astype(dt)
        y = jax.nn.gelu(y, approximate=True)
        return x + y @ p["mlp_out_w"].astype(dt) + p["mlp_out_b"].astype(dt)


# jax-hot-path: traced into the engine's single compiled prefill program
def gpt2_prefill_chunk(params: Params, cache: Params, tokens: jax.Array,
                       slots: jax.Array, start: jax.Array,
                       lengths: jax.Array, cfg: GPT2Config,
                       window: int | None = None
                       ) -> tuple[jax.Array, Params]:
    """A chunk of a prompt: the engine's SECOND (and only other) jitted
    shape, run once for every C tokens of a request.

    tokens [R, C] int32, row r's prompt tokens ``start[r] .. start[r] + C``
    (zero-padded past the prompt), slots [R] int32 (each row's cache slot),
    start [R] int32 (a multiple of C), lengths [R] int32 (the chunk's real
    tokens). Writes rows ``[start, start + C)`` of each slot's K/V and
    attends each query ``start + i`` over its slot's rows ``<= start + i``
    (what earlier chunks left there and the chunk's own), among the first
    ``window`` rows (static; the whole slot when None). Returns (logits
    [R, V] fp32 at each chunk's last real token, new cache); the last
    chunk's are the prompt's. Rows past a prompt's length hold pad
    garbage; the decode mask never reads them — the slot's own later
    writes overwrite them in order."""
    r, c = tokens.shape
    d, h, hd = cfg.d_model, cfg.n_head, cfg.head_dim
    dt = cfg.dtype
    window = window or cache["k"].shape[2]
    pos = jnp.clip(start[:, None] + jnp.arange(c)[None, :], 0,
                   cfg.seq_len - 1)
    with jax.named_scope("embed"):
        x = params["wte"].astype(dt)[tokens] + params["wpe"].astype(dt)[pos]
    from ray_tpu.ops.attention import (cache_write_chunk,
                                       merged_chunk_attention)

    def block(x, layer):
        p, i = layer  # the stacked cache is read as it was: rows out as ys
        with jax.named_scope("ln"):
            y = _layer_norm(x, p["ln1_scale"], p["ln1_bias"])
        with jax.named_scope("attn_proj"):
            qkv = y @ p["attn_qkv_w"].astype(dt) + p["attn_qkv_b"].astype(dt)
            q, k_, v_ = jnp.split(qkv, 3, axis=-1)
            k_ = _merged_row(k_, cache["k"])
            v_ = _merged_row(v_, cache["v"])
        with jax.named_scope("attn"):
            attn = merged_chunk_attention(
                q.reshape(r, c, h, hd), cache["k"], cache["v"], k_, v_, i,
                slots, start, window)
        with jax.named_scope("attn_proj"):
            x = x + attn.reshape(r, c, d) @ p["attn_out_w"].astype(dt) \
                + p["attn_out_b"].astype(dt)
        x = _mlp_block(x, p, dt)
        return x, (k_, v_)

    x, (k_rows, v_rows) = jax.lax.scan(
        block, x, (params["blocks"], jnp.arange(cfg.n_layer)))
    with jax.named_scope("cache_write"):
        cache = {"k": cache_write_chunk(cache["k"], k_rows, slots, start),
                 "v": cache_write_chunk(cache["v"], v_rows, slots, start)}
    with jax.named_scope("ln"):
        x = _layer_norm(x, params["lnf_scale"], params["lnf_bias"])
    with jax.named_scope("head"):
        last = x[jnp.arange(r), jnp.clip(lengths - 1, 0, c - 1)]  # [R, D]
        logits = jnp.einsum(
            "rd,vd->rv", last, params["wte"].astype(dt),
            preferred_element_type=jnp.float32)
    return logits, cache


def gpt2_prefill(params: Params, cache: Params, tokens: jax.Array,
                 slots: jax.Array, lengths: jax.Array, cfg: GPT2Config
                 ) -> tuple[jax.Array, Params]:
    """Whole padded prompts tokens [R, P] through ``gpt2_prefill_chunk``,
    chunk after chunk from row 0 of each slot (``models/prefill.py``):
    (logits [R, V] fp32 at each prompt's last real token, new cache)."""
    from ray_tpu.models.prefill import whole_prompts

    return whole_prompts(gpt2_prefill_chunk, params, cache, tokens, slots,
                         lengths, cfg)
