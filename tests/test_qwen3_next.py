"""Qwen3-Next (``models/qwen3_next.py``) against its plain reference
(``benchmark/reference/qwen3_next.py``) at toy widths on the CPU: prefill in
toy chunks then decode steps through the three kinds of slot state, a wrapped
ring of rotated keys, the ten controls that must fail the limit the
benchmark's configuration states, the four expert shares against the uncut
layer and the four vocabulary slices against the uncut head, the router
against its two-step form. The contracts every served family holds (sizes,
types, scopes, the forward pass, the engine against the reference) are
``tests/test_served_family_contract.py``'s. Every family's two programs, this
one's among them, are held bit for bit by ``tests/test_deepseek_v2.py``'s one
table.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import qwen3_next as qn
from ray_tpu.models.prefill import whole_prompts
from ray_tpu.ops import gated_delta
from ray_tpu.ops.moe import route_topk_softmax
from served_families import (FAMILIES, benchmark_file, contract_params,
                             contract_tokens, contract_want, moved, rel_l2)

ROW = FAMILIES["qwen3_next"]
reference, family, CFG = ROW.reference, ROW.family, ROW.cfg
check_tool = benchmark_file("tools", "serve_check_many.py")
CONFIG = ROW.CONFIG
to_ref, ref_kwargs = ROW.to_reference, ROW.reference_kwargs
F32 = jnp.float32


@pytest.fixture(scope="module")
def params():
    return contract_params("qwen3_next")


@pytest.fixture(scope="module")
def tokens():
    return contract_tokens("qwen3_next")


@pytest.fixture(scope="module")
def want():
    return contract_want("qwen3_next")


@functools.lru_cache(maxsize=None)
def _serving(cfg, chunk):
    """The prompts' chunks and the step, each ONE compiled program a
    (configuration, shape) for every test that runs them: the parameters
    are arguments, not constants of the program."""
    return (jax.jit(lambda params, c, prompts, slots, lengths: whole_prompts(
        qn.qwen3_next_prefill_chunk, params, c, prompts, slots, lengths,
        cfg, chunk=chunk)),
        jax.jit(lambda params, c, t, n: qn.qwen3_next_decode_step(
            params, c, t, n, cfg)[:2]))


def through_the_cache(cfg, params, tokens, lengths, steps, chunk=8,
                      cache_len=64, window=48, fresh=False):
    """The serving functions: the prompts (``tokens[r, :lengths[r]]``) in
    chunks through ``qwen3_next_prefill_chunk``, then ``steps`` decode steps
    fed ``tokens``' continuation. -> logits [R, 1 + steps, V]. ``fresh``:
    traced anew, for a control that has turned a function the programs
    call."""
    r = tokens.shape[0]
    prompts = jnp.where(jnp.arange(window)[None] < lengths[:, None],
                        tokens[:, :window], 0)
    cache = qn.qwen3_next_init_cache(cfg, r + 1, cache_len)
    prefill, step = (_serving.__wrapped__ if fresh else _serving)(cfg, chunk)
    logits, cache = prefill(params, cache, prompts, jnp.arange(r), lengths)
    out, rows, free = [logits], jnp.arange(r), jnp.zeros(1, jnp.int32)
    for i in range(steps):
        logits, cache = step(
            params, cache, jnp.concatenate([tokens[rows, lengths + i], free]),
            jnp.concatenate([lengths + i, free]))
        out.append(logits[:r])
    return jnp.stack(out, axis=1)


def reference_rows(params, cfg, tokens, lengths, steps):
    full = ROW.reference_forward(params, cfg)(tokens)
    rows = jnp.arange(tokens.shape[0])
    return jnp.stack([full[rows, lengths - 1 + i]
                      for i in range(steps + 1)], axis=1)


# -- sizes and types ----------------------------------------------------------


def test_weights_are_bfloat16_and_drawn_by_the_gains():
    cfg = qn.Qwen3NextConfig.tiny()
    params = qn.qwen3_next_init(jax.random.PRNGKey(0), cfg)
    assert {x.dtype for x in jax.tree.leaves(params)} \
        == {jnp.dtype(jnp.bfloat16)}
    assert cfg.serving_dtypes(params) == jax.tree.map(
        lambda x: x.dtype, params)
    assert params["lm_head"].shape == params["embed"].shape
    linear, full = params["layers"][0], params["layers"][3]
    assert "in_qkvz" in linear and "wq" not in linear
    assert "wq" in full and "in_qkvz" not in full
    assert full["wq"].shape == (48, 2 * 4 * 16)  # a head's q and its gate
    assert linear["w1"].shape == (8, 48, 48) and "router" in full
    # zero-centred norms at 0, the delta rule's own at 1
    assert float(jnp.abs(linear["norm"].astype(F32)).max()) == 0.0
    assert float(jnp.abs(full["q_norm"].astype(F32)).max()) == 0.0
    assert float(linear["gate_norm"].astype(F32).min()) == 1.0
    std = qn.init_stds(cfg)
    assert std["wo"] == pytest.approx(16.0 / 64 ** 0.5)
    assert std["w2"] == pytest.approx(1.0 / 24 ** 0.5)
    got = float(jnp.std(full["wo"].astype(F32)))
    assert got == pytest.approx(std["wo"], rel=0.05)
    with pytest.raises(ValueError, match="gains"):
        qn.Qwen3NextConfig.tiny(gains=(("embed", 1.0),))


# -- against the reference ----------------------------------------------------


@pytest.mark.parametrize("chunk", [4, 8, 16])
def test_prefill_in_toy_chunks_then_decode_through_the_cache(chunk, params,
                                                             tokens, want):
    """Prompts of three lengths, each cut into chunks that end inside a
    block of the scan (blocks of 8), then seven decode steps: logits, not
    tokens, against the reference's full forward."""
    lengths = jnp.asarray([27, 9, 16], jnp.int32)
    got = through_the_cache(CFG, params, tokens, lengths, steps=7,
                            chunk=chunk, window=32)
    rows = jnp.arange(3)
    wanted = jnp.stack([want[rows, lengths - 1 + i] for i in range(8)], 1)
    assert rel_l2(got, wanted) < 2e-4


def test_the_counters_count_the_pairs_the_held_experts_took(params, tokens):
    lengths = jnp.asarray([27, 9, 16], jnp.int32)
    cache = qn.qwen3_next_init_cache(CFG, 4, 64)
    prompts = jnp.where(jnp.arange(32)[None] < lengths[:, None],
                        tokens[:, :32], 0)
    _, cache = whole_prompts(qn.qwen3_next_prefill_chunk, params, cache,
                             prompts, jnp.arange(3), lengths, CFG, chunk=8)
    pairs = int(cache["counted"]["prefill_expert_rows"])
    # 52 real tokens x 3 experts a token x 8 layers, the half that is held
    assert 0 < pairs <= 52 * 3 * 8
    assert abs(pairs - 52 * 3 * 8 / 2) < 52 * 3 * 8 / 4
    _, _, counted = qn.qwen3_next_decode_step(
        params, cache, jnp.concatenate([tokens[:, 0], jnp.zeros(1, jnp.int32)]),
        jnp.asarray([27, 9, 16, 0], jnp.int32), CFG)
    assert 0 < int(counted["experts_hit"]) <= int(counted["expert_rows"]) \
        <= 4 * 3 * 8


def _windowed_attention(ring):
    """``reference.gated_attention`` with each query seeing its last
    ``ring`` keys."""
    def attention(p, x, *, eps, n_head, n_kv_head, head_dim, rope_theta,
                  rotary_dim):
        w = reference._w
        r, t, _ = x.shape
        qg = (x @ w(p["q_proj"])).reshape(r, t, n_head, 2 * head_dim)
        q, gate = qg[..., :head_dim], qg[..., head_dim:]
        k = (x @ w(p["k_proj"])).reshape(r, t, n_kv_head, head_dim)
        v = (x @ w(p["v_proj"])).reshape(r, t, n_kv_head, head_dim)
        q = reference.partial_rotary(reference.zero_centred_norm(
            q, p["q_norm"], eps), rope_theta, rotary_dim)
        k = reference.partial_rotary(reference.zero_centred_norm(
            k, p["k_norm"], eps), rope_theta, rotary_dim)
        q = q.reshape(r, t, n_kv_head, n_head // n_kv_head, head_dim)
        scores = jnp.einsum("rigqd,rjgd->rgqij", q, k) / head_dim ** 0.5
        gap = jnp.arange(t)[:, None] - jnp.arange(t)[None, :]
        scores = jnp.where((gap >= 0) & (gap < ring), scores, -jnp.inf)
        out = jnp.einsum("rgqij,rjgd->rigqd",
                         jax.nn.softmax(scores, axis=-1), v)
        out = out.reshape(r, t, n_head, head_dim) * jax.nn.sigmoid(gate)
        return out.reshape(r, t, n_head * head_dim) @ w(p["o_proj"])
    return attention


def test_a_wrapped_ring_of_rotated_keys_is_a_window(params, tokens,
                                                    monkeypatch):
    """Keys are rotated at their TRUE positions before they are stored, so
    a ring of 16 rows that has wrapped (positions up to 39) gives what the
    reference gives when each query sees its last 16 keys. The delta state
    beside it forgets nothing."""
    ring, length, steps = 16, 12, 27
    lens = jnp.full(3, length, jnp.int32)
    got = through_the_cache(CFG, params, tokens, lens, steps=steps,
                            chunk=4, cache_len=ring, window=12)
    whole = reference_rows(params, CFG, tokens, lens, steps)
    monkeypatch.setattr(reference, "gated_attention",
                        _windowed_attention(ring))
    window = reference_rows(params, CFG, tokens, lens, steps)
    assert length + steps > 2 * ring  # wrapped, and wrapped again
    assert rel_l2(got, window) < 2e-4
    assert rel_l2(window[:, :ring - length], whole[:, :ring - length]) < 1e-5
    assert rel_l2(window[:, -4:], whole[:, -4:]) > 1e-3


# -- the controls: what the configuration's limit must refuse -----------------


def _without(params, leaf):
    return {**params, "layers": [
        {**p, leaf: jnp.zeros_like(p[leaf])} if leaf in p else p
        for p in params["layers"]]}


def test_the_chunk_program_through_the_kernel_arm_is_the_xla_arms(
        monkeypatch):
    """The tiny preset's heads of 16 keep ``merged_chunk_attention``'s XLA
    arm, which the chip never runs at the published widths (PR 64). Here the
    chunk program END TO END through the kernel of ``ops/merged_chunk.py``
    (interpret mode): heads of 128 lanes, chunks of 128 over a window of
    one block more, two chunks of one prompt (the second a padded one, over
    the rows the first wrote), float32, against the same program built on
    the XLA arm."""
    from ray_tpu.ops import merged_chunk

    cfg = qn.Qwen3NextConfig.tiny(dtype=F32, param_dtype=F32, head_dim=128,
                                  n_layer=2, full_attention_interval=2)
    params = jax.jit(lambda key: qn.qwen3_next_init(key, cfg))(
        jax.random.PRNGKey(0))  # (one compile, not one a leaf)
    chunk, window = 128, 256
    monkeypatch.setattr(merged_chunk, "block_rows", lambda old: 128)
    assert cfg.serving_stats(chunk, window)["chunk_attention_arm"] == "kernel"

    def run():
        program = jax.jit(lambda p, c, t, s, at, n:
                          qn.qwen3_next_prefill_chunk(p, c, t, s, at, n, cfg,
                                                      window=window))
        cache, said = qn.qwen3_next_init_cache(cfg, 2, 384), []
        for i, real in enumerate((chunk, 100)):
            tokens = jax.random.randint(jax.random.PRNGKey(10 + i),
                                        (1, chunk), 0, cfg.vocab_size)
            logits, cache = program(
                params, cache, tokens, jnp.asarray([1]),
                jnp.asarray([i * chunk]), jnp.asarray([real]))
            said.append(np.asarray(logits))
        return np.stack(said)

    got = run()
    monkeypatch.setattr(merged_chunk, "takes_kernel", lambda *a: False)
    assert cfg.serving_stats(chunk, window)["chunk_attention_arm"] == "xla"
    want = run()
    assert np.abs(want).max() > 1
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)


@pytest.fixture(scope="module")
def served():
    """The tiny preset AS THE CELL COMPUTES (bfloat16 weights, activations
    and matmuls, a float32 delta state) through the cache, and the float32
    reference's rows of the same seeded weights."""
    cfg = qn.Qwen3NextConfig.tiny()
    params = qn.qwen3_next_init(jax.random.PRNGKey(4), cfg)
    tokens = jnp.asarray(np.random.default_rng(4).integers(
        0, cfg.vocab_size, (2, 60), dtype=np.int32))
    lens = jnp.asarray([43, 21], jnp.int32)
    return cfg, params, tokens, lens, reference_rows(
        params, cfg, tokens, lens, 6)


# What bfloat16 reads at the TINY preset: at 48 lanes with three of sixteen
# experts a token its floor is 0.05-0.08 over seeds, where the published
# widths read 0.012-0.020 on the chip under the stated limit of 0.05 (the
# configuration file's ``tolerance.reason``). So the tiny preset's own sound
# bound stands beside the stated limit, and a control must pass the larger
# of the two twice over.
TINY_SOUND = 0.12


def test_the_stated_limit_holds_the_sound_program(served):
    cfg, params, tokens, lens, want = served
    limit = CONFIG["tolerance"]["serve_logits_rel_l2"]
    got = through_the_cache(cfg, params, tokens, lens, steps=6)
    assert limit < TINY_SOUND and rel_l2(got, want) < TINY_SOUND


def _no_gate(p, attn, gate, cfg):
    return attn.reshape(*attn.shape[:-2], -1) @ p["wo"].astype(cfg.dtype)


def _state_not_carried(rows_through_cache):
    def rows(p, y, lengths, conv_all, delta, layer, slots, goes_on, dims):
        return rows_through_cache(p, y, lengths, conv_all, delta, layer,
                                  slots, jnp.zeros_like(goes_on), dims)
    return rows


CONTROLS = ["no_delta_term", "state_not_carried", "no_l2_on_q_and_k",
            "no_linear_branch", "no_attention_branch", "no_output_gate",
            "rotary_over_every_lane", "no_shared_expert_gate",
            "norm_read_as_w", "float8_weights"]


@pytest.mark.parametrize("control", CONTROLS)
def test_the_stated_limit_refuses_each_control(served, control, monkeypatch):
    """``serve_logits_rel_l2`` of the benchmark's configuration, at the
    tiny preset in the cell's precision. Each control is one function's
    difference from another, put where it is shortest to write: into the
    SYSTEM (the state not carried across a chunk boundary, a branch's output
    matrix zeroed, the output gate left out, the rotary over every lane,
    float8 weights as ``tools/serve_check_many.py --fault fp8_weights``
    rounds them) or into the REFERENCE the sound system is then held to (the
    delta term left out, ``l2`` left off q and k, the shared expert's gate
    left out, a norm read as ``w`` and not ``1 + w``). Every one reads over
    the limit, by a wide margin."""
    cfg, params, tokens, lens, want = served
    limit = CONFIG["tolerance"]["serve_logits_rel_l2"]
    if control == "no_delta_term":
        monkeypatch.setattr(reference, "gated_delta_net", functools.partial(
            reference.gated_delta_net, delta_term=False))
    elif control == "no_l2_on_q_and_k":
        monkeypatch.setattr(reference, "l2", lambda x: x)
    elif control == "no_shared_expert_gate":
        monkeypatch.setattr(reference, "shared_expert", lambda p, x: (
            reference.gated(x @ reference._w(p["shared_in"]))
            @ reference._w(p["shared_out"])))
    elif control == "norm_read_as_w":
        monkeypatch.setattr(
            reference, "zero_centred_norm", lambda x, w, eps: x
            * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
            * reference._w(w))
        params = moved(params)  # at w = 0 the control is a zero, not a norm
    elif control == "state_not_carried":
        monkeypatch.setattr(gated_delta, "rows_through_cache",
                            _state_not_carried(gated_delta.rows_through_cache))
    elif control == "no_linear_branch":
        params = _without(params, "out_proj")
    elif control == "no_attention_branch":
        params = _without(params, "wo")
    elif control == "no_output_gate":
        monkeypatch.setattr(qn, "_attn_out", _no_gate)
    elif control == "rotary_over_every_lane":
        cfg = dataclasses.replace(cfg, partial_rotary_factor=1.0)
    else:
        params = check_tool.rounded(jax.tree.map(jnp.copy, params), 2)
    if control in ("no_delta_term", "no_l2_on_q_and_k",
                   "no_shared_expert_gate", "norm_read_as_w"):
        want = reference_rows(params, cfg, tokens, lens, 6)
    got = through_the_cache(cfg, params, tokens, lens, steps=6, fresh=True)
    assert rel_l2(got, want) > 2 * max(limit, TINY_SOUND), control


# -- the share: experts and vocabulary ----------------------------------------


def test_the_four_expert_shares_add_up_to_the_uncut_layer(params):
    """One layer's block at toy width with all 16 experts against its four
    shares of 4: the routed parts the shares give add up to the uncut
    layer's routed output, and the shared expert (which every chip computes
    alike) is counted once. By the program's ``_moe`` and by the
    reference."""
    cfg = dataclasses.replace(CFG, experts_held=(0, 16))
    p = moved(qn.qwen3_next_init(jax.random.PRNGKey(3), cfg))["layers"][0]
    x = jax.random.normal(jax.random.PRNGKey(5), (37, cfg.d_model), F32)
    whole, counts = qn._moe(p, x, cfg)
    zero_shared = {**p, "shared_w2": jnp.zeros_like(p["shared_w2"])}
    routed = []
    for first in (0, 4, 8, 12):
        share_cfg = dataclasses.replace(cfg, experts_held=(first, 4))
        share = {**zero_shared, "w1": p["w1"][first:first + 4],
                 "w2": p["w2"][first:first + 4]}
        out, c = qn._moe(share, x, share_cfg)
        np.testing.assert_array_equal(np.asarray(c),
                                      np.asarray(counts[first:first + 4]))
        routed.append(out - x)  # the stream is in every share's output
    shared_once, _ = qn._moe(
        {**p, "w2": jnp.zeros_like(p["w2"])}, x, cfg)
    np.testing.assert_allclose(
        np.asarray(sum(routed) + shared_once), np.asarray(whole),
        rtol=1e-4, atol=1e-5)
    assert int(jnp.sum(counts)) == 37 * cfg.top_k
    # and the reference's share is the same share
    y = reference.zero_centred_norm(x, p["norm2"], cfg.eps)
    ref_p = {"router": p["router"], "experts_in": p["w1"],
             "experts_out": p["w2"]}
    uncut = reference.routed_experts(ref_p, y, top_k=cfg.top_k,
                                     first_expert=0)
    parts = [reference.routed_experts(
        {**ref_p, "experts_in": p["w1"][f:f + 4],
         "experts_out": p["w2"][f:f + 4]}, y, top_k=cfg.top_k,
        first_expert=f) for f in (0, 4, 8, 12)]
    np.testing.assert_allclose(np.asarray(sum(parts)), np.asarray(uncut),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(routed[1]), np.asarray(parts[1]),
                               rtol=1e-3, atol=1e-4)


def test_the_four_vocabulary_slices_add_up_to_the_uncut_head(params, tokens):
    """Four row slices of both tables, side by side, are the uncut head's
    logits: a sliced vocabulary is a smaller vocabulary, nothing else."""
    x = jax.random.normal(jax.random.PRNGKey(8), (5, CFG.d_model), F32)
    whole = qn._head(x, params, CFG)
    v = CFG.vocab_size // 4
    parts = [qn._head(x, {**params, "lm_head": params["lm_head"][i * v:
                                                                  (i + 1) * v]},
                      CFG) for i in range(4)]
    np.testing.assert_allclose(np.asarray(jnp.concatenate(parts, -1)),
                               np.asarray(whole), rtol=1e-5, atol=1e-6)
    rows = tokens[0, :6] % v  # ids drawn from the slice find their rows
    np.testing.assert_array_equal(
        np.asarray(qn._embed({"embed": params["embed"][:v]}, rows, CFG)),
        np.asarray(qn._embed(params, rows, CFG)))


def test_the_router_is_softmax_over_all_then_the_top_renormalised():
    """``route_topk_softmax`` (a softmax over the chosen logits) IS the
    released two-step form: a softmax over all 512, the ten largest,
    renormalised over the ten (``norm_topk_prob``)."""
    x = jax.random.normal(jax.random.PRNGKey(0), (33, 64), F32)
    w = jax.random.normal(jax.random.PRNGKey(1), (64, 512), F32) * 0.3
    ids, weights = route_topk_softmax(x, w, 10)
    probs = jax.nn.softmax(jnp.dot(x, w, precision="highest"), axis=-1)
    top, chosen = jax.lax.top_k(probs, 10)
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(chosen))
    np.testing.assert_allclose(
        np.asarray(weights),
        np.asarray(top / jnp.sum(top, axis=-1, keepdims=True)), rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(reference.gating(x, w, 10)[jnp.arange(33)[:, None], ids]),
        np.asarray(weights), rtol=1e-5)

