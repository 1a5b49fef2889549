"""SmallThinker (``models/smallthinker.py``) against its plain reference
(``benchmark/reference/smallthinker.py``) at toy widths on the CPU: the
forward pass in bfloat16, prefill in toy chunks then decode steps through BOTH
stacks of rings with prompts that end before the window rings' first wrap,
exactly at it and several wraps on, in slots other than 0 beside a scratch
row, the controls that must fail the limit the benchmark's configuration
states, the cache's two stacks and a slot's bytes, and the counters. The
contracts every served family holds (sizes, types, scopes, the forward pass,
the engine against the reference) are
``tests/test_served_family_contract.py``'s. Every family's two programs, this
one's among them, are held bit for bit by ``tests/test_deepseek_v2.py``'s one
table.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import smallthinker as st
from ray_tpu.ops import attention
from served_families import (FAMILIES, benchmark_file, contract_params,
                             contract_tokens, rel_l2)

ROW = FAMILIES["smallthinker"]
reference, family, CFG = ROW.reference, ROW.family, ROW.cfg
check_tool = benchmark_file("tools", "serve_check_many.py")
CONFIG = ROW.CONFIG
to_ref, ref_kwargs = ROW.to_reference, ROW.reference_kwargs


@pytest.fixture(scope="module")
def params():
    return contract_params("smallthinker")


@pytest.fixture(scope="module")
def tokens():
    return contract_tokens("smallthinker")


@functools.lru_cache(maxsize=None)
def _serving(cfg, chunk):
    """The whole-window prefill and the step, each ONE compiled program a
    (configuration, shape) for every test that runs them: the parameters
    are arguments, not constants of the program."""
    return (jax.jit(lambda params, c, prompts, slots, lengths:
                    st.smallthinker_prefill(params, c, prompts, slots,
                                            lengths, cfg, chunk=chunk)),
            jax.jit(lambda params, c, t, n: st.smallthinker_decode_step(
                params, c, t, n, cfg)[:2]))


def through_the_cache(cfg, params, tokens, lengths, steps, chunk=4,
                      cache_len=64, padded=48, slots=None, n_slots=None,
                      fresh=False):
    """The serving functions: the prompts (``tokens[r, :lengths[r]]``) in
    chunks through ``smallthinker_prefill_chunk``, every row run on to the
    end of the padded window as ``whole_prompts`` runs it, then ``steps``
    decode steps fed ``tokens``' continuation, the rows in ``slots`` (the
    first ones by default) of ``n_slots`` (one more than the rows: a
    scratch row that every step computes). -> logits [R, 1 + steps, V].
    ``fresh``: traced anew, for a control that has turned a function the
    programs call."""
    r = tokens.shape[0]
    n_slots = n_slots or r + 1
    slots = jnp.arange(r) if slots is None else jnp.asarray(slots)
    prompts = jnp.where(jnp.arange(padded)[None] < lengths[:, None],
                        tokens[:, :padded], 0)
    cache = st.smallthinker_init_cache(cfg, n_slots, cache_len)
    prefill, step = (_serving.__wrapped__ if fresh else _serving)(cfg, chunk)
    logits, cache = prefill(params, cache, prompts, slots, lengths)
    out, rows = [logits], jnp.arange(r)
    for i in range(steps):
        toks = jnp.zeros((n_slots,), jnp.int32).at[slots].set(
            tokens[rows, lengths + i])
        pos = jnp.zeros((n_slots,), jnp.int32).at[slots].set(lengths + i)
        logits, cache = step(params, cache, toks, pos)
        out.append(logits[slots])
    return jnp.stack(out, axis=1)


def reference_rows(params, cfg, tokens, lengths, steps, **turned):
    full = ROW.reference_forward(params, cfg, **turned)(tokens)
    rows = jnp.arange(tokens.shape[0])
    return jnp.stack([full[rows, lengths - 1 + i]
                      for i in range(steps + 1)], axis=1)


# -- sizes, the cache and types -----------------------------------------------


@pytest.mark.parametrize("which", ["tiny", "published"])
def test_the_cache_is_two_stacks_of_the_lengths_the_file_states(which):
    """A slot's bytes are ``2 * n_global * cache_len * W + 2 * n_window *
    window * W`` elements whatever ``cache_len`` is: the window stack does
    not grow with it."""
    cfg, slots, cache_len = (st.SmallThinkerConfig.tiny(), 3, 40) \
        if which == "tiny" else (family.system_config(CONFIG), 49, 16384)
    cache = jax.eval_shape(
        lambda: st.smallthinker_init_cache(cfg, slots, cache_len))
    w = cfg.row_width
    assert cache["k_full"].shape == cache["v_full"].shape \
        == (cfg.n_global, slots, cache_len, w)
    assert cache["k_win"].shape == cache["v_win"].shape \
        == (cfg.n_window, slots, cfg.window, w)
    rings = [cache[k] for k in ("k_full", "v_full", "k_win", "v_win")]
    assert {a.dtype for a in rings} == {jnp.dtype(jnp.bfloat16)}
    elements = sum(int(np.prod(a.shape)) for a in rings)
    assert elements == slots * (2 * cfg.n_global * cache_len * w
                                + 2 * cfg.n_window * cfg.window * w)
    if which == "published":
        assert w == 512 and elements * 2 == 49 * 117_440_512 \
            == family.cache_bytes(CONFIG, 49, 16384)
        stats = cfg.serving_stats()
        assert (stats["kv_bytes_per_token"],
                stats["window_kv_bytes_per_token"],
                stats["window_rows"]) == (2 * 2048, 6 * 2048, 4096)
        # the engine's chunk and key window, and an engine with no ring
        assert [cfg.serving_stats(*at)["chunk_attention_arm"]
                for at in ((512, 14336), (0, 0))] == ["kernel", "xla"]
    else:
        assert cfg.serving_stats(512, 14336)["chunk_attention_arm"] == "xla"


# -- against the reference ----------------------------------------------------


@pytest.mark.parametrize("dtype", ["bfloat16"])
def test_forward_agrees_with_the_reference(dtype, params, tokens):
    """Rows of 56 tokens, seven windows of eight, as the cell computes (the
    float32 case is ``tests/test_served_family_contract.py``'s)."""
    cfg = st.SmallThinkerConfig.tiny()
    cast = jax.tree.map(lambda x: x.astype(jnp.bfloat16), params)
    got = jax.jit(lambda p, t: st.smallthinker_forward(p, t, cfg))(
        cast, tokens)
    exact = ROW.reference_forward(cast, cfg)(tokens)
    # (the largest of 168 positions, where the served fixture has 14)
    assert got.dtype == jnp.float32 and rel_l2(got, exact) < 2 * TINY_SOUND


# Where a prompt ends, against window rings of 8 rows written in chunks of
# 4: before the first wrap, exactly at it, a token past it, off a chunk
# boundary several wraps on (the last chunk's padded rows must keep what the
# ring held), and on one.
ENDS = {"before_the_first_wrap": [5, 3, 7], "exactly_at_it": [8, 8, 6],
        "a_token_past_it": [9, 4, 8], "several_wraps_on": [37, 22, 11],
        "on_a_chunk_boundary_wraps_on": [40, 16, 24]}


@pytest.mark.parametrize("chunk", [4, 8])
@pytest.mark.parametrize("ends", sorted(ENDS))
def test_prefill_in_toy_chunks_then_decode_through_both_stacks(
        ends, chunk, params, tokens):
    """Three prompts of different lengths in slots 3, 1 and 2 of five (slot
    0 free, slot 4 the scratch row), every row run on to the end of the
    padded window, then eight decode steps: a window ring's cursor goes
    round once more."""
    lengths = jnp.asarray(ENDS[ends], jnp.int32)
    got = through_the_cache(CFG, params, tokens, lengths, steps=8,
                            chunk=chunk, slots=[3, 1, 2], n_slots=5)
    assert rel_l2(got, reference_rows(params, CFG, tokens, lengths, 8)) \
        < 2e-5


def test_a_recycled_slots_window_ring_holds_the_last_tenants_rows(params,
                                                                  tokens):
    """A slot that served a long prompt serves a short one next: the window
    rings still hold the last tenant's rows past the new prompt's end, and
    nothing of them is seen (a ring row whose position is negative for this
    prompt is nobody's)."""
    cache = st.smallthinker_init_cache(CFG, 2, 64)
    slot = jnp.zeros((1,), jnp.int32)
    prefill = jax.jit(lambda c, t, n: st.smallthinker_prefill(
        params, c, t, slot, n, CFG, chunk=4))
    step = jax.jit(lambda c, t, n: st.smallthinker_decode_step(
        params, c, t, n, CFG)[:2])
    _, cache = prefill(cache, tokens[:1, :40], jnp.asarray([37]))
    assert float(jnp.abs(cache["k_win"][:, 0]).min(axis=-1).min()) > 0
    lengths = jnp.asarray([6], jnp.int32)
    logits, cache = prefill(cache, tokens[1:2, :8], lengths)
    out = [logits]
    for i in range(4):
        got, cache = step(cache, jnp.asarray([tokens[1, 6 + i], 0]),
                          jnp.asarray([6 + i, 0]))
        out.append(got[:1])
    assert rel_l2(jnp.stack(out, axis=1), reference_rows(
        params, CFG, tokens[1:2], lengths, 4)) < 2e-5


def test_the_counters_count_the_pairs_and_the_rows_of_both_stacks(params,
                                                                  tokens):
    cache = st.smallthinker_init_cache(CFG, 3, 32)
    lengths = jnp.asarray([13, 6], jnp.int32)
    _, cache = jax.jit(lambda c: st.smallthinker_prefill(
        params, c, tokens[:2, :16], jnp.arange(2), lengths, CFG, chunk=4))(
            cache)
    # every real token's three experts in each of eight layers, no padding
    assert int(cache["counted"]["prefill_expert_rows"]) == (13 + 6) * 3 * 8
    _, _, counted = jax.jit(lambda c: st.smallthinker_decode_step(
        params, c, jnp.zeros((3,), jnp.int32), jnp.asarray([13, 6, 0]),
        CFG))(cache)
    got = {k: int(v) for k, v in counted.items()}
    assert got["expert_rows"] == 3 * 3 * 8  # every row is routed
    assert 0 < got["experts_hit"] <= 8 * 8
    # toy rings take the XLA arm, which reads every ring whole
    assert got["window_rows_read"] == got["window_rows_held"] == 6 * 3 * 8
    assert got["ring_rows_read"] == got["ring_rows_held"] \
        == 6 * 3 * 8 + 2 * 3 * 32


# -- the limit and its controls -----------------------------------------------


@pytest.fixture(scope="module")
def served():
    """The tiny preset AS THE CELL COMPUTES (bfloat16 weights, activations
    and matmuls) through the cache, and the float32 reference's rows of the
    same seeded weights: prompts that have wrapped the window rings five
    times and twice and end off a chunk boundary."""
    cfg = st.SmallThinkerConfig.tiny()
    params = st.smallthinker_init(jax.random.PRNGKey(4), cfg)
    tokens = jnp.asarray(np.random.default_rng(4).integers(
        0, cfg.vocab_size, (2, 60), dtype=np.int32))
    lens = jnp.asarray([43, 21], jnp.int32)
    return cfg, params, tokens, lens, reference_rows(
        params, cfg, tokens, lens, 6)


# What bfloat16 reads at the TINY preset: 0.008-0.021 over three seeds (48
# lanes, three of eight experts a token), under the stated limit as the
# published widths are on the chip (the configuration file's
# ``tolerance.reason``). The tiny preset's own sound bound stands beside the
# stated limit, and a control must pass the larger of the two twice over.
TINY_SOUND = 0.04


def test_the_stated_limit_holds_the_sound_program(served):
    cfg, params, tokens, lens, want = served
    limit = CONFIG["tolerance"]["serve_logits_rel_l2"]
    got = through_the_cache(cfg, params, tokens, lens, steps=6)
    assert rel_l2(got, want) < min(limit, TINY_SOUND)


def _ring_as_if_not_wrapped(start, n_rows):
    """``ring_positions`` of a ring read as a straight cache: row j holds
    position j where the prompt has got that far."""
    rows = jnp.arange(n_rows)
    return jnp.where(rows < start[..., None], rows, -1)


def _padded_rows_written(cache, rows, slots, start, lengths):
    """``cache_write_ring_chunk`` that writes a chunk's padded rows too."""
    return attention.cache_write_ring_chunk(
        cache, rows, slots, start, jnp.full_like(lengths, rows.shape[2]))


# each control, and where it is turned: the REFERENCE's argument (the sound
# system is then held to another model) or the SYSTEM
TURNED_IN_THE_REFERENCE = {
    "window_ignored_on_window_layers": dict(windows=(False,) * 8),
    "global_layers_rotated": dict(rotates=(True,) * 8),
    "window_layers_not_rotated": dict(rotates=(False,) * 8),
    "router_fed_the_normed_input": dict(router_reads="normed_input"),
    "router_fed_the_post_attention_stream":
        dict(router_reads="post_attention"),
    "silu_for_relu": dict(activation="silu"),
}
CONTROLS = sorted(TURNED_IN_THE_REFERENCE) + [
    "chunk_reads_the_ring_as_if_not_wrapped",
    "padded_rows_overwrite_the_ring", "float8_weights"]


@pytest.mark.parametrize("control", CONTROLS)
def test_the_stated_limit_refuses_each_control(served, control, monkeypatch):
    """``serve_logits_rel_l2`` of the benchmark's configuration, at the
    tiny preset in the cell's precision. Each control is one function's
    difference from another, put where it is shortest to write: into the
    REFERENCE the sound system is then held to (the window ignored, the
    global layers rotated, the window layers not rotated, the router fed
    ``N1(x)`` or the post-attention stream, SiLU for ReLU) or into the
    SYSTEM (a chunk that reads a wrapped ring as if it had not wrapped, a
    last chunk whose padded rows overwrite the ring, float8 weights as
    ``tools/serve_check_many.py --fault fp8_weights`` rounds them). Every
    one reads over the limit, by a wide margin."""
    cfg, params, tokens, lens, want = served
    limit = CONFIG["tolerance"]["serve_logits_rel_l2"]
    if control == "router_fed_the_normed_input":
        # at w = 1 and a stream of rms one N1(x) IS x: the norms' weights
        # drawn well away from 1, as a trained checkpoint's are
        keys = iter(jax.random.split(jax.random.PRNGKey(9), 16))
        params = {**params, "layers": [
            {**p, "norm": (1 + 0.5 * jax.random.normal(
                next(keys), p["norm"].shape)).astype(p["norm"].dtype)}
            for p in params["layers"]]}
    if control in TURNED_IN_THE_REFERENCE:
        want = reference_rows(params, cfg, tokens, lens, 6,
                              **TURNED_IN_THE_REFERENCE[control])
    elif control == "chunk_reads_the_ring_as_if_not_wrapped":
        monkeypatch.setattr(attention, "ring_positions",
                            _ring_as_if_not_wrapped)
    elif control == "padded_rows_overwrite_the_ring":
        monkeypatch.setattr(st, "cache_write_ring_chunk",
                            _padded_rows_written)
    else:
        params = check_tool.rounded(jax.tree.map(jnp.copy, params), 2)
    got = through_the_cache(cfg, params, tokens, lens, steps=6, fresh=True)
    assert rel_l2(got, want) > 2 * max(limit, TINY_SOUND), control


def test_the_router_reads_the_unnormed_input_before_attention(params, tokens):
    """The system's choice of experts in a layer is the reference's gating
    of ``x W_r`` of the layer's own un-normed input: with the input's norm
    weight moved, a router fed ``N1(x)`` would choose otherwise."""
    p = params["layers"][0]
    x = params["embed"][tokens[0]]
    ids, weights = st._route(p, x, CFG)
    dense = reference.gating(x @ p["router"], CFG.top_k)
    np.testing.assert_allclose(
        np.take_along_axis(np.asarray(dense), np.asarray(ids), axis=-1),
        np.asarray(weights), rtol=1e-5)
    assert np.allclose(np.asarray(weights).sum(-1), 1.0, atol=1e-5)
    normed = st._norm(x, p["norm"], CFG.eps)
    other, _ = st._route(p, normed, CFG)
    assert (np.sort(np.asarray(other)) != np.sort(np.asarray(ids))).any()

