"""``models/nemotron_h.py`` against its plain reference
(``benchmark/reference/nemotron_h.py``), at ``tiny`` size in float32 on
seeded random weights: prefill then decoding through the cache (K/V rows and
both Mamba states) and the state's handling (a padded lane, a re-used slot,
a free slot). The contracts every served family holds (sizes, types, the
forward pass, the engine against the reference) are
``tests/test_served_family_contract.py``'s."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import nemotron_h as nh
from served_families import (FAMILIES, benchmark_file, contract_params,
                             contract_tokens, contract_want)

ROW = FAMILIES["nemotron_h"]
reference, CFG = ROW.reference, ROW.cfg
to_ref, ref_kwargs = ROW.to_reference, ROW.reference_kwargs


@pytest.fixture(scope="module")
def params():
    return contract_params("nemotron_h")


@pytest.fixture(scope="module")
def tokens():
    return contract_tokens("nemotron_h")


@pytest.fixture(scope="module")
def want():
    return contract_want("nemotron_h")


def test_weights_are_stored_in_bfloat16_by_default():
    cfg = nh.NemotronHConfig.tiny()
    params = nh.nemotron_h_init(jax.random.PRNGKey(0), cfg)
    assert all(x.dtype == jnp.bfloat16 for x in jax.tree.leaves(params))
    cache = nh.nemotron_h_init_cache(cfg, 3, 16)
    assert [s.dtype for s in cache["ssm"]] == [jnp.float32] * 2
    assert cache["conv"].dtype == cache["k"].dtype == jnp.bfloat16
    assert cache["k"].shape == (1, 3, 16, 2, 16)
    assert cache["conv"].shape == (2, 3, 3, cfg.conv_dim)
    assert [s.shape for s in cache["ssm"]] == [(3, 8, 16, 16)] * 2


def test_the_reference_without_a_mechanism_is_another_model(params, tokens,
                                                            want):
    """The controls: each of the model's own mechanisms moves the result
    by far more than the comparisons' 1e-4."""
    ref = to_ref(params, CFG)
    kw = ref_kwargs(CFG)
    # the score correction moves the choice of experts
    flat = {**ref, "layers": [
        {**p, "e_score_correction_bias":
         jnp.zeros_like(p["e_score_correction_bias"])}
        if "gate_w" in p else p for p in ref["layers"]]}
    for other in (reference.forward(ref, tokens, **{**kw, "routed_scale": 1.0}),
                  reference.forward(ref, tokens, **{**kw, "top_k": 2}),
                  reference.forward(ref, tokens, **{**kw, "first_expert": 4}),
                  reference.forward(flat, tokens, **kw)):
        assert float(jnp.abs(other - want).max()) > 1e-2


# One compiled program a shape for every test of this file (the
# configuration is a static argument): op by op a prefill or a step of this
# family costs seconds.
_prefill = jax.jit(nh.nemotron_h_prefill, static_argnums=5)
_step = jax.jit(nh.nemotron_h_decode_step, static_argnums=4)


def serve_rows(params, tokens, lengths, steps, cache=None, slots=None,
               cfg=CFG, lane=32):
    """Prefill rows of ``lengths`` in a ``lane``-wide padded lane, then
    ``steps`` decode steps fed ``tokens``' own continuation: the logits at
    each row's last prompt token and after every step."""
    r = len(lengths)
    n_slots = 4
    lengths = jnp.asarray(lengths, jnp.int32)
    prompts = np.zeros((r, lane), np.int32)
    for i, n in enumerate(np.asarray(lengths)):
        prompts[i, :n] = np.asarray(tokens)[i, :n]
    if cache is None:
        cache = nh.nemotron_h_init_cache(cfg, n_slots, max(64, lane))
    slots = jnp.arange(r) if slots is None else jnp.asarray(slots)
    logits, cache = _prefill(
        params, cache, jnp.asarray(prompts), slots, lengths, cfg)
    out = [logits]
    for s in range(steps):
        pos = jnp.zeros((n_slots,), jnp.int32).at[slots].set(lengths + s)
        toks = jnp.zeros((n_slots,), jnp.int32).at[slots].set(
            tokens[jnp.arange(r), lengths + s])
        logits, cache, _ = _step(
            params, cache, toks, pos, cfg)
        out.append(logits[slots])
    return jnp.stack(out, axis=1), cache


@pytest.mark.parametrize("rows", [24, 288], ids=["batched", "grouped"])
def test_the_routed_experts_keep_bfloat16s_precision(rows):
    """What ``correct`` cannot see through the logits, held by numbers a
    layer at a time: with the program's own types the routed part of an
    ``E`` layer lies within 1e-2 of itself computed in float32 from the
    same bfloat16-valued weights and the same routing (4e-3 and 5e-3
    measured); with the experts' matrices rounded to float8, the control of
    the configuration file's ``reason``, it lies 4e-2 off."""
    tool = benchmark_file("tools", "serve_check_many.py")
    cfg = nh.NemotronHConfig.tiny()
    p = nh._layer_init(jax.random.PRNGKey(4), "E", cfg)
    # the routed part alone, at a size that counts beside rounding
    p = {**p, "shared_w2": jnp.zeros_like(p["shared_w2"]),
         **{k: 8 * p[k] for k in ("w1", "w2")}}
    y = jax.random.normal(jax.random.PRNGKey(5),
                          (rows, cfg.d_model)).astype(cfg.dtype)
    widen = lambda tree: jax.tree.map(lambda x: x.astype(jnp.float32), tree)
    want, want_counts = nh._moe(widen(p), widen(y), CFG)

    def off(layer):
        got, counts = nh._moe(layer, y, cfg)
        assert counts.tolist() == want_counts.tolist()  # one routing
        return float(jnp.linalg.norm(widen(got) - want)
                     / jnp.linalg.norm(want))

    assert int(want_counts.sum()) > rows and off(p) < 1e-2
    assert off({**p, **tool.rounded({k: p[k] for k in ("w1", "w2")}, 3)}) \
        > 2.5e-2


def test_a_thousand_steps_keep_the_state_a_prefill_computes(params):
    """The SSM state's precision, held by numbers where the benchmark's
    check cannot afford to (it rounds the state nine times; the error of a
    narrower state builds over a long answer): after 1024 single steps the
    state, in the type the configuration states, is the state one prefill
    over the same tokens computes, to 1e-5 of its size (5e-7 measured). The
    control, the state held in bfloat16, drifts 6e-3 to 2e-2."""
    n = 1024
    tokens = jax.random.randint(jax.random.PRNGKey(11), (1, n), 0,
                                CFG.vocab_size)

    def drift(cfg):
        def step(cache, at):
            tok, t = at
            zero = jnp.zeros((), jnp.int32)
            _, cache, _ = _step(
                params, cache, jnp.stack([tok, zero]), jnp.stack([t, zero]),
                cfg)
            return cache, ()

        stepped, _ = jax.jit(lambda cache: jax.lax.scan(
            step, cache, (tokens[0], jnp.arange(n, dtype=jnp.int32))))(
                nh.nemotron_h_init_cache(cfg, 2, n))
        _, filled = _prefill(
            params, nh.nemotron_h_init_cache(cfg, 2, n), tokens,
            jnp.asarray([0]), jnp.asarray([n]), cfg)
        wide = lambda s: np.asarray(s[0], np.float64)
        return [np.linalg.norm(wide(a) - wide(b)) / np.linalg.norm(wide(b))
                for a, b in zip(stepped["ssm"], filled["ssm"])]

    assert CFG.ssm_state_dtype == nh.NemotronHConfig().ssm_state_dtype
    assert max(drift(CFG)) < 1e-5
    assert min(drift(dataclasses.replace(
        CFG, ssm_state_dtype=jnp.bfloat16))) > 2e-3


@pytest.mark.parametrize("lane", [32, 96])
def test_prefill_then_decode_through_the_cache(params, tokens, want, lane):
    """Rows of different ``length`` in one padded lane, then 10 decode
    steps: every logits row is the reference's at that position. The wider
    lane holds 288 rows for the experts (the TPU's grouped product took it
    until PR 52; toy widths keep the batched one at any row count)."""
    lengths = [30, 19, 5]
    got, _ = serve_rows(params, tokens, lengths, 10, lane=lane)
    for i, n in enumerate(lengths):
        assert float(jnp.abs(got[i] - want[i, n - 1:n + 10]).max()) < 1e-4


def test_a_reused_slot_loses_its_old_state_whole(params, tokens, want):
    """Serve rows, step them, then prefill OTHER rows into the same slots
    (in another order, beside a scratch row): what the slots held before
    leaves no trace in K/V, convolution tail or SSM state."""
    _, cache = serve_rows(params, tokens, [30, 19, 5], 6)
    assert all(float(jnp.abs(s[:3]).min()) > 0
               for s in cache["ssm"])  # used, not empty
    again = tokens[::-1]
    lengths = [7, 28, 16]
    got, _ = serve_rows(params, again, lengths, 8, cache=cache,
                        slots=[2, 0, 1])
    want_again = want[::-1]
    for i, n in enumerate(lengths):
        assert float(jnp.abs(got[i] - want_again[i, n - 1:n + 8]).max()) \
            < 1e-4


def test_prefill_state_is_the_state_of_single_steps(params, tokens):
    """The convolution tail and the SSM state a prefill of ``length``
    tokens leaves (padded lane, chunked scan) are those of ``length``
    decode steps from an empty slot, in every ``M`` layer."""
    lengths = [21, 2, 9]  # 2: shorter than the convolution's tail
    _, by_prefill = serve_rows(params, tokens, lengths, 0)
    cache = nh.nemotron_h_init_cache(CFG, 4, 64)
    reached = {}
    for t in range(max(lengths)):
        pos = jnp.full((4,), t, jnp.int32)
        toks = jnp.concatenate([tokens[:, t], jnp.zeros((1,), jnp.int32)])
        _, cache, _ = _step(params, cache, toks, pos, CFG)
        for i, n in enumerate(lengths):
            if n == t + 1:
                reached[i] = {"ssm": jnp.stack(cache["ssm"])[:, i],
                              "conv": cache["conv"][:, :, i]}
    for i in range(3):
        assert float(jnp.abs(jnp.stack(by_prefill["ssm"])[:, i]
                             - reached[i]["ssm"]).max()) < 1e-5
        assert float(jnp.abs(by_prefill["conv"][:, :, i]
                             - reached[i]["conv"]).max()) < 1e-5
    # the scratch slot was never written
    assert float(jnp.abs(jnp.stack(by_prefill["ssm"])[:, 3]).max()) == 0


def test_inactive_prefill_rows_write_to_the_scratch_slot(params, tokens):
    """A lane with one real row and one inactive row (length 1, slot =
    the scratch one, as the engine fills it): the other slots' state and
    K/V rows are as they were."""
    _, cache = serve_rows(params, tokens, [30, 19, 5], 2)
    stacked = lambda c: {**c, "ssm": jnp.stack(c["ssm"])}
    before = jax.tree.map(np.asarray, stacked(cache))
    prompts = np.zeros((2, 32), np.int32)
    prompts[0, :12] = np.asarray(tokens)[1, :12]
    _, after = _prefill(
        params, cache, jnp.asarray(prompts), jnp.asarray([1, 3]),
        jnp.asarray([12, 1]), CFG)
    after = stacked(after)
    for name, slot_axis in (("k", 1), ("v", 1), ("ssm", 1), ("conv", 2)):
        for slot in (0, 2):
            np.testing.assert_array_equal(
                np.take(np.asarray(after[name]), slot, axis=slot_axis),
                np.take(before[name], slot, axis=slot_axis))
        assert not np.array_equal(
            np.take(np.asarray(after[name]), 1, axis=slot_axis),
            np.take(before[name], 1, axis=slot_axis))


def test_a_free_slot_stepped_500_times_stays_finite(params):
    step = jax.jit(lambda c, t, n: nh.nemotron_h_decode_step(
        params, c, t, n, CFG)[:2])
    cache = nh.nemotron_h_init_cache(CFG, 2, 8)
    toks, pos = jnp.asarray([7, 0]), jnp.zeros((2,), jnp.int32)
    for _ in range(500):  # a free slot: its token and position stand still
        logits, cache = step(cache, toks, pos)
    assert bool(jnp.isfinite(logits).all())
    assert all(bool(jnp.isfinite(x).all()) for x in jax.tree.leaves(cache))
    assert float(jnp.abs(jnp.stack(cache["ssm"])).max()) < 1e3


def test_the_kv_part_keeps_the_ring_contract(params, tokens):
    """A position past ``cache_len`` wraps; with no position embedding
    that is a window of ``cache_len`` rows."""
    cache = nh.nemotron_h_init_cache(CFG, 1, 8)
    for t in range(12):
        _, cache, _ = _step(
            params, cache, tokens[0, t][None], jnp.asarray([t]), CFG)
    assert bool(jnp.isfinite(cache["k"]).all())
    # rows 8..11 overwrote rows 0..3
    fresh = nh.nemotron_h_init_cache(CFG, 1, 16)
    for t in range(12):
        _, fresh, _ = _step(
            params, fresh, tokens[0, t][None], jnp.asarray([t]), CFG)
    np.testing.assert_allclose(np.asarray(cache["k"][0, 0, :4]),
                               np.asarray(fresh["k"][0, 0, 8:12]),
                               atol=1e-5)


# -- the engine ---------------------------------------------------------------


def test_a_dense_familys_engine_and_decode_program_are_as_before():
    """Llama returns no counters (no experts, and its rings are no merged
    rows: GPT-2, which this test used to take, has counted the rows its
    rings' kernel reads since PR 48): its engine reports no new key, and
    the engine's step program is the model's own decode step and an
    argmax, nothing joined to its tokens."""
    from ray_tpu.models import llama
    from ray_tpu.serve.llm_engine import LLMEngine

    cfg = dataclasses.replace(llama.LlamaConfig.tiny(), dtype=jnp.float32)
    eng = LLMEngine(model="llama", config=cfg, max_batch=2, cache_len=16,
                    max_prompt_len=8)
    try:
        assert len(eng.generate([1, 2, 3], 4)) == 4
        stats = eng.llm_stats()
        assert not {"experts_hit", "expert_rows", "expert_row_tiles",
                    "expert_layers",
                    "experts_held", "ring_rows_read",
                    "ring_rows_held"} & set(stats)
        assert eng._step_counters == ()
        toks, pos = jnp.zeros((3,), jnp.int32), jnp.zeros((3,), jnp.int32)
        before = eng._compiles["decode"]
        engine_jaxpr = jax.make_jaxpr(eng._step_fn)(
            eng.params, eng._cache, toks, pos)
        eng._compiles["decode"] = before  # tracing it again counted one

        def plain(params, cache, tokens, pos):
            logits, cache = llama.llama_decode_step(params, cache, tokens,
                                                    pos, cfg)
            return jnp.argmax(logits, axis=-1).astype(jnp.int32), cache

        plain_jaxpr = jax.make_jaxpr(jax.jit(plain, donate_argnums=(1,)))(
            eng.params, eng._cache, toks, pos)
        shapes = lambda j: [v.aval.str_short() for v in j.jaxpr.outvars]
        assert shapes(engine_jaxpr) == shapes(plain_jaxpr)
        count = lambda j: str(j).count(" = ")
        assert count(engine_jaxpr) == count(plain_jaxpr)
        assert "concatenate" not in str(engine_jaxpr).split("argmax")[-1]
    finally:
        eng.shutdown_engine()
