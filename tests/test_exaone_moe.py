"""K-EXAONE (``models/exaone_moe.py``) against its plain reference
(``benchmark/reference/exaone_moe.py``) at toy widths on the CPU: the forward
pass and the prediction module's, prefill in toy chunks then VERIFY steps (two
rows a slot, accepted and rejected drafts interleaved) through both stacks of
rings with prompts that end before the window rings' first wrap, exactly at it
and several wraps on, the controls that must fail the limit the benchmark's
configuration states, the shares of all eight expert-parallel chips, the
cache's two stacks, and the engine on the normal path (sizes, types and scopes
are ``tests/test_served_family_contract.py``'s): whatever the drafts are (the
module's, an oracle's, always wrong ones), what it serves is token for token
what it serves undrafted.
"""

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import exaone_moe as ex
from ray_tpu.ops import attention
from ray_tpu.ops.moe import dropless_experts, route
from ray_tpu.serve import llm_engine
from served_families import (FAMILIES, benchmark_file, contract_params, moved,
                             rel_l2)

ROW = FAMILIES["exaone_moe"]
reference, family, CFG = ROW.reference, ROW.family, ROW.cfg
check_tool = benchmark_file("tools", "serve_check_many.py")
CONFIG = ROW.CONFIG
toy_file = ROW.toy_file
to_ref, ref_kwargs = ROW.to_reference, ROW.reference_kwargs
F32 = jnp.float32
L, G = "sliding_attention", "full_attention"


@pytest.fixture(scope="module")
def params():
    return contract_params("exaone_moe")


def median_l2(got, want):
    """The MEDIAN position's error. At the toy's 48 lanes in bfloat16 a
    routing choice that rounding turns swaps one expert of three at weight
    0.83 and moves that row by tenths, so a run's largest position says
    little here (0.07-0.31 over three seeds of the sound program); the
    median reads 0.014-0.017 sound and 0.14-1.05 under the controls."""
    return float(jnp.median(jnp.linalg.norm(got - want, axis=-1)
                            / jnp.linalg.norm(want, axis=-1)))


@functools.lru_cache(maxsize=None)
def serving_programs(cfg, chunk):
    """``families/exaone_moe._serving_programs`` at a toy chunk (the family
    file calls the rule's chunk: a toy's is one window), compiled once for
    every test that runs them: a compile costs seconds."""
    return family._serving_programs(
        cfg, lambda p, c, t, s, n, cfg_: ex.exaone_moe_prefill(
            p, c, t, s, n, cfg_, chunk=chunk))


def through_the_cache(cfg, params, tokens, lengths, follow, chunk=8,
                      cache_len=64, padded=48, wrong_every=2, fresh=False):
    """The serving path's own functions by ``families/exaone_moe.
    _verify_steps``, in the tests' toy chunks: prefill, then verify steps
    fed ``tokens``' own continuation as drafts (accepted) with a wrong one
    every ``wrong_every``-th step (rejected). -> (main logits [R, 1 + N, V],
    module logits [R, 1 + N, V], the device's counts a step, which steps
    were handed a wrong draft). ``fresh``: traced anew, for a control that
    has turned a function the programs call."""
    r = tokens.shape[0]
    padded = min(padded, tokens.shape[1])
    prompts = jnp.where(jnp.arange(padded)[None, :] < lengths[:, None],
                        tokens[:, :padded], 0)
    rows = jnp.arange(r)[:, None]
    after = tokens[rows, lengths[:, None] + jnp.arange(follow)[None, :]]
    programs = (serving_programs.__wrapped__ if fresh
                else serving_programs)(cfg, chunk)
    return family._verify_steps(
        toy_file(cfg), params, prompts, lengths, after, r + 1, cache_len,
        wrong_every=wrong_every, cfg=cfg, programs=programs)


@functools.lru_cache(maxsize=None)
def _reference_fn(cfg, module, turned):
    """The reference's forward (or its module's) under one jit: the plain
    functions run op by op take several times as long."""
    fn = reference.module_forward if module else reference.forward
    kwargs = ref_kwargs(cfg, **dict(turned))
    return jax.jit(lambda ref, tokens: fn(ref, tokens, **kwargs))


def reference_logits(params, cfg, tokens, module=False, **turned):
    return _reference_fn(cfg, module, tuple(sorted(turned.items())))(
        to_ref(params, cfg), tokens)


def reference_rows(params, cfg, tokens, lengths, follow, module=False,
                   **turned):
    """The reference's logits at each prompt's last token and the
    ``follow`` after it (main), or the module's there. ``params`` hold the
    teacher's tokens, so row i of the module reads ``tokens[i + 1]``."""
    logits = reference_logits(params, cfg, tokens, module, **turned)
    at = lengths[:, None] - 1 + jnp.arange(follow + 1)[None, :]
    return logits[jnp.arange(tokens.shape[0])[:, None], at]


@functools.lru_cache(maxsize=None)
def _forward_fn(cfg):
    return jax.jit(lambda params, tokens: ex.exaone_moe_forward(
        params, tokens, cfg))


def greedy_rows(cfg, params, prompt_rows, lengths, total):
    """``prompt_rows`` [R, total] continued greedily by the system's own
    whole-row forward from each row's ``lengths`` on: a row whose verify
    steps, handed its own next token as the draft, ACCEPT."""
    rows = np.array(prompt_rows)
    for at in range(int(min(lengths)), total):
        logits = np.asarray(_forward_fn(cfg)(params, jnp.asarray(rows))[0])
        for i, n in enumerate(np.asarray(lengths)):
            if at >= n:
                rows[i, at] = logits[i, at - 1].argmax()
    return jnp.asarray(rows)


@pytest.fixture(scope="module")
def tokens():
    return jnp.asarray(np.random.default_rng(0).integers(
        0, CFG.vocab_size, (3, 60), dtype=np.int32))


@pytest.mark.parametrize("which", ["tiny", "published"])
def test_the_cache_is_two_stacks_and_the_modules_ring_is_a_full_one(which):
    cfg = ex.ExaoneMoeConfig.tiny() if which == "tiny" \
        else family.system_config(CONFIG)
    slots, cache_len = (3, 16) if which == "tiny" else (65, 8192)
    cache = jax.eval_shape(
        lambda: ex.exaone_moe_init_cache(cfg, slots, cache_len))
    w = cfg.row_width
    assert cache["k_full"].shape == cache["v_full"].shape \
        == (cfg.n_global + 1, slots, cache_len, w)
    assert cache["k_win"].shape == cache["v_win"].shape \
        == (cfg.n_window, slots, cfg.window, w)
    assert set(cache) == {"k_full", "v_full", "k_win", "v_win", "counted"}
    if which == "published":
        nbytes = sum(x.size * x.dtype.itemsize
                     for x in jax.tree.leaves(cache))
        assert nbytes == 65 * (2 * 8192 + 4 * 128) * 4096 + 4
        assert w == 1024  # eight K/V heads of 128: whole lane tiles, no pad


# -- against the reference ----------------------------------------------------


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_forward_and_the_module_agree_with_the_reference(dtype, params,
                                                         tokens):
    """The whole-row forward and the module's, rows that wrap the window
    seven times: float32 to rounding, bfloat16 within the stated limit."""
    cfg = CFG if dtype == "float32" else ex.ExaoneMoeConfig.tiny()
    held = jax.tree.map(lambda x: x.astype(cfg.param_dtype)
                        if x.dtype == F32 and x.ndim > 1 else x, params)
    main, module = _forward_fn(cfg)(held, tokens)
    want = reference_logits(held, cfg, tokens)
    want_module = reference_logits(held, cfg, tokens, module=True)
    assert main.shape == (3, 60, 256) and module.shape == (3, 59, 256)
    if dtype == "float32":
        assert rel_l2(main, want) < 2e-5
        assert rel_l2(module, want_module) < 2e-5
    else:
        assert median_l2(main, want) < 0.03
        assert median_l2(module, want_module) < 0.03


ENDS = {"before_the_first_wrap": [5, 3, 7], "exactly_at_it": [8, 8, 6],
        "a_pair_across_it": [7, 15, 23], "wraps_on": [43, 21, 30]}


@pytest.mark.parametrize("ends, chunk", [
    (ends, chunk) for ends in sorted(ENDS) for chunk in (4, 8, 16)
    # (chunks that divide a ring or are two rings long: where a prompt ends
    # off a boundary of either; every compile costs seconds)
    if chunk == 8 or ends in ("a_pair_across_it", "wraps_on")])
def test_prefill_in_toy_chunks_then_verify_steps_through_both_stacks(
        params, tokens, chunk, ends):
    """Prefill in chunks that divide a window ring, are one, or are two
    rings long, then six verify steps with accepted and rejected drafts
    interleaved (and every step rejected), against the reference's rows:
    the main stack's logits at every position, float32 to rounding. A
    prompt that ends at 7 makes the first step's pair the rows 7 and 8: the
    second takes ring row 0, which the first still reads."""
    lens = jnp.asarray(ENDS[ends], jnp.int32)
    want = reference_rows(params, CFG, tokens, lens, 9)
    # (every step rejected: in chunks of one window only, to save compiles)
    for wrong_every in (2, 1) if chunk == 8 else (2,):
        got, _, counts, wrong = through_the_cache(
            CFG, params, tokens, lens, 9, chunk=chunk,
            wrong_every=wrong_every)
        assert got.shape == want.shape == (3, 10, 256)
        assert rel_l2(got, want) < 2e-5, wrong_every
        # random tokens are no greedy continuation: the device rejects them
        assert np.asarray(counts).max() <= 2
        assert [bool(b) for b in wrong][:4] == (
            [False, True, False, True] if wrong_every == 2 else [True] * 4)


def test_accepted_drafts_and_the_modules_rows_against_the_reference(params):
    """Rows that ARE the system's greedy continuation: a step handed the
    next token accepts it (count 2), one handed another rejects it (count
    1), the module's logits beside every main row are the reference's
    module's for the same tokens, and its ring holds a rejected step's
    first row and no more."""
    lens = jnp.asarray([7, 21], jnp.int32)
    seed = np.random.default_rng(5).integers(0, 256, (2, 40))
    rows = greedy_rows(CFG, params, seed, lens, 40)
    want = reference_rows(params, CFG, rows, lens, 8)
    want_module = reference_rows(params, CFG, rows, lens, 8, module=True)
    got, module, counts, wrong = through_the_cache(CFG, params, rows, lens, 8)
    assert rel_l2(got, want) < 2e-5
    assert rel_l2(module, want_module) < 2e-5
    assert [bool(b) for b in wrong] == [False, True, False, True, False]
    np.testing.assert_array_equal(
        np.asarray(counts), [[2, 2], [1, 1], [2, 2], [1, 1], [2, 2]])


def test_the_first_draft_comes_from_the_prefill_and_the_next_from_the_step(
        params):
    """What the engine carries: the prefill's draft is the module's greedy
    token at the prompt's last row, fed the main stack's own greedy token;
    a step's next draft is the module's first row's on a rejection and its
    second's on an acceptance."""
    lens = jnp.asarray([11, 21], jnp.int32)
    seed = np.random.default_rng(7).integers(0, 256, (2, 40))
    rows = greedy_rows(CFG, params, seed, lens, 40)
    module = reference_logits(params, CFG, rows, module=True)
    cache = ex.exaone_moe_init_cache(CFG, 3, 64)
    prompts = jnp.where(jnp.arange(24)[None, :] < lens[:, None],
                        rows[:, :24], 0)
    prefill, step = serving_programs(CFG, 8)
    logits, cache, drafts = prefill(params, cache, prompts, jnp.arange(2),
                                    lens)
    at = np.arange(2)
    np.testing.assert_array_equal(np.argmax(logits, -1),
                                  np.asarray(rows)[at, lens])
    np.testing.assert_array_equal(
        np.argmax(drafts, -1), np.argmax(module[at, lens - 1], -1))
    for accept in (True, False):
        first = rows[at, lens]
        draft = rows[at, lens + 1] if accept else rows[at, lens + 1] + 1
        toks = jnp.concatenate([jnp.stack([first, draft % 256], 1),
                                jnp.zeros((1, 2), jnp.int32)])
        pos = jnp.concatenate([lens, jnp.zeros((1,), jnp.int32)])
        _, _, served, _ = step(params, jax.tree.map(jnp.copy, cache), toks,
                               pos)
        served = np.asarray(served)[:2]
        np.testing.assert_array_equal(served[:, 0], 2 if accept else 1)
        np.testing.assert_array_equal(served[:, 1],
                                      np.asarray(rows)[at, lens + 1])
        if accept:
            np.testing.assert_array_equal(served[:, 2],
                                          np.asarray(rows)[at, lens + 2])
        last = lens + (1 if accept else 0)   # the module's row that drafts
        np.testing.assert_array_equal(
            served[:, 3], np.argmax(module[at, last], -1))


def test_the_shares_of_eight_chips_add_up_to_the_uncut_layer(params):
    """Eight chips share a layer by expert parallelism, one of the toy's
    eight experts each: what each chip's ``dropless_experts`` gives for
    its own expert, summed over the chips, plus the shared expert counted
    ONCE, is the reference's uncut layer with every expert held."""
    cfg = ex.ExaoneMoeConfig.tiny(dtype=F32, param_dtype=F32,
                                  experts_held=(0, 8))
    p = moved(ex.exaone_moe_init(jax.random.PRNGKey(2), cfg))["layers"][1]
    h = jax.random.normal(jax.random.PRNGKey(3), (40, cfg.d_model), F32)
    ids, weights = route(h, p["router"], p["router_bias"], cfg.top_k,
                         cfg.routed_scale)
    parts, pairs = [], 0
    for chip in range(8):
        out, counts = dropless_experts(
            h, ids, weights, p["w1"][chip:chip + 1], p["w2"][chip:chip + 1],
            first=chip, activation=ex._swiglu)
        parts.append(out)
        pairs += int(counts.sum())
    assert pairs == 40 * cfg.top_k  # every pair landed on exactly one chip
    shared = ex._swiglu(h @ p["shared_w1"]) @ p["shared_w2"]
    ref = family._block_to_reference(p, toy_file(cfg))
    want = reference.feed_forward(ref, h, top_k=cfg.top_k,
                                  routed_scale=cfg.routed_scale)
    np.testing.assert_allclose(np.asarray(sum(parts) + shared),
                               np.asarray(want), rtol=2e-5, atol=2e-5)
    # one chip's share alone is what the reference gives for its range
    held = {**ref, **{k: ref[k][2:3] for k in
                      ("experts_gate", "experts_up", "experts_down")}}
    np.testing.assert_allclose(
        np.asarray(parts[2] + shared), np.asarray(reference.feed_forward(
            held, h, top_k=cfg.top_k, routed_scale=cfg.routed_scale,
            first_expert=2)), rtol=2e-5, atol=2e-5)


def test_the_router_is_the_references_gating(params):
    p = params["layers"][1]
    h = jax.random.normal(jax.random.PRNGKey(4), (30, CFG.d_model), F32)
    ids, weights = route(h, p["router"], p["router_bias"], CFG.top_k,
                         CFG.routed_scale)
    dense = reference.gating(h @ p["router"], p["router_bias"], CFG.top_k,
                             CFG.routed_scale)
    np.testing.assert_allclose(
        np.take_along_axis(np.asarray(dense), np.asarray(ids), axis=-1),
        np.asarray(weights), rtol=1e-5)
    assert np.allclose(np.asarray(weights).sum(-1), 2.5, atol=1e-5)
    assert (np.asarray(dense) > 0).sum(-1).tolist() == [CFG.top_k] * 30


def test_the_counters_count_the_pairs_and_the_rows_of_both_stacks(params,
                                                                  tokens):
    cache = ex.exaone_moe_init_cache(CFG, 3, 32)
    lens = jnp.asarray([13, 5], jnp.int32)
    _, cache, _ = ex.exaone_moe_prefill(
        params, cache, tokens[:2, :16], jnp.arange(2), lens, CFG, chunk=8)
    # five sparse layers, three of eight experts a real token; which of the
    # pairs land on the four held experts is the router's business
    pairs = int(cache["counted"]["prefill_expert_rows"])
    assert 0 < pairs <= (13 + 5) * 3 * 5
    toks = jnp.zeros((3, 2), jnp.int32)
    pos = jnp.asarray([13, 5, 0], jnp.int32)
    _, _, counted, _, _ = ex.exaone_moe_verify_step(params, cache, toks, pos,
                                                    CFG)
    assert int(counted["ring_rows_held"]) == 3 * (2 * 32 + 5 * 8)
    assert int(counted["window_rows_held"]) == 3 * 5 * 8
    assert int(counted["ring_rows_read"]) == 3 * (2 * 32 + 5 * 8)
    assert 0 < int(counted["experts_hit"]) <= 5 * 4
    # two rows a slot: up to 3 x 2 x 3 pairs a sparse layer
    assert 0 < int(counted["expert_rows"]) <= 5 * 3 * 2 * 3


# -- the controls -------------------------------------------------------------


@pytest.fixture(scope="module")
def served():
    """The tiny preset AS THE CELL COMPUTES (bfloat16 weights, activations
    and matmuls) through the cache, and the float32 reference's rows of the
    same seeded weights: rows that are the system's own greedy continuation
    (so every other step accepts), prompts that have wrapped the window
    rings five times and twice."""
    cfg = ex.ExaoneMoeConfig.tiny()
    params = ex.exaone_moe_init(jax.random.PRNGKey(4), cfg)
    lens = jnp.asarray([43, 21], jnp.int32)
    seed = np.random.default_rng(4).integers(0, cfg.vocab_size, (2, 60))
    rows = greedy_rows(cfg, params, seed, lens, 60)
    return (cfg, params, rows, lens, reference_rows(params, cfg, rows, lens, 8),
            through_the_cache(cfg, params, rows, lens, 8)[0])


def test_the_stated_limit_holds_the_sound_program(served):
    """(``median_l2`` says why the median position and not the largest.)"""
    *_, want, got = served
    limit = CONFIG["tolerance"]["serve_logits_rel_l2"]
    assert median_l2(got, want) < limit / 2


def _faulty_ring_sums(fault):
    """``ops/attention._verify_ring_sums`` with one mask turned: the second
    row not causal on the first (the FIRST row also sees the second's key),
    or a rejected row left readable (the place the first new row takes,
    where a rejected draft's row lies, is read as a live key)."""

    def sums(q, k, v, k_new, v_new, cursor, valid, hd, scale):
        n, r = k.shape[1], q.shape[1]
        idx = jnp.arange(n)
        past = jnp.mod(idx[None, :] - cursor[:, None], n)[:, None, :]
        seen = (idx[None, :] < valid[:, None])[:, None, :] \
            & (past > jnp.arange(r)[None, :, None])
        if fault == "rejected_row_left_readable":
            seen = seen | (past == 0)
        among = jnp.ones((r, r), bool) \
            if fault == "second_row_not_causal_on_the_first" \
            else jnp.tril(jnp.ones((r, r), bool))
        ring = jnp.einsum("srhw,slw->srhl", q, k,
                          preferred_element_type=jnp.float32)
        own = jnp.einsum("srhw,sjw->srhj", q, k_new,
                         preferred_element_type=jnp.float32)
        scores = jnp.concatenate([
            jnp.where(seen[:, :, None, :], ring * hd ** -0.5, -1e30),
            jnp.where(among[None, :, None, :], own * hd ** -0.5, -1e30)], -1)
        weights = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum("srhl,slw->srhw", weights[..., :n],
                          v.astype(jnp.float32)) \
            + jnp.einsum("srhj,sjw->srhw", weights[..., n:],
                         v_new.astype(jnp.float32))

    return sums


TURNED_IN_THE_REFERENCE = {
    "pre_norm_placement": dict(norm_placement="pre"),
    "window_of_129_keys": dict(window=9),   # the toy's window + 1
    "window_of_127_keys": dict(window=7),   # the toy's window - 1
    "global_layers_rotated": dict(rotates=(True,) * 6),
    "window_layers_not_rotated": dict(rotates=(False,) * 6),
}
TURNED_IN_THE_SYSTEM = ["second_row_not_causal_on_the_first",
                        "rejected_row_left_readable", "float8_weights"]


@pytest.mark.parametrize(
    "control", sorted(TURNED_IN_THE_REFERENCE) + TURNED_IN_THE_SYSTEM)
def test_the_stated_limit_refuses_each_control(served, control, monkeypatch):
    """``serve_logits_rel_l2`` of the benchmark's configuration, at the
    tiny preset in the cell's precision. Each control is one function's
    difference from another, put where it is shortest to write: into the
    REFERENCE the sound system is then held to (each sublayer's norm before
    it, a window one key wider or narrower, the global layers rotated, the
    window layers not) or into the SYSTEM (a verify step whose first row
    sees the second's key, one that reads the row a rejected draft left,
    float8 weights as ``tools/serve_check_many.py --fault fp8_weights``
    rounds them). Every one's MEDIAN position reads over the limit, where
    the sound program's reads under half of it."""
    cfg, params, rows, lens, want, got = served
    limit = CONFIG["tolerance"]["serve_logits_rel_l2"]
    if control in TURNED_IN_THE_REFERENCE:
        # the sound system's rows, held to the turned reference
        want = reference_rows(params, cfg, rows, lens, 8,
                              **TURNED_IN_THE_REFERENCE[control])
    elif control == "float8_weights":
        params = check_tool.rounded(jax.tree.map(jnp.copy, params), 2)
        got = through_the_cache(cfg, params, rows, lens, 8)[0]
    else:
        monkeypatch.setattr(attention, "_verify_ring_sums",
                            _faulty_ring_sums(control))
        got = through_the_cache(cfg, params, rows, lens, 8, fresh=True)[0]
    assert median_l2(got, want) > 1.3 * limit, control


# -- the engine ---------------------------------------------------------------


def _undrafted_bundle(real):
    """The family's bundle without its sixth element: the engine then runs
    ``exaone_moe_decode_step``, one token a slot a step."""
    def bundle(model, config, preset):
        cfg, init, init_cache, chunk, step, _ = real(model, config, preset)
        return (cfg, init, init_cache,
                lambda *a, **k: chunk(*a, **k)[:2], step)
    return bundle


def _bundle_drafting(real, source):
    """The family's bundle with another DRAFT SOURCE behind the same verify
    step: ``oracle`` hands the main stack's own next greedy token (one more
    undrafted step on the new cache, its cache thrown away), so every draft
    is accepted; ``wrong`` hands that token plus one, so none is."""
    def bundle(model, config, preset):
        cfg, init, init_cache, chunk, step, verify = real(model, config,
                                                          preset)

        def drafting(params, cache, tokens, pos, cfg_):
            logits, cache, counted, served, drafts = verify(
                params, cache, tokens, pos, cfg_)
            n = served[:, 0]
            last = jnp.where(n == 2, served[:, 2], served[:, 1])
            nxt = jnp.argmax(step(params, cache, last, pos + n, cfg_)[0],
                             axis=-1).astype(jnp.int32)
            if source == "wrong":
                nxt = jnp.mod(nxt + 1, cfg_.vocab_size)
            return (logits, cache, counted, served.at[:, 3].set(nxt), drafts)

        return cfg, init, init_cache, chunk, step, drafting
    return bundle


# a vocabulary of 12: the module's own drafts, seeded, are right often
# enough that both branches run
SMALL = ex.ExaoneMoeConfig.tiny(vocab_size=12, dtype=F32, param_dtype=F32)
PROMPTS = [np.random.default_rng(1).integers(0, 12, n).tolist()
           for n in (5, 13, 20, 9, 17, 3)]


def _engine(monkeypatch, source, **kw):
    real = llm_engine._model_bundle
    if source == "undrafted":
        monkeypatch.setattr(llm_engine, "_model_bundle",
                            _undrafted_bundle(real))
    elif source != "module":
        monkeypatch.setattr(llm_engine, "_model_bundle",
                            _bundle_drafting(real, source))
    try:
        return llm_engine.LLMEngine(**{**dict(
            model="exaone_moe", config=SMALL, max_batch=3, cache_len=64,
            max_prompt_len=24, prefill_chunk=8, max_new_cap=40), **kw})
    finally:
        monkeypatch.setattr(llm_engine, "_model_bundle", real)


def _serve(eng, prompts, n):
    rids = [eng.llm_submit(p, n) for p in prompts]
    outs, chunks = [], []
    for rid in rids:
        out = []
        while True:
            resp = eng.llm_next(rid, 2.0)
            for c in resp["chunks"]:
                out.extend(c)
                chunks.append(len(c))
            if resp["done"]:
                assert not resp["error"] and not resp["shed"], resp
                break
        outs.append(out)
    return outs, chunks


@pytest.fixture(scope="module")
def undrafted():
    """What the family serves one token a slot a step: six requests on
    three slots, so that slots are recycled."""
    with pytest.MonkeyPatch.context() as patch:
        eng = _engine(patch, "undrafted")
    try:
        outs, chunks = _serve(eng, PROMPTS, 30)
        stats = eng.llm_stats()
    finally:
        eng.shutdown_engine()
    assert set(chunks) == {1} and stats["draft_proposed"] == 0
    assert stats["tokens_out"] == 6 * 30
    # (most follow their context; a seeded toy may fall into a fixed point)
    assert sum(len(set(out)) > 2 for out in outs) >= 4
    return outs


@pytest.mark.parametrize("source", ["module", "oracle", "wrong"])
def test_the_engine_serves_what_it_serves_undrafted_whatever_the_drafts(
        undrafted, source, monkeypatch):
    """The module's drafts (a vocabulary of 12: both branches), an oracle's
    (every one accepted: two tokens a step) and always wrong ones (none
    accepted): token for token the undrafted engine's output, in one decode
    program and one chunk program, the counters saying what happened."""
    eng = _engine(monkeypatch, source)
    try:
        outs, chunks = _serve(eng, PROMPTS, 30)
        stats = eng.llm_stats()
    finally:
        eng.shutdown_engine()
    assert outs == undrafted
    assert stats["compiles"] == {"decode": 1, "prefill": 1}
    assert stats["tokens_out"] == 6 * 30 and stats["draft_depth"] == 1
    proposed, accepted = stats["draft_proposed"], stats["draft_accepted"]
    assert proposed == stats["occupancy_sum"] > 0
    assert set(chunks) <= {1, 2}
    # (a request's FIRST draft is the module's from its prefill, whatever
    # the source: six drafts here are not the source's)
    if source == "oracle":
        assert accepted >= proposed - 6 and 2 in chunks
        assert stats["steps"] < 6 * 30 / 2  # two tokens a step a slot
    elif source == "wrong":
        assert accepted <= 6
    else:
        assert 0 < accepted < proposed and 2 in chunks
    # tokens a step a slot: what the steps yielded over the slots they ran
    assert stats["tokens_out"] - stats["admitted"] \
        <= stats["occupancy_sum"] + accepted


def test_max_tokens_and_the_end_token_inside_a_pair(undrafted, monkeypatch):
    """With every draft accepted a step yields a pair: ``max_tokens`` that
    ends on a pair's first token cuts the second off, an end token that is
    a pair's FIRST ends the stream without the second, one that is its
    second ends it after both: exactly the undrafted stream up to there."""
    eng = _engine(monkeypatch, "oracle")
    try:
        # the first token comes from the prefill; pairs are (1, 2), (3, 4)..
        # A stream in which some pair's first token and some pair's second
        # are each new when they come (an end token ends at its FIRST
        # occurrence)
        new_at = lambda want, at: [i for i in at if want[i] not in want[:i]]
        which = [i for i, want in enumerate(undrafted)
                 if new_at(want, range(1, 12, 2))
                 and new_at(want, range(2, 12, 2))]
        assert which, undrafted
        prompt, want = PROMPTS[which[0]], undrafted[which[0]]
        firsts = new_at(want, range(1, 12, 2))
        seconds = new_at(want, range(2, 12, 2))
        for n in (1, 2, 3, 4, 5, 8):
            got, _ = _serve(eng, [prompt], n)
            assert got == [want[:n]], n
        for i in (firsts[0], seconds[0]):
            eng.eos_token = want[i]
            got, _ = _serve(eng, [prompt], 30)
            assert got == [want[:i + 1]], i
        assert eng.llm_stats()["compiles"] == {"decode": 1, "prefill": 1}
    finally:
        eng.shutdown_engine()


def test_a_cancel_while_a_step_is_in_flight(undrafted, monkeypatch):
    """A drafted request cancelled mid-generation frees its slot at the
    cancel (the in-flight step's pair for it is thrown away), and the
    request that takes the slot over is served what it is served alone."""
    eng = _engine(monkeypatch, "oracle", max_batch=1, prefill_rows=1,
                  step_throttle_s=0.01)
    try:
        active = eng.llm_submit(PROMPTS[2], 40)
        deadline = time.monotonic() + 60.0
        while eng.llm_stats()["steps"] < 2 and time.monotonic() < deadline:
            time.sleep(0.02)
        assert eng.llm_cancel(active) and not eng.llm_cancel(active)
        resp = eng.llm_next(active, timeout_s=2.0)
        assert resp["done"] and resp["error"] == "cancelled"
        eng.step_throttle_s = 0.0
        got, _ = _serve(eng, [PROMPTS[4]], 30)
        assert got == [undrafted[4]]
    finally:
        eng.shutdown_engine()


def test_the_inter_token_event_and_the_ring_wraps(monkeypatch):
    """A two-token chunk is ONE gap and a token that came with its
    neighbour (gap 0), not two tokens a whole step apart; a position that
    CROSSES a multiple of the ring counts one wrap even where a pair jumps
    over it."""
    from ray_tpu.serve import _observability as obs

    events = []
    monkeypatch.setattr(obs, "record_decode_itl",
                        lambda dep, s, n: events.append((s, n)))
    eng = _engine(monkeypatch, "oracle", max_batch=1, cache_len=16,
                  max_prompt_len=8)
    try:
        got, chunks = _serve(eng, [PROMPTS[0]], 30)
        stats = eng.llm_stats()
    finally:
        eng.shutdown_engine()
    assert len(got[0]) == 30 and chunks.count(2) >= 13
    # a prompt of 5 and 29 decoded tokens: positions 5 .. 34 cross 16 and 32
    assert stats["ring_wraps"] == 2
    gaps = [e for e in events if e[0] > 0]
    together = [e for e in events if e[0] == 0.0]
    assert sum(n for _, n in gaps) == len(chunks) - 1   # a chunk, one gap
    assert sum(n for _, n in together) == chunks.count(2)
    assert sum(n for _, n in events) == 29