"""Keye-VL-2.0's language model (``models/keye_vl2.py``) against its plain
reference (``benchmark/reference/keye_vl2.py``) at toy widths on the CPU:
prefill in toy chunks then decode steps through both stacks of rings, logits
in float32, with prompts that end before the selection starts, exactly at it
and well past it, with a chunk boundary inside the crossing, in slots other
than 0 beside a scratch row; the controls that turn an ``assumed`` reading the
other way and must fail; M-RoPE with three equal streams against the
one-stream rotation; one set a token for all heads; a free slot and a padded
row that pick and count nothing; the counters; the share test (eight shares of
the experts add up to the uncut layer); and the cache's two stacks. The
contracts every served family holds (sizes, scopes, the forward pass, the
engine against the reference) are ``tests/test_served_family_contract.py``'s.
Every family's two programs, this one's among them, are held bit for bit by
``tests/test_deepseek_v2.py``'s one table.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import keye_vl2 as kv
from ray_tpu.ops.rotary import rotate
from served_families import FAMILIES, contract_params, moved, rel_l2

ROW = FAMILIES["keye_vl2"]
reference, family, CFG = ROW.reference, ROW.family, ROW.cfg
CONFIG = ROW.CONFIG
to_ref, ref_kwargs = ROW.to_reference, ROW.reference_kwargs
F32 = jnp.float32
TOPK = CFG.index_topk  # 16


@pytest.fixture(scope="module")
def params():
    return contract_params("keye_vl2")


@functools.lru_cache(maxsize=None)
def _serving(cfg, chunk):
    """The whole-window prefill and the step with its sets, each ONE
    compiled program a (configuration, shape) for every test that runs
    them: the parameters are arguments, not constants of the program."""
    return (jax.jit(lambda params, c, prompts, slots, lengths:
                    kv.keye_vl2_prefill(params, c, prompts, slots, lengths,
                                        cfg, chunk=chunk)),
            jax.jit(lambda params, c, t, n: kv.keye_vl2_step_with_sets(
                params, c, t, n, cfg)))


def through_the_cache(cfg, params, tokens, lengths, steps, chunk=8,
                      cache_len=96, padded=64, slots=None, n_slots=None,
                      with_sets=False, fresh=False):
    """The serving functions: the prompts (``tokens[r, :lengths[r]]``) in
    chunks through ``keye_vl2_prefill_chunk``, then ``steps`` decode steps
    fed ``tokens``' continuation, the rows in ``slots`` (the first ones by
    default) of ``n_slots`` (one more than the rows: a scratch row that
    every step computes). -> logits [R, 1 + steps, V] (and every step's
    counters and sets). ``fresh``: traced anew, for a test that watches the
    trace or has turned a function the programs call."""
    r = tokens.shape[0]
    n_slots = n_slots or r + 1
    slots = jnp.arange(r) if slots is None else jnp.asarray(slots)
    prompts = jnp.where(jnp.arange(padded)[None] < lengths[:, None],
                        tokens[:, :padded], 0)
    cache = kv.keye_vl2_init_cache(cfg, n_slots, cache_len)
    prefill, step = (_serving.__wrapped__ if fresh else _serving)(cfg, chunk)
    logits, cache = prefill(params, cache, prompts, slots, lengths)
    out, rows, kept = [logits], jnp.arange(r), []
    for i in range(steps):
        toks = jnp.zeros((n_slots,), jnp.int32).at[slots].set(
            tokens[rows, lengths + i])
        pos = jnp.zeros((n_slots,), jnp.int32).at[slots].set(lengths + i)
        logits, cache, counters, sets, sizes = step(params, cache, toks, pos)
        out.append(logits[slots])
        kept.append((counters, sets, sizes))
    out = jnp.stack(out, axis=1)
    return (out, kept, cache) if with_sets else out


def reference_rows(params, cfg, tokens, lengths, steps, **turned):
    full = ROW.reference_forward(params, cfg, **turned)(tokens)
    rows = jnp.arange(tokens.shape[0])
    return jnp.stack([full[rows, lengths - 1 + i] for i in range(steps + 1)],
                     axis=1)


@pytest.fixture(scope="module")
def tokens():
    return jax.random.randint(jax.random.PRNGKey(1), (3, 80), 0,
                              CFG.vocab_size)


# -- sizes ----------------------------------------------------------------------


@pytest.mark.parametrize("bad", [
    dict(n_head=3), dict(head_dim=15), dict(index_dim=7),
    dict(index_topk=0), dict(top_k=9), dict(experts_held=(4, 8)),
    dict(gains=(("embed", 1.0),))])
def test_the_config_refuses_sizes_that_cannot_be(bad):
    with pytest.raises(ValueError):
        kv.KeyeVL2Config.tiny(**bad)


def test_a_layers_parameters_are_the_files_count():
    """At the published widths with the cell's share: 96,899,456 a layer
    and 852,988,928 in all (the configuration file's arithmetic)."""
    cfg = family.system_config(CONFIG)
    shapes = jax.eval_shape(
        lambda: kv.keye_vl2_init(jax.random.PRNGKey(0), cfg))
    count = lambda tree: sum(int(np.prod(x.shape))
                             for x in jax.tree.leaves(tree))
    assert count(shapes["layers"][0]) == 96_899_456
    assert count(shapes) == 852_988_928 == family.param_count(CONFIG)
    assert {x.dtype for x in jax.tree.leaves(shapes)} \
        == {jnp.dtype(jnp.bfloat16)}


@pytest.mark.parametrize("which", ["tiny", "published"])
def test_the_cache_is_two_stacks_of_rings(which):
    cfg, slots, rows = (CFG, 3, 40) if which == "tiny" else (
        family.system_config(CONFIG), 17, 33792)
    cache = jax.eval_shape(lambda: kv.keye_vl2_init_cache(cfg, slots, rows))
    w = cfg.n_kv_head * cfg.head_dim
    # a token's merged K row and V row side by side in one ring row
    assert cache["kv"].shape == (cfg.n_layer, slots, rows, 2 * w)
    assert cache["idx"].shape == (cfg.n_layer, slots, rows, cfg.index_dim)
    assert sorted(cache["counted"]) == ["prefill_expert_rows"]
    held = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(cache)
               if x.ndim == 4)
    stats = cfg.serving_stats()
    assert held == slots * rows * (stats["kv_bytes_per_token"]
                                   + stats["index_bytes_per_token"])
    if which == "published":
        assert held == 17 * 8 * 33792 * 2176 == family.cache_bytes(
            CONFIG, 17, 33792)
        assert stats == {
            "expert_layers": 8, "experts_held": 16, "sparse_layers": 8,
            "sparse_topk": 2048, "sparse_chunk_select": "xla",
            "kv_bytes_per_token": 8 * 2048,
            "index_bytes_per_token": 8 * 128}
        # the chunk program's arm is the engine's shapes' to say: chunks
        # of 512 over the cell's window take both kernels, a single chunk
        # over no ring, or a ragged one, the XLA arm
        assert [cfg.serving_stats(*at)["sparse_chunk_select"]
                for at in ((512, 32768), (512, 33792), (512, 512),
                           (500, 32768))] == ["kernel", "kernel", "xla",
                                              "xla"]


def test_every_counter_of_the_cache_is_a_buffer_of_its_own():
    """The engine donates every leaf: two leaves in one buffer are a
    buffer donated twice."""
    cache = kv.keye_vl2_init_cache(CFG, 2, 16)
    leaves = jax.tree.leaves(cache)
    assert len({x.unsafe_buffer_pointer() for x in leaves}) == len(leaves)


# -- against the reference ----------------------------------------------------------

# prompts' lengths by what their eight decode steps cross: TOPK is 16
ENDS = {
    "all-rows": (3, 5, 7),              # never selects
    "crossing-in-the-steps": (10, 12, 15),  # the steps cross position 16
    "crossing-in-a-chunk": (17, 20, 23),    # the chunk at 16 selects
    "selecting": (30, 44, 60),          # every chunk past 16 and every step
}


@pytest.mark.parametrize("ends, chunk", [
    *((ends, 8) for ends in sorted(ENDS)), ("crossing-in-a-chunk", 16),
    ("selecting", 16)])
def test_prefill_in_toy_chunks_then_decode_through_the_rings(
        ends, chunk, params, tokens):
    """Logits, float32: the prompt's last position and eight decode steps
    against the reference's full forward over the same tokens."""
    lengths = jnp.asarray(ENDS[ends], jnp.int32)
    got = through_the_cache(CFG, params, tokens, lengths, 8, chunk=chunk)
    want = reference_rows(params, CFG, tokens, lengths, 8)
    assert rel_l2(got, want) < 2e-5


def test_slots_other_than_the_first_beside_a_used_scratch_row(params, tokens):
    lengths = jnp.asarray([21, 9, 40], jnp.int32)
    got = through_the_cache(CFG, params, tokens, lengths, 4, slots=[4, 0, 2],
                            n_slots=6)
    want = reference_rows(params, CFG, tokens, lengths, 4)
    assert rel_l2(got, want) < 2e-5


def test_bfloat16_stays_near_the_reference(tokens):
    """The types the cell computes in, at toy width: the MEDIAN position
    (a turned pick or routing choice moves single positions far at 48
    columns)."""
    cfg = kv.KeyeVL2Config.tiny()
    params = kv.keye_vl2_init(jax.random.PRNGKey(0), cfg)
    lengths = jnp.asarray([12, 30, 50], jnp.int32)
    got = through_the_cache(cfg, params, tokens, lengths, 6)
    want = reference_rows(params, cfg, tokens, lengths, 6)
    err = jnp.linalg.norm(got - want, axis=-1) / jnp.linalg.norm(want,
                                                                 axis=-1)
    assert float(jnp.median(err)) < 0.05
    assert got.dtype == jnp.float32


@pytest.mark.parametrize("lengths", [(600, 300), (129, 640)])
def test_the_chunk_program_through_its_kernel_is_the_references(monkeypatch,
                                                                lengths):
    """The chunk program AS THE CHIP RUNS IT: heads of 128 lanes, chunks of
    128 queries, indexer keys of 64 and a window of 128 + 512 ring rows
    take the Pallas kernels of ``ops/sparse_pick.py`` and
    ``ops/sparse_chunk.py`` (interpret mode here), where the tiny preset's
    widths take the XLA arm. Prompts in five chunks (the
    selection starts inside the second; one prompt ends inside a chunk and
    its row runs on padded), then decode steps, against the reference's
    full forward, in float32."""
    from ray_tpu.ops import sparse_chunk, sparse_pick

    cfg = kv.KeyeVL2Config.tiny(dtype=F32, param_dtype=F32, n_layer=2,
                                head_dim=128, index_dim=64, index_topk=160)
    assert cfg.serving_stats(128, 640)["sparse_chunk_select"] == "kernel"
    calls, picks = [], []
    kernel, pick = sparse_chunk.sparse_chunk_attention, sparse_pick.sparse_pick
    monkeypatch.setattr(
        sparse_chunk, "sparse_chunk_attention",
        lambda *a, **k: calls.append(a[0].shape) or kernel(*a, **k))
    monkeypatch.setattr(
        sparse_pick, "sparse_pick",
        lambda *a, **k: picks.append(a[0].shape) or pick(*a, **k))
    # (key 2's draw at these widths turns a pick: the test below)
    params = moved(kv.keye_vl2_init(jax.random.PRNGKey(4), cfg))
    toks = jax.random.randint(jax.random.PRNGKey(3), (2, 648), 0,
                              cfg.vocab_size)
    lengths = jnp.asarray(lengths, jnp.int32)
    got = through_the_cache(cfg, params, toks, lengths, 3, chunk=128,
                            cache_len=1024, padded=640, fresh=True)
    # a call a row a layer in every trace of the chunk
    assert len(calls) >= 4 and set(calls) == {(128, 4, 128)}
    # and a call of the picking kernel before each: (C, J, dI)
    assert len(picks) == len(calls) and set(picks) == {(128, 3, 64)}
    want = reference_rows(params, cfg, toks, lengths, 3)
    assert rel_l2(got, want) < 2e-4


def test_the_pick_that_key_2_turns_lies_at_its_threshold_in_both_arms(
        monkeypatch):
    """Why the test above draws its parameters from key 4: at these widths
    key 2's hold a near-tie. In the first layer the query at position 502
    of the first prompt scores keys 52 and 316 within 1e-5 of each other
    (of the scores' scale; 1e-6 apart at 29) and they are its 160th and
    161st: which of them is picked is float32's rounding to say, and the
    reference's own changes with how it is compiled. The kernels and the
    XLA arm pick the SAME sets; in that layer they are the reference's but
    for, at most, those two keys of that query. (Where the pick is turned,
    the next layer's queries that read row 502 stand 8.8e-4 from the
    reference, in both arms alike and in the parent's programs too: my
    runs, PR 61.)"""
    from ray_tpu.ops import sparse_chunk

    cfg = kv.KeyeVL2Config.tiny(dtype=F32, param_dtype=F32, n_layer=2,
                                head_dim=128, index_dim=64, index_topk=160)
    params = moved(kv.keye_vl2_init(jax.random.PRNGKey(2), cfg))
    toks = jax.random.randint(jax.random.PRNGKey(3), (2, 640), 0,
                              cfg.vocab_size)
    chunk, window, lengths = 128, 640, jnp.asarray([600, 300], jnp.int32)

    def picked():
        """-> ([layer, R, T, T] bool by position, logits at the ends)"""
        cache = kv.keye_vl2_init_cache(cfg, 3, 1024)
        one = jax.jit(lambda c, t, at, n: kv.keye_vl2_chunk_with_sets(
            params, c, t, jnp.arange(2), at, n, cfg, window=window))
        sets = np.zeros((cfg.n_layer, 2, window, window), bool)
        ends = {}
        for at in range(0, window, chunk):
            logits, cache, masks = one(
                cache, toks[:, at:at + chunk], jnp.full((2,), at, jnp.int32),
                jnp.clip(lengths - at, 0, chunk))
            masks = np.asarray(masks)
            sets[:, :, at:at + chunk, :at] = masks[..., :at]
            sets[:, :, at:at + chunk, at:at + chunk] \
                = masks[..., window - chunk:]
            for row, n in enumerate(np.asarray(lengths)):
                if at < n <= at + chunk:
                    ends[row] = logits[row]
        return sets, jnp.stack([ends[0], ends[1]])

    assert cfg.serving_stats(chunk, window)["sparse_chunk_select"] == "kernel"
    got, logits = picked()
    monkeypatch.setattr(sparse_chunk, "takes_kernel", lambda *a: False)
    xla, logits_too = picked()
    np.testing.assert_array_equal(got, xla)
    assert rel_l2(logits, logits_too) < 2e-4
    _, ref = jax.jit(lambda t: reference.forward(
        to_ref(params, cfg), t, with_sets=True, **ref_kwargs(cfg)))(toks)
    scores, want = (np.asarray(x) for x in ref[0])
    real = np.arange(window)[None] < np.asarray(lengths)[:, None]
    turned = np.argwhere((got[0] != want) & real[:, :, None])
    assert {tuple(x) for x in turned.tolist()} <= {(0, 502, 52), (0, 502, 316)}
    assert got[0, 0, 502].sum() == want[0, 502].sum() == 160
    at = scores[0, 502, :503]
    assert sorted(np.argsort(-at, kind="stable")[159:161]) == [52, 316]
    assert abs(at[52] - at[316]) <= 1e-5 * np.abs(at).max()


CONTROLS = {"indexer_rotary": "none", "indexer_key_norm": "none"}


@pytest.mark.parametrize("key", sorted(CONTROLS))
def test_an_assumed_reading_turned_the_other_way_fails(key, params, tokens):
    """The reference with the indexer's rotation, or its key's LayerNorm,
    left out picks other sets: past the crossing the sound program is far
    from it (and before it, where every row is read, it is not)."""
    lengths = jnp.asarray([30, 44, 60], jnp.int32)
    got = through_the_cache(CFG, params, tokens, lengths, 4)
    turned = reference_rows(params, CFG, tokens, lengths, 4,
                            **{key: CONTROLS[key]})
    assert rel_l2(got, turned) > 0.02
    early = jnp.asarray([3, 5, 7], jnp.int32)
    same = reference_rows(params, CFG, tokens, early, 4,
                          **{key: CONTROLS[key]})
    assert rel_l2(through_the_cache(CFG, params, tokens, early, 4),
                  same) < 2e-5


@pytest.mark.parametrize("key, value", [("indexer_rotary", "half"),
                                        ("indexer_key_norm", "rmsnorm")])
def test_the_reference_refuses_a_reading_it_does_not_know(key, value, params,
                                                          tokens):
    with pytest.raises(ValueError):
        reference.forward(to_ref(params), tokens[:, :8],
                          **ref_kwargs(**{key: value}))


# -- M-RoPE ----------------------------------------------------------------------


@pytest.mark.parametrize("hd, sections, theta", [
    (128, (16, 24, 24), 1e7), (16, (4, 2, 2), 1e4), (64, (32,), 1e6)])
def test_mrope_with_equal_streams_is_the_ordinary_rotation(hd, sections,
                                                           theta):
    """Text: the three position streams are the token's index, so whichever
    stream a frequency reads, it reads the same position; the program's
    one-stream ``ops/rotary.rotate`` is that."""
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 9, 3, hd), F32)
    streams = jnp.broadcast_to(jnp.arange(9), (len(sections), 2, 9))
    got = reference.mrope(x, streams, theta, sections)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(reference.rotary(x, theta)),
                               rtol=1e-6, atol=1e-6)
    pos = jnp.broadcast_to(jnp.arange(9), (2, 9))
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(rotate(x, pos, theta)),
                               rtol=1e-5, atol=1e-5)


def test_mrope_with_unequal_streams_is_not_and_sections_must_cover_a_head():
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 9, 2, 16), F32)
    streams = jnp.stack([jnp.arange(9), jnp.arange(9) // 3,
                         jnp.arange(9) % 3])[:, None]
    got = reference.mrope(x, streams, 1e4, (4, 2, 2))
    assert float(jnp.max(jnp.abs(got - reference.rotary(x, 1e4)))) > 0.05
    # the first section's frequencies read the first stream alone
    np.testing.assert_allclose(
        np.asarray(got[..., :4]),
        np.asarray(reference.rotary(x, 1e4)[..., :4]), rtol=1e-6, atol=1e-6)
    with pytest.raises(ValueError):
        reference.mrope(x, streams, 1e4, (4, 2, 1))
    assert reference.text_positions(2, 5).shape == (3, 2, 5)


# -- the sets ----------------------------------------------------------------------


def test_a_steps_sets_are_the_references_one_a_token_for_all_heads(params,
                                                                   tokens):
    """Every step hands back ONE set a slot a layer ([n_layer, S, topk]
    positions, shared by the layer's heads), of ``min(topk, context)``
    rows, and it is the reference's set for that query."""
    lengths = jnp.asarray([10, 30, 55], jnp.int32)
    _, kept, _ = through_the_cache(CFG, params, tokens, lengths, 8,
                                   with_sets=True)
    _, ref_sets = jax.jit(lambda t: reference.forward(
        to_ref(params), t, with_sets=True, **ref_kwargs()))(tokens[:, :70])
    for i, (_, sets, sizes) in enumerate(kept):
        assert sets.shape == (CFG.n_layer, 4, TOPK)
        assert sizes.shape == (CFG.n_layer, 4)
        for row, n in enumerate(np.asarray(lengths)):
            t = n + i
            for layer in range(CFG.n_layer):
                size = int(sizes[layer, row])
                assert size == min(TOPK, t + 1)
                want = np.nonzero(np.asarray(ref_sets[layer][1][row, t]))[0]
                np.testing.assert_array_equal(
                    np.asarray(sets[layer, row, :size]), want)


@pytest.mark.parametrize("lengths", [(10, 30, 55), (16, 17, 64)])
def test_a_chunks_sets_are_the_references_and_a_padded_query_picks_nothing(
        lengths, params, tokens):
    """``keye_vl2_chunk_with_sets`` hands back what each query of a chunk
    picked, a layer: over the ring rows (row = position) and then the
    chunk's own. Every real query's set is the reference's for its
    position, of ``min(topk, position + 1)`` rows; a query past its
    prompt's end (a last chunk's padding, a prompt that ended earlier)
    picks nothing."""
    lengths = np.asarray(lengths, np.int32)
    chunk, window = 8, 64
    _, ref_sets = jax.jit(lambda t: reference.forward(
        to_ref(params), t, with_sets=True, **ref_kwargs()))(tokens[:, :64])
    want = np.stack([np.asarray(sets) for _, sets in ref_sets])
    cache = kv.keye_vl2_init_cache(CFG, 4, 96)
    one = jax.jit(lambda c, t, at, n: kv.keye_vl2_chunk_with_sets(
        params, c, t, jnp.arange(3), at, n, CFG, window=window))
    for at in range(0, window, chunk):
        _, cache, masks = one(
            cache, tokens[:, at:at + chunk], jnp.full((3,), at, jnp.int32),
            jnp.clip(jnp.asarray(lengths) - at, 0, chunk))
        masks = np.asarray(masks)
        assert masks.shape == (CFG.n_layer, 3, chunk, window)
        # ring row = position; the chunk's own rows stand after the ring's
        by_position = np.zeros((CFG.n_layer, 3, chunk, window), bool)
        by_position[..., :at] = masks[..., :at]
        by_position[..., at:at + chunk] = masks[..., window - chunk:]
        assert not masks[..., at:window - chunk].any()
        for row, n in enumerate(lengths):
            for i in range(chunk):
                got = by_position[:, row, i]
                if at + i >= n:
                    assert not got.any()
                    continue
                assert (got.sum(-1) == min(TOPK, at + i + 1)).all()
                np.testing.assert_array_equal(got, want[:, row, at + i])


def test_a_free_slot_picks_nothing_and_counts_nothing(params, tokens):
    """The scratch row and a free slot stand at position 0: their sets are
    empty and the step's counters count the live slots alone."""
    lengths = jnp.asarray([20, 33], jnp.int32)
    _, kept, _ = through_the_cache(CFG, params, tokens[:2], lengths, 3,
                                   slots=[0, 3], n_slots=5, with_sets=True)
    for i, (counters, _, sizes) in enumerate(kept):
        sizes = np.asarray(sizes)
        assert (sizes[:, [1, 2, 4]] == 0).all()
        assert (sizes[:, [0, 3]] == TOPK).all()
        assert int(counters["sparse_keys_selected"]) \
            == CFG.n_layer * 2 * TOPK
        assert int(counters["sparse_keys_eligible"]) \
            == CFG.n_layer * int(jnp.sum(lengths + i + 1))


@pytest.mark.parametrize("lengths", [(5, 9), (16, 17), (40, 64), (1, 33)])
def test_the_counters_add_up(lengths, params, tokens):
    """A row-layer of a step picks ``min(topk, context)`` of ``context``
    eligible keys, summed over its live slots; the chunk program counts the
    pairs its experts took of a prompt's real rows."""
    lengths = jnp.asarray(lengths, jnp.int32)
    _, kept, cache = through_the_cache(CFG, params, tokens[:2], lengths, 2,
                                       with_sets=True)
    counted = {k: int(v) for k, v in cache["counted"].items()}
    assert counted["prefill_expert_rows"] \
        == CFG.n_layer * CFG.top_k * int(jnp.sum(lengths))
    for i, (counters, _, _) in enumerate(kept):
        contexts = np.asarray(lengths) + i + 1
        assert int(counters["sparse_keys_eligible"]) \
            == CFG.n_layer * contexts.sum()
        assert int(counters["sparse_keys_selected"]) \
            == CFG.n_layer * np.minimum(contexts, TOPK).sum()
        assert int(counters["expert_rows"]) == CFG.n_layer * 3 * CFG.top_k


def test_a_padded_rows_keys_are_never_picked(params, tokens):
    """A prompt that ends inside a chunk leaves garbage past its end; the
    steps that follow see rows up to their own position only, so the
    logits are the reference's whatever the padded rows held (a prompt of
    19 in chunks of 8: five padded rows)."""
    lengths = jnp.asarray([19], jnp.int32)
    noisy = tokens[:1].at[:, 19:24].set(7)  # what the padded lanes carry
    got = through_the_cache(CFG, params, tokens[:1], lengths, 6)
    also = through_the_cache(CFG, params, noisy.at[:, 19:].set(
        tokens[:1, 19:]), lengths, 6)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(also))
    assert rel_l2(got, reference_rows(params, CFG, tokens[:1], lengths,
                                      6)) < 2e-5


# -- the share of a deployment ----------------------------------------------------


def test_the_eight_shares_add_up_to_the_uncut_layer(tokens):
    """One layer at toy width with 8 of 8 experts, against eight programs
    that each hold ONE expert (ids 0, 1, ...): attention, the indexer and
    the router are computed alike by all and counted once, the shares'
    expert outputs add up to the uncut reference's layer."""
    whole = kv.KeyeVL2Config.tiny(dtype=F32, param_dtype=F32, n_layer=1)
    params = moved(kv.keye_vl2_init(jax.random.PRNGKey(4), whole))
    toks = tokens[:2, :24]
    want = ROW.reference_forward(params, whole)(toks)
    layer = params["layers"][0]

    def hidden(cfg, p):  # the stream after the layer, before the last norm
        x, _, _ = kv._rows(p, toks, jnp.full((2,), 24, jnp.int32), cfg,
                           kv.keye_vl2_init_cache(cfg, 2, 24), jnp.arange(2),
                           jnp.zeros((2,), jnp.int32), 24)
        return x

    def share(first):
        cfg = kv.KeyeVL2Config.tiny(dtype=F32, param_dtype=F32, n_layer=1,
                                    experts_held=(first, 1))
        p = {**params, "layers": [{**layer,
                                   "w1": layer["w1"][first:first + 1],
                                   "w2": layer["w2"][first:first + 1]}]}
        return cfg, p

    # what every share computes alike: the stream with NO expert's part
    base_cfg, base = share(0)
    base = {**base, "layers": [{**base["layers"][0],
                                "w2": jnp.zeros_like(
                                    base["layers"][0]["w2"])}]}
    common = hidden(base_cfg, base)
    total = common + sum(hidden(*share(e)) - common for e in range(8))
    got = kv._head(total, params, whole)
    assert rel_l2(got, want) < 2e-5
    one = kv._head(hidden(*share(0)), params, whole)
    assert rel_l2(one, want) > 1e-3  # a share alone is not the layer


def test_a_share_is_the_reference_given_the_same_share(tokens):
    """Experts 2-5 of 8 held: the program and the reference, handed the
    same four experts and ``first_expert`` 2, agree."""
    cfg = kv.KeyeVL2Config.tiny(dtype=F32, param_dtype=F32,
                                experts_held=(2, 4))
    params = moved(kv.keye_vl2_init(jax.random.PRNGKey(5), cfg))
    assert params["layers"][0]["w1"].shape[0] == 4
    assert params["layers"][0]["router"].shape[1] == 8
    lengths = jnp.asarray([9, 28, 45], jnp.int32)
    got = through_the_cache(cfg, params, tokens, lengths, 4)
    want = reference_rows(params, cfg, tokens, lengths, 4)
    assert rel_l2(got, want) < 2e-5


# -- the family's file --------------------------------------------------------------


def test_system_config_is_the_files_and_refuses_what_does_not_run():
    cfg = family.system_config(CONFIG)
    assert (cfg.vocab_size, cfg.n_layer, cfg.experts_held, cfg.n_experts,
            cfg.top_k) == (18992, 8, (0, 16), 128, 8)
    assert (cfg.index_heads, cfg.index_dim, cfg.index_topk,
            cfg.rope_theta) == (16, 64, 2048, 1e7)
    assert dict(cfg.gains) == CONFIG["assumed"]["init_gains"]
    for key, value in [("tie_word_embeddings", True),
                       ("norm_topk_prob", False), ("sliding_window", 4096),
                       ("attention_bias", True), ("hidden_act", "gelu"),
                       ("mlp_only_layers", [0]), ("num_local_experts", 128)]:
        with pytest.raises(ValueError):
            family.system_config({**CONFIG, key: value})
    for key, value in [("indexer_rotary", "none"),
                       ("indexer_key_norm", "none"),
                       ("indexer_reads", "query_latent"),
                       ("vision_tower", "served")]:
        with pytest.raises(ValueError):
            family.system_config({**CONFIG, "assumed": {
                **CONFIG["assumed"], key: value}})
    with pytest.raises(ValueError):
        family.system_config({**CONFIG, "rope_scaling": {
            "mrope_section": [16, 24, 8], "rope_type": "default"}})


def test_the_training_functions_refuse():
    for fn, args in ((family.train_flops_per_token, (CONFIG,)),
                     (family.attention_calls, (CONFIG, 4)),
                     (family.build_train, (CONFIG, None))):
        with pytest.raises(NotImplementedError):
            fn(*args)


def test_the_reference_has_a_loss_the_interface_asks_for(params, tokens):
    kwargs = ref_kwargs()
    loss, gnorm = jax.jit(lambda ref, t: reference.loss_and_grad_norm(
        ref, t, **kwargs))(to_ref(params), tokens[:1, :8])
    assert np.isfinite(float(loss)) and float(gnorm) > 0

