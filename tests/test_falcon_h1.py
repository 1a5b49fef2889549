"""Falcon-H1 (``models/falcon_h1.py``) against its plain reference
(``benchmark/reference/falcon_h1.py``) at toy widths on the CPU: prefill in
toy chunks then decode steps through BOTH caches of every layer, a wrapped
ring of rotated keys, each of the fourteen multipliers moved alone, the
controls that must fail the limit the benchmark's configuration states, the
vocabulary's slices against the uncut head, the shared ops this family added
to (``ops/rotary.py``, ``ops/mamba2.py``'s column multipliers). The contracts
every served family holds (sizes, types, scopes, the forward pass, the engine
against the reference) are ``tests/test_served_family_contract.py``'s; its two
programs are held bit for bit by ``tests/test_deepseek_v2.py``'s table.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import falcon_h1 as fh
from ray_tpu.models.prefill import whole_prompts
from ray_tpu.ops import mamba2, rotary
from served_families import (FALCON_H1_SCALARS as SCALARS, FAMILIES,
                             benchmark_file, contract_params, contract_tokens,
                             contract_want, moved, rel_l2)

ROW = FAMILIES["falcon_h1"]
reference, family, CFG = ROW.reference, ROW.family, ROW.cfg
check_tool = benchmark_file("tools", "serve_check_many.py")
CONFIG = ROW.CONFIG
to_ref, ref_kwargs = ROW.to_reference, ROW.reference_kwargs
F32 = jnp.float32
FOURTEEN = [(name, None) for name in SCALARS] \
    + [("ssm_multipliers", i) for i in range(5)] \
    + [("mlp_multipliers", i) for i in range(2)]


@pytest.fixture(scope="module")
def params():
    return contract_params("falcon_h1")


@pytest.fixture(scope="module")
def tokens():
    return contract_tokens("falcon_h1")


@pytest.fixture(scope="module")
def want():
    return contract_want("falcon_h1")


def with_multiplier(cfg, name, index, factor=1.7):
    """``cfg`` with ONE of the fourteen multipliers times ``factor``."""
    value = getattr(cfg, name)
    if index is not None:
        value = tuple(v * factor if i == index else v
                      for i, v in enumerate(value))
    else:
        value = value * factor
    return dataclasses.replace(cfg, **{name: value})


@functools.lru_cache(maxsize=None)
def _serving(cfg, chunk):
    """The prompts' chunks and the step, each ONE compiled program a
    (configuration, shape) for every test that runs them: the parameters
    are arguments, not constants of the program."""
    return (jax.jit(lambda params, c, prompts, slots, lengths: whole_prompts(
        fh.falcon_h1_prefill_chunk, params, c, prompts, slots, lengths, cfg,
        chunk=chunk)),
        jax.jit(lambda params, c, t, n: fh.falcon_h1_decode_step(
            params, c, t, n, cfg)[:2]))


def through_the_cache(cfg, params, tokens, lengths, steps, chunk=8,
                      cache_len=64, window=48):
    """The serving functions: the prompts (``tokens[r, :lengths[r]]``) in
    chunks through ``falcon_h1_prefill_chunk``, then ``steps`` decode steps
    fed ``tokens``' continuation. -> logits [R, 1 + steps, V]."""
    r = tokens.shape[0]
    prompts = jnp.where(jnp.arange(window)[None] < lengths[:, None],
                        tokens[:, :window], 0)
    cache = fh.falcon_h1_init_cache(cfg, r + 1, cache_len)
    prefill, step = _serving(cfg, chunk)
    logits, cache = prefill(params, cache, prompts, jnp.arange(r), lengths)
    out, rows, free = [logits], jnp.arange(r), jnp.zeros(1, jnp.int32)
    for i in range(steps):
        logits, cache = step(
            params, cache, jnp.concatenate([tokens[rows, lengths + i], free]),
            jnp.concatenate([lengths + i, free]))
        out.append(logits[:r])
    return jnp.stack(out, axis=1)


def reference_rows(params, cfg, tokens, lengths, steps, **over):
    full = ROW.reference_forward(params, cfg, **over)(tokens)
    rows = jnp.arange(tokens.shape[0])
    return jnp.stack([full[rows, lengths - 1 + i]
                      for i in range(steps + 1)], axis=1)


def test_weights_are_bfloat16_the_head_is_its_own_and_every_layer_has_both():
    cfg = fh.FalconH1Config.tiny()
    params = fh.falcon_h1_init(jax.random.PRNGKey(0), cfg)
    assert all(x.dtype == jnp.bfloat16 for x in jax.tree.leaves(params))
    assert set(params) == {"embed", "layers", "norm_f", "lm_head"}
    assert params["lm_head"].shape == params["embed"].shape == (256, 48)
    assert not np.array_equal(np.asarray(params["lm_head"], np.float32),
                              np.asarray(params["embed"], np.float32))
    for p in params["layers"]:  # no pattern: attention AND a mixer, each
        assert {"wq", "wk", "wv", "wo", "in_proj", "out_proj", "conv_w",
                "a_log", "w_gate", "w_up", "w_down"} <= set(p)
        assert p["in_proj"].shape == (48, 64 + 64 + 2 * 2 * 24 + 8)
        assert p["wk"].shape == (48, 2 * 16)
    cache = fh.falcon_h1_init_cache(cfg, 3, 16)
    # a K/V ring AND a tail AND a float32 state for each of the 3 layers
    assert cache["k"].shape == cache["v"].shape == (3, 3, 16, 2 * 16)
    assert cache["conv"].shape == (3, 3, 3, cfg.mamba.conv_dim)
    assert [(s.shape, s.dtype) for s in cache["ssm"]] \
        == [((3, 8, 8, 24), jnp.float32)] * 3
    assert cfg.serving_dtypes(params) == jax.tree.map(
        lambda x: x.dtype, params)
    # the seeded draw: each matrix at gain / (sqrt(fan_in) * multiplier)
    std = fh.init_stds(cfg)
    assert std["embed"] == pytest.approx(1.0 / 2.3)
    assert std["wk"] == pytest.approx(1.2 / (48 ** 0.5 * 0.9 * 0.43))
    assert std["in_proj"] == pytest.approx(1.25 / (48 ** 0.5 * 0.7 * 0.45))
    assert std["w_down"] == pytest.approx(1.2 / (80 ** 0.5 * 0.21))
    got = float(jnp.std(params["layers"][0]["w_down"].astype(F32)))
    assert got == pytest.approx(std["w_down"], rel=0.05)


@pytest.mark.parametrize("name, index", FOURTEEN, ids=[
    n if i is None else f"{n}[{i}]" for n, i in FOURTEEN])
def test_each_multiplier_moved_alone_moves_both_alike(params, tokens, want,
                                                      name, index):
    """One of the fourteen times 1.7, in the program's configuration and in
    the reference's arguments: the two still agree, and neither is the
    model it was (a multiplier that one of them dropped, applied twice or
    applied elsewhere would part them)."""
    cfg = with_multiplier(CFG, name, index)
    other = ROW.reference_forward(params, cfg)(tokens)
    got = fh.falcon_h1_forward(params, tokens, cfg)
    assert rel_l2(got, other) < 1e-4
    assert rel_l2(other, want) > 5e-3, (name, index)
    assert rel_l2(got, want) > 5e-3, (name, index)


CHUNK = 4  # a toy chunk; the scan blocks by 4 too


@pytest.mark.parametrize("chunks, length", [(1, 4), (2, 7), (17, 66)])
def test_prefill_in_toy_chunks_then_decode_through_the_cache(chunks,
                                                             length):
    """A prompt of 1, 2 and 17 chunks (the second and third end inside a
    chunk) through the chunk program, then five decode steps through both
    caches of every layer, against the reference's full forward: logits at
    the prompt's last token and after every step."""
    assert -(-length // CHUNK) == chunks
    cfg = fh.FalconH1Config.tiny(dtype=F32, param_dtype=F32, chunk_size=4)
    params = moved(fh.falcon_h1_init(jax.random.PRNGKey(2), cfg))
    steps, window = 5, 72
    row = jnp.asarray(np.random.default_rng(length).integers(
        0, cfg.vocab_size, (1, length + steps), dtype=np.int32))
    want = ROW.reference_forward(params, cfg)(row)
    cache = fh.falcon_h1_init_cache(cfg, 2, window + 8)
    chunk = jax.jit(lambda c, t, at, n: fh.falcon_h1_prefill_chunk(
        params, c, t, jnp.ones(1, jnp.int32), at, n, cfg, window=window))
    for at in range(0, length, CHUNK):
        piece = np.zeros((1, CHUNK), np.int32)
        n = min(CHUNK, length - at)
        piece[0, :n] = np.asarray(row)[0, at:at + n]
        logits, cache = chunk(
            cache, jnp.asarray(piece), jnp.full(1, at, jnp.int32),
            jnp.full(1, n, jnp.int32))
    out = [logits[0]]
    step = jax.jit(lambda c, t, n: fh.falcon_h1_decode_step(
        params, c, t, n, cfg)[:2])
    for i in range(steps):
        toks = jnp.zeros(2, jnp.int32).at[1].set(row[0, length + i])
        pos = jnp.zeros(2, jnp.int32).at[1].set(length + i)
        logits, cache = step(cache, toks, pos)
        out.append(logits[1])
    assert rel_l2(jnp.stack(out), want[0, length - 1:]) < 2e-4


def test_the_whole_window_form_serves_rows_of_different_lengths(params,
                                                                tokens,
                                                                want):
    """``falcon_h1_prefill``'s loop (what the benchmark's reference check
    calls; here in four chunks of 8): three rows of different lengths in
    one window, then decode."""
    lens = jnp.asarray([17, 32, 5], jnp.int32)
    got = through_the_cache(CFG, params, tokens, lens, steps=4, window=32)
    rows = jnp.arange(3)
    ref = jnp.stack([want[rows, lens - 1 + s] for s in range(5)], axis=1)
    assert rel_l2(got, ref) < 2e-4


def _windowed_attention(window):
    """The reference's attention over the last ``window`` keys only (the
    query's own included): what a ring of ``window`` rows holds."""
    def attention(p, u, *, n_head, n_kv_head, head_dim, rope_theta,
                  key_multiplier):
        r, t, _ = u.shape
        rep = n_head // n_kv_head
        w = lambda x: x.astype(F32)
        q = reference.rotary((u @ w(p["q_proj"])).reshape(
            r, t, n_head, head_dim), rope_theta)
        k = reference.rotary(((u @ w(p["k_proj"])) * key_multiplier).reshape(
            r, t, n_kv_head, head_dim), rope_theta)
        v = (u @ w(p["v_proj"])).reshape(r, t, n_kv_head, head_dim)
        k, v = (jnp.repeat(x, rep, axis=2) for x in (k, v))
        scores = jnp.einsum("rihd,rjhd->rhij", q, k) / head_dim ** 0.5
        i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
        scores = jnp.where((j <= i) & (j > i - window), scores, -jnp.inf)
        out = jnp.einsum("rhij,rjhd->rihd", jax.nn.softmax(scores, -1), v)
        return out.reshape(r, t, n_head * head_dim) @ w(p["o_proj"])
    return attention


def test_a_wrapped_ring_of_rotated_keys_is_a_window(params, tokens,
                                                    monkeypatch):
    """Keys are rotated at their TRUE positions before they are stored, so
    a ring of 16 rows that has wrapped (positions up to 39) gives what the
    reference gives when each query sees its last 16 keys: the rotation's
    score depends on the distance alone, not on the row a key lies in. The
    state-space branch beside it forgets nothing."""
    ring, length, steps = 16, 12, 27
    lens = jnp.full(3, length, jnp.int32)
    got = through_the_cache(CFG, params, tokens, lens, steps=steps,
                            chunk=4, cache_len=ring, window=12)
    whole = reference_rows(params, CFG, tokens, lens, steps)
    monkeypatch.setattr(reference, "attention", _windowed_attention(ring))
    window = reference_rows(params, CFG, tokens, lens, steps)
    assert length + steps > 2 * ring  # wrapped, and wrapped again
    assert rel_l2(got, window) < 2e-4
    # before the wrap the window is everything; after it, it is not
    assert rel_l2(window[:, :ring - length], whole[:, :ring - length]) < 1e-5
    assert rel_l2(window[:, -4:], whole[:, -4:]) > 1e-2


# -- the controls: what the configuration's limit must refuse -----------------


def _without(params, leaf):
    return {**params, "layers": [{**p, leaf: jnp.zeros_like(p[leaf])}
                                 for p in params["layers"]]}


@pytest.fixture(scope="module")
def served():
    """The tiny preset AS THE CELL COMPUTES (bfloat16 weights, activations
    and matmuls, a float32 state) through the cache, and the float32
    reference's rows of the same seeded weights."""
    cfg = fh.FalconH1Config.tiny()
    params = fh.falcon_h1_init(jax.random.PRNGKey(4), cfg)
    tokens = jnp.asarray(np.random.default_rng(4).integers(
        0, cfg.vocab_size, (2, 60), dtype=np.int32))
    lens = jnp.asarray([43, 21], jnp.int32)
    return cfg, params, tokens, lens, reference_rows(
        params, cfg, tokens, lens, 6)


def test_the_stated_limit_holds_the_sound_program(served):
    cfg, params, tokens, lens, want = served
    limit = CONFIG["tolerance"]["serve_logits_rel_l2"]
    got = through_the_cache(cfg, params, tokens, lens, steps=6)
    assert rel_l2(got, want) < limit / 1.5


@pytest.mark.parametrize("control", ["no_ssm_branch", "no_attention_branch",
                                     "mup_vector_on_wrong_columns",
                                     "float8_weights"])
def test_the_stated_limit_refuses_each_control(served, control):
    """``serve_logits_rel_l2`` of the benchmark's configuration, at the
    tiny preset in the cell's precision: a program without its state-space
    branch, without its attention branch, with ``ssm_multipliers`` laid
    over the wrong columns of ``in_proj``'s output, or with float8 weights
    (every matrix rounded to e4m3 per output channel, as
    ``tools/serve_check_many.py --fault fp8_weights`` rounds them) reads
    over the limit, each by a wide margin."""
    cfg, params, tokens, lens, want = served
    limit = CONFIG["tolerance"]["serve_logits_rel_l2"]
    if control == "no_ssm_branch":
        params = _without(params, "out_proj")
    elif control == "no_attention_branch":
        params = _without(params, "wo")
    elif control == "mup_vector_on_wrong_columns":
        m = cfg.ssm_multipliers  # (z, x, B, C, dt) read as (x, B, C, dt, z)
        cfg = dataclasses.replace(cfg, ssm_multipliers=m[1:] + m[:1])
    else:
        params = check_tool.rounded(jax.tree.map(jnp.copy, params), 2)
    got = through_the_cache(cfg, params, tokens, lens, steps=6)
    assert rel_l2(got, want) > 2 * limit, control


def test_the_eight_vocabulary_slices_add_up_to_the_uncut_head(params,
                                                              tokens):
    """The cut ties to the model: token ids drawn from this chip's slice
    (the first 32 of 256 rows of ``embed_tokens``), the head's table in
    eight row slices, one a chip: the slices' logits side by side are the
    uncut head's, by the reference and by the program alike."""
    v = CFG.vocab_size // 8
    ids = tokens % v  # ids of the slice held here
    uncut = ROW.reference_forward(params, CFG)(ids)
    small = dataclasses.replace(CFG, vocab_size=v)
    parts, served = [], []
    for s in range(8):
        share = {**params, "embed": params["embed"][:v],
                 "lm_head": params["lm_head"][s * v:(s + 1) * v]}
        parts.append(ROW.reference_forward(share, small)(ids))
        served.append(fh.falcon_h1_forward(share, ids, small))
    np.testing.assert_allclose(np.asarray(jnp.concatenate(parts, -1)),
                               np.asarray(uncut), rtol=1e-5, atol=1e-5)
    assert rel_l2(jnp.concatenate(served, -1), uncut) < 1e-4
    # this chip's share is the first slice: a model of 32 tokens
    assert parts[0].shape == (3, 40, v)


# -- the ops this family added to ---------------------------------------------


def test_the_rotary_helper_is_the_private_copies_mathematics():
    """``ops/rotary.rotate`` at one position a slot and at a chunk's
    positions against ``models/llama.py``'s private copies (the halves
    against each other)."""
    from ray_tpu.models import llama

    x = jax.random.normal(jax.random.PRNGKey(0), (3, 5, 4, 16))
    pos = jnp.asarray([7, 0, 123], jnp.int32)
    np.testing.assert_allclose(
        np.asarray(rotary.rotate(x[:, 0], pos, 1e4)),
        np.asarray(llama._rope_at(x[:, 0], pos, 1e4)), atol=2e-6)
    np.testing.assert_allclose(
        np.asarray(rotary.rotate(x, jnp.arange(5)[None].repeat(3, 0), 1e4)),
        np.asarray(llama._rope(x, 1e4)), atol=2e-6)
    chunk = jnp.arange(5)[None] + pos[:, None]
    assert rotary.rotate(x.astype(jnp.bfloat16), chunk, 1e4).dtype \
        == jnp.bfloat16
    assert rotary.inv_freq(128, 1e11).dtype == np.float32
    assert rotary.inv_freq(128, 1e11)[-1] == pytest.approx(
        1e11 ** (-126 / 128), rel=1e-6)
    with pytest.raises(ValueError, match="pairs"):
        rotary.rotate(x[..., :15], chunk, 1e4)


def test_a_rotated_score_depends_on_the_distance_alone():
    q, k = jax.random.normal(jax.random.PRNGKey(1), (2, 1, 1, 32))
    score = lambda i, j: float(jnp.sum(
        rotary.rotate(q, jnp.asarray([i]), 1e4)
        * rotary.rotate(k, jnp.asarray([j]), 1e4)))
    assert score(5, 2) == pytest.approx(score(4005, 4002), abs=2e-3)
    assert abs(score(5, 2) - score(5, 4)) > 1e-2


def test_the_mixers_column_multipliers_in_the_step_and_in_rows_alike():
    """``Mamba2Dims.in_multipliers``: ``in_proj``'s output columns times
    their part's factor, in ``mamba_step`` and ``mamba_rows``: the same as
    the factors folded into ``in_proj``'s columns with none stated. And
    with none stated nothing is traced: the two hybrids' programs are the
    ones they were."""
    dims = dataclasses.replace(CFG.mamba)
    plain = dataclasses.replace(dims, in_multipliers=None)
    vector = mamba2.in_multiplier_vector(dims)
    assert vector.shape == (dims.in_width,) and vector.dtype == np.float32
    m, di, gn = dims.in_multipliers, dims.d_inner, dims.groups * dims.state
    assert [float(vector[i]) for i in (0, di, 2 * di, 2 * di + gn,
                                       2 * di + 2 * gn)] \
        == pytest.approx(list(m))
    assert float(vector[-1]) == pytest.approx(m[4])
    keys = iter(jax.random.split(jax.random.PRNGKey(3), 8))
    p = mamba2.mixer_init(keys, 48, dims, F32, lambda k, s, std, dt: (
        jax.random.normal(k, s, F32) * std).astype(dt), 0.3, in_std=0.4)
    folded = {**p, "in_proj": p["in_proj"] * vector}
    y = jax.random.normal(jax.random.PRNGKey(4), (2, 11, 48))
    lens = jnp.asarray([11, 6])
    got = mamba2.mamba_rows(p, y, lens, dims)
    want = mamba2.mamba_rows(folded, y, lens, plain)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)
    other = mamba2.mamba_rows(p, y, lens, plain)[0]
    assert float(jnp.abs(other - want[0]).max()) > 1e-2
    tail = jnp.zeros((3, 2, dims.conv_dim))
    state = jax.random.normal(jax.random.PRNGKey(5),
                              (2, dims.heads, dims.head_dim, dims.state))
    got = mamba2.mamba_step(p, y[:, 0], tail, state, dims)
    want = mamba2.mamba_step(folded, y[:, 0], tail, state, plain)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)
    traced = lambda d: str(jax.make_jaxpr(
        lambda y: mamba2.mamba_step(p, y, tail, state, d)[0])(y[:, 0]))
    assert traced(plain).count(" mul ") + 1 == traced(dims).count(" mul ")

