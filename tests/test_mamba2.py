"""The Mamba-2 mixer both served hybrids call (``ops/mamba2.py``): its
sizes at the two published configurations, rows against the two plain
references' token-by-token recurrence (one group and several), a prompt in
pieces against the prompt whole, single steps against rows, the scan's
block moving nothing, and the two families calling these very functions.
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.loading import load_module
from ray_tpu.models import granite_hybrid as gh
from ray_tpu.models import nemotron_h as nh
from ray_tpu.ops import mamba2

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
F32 = jnp.float32
D = 32
DIMS = {g: mamba2.Mamba2Dims(heads=8, head_dim=4, groups=g, state=8,
                             kernel=4, block=4, eps=1e-5, dtype=F32)
        for g in (1, 2)}
REFERENCES = {1: "granite_hybrid.py", 2: "nemotron_h.py"}
REF_NAMES = {"in_proj": "in_proj", "conv_w": "conv_w", "conv_b": "conv_b",
             "dt_bias": "dt_bias", "a_log": "A_log", "d_skip": "D",
             "gate_norm": "norm_w", "out_proj": "out_proj"}
groups = pytest.mark.parametrize("g", [1, 2], ids=["one_group", "two_groups"])


def _normal(key, shape, std, dtype):
    return (jax.random.normal(key, shape, F32) * std).astype(dtype)


def _mixer(g, seed=0):
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 16))
    p = mamba2.mixer_init(keys, D, DIMS[g], F32, _normal, 0.1)
    noise = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 16))
    return {k: v + 0.05 * jax.random.normal(next(noise), v.shape, F32)
            for k, v in p.items()}


def _rows(r=2, t=19, seed=3):
    return jax.random.normal(jax.random.PRNGKey(seed), (r, t, D), F32)


def test_the_sizes_of_the_two_published_mixers():
    granite = gh.GraniteHybridConfig().mamba
    nemotron = nh.NemotronHConfig().mamba
    assert (granite.d_inner, granite.conv_dim, granite.in_width) \
        == (8192, 8448, 16768)
    assert (nemotron.d_inner, nemotron.conv_dim, nemotron.in_width) \
        == (8192, 8192 + 2 * 8 * 128, 2 * 8192 + 2 * 8 * 128 + 128)
    assert (granite.groups, granite.block) == (1, 256)
    assert (nemotron.groups, nemotron.block) == (8, 128)
    assert granite.state_dtype == nemotron.state_dtype == jnp.float32
    assert granite.dtype == nemotron.dtype == jnp.bfloat16
    # a float32 state of 4 MiB a layer a slot in both
    assert granite.heads * granite.head_dim * granite.state * 4 == 4_194_304


@groups
def test_rows_agree_with_the_references_recurrence(g):
    """``mamba_rows`` (the blocked scan) against the plain reference of
    the family that publishes ``g`` groups: a scan over tokens."""
    reference = load_module(os.path.join(REPO, "benchmark", "reference",
                                         REFERENCES[g]))
    dims, p, y = DIMS[g], _mixer(g), _rows()
    want = reference.mamba2(
        {REF_NAMES[k]: v for k, v in p.items()}, y, eps=dims.eps,
        mamba_heads=dims.heads, mamba_head_dim=dims.head_dim,
        n_groups=g, ssm_state=dims.state)
    lengths = jnp.full((2,), y.shape[1], jnp.int32)
    got, tail, state = mamba2.mamba_rows(p, y, lengths, dims)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    assert tail.shape == (dims.kernel - 1, 2, dims.conv_dim)
    assert state.shape == (2, dims.heads, dims.head_dim, dims.state)
    assert state.dtype == jnp.float32


@groups
@pytest.mark.parametrize("block", [1, 4, 7, 256])
def test_the_scans_block_moves_no_result(g, block):
    p, y = _mixer(g), _rows()
    lengths = jnp.asarray([19, 11], jnp.int32)
    want = mamba2.mamba_rows(p, y, lengths, DIMS[g])
    got = mamba2.mamba_rows(p, y, lengths,
                            dataclasses.replace(DIMS[g], block=block))
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


@groups
def test_a_prompt_in_pieces_leaves_what_the_prompt_whole_leaves(g):
    """Pieces of 5 continue from the tail and the state the piece before
    left; the last piece holds 4 real tokens and padding."""
    dims, p, y = DIMS[g], _mixer(g), _rows(r=1, t=19)
    whole, tail_w, state_w = mamba2.mamba_rows(
        p, y, jnp.asarray([19], jnp.int32), dims)
    tail = state = None
    outs = []
    for at in range(0, 19, 5):
        n = min(5, 19 - at)
        piece = jnp.pad(y[:, at:at + n], ((0, 0), (0, 5 - n), (0, 0)))
        out, tail, state = mamba2.mamba_rows(
            p, piece, jnp.asarray([n], jnp.int32), dims,
            None if tail is None else tail.swapaxes(0, 1), state)
        outs.append(out[:, :n])
    np.testing.assert_allclose(np.asarray(jnp.concatenate(outs, 1)),
                               np.asarray(whole), rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(tail), np.asarray(tail_w),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(state), np.asarray(state_w),
                               rtol=1e-4, atol=1e-6)


@groups
def test_single_steps_are_the_rows_recurrence(g):
    dims, p, y = DIMS[g], _mixer(g), _rows(r=3, t=9)
    want, tail_w, state_w = mamba2.mamba_rows(
        p, y, jnp.full((3,), 9, jnp.int32), dims)
    cache = mamba2.init_state(dims, 2, 3)
    conv, ssm = cache["conv"], cache["ssm"][1]
    for i in range(9):
        out, conv, ssm = mamba2.step_through_cache(p, y[:, i], conv, ssm, 1,
                                                   dims)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want[:, i]),
                                   rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(conv[1]), np.asarray(tail_w),
                               rtol=1e-5, atol=1e-6)
    assert not np.asarray(conv[0]).any()  # the other layer's tail untouched
    np.testing.assert_allclose(np.asarray(ssm), np.asarray(state_w),
                               rtol=1e-4, atol=1e-6)


@groups
def test_a_chunk_through_the_cache_begins_or_goes_on_by_slot(g):
    """``rows_through_cache``: a row whose ``goes_on`` is False begins
    anew whatever its slot held; one that goes on continues from it; other
    slots keep what they held, bit for bit."""
    dims, p = DIMS[g], _mixer(g)
    y = _rows(r=2, t=6)
    rng = np.random.default_rng(5)
    held = jax.tree.map(lambda a: jnp.asarray(rng.normal(size=a.shape),
                                              a.dtype),
                        mamba2.init_state(dims, 1, 4))
    slots, lengths = jnp.asarray([2, 0]), jnp.asarray([6, 4], jnp.int32)
    out, conv, ssm = mamba2.rows_through_cache(
        p, y, lengths, held["conv"], held["ssm"][0], 0, slots,
        jnp.asarray([False, True]), dims)
    fresh, _, state_f = mamba2.mamba_rows(p, y[:1], lengths[:1], dims)
    on, _, state_o = mamba2.mamba_rows(
        p, y[1:], lengths[1:], dims,
        held["conv"][0, :, 0][None], held["ssm"][0][:1])
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(fresh[0]),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(out[1]), np.asarray(on[0]),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(ssm[2]), np.asarray(state_f[0]),
                               rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(np.asarray(ssm[0]), np.asarray(state_o[0]),
                               rtol=1e-4, atol=1e-6)
    for other in (1, 3):
        np.testing.assert_array_equal(np.asarray(ssm[other]),
                                      np.asarray(held["ssm"][0][other]))
        np.testing.assert_array_equal(np.asarray(conv[0, :, other]),
                                      np.asarray(held["conv"][0, :, other]))


@pytest.mark.parametrize("model", [nh, gh], ids=["nemotron_h",
                                                 "granite_hybrid"])
def test_both_hybrids_call_this_mixer(model, monkeypatch):
    """No family keeps a scan of its own: each one's decode step and
    chunk run ``ops/mamba2.py``'s functions, once a Mamba layer."""
    assert model.mamba2 is mamba2
    for name in ("_mamba_step", "_ssd_scan", "_mamba_rows", "mamba_step",
                 "ssd_scan", "mamba_rows"):
        assert not hasattr(model, name), name
    calls = {"mamba_step": 0, "ssd_scan": 0}
    for name in calls:
        real = getattr(mamba2, name)

        def counted(*a, _real=real, _name=name, **kw):
            calls[_name] += 1
            return _real(*a, **kw)

        monkeypatch.setattr(mamba2, name, counted)
    if model is nh:
        cfg = nh.NemotronHConfig.tiny()
        params = nh.nemotron_h_init(jax.random.PRNGKey(0), cfg)
        cache = nh.nemotron_h_init_cache(cfg, 2, 16)
        step, chunk = nh.nemotron_h_decode_step, nh.nemotron_h_prefill_chunk
        n_mamba = cfg.count("M")
    else:
        cfg = gh.GraniteHybridConfig.tiny()
        params = gh.granite_hybrid_init(jax.random.PRNGKey(0), cfg)
        cache = gh.granite_hybrid_init_cache(cfg, 2, 16)
        step = gh.granite_hybrid_decode_step
        chunk = gh.granite_hybrid_prefill_chunk
        n_mamba = cfg.count("mamba")
    zeros = jnp.zeros((2,), jnp.int32)
    jax.eval_shape(lambda c: step(params, c, zeros, zeros, cfg), cache)
    assert calls == {"mamba_step": n_mamba, "ssd_scan": 0}
    one = jnp.zeros((1,), jnp.int32)
    jax.eval_shape(lambda c: chunk(params, c, jnp.zeros((1, 8), jnp.int32),
                                   one, one, one + 8, cfg), cache)
    assert calls == {"mamba_step": n_mamba, "ssd_scan": n_mamba}
