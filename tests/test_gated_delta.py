"""The Gated DeltaNet mixer (``ops/gated_delta.py``): its sizes at the
published configuration, the blocked scan against the plain reference's
token-by-token recurrence (lengths that end inside a block, a carried state,
``lengths`` that differ by row), the block solve against a textbook
triangular solve, a prompt in pieces against the prompt whole, single steps
against rows, the scan's block moving nothing, a chunk through the cache by
slot, the released per-key-head layout, and the convolution it shares with
``ops/mamba2.py``.
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.loading import load_module
from ray_tpu.models import qwen3_next as qn
from ray_tpu.ops import gated_delta as gd
from ray_tpu.ops import mamba2

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
reference = load_module(os.path.join(REPO, "benchmark", "reference",
                                     "qwen3_next.py"))
family = load_module(os.path.join(REPO, "benchmark", "families",
                                  "qwen3_next.py"))
F32 = jnp.float32
D = 32
# value heads twice the key heads, a key head that is not a value head's size
DIMS = gd.GatedDeltaDims(key_heads=2, value_heads=4, key_dim=8, value_dim=12,
                         kernel=4, block=8, eps=1e-6, dtype=F32)
SHAPE = {"key_heads": 2, "value_heads": 4, "key_dim": 8, "value_dim": 12}
REF_NAMES = {"conv_w": "conv_w", "dt_bias": "dt_bias", "a_log": "A_log",
             "gate_norm": "norm_w", "out_proj": "out_proj"}


def _normal(key, shape, std, dtype):
    return (jax.random.normal(key, shape, F32) * std).astype(dtype)


def _mixer(seed=0):
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 16))
    p = gd.mixer_init(keys, D, DIMS, F32, _normal, 0.2, in_std=0.3)
    noise = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 16))
    return {k: v + 0.05 * jax.random.normal(next(noise), v.shape, F32)
            for k, v in p.items()}


def _to_ref(p):
    return {**{REF_NAMES[k]: p[k] for k in REF_NAMES},
            **family.split_released(p, SHAPE)}


def _rows(r=2, t=21, seed=3):
    return jax.random.normal(jax.random.PRNGKey(seed), (r, t, D), F32)


def _reference(p, y, **kw):
    return reference.gated_delta_net(
        _to_ref(p), y, eps=DIMS.eps, key_heads=2, value_heads=4, key_dim=8,
        value_dim=12, **kw)


def test_the_sizes_of_the_published_mixer():
    dims = qn.Qwen3NextConfig().delta
    assert (dims.key_width, dims.value_width, dims.conv_dim,
            dims.qkvz_width, dims.per_key) == (2048, 4096, 8192, 12288, 2)
    assert (dims.kernel, dims.block) == (4, 64)
    assert dims.state_dtype == jnp.float32 and dims.dtype == jnp.bfloat16
    # a float32 matrix a value head: 2 MiB a layer a slot
    assert dims.value_heads * dims.key_dim * dims.value_dim * 4 == 2_097_152
    state = jax.eval_shape(lambda: gd.init_state(dims, 6, 65))
    assert state["conv"].shape == (6, 3, 65, 8192)
    assert [s.shape for s in state["delta"]] == [(65, 32, 128, 128)] * 6
    with pytest.raises(ValueError, match="power of two"):
        dataclasses.replace(dims, block=48)
    with pytest.raises(ValueError, match="divide"):
        dataclasses.replace(dims, value_heads=24)


@pytest.mark.parametrize("t", [1, 5, 8, 21, 24])
def test_rows_agree_with_the_references_recurrence(t):
    """``delta_rows`` (the blocked scan, blocks of 8) against the plain
    reference's scan over tokens, for rows that end inside a block, at its
    edge, and inside the first one."""
    p, y = _mixer(), _rows(t=t)
    want = _reference(p, y)
    got, tail, state = gd.delta_rows(p, y, jnp.full((2,), t, jnp.int32),
                                     DIMS)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)
    assert tail.shape == (3, 2, DIMS.conv_dim)
    assert state.shape == (2, 4, 8, 12) and state.dtype == jnp.float32


def test_the_delta_term_is_what_the_reference_says_it_is():
    """Gated linear attention without the rule (``d_t = beta_t v_t``) is
    another function: the scan holds the term."""
    p, y = _mixer(), _rows()
    got, _, _ = gd.delta_rows(p, y, jnp.full((2,), 21, jnp.int32), DIMS)
    without = _reference(p, y, delta_term=False)
    assert float(jnp.max(jnp.abs(got - without))) \
        > 0.05 * float(jnp.max(jnp.abs(got)))


@pytest.mark.parametrize("n", [1, 2, 8, 64])
def test_the_block_solve_is_a_triangular_solve(n):
    a = jnp.tril(jax.random.normal(jax.random.PRNGKey(n), (3, n, n), F32),
                 -1)
    got = gd._unit_lower_inverse(a)
    want = np.linalg.inv(np.eye(n) + np.asarray(a, np.float64))
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-3,
                               atol=1e-4 * np.abs(want).max())


@pytest.mark.parametrize("block", [1, 2, 4, 16, 32])
def test_the_scans_block_moves_no_result(block):
    p, y = _mixer(), _rows()
    lengths = jnp.asarray([21, 13], jnp.int32)
    want = gd.delta_rows(p, y, lengths, DIMS)
    got = gd.delta_rows(p, y, lengths,
                        dataclasses.replace(DIMS, block=block))
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=2e-5)


def test_lengths_that_differ_by_row_leave_each_rows_own_state():
    """A padded row's tail and state are those after ITS real tokens: the
    row alone, cut to its length, leaves the same."""
    p, y = _mixer(), _rows()
    lengths = jnp.asarray([21, 13], jnp.int32)
    out, tail, state = gd.delta_rows(p, y, lengths, DIMS)
    alone, tail1, state1 = gd.delta_rows(
        p, y[1:, :13], jnp.asarray([13], jnp.int32), DIMS)
    np.testing.assert_allclose(np.asarray(out[1, :13]), np.asarray(alone[0]),
                               rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(state[1]), np.asarray(state1[0]),
                               rtol=2e-4, atol=2e-6)
    np.testing.assert_array_equal(np.asarray(tail[:, 1]),
                                  np.asarray(tail1[:, 0]))
    # no real token: the tail and state given
    given_tail = jnp.ones((2, 3, DIMS.conv_dim), F32)
    given = jnp.ones((2, 4, 8, 12), F32)
    _, tail0, state0 = gd.delta_rows(p, y, jnp.zeros((2,), jnp.int32), DIMS,
                                     given_tail, given)
    np.testing.assert_array_equal(np.asarray(state0), np.asarray(given))
    np.testing.assert_array_equal(np.asarray(tail0),
                                  np.asarray(given_tail.swapaxes(0, 1)))


@pytest.mark.parametrize("cut", [3, 8, 10, 16])
def test_a_prompt_in_pieces_leaves_what_the_prompt_whole_leaves(cut):
    """A carried state and tail: the first ``cut`` tokens, then the rest
    from what they left, against the rows whole (and so, by the test above,
    against the reference's recurrence)."""
    p, y = _mixer(), _rows()
    lengths = jnp.asarray([21, 13], jnp.int32)
    want, tail, state = gd.delta_rows(p, y, lengths, DIMS)
    first = jnp.minimum(lengths, cut)
    o1, t1, s1 = gd.delta_rows(p, y[:, :cut], first, DIMS)
    o2, t2, s2 = gd.delta_rows(p, y[:, cut:], lengths - first, DIMS,
                               t1.swapaxes(0, 1), s1)
    got = jnp.concatenate([o1, o2], axis=1)
    for r, n in enumerate((21, 13)):
        np.testing.assert_allclose(np.asarray(got[r, :n]),
                                   np.asarray(want[r, :n]), rtol=2e-4,
                                   atol=2e-5)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(state), rtol=2e-4,
                               atol=2e-6)
    np.testing.assert_array_equal(np.asarray(t2), np.asarray(tail))
    # the state NOT carried across the boundary is another answer
    o3, _, _ = gd.delta_rows(p, y[:, cut:], lengths - first, DIMS)
    assert float(jnp.max(jnp.abs(o3[0] - o2[0]))) \
        > 0.05 * float(jnp.max(jnp.abs(o2[0])))


def test_single_steps_are_the_rows_recurrence():
    p, y = _mixer(), _rows()
    t = y.shape[1]
    want, tail_w, state_w = gd.delta_rows(
        p, y, jnp.full((2,), t, jnp.int32), DIMS)
    tail = jnp.zeros((3, 2, DIMS.conv_dim), F32)
    state = gd.init_state(DIMS, 1, 2)["delta"][0]
    outs = []
    for i in range(t):
        out, tail, state = gd.delta_step(p, y[:, i], tail, state, DIMS)
        outs.append(out)
    np.testing.assert_allclose(np.asarray(jnp.stack(outs, 1)),
                               np.asarray(want), rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(state), np.asarray(state_w),
                               rtol=2e-4, atol=2e-6)
    np.testing.assert_allclose(np.asarray(tail), np.asarray(tail_w),
                               rtol=1e-6)


def test_a_chunk_through_the_cache_begins_or_goes_on_by_slot():
    """Two rows in slots 3 and 1 of a cache of five: the row that goes on
    continues what its slot holds, the row that begins takes nothing from
    it, both leave their state in their own slot and no other slot moves;
    a decode step then rewrites every slot's state of that layer only."""
    p, y = _mixer(), _rows(t=8)
    cache = gd.init_state(DIMS, 2, 5)
    conv_all = cache["conv"] + 1.0
    delta = [s + 0.5 for s in cache["delta"]]
    lengths = jnp.asarray([8, 5], jnp.int32)
    slots = jnp.asarray([3, 1], jnp.int32)
    goes_on = jnp.asarray([True, False])
    out, conv_new, delta_new = gd.rows_through_cache(
        p, y, lengths, conv_all, delta[1], 1, slots, goes_on, DIMS)
    want_on, tail_on, state_on = gd.delta_rows(
        p, y[:1], lengths[:1], DIMS, jnp.ones((1, 3, DIMS.conv_dim), F32),
        jnp.full((1, 4, 8, 12), 0.5, F32))
    want_new, tail_b, state_b = gd.delta_rows(p, y[1:], lengths[1:], DIMS)
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(want_on[0]),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(out[1]), np.asarray(want_new[0]),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(delta_new[3]),
                               np.asarray(state_on[0]), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(delta_new[1]),
                               np.asarray(state_b[0]), rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(np.asarray(conv_new[1, :, 3]),
                               np.asarray(tail_on[:, 0]), rtol=1e-6)
    for untouched in (0, 2, 4):
        assert float(jnp.max(jnp.abs(delta_new[untouched] - 0.5))) == 0.0
        assert float(jnp.max(jnp.abs(conv_new[1, :, untouched] - 1.0))) == 0.0
    assert float(jnp.max(jnp.abs(conv_new[0] - 1.0))) == 0.0
    out, conv_step, state_step = gd.step_through_cache(
        p, y[0, :5], conv_new, delta_new, 1, DIMS)
    assert out.shape == (5, D) and state_step.shape == delta_new.shape
    assert float(jnp.max(jnp.abs(conv_step[0] - 1.0))) == 0.0
    np.testing.assert_array_equal(np.asarray(conv_step[1, :2]),
                                  np.asarray(conv_new[1, 1:]))


def test_the_released_per_key_head_layout():
    """``in_qkvz``'s columns are laid out per KEY head (q dk | k dk | v
    2 x dv | z 2 x dv) and ``in_ba``'s (b 2 | a 2): column by column the
    program's split is the reference's six plain matrices, value head j
    under key head ``j // 2``."""
    dk, dv, per = 8, 12, 2
    width = 2 * dk + 2 * per * dv
    cols = jnp.arange(2 * width, dtype=F32)[None]  # a row that names columns
    mixed, z = gd.split_qkvz(cols, DIMS)
    head1 = width  # key head 1's first column
    np.testing.assert_array_equal(np.asarray(mixed[0, :2 * dk]), np.r_[
        0:dk, head1:head1 + dk])  # every head's q first
    np.testing.assert_array_equal(np.asarray(mixed[0, 2 * dk:4 * dk]), np.r_[
        dk:2 * dk, head1 + dk:head1 + 2 * dk])  # then every k
    np.testing.assert_array_equal(np.asarray(mixed[0, 4 * dk:]), np.r_[
        2 * dk:2 * dk + per * dv, head1 + 2 * dk:head1 + 2 * dk + per * dv])
    assert z.shape == (1, 4, dv)
    np.testing.assert_array_equal(
        np.asarray(z[0, 2]),  # value head 2 = key head 1's first
        np.arange(head1 + 2 * dk + per * dv, head1 + 2 * dk + per * dv + dv))
    b, a = gd.split_ba(jnp.arange(8, dtype=F32)[None], DIMS)
    np.testing.assert_array_equal(np.asarray(b[0]), [0, 1, 4, 5])
    np.testing.assert_array_equal(np.asarray(a[0]), [2, 3, 6, 7])
    # and the family's map onto the reference's names is that split
    p = _mixer()
    ref = family.split_released(p, SHAPE)
    y = _rows(r=1, t=3)
    mixed, z = gd.split_qkvz(y @ p["in_qkvz"], DIMS)
    want = jnp.concatenate([y @ ref["q_proj"], y @ ref["k_proj"],
                            y @ ref["v_proj"]], axis=-1)
    np.testing.assert_allclose(np.asarray(mixed), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(z.reshape(1, 3, -1)), np.asarray(y @ ref["z_proj"]),
        rtol=1e-5, atol=1e-6)
    b, a = gd.split_ba(y @ p["in_ba"], DIMS)
    np.testing.assert_allclose(np.asarray(b), np.asarray(y @ ref["b_proj"]),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(a), np.asarray(y @ ref["a_proj"]),
                               rtol=1e-5, atol=1e-6)


def test_the_decay_is_drawn_so_that_a_state_lasts():
    """``mixer_init`` draws the step in [1e-3, 1e-1] and ``exp(a_log)`` in
    [1, 16] (``ops/mamba2.mixer_init``'s draw): with ``a = 0`` a head's
    state halves in 0.4 to 700 tokens, most heads in tens; under the
    released initialisation most would forget within one."""
    keys = iter(jax.random.split(jax.random.PRNGKey(0), 16))
    dims = dataclasses.replace(DIMS, key_heads=16, value_heads=32)
    p = gd.mixer_init(keys, D, dims, F32, _normal, 0.2)
    _, g = gd.beta_and_g(p, jnp.zeros(32), jnp.zeros(32))
    halves = np.log(2.0) / -np.asarray(g)
    assert 0.4 < halves.min() and halves.max() < 700
    assert 3 < np.median(halves) < 200
    assert float(jnp.max(jnp.abs(p["gate_norm"] - 1.0))) == 0.0


def test_the_convolution_is_the_one_mamba2_runs(monkeypatch):
    """One helper, two callers: ``delta_rows`` and ``delta_step`` take the
    causal depthwise convolution and its carried tail from
    ``ops/mamba2.py``."""
    assert gd.conv_rows is mamba2.conv_rows
    assert gd.conv_step is mamba2.conv_step
    assert gd.conv_tail is mamba2.conv_tail
    x = _rows(r=2, t=6)[..., :5]
    w = jax.random.normal(jax.random.PRNGKey(9), (4, 5), F32)
    tail = jnp.zeros((2, 3, 5), F32)
    padded, rows = mamba2.conv_rows(x, tail, w)
    window = jnp.zeros((3, 2, 5), F32)
    for i in range(6):
        window, one = mamba2.conv_step(window, x[:, i], w)
        np.testing.assert_allclose(np.asarray(one), np.asarray(rows[:, i]),
                                   rtol=1e-5, atol=1e-6)
        window = window[1:]
    np.testing.assert_array_equal(
        np.asarray(mamba2.conv_tail(padded, jnp.asarray([6, 2]), 4)[:, 1]),
        np.asarray(jnp.concatenate([jnp.zeros((1, 5)), x[1, :2]])))
