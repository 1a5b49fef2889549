"""``ops/sparse_select.py`` alone: the keys' order, the EXACT top-k against a
stable sort (planted ties, fewer candidates than k, none at all), the
positions a mask picks, the indexer's scores, and both attention forms
against a plain float32 computation of the same equations over the same
rings: a decode step that gathers and a prompt chunk that masks, before the
selection starts, at it and past it, with a chunk boundary inside it."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import sparse_pick
from ray_tpu.ops import sparse_select as ss
from ray_tpu.ops.attention import merged_rows


def _stable_topk(scores, eligible, k):
    """[n] bool: the first ``k`` of a stable descending sort of the
    eligible scores (-0.0 is 0.0)."""
    scores = np.where(scores == 0, 0.0, scores).astype(np.float32)
    order = np.argsort(-np.where(eligible, scores, -np.inf), kind="stable")
    want = np.zeros(scores.shape, bool)
    want[order[:min(k, int(eligible.sum()))]] = True
    return want & eligible


# -- the keys -----------------------------------------------------------------


@pytest.mark.parametrize("values", [
    [-np.inf, -3.5, -1e-30, -0.0, 0.0, 1e-30, 2.0, 1e30, np.inf],
    [-2.0, -1.0, -1.0, 0.5, 0.5, 0.5, 7.0],
    list(np.linspace(-5, 5, 41)),
], ids=["extremes", "ties", "a-ramp"])
def test_keys_are_in_the_scores_order(values):
    x = jnp.asarray(values, jnp.float32)
    keys = np.asarray(ss.sort_keys(x, jnp.ones(x.shape, bool))).astype(
        np.int64)
    x = np.asarray(values, np.float32)
    for i in range(len(x)):
        for j in range(len(x)):
            assert (keys[i] < keys[j]) == (x[i] < x[j]), (x[i], x[j])
            assert (keys[i] == keys[j]) == (x[i] == x[j]), (x[i], x[j])
    assert keys.min() > 0  # below every score's key: the ineligible's 0


def test_an_ineligible_candidates_key_is_below_every_scores():
    x = jnp.asarray([-np.inf, 0.0, 5.0], jnp.float32)
    keys = np.asarray(ss.sort_keys(x, jnp.asarray([True, False, True])))
    assert keys[1] == 0 and keys[0] > 0 and keys[2] > keys[0]


@pytest.mark.parametrize("bits", [1, 2, 4, 8])
@pytest.mark.parametrize("n, k", [(64, 1), (300, 16), (300, 300), (1000, 77)])
def test_the_kth_largest_is_a_sorts(monkeypatch, bits, n, k):
    monkeypatch.setattr(ss, "RADIX_BITS", bits)
    rng = np.random.default_rng(n + k)
    keys = rng.integers(1, 2 ** 32, (6, n), dtype=np.uint64).astype(
        np.uint32)
    keys[1, ::3] = keys[1, 0]  # ties
    keys[2] = 12345            # all equal
    got = np.asarray(ss.kth_largest(jnp.asarray(keys),
                                    jnp.full((6,), k, jnp.int32)))
    np.testing.assert_array_equal(got, np.sort(keys, axis=-1)[:, n - k])


def test_the_zeroth_largest_is_above_every_key():
    keys = jnp.asarray([[5, 9, 2 ** 32 - 2]], jnp.uint32)
    assert int(ss.kth_largest(keys, jnp.zeros((1,), jnp.int32))[0]) \
        == 2 ** 32 - 1


# -- the exact top-k ------------------------------------------------------------


@pytest.mark.parametrize("n, k", [(40, 16), (128, 128), (300, 16),
                                  (1000, 77), (513, 512), (96, 200)])
@pytest.mark.parametrize("plant", ["none", "ties", "zeros", "all-equal"])
def test_the_selection_is_a_stable_sorts_first_k(n, k, plant):
    """Against ``np.argsort(kind="stable")``: with no ties, with every
    seventh score equal (so that the k-th lies among equals and the LOWER
    positions must win), with a run of 0.0 and -0.0 (one value), and with
    every score the same (the first k positions)."""
    rng = np.random.default_rng(n * 7 + k)
    x = rng.standard_normal((4, n)).astype(np.float32)
    if plant == "ties":
        x[:, ::7] = x[:, 3:4]
        x[:, 1::5] = np.sort(x, axis=-1)[:, -min(k, n):][:, :1]  # at the k-th
    elif plant == "zeros":
        x[:, :n // 2] = 0.0
        x[:, 5:n // 2:2] = -0.0
        x[:, n // 2:] = -np.abs(x[:, n // 2:])
    elif plant == "all-equal":
        x[:] = 1.25
    eligible = np.ones((4, n), bool)
    eligible[1, n // 2:] = False       # fewer candidates
    eligible[2, ::2] = False
    eligible[3, 3:] = False            # fewer than k (where k > 3)
    mask = np.asarray(jax.jit(lambda a, b: ss.select_mask(
        ss.sort_keys(a, b), jnp.full((4,), k, jnp.int32)))(x, eligible))
    for r in range(4):
        np.testing.assert_array_equal(
            mask[r], _stable_topk(x[r], eligible[r], k), err_msg=str(r))


def test_a_row_without_candidates_picks_nothing():
    x = jnp.ones((2, 50), jnp.float32)
    eligible = jnp.zeros((2, 50), bool).at[1, 7].set(True)
    mask = np.asarray(ss.select_mask(ss.sort_keys(x, eligible),
                                     jnp.full((2,), 16, jnp.int32)))
    assert mask[0].sum() == 0
    assert mask[1].sum() == 1 and mask[1, 7]


def test_each_row_has_its_own_k():
    x = jnp.asarray(np.random.default_rng(0).standard_normal((3, 80)),
                    jnp.float32)
    k = jnp.asarray([0, 5, 80], jnp.int32)
    mask = np.asarray(ss.select_mask(
        ss.sort_keys(x, jnp.ones(x.shape, bool)), k))
    assert mask.sum(-1).tolist() == [0, 5, 80]


def _selection_jaxpr(which):
    if which == "xla":
        return jax.make_jaxpr(lambda a: ss.select_mask(
            ss.sort_keys(a, a > -9), jnp.full((3,), 16, jnp.int32)))(
                jnp.zeros((3, 100)))
    return jax.make_jaxpr(lambda q, w, own, old: sparse_pick.sparse_pick(
        q, w, own, old, 100, 128, 64, block=128))(
            jnp.zeros((128, 2, 64), jnp.bfloat16), jnp.zeros((128, 2)),
            jnp.zeros((128, 64), jnp.bfloat16),
            jnp.zeros((64, 256), jnp.bfloat16))


@pytest.mark.parametrize("which", ["xla", "kernel"])
def test_no_approximate_top_k_and_no_sort_on_the_path(which):
    """The jaxpr of the selection, and of the kernel of
    ``ops/sparse_pick.py`` with its body, holds comparisons and sums: no
    ``sort``, ``top_k`` or ``approx_top_k``."""
    text = str(_selection_jaxpr(which))
    assert ("pallas_call" in text) == (which == "kernel")
    for word in ("sort", "top_k", "approx"):
        assert word not in text, word


# -- mask -> positions ------------------------------------------------------------


@pytest.mark.parametrize("n, k", [(128, 16), (300, 16), (1000, 64),
                                  (4096, 256), (33, 33)])
def test_mask_indices_are_the_masks_positions_in_rising_order(n, k):
    rng = np.random.default_rng(n + k)
    mask = np.zeros((5, n), bool)
    for r, count in enumerate([k, k // 2, 1, 0, k]):
        mask[r, rng.choice(n, count, replace=False)] = True
    mask[4] = False
    mask[4, n - k:] = True  # the last k positions, a block's end
    idx, count = (np.asarray(x) for x in jax.jit(
        lambda m: ss.mask_indices(m, k))(mask))
    for r in range(5):
        assert count[r] == mask[r].sum()
        np.testing.assert_array_equal(idx[r, :count[r]],
                                      np.nonzero(mask[r])[0])
        assert (idx[r, count[r]:] == 0).all()


# -- the indexer's scores -------------------------------------------------------


def test_index_scores_are_the_weighted_relus():
    rng = np.random.default_rng(3)
    q = rng.standard_normal((2, 5, 3, 8)).astype(np.float32)
    w = rng.standard_normal((2, 5, 3)).astype(np.float32)
    k = rng.standard_normal((2, 11, 8)).astype(np.float32)
    want = np.einsum("rtjs,rtj->rts",
                     np.maximum(np.einsum("rtjd,rsd->rtjs", q, k), 0), w)
    np.testing.assert_allclose(
        np.asarray(ss.index_scores(jnp.asarray(q), jnp.asarray(w),
                                   jnp.asarray(k))), want, rtol=1e-5,
        atol=1e-5)


# -- the two attention forms against plain float32 ----------------------------------

H, G, HD, J, DI, TOPK = 4, 2, 16, 3, 8, 16
W = G * HD


def _plain(q, keys, values, q_idx, w_idx, k_idx, sees, topk):
    """One query [H, hd] over keys / values [n, G, hd] of which ``sees`` [n]
    bool are eligible: the index scores, a stable sort's first ``topk``,
    the softmax over those. -> (out [H, hd], the set [n] bool)."""
    scores = (np.maximum(q_idx @ k_idx.T, 0) * w_idx[:, None]).sum(0)
    picked = _stable_topk(scores, sees, topk)
    out = np.zeros((H, HD), np.float32)
    for h in range(H):
        g = h // (H // G)
        s = keys[picked, g] @ q[h] / np.sqrt(HD)
        p = np.exp(s - s.max())
        out[h] = (p / p.sum()) @ values[picked, g]
    return out, picked


def _rings(rng, n_layer, slots, n_rows):
    k = rng.standard_normal((n_layer, slots, n_rows, G, HD)).astype(
        np.float32)
    v = rng.standard_normal((n_layer, slots, n_rows, G, HD)).astype(
        np.float32)
    idx = rng.standard_normal((n_layer, slots, n_rows, DI)).astype(
        np.float32)
    return k, v, idx


def _merged(k, v):
    """K and V heads [..., G, hd] each -> the ring's rows [..., 2 W]: the
    merged K row and the merged V row side by side."""
    return jnp.concatenate([merged_rows(jnp.asarray(
        x.reshape(*x.shape[:-2], W)), W) for x in (k, v)], axis=-1)


@pytest.mark.parametrize("contexts", [
    (1, 5, 15), (16, 17, 40), (60, 3, 30), (0, 33, 0)],
    ids=["all-rows", "at-the-crossing", "selecting", "free-slots"])
def test_a_step_attends_the_rows_its_indexer_picks(contexts):
    """S slots at ``contexts`` earlier rows each (0: a free slot, valid 0):
    out, rows and sizes against the plain computation over the ring's live
    rows with the token's own row at the cursor."""
    rng = np.random.default_rng(sum(contexts))
    s, n_rows, layer = len(contexts), 64, 1
    k, v, idx = _rings(rng, 2, s, n_rows)
    q = rng.standard_normal((s, H, HD)).astype(np.float32)
    k_new, v_new = (rng.standard_normal((s, G, HD)).astype(np.float32)
                    for _ in range(2))
    i_new = rng.standard_normal((s, DI)).astype(np.float32)
    q_idx = rng.standard_normal((s, J, DI)).astype(np.float32)
    w_idx = rng.standard_normal((s, J)).astype(np.float32)
    pos = np.asarray(contexts, np.int32)
    valid = np.where(pos > 0, pos + 1, 0).astype(np.int32)
    out, rows, sizes = jax.jit(lambda *a: ss.sparse_decode_attention(
        *a, layer, jnp.asarray(pos), jnp.asarray(valid), TOPK,
        jnp.float32))(
        jnp.asarray(q), _merged(k, v), jnp.asarray(idx),
        _merged(k_new, v_new), jnp.asarray(i_new), jnp.asarray(q_idx),
        jnp.asarray(w_idx))
    out, rows, sizes = (np.asarray(x) for x in (out, rows, sizes))
    assert rows.shape == (s, TOPK)
    for slot, ctx in enumerate(contexts):
        if ctx == 0:
            assert sizes[slot] == 0  # a free slot picks nothing
            continue
        keys, values, kidx = (x[layer, slot].copy() for x in (k, v, idx))
        keys[ctx], values[ctx], kidx[ctx] = (k_new[slot], v_new[slot],
                                             i_new[slot])
        want, picked = _plain(q[slot], keys, values, q_idx[slot],
                              w_idx[slot], kidx,
                              np.arange(n_rows) <= ctx, TOPK)
        assert sizes[slot] == min(TOPK, ctx + 1) == picked.sum()
        np.testing.assert_array_equal(rows[slot, :sizes[slot]],
                                      np.nonzero(picked)[0])
        np.testing.assert_allclose(out[slot], want, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("start, length", [
    (0, 8), (8, 8), (16, 8), (24, 5), (40, 8), (56, 1)],
    ids=["first", "all-rows", "crossing", "padded", "selecting", "one-real"])
@pytest.mark.parametrize("key_block", [8192, 16])
def test_a_chunk_attends_under_its_queries_sets(monkeypatch, start, length,
                                                key_block):
    """A chunk of 8 rows at ``start`` over a ring the earlier chunks left,
    ``length`` of its rows real, the chunk's own rows beside the ring (not
    in it): every real query against the plain computation over positions
    ``0 .. start + i``; the padded rows pick nothing. Once with the keys a
    block of 16 at a time (the running softmax over several blocks)."""
    monkeypatch.setattr(ss, "_KEY_BLOCK", key_block)
    rng = np.random.default_rng(start + length)
    c, n_rows, window, layer, slot = 8, 72, 64, 0, 2
    k, v, idx = _rings(rng, 2, 3, n_rows)
    q = rng.standard_normal((1, c, H, HD)).astype(np.float32)
    k_own, v_own = (rng.standard_normal((1, c, G, HD)).astype(np.float32)
                    for _ in range(2))
    i_own = rng.standard_normal((1, c, DI)).astype(np.float32)
    q_idx = rng.standard_normal((1, c, J, DI)).astype(np.float32)
    w_idx = rng.standard_normal((1, c, J)).astype(np.float32)
    out, mask = jax.jit(lambda *a: ss.sparse_chunk_attention(
        *a, layer, jnp.asarray([slot]), jnp.asarray([start]),
        jnp.asarray([length]), window, TOPK))(
        jnp.asarray(q), _merged(k, v), jnp.asarray(idx),
        _merged(k_own, v_own), jnp.asarray(i_own), jnp.asarray(q_idx),
        jnp.asarray(w_idx))
    out = np.asarray(out)
    keys, values, kidx = (x[layer, slot].copy() for x in (k, v, idx))
    keys[start:start + c] = k_own[0]
    values[start:start + c] = v_own[0]
    kidx[start:start + c] = i_own[0]
    # the mask handed back: ring rows (row = position) and then the own rows
    mask = np.asarray(mask)
    assert mask.shape == (1, c, window)
    by_position = np.zeros((c, n_rows), bool)
    by_position[:, :window - c] = mask[0, :, :window - c]
    assert not by_position[:, start:].any()  # no ring row at or past start
    by_position[:, start:start + c] = mask[0, :, window - c:]
    for i in range(length):
        want, chosen = _plain(q[0, i], keys, values, q_idx[0, i],
                              w_idx[0, i], kidx,
                              np.arange(n_rows) <= start + i, TOPK)
        assert chosen.sum() == min(TOPK, start + i + 1)
        np.testing.assert_array_equal(by_position[i], chosen, str(i))
        np.testing.assert_allclose(out[0, i], want, rtol=2e-4, atol=2e-4,
                                   err_msg=str(i))
    assert not by_position[length:].any()  # a padded row picks nothing
    assert np.isfinite(out).all()  # a padded row: zeros, not 0 / 0


def test_two_rows_of_a_chunk_share_the_longer_ones_window():
    """R = 2 rows at different starts: one branch for both, each row under
    its own positions."""
    rng = np.random.default_rng(9)
    c, n_rows, window = 8, 72, 64
    k, v, idx = _rings(rng, 1, 3, n_rows)
    q = rng.standard_normal((2, c, H, HD)).astype(np.float32)
    own = [rng.standard_normal((2, c, G, HD)).astype(np.float32)
           for _ in range(2)]
    i_own = rng.standard_normal((2, c, DI)).astype(np.float32)
    q_idx = rng.standard_normal((2, c, J, DI)).astype(np.float32)
    w_idx = rng.standard_normal((2, c, J)).astype(np.float32)
    args = (jnp.asarray(q), _merged(k, v), jnp.asarray(idx),
            _merged(own[0], own[1]), jnp.asarray(i_own), jnp.asarray(q_idx),
            jnp.asarray(w_idx))
    both, _ = ss.sparse_chunk_attention(
        *args, 0, jnp.asarray([0, 2]), jnp.asarray([40, 8]),
        jnp.asarray([8, 8]), window, TOPK)
    own_rows = (0, 3, 4, 5, 6)  # a row's own arguments; the rest: stacks
    for r, (slot, start) in enumerate([(0, 40), (2, 8)]):
        alone, _ = ss.sparse_chunk_attention(
            *(a[r:r + 1] if i in own_rows else a
              for i, a in enumerate(args)), 0, jnp.asarray([slot]),
            jnp.asarray([start]), jnp.asarray([8]), window, TOPK)
        np.testing.assert_allclose(np.asarray(both[r]), np.asarray(alone[0]),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("chunk, window, want", [
    (512, 32768, (512, 1024, 2048, 4096, 8192, 16384, 32768)),
    (512, 33792, (512, 1024, 2048, 4096, 8192, 16384, 32768, 33792)),
    (8, 64, (8, 16, 32, 64)), (8, 8, (8,)), (16, 40, (16, 32, 40))])
def test_a_chunk_program_holds_a_branch_a_doubling_window(chunk, window,
                                                          want):
    assert ss.chunk_windows(chunk, window) == want


@pytest.mark.parametrize("chunk, rows, keys, want", [
    (512, 32, 8192, 64), (512, 32, 512, 512), (512, 16, 32768, 32),
    (512, 16, 8192, 128), (8, 4, 64, 8), (12, 4, 10 ** 9, 6)])
def test_a_groups_scores_stay_within_the_limit(chunk, rows, keys, want):
    g = ss._group(chunk, rows, keys)
    assert g == want and chunk % g == 0
    assert g * rows * keys * 4 <= ss._GROUP_BYTES or g <= 8 or g % 2


# -- the chunk's Pallas kernel (interpret mode here) ------------------------------


@pytest.mark.parametrize("start, length", [(0, 128), (128, 128), (384, 100),
                                           (512, 128), (896, 7)])
def test_the_chunk_kernel_is_the_xla_arm(monkeypatch, start, length):
    """Heads of 128 lanes and indexer keys of 64 over a window of whole
    blocks take the kernels of ``ops/sparse_pick.py`` and
    ``ops/sparse_chunk.py``: against the XLA arm (the kernels turned away)
    on the same rings, before the selection starts and past it, a ring
    block that holds nothing in sight, a padded chunk."""
    from ray_tpu.ops import sparse_chunk

    h, g, hd, c, topk, di = 4, 2, 128, 128, 160, 64
    w, n_rows, window = g * hd, 1152, 1152
    rng = np.random.default_rng(start + length)
    kv = jnp.asarray(rng.standard_normal((2, 2, n_rows, 2 * w)),
                     jnp.bfloat16)
    idx = jnp.asarray(rng.standard_normal((2, 2, n_rows, di)), jnp.bfloat16)
    q = jnp.asarray(rng.standard_normal((1, c, h, hd)), jnp.bfloat16)
    kv_own = jnp.asarray(rng.standard_normal((1, c, 2 * w)), jnp.bfloat16)
    i_own = jnp.asarray(rng.standard_normal((1, c, di)), jnp.bfloat16)
    q_idx = jnp.asarray(rng.standard_normal((1, c, J, di)), jnp.bfloat16)
    w_idx = jnp.asarray(rng.standard_normal((1, c, J)), jnp.float32)
    args = (q, kv, idx, kv_own, i_own, q_idx, w_idx, 1, jnp.asarray([1]),
            jnp.asarray([start]), jnp.asarray([length]), window, topk)
    assert ss.chunk_select(c, hd, w, window - c, J, di) == "kernel"
    assert not sparse_chunk.takes_kernel(8, 16, 32, 56)
    got, mask = jax.jit(lambda: ss.sparse_chunk_attention(*args))()
    monkeypatch.setattr(sparse_chunk, "takes_kernel", lambda *a: False)
    assert ss.chunk_select(c, hd, w, window - c, J, di) == "xla"
    want, mask_too = jax.jit(lambda: ss.sparse_chunk_attention(*args))()
    # both arms hand back the same sets, of the sizes the positions give
    np.testing.assert_array_equal(np.asarray(mask), np.asarray(mask_too))
    assert np.asarray(mask)[0].sum(-1).tolist() == [
        min(topk, start + i + 1) if i < length else 0 for i in range(c)]
    np.testing.assert_allclose(
        np.asarray(got[0, :length], np.float32),
        np.asarray(want[0, :length], np.float32), rtol=2e-2, atol=2e-2)
    assert np.isfinite(np.asarray(got, np.float32)).all()


# -- the picking kernel (interpret mode here) -------------------------------------

PICK = dict(c=128, block=128, heads=3, di=64, old=512, topk=64)


def _pick_inputs(rng, exact):
    """A chunk's indexer operands over one slot's ring: small integers,
    whose products and sums float32 holds exactly whatever their order, or
    normal draws."""
    def draw(shape, dtype):
        if exact:
            return jnp.asarray(rng.integers(-3, 4, shape), dtype)
        return jnp.asarray(rng.standard_normal(shape), dtype)

    c, j, di, old = (PICK[k] for k in ("c", "heads", "di", "old"))
    return (draw((c, j, di), jnp.bfloat16), draw((c, j), jnp.float32),
            draw((c, di), jnp.bfloat16), draw((old, di), jnp.bfloat16))


def _xla_picks(q_idx, w_idx, idx_own, idx_old, start, length, topk):
    """What the XLA arm picks: (mask [C, old + C] over the ring's rows and
    then the chunk's own, the scores it picked by)."""
    c, old = q_idx.shape[0], idx_old.shape[0]
    real = (jnp.arange(c) < length)[:, None]
    scores = jnp.concatenate([ss.index_scores(q_idx, w_idx, idx_old),
                              ss.index_scores(q_idx, w_idx, idx_own)], -1)
    seen = jnp.concatenate([(jnp.arange(old)[None] < start) & real,
                            jnp.tril(jnp.ones((c, c), bool)) & real], -1)
    mask = ss.select_mask(ss.sort_keys(scores, seen),
                          jnp.minimum(topk, start + jnp.arange(c) + 1))
    return np.asarray(mask), np.asarray(scores), np.asarray(seen)


def _kernel_picks(q_idx, w_idx, idx_own, idx_old, start, length, topk, **kw):
    bias = sparse_pick.sparse_pick(
        q_idx, w_idx, idx_own, idx_old.T, jnp.int32(start),
        jnp.int32(length), topk, block=PICK["block"], **kw)
    assert [b.dtype for b in bias] == [jnp.bfloat16] * 2
    bias = np.concatenate([np.asarray(b, np.float32) for b in bias], -1)
    assert ((bias == 0) | (bias < -9e29)).all()
    return bias == 0


@pytest.mark.parametrize("start, length, plant, kw", [
    (0, 128, None, {}),
    (200, 128, None, {}),
    (512, 128, None, {}),
    (384, 50, None, {}),
    (7, 128, None, {}),
    (300, 128, "boundary", {}),
    (512, 128, "all-equal", {}),
    (200, 100, None, {"rows": 64}),
], ids=["start-0", "start-inside-a-block", "start-at-the-windows-end",
        "padded-queries-pick-nothing", "fewer-eligible-than-topk",
        "ties-across-the-ring-and-own-rows", "every-key-equal",
        "blocks-of-64-queries"])
def test_the_picking_kernel_is_the_xla_selection_bit_for_bit(start, length,
                                                             plant, kw):
    """On scores that float32 holds exactly the kernel's bias is
    ``select_mask(sort_keys(index_scores(...)))``'s mask: before the ring
    holds a row, with ``start`` inside a block and at the window's end, a
    padded chunk, queries that see fewer keys than ``topk`` (every eligible
    key), and PLANTED ties (the chunk's own keys are copies of ring rows,
    so a query's scores tie across the boundary, and the ring's rows, the
    lower positions, win)."""
    assert sparse_pick.takes_kernel(PICK["c"], PICK["heads"], PICK["di"],
                                    PICK["old"], PICK["block"])
    topk = PICK["topk"]
    q_idx, w_idx, idx_own, idx_old = _pick_inputs(
        np.random.default_rng(start + length), exact=True)
    if plant == "boundary":
        # own row i is ring row 2 i: (of those in sight) equal scores
        idx_own = idx_old[::2][:PICK["c"]]
        idx_old = idx_old.at[start - 40:start].set(idx_old[:40])
    elif plant == "all-equal":
        idx_old = jnp.broadcast_to(idx_old[:1], idx_old.shape)
        idx_own = jnp.broadcast_to(idx_old[:1], idx_own.shape)
    args = (q_idx, w_idx, idx_own, idx_old, start, length, topk)
    want, scores, seen = _xla_picks(*args)
    got = _kernel_picks(*args, **kw)
    np.testing.assert_array_equal(got, want)
    assert got.sum(-1).tolist() == [
        min(topk, start + i + 1) if i < length else 0
        for i in range(PICK["c"])]
    if plant:  # a stable sort's first k won, and ties really were cut
        old, cut, across = PICK["old"], 0, 0
        for i in range(length):
            np.testing.assert_array_equal(
                got[i], _stable_topk(scores[i], seen[i], topk), str(i))
            at = scores[i] == scores[i][got[i]].min()
            left = at & seen[i] & ~got[i]
            cut += left.any()
            # a ring row in, an own row of the same score out
            across += (at & got[i])[:old].any() and left[old:].any()
        assert cut > 10 and across > 0, (cut, across)


@pytest.mark.parametrize("start, length", [(130, 128), (512, 128), (401, 77)])
def test_on_any_scores_the_kernels_picks_differ_only_at_the_threshold(
        start, length):
    """The kernel's float32 sum over the indexer's heads may round
    otherwise than XLA's: the sets are of the same sizes, and a key that
    only one of them holds lies within 1e-5 (of the scores' scale) of the
    query's threshold."""
    topk = PICK["topk"]
    args = (*_pick_inputs(np.random.default_rng(start), exact=False), start,
            length, topk)
    want, scores, _ = _xla_picks(*args)
    got = _kernel_picks(*args)
    np.testing.assert_array_equal(got.sum(-1), want.sum(-1))
    assert want.sum() > 0
    for i in np.nonzero((got != want).any(-1))[0]:
        tau = scores[i][want[i]].min()
        gap = np.abs(scores[i][got[i] != want[i]] - tau).max()
        assert gap <= 1e-5 * max(1.0, np.abs(scores[i]).max()), (i, gap)


@pytest.mark.parametrize("c, hd, w, old, heads, di, want", [
    (512, 128, 512, 33280, 16, 64, "kernel"),   # the published widths
    (512, 128, 512, 32256, 16, 64, "kernel"),   # the cell's window
    (128, 128, 256, 1024, 3, 64, "kernel"),
    (512, 128, 512, 64512, 16, 64, "kernel"),   # a 64k window: 64 queries
    (512, 128, 512, 171008, 16, 64, "kernel"),  # the longest: 16 queries
    (512, 128, 512, 171520, 16, 64, "xla"),     # too long for VMEM
    (512, 128, 512, 0, 16, 64, "xla"),          # no ring yet: one chunk
    (512, 128, 512, 33000, 16, 64, "xla"),      # not whole blocks
    (512, 128, 512, 33280, 16, 8, "xla"),       # a toy indexer
    (8, 16, 32, 56, 3, 8, "xla"),               # the tiny preset
], ids=["published", "the-cells-window", "the-tests", "a-64k-window",
        "the-longest-window", "past-the-longest", "no-ring", "ragged-window",
        "toy-indexer", "tiny"])
def test_the_arm_is_chosen_by_the_shapes(c, hd, w, old, heads, di, want):
    assert ss.chunk_select(c, hd, w, old, heads, di) == want


@pytest.mark.parametrize("old, want", [
    (512, 128), (33280, 128), (45568, 128), (46080, 64), (64512, 64),
    (81408, 64), (81920, 32), (126976, 16), (171008, 16), (171520, 0)])
def test_a_longer_window_takes_fewer_queries_a_step(old, want):
    """The kernel's VMEM grows with the window (a ring row's key, and a
    sort key and two bias buffers a query): the published chunk takes 128
    queries a grid step up to 45,568 ring rows and halves them from there
    (``tests/test_serving_programs_keye_vl2_v5e.py`` compiles the ends of
    each range for the chip); a smaller cap stays a cap."""
    assert sparse_pick.query_rows(512, 16, 64, old) == want
    assert sparse_pick.query_rows(512, 16, 64, old, rows=32) == min(want, 32)
