"""The dropless share layer of ``ops/moe.py`` (``route``,
``dropless_experts``): nothing dropped at any imbalance, experts that are
absent contribute nothing, the two forms (the kernel of
``ops/moe_experts.py`` at widths of whole lane tiles, in interpret mode here,
and the batched XLA product at toy widths) give one result, the counters
count what a hand can count, and the shares of all chips add up to the uncut
layer."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.loading import load_module
from ray_tpu.models import granite_hybrid as gh
from ray_tpu.models import nemotron_h as nh
from ray_tpu.ops import moe

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
reference = load_module(os.path.join(REPO, "benchmark", "reference",
                                     "nemotron_h.py"))
gated_reference = load_module(os.path.join(REPO, "benchmark", "reference",
                                           "granite_hybrid.py"))
T, D, F, E, K = 24, 16, 20, 12, 3
relu2 = lambda x: jnp.square(jax.nn.relu(x))
# the experts' widths alone choose the form: whole lane tiles (D and F 128,
# the smallest) take the kernel, the toy widths the batched product. (Until
# PR 52 the row count chose between the batched and the TPU's grouped
# product; the grouped cases run against the kernel now.)
WIDTHS = {"kernel": (128, 128), "batched": (D, F)}
PATHS = pytest.mark.parametrize("layer", list(WIDTHS), indirect=True)


@pytest.fixture(scope="module")
def layer(request):
    d, f = WIDTHS[getattr(request, "param", "batched")]
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    return {"h": jax.random.normal(ks[0], (T, d)),
            "w1": jax.random.normal(ks[1], (E, d, f)) * 1.2 / d ** 0.5,
            "w2": jax.random.normal(ks[2], (E, f, d)) * 1.2 / f ** 0.5,
            "gate": jax.random.normal(ks[3], (d, E)),
            "bias": jax.random.normal(ks[4], (E,)) * 0.3,
            "kernel": moe.moe_experts.takes_kernel(d, f, f)}


def by_hand(h, ids, weights, w1, w2, first):
    """Token by token, pair by pair, in numpy (relu squared experts)."""
    return pair_by_pair(h, ids, weights, w1, w2, first, np_relu2)


def test_route_is_the_references_router(layer):
    ids, w = moe.route(layer["h"], layer["gate"], layer["bias"], K, 5.0)
    s = jax.nn.sigmoid(layer["h"] @ layer["gate"])
    want_ids = np.argsort(-np.asarray(s + layer["bias"]), axis=-1)[:, :K]
    assert [sorted(r) for r in np.asarray(ids).tolist()] \
        == [sorted(r) for r in want_ids.tolist()]
    # the weights are the scores WITHOUT the bias, normalised, times 5
    chosen = np.take_along_axis(np.asarray(s), np.asarray(ids), axis=-1)
    np.testing.assert_allclose(
        np.asarray(w), 5.0 * chosen / chosen.sum(-1, keepdims=True),
        rtol=1e-5)
    assert ids.dtype == jnp.int32 and w.dtype == jnp.float32


@PATHS
@pytest.mark.parametrize("first, held", [(0, 12), (0, 4), (4, 4), (8, 4)])
def test_a_share_is_its_experts_part_of_the_layer(layer, first, held):
    h = layer["h"]
    ids, w = moe.route(h, layer["gate"], layer["bias"], K, 5.0)
    got, counts = moe.dropless_experts(
        h, ids, w, layer["w1"][first:first + held],
        layer["w2"][first:first + held], first=first, activation=relu2)
    want, want_counts = by_hand(h, ids, w,
                                layer["w1"][first:first + held],
                                layer["w2"][first:first + held], first)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-4)
    assert np.asarray(counts).tolist() == want_counts.tolist()
    assert int(counts.sum()) == int(((np.asarray(ids) >= first)
                                     & (np.asarray(ids) < first + held)).sum())


@PATHS
def test_nothing_is_dropped_when_every_token_takes_one_expert(layer):
    """The worst imbalance: every token on expert 2 (and on two absent
    ones). A capacity layer would drop most of them."""
    h, n = layer["h"], T
    ids = jnp.tile(jnp.asarray([[2, 9, 11]], jnp.int32), (n, 1))
    w = jnp.tile(jnp.asarray([[0.5, 0.3, 0.2]]), (n, 1))
    got, counts = moe.dropless_experts(
        h, ids, w, layer["w1"][:4], layer["w2"][:4], first=0,
        activation=relu2)
    want = 0.5 * relu2(h @ layer["w1"][2]) @ layer["w2"][2]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4)
    assert np.asarray(counts).tolist() == [0, 0, n, 0]
    assert int((counts > 0).sum()) == 1  # experts_hit, by hand


@PATHS
def test_tokens_sent_only_to_absent_experts_add_nothing(layer):
    ids = jnp.tile(jnp.asarray([[5, 9, 11]], jnp.int32), (T, 1))
    w = jnp.full((T, K), 1.0 / K)
    got, counts = moe.dropless_experts(
        layer["h"], ids, w, layer["w1"][:4], layer["w2"][:4],
        first=0, activation=relu2)
    assert float(jnp.abs(got).max()) == 0.0
    assert int(counts.sum()) == 0


@PATHS
def test_rows_that_are_padding_are_routed_nowhere(layer):
    """A padded lane's other rows take no expert: zeros for them, the
    real rows' result as it was, and the counts count real pairs only."""
    h = layer["h"]
    ids, w = moe.route(h, layer["gate"], layer["bias"], K, 5.0)
    live = jnp.arange(T) % 3 != 1
    args = (h, ids, w, layer["w1"][:6], layer["w2"][:6])
    every, _ = moe.dropless_experts(*args, first=0, activation=relu2)
    got, counts = moe.dropless_experts(*args, first=0, activation=relu2,
                                       live=live)
    np.testing.assert_allclose(np.asarray(got[live]),
                               np.asarray(every[live]), atol=2e-5)
    assert float(jnp.abs(got[~live]).max()) == 0.0
    assert int(counts.sum()) == int((np.asarray(ids)[np.asarray(live)]
                                     < 6).sum())


@PATHS
def test_the_shape_chooses_the_path_and_not_the_result(layer):
    """Widths of whole lane tiles take the kernel at ANY row count, toy
    widths the batched product; the caller has no say, no grouped product
    is left, and the result does not know the lane: 288 rows that are
    twelve copies of 24 give twelve copies of what the 24 give."""
    assert not hasattr(moe, "DENSE_ROWS") \
        and not hasattr(moe, "_grouped_share")

    def share(h):
        ids, w = moe.route(h, layer["gate"], layer["bias"], K, 5.0)
        return moe.dropless_experts(h, ids, w, layer["w1"][:6],
                                    layer["w2"][:6], first=0,
                                    activation=relu2)

    few, many = layer["h"], jnp.tile(layer["h"], (12, 1))
    (one, few_counts), (twelve, many_counts) = share(few), share(many)
    np.testing.assert_allclose(np.asarray(twelve),
                               np.tile(np.asarray(one), (12, 1)), atol=2e-4)
    assert np.asarray(many_counts).tolist() \
        == (12 * np.asarray(few_counts)).tolist()
    for h in (few, many):
        text = str(jax.make_jaxpr(lambda h: share(h)[0])(h))
        assert ("pallas_call" in text) == layer["kernel"]
        assert "ragged_dot" not in text


def test_the_shares_of_all_chips_add_up_to_the_uncut_layer():
    """The share is tied to the model: at ``tiny``, the routed parts that
    the two shares of the 8 experts give, plus the shared expert and what
    every chip computes alike counted once, equal the reference's layer
    with every expert held."""
    cfg = nh.NemotronHConfig.tiny(dtype=jnp.float32,
                                  param_dtype=jnp.float32,
                                  experts_held=(0, 8))
    p = nh._layer_init(jax.random.PRNGKey(4), "E", cfg)
    # at its initial scale the routed part is a thousandth of the shared
    # expert's: make it count
    p = {**p, **{k: 8.0 * p[k] for k in ("w1", "w2", "w_up")}}
    y = jax.random.normal(jax.random.PRNGKey(5), (2, 9, cfg.d_model))
    whole = reference.latent_moe(
        {"gate_w": p["router"], "e_score_correction_bias": p["router_bias"],
         "fc1_latent_proj": p["w_down"], "fc2_latent_proj": p["w_up"],
         "experts_up": p["w1"], "experts_down": p["w2"],
         "shared_up": p["shared_w1"], "shared_down": p["shared_w2"]},
        y, top_k=cfg.top_k, routed_scale=cfg.routed_scale, first_expert=0)
    flat = y.reshape(-1, cfg.d_model)
    parts, rows = [], 0
    for first in (0, 4):  # two chips, four experts each
        share = nh.NemotronHConfig.tiny(
            dtype=jnp.float32, param_dtype=jnp.float32,
            experts_held=(first, 4))
        mine = {**p, "w1": p["w1"][first:first + 4],
                "w2": p["w2"][first:first + 4]}
        out, counts = nh._moe(mine, flat, share)
        parts.append(out)
        rows += int(counts.sum())
    # every chip adds the shared expert: count it once
    shared = nh._relu2(flat @ p["shared_w1"]) @ p["shared_w2"]
    total = parts[0] + parts[1] - shared
    scale = float(jnp.abs(whole).max())
    assert float(jnp.abs(total.reshape(y.shape) - whole).max()) < 1e-5 * scale
    assert rows == flat.shape[0] * cfg.top_k  # every pair landed somewhere
    # and one share alone is not the layer
    assert float(jnp.abs(parts[0].reshape(y.shape) - whole).max()) \
        > 1e-2 * scale


# -- the router that is a softmax over the chosen logits, gated experts -------


def test_route_topk_softmax_is_the_references_gating(layer):
    """The top K of the float32 logits and a softmax over those K: no
    softmax over all first, no bias, weights that sum to one."""
    ids, w = moe.route_topk_softmax(layer["h"], layer["gate"], K)
    assert ids.shape == w.shape == (T, K)
    assert ids.dtype == jnp.int32 and w.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 1.0, atol=1e-6)
    want = gated_reference.gating(layer["h"], layer["gate"], K)  # [T, E]
    dense = jnp.zeros((T, E)).at[jnp.arange(T)[:, None], ids].set(w)
    np.testing.assert_allclose(np.asarray(dense), np.asarray(want),
                               atol=1e-6)
    # not the softmax over every expert cut to its top K
    every = jax.nn.softmax(layer["h"] @ layer["gate"], axis=-1)
    cut = jnp.take_along_axis(every, ids, axis=-1)
    assert float(jnp.abs(cut - w).max()) > 1e-2
    # float32 at the highest precision whatever the rows' type
    low, _ = moe.route_topk_softmax(layer["h"].astype(jnp.bfloat16),
                                    layer["gate"].astype(jnp.bfloat16), K)
    text = str(jax.make_jaxpr(lambda h, g: moe.route_topk_softmax(h, g, K))(
        layer["h"].astype(jnp.bfloat16), layer["gate"]))
    assert "HIGHEST" in text and low.shape == (T, K)


@PATHS
def test_gated_experts_go_through_the_share_as_it_is(layer):
    """``w1`` [E, D, 2 F] and ``silu(a) * b`` between the two products:
    both forms of ``dropless_experts`` give the reference's routed part, an
    expert at a time. No new product."""
    ks = jax.random.split(jax.random.PRNGKey(9), 2)
    d, f = layer["w1"].shape[1:]
    w1 = jax.random.normal(ks[0], (E, d, 2 * f)) * 1.2 / d ** 0.5
    h = layer["h"]
    ids, w = moe.route_topk_softmax(h, layer["gate"], K)
    first, held = 3, 6
    got, counts = moe.dropless_experts(
        h, ids, w, w1[first:first + held], layer["w2"][first:first + held],
        first=first, activation=gh._gate)
    mine = gated_reference.gating(h, layer["gate"], K)[:, first:first + held]
    want = sum(mine[:, e, None] * (gated_reference.gated(
        h @ w1[first + e]) @ layer["w2"][first + e]) for e in range(held))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4)
    assert np.asarray(counts).tolist() \
        == np.asarray((mine > 0).sum(0)).tolist()
    path = "pallas_call" in str(jax.make_jaxpr(
        lambda h: moe.dropless_experts(
            h, ids, w, w1[:held], layer["w2"][:held], first=0,
            activation=gh._gate)[0])(h))
    assert path == layer["kernel"]  # the widths alone chose


def test_the_two_shares_of_a_gated_layer_add_up_to_the_uncut_layer():
    """``experts_held`` (0, 4) and (4, 4) of 8: the routed parts the two
    chips give, with the shared expert (which both compute) counted once,
    are the reference's layer with every expert held."""
    cfg = gh.GraniteHybridConfig.tiny(dtype=jnp.float32,
                                      param_dtype=jnp.float32,
                                      experts_held=(0, 8))
    p = gh._layer_init(jax.random.PRNGKey(4), "mamba", cfg)
    p = {**p, "w2": 8.0 * p["w2"]}  # at its seeded scale the routed part
    # is a tenth of the shared expert's: make it count
    y = jax.random.normal(jax.random.PRNGKey(5), (2, 9, cfg.d_model))
    whole = gated_reference.experts(
        {"router": p["router"], "experts_in": p["w1"],
         "experts_out": p["w2"], "shared_in": p["shared_w1"],
         "shared_out": p["shared_w2"]}, y, top_k=cfg.top_k, first_expert=0)
    flat = y.reshape(-1, cfg.d_model)
    parts, rows = [], 0
    for first in (0, 4):  # two chips, four experts each
        share = gh.GraniteHybridConfig.tiny(
            dtype=jnp.float32, param_dtype=jnp.float32,
            experts_held=(first, 4))
        mine = {**p, "w1": p["w1"][first:first + 4],
                "w2": p["w2"][first:first + 4]}
        out, counts = gh._moe(mine, flat, share)
        parts.append(out)
        rows += int(counts.sum())
    shared = gh._gate(flat @ p["shared_w1"]) @ p["shared_w2"]
    total = parts[0] + parts[1] - shared
    scale = float(jnp.abs(whole).max())
    assert float(jnp.abs(total.reshape(y.shape) - whole).max()) < 1e-5 * scale
    assert rows == flat.shape[0] * cfg.top_k  # every pair landed somewhere
    assert float(jnp.abs(parts[0].reshape(y.shape) - whole).max()) \
        > 1e-2 * scale


def test_the_step_counters_count_hit_experts_and_pairs():
    counts = [jnp.asarray([2, 0, 1, 0]), jnp.asarray([0, 0, 0, 5])]
    got = moe.held_counters(counts)
    assert {k: int(v) for k, v in got.items()} \
        == {"experts_hit": 3, "expert_rows": 8, "expert_row_tiles": 3}
    assert {k: int(v) for k, v in moe.held_counters([]).items()} \
        == {"experts_hit": 0, "expert_rows": 0, "expert_row_tiles": 0}
    # a tile is ``ROW_TILE`` pairs of one expert: 129 pairs are two
    tile = moe.moe_experts.ROW_TILE
    assert int(moe.held_counters([jnp.asarray([tile + 1, tile, 0, 1])])[
        "expert_row_tiles"]) == 4
    assert all(v.dtype == jnp.int32 for v in got.values())


# -- the kernel (``ops/moe_experts.py``), in interpret mode --------------------
#
# The smallest widths that are whole lane tiles: D and F 128, six experts
# held of twelve. ``ROW_TILE`` is 128 pairs, so 160 rows on one expert are a
# tile and a quarter.

KD = KF = 128


def np_relu2(x):
    return np.maximum(x, 0) ** 2


def np_gate(ab):
    a, b = ab[..., :ab.shape[-1] // 2], ab[..., ab.shape[-1] // 2:]
    return a / (1 + np.exp(-a)) * b


ACTS = pytest.mark.parametrize("gated", [False, True],
                               ids=["relu_squared", "gated"])


def pair_by_pair(h, ids, weights, w1, w2, first, act, live=None):
    """``by_hand`` with the expert's activation given, in float64."""
    out = np.zeros((h.shape[0], w2.shape[2]), np.float64)
    counts = np.zeros(w1.shape[0], np.int64)
    h, w1, w2 = (np.asarray(a, np.float64) for a in (h, w1, w2))
    for t in range(h.shape[0]):
        if live is not None and not live[t]:
            continue
        for e, w in zip(np.asarray(ids[t]), np.asarray(weights[t])):
            if first <= e < first + w1.shape[0]:
                out[t] += w * (act(h[t] @ w1[e - first]) @ w2[e - first])
                counts[e - first] += 1
    return out, counts


@pytest.fixture(scope="module")
def wide():
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    return {"h": jax.random.normal(ks[0], (160, KD)),
            "w1": jax.random.normal(ks[1], (E, KD, 2 * KF)) * 0.1,
            "w2": jax.random.normal(ks[2], (E, KF, KD)) * 0.1,
            "gate": jax.random.normal(ks[3], (KD, E))}


def kernel_share(wide, gated, h, ids, w, first, held, live=None):
    """(got, counts, want, want_counts) of experts ``first .. first +
    held`` through ``dropless_experts``, which these widths send to the
    kernel, and pair by pair."""
    w1 = wide["w1"][first:first + held, :, :KF * (1 + gated)]
    w2 = wide["w2"][first:first + held]
    assert moe.moe_experts.takes_kernel(KD, w1.shape[2], KF)
    got, counts = moe.dropless_experts(
        h, ids, w, w1, w2, first=first,
        activation=gh._gate if gated else relu2, live=live)
    assert got.dtype == jnp.float32 and counts.dtype == jnp.int32
    want, want_counts = pair_by_pair(
        h, ids, w, w1, w2, first, np_gate if gated else np_relu2,
        None if live is None else np.asarray(live))
    return np.asarray(got), np.asarray(counts).tolist(), want, \
        want_counts.tolist()


@ACTS
@pytest.mark.parametrize("first, held", [(0, 12), (0, 6), (3, 6), (8, 4)])
def test_the_kernel_gives_the_held_experts_part(wide, gated, first, held):
    h = wide["h"][:24]
    ids, w = moe.route_topk_softmax(h, wide["gate"], K)
    got, counts, want, want_counts = kernel_share(
        wide, gated, h, ids, w, first, held)
    np.testing.assert_allclose(got, want, atol=2e-4)
    assert counts == want_counts


@ACTS
def test_the_kernel_with_no_expert_hit_gives_zeros(wide, gated):
    """Every pair on an absent expert: no tile is live, the kernel's one
    grid step that fetches anything computes nothing."""
    ids = jnp.tile(jnp.asarray([[7, 9, 11]], jnp.int32), (24, 1))
    got, counts, _, _ = kernel_share(
        wide, gated, wide["h"][:24], ids, jnp.full((24, K), 1.0 / K), 0, 6)
    assert float(np.abs(got).max()) == 0.0 and counts == [0] * 6


@ACTS
def test_the_kernel_runs_one_expert_over_every_row(wide, gated):
    """160 rows all on held expert 2 (and on two absent ones): a whole
    tile of 128 pairs and one of 32, the only expert fetched."""
    ids = jnp.tile(jnp.asarray([[5, 11, 9]], jnp.int32), (160, 1))
    w = jnp.tile(jnp.asarray([[0.5, 0.3, 0.2]]), (160, 1))
    got, counts, want, want_counts = kernel_share(
        wide, gated, wide["h"], ids, w, 3, 4)
    np.testing.assert_allclose(got, want, atol=2e-4)
    assert counts == want_counts == [0, 0, 160, 0]


@ACTS
def test_the_kernel_routes_padding_rows_nowhere(wide, gated):
    h = wide["h"][:24]
    ids, w = moe.route_topk_softmax(h, wide["gate"], K)
    live = jnp.arange(24) % 3 != 1
    got, counts, want, want_counts = kernel_share(
        wide, gated, h, ids, w, 0, 6, live=live)
    np.testing.assert_allclose(got, want, atol=2e-4)
    assert float(np.abs(got[~np.asarray(live)]).max()) == 0.0
    assert counts == want_counts


def test_a_lane_longer_than_the_kernel_holds_goes_through_it_in_blocks(
        wide, monkeypatch):
    """``LANE_ROWS`` rows a call (cut to 64 here): 160 rows are three
    calls whose counts add up, and the result is the one call's."""
    ids, w = moe.route_topk_softmax(wide["h"], wide["gate"], K)
    whole = kernel_share(wide, True, wide["h"], ids, w, 0, 6)
    monkeypatch.setattr(moe.moe_experts, "LANE_ROWS", 64)
    got, counts, want, want_counts = kernel_share(
        wide, True, wide["h"], ids, w, 0, 6)
    np.testing.assert_allclose(got, want, atol=2e-4)
    np.testing.assert_allclose(got, whole[0], atol=1e-5)
    assert counts == want_counts == whole[1]
