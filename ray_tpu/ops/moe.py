"""Mixture-of-Experts layer with expert parallelism.

SURVEY.md §2.4: expert-axis sharding + all_to_all dispatch — absent from
the reference, first-class here. Design (switch-style top-1 / top-2):

  * router: [tokens, E] logits -> top-k experts per token + combine weights;
  * capacity: each expert takes at most C = capacity_factor * tokens/E
    tokens per device shard; overflow tokens are dropped (standard switch
    behavior) — keeps shapes static for XLA;
  * dispatch: one-hot combine matrices turn gather/scatter into einsums
    (MXU-friendly; no dynamic shapes);
  * expert parallelism: experts shard over mesh axis ``ep``; the dispatch
    einsum's tokens flow through ``all_to_all`` so each device computes
    only its local experts' FFNs.

The dense path (``moe_ffn``) works on any mesh; ``moe_ffn_ep`` adds the
all_to_all when an ``ep`` axis exists.

Beside that capacity path (training; it drops tokens over the capacity)
lives the dropless SHARE layer of the serving path: ``route`` (sigmoid
scores), ``route_topk_softmax`` (softmax over the chosen logits) or
``route_group_limited`` (softmax over all, the best groups only) scores
every expert of the model, ``dropless_experts`` computes the part of the result
that the experts held on this chip give, for every token routed to them,
whatever the imbalance. It has no exchange: on one chip there is none, and
nothing here stands in for absent chips.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ray_tpu.ops import moe_experts


def init_moe_params(rng, d_model: int, d_ff: int, n_experts: int,
                    dtype=jnp.float32) -> dict:
    k1, k2, k3 = jax.random.split(rng, 3)
    std = 0.02
    return {
        "router": (jax.random.normal(k1, (d_model, n_experts)) * std).astype(dtype),
        "w_in": (jax.random.normal(k2, (n_experts, d_model, d_ff)) * std).astype(dtype),
        "w_out": (jax.random.normal(k3, (n_experts, d_ff, d_model)) * std).astype(dtype),
    }


def moe_param_axes() -> dict:
    """Logical axes: experts shard over ep; ffn dim over tp."""
    return {
        "router": ("embed", None),
        "w_in": ("experts", "embed", "mlp"),
        "w_out": ("experts", "mlp", "embed"),
    }


def _route(x2d, router_w, n_experts, top_k, capacity):
    """Returns (dispatch [T, E, C] one-hot, combine [T, E, C] weights,
    aux_loss). Shapes static; overflow dropped."""
    logits = x2d.astype(jnp.float32) @ router_w.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)  # [T, E]
    t = x2d.shape[0]

    gates, experts = jax.lax.top_k(probs, top_k)  # [T, k]
    if top_k > 1:
        # GShard-style top-k gating: renormalize over the selected experts
        # so the combined output isn't attenuated by dropped probability
        # mass (sum of selected gates == 1).
        gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-9)
    # Load-balancing auxiliary loss (Switch Transformer eq. 4).
    density = jnp.mean(probs, axis=0)
    top1_mask = jax.nn.one_hot(experts[:, 0], n_experts)
    density_proxy = jnp.mean(top1_mask, axis=0)
    aux_loss = n_experts * jnp.sum(density * density_proxy)

    dispatch = jnp.zeros((t, n_experts, capacity), jnp.float32)
    combine = jnp.zeros((t, n_experts, capacity), jnp.float32)
    # Position of each token within its expert's capacity buffer: running
    # per-expert counts across the k routing slots keep positions unique.
    counts = jnp.zeros((n_experts,), jnp.float32)
    for j in range(top_k):
        onehot = jax.nn.one_hot(experts[:, j], n_experts)  # [T, E]
        prior = jnp.cumsum(onehot, axis=0) - onehot + counts[None, :]
        pos = jnp.sum(prior * onehot, axis=1).astype(jnp.int32)  # [T]
        counts = counts + jnp.sum(onehot, axis=0)
        keep = pos < capacity
        pos_oh = jax.nn.one_hot(pos, capacity)  # [T, C]
        sel = (onehot * keep[:, None])[:, :, None] * pos_oh[:, None, :]
        dispatch = dispatch + sel
        combine = combine + sel * gates[:, j][:, None, None]
    return dispatch, combine, aux_loss


def moe_ffn(params: dict, x: jax.Array, *, top_k: int = 1,
            capacity_factor: float = 1.25,
            activation=jax.nn.gelu) -> tuple[jax.Array, jax.Array]:
    """Dense-mesh MoE FFN. x: [B, T, D] -> ([B, T, D], aux_loss).

    All experts computed on every device (XLA partitions the expert einsum
    by the param shardings); for explicit expert parallelism use
    ``moe_ffn_ep``.
    """
    b, t, d = x.shape
    e = params["router"].shape[1]
    x2d = x.reshape(b * t, d)
    capacity = max(1, int(capacity_factor * (b * t) / e))
    dispatch, combine, aux = _route(x2d, params["router"], e, top_k, capacity)
    # [E, C, D] expert inputs via einsum dispatch.
    expert_in = jnp.einsum("tec,td->ecd", dispatch, x2d.astype(jnp.float32))
    h = jnp.einsum("ecd,edf->ecf", expert_in, params["w_in"].astype(jnp.float32))
    h = activation(h)
    expert_out = jnp.einsum("ecf,efd->ecd", h, params["w_out"].astype(jnp.float32))
    out = jnp.einsum("tec,ecd->td", combine, expert_out)
    return out.reshape(b, t, d).astype(x.dtype), aux


def moe_ffn_ep(params: dict, x: jax.Array, mesh: Mesh, *,
               axis: str = "ep", top_k: int = 1,
               capacity_factor: float = 1.25,
               activation=jax.nn.gelu,
               batch_axes=("dp", "fsdp")) -> tuple[jax.Array, jax.Array]:
    """Expert-parallel MoE: tokens all_to_all to their experts' devices.

    x: [B, T, D] with B sharded over batch_axes; experts sharded over
    ``axis``. Per-device: route locally, all_to_all token buffers so each
    device holds only its E/ep experts' inputs, compute FFN, route back.
    """
    ep = mesh.shape[axis]
    e = params["router"].shape[1]
    if e % ep:
        raise ValueError(f"n_experts {e} must divide by ep={ep}")

    local = functools.partial(
        moe_ffn_ep_local, n_experts=e, axis=axis, top_k=top_k,
        capacity_factor=capacity_factor, activation=activation,
    )
    xspec = P(batch_axes, None, None)
    fn = shard_map(
        local,
        mesh=mesh,
        in_specs=(xspec, P(), P(axis, None, None), P(axis, None, None)),
        out_specs=(xspec, P()),
        check_vma=False,
    )
    return fn(x, params["router"], params["w_in"], params["w_out"])


def moe_ffn_ep_local(px, p_router, p_win, p_wout, *, n_experts: int,
                     axis: str = "ep", top_k: int = 1,
                     capacity_factor: float = 1.25,
                     activation=jax.nn.gelu):
    """Per-device expert-parallel FFN body — usable inside ANY shard_map
    whose mesh has an ``axis`` dimension (e.g. a pipeline stage under the
    ``pp`` shard_map), not just the one ``moe_ffn_ep`` builds. w_in/w_out
    carry this device's E/ep expert slices; the router is replicated."""
    e = n_experts
    b, t, d = px.shape
    x2d = px.reshape(b * t, d)
    capacity = max(1, int(capacity_factor * (b * t) / e))
    dispatch, combine, aux = _route(x2d, p_router, e, top_k, capacity)
    # [E, C, D] on this device -> exchange so device i holds expert
    # rows for its local experts from ALL devices' tokens:
    expert_in = jnp.einsum("tec,td->ecd", dispatch, x2d.astype(jnp.float32))
    # [E, C, D] -> [E/ep, ep*C, D]: split experts, concat capacity.
    expert_in = jax.lax.all_to_all(
        expert_in, axis, split_axis=0, concat_axis=1, tiled=True
    )
    h = activation(jnp.einsum(
        "ecd,edf->ecf", expert_in, p_win.astype(jnp.float32)
    ))
    expert_out = jnp.einsum("ecf,efd->ecd", h, p_wout.astype(jnp.float32))
    # Route back: [E/ep, ep*C, D] -> [E, C, D].
    expert_out = jax.lax.all_to_all(
        expert_out, axis, split_axis=1, concat_axis=0, tiled=True
    )
    out = jnp.einsum("tec,ecd->td", combine, expert_out)
    aux = jax.lax.pmean(aux, axis)
    return out.reshape(b, t, d).astype(px.dtype), aux


# -- the dropless share layer (serving path) ----------------------------------
#
# An expert layer that is told which experts it holds: the router keeps the
# model's width and its experts per token, and this chip computes what its
# own experts add for the tokens routed to them. What the absent experts
# would have added is left out (the chips that hold them add it).


def route(x: jax.Array, w_gate: jax.Array, bias: jax.Array, top_k: int,
          scale: float) -> tuple[jax.Array, jax.Array]:
    """Sigmoid router over ALL experts, in float32.

    x [T, D], w_gate [D, E], bias [E] (the score correction: it moves the
    choice, not the weight). -> ids [T, K] int32, weights [T, K] float32:
    the chosen experts' own scores, normalised over the chosen and times
    ``scale``."""
    scores = jax.nn.sigmoid(jnp.dot(
        x.astype(jnp.float32), w_gate.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    _, ids = jax.lax.top_k(scores + bias.astype(jnp.float32), top_k)
    w = jnp.take_along_axis(scores, ids, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) * scale
    return ids.astype(jnp.int32), w


def route_topk_softmax(x: jax.Array, w_gate: jax.Array, top_k: int
                       ) -> tuple[jax.Array, jax.Array]:
    """Top-k of the experts' float32 logits, softmax over the chosen.

    x [T, D], w_gate [D, E] over ALL experts. -> ids [T, K] int32,
    weights [T, K] float32 that sum to one a row."""
    logits = jnp.dot(x.astype(jnp.float32), w_gate.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    top, ids = jax.lax.top_k(logits, top_k)
    return ids.astype(jnp.int32), jax.nn.softmax(top, axis=-1)


def route_group_limited(x: jax.Array, w_gate: jax.Array, top_k: int,
                        n_group: int, topk_group: int, scale: float
                        ) -> tuple[jax.Array, jax.Array]:
    """Group-limited greedy router: a softmax over ALL experts in float32,
    the choice held to a token's best groups.

    x [T, D], w_gate [D, E]; the E experts lie in ``n_group`` groups of
    E // n_group consecutive ids (one group a device where the experts are
    spread out, so a token goes to at most ``topk_group`` devices). A
    group's score is the largest softmax score among its experts; the
    ``topk_group`` best groups keep their experts' scores and the others'
    become 0; the ``top_k`` largest of what is left are chosen. -> ids
    [T, K] int32, weights [T, K] float32: the chosen experts' own softmax
    scores times ``scale``, NOT normalised over the chosen."""
    scores = jax.nn.softmax(jnp.dot(
        x.astype(jnp.float32), w_gate.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST), axis=-1)
    t, e = scores.shape
    by_group = scores.reshape(t, n_group, e // n_group)
    _, best = jax.lax.top_k(jnp.max(by_group, axis=-1), topk_group)
    kept = jnp.any(best[..., None] == jnp.arange(n_group), axis=1)  # [T, G]
    left = jnp.where(kept[..., None], by_group, 0.0).reshape(t, e)
    w, ids = jax.lax.top_k(left, top_k)
    return ids.astype(jnp.int32), w * scale


def dropless_experts(h: jax.Array, ids: jax.Array, weights: jax.Array,
                     w1: jax.Array, w2: jax.Array, *, first: int,
                     activation, live: jax.Array | None = None
                     ) -> tuple[jax.Array, jax.Array]:
    """The held experts' part of a routed layer, nothing dropped.

    h [T, D] rows, ids / weights [T, K] over all experts (``route``),
    w1 [E, D, F] and w2 [E, F, D] the experts ``first .. first + E`` held
    here; ``activation`` between the two products; ``live`` [T] bool, where
    given, says which rows are real (a padded lane's other rows are routed
    nowhere and get zeros). -> (out [T, D] float32:
    ``sum_k weights[t, k] * expert_{ids[t, k]}(h[t])`` over the pairs whose
    expert is held, and counts [E] int32: the pairs each held expert took).

    One result in two forms, chosen by the experts' widths alone
    (``moe_experts.takes_kernel``). Experts of whole lane tiles, which is
    every published width, go through the Pallas kernel of
    ``ops/moe_experts.py`` at any row count: the pairs are sorted by expert,
    an expert that took no row is neither read nor computed and one that
    took rows is multiplied with those rows only (on a v5e it is the faster
    form at all five serving shapes from 33 rows to 1,024, against both the
    batched product it replaced and the TPU's grouped product: PERF.md
    section 6, PR 52). Toy widths (the tiny presets the CPU tests run)
    apply every held expert to every row in one batched XLA product and
    mask it by the routing weights. Products in the rows' type accumulate
    in float32, the activation and the sum over a token's experts are
    float32."""
    n_held = w1.shape[0]
    local = ids - first
    held = (local >= 0) & (local < n_held)
    if live is not None:
        held = held & live[:, None]
    weights = jnp.where(held, weights, 0.0)
    w1, w2 = w1.astype(h.dtype), w2.astype(h.dtype)
    share = _kernel_share if moe_experts.takes_kernel(
        h.shape[1], w1.shape[2], w2.shape[1]) else _batched_share
    return share(h, local, held, weights, w1, w2, activation)


def held_counters(counts: list) -> dict:
    """Over a step's expert layers (``dropless_experts``' counts, one [E]
    a layer): the held experts that took at least one row, the
    token-expert pairs that landed here, and the row tiles the kernel
    computed for them (``ceil(count / ROW_TILE)`` an expert:
    ``expert_rows`` over ``expert_row_tiles x ROW_TILE`` is the tiles'
    fill, ``experts_hit`` over the held the share of experts fetched)."""
    if not counts:
        return {"experts_hit": jnp.int32(0), "expert_rows": jnp.int32(0),
                "expert_row_tiles": jnp.int32(0)}
    stacked = jnp.stack(counts)
    return {"experts_hit": jnp.sum(stacked > 0, dtype=jnp.int32),
            "expert_rows": jnp.sum(stacked, dtype=jnp.int32),
            "expert_row_tiles": jnp.sum(moe_experts.row_tiles(stacked),
                                        dtype=jnp.int32)}


def _tile_table(count, pairs):
    """The kernel's row tiles for experts that took ``count`` [E] of a
    lane's ``pairs`` sorted pairs -> each tile's (expert, first pair in the
    sorted order, pairs), [``n_tiles``] int32 each. A tile past the last
    live one has no pairs and names that one's expert: its weight blocks
    stand still and nothing is fetched for it. Written as comparisons of
    every tile with every expert (a few hundred by a hundred at most), which
    the compiler makes one fusion of, where a search and gathers from
    [E]-long tables would each be an operation a layer."""
    tile, n_held = moe_experts.ROW_TILE, count.shape[0]
    tiles = moe_experts.row_tiles(count)
    e = jnp.arange(n_held, dtype=jnp.int32)
    tile_end = jnp.sum(jnp.where(e[:, None] <= e[None, :], tiles[:, None],
                                 0), axis=0)  # the running sum, inclusive
    i = jnp.arange(moe_experts.n_tiles(pairs, n_held), dtype=jnp.int32)
    live = i < tile_end[-1]
    at = jnp.where(live, i, jnp.maximum(tile_end[-1] - 1, 0))
    before = at[:, None] >= tile_end[None, :]  # [tile, expert]: e < its own
    expert = jnp.minimum(jnp.sum(before, axis=1, dtype=jnp.int32),
                         n_held - 1)
    mine = e[None, :] == expert[:, None]
    nth = at - jnp.sum(jnp.where(before, tiles, 0), axis=1)  # of its expert
    start = jnp.sum(jnp.where(before, count, 0), axis=1) + nth * tile
    left = jnp.sum(jnp.where(mine, count, 0), axis=1) - nth * tile
    return expert, start, jnp.where(live, jnp.minimum(left, tile), 0)


def _kernel_share(h, local, held, weights, w1, w2, activation):
    """The pairs sorted by expert and cut into the kernel's row tiles; the
    products, and each pair's way back to its token, are the kernel's. A
    lane longer than the kernel holds goes through it a block of rows at a
    time."""
    n_rows, k = local.shape
    n_held = w1.shape[0]
    out, counts = [], jnp.zeros((n_held,), jnp.int32)
    for r in range(0, n_rows, moe_experts.LANE_ROWS):
        lane = slice(r, r + moe_experts.LANE_ROWS)
        with jax.named_scope("moe_dispatch"):
            key = jnp.where(held[lane], local[lane], n_held).reshape(-1)
            order = jnp.argsort(key).astype(jnp.int32)
            count = jnp.sum(key[:, None] == jnp.arange(n_held)[None, :],
                            axis=0, dtype=jnp.int32)
            tiles = _tile_table(count, key.shape[0])
        with jax.named_scope("experts"):
            out.append(moe_experts.grouped_experts(
                h[lane], *tiles, order // k,
                weights[lane].reshape(-1)[order], w1, w2, activation))
        counts = counts + count
    with jax.named_scope("moe_combine"):
        return jnp.concatenate(out), counts


def _batched_share(h, local, held, weights, w1, w2, activation):
    """Every held expert over every row in one batched product a matrix,
    masked by each row's weight on each expert: the form of widths that
    are no whole lane tiles."""
    with jax.named_scope("moe_dispatch"):
        onehot = (local[..., None] == jnp.arange(w1.shape[0])) \
            & held[..., None]  # [T, K, E]
        counts = jnp.sum(onehot, axis=(0, 1), dtype=jnp.int32)
        by_expert = jnp.sum(
            jnp.where(onehot, weights[..., None], 0.0), axis=1)  # [T, E]
    with jax.named_scope("experts"):
        a = activation(jnp.einsum(
            "td,edf->etf", h, w1,
            preferred_element_type=jnp.float32)).astype(h.dtype)
        # (its result in the rows' type: XLA's CPU backend cannot run a
        # batched bfloat16 product into float32 behind another one)
        y = jnp.einsum("etf,efd->etd", a, w2)
    with jax.named_scope("moe_combine"):
        out = jnp.einsum("te,etd->td", by_expert, y.astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
    return out, counts
