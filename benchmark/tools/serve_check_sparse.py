"""Hold the key SETS a sparse-attention family's programs picked to the
reference's, beside the logits that ``compare.check_serve`` holds, and say
what a wrong set does to those logits.

    python3 benchmark/tools/serve_check_sparse.py --workload <serving cell> \\
        --seeds 3 [--first-seed N] [--fault]

For every seed, at the configuration's own widths, from the seeded weights
(``compare.check_serve`` first: its prompts and the reference's greedy
tokens are what everything here is fed): the serving path's own chunk
program and decode steps run again through a fresh cache and hand back what
each layer picked (``families/<family>.serve_logits`` with ``sets``): of
every chunk a few evenly spaced PREFILL queries' sets, of every step each
prompt's set. The reference runs once over each prompt with its followed
tokens and keeps, for the same query positions, its index scores, its sets
and the stream each layer received. Three readings a layer:

* OVERLAP: rows picked by both over the reference's set, averaged over the
  layer's queries (``overlap_by_layer``; the prefill's queries and the
  steps' apart, under ``prefill`` and ``steps``).
* MARGIN: for every row only one of them picked, how far that row's index
  score (the reference's) lies from the score of the reference's last
  pick, in units of the spread of that query's scores.
* FORCED margin (``forced``): the same two readings of what the programs'
  indexer and selection pick when each layer is fed the REFERENCE's stream
  (``families/<family>.forced_picks``): the indexer's own bfloat16
  arithmetic alone. What the live programs' margins hold beyond it comes
  from the stream: from the second layer on the query's row was computed
  from a bfloat16 stream in which a routing choice that rounding turned
  upstream may have swapped one expert for another, and the whole score row
  of that query then shifts.

A seed passes if every set has the reference's size, every layer's overlap
is at least ``OVERLAP_MIN``, every differing row's margin is under
``MARGIN_EPS``, every FORCED margin under ``FORCED_EPS``, and the logits of
THIS run of the serving functions (not ``check_serve``'s) lie within the
configuration's ``serve_logits_rel_l2`` of the reference's
(``logits_rel_l2``, ``logits_ok``).

``--fault`` shifts the indexer's rings by one row between the prefill and
the steps (every score then belongs to its neighbour's position): the
control, which must fail; ``logits_ok`` then says whether ``correct``, which
holds logits only, would have caught it. By hand only: the driver never
runs this. The last line is one JSON object.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import compare  # noqa: E402
from benchmark import run as bench_run  # noqa: E402

# The largest margin a differing row may have, in units of the spread of
# its query's index scores, the least overlap a layer may average, and the
# largest margin under the reference's own stream (my chip runs, PR 60,
# published widths, 80 queries a seed. Sound, 3 seeds: largest margin a
# layer 0.007-0.497, overlap a layer 0.9808-0.9993, forced margins
# 0.011-0.036. The fault, 6 seeds: the steps' largest margin a layer
# 1.02-5.62 (a seed's largest 4.89-5.62), their overlap a layer
# 0.661-0.951; the forced readings do not see the fault, which is made to
# the rings and not to the arithmetic).
MARGIN_EPS = 1.0
OVERLAP_MIN = 0.97
FORCED_EPS = 0.05


def shifted(cache):
    """The control: the indexer's rings one row on."""
    import jax.numpy as jnp

    return {**cache, "idx": jnp.roll(cache["idx"], 1, axis=2)}


def readings(got, want, scores, at, real) -> dict:
    """got, want [layers, R, Q, T] bool (the programs' sets and the
    reference's, by position), scores [layers, R, Q, T] the reference's
    index scores, at [R, Q] the queries' positions, real [R, Q] which of
    them exist (a query past its prompt's end must have picked nothing)."""
    import numpy as np

    layers = got.shape[0]
    wrong_size = int((got[:, ~real].any()))
    overlap, margins = [[] for _ in range(layers)], [[] for _ in range(layers)]
    for row, q in zip(*np.nonzero(real)):
        t = at[row, q]
        for layer in range(layers):
            mine, ref_set = got[layer, row, q], want[layer, row, q]
            wrong_size += int(mine.sum() != ref_set.sum())
            overlap[layer].append((mine & ref_set).sum()
                                  / max(ref_set.sum(), 1))
            seen = scores[layer, row, q, :t + 1]
            last = seen[ref_set[:t + 1]].min()
            differ = np.nonzero(mine != ref_set)[0]
            margins[layer].extend(
                (np.abs(scores[layer, row, q, differ] - last)
                 / seen.std()).tolist())
    return {"queries": int(real.sum()), "wrong_size": wrong_size,
            "overlap_by_layer": np.round(
                [np.mean(o) for o in overlap], 5).tolist(),
            "overlap_min": float(min(min(o) for o in overlap)),
            "rows_differing_by_layer": [len(m) for m in margins],
            "largest_margin_by_layer": np.round(
                [max(m, default=0.0) for m in margins], 5).tolist(),
            "median_margin_by_layer": np.round(
                [np.median(m) if m else 0.0 for m in margins], 5).tolist()}


def one_seed(run, fault: bool) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    config, engine = run.config, run.params["engine"]
    ref = compare.check_serve(run, engine)
    tokens = np.asarray(ref["tokens"])                     # [R, N + 1]
    r, n = tokens.shape[0], tokens.shape[1] - 1
    lens = np.asarray([len(p) for p in ref["prompts"]], np.int32)
    prompts = np.zeros((r, engine["max_prompt_len"]), np.int32)
    full = np.zeros((r, lens.max() + n), np.int32)
    for i, p in enumerate(ref["prompts"]):
        prompts[i, :len(p)] = p
        full[i, :len(p)] = p
        full[i, len(p):len(p) + n] = tokens[i, :n]
    params = run.family.init_params(config, compare.jax_seed(run.seed))

    picked = {}
    got = np.asarray(run.family.serve_logits(
        config, params, jnp.asarray(prompts), jnp.asarray(lens),
        jnp.asarray(tokens[:, :n]), slots=r + 1,
        cache_len=engine["cache_len"], sets=picked,
        cache_fault=shifted if fault else None), np.float32)
    vocab = ref["logits"].shape[-1]
    err = np.linalg.norm(got[..., :vocab] - ref["logits"], axis=-1) \
        / np.linalg.norm(ref["logits"], axis=-1)
    limit = config["tolerance"]["serve_logits_rel_l2"]

    # the queries: a few of every chunk (the same positions for every
    # prompt; real where the prompt reaches them), then every step's
    chunk_at, chunk_sets = picked["prefill"]
    at = np.concatenate([np.broadcast_to(chunk_at, (r, len(chunk_at))),
                         lens[:, None] + np.arange(n)[None, :]], axis=1)
    real = np.concatenate([chunk_at[None, :] < lens[:, None],
                           np.ones((r, n), bool)], axis=1)
    at = np.where(real, at, 0)
    width = full.shape[1]
    mine = np.zeros(chunk_sets.shape[:2] + (at.shape[1], width), bool)
    mine[:, :, :len(chunk_at), :chunk_sets.shape[-1]] = \
        chunk_sets[..., :width]
    for i, (rows, sizes) in enumerate(picked["steps"]):
        rows, sizes = np.asarray(rows), np.asarray(sizes)
        for layer, row in np.ndindex(sizes.shape):
            mine[layer, row, len(chunk_at) + i,
                 rows[layer, row, :sizes[layer, row]]] = True

    def ref_rows(p, t):
        _, kept = run.reference.forward(
            run.family.to_reference(p, config), t, with_sets=True,
            with_streams=True, **run.family.reference_kwargs(config))
        rows = np.arange(r)[:, None]
        return (jnp.stack([x[0][rows, at] for x in kept]),
                jnp.stack([x[1][rows, at] for x in kept]),
                jnp.stack([x[2] for x in kept]))

    # [layers, R, Q, T]: the queries' scores and sets; the layers' streams
    scores, want, streams = jax.jit(ref_rows)(params, jnp.asarray(full))
    forced = np.array(run.family.forced_picks(
        config, params, streams, jnp.asarray(at)))
    del streams
    scores, want = np.asarray(scores), np.asarray(want)
    forced[:, ~real] = False  # (position 0 stands in where no query is)
    chunks = np.arange(at.shape[1]) < len(chunk_at)
    out = {"seed": run.seed, "logits_rel_l2": float(err.max()),
           "logits_ok": bool(np.isfinite(err).all() and err.max() <= limit),
           "set_sizes": sorted({int(s) for _, sizes in picked["steps"]
                                for s in np.asarray(sizes).reshape(-1)}),
           "forced": readings(forced, want, scores, at, real)}
    for name, of in (("prefill", chunks), ("steps", ~chunks)):
        out[name] = readings(mine[:, :, of], want[:, :, of],
                             scores[:, :, of], at[:, of], real[:, of])
    out["ok"] = out["logits_ok"] and all(
        out[name]["wrong_size"] == 0
        and max(out[name]["largest_margin_by_layer"]) < eps
        and min(out[name]["overlap_by_layer"]) >= OVERLAP_MIN
        for name, eps in (("prefill", MARGIN_EPS), ("steps", MARGIN_EPS),
                          ("forced", FORCED_EPS)))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2147482000)
    ap.add_argument("--fault", action="store_true")
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--root", default=os.path.dirname(bench_run.PKG_DIR))
    args = ap.parse_args(argv)
    out, devices = [], None
    for i in range(args.seeds):
        run = bench_run.Run(args.root, args.workload, args.first_seed + i,
                            1.0, False, args.rehearsal)
        if devices is None:
            if not run.take_devices():
                return 2
            devices = (run.devices, run.device_kind, run.all_devices)
        run.devices, run.device_kind, run.all_devices = devices
        out.append(one_seed(run, args.fault))
        run._log.close()
    print(json.dumps({
        "workload": args.workload, "fault": args.fault,
        "margin_eps": MARGIN_EPS, "overlap_min_a_layer": OVERLAP_MIN,
        "forced_eps": FORCED_EPS,
        "failed": sum(not o["ok"] for o in out),
        "logits_failed": sum(not o["logits_ok"] for o in out),
        "logits_rel_l2": [o["logits_rel_l2"] for o in out],
        **{f"{name}_{what}": of(of(o[name][key]) if by_layer
                                else o[name][key] for o in out)
           for name in ("prefill", "steps", "forced")
           for what, key, of, by_layer in (
               ("overlap_a_layer_min", "overlap_by_layer", min, True),
               ("overlap_min", "overlap_min", min, False),
               ("largest_margin", "largest_margin_by_layer", max, True))},
        "seeds": out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
