"""What a self-drafting family's verify-and-draft steps do beside the logits
that ``compare.check_serve`` holds: the prediction MODULE's logits against
the reference's module, and the main stack's logits with rejected drafts
among the steps against the same steps with every draft accepted.

    python3 benchmark/tools/serve_check_draft.py --workload <serving cell> \\
        --seeds 3 [--first-seed N] [--wrong-every K] [--fault fp8_weights]

For every seed, at the configuration's own widths, from the seeded weights
(``compare.check_serve`` first: its prompts and the reference's greedy
tokens are what everything here is fed):

* **the module**: the serving path's module logits (the family's
  ``_verify_steps``: the prefill chunks' last row, then both rows of every
  verify step, drafts accepted and, every ``K``-th step, a wrong one
  rejected) against ``reference.module_forward`` over the prompt and the
  reference's tokens, relative L2 a position. A position counts where the
  token the system fed its module there (its OWN greedy token) is the
  reference's: elsewhere the two modules read different inputs;
* **the main stack, drafts rejected or not**: the same steps' main logits
  against a second pass in which no draft is wrong (so every step yields
  two tokens and the rows fall differently into the steps): relative L2 a
  position. A rejected row that stayed readable, or a row that lost a key
  to a draft, shows here as whole tenths; rounding alone reads about 1e-3
  (a position is the first row of one pass's step and the second of the
  other's);
* **the device's comparison**: a step handed the reference's next token as
  its draft must count 2 where its own greedy token is that token, a step
  handed a wrong one must count 1.

``--fault fp8_weights`` rounds every matrix of the SYSTEM to float8 first
(``serve_check_many.py``'s): the control for the module's reading. By hand
only: the driver never runs this. The last line is one JSON object.
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
from benchmark.loading import sibling  # noqa: E402

many = sibling(__file__, "serve_check_many.py")


def rel_l2(got, want):
    import numpy as np

    return np.linalg.norm(got - want, axis=-1) / np.linalg.norm(want, axis=-1)


def one_seed(run, wrong_every: int, fault: str) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    config, engine = run.config, run.params["engine"]
    ref = compare.check_serve(run, engine)
    tokens = np.asarray(ref["tokens"])                     # [R, N + 1]
    r, n = tokens.shape[0], tokens.shape[1] - 1
    lens = np.asarray([len(p) for p in ref["prompts"]], np.int32)
    prompts = np.zeros((r, engine["max_prompt_len"]), np.int32)
    full = np.zeros((r, lens.max() + n + 1), np.int32)
    for i, p in enumerate(ref["prompts"]):
        prompts[i, :len(p)] = p
        full[i, :len(p)] = p
        full[i, len(p):len(p) + n + 1] = tokens[i]
    vocab = run.family.shape(config)["vocab"]
    params = run.family.init_params(config, compare.jax_seed(run.seed))
    at = lens[:, None] - 1 + np.arange(n + 1)[None, :]     # [R, N + 1]
    want = np.asarray(jax.jit(lambda p, t: run.reference.module_forward(
        run.family.to_reference(p, config), t,
        **run.family.reference_kwargs(config))[np.arange(r)[:, None], at])(
            params, jnp.asarray(full)), np.float32)
    if fault == "fp8_weights":
        params = many.rounded(params, 2)
    # both runs through one pair of compiled programs
    programs = run.family._serving_programs(run.family.system_config(config))
    steps = lambda every: run.family._verify_steps(
        config, params, jnp.asarray(prompts), jnp.asarray(lens),
        jnp.asarray(tokens[:, :n]), len(lens) + 1, engine["cache_len"],
        wrong_every=every, programs=programs)
    main, module, counts, wrong = steps(wrong_every)
    main = np.asarray(main[..., :vocab], np.float32)
    module = np.asarray(module[..., :vocab], np.float32)
    # the module at index j was fed the system's own greedy token there
    same = main.argmax(-1) == tokens
    err = rel_l2(module, want)
    accepted_all, *_ = steps(0)
    shift = rel_l2(np.asarray(accepted_all[..., :vocab], np.float32), main)
    counts = np.asarray(counts)                            # [steps, R]
    # the main index each step's first row stands at, to know its greedy
    first, index = [], 0
    for bad in wrong:
        first.append(index)
        index += 1 if bad else 2
    greedy_ok = np.stack([same[:, min(i + 1, n)] for i in first])
    expected = np.where(np.asarray(wrong)[:, None], 1,
                        np.where(greedy_ok, 2, 1))
    held = err[same]
    return {
        "seed": run.seed, "positions": int(same.size),
        "module_positions_compared": int(same.sum()),
        "module_rel_l2_max": float(held.max()),
        "module_rel_l2_median": float(np.median(held)),
        "module_rel_l2_min": float(held.min()),
        "module_rel_l2": np.round(err, 5).tolist(),
        "module_fed_the_references_token": same.tolist(),
        "main_shift_rel_l2_max": float(shift.max()),
        "main_shift_rel_l2": np.round(shift, 6).tolist(),
        "steps": len(wrong), "wrong_drafts": [bool(b) for b in wrong],
        "device_counts": counts.tolist(),
        "counts_as_expected": bool((counts == expected).all()),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2147480000)
    ap.add_argument("--wrong-every", type=int, default=2)
    ap.add_argument("--fault", default="none",
                    choices=("none", "fp8_weights"))
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
        if not hasattr(run.family, "_verify_steps") \
                or not hasattr(run.reference, "module_forward"):
            print(f"{args.workload}: its family drafts nothing",
                  file=sys.stderr)
            return 2
        out.append(one_seed(run, args.wrong_every, args.fault))
        run._log.close()
    print(json.dumps({
        "workload": args.workload, "fault": args.fault,
        "module_rel_l2_max": max(o["module_rel_l2_max"] for o in out),
        "main_shift_rel_l2_max": max(o["main_shift_rel_l2_max"]
                                     for o in out),
        "counts_as_expected": all(o["counts_as_expected"] for o in out),
        "seeds": out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
