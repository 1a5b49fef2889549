"""Read the serving cells' logits comparison over many seeds in ONE
process, clean or with a lower precision put into the SYSTEM by hand: the
readings a configuration's ``serve_logits_rel_l2`` is set from, and the
control that shows the comparison fails where it should.

    python3 benchmark/tools/serve_check_many.py --workload <serving cell> \
        --seeds 12 [--first-seed N] [--scale-leaf NAME=FACTOR] \
        [--fault none|fp8_weights|fp8_experts|state_bf16] [--engine]

Without ``--engine`` only ``compare.check_serve`` runs (the reference's
greedy continuation, then the serving functions through a fresh cache).
With it the cell's whole set-up runs for every seed, as
``kinds/serve_common.start_engine`` makes it: the same comparison, then the
engine deployed WITH the fault and what it serves held to the reference's
tokens (``serve_token_regret_rms``); one seed a process then, because a
stopped engine's weights and cache stay on the device until the process
ends. Faults, each on the system alone (the reference keeps the seeded
weights):

* ``fp8_weights``: every weight matrix (a leaf of two or more dimensions)
  rounded to float8 (e4m3, scaled per output channel) and back: the nearest
  matmul precision below bfloat16, model-wide;
* ``fp8_experts``: only the stacked expert matrices (leaves of three or more
  dimensions);
* ``state_bf16``: a configuration object that has ``ssm_state_dtype`` gets
  bfloat16 there (the recurrent state held in the activations' type).

By hand only: the driver never runs this, and it patches the program from
outside. The last line is one JSON object: per seed the largest reading
(what the check compares with its limit), the smallest and the median of
its positions (a position where rounding turned no routing choice reads
the smallest: the floor a lower precision must rise over to be seen at
all), whether each check passed and every reading; with ``--engine`` the
largest regret of the deployed engine's tokens, without it the same regret
of the serving functions' own greedy choice at ALL positions (they are fed
the reference's tokens, so a turned token ends nothing): what
``serve_token_regret_rms`` has to leave room for.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import compare  # noqa: E402
from benchmark import run as bench_run  # noqa: E402


def rounded(params, min_ndim: int):
    import jax
    import jax.numpy as jnp

    def one(x):
        if x.ndim < min_ndim:
            return x
        # scaled per output channel, as a float8 deployment would; the
        # rounding is an operation of its own (a pair of converts is what
        # the compiler may drop: it is allowed excess precision)
        # (4 exponent bits with an infinity: the largest finite value is 240)
        scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-2,
                        keepdims=True) / 240.0 + 1e-30
        low = jax.lax.reduce_precision(x.astype(jnp.float32) / scale,
                                       exponent_bits=4, mantissa_bits=3)
        return (low * scale).astype(x.dtype)

    return jax.jit(lambda p: jax.tree.map(one, p), donate_argnums=(0,))(
        params)


def scale_leaves(family, spec: str) -> None:
    """``name=factor``: every leaf of that name in the seeded weights times
    ``factor``, for the system and the reference alike (they share
    ``init_params``): how the readings move with a part's share of the
    output."""
    import jax

    name, factor = spec.split("=")
    init = family.init_params

    def scaled(config, seed):
        return jax.tree_util.tree_map_with_path(
            lambda path, x: x * float(factor)
            if getattr(path[-1], "key", None) == name else x,
            init(config, seed))

    family.init_params = scaled


def patch(family, fault: str) -> None:
    """The fault in the serving functions of the logits comparison and in
    every engine deployed from here on."""
    if fault in ("fp8_weights", "fp8_experts"):
        from ray_tpu.serve import llm_engine

        logits, bundle = family.serve_logits, llm_engine._model_bundle
        ndim = 2 if fault == "fp8_weights" else 3
        family.serve_logits = lambda config, params, *a, **k: logits(
            config, rounded(params, ndim), *a, **k)

        def faulty(model, config, preset):
            cfg, init, *rest = bundle(model, config, preset)
            return (cfg, lambda key, cfg: rounded(init(key, cfg), ndim),
                    *rest)

        llm_engine._model_bundle = faulty
    elif fault == "state_bf16":
        import jax.numpy as jnp

        system_config = family.system_config
        family.system_config = lambda config: dataclasses.replace(
            system_config(config), ssm_state_dtype=jnp.bfloat16)


def function_regret(got, ref) -> tuple:
    """(largest regret, tokens turned) of the serving functions' greedy
    choice, by ``compare.check_engine_tokens``'s measure: how far below its
    best the reference ranks the chosen token, in the row's spread."""
    import numpy as np

    want = ref["logits"]
    chosen = got[..., :want.shape[-1]].argmax(-1)
    value = lambda ids: np.take_along_axis(want, ids[..., None], -1)[..., 0]
    regret = (value(ref["tokens"]) - value(chosen)) / want.std(-1)
    return float(regret.max()), int((chosen != ref["tokens"]).sum())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=2147480000)
    ap.add_argument("--fault", default="none", choices=(
        "none", "fp8_weights", "fp8_experts", "state_bf16"))
    ap.add_argument("--scale-leaf", default=None, metavar="NAME=FACTOR")
    ap.add_argument("--engine", action="store_true")
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--root", default=os.path.dirname(bench_run.PKG_DIR))
    args = ap.parse_args(argv)
    if args.engine and args.seeds != 1:
        ap.error("--engine takes --seeds 1: a stopped engine's arrays stay "
                 "on the device until the process ends")
    out = []
    for i in range(args.seeds):
        run = bench_run.Run(args.root, args.workload, args.first_seed + i,
                            1.0, False, args.rehearsal)
        if i == 0:
            if not run.take_devices():
                return 2
            devices = (run.devices, run.device_kind, run.all_devices)
            patch(run.family, args.fault)
            if args.scale_leaf:
                scale_leaves(run.family, args.scale_leaf)
            kept, logits = {}, run.family.serve_logits

            def keeping(*a, **k):
                import numpy as np

                kept["got"] = np.asarray(logits(*a, **k), np.float32)
                return kept["got"]

            run.family.serve_logits = keeping
        run.devices, run.device_kind, run.all_devices = devices
        said = []
        say = run.say
        run.say = lambda event, **f: (said.append((event, f)),
                                      say(event, **f))[1]
        if args.engine:
            common = bench_run.load_module(os.path.join(
                run.bench_dir, "kinds", "serve_common.py"))
            handle = None
            try:
                handle = common.start_engine(run)
            finally:
                common.stop_engine(run, handle)
        else:
            ref = compare.check_serve(run, run.params["engine"])
        checks = {n: (ok, d) for n, ok, d in run.checks}
        readings = json.loads(
            checks["reference_logits"][1].split("per position: ")[1]
            .split(", tolerance")[0])
        flat = sorted(x for row in readings for x in row)
        one = {"seed": run.seed, "ok": checks["reference_logits"][0],
               "max": flat[-1], "min": flat[0],
               "median": flat[len(flat) // 2], "readings": readings}
        if args.engine:
            tokens = [f for e, f in said if e == "reference_tokens"][0]
            one.update(tokens_ok=checks["reference_tokens"][0],
                       regret=tokens["regret_rms_max"],
                       flips=tokens["flips"], compared=tokens["compared"])
        else:
            one["regret"], one["turned"] = function_regret(kept["got"], ref)
        out.append(one)
        run._log.close()
    summary = {"fault": args.fault, "workload": args.workload,
               "largest": max(o["max"] for o in out),
               "failed": sum(not o["ok"] for o in out),
               "largest_regret": max(o["regret"] for o in out)}
    if args.engine:
        summary["tokens_failed"] = sum(not o["tokens_ok"] for o in out)
    print(json.dumps({**summary, "seeds": out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
