"""Show that the serving cells' ``correct`` fails where it should: run a
serving cell's set-up (both reference comparisons) with a fault put into
the system by hand, and print what each comparison read.

    python3 benchmark/tools/lowprec.py --workload <serving cell> \
        --fault int8|int4|bf16 [--seed N]

``bf16`` / ``int8`` / ``int4`` round every weight matrix of the SYSTEM
(the serving functions in the logits comparison, and the deployed engine)
to that type, per output channel, and back; the reference keeps the seeded
float32 weights. By hand only: the driver never runs this, and a later PR
cannot pass through it, since it patches the program from outside.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import run as bench_run  # noqa: E402


def rounded(params, fault: str):
    """Every matrix (``wte``, ``wpe``, the blocks' ``*_w``) rounded, in
    the buffers of ``params`` (two float32 copies do not fit the chip)."""
    import jax

    return jax.jit(lambda p: _rounded(p, fault), donate_argnums=(0,))(params)


def _rounded(params, fault: str):
    import jax.numpy as jnp

    def one(w, axis):
        if fault == "bf16":
            return w.astype(jnp.bfloat16).astype(w.dtype)
        top = {"int8": 127.0, "int4": 7.0}[fault]
        scale = jnp.max(jnp.abs(w), axis=axis, keepdims=True) / top
        return (jnp.round(w / scale) * scale).astype(w.dtype)

    out = dict(params)
    out["wte"], out["wpe"] = one(params["wte"], -1), one(params["wpe"], -1)
    out["blocks"] = {k: one(v, -2) if k.endswith("_w") else v
                     for k, v in params["blocks"].items()}
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--fault", required=True,
                    choices=("bf16", "int8", "int4"))
    ap.add_argument("--seed", type=int, default=2147480000)
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--root", default=os.path.dirname(bench_run.PKG_DIR))
    args = ap.parse_args()
    run = bench_run.Run(args.root, args.workload, args.seed, 1.0, False,
                        args.rehearsal)
    if not run.take_devices():
        return 2
    from ray_tpu.serve import llm_engine

    bundle = llm_engine._model_bundle

    def faulty(model, config, preset):
        cfg, init, init_cache, prefill, decode = bundle(model, config, preset)
        return (cfg, lambda key, cfg: rounded(init(key, cfg), args.fault),
                init_cache, prefill, decode)

    logits = run.family.serve_logits
    run.family.serve_logits = lambda config, params, *a, **k: logits(
        config, rounded(params, args.fault), *a, **k)
    llm_engine._model_bundle = faulty
    common = bench_run.load_module(os.path.join(
        run.bench_dir, "kinds", "serve_common.py"))
    handle = None
    try:
        handle = common.start_engine(run)
    finally:
        common.stop_engine(run, handle)
    print(json.dumps({"fault": args.fault, "checks": [
        [name, ok] for name, ok, _ in run.checks]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
