"""1F1B vs GPipe pipeline microbenchmark (CPU virtual devices).

Quantifies the two claims ``parallel/pipeline.py`` makes (the round-4
verdict asked for measurements, not assertions):

  * step time: both schedules share the bubble-fraction law
    (pp-1)/(n_micro+pp-1); 1F1B's interleaving shaves the flush tail
    (fewer ticks for the same work);
  * memory: 1F1B stashes O(pp) live activations per stage, GPipe
    O(n_micro) — read straight off XLA's compiled-buffer analysis.

Usage: python -m ray_tpu.scripts.pipeline_bench [--out MICROBENCH.json]
Writes/merges a "pipeline" section keyed by pp/n_micro/style.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time


def run_all() -> dict:
    # CPU-device benchmark by design: eight virtual devices, pinned
    # before any backend is initialized.
    os.environ["JAX_PLATFORMS"] = "cpu"
    import numpy as np

    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 8)
    import jax.numpy as jnp
    from jax.sharding import Mesh

    from ray_tpu.parallel.pipeline import (
        build_1f1b_schedule,
        pipeline_value_and_grad,
    )

    d_model, seq = 128, 64
    results: dict = {}
    for pp in (2, 4):
        n_micro = 4 * pp
        mb = 2
        batch = mb * n_micro
        mesh = Mesh(np.array(jax.devices()[:pp]).reshape(pp), ("pp",))
        rngs = jax.random.split(jax.random.key(0), pp)
        params = {
            "w1": jnp.stack([jax.random.normal(r, (d_model, 4 * d_model))
                             * 0.02 for r in rngs]),
            "w2": jnp.stack([jax.random.normal(r, (4 * d_model, d_model))
                             * 0.02 for r in rngs]),
        }
        x = jax.random.normal(jax.random.key(1), (batch, seq, d_model))
        y = jax.random.normal(jax.random.key(2), (batch, seq, d_model))

        def stage_fn(p, xx):
            return xx + jax.nn.gelu(xx @ p["w1"]) @ p["w2"]

        def loss_fn(o, yy):
            return jnp.mean((o - yy) ** 2)

        for style in ("1f1b", "gpipe"):
            def step(sp):
                return pipeline_value_and_grad(
                    sp, x, y, mesh, stage_fn=stage_fn, loss_fn=loss_fn,
                    n_micro=n_micro, style=style)

            jitted = jax.jit(step)
            compiled = jitted.lower(params).compile()
            mem = compiled.memory_analysis()
            temp_mb = getattr(mem, "temp_size_in_bytes", 0) / 2**20
            loss, grads = jitted(params)  # warm
            jax.block_until_ready(loss)
            t0 = time.perf_counter()
            steps = 10
            for _ in range(steps):
                loss, grads = jitted(params)
            jax.block_until_ready(loss)
            dt = (time.perf_counter() - t0) / steps
            ticks = len(build_1f1b_schedule(n_micro, pp, style)[0])
            # Every tick executes one (masked) fwd AND one (masked) bwd
            # slot, so a bubble-free schedule would need n_micro ticks;
            # the excess is warmup/drain slots that run masked work.
            ideal = n_micro
            key = f"pp{pp}_m{n_micro}_{style}"
            results[key] = {
                "step_ms": round(dt * 1000, 2),
                "ticks": ticks,
                "bubble_frac": round(1 - ideal / ticks, 4),
                "xla_temp_mb": round(temp_mb, 2),
            }
            print(f"{key}: {results[key]}", file=sys.stderr, flush=True)
    return results


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    results = run_all()
    if args.out:
        merged = {}
        if os.path.exists(args.out):
            with open(args.out) as f:
                merged = json.load(f)
        merged["pipeline"] = results
        merged.setdefault("meta", {})["pipeline_cmd"] = (
            "python -m ray_tpu.scripts.pipeline_bench")
        with open(args.out, "w") as f:
            json.dump(merged, f, indent=2, sort_keys=True)
    print(json.dumps(results))


if __name__ == "__main__":
    main()
