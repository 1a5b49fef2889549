"""Fused-norm kernel microbench point (perfsuite ``--fused-norm``).

Pins the ``ops/fused_norm.py`` kernels' shape coverage into
MICROBENCH.json machine-independently: per shape it records the chosen
row block, the number of Pallas kernel launches in a fwd+bwd trace
(trace-time counters — wall-clock-free), the fp32 bytes the fused path
keeps out of HBM per step (saved-statistics vs XLA's materialized fp32
recompute chain), and fwd/grad parity error vs the plain-XLA chain.
Kernel-only µs (CPU interpret vs the XLA fusion, jitted, best-of-N) ride
along for relative sanity only — interpret-mode wall time is NOT a TPU
perf claim; the on-chip numbers come from ``tpu_sweep``.

Run: python -m ray_tpu.scripts.fused_norm_bench [--out MICROBENCH.json]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

# CPU-interpret benchmark by design, same as pipeline_bench: this stage
# pins shape coverage, not TPU speed.
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp  # noqa: E402

from ray_tpu.ops import fused_norm as fn  # noqa: E402

# (name, kind, rows, d): the GPT-2-small / Llama-small shapes the models
# feed the kernels, plus one deliberately untileable shape to pin the
# fallback contract.
SHAPES = [
    ("gpt2_ln_768", "ln", 256, 768),
    ("llama_rms_1024", "rms", 256, 1024),
    ("gpt2_gelu_3072", "gelu", 256, 3072),
    ("odd_d100_fallback", "ln", 64, 100),
]


def _time_us(f, *args, reps: int = 5) -> float:
    g = jax.jit(f)
    jax.block_until_ready(g(*args))  # compile outside the timed reps
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        out = g(*args)
        jax.block_until_ready(out)
        best = min(best, time.perf_counter() - t0)
    return round(best * 1e6, 1)


def bench_point(kind: str, rows: int, d: int) -> dict:
    ks = jax.random.split(jax.random.key(0), 3)
    x = jax.random.normal(ks[0], (rows, d), jnp.float32)
    scale = jax.random.normal(ks[1], (d,), jnp.float32) * 0.1 + 1.0
    bias = jax.random.normal(ks[2], (d,), jnp.float32) * 0.1

    if kind == "ln":
        fused = lambda a: jax.value_and_grad(  # noqa: E731
            lambda b: jnp.sum(fn.fused_layer_norm(b, scale, bias)))(a)
        ref = lambda a: jax.value_and_grad(  # noqa: E731
            lambda b: jnp.sum(fn.ref_layer_norm(b, scale, bias)))(a)
        stats_bytes_per_row = 8      # fp32 mu + rstd
    elif kind == "rms":
        fused = lambda a: jax.value_and_grad(  # noqa: E731
            lambda b: jnp.sum(fn.fused_rms_norm(b, scale)))(a)
        ref = lambda a: jax.value_and_grad(  # noqa: E731
            lambda b: jnp.sum(fn.ref_rms_norm(b, scale)))(a)
        stats_bytes_per_row = 4      # fp32 rstd
    else:
        fused = lambda a: jax.value_and_grad(  # noqa: E731
            lambda b: jnp.sum(fn.fused_gelu(b)))(a)
        ref = lambda a: jax.value_and_grad(  # noqa: E731
            lambda b: jnp.sum(fn.ref_gelu(b)))(a)
        stats_bytes_per_row = 0      # saves the pre-activation it gets

    block = fn._should_fuse(rows, d, jnp.float32)
    before = dict(fn.KERNEL_INVOCATIONS)
    loss_f, grad_f = fused(x)
    launches = sum(fn.KERNEL_INVOCATIONS.values()) \
        - sum(before.values())
    loss_r, grad_r = ref(x)

    entry = {
        "rows": rows,
        "d": d,
        "fused": block is not None,
        "row_block": block,
        "grid_cells": (rows // block) if block else 0,
        # One fwd+bwd trace's Pallas launches (0 == XLA fallback).
        "kernel_launches": launches,
        # fp32 bytes/step the fused path keeps out of HBM: XLA
        # materializes the fp32 recompute chain (x32 [R, D]) for
        # backward; the kernel saves only the per-row statistics.
        "fp32_roundtrip_saved_bytes": (rows * d * 4
                                       - rows * stats_bytes_per_row)
        if block else 0,
        "loss_abs_err": float(jnp.abs(loss_f - loss_r)),
        "grad_max_err": float(jnp.abs(grad_f - grad_r).max()),
        # CPU-interpret relative timing only — not a TPU perf claim.
        "interpret_us": {
            "fused_fwd_bwd": _time_us(fused, x),
            "xla_fwd_bwd": _time_us(ref, x),
        },
    }
    return entry


def run_all() -> dict:
    assert jax.default_backend() == "cpu", "microbench pins CPU interpret"
    return {name: bench_point(kind, rows, d)
            for name, kind, rows, d in SHAPES}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="MICROBENCH.json")
    args = ap.parse_args()
    results = run_all()
    # Merge-preserve: every perfsuite stage owns one section of the
    # artifact (same contract as microbench/scalebench).
    payload = {}
    if os.path.exists(args.out):
        try:
            with open(args.out) as f:
                payload = json.load(f)
        except (OSError, ValueError):
            payload = {}
    payload["fused_norm"] = {
        "cmd": " ".join(sys.argv),
        "shapes": results,
    }
    with open(args.out, "w") as f:
        # Match perfsuite's final-dump format exactly (indent=1,
        # sorted): whichever tool runs last must not reflow the whole
        # committed artifact into an unreviewable whitespace diff.
        json.dump(payload, f, indent=1, sort_keys=True)
        f.write("\n")
    print(json.dumps({"fused_norm": results}))


if __name__ == "__main__":
    main()
