"""One-off TPU config sweep for the GPT-2 headline bench.

Measures step time / MFU for a grid of (config, batch) points on whatever
device is attached, printing one JSON line per point. Used to pick the
shipped `bench.py` config; results are recorded in PROFILE.md, and every
successful on-chip point auto-appends to BENCH_TPU_SESSIONS.jsonl.

The timed-step protocol (steps/warmup/sync/FLOPs accounting) is the
shared harness in ``scripts/measure.py`` — the same loop ``bench.py``
times, so sweep points and the headline number are directly comparable.
Failed points record the full traceback tail, not a truncated repr, so
a failure is diagnosable from the JSON alone. Like ``bench.py`` it
refuses to run off the chip (``measure.require_tpu``).

Run: python -m ray_tpu.scripts.tpu_sweep '[["base",16],["fused_norm",16],...]'

Named configs: base (round-3 winner), lever (round-5: bf16 logits +
chunked CE), bf16_only, chunk_only, chunk6, fused_norm (round-7: lever +
fused Pallas norm/residual/GELU backward kernels), fused_only (base +
fused kernels, isolating the kernel effect from the round-5 lever).
The default point list is the round-7 before/after ablation —
base/lever vs fused_norm at batch 16 and 24.
"""

from __future__ import annotations

import dataclasses
import json
import sys

import jax.numpy as jnp

from ray_tpu.models.gpt2 import GPT2Config
from ray_tpu.scripts.measure import error_entry, measure_gpt2, require_tpu


def named_configs() -> dict[str, GPT2Config]:
    base = GPT2Config(use_flash=True, remat="dots", scan_layers=False)
    lever = dataclasses.replace(
        base, logits_dtype=jnp.bfloat16, ce_vocab_chunks=3)
    return {
        "base": base,
        "lever": lever,
        "bf16_only": dataclasses.replace(base, logits_dtype=jnp.bfloat16),
        "chunk_only": dataclasses.replace(base, ce_vocab_chunks=3),
        "chunk6": dataclasses.replace(
            base, logits_dtype=jnp.bfloat16, ce_vocab_chunks=6),
        "fused_norm": dataclasses.replace(lever, fused_norm=True),
        "fused_only": dataclasses.replace(base, fused_norm=True),
    }


# Round-7 ablation grid (PROFILE.md sink #3): before/after for the fused
# norm kernels at the shipped batch and the next size up.
DEFAULT_POINTS = [
    ["base", 16],
    ["lever", 16],
    ["fused_norm", 16],
    ["lever", 24],
    ["fused_norm", 24],
]


def main() -> None:
    device = require_tpu()
    device_kind, n_dev = device["kind"], device["count"]
    named = named_configs()
    points = json.loads(sys.argv[1]) if len(sys.argv) > 1 else DEFAULT_POINTS
    from ray_tpu.scripts.bench_log import record_if_on_chip

    for name, batch in points:
        try:
            r = measure_gpt2(named[name], int(batch))
            r.pop("dt", None)
            print(json.dumps({"config": name, **r}), flush=True)
            # Evidence trail (VERDICT r5 item 1a): every successful
            # on-chip point lands in BENCH_TPU_SESSIONS.jsonl.
            record_if_on_chip({
                "script": "tpu_sweep", "config": name,
                "device": device_kind, "n_devices": n_dev, **r,
            })
        except Exception as e:  # noqa: BLE001 — sweep survives OOM points
            print(json.dumps({"config": name, "batch": batch,
                              **error_entry(e)}), flush=True)


if __name__ == "__main__":
    main()
