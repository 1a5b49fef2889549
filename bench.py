"""GPT-2 training throughput on the local TPU, in one process.

Prints the device to stderr first and ONE JSON line to stdout:
  {"metric": "gpt2_train_mfu", "value": <MFU %>, "unit": "%",
   "vs_baseline": <MFU / 45%>, "device": ..., "n_devices": ..., ...}

Off the chip it prints no metric and exits 2: a CPU timing under a
device metric's name is worse than no number. The process that runs
this holds the chip, so nothing is probed or measured in a child.
(ROADMAP S1 replaces this file with the benchmark grid.)
"""

from __future__ import annotations

import json
import sys


def main() -> None:
    from ray_tpu.models.gpt2 import GPT2Config
    from ray_tpu.parallel.mesh import MeshConfig, build_mesh
    from ray_tpu.scripts.measure import measure_gpt2, require_tpu

    device = require_tpu()
    n_dev = device["count"]
    # GPT-2 small, seq 1024: Pallas flash attention (the library's own
    # choice at this length), selective remat, unrolled layer loop —
    # the one configuration with a chip record.
    cfg = GPT2Config(remat="dots", scan_layers=False)
    batch, steps, warmup = 16 * n_dev, 20, 3
    mesh = build_mesh(MeshConfig(fsdp=-1))  # also places the compile cache
    r = measure_gpt2(cfg, batch, steps=steps, warmup=warmup, mesh=mesh)
    print(
        f"gpt2 {cfg.n_params / 1e6:.0f}M params, batch={batch}, "
        f"seq={cfg.seq_len}, {steps} steps in {r['dt']:.2f}s, "
        f"loss={r['loss']:.3f}",
        file=sys.stderr,
    )
    print(json.dumps({
        "metric": "gpt2_train_mfu",
        "value": r["mfu"],
        "unit": "%",
        "vs_baseline": round(r["mfu"] / 45.0, 3),
        "tokens_per_sec_per_chip": round(r["tok_s"] / n_dev, 1),
        "device": device["kind"],
        "platform": device["platform"],
        "n_devices": n_dev,
        "batch": batch,
        "steps": steps,
    }), flush=True)


if __name__ == "__main__":
    main()
