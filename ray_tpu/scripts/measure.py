"""Shared GPT-2 throughput-measurement harness.

ONE definition of the timed-step protocol (steps / warmup / sync /
tok-s / FLOPs accounting) used by both the headline ``bench.py`` and
the ablation ``scripts/tpu_sweep.py`` — previously each re-implemented
its own 20-step loop and they could silently drift. Also owns the
per-chip peak-FLOPs table (MFU denominators) and the error-JSON shape
(full traceback tail, not a 200-char repr) so a failed sweep point is
diagnosable from its JSON line alone.
"""

from __future__ import annotations

import sys
import time
import traceback

# bf16 peak TFLOP/s per chip by device kind substring.
PEAK_TFLOPS = {
    "v5 lite": 197.0,
    "v5litepod": 197.0,
    "v5e": 197.0,
    "v4": 275.0,
    "v5p": 459.0,
    "v6 lite": 918.0,
    "v6e": 918.0,
    "cpu": 0.5,  # nominal: tier-1's step-anatomy tests run off-TPU
}


def peak_flops_per_chip(device_kind: str) -> float:
    """Peak bf16 FLOP/s of one chip of this kind. A kind that is not in
    the table is an error, never a default: a utilization computed
    against a guessed peak is a wrong number under a real name."""
    kind = device_kind.lower()
    for key, tf in PEAK_TFLOPS.items():
        if key in kind:
            return tf * 1e12
    raise ValueError(
        f"no peak FLOP/s known for device kind {device_kind!r}; add it to "
        f"PEAK_TFLOPS with its source")


def device_summary() -> dict:
    """The device as JAX reports it (this initialises the backend)."""
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def require_tpu() -> dict:
    """``device_summary()``, or exit: a throughput or a utilization is
    only ever taken on the chip. Off it, the process prints the device
    to stderr, prints no metric and exits 2."""
    device = device_summary()
    print(f"device: {device}", file=sys.stderr, flush=True)
    if device["platform"] != "tpu":
        print("no TPU: refusing to measure (a CPU timing is not a device "
              "metric)", file=sys.stderr, flush=True)
        raise SystemExit(2)
    return device


def measure_gpt2(cfg, batch: int, *, steps: int = 20, warmup: int = 3,
                 mesh=None) -> dict:  # step-timed
    """Timed GPT-2 train-step loop -> measurement dict.

    Builds the sharded state on ``mesh`` (default: fsdp over all local
    devices), runs ``warmup`` steps, waits for the device (a ``float()``
    of the loss is a device->host transfer, so it cannot return before
    the step has run), then times ``steps`` steps the same way.

    Returns {tok_s, ms_step, loss, dt, steps, warmup, batch, mfu} where
    ``mfu`` is computed against this host's device peak (one chip's
    peak x device count).
    """
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.gpt2 import (
        gpt2_flops_per_token,
        gpt2_init,
        gpt2_loss,
        gpt2_shardings,
    )
    from ray_tpu.parallel.mesh import MeshConfig, build_mesh
    from ray_tpu.train.train_step import make_init_fn, make_train_step

    warmup = max(warmup, 1)  # >=1: the post-warmup sync reads metrics
    if mesh is None:
        mesh = build_mesh(MeshConfig(fsdp=-1))
    shardings = gpt2_shardings(cfg, mesh)
    init_fn = make_init_fn(lambda r: gpt2_init(r, cfg), shardings, mesh)
    state = init_fn(jax.random.key(0))
    step_fn = make_train_step(
        lambda p, b: gpt2_loss(p, b, cfg), shardings, mesh)
    tokens = jax.random.randint(
        jax.random.key(1), (batch, cfg.seq_len + 1), 0, cfg.vocab_size,
        jnp.int32,
    )
    batch_data = {"tokens": tokens}
    for _ in range(warmup):
        state, metrics = step_fn(state, batch_data)
    float(metrics["loss"])
    t0 = time.perf_counter()
    for _ in range(steps):
        state, metrics = step_fn(state, batch_data)
    loss = float(metrics["loss"])
    dt = time.perf_counter() - t0
    tok_s = batch * cfg.seq_len * steps / dt
    n_dev = jax.device_count()
    peak = peak_flops_per_chip(jax.devices()[0].device_kind) * n_dev
    mfu = tok_s * gpt2_flops_per_token(cfg) / peak * 100.0
    return {
        "tok_s": round(tok_s, 1),
        "mfu": round(mfu, 2),
        "ms_step": round(dt / steps * 1000, 2),
        "loss": round(loss, 3),
        "dt": dt,
        "steps": steps,
        "warmup": warmup,
        "batch": batch,
    }


def error_entry(exc: BaseException, *, tb_chars: int = 1500) -> dict:
    """Error fields for a failed measurement point: the repr AND the
    traceback tail, so the failure is diagnosable from the JSON alone."""
    tb = traceback.format_exc()
    if tb is None or tb.strip() in ("", "NoneType: None"):
        tb = "".join(traceback.format_exception(
            type(exc), exc, exc.__traceback__))
    return {
        "error": repr(exc)[:300],
        "traceback_tail": tb[-tb_chars:],
    }
