#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process, holding every local chip, drives the repo's two main paths
once through the entry points a user calls, at GPT-2 small's full width:

* kernel check: the Pallas flash attention (forward and backward) against
  the XLA reference at model width (heads 12 x 64, seq 1024);
* train leg: ``ray_tpu.init()`` -> ``train.JaxTrainer(loop).fit()`` with
  ``build_mesh(MeshConfig(fsdp=-1))`` over every local chip,
  ``make_init_fn`` / ``make_train_step``, 16 seeded sequences of 1025 tokens
  per chip, one warm-up step and five more. On more than one chip it also
  proves placement: every fsdp-sharded leaf is split 1/n, no chip holds the
  global batch, the devices' peak bytes agree, and the first-step loss of 16
  sequences on the full mesh equals the one-device mesh's;
* serve leg: ``serve.run(LLMEngine)`` on device 0 -> requests of several
  prompt lengths through ``handle.remote``, ``handle.stream`` and the HTTP
  proxy; streamed tokens must equal the blocking lane's, with one compile
  per engine shape and no error, shed or loop restart.

Any failed phase raises, so the exit code is non-zero. Without a TPU the
script prints no result and exits 2. ``--cpu-rehearsal N`` runs the same
code at a toy size on N virtual CPU devices (kernels interpreted) to find
typos before chip time is spent; it says so and is not evidence for chips.
It prints set-up (compile) seconds apart from step seconds, the persistent
compile cache's hits and misses and each device's peak bytes — and no rate
or utilization: this is a smoke test, not a benchmark.

The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": n}}``.
"""

from __future__ import annotations

import argparse
import collections
import gc
import http.client
import json
import math
import re
import sys
import time

# GPT-2 small as the benchmark's cell train_gpt2s_1chip shapes it, with
# the attention the library chooses by itself.
FULL = {
    "model": {"remat": "dots", "scan_layers": False, "use_flash": None},
    "per_chip": 16, "steps": 5, "min_mosaic_calls": 24,
    "kernel_shape": (2, 1024, 12, 64), "kernel_dtype": "bfloat16",
    "engine": {"preset": "small", "max_batch": 32, "cache_len": 1024,
               "max_prompt_len": 512, "prefill_rows": 4},
    "prompt_lens": (5, 37, 128, 300, 512), "max_new_tokens": 16,
}
# Same code, toy width; the kernels run in Pallas interpret mode, so no
# Mosaic call can be counted.
REHEARSAL = {
    "model": {"vocab_size": 256, "n_layer": 2, "n_head": 4, "d_model": 64,
              "seq_len": 64, "remat": "dots", "scan_layers": False,
              "use_flash": True},
    "per_chip": 8, "steps": 5, "min_mosaic_calls": 0,
    "kernel_shape": (2, 64, 4, 16), "kernel_dtype": "float32",
    "engine": {"preset": "tiny", "max_batch": 4, "cache_len": 64,
               "max_prompt_len": 16, "prefill_rows": 2},
    "prompt_lens": (3, 7, 16), "max_new_tokens": 6,
}


class SmokeFailure(RuntimeError):
    """A check of the smoke test did not hold."""


def require(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def memory_by_device(key: str) -> list | None:
    """``memory_stats()[key]`` of every local device; None where the
    backend reports no allocator stats (the CPU rehearsal)."""
    import jax

    stats = [d.memory_stats() for d in jax.local_devices()]
    if any(s is None for s in stats):
        return None
    return [int(s[key]) for s in stats]


# -- kernel check -----------------------------------------------------------


def kernel_check(sizes: dict) -> dict:
    """Flash attention forward + backward vs the XLA reference."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.attention import xla_causal_attention
    from ray_tpu.ops.flash_attention import flash_causal_attention

    shape = sizes["kernel_shape"]
    dtype = jnp.dtype(sizes["kernel_dtype"])
    keys = jax.random.split(jax.random.key(7), 4)
    q, k, v = (jax.random.normal(kk, shape, jnp.float32).astype(dtype)
               for kk in keys[:3])
    w = jax.random.normal(keys[3], shape, jnp.float32)

    def out_and_grads(attn, q, k, v, w):
        def loss(q, k, v):
            out = attn(q, k, v)
            return jnp.sum(out.astype(jnp.float32) * w), out
        (_, out), grads = jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
        return (out,) + grads

    t0 = time.perf_counter()
    got, want = (
        jax.block_until_ready(
            jax.jit(out_and_grads, static_argnums=0)(attn, q, k, v, w))
        for attn in (flash_causal_attention, xla_causal_attention))
    errs = {}
    for name, g, r in zip(("out", "dq", "dk", "dv"), got, want):
        g32, r32 = g.astype(jnp.float32), r.astype(jnp.float32)
        require(bool(jnp.all(jnp.isfinite(g32))), f"flash {name} not finite")
        errs[name] = float(jnp.linalg.norm(g32 - r32) / jnp.linalg.norm(r32))
    # Relative L2 error. bf16 carries 8 mantissa bits and the reference
    # itself rounds its probabilities to bf16, so 2e-2 is a few ulps.
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-4
    require(max(errs.values()) <= tol,
            f"flash attention disagrees with the XLA reference: {errs}")
    return {"shape": list(shape), "dtype": str(dtype), "rel_l2_err": errs,
            "tolerance": tol, "seconds": round(time.perf_counter() - t0, 2)}


# -- train leg --------------------------------------------------------------


def train_loop(config: dict) -> None:
    """The README quick-tour loop at ``config``'s sizes. Runs in the
    trainer's worker: on the local backend a thread of this process, so
    the process that printed the device is the one that holds it."""
    import jax
    import jax.numpy as jnp

    from ray_tpu import train
    from ray_tpu.models.gpt2 import (GPT2Config, gpt2_init, gpt2_loss,
                                     gpt2_shardings)
    from ray_tpu.parallel import MeshConfig, build_mesh
    from ray_tpu.train import make_init_fn, make_train_step
    from ray_tpu.train.train_step import batch_sharding

    cfg = GPT2Config(**config["model"])
    per_chip, n_steps = config["per_chip"], config["steps"]
    n_dev = jax.local_device_count()
    tokens = jax.random.randint(
        jax.random.key(1), (per_chip * n_dev, cfg.seq_len + 1), 0,
        cfg.vocab_size, jnp.int32)

    def start(mesh, toks):
        """Seeded state + compiled step on ``mesh``; runs the first
        (warm-up) step. -> (step, state, batch, first loss, set-up s)."""
        t0 = time.perf_counter()
        shardings = gpt2_shardings(cfg, mesh)
        state = make_init_fn(lambda r: gpt2_init(r, cfg), shardings, mesh)(
            jax.random.key(0))
        step = make_train_step(
            lambda p, b: gpt2_loss(p, b, cfg), shardings, mesh)
        batch = {"tokens": jax.device_put(toks, batch_sharding(mesh))}
        state, metrics = step(state, batch)
        jax.block_until_ready((state, metrics))
        return (step, state, batch, float(metrics["loss"]),
                time.perf_counter() - t0)

    mesh = build_mesh(MeshConfig(fsdp=-1))  # ZeRO-3 over every local chip
    step, state, batch, first_loss, setup_s = start(mesh, tokens)
    train.session.report({"step": 0, "loss": first_loss})
    step_s = []
    for i in range(1, n_steps + 1):
        t0 = time.perf_counter()
        state, metrics = step(state, batch)
        jax.block_until_ready((state, metrics))
        step_s.append(time.perf_counter() - t0)
        train.session.report({"step": i, "loss": float(metrics["loss"])})

    # The compiled program itself: Mosaic calls prove the Pallas kernel is
    # in it (neither interpret mode nor XLA attention produces one).
    t0 = time.perf_counter()
    compiled = step.lower(state, batch).compile()
    hlo = compiled.as_text()
    facts = {
        "mesh": {a: s for a, s in mesh.shape.items() if s > 1},
        "global_batch": per_chip * n_dev,
        "seq_len": cfg.seq_len,
        "setup_seconds": round(setup_s, 2),
        "step_seconds": [round(s, 4) for s in step_s],
        "recompile_for_hlo_seconds": round(time.perf_counter() - t0, 2),
        "mosaic_calls": hlo.count('custom_call_target="tpu_custom_call"'),
        "compiled_temp_bytes": int(
            compiled.memory_analysis().temp_size_in_bytes),
        "peak_bytes_in_use": memory_by_device("peak_bytes_in_use"),
    }
    if n_dev > 1:
        facts["placement"] = check_placement(
            state, hlo, n_dev, per_chip, cfg, facts["peak_bytes_in_use"])
        # Same 16 seeded sequences, full mesh vs one device. Runs after the
        # peak bytes were read: the one-device run lands on device 0 alone.
        del state, batch, compiled
        gc.collect()
        toks = tokens[:per_chip]
        loss_n = start(mesh, toks)[3]
        gc.collect()
        loss_1 = start(
            build_mesh(MeshConfig(devices=jax.devices()[:1])), toks)[3]
        require(abs(loss_n - loss_1) <= 1e-2,
                f"first-step loss on {n_dev} chips {loss_n} != one chip "
                f"{loss_1}")
        facts["first_loss_parity"] = {
            "n_chip": loss_n, "one_chip": loss_1,
            "abs_diff": abs(loss_n - loss_1)}
    train.session.report({"facts": facts})


def global_batch_buffers(hlo: str, global_batch: int, share: int) -> list:
    """Shapes in a per-device HLO that lead with the global batch and hold
    more elements than ``share``, one device's share of an activation
    ([per_chip, T, D]). GSPMD does look every token up in each chip's slice
    of the embedding table before an all-to-all ([global, T, D/n], exactly
    that share); replicated compute shows [global, T, D] or the
    [global, T, vocab] logits."""
    return sorted({
        m.group(0) for m in re.finditer(
            r"\b[a-z]+[0-9]*\[%d,([0-9,]+)\]" % global_batch, hlo)
        if global_batch * math.prod(map(int, m.group(1).split(","))) > share})


def check_placement(state, hlo: str, n_dev: int, per_chip: int, cfg,
                    peaks: list | None) -> dict:
    """Proof that n chips split the work instead of each doing all of it."""
    import jax

    # 1. Every fsdp-sharded leaf (params, Adam mu/nu) is n shards of 1/n.
    leaves = jax.tree.leaves(
        {"params": state["params"], "opt": state["opt"]})
    n_sharded = 0
    for leaf in leaves:
        if "fsdp" not in jax.tree.leaves(tuple(leaf.sharding.spec)):
            continue
        n_sharded += 1
        shards = leaf.addressable_shards
        require(len({s.device for s in shards}) == n_dev
                and all(s.data.size * n_dev == leaf.size for s in shards),
                f"leaf {leaf.shape} {leaf.sharding.spec} is not split "
                f"{n_dev} ways")
    require(n_sharded > 0, "no fsdp-sharded leaf found")

    # 2. No buffer of the per-chip program holds the global batch.
    oversized = global_batch_buffers(
        hlo, per_chip * n_dev, per_chip * cfg.seq_len * cfg.d_model)
    require(not oversized,
            f"per-chip HLO holds global-batch buffers: {oversized[:6]}")

    # 3. The chips' peak bytes agree (one chip doing everything would not).
    out = {"fsdp_sharded_leaves": n_sharded, "global_batch_buffers": 0}
    if peaks is not None:
        spread = (max(peaks) - min(peaks)) / max(peaks)
        require(spread <= 0.10, f"device peak bytes differ: {peaks}")
        out["peak_bytes_spread"] = round(spread, 4)
    return out


def train_leg(sizes: dict) -> dict:
    from ray_tpu import train

    result = train.JaxTrainer(
        train_loop,
        train_loop_config={k: sizes[k] for k in ("model", "per_chip",
                                                 "steps")},
        scaling_config=train.ScalingConfig(num_workers=1),
    ).fit()
    if result.error is not None:
        raise SmokeFailure("train leg failed") from result.error
    losses = [m["loss"] for m in result.metrics_history if "loss" in m]
    facts = result.metrics["facts"]
    require(len(losses) == sizes["steps"] + 1,
            f"expected {sizes['steps'] + 1} reported losses, got {losses}")
    require(all(math.isfinite(x) for x in losses), f"loss not finite: {losses}")
    require(losses[-1] < losses[0], f"loss did not fall: {losses}")
    require(facts["mosaic_calls"] >= sizes["min_mosaic_calls"],
            f"compiled step has {facts['mosaic_calls']} Mosaic calls, want >= "
            f"{sizes['min_mosaic_calls']}: the Pallas kernel is not in it")
    return {"losses": [round(x, 4) for x in losses], **facts}


# -- serve leg --------------------------------------------------------------


def serve_leg(sizes: dict) -> dict:
    import numpy as np

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.models.gpt2 import GPT2Config
    from ray_tpu.serve.llm_engine import LLMEngine
    from ray_tpu.util import metrics

    n_new = sizes["max_new_tokens"]
    eng = sizes["engine"]
    cfg = getattr(GPT2Config, eng["preset"])()
    rng = np.random.RandomState(3)
    prompts = [[int(t) for t in rng.randint(0, cfg.vocab_size, size=n)]
               for n in sizes["prompt_lens"]]

    t0 = time.perf_counter()
    engine = serve.deployment(name="llm", max_concurrent_queries=64,
                              route_prefix="/llm")(LLMEngine)
    handle = serve.run(engine.bind(model="gpt2", max_new_tokens=n_new,
                                   **eng))
    # First request compiles the engine's two shapes: set-up, not serving.
    warm = ray_tpu.get(handle.remote(
        {"tokens": prompts[0], "max_tokens": n_new}), timeout=900)
    require(len(warm["tokens"]) == n_new, f"warm-up returned {warm}")
    setup_s = time.perf_counter() - t0
    in_use = memory_by_device("bytes_in_use")
    if in_use is not None and len(in_use) > 1:
        # One replica, one chip: the engine's params (as it says it
        # stores them) and bf16 K/V cache are on device 0 and on no other
        # (whose train state is gone).
        held = ray_tpu.get(handle.llm_stats.remote(),
                           timeout=60)["param_bytes"]
        engine_bytes = sum(held.values()) + 2 * 2 * cfg.n_layer * (
            eng["max_batch"] + 1) * eng["cache_len"] * cfg.d_model
        require(in_use[0] - max(in_use[1:]) >= 0.9 * engine_bytes,
                f"engine ({engine_bytes} bytes) is not on device 0 alone: "
                f"bytes in use {in_use}")

    # Blocking lane: every prompt in flight at once.
    t0 = time.perf_counter()
    refs = [handle.remote({"tokens": p, "max_tokens": n_new})
            for p in prompts]
    blocking = [r["tokens"] for r in ray_tpu.get(refs, timeout=600)]
    for p, toks in zip(prompts, blocking):
        require(len(toks) == n_new,
                f"prompt of {len(p)} tokens returned {len(toks)} tokens")
    # Streaming lane: same prompts, token for token.
    for p, want in zip(prompts, blocking):
        got = [t for chunk in handle.stream(p, n_new) for t in chunk]
        require(got == want, f"stream != blocking for prompt of {len(p)} "
                f"tokens: {got} vs {want}")
    # HTTP proxy: one request end to end.
    port = serve.start_http_proxy()
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
    try:
        conn.request(
            "POST", "/llm",
            body=json.dumps({"tokens": prompts[1], "max_tokens": n_new}),
            headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        body = resp.read()
    finally:
        conn.close()
    require(resp.status == 200, f"HTTP {resp.status}: {body[:300]!r}")
    require(json.loads(body)["tokens"] == blocking[1],
            "HTTP lane != blocking lane")
    requests_s = time.perf_counter() - t0

    stats = ray_tpu.get(handle.llm_stats.remote(), timeout=60)
    restarts = metrics.sum_counter(
        metrics.parse_prometheus(metrics.prometheus_text()),
        "ray_tpu_loop_restarts_total", "loop").get("llm.engine", 0)
    require(stats["compiles"] == {"decode": 1, "prefill": 1},
            f"engine recompiled: {stats['compiles']}")
    require(stats["errors"] == 0 and stats["shed"] == 0 and restarts == 0,
            f"engine errors={stats['errors']} shed={stats['shed']} "
            f"loop restarts={restarts}")
    n_requests = 1 + 2 * len(prompts) + 1
    require(stats["completed"] == n_requests,
            f"completed {stats['completed']} of {n_requests} requests")
    return {
        "device": "device 0 (one replica; four one-chip replicas are "
                  "ROADMAP W2)",
        "engine": eng, "prompt_lens": list(sizes["prompt_lens"]),
        "max_new_tokens": n_new, "requests": n_requests,
        "setup_seconds": round(setup_s, 2),
        "requests_seconds": round(requests_s, 2),
        "compiles": stats["compiles"], "errors": stats["errors"],
        "shed": stats["shed"], "loop_restarts": restarts,
        "decode_steps": stats["steps"], "tokens_out": stats["tokens_out"],
        "bytes_in_use": in_use,
    }


# -- driver -----------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--cpu-rehearsal", type=int, default=0, metavar="N_DEVICES",
        help="toy-size dry run on N virtual CPU devices; not what the "
             "driver runs, and not evidence for chips")
    args = ap.parse_args(argv)
    rehearsal = args.cpu_rehearsal > 0
    sizes = REHEARSAL if rehearsal else FULL

    import jax  # the first JAX touch of this process

    if rehearsal:
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", args.cpu_rehearsal)
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.util.compile_cache import ensure_compile_cache

    cache_dir = ensure_compile_cache()
    cache = collections.Counter()
    jax.monitoring.register_event_listener(
        lambda event, **_: cache.update([event.rsplit("/", 1)[-1]])
        if event.startswith("/jax/compilation_cache/") else None)

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    say(f"device: {json.dumps(device)}")
    if rehearsal:
        say("CPU REHEARSAL at toy size: control flow only, kernels "
            "interpreted; nothing below is evidence for chips")
    elif device["platform"] != "tpu":
        print("[chip_smoke] no TPU found: nothing was run", file=sys.stderr)
        return 2
    say(f"compile cache: {cache_dir}")

    t_start = time.perf_counter()
    report = {"device": device, "compile_cache_dir": cache_dir}
    ray_tpu.init()
    try:
        report["kernel"] = kernel_check(sizes)
        say(f"kernel check ok: {json.dumps(report['kernel'])}")
        report["train"] = train_leg(sizes)
        say(f"train leg ok: {json.dumps(report['train'])}")
        gc.collect()  # the train state is gone before the engine arrives
        report["serve"] = serve_leg(sizes)
        say(f"serve leg ok: {json.dumps(report['serve'])}")
    finally:
        serve.shutdown()
        ray_tpu.shutdown()
    report["compile_cache"] = {
        "requests": cache["compile_requests_use_cache"],
        "hits": cache["cache_hits"],
        "misses_written": cache["cache_misses"]}
    report["peak_bytes_in_use"] = memory_by_device("peak_bytes_in_use")
    report["total_seconds"] = round(time.perf_counter() - t_start, 1)
    say(f"report: {json.dumps(report)}")
    result = {"ok": True, "device": device}
    if rehearsal:
        result["rehearsal"] = "cpu"
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
