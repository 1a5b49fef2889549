"""Kind ``train_packed``: documents packed end to end into rows, trained
through ``JaxTrainer.fit`` -> ``make_train_step`` on every chip of the cell.

The traffic file gives the documents (length unit, Zipf exponent and cut,
ids, separator); the cell's file gives the batch in rows per chip and
``report_every``. The window is cut into slices of ``report_every`` steps,
each ending where the loop brings the loss to the host and reports it, as
a job that logs every N steps does. Steps inside a slice are dispatched
ahead; nothing syncs per step. A slice cut by the window's end is dropped.
"""

from __future__ import annotations

import time

# The keys of the configuration's ``tolerance`` that this kind's comparison
# (``compare.check_train``) reads.
TOLERANCES = ("train_loss_rel", "train_grad_norm_rel")


def doc_lengths(traffic: dict, n_tokens: int, rng) -> "list":
    """Document lengths ``unit * k``, k Zipf(exponent) cut at ``cut``,
    enough of them to fill ``n_tokens`` with their separators."""
    import numpy as np

    k = np.arange(1, traffic["zipf_cut"] + 1)
    p = k ** -float(traffic["zipf_exponent"])
    p /= p.sum()
    mean = float((p * k).sum()) * traffic["doc_len_unit"] + 1
    out, total = [], 0
    while total < n_tokens:
        draw = rng.choice(k, size=int(n_tokens / mean) + 16, p=p) \
            * traffic["doc_len_unit"]
        out.append(draw)
        total += int(draw.sum()) + len(draw)
    return np.concatenate(out)


def make_pool(traffic: dict, rows: int, row_tokens: int, rng):
    """``rows`` rows of ``row_tokens`` ids: documents joined by the
    separator and packed end to end, split across rows, no padding."""
    import numpy as np

    n = rows * row_tokens
    lengths = doc_lengths(traffic, n, rng)
    ids = rng.integers(0, traffic["token_id_below"], size=n, dtype=np.int32)
    ends = np.cumsum(lengths + 1) - 1
    ids[ends[ends < n]] = traffic["separator_id"]
    return ids.reshape(rows, row_tokens)


def train_loop(config: dict) -> None:
    """Runs in the trainer's worker (a thread of this process on the local
    backend, so the process that reported the device holds it)."""
    import jax
    import numpy as np

    from benchmark import compare
    from ray_tpu import train
    from ray_tpu.parallel import MeshConfig, build_mesh

    run = config["run"]  # the local backend hands objects over as they are
    cell = run.params
    rows = cell["rows_per_chip"] * run.chips
    every = cell["report_every"]
    mesh = build_mesh(MeshConfig(fsdp=run.chips, devices=run.devices))
    prog = run.family.build_train(run.config, mesh)
    step, sharding = prog["step"], prog["batch_sharding"]
    state = prog["init"](jax.random.key(compare.jax_seed(run.seed)))
    run.say("state_made")
    pool = train.session.get_dataset_shard("train")
    cursor = [0]

    def next_batch():
        with run.span("bench.next_batch"):
            i = cursor[0]
            if i + rows > len(pool):
                i = 0
            cursor[0] = i + rows
            return {"tokens": jax.device_put(pool[i:i + rows], sharding)}

    # The reference decides `correct`: two seeded rows, tiled to the
    # cell's batch shape so that the cell's own compiled step runs them.
    two = np.asarray(pool[:2])
    tiled = {"tokens": jax.device_put(
        np.tile(two, (rows // 2, 1)), sharding)}
    holder = {"state": state}
    del state

    def step_once():
        holder["state"], m = step(holder["state"], tiled)
        return float(m["loss"]), float(m["grad_norm"])

    compare.check_train(run, prog["params_of"](holder["state"]), step_once,
                        jax.numpy.asarray(two),
                        remat=bool(cell.get("reference_remat", False)))
    state = holder.pop("state")
    run.say("first_step_done")

    losses = []  # device scalars; read after the window

    def run_slice(state):
        for _ in range(every):
            state, metrics = step(state, next_batch())
            losses.append(metrics["loss"])
        with run.span("bench.loss_to_host"):
            loss = float(metrics["loss"])
        return state, loss

    # Warm-up: one whole slice and at least three steps.
    for _ in range(-(-max(3, every) // every)):
        state, loss = run_slice(state)
        train.session.report({"loss": loss, "warmup": True})
    losses.clear()

    trace_at = cell.get("trace_after_slices", 2) if run.trace_on else None
    trace_for = cell.get("trace_slices", 2)
    run.open_window()
    t_open = time.perf_counter()
    slices, t_prev, n = [], t_open, 0
    while True:
        if trace_at is not None and n == trace_at:
            run.start_trace()
            t_prev = time.perf_counter()  # starting the profiler is no slice
        state, loss = run_slice(state)
        t_now = time.perf_counter()
        n += 1
        if t_now - t_open > run.seconds:
            break  # cut by the window's end: dropped
        slices.append(t_now - t_prev)
        run.say("slice", n=n, seconds=t_now - t_prev, loss=loss)
        train.session.report({"loss": loss, "slice": n})
        t_prev = t_now
        if trace_at is not None and n == trace_at + trace_for:
            run.stop_trace()
            t_prev = time.perf_counter()  # nor is writing the profile out
    run.close_window()
    if run._tracing:
        run.stop_trace()
    whole = len(slices) * every
    values = np.asarray(jax.device_get(losses[:whole]), np.float64)
    run.raw.update({
        "slice_seconds": slices,
        "window_whole_s": float(sum(slices)),
        "steps_per_slice": every,
        "tokens_per_step": rows * (prog["row_tokens"] - 1),
        "rows_per_step": rows,
    })
    run.attempted = whole
    run.failed = int((~np.isfinite(values)).sum())
    run.check("losses_finite", run.failed == 0 and whole > 0,
              f"{run.failed} of {whole} losses not finite")
    train.session.report({"done": True})


def run(run) -> None:
    import ray_tpu
    from ray_tpu import train

    traffic, cell = run.traffic, run.params
    row_tokens = run.family.shape(run.config)["n_positions"] + 1
    with run.span("bench.make_pool"):
        pool = make_pool(traffic, cell["pool_rows"], row_tokens,
                         run.rng("pool"))
    ray_tpu.init()
    try:
        result = train.JaxTrainer(
            train_loop, train_loop_config={"run": run},
            scaling_config=train.ScalingConfig(num_workers=1),
            datasets={"train": pool},
        ).fit()
    finally:
        ray_tpu.shutdown()
    if result.error is not None:
        raise RuntimeError("the training loop failed") from result.error
