"""What the two serving kinds share: one deployment
(``serve.run(LLMEngine)`` on the cell's chip), the request generator, the
client that times a stream, and the checks that decide ``correct``.

Sizes (prompt length, ``max_tokens``) are one fixed sequence drawn from the
traffic file's own ``sizes_seed``: ``--seed`` picks the token ids (and the
weights), so every seed gives the system the same work in the same order.
A window holds only some tens of long requests, and a heavy-tailed
``max_tokens`` drawn anew for each seed would change how many of them end,
and so how many prefills interrupt decoding (measured: 3.7 % between two
seeds' tokens/s, PERF.md).
"""

from __future__ import annotations

import math
import time

# The keys of the configuration's ``tolerance`` that the serving kinds'
# comparisons (``compare.check_serve``, ``check_engine_tokens``) read.
TOLERANCES = ("serve_logits_rel_l2", "serve_token_regret_rms")


def draw_sizes(traffic: dict, n: int):
    """``n`` (prompt_len, max_tokens) pairs from the traffic's own seed."""
    import numpy as np

    rng = np.random.default_rng(traffic["sizes_seed"])
    p = traffic["prompt_len"]
    if p["dist"] == "log_uniform":
        lens = np.exp(rng.uniform(math.log(p["min"]), math.log(p["max"]), n))
    elif p["dist"] == "log_normal":
        lens = np.exp(rng.normal(math.log(p["median"]), p["sigma"], n))
    else:
        raise ValueError(f"unknown prompt_len dist {p['dist']!r}")
    lens = np.clip(np.rint(lens), p["min"], p["max"]).astype(int)
    m = traffic["max_tokens"]
    if m["dist"] == "log_normal":
        new = np.exp(rng.normal(math.log(m["median"]), m["sigma"], n))
    elif m["dist"] == "uniform":
        new = rng.uniform(m["min"], m["max"] + 1, n)
    else:
        raise ValueError(f"unknown max_tokens dist {m['dist']!r}")
    new = np.clip(np.floor(new), m["min"], m["max"]).astype(int)
    return lens, new


def make_requests(run, n: int) -> list:
    """``n`` requests: the traffic's fixed sizes, token ids uniform below
    the vocabulary from ``--seed``."""
    lens, new = draw_sizes(run.traffic, n)
    rng = run.rng("requests")
    vocab = run.family.shape(run.config)["vocab"]
    return [{"i": i, "prompt": rng.integers(0, vocab, int(lens[i])).tolist(),
             "prompt_len": int(lens[i]), "asked": int(new[i])}
            for i in range(n)]


def start_engine(run):
    """The reference comparison of the serving functions, then the
    deployment, then the comparison of what the deployed engine serves;
    returns the handle. The engine's weights come from the same seed as
    the reference's."""
    import ray_tpu
    from benchmark import compare
    from ray_tpu import serve
    from ray_tpu.serve.llm_engine import LLMEngine

    engine = run.params["engine"]
    ref = compare.check_serve(run, engine)
    before = _bytes_in_use(run)
    ray_tpu.init()
    dep = serve.deployment(
        name="llm", max_concurrent_queries=run.params["max_concurrent"])(
            LLMEngine)
    handle = serve.run(dep.bind(**run.family.engine_bind(
        run.config, engine, compare.jax_seed(run.seed))))
    # The first requests compile the engine's two programs (set-up), and
    # they are the reference's prompts: what the engine serves for them
    # is held to the reference's own greedy tokens.
    asked = [handle.remote({"tokens": p, "max_tokens": len(t)})
             for p, t in zip(ref["prompts"], ref["tokens"])]
    compare.check_engine_tokens(
        run, ref, [ray_tpu.get(a, timeout=1100)["tokens"] for a in asked])
    run.raw["weight_bytes"] = _weight_bytes(
        run, _bytes_in_use(run) - before, engine)
    run.epoch_offset_ns = time.time_ns() - time.perf_counter_ns()
    if run.trace_on:
        from ray_tpu.util import tracing

        run.raw["tracing_was_on"] = tracing.is_enabled()
        tracing.enable()
        tracing.drain()
    return handle


def _bytes_in_use(run) -> int:
    st = run.devices[0].memory_stats() or {}
    return int(st.get("bytes_in_use", 0))


def _weight_bytes(run, engine_bytes: int, engine: dict) -> float:
    """Bytes of weights as the engine stores them: what the engine added
    to the device less its cache (the family's ``cache_bytes``, by shape)
    over the family's ``param_count``, snapped to the nearest whole number
    of bytes a parameter (1, 2 or 4) so that the figure is a shape figure
    and not an allocator reading."""
    n_params = run.family.param_count(run.config)
    cache = run.family.cache_bytes(
        run.config, engine["max_batch"] + 1, engine["cache_len"])
    per_param = (engine_bytes - cache) / n_params
    snapped = min((1, 2, 4, 6, 8), key=lambda b: abs(b - per_param))
    run.say("engine_memory", engine_bytes=engine_bytes, cache_bytes=cache,
            bytes_per_param_measured=per_param, bytes_per_param=snapped)
    return float(snapped * n_params)


def stream_request(run, handle, req: dict, due_ns=None) -> dict:
    """Send one request through ``handle.stream`` and time its chunks at
    the client. In a traced run the request carries a client span, so the
    engine's ``llm.queue`` / ``llm.prefill`` spans can be matched to it."""
    from ray_tpu.util import tracing

    rec = {"i": req["i"], "prompt_len": req["prompt_len"],
           "asked": req["asked"], "due_ns": due_ns, "sent_ns": None,
           "first_ns": None, "chunk_ns": [], "chunk_tokens": [],
           "n_out": 0, "done_ns": None, "error": None, "trace_id": None}
    try:
        with tracing.span("bench.request") as client_span:
            if client_span is not None:
                rec["trace_id"] = client_span["trace_id"]
            with run.span("bench.send"):
                rec["sent_ns"] = time.perf_counter_ns()
                stream = handle.stream(req["prompt"], req["asked"])
            with run.span("bench.first_chunk"):
                first = next(stream, None)
            if first is not None:
                now = time.perf_counter_ns()
                rec["first_ns"] = now
                rec["chunk_ns"].append(now)
                rec["chunk_tokens"].append(len(first))
                rec["n_out"] += len(first)
                for chunk in stream:
                    rec["chunk_ns"].append(time.perf_counter_ns())
                    rec["chunk_tokens"].append(len(chunk))
                    rec["n_out"] += len(chunk)
                    if run.raw.get("abandon"):
                        stream.close()
                        rec["error"] = "abandoned at the end of the run"
                        break
    except Exception as e:  # noqa: BLE001 - a failed request is counted
        rec["error"] = repr(e)
    rec["done_ns"] = time.perf_counter_ns()
    return rec


def stats_now(handle) -> dict:
    import ray_tpu

    return ray_tpu.get(handle.llm_stats.remote(), timeout=60)


def trace_middle(run, handle) -> None:
    """Sleep through the window in the main thread; in a traced run, the
    profiler records ``trace_seconds`` from the window's middle."""
    seconds = run.seconds
    if run.trace_on:
        span = min(run.params.get("trace_seconds", 5.0), seconds / 2)
        time.sleep((seconds - span) / 2)
        run.start_trace()
        time.sleep(span)
        run.stop_trace()
        rest = seconds - (time.perf_counter_ns() - run._window_open_ns) * 1e-9
        time.sleep(max(0.0, rest))
    else:
        time.sleep(seconds / 2)
        run.counters["mid"] = stats_now(handle)
        rest = seconds - (time.perf_counter_ns() - run._window_open_ns) * 1e-9
        time.sleep(max(0.0, rest))


def finish(run, handle, terminal: list, shed_allowed: bool) -> None:
    """Counters, program spans, and the checks every serving cell makes:
    every completed request returned exactly the tokens it asked for, the
    engine saw no error, and (where the cell says so) shed nothing."""
    from ray_tpu.util import tracing

    close = run.counters["close"]
    opened = run.counters["open"]
    run.program_spans = tracing.collect() if run.trace_on else []
    run.attempted = len(terminal)
    bad = [r for r in terminal
           if r["error"] is not None or r["n_out"] != r["asked"]]
    run.failed = len(bad)
    run.check("requests_complete", not bad and terminal,
              f"{len(bad)} of {len(terminal)} requests failed or came back "
              f"short; first: "
              f"{[(r['i'], r['asked'], r['n_out'], r['error']) for r in bad[:3]]}")
    run.check("engine_errors", close["errors"] == opened["errors"],
              f"engine errors {opened['errors']} -> {close['errors']}")
    run.check("engine_compiles",
              close["compiles"] == {"decode": 1, "prefill": 1},
              f"engine compiles {close['compiles']}")
    if not shed_allowed:
        run.check("nothing_shed", close["shed"] == opened["shed"],
                  f"shed {opened['shed']} -> {close['shed']}")
    run.say("engine_counters", open=opened, close=close,
            mid=run.counters.get("mid"))


def stop_engine(run, handle, clients=()) -> None:
    """End the engine's loop and the deployment, then wait for the loop's
    thread and every client thread: a thread still inside the runtime
    when the interpreter exits aborts the process (seen on the chip).
    ``serve.shutdown()`` alone leaves the engine's loop running."""
    import threading

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.util import tracing

    if run.raw.get("tracing_was_on") is False:
        tracing.disable()
    t0 = time.perf_counter()
    try:
        if handle is not None:
            ray_tpu.get(handle.shutdown_engine.remote(), timeout=60)
        serve.shutdown()
    finally:
        ray_tpu.shutdown()
    deadline = time.perf_counter() + 60
    loops = [t for t in threading.enumerate()
             if t.name == "llm-engine-loop"]
    for t in loops + list(clients):
        t.join(timeout=max(0.0, deadline - time.perf_counter()))
    run.say("stopped", seconds=time.perf_counter() - t0,
            clients_alive=sum(t.is_alive() for t in clients),
            engine_loops_alive=sum(t.is_alive() for t in loops),
            threads=sorted({t.name.rstrip("0123456789-")
                            for t in threading.enumerate()}))
