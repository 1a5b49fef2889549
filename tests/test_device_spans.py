"""The program on the device's clock (PR 24): ``tracing.device_span`` and
the roots-only switch, the engine loop's phases and prefill-lane counters,
the trainer's annotations, the named scopes of the model and optimizer,
the compile log, and ``serve.shutdown()`` stopping the engine's loop.

Profiles here are taken on the CPU: they show that the annotations exist,
in which order and with which attributes. They say nothing about a device.
"""

import contextlib
import dataclasses
import re
import sys
import threading
import time

import jax
import jax.numpy as jnp
import pytest

import ray_tpu
from ray_tpu import serve
from ray_tpu.models import gpt2
from ray_tpu.serve import _observability as obs
from ray_tpu.serve.llm_engine import LLMEngine
from ray_tpu.util import device_telemetry, failpoints, tracing

TINY = dataclasses.replace(gpt2.GPT2Config.tiny(), dtype=jnp.float32)
CTX = {"trace_id": "ab" * 16, "span_id": "cd" * 8}


@pytest.fixture(autouse=True)
def _clean():
    tracing.disable()
    tracing.drain()
    yield
    tracing.disable()
    tracing.drain()
    failpoints.reset()


def _engine(**kw):
    kw.setdefault("model", "gpt2")
    kw.setdefault("config", TINY)
    kw.setdefault("max_batch", 4)
    kw.setdefault("cache_len", 32)
    kw.setdefault("max_prompt_len", 8)
    kw.setdefault("max_new_tokens", 4)
    return LLMEngine(**kw)


def _profiled(tmp_path, body):
    """Run ``body()`` under the profiler; returns the host events whose
    names start with ``llm.``, ``train.`` or ``t.`` as ``(name, start ns,
    end ns, stats, line)``, by start."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    path = next(tmp_path.glob("plugins/profile/*/*.xplane.pb"))
    events = []
    data = jax.profiler.ProfileData.from_file(str(path))
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for n, line in enumerate(plane.lines):
            for ev in line.events:
                name = str(ev.name)
                if name.startswith(("llm.", "train.", "t.")):
                    events.append((
                        name.split("#", 1)[0], ev.start_ns,
                        ev.start_ns + ev.duration_ns, dict(ev.stats), n))
    return sorted(events, key=lambda e: e[1])


# -- tracing.py ---------------------------------------------------------------


def test_device_span_is_nothing_where_jax_is_not_loaded(monkeypatch):
    monkeypatch.delitem(sys.modules, "jax")
    with tracing.device_span("t.no_jax", k=1) as ds:
        ds.set_metadata(more=2)          # same surface, no effect
    assert "jax" not in sys.modules      # and it did not import it


@pytest.mark.parametrize("switch,parent,under_current,want", [
    (False, None, False, False),     # a root obeys the switch
    (True, None, False, True),
    (False, CTX, False, True),       # an explicit remote parent
    (False, None, True, True),       # the thread's current span
    (False, {}, False, False),       # parent={} forces a root
    (False, {}, True, False),
    (True, {}, True, True),
])
def test_the_switch_gates_roots_only(switch, parent, under_current, want):
    if switch:
        tracing.enable()
    outer = tracing.span("outer", parent=CTX) if under_current \
        else contextlib.nullcontext()
    with outer:
        with tracing.span("inner", parent=parent) as s:
            pass
        manual = tracing.start_span("manual", parent=parent)
    assert (s is not None) == want
    assert (manual is not None) == want     # start_span: the same rule
    assert tracing.is_enabled() == switch
    if want and parent:
        assert s["trace_id"] == CTX["trace_id"]
        assert s["parent_id"] == CTX["span_id"]
    with tracing.suppressed():
        with tracing.span("quiet", parent=CTX) as q:
            assert q is None


def test_a_recorded_span_is_written_into_the_profile(tmp_path):
    def body():
        with tracing.span("t.recorded", parent=CTX):
            with tracing.device_span("t.always", n=3):
                pass
        with tracing.span("t.root_off"):       # switch off: no span at all
            pass

    names = [e[0] for e in _profiled(tmp_path, body)]
    assert names == ["t.recorded", "t.always"]


def test_engine_records_a_request_with_context_and_nothing_after():
    """A request that carries a context is recorded on a process whose
    switch is off; an untraced one after it records nothing; neither
    moves the switch."""
    eng = _engine(max_batch=2)
    try:
        assert not tracing.is_enabled()
        with obs.request_scope("llm", None, trace_ctx=CTX):
            assert len(eng.generate([5, 9, 2], 3)) == 3
        spans = tracing.collect(clear=True)
        names = {s["name"] for s in spans}
        assert {"llm.queue", "llm.prefill", "llm.decode", "llm.step"} <= names
        assert all(s["trace_id"] == CTX["trace_id"] for s in spans)
        assert not tracing.is_enabled()
        assert len(eng.generate([5, 9, 2], 3)) == 3
        assert tracing.collect() == []
        assert not tracing.is_enabled()
    finally:
        eng.shutdown_engine()


# -- the engine loop ----------------------------------------------------------

# (an admission turn dispatches and reads nothing: its first tokens come
# to the host in the next decode turn's sync)
PREFILL = ["llm.prefill.dispatch"]
STEP = ["llm.step.select", "llm.step.dispatch", "llm.step.sync",
        "llm.step.fanout"]
# what a caller's thread annotates inside ``llm_next``
# (tests/test_llm_delivery.py); everything else is the loop's
DRAINS = ("llm.next.drain",)


def _loop_events(events):
    """The loop's own phases, and the lines the drains were on."""
    return ([e for e in events if e[0] not in DRAINS],
            {e[4] for e in events if e[0] in DRAINS})


def test_loop_phases_in_a_profile_in_order_with_attributes(tmp_path):
    eng = _engine()
    try:
        eng.generate([1, 2, 3], 2)      # compile outside the profile

        def body():
            eng.generate([7, 8, 9, 10, 11], 4)
            time.sleep(0.06)            # a few idle turns

        before = time.time_ns()
        events = _profiled(tmp_path, body)
        after = time.time_ns()
    finally:
        eng.shutdown_engine()
    events, drain_lines = _loop_events(events)
    loop_lines = {e[4] for e in events}
    assert len(loop_lines) == 1         # every phase is on the loop thread
    assert drain_lines and not drain_lines & loop_lines    # the caller's
    names = [e[0] for e in events]
    # never two open at once: each ends before the next starts
    assert all(a[2] <= b[1] for a, b in zip(events, events[1:]))
    # one admission: admit -> the chunks' dispatch -> the first turn's four
    # phases (it enqueues step 1, then reads and hands out the first token)
    i = names.index("llm.prefill.dispatch")
    assert names[i - 1] == "llm.admit"
    assert names[i:i + 1] == PREFILL
    j = names.index("llm.step.dispatch")
    assert i + 1 <= j - 1 and names[j - 1:j + 3] == STEP
    # 4 tokens: 1 from the prefill, 3 decode steps, each enqueued a turn
    # before it is read; the last turn enqueues nothing and reads step 3
    assert names.count("llm.step.dispatch") == 3
    assert names.count("llm.step.sync") == names.count("llm.step.fanout") == 4
    k = len(names) - 1 - names[::-1].index("llm.step.fanout")
    assert names[k - 2:k + 1] == [STEP[0], STEP[2], STEP[3]]
    assert "llm.loop.wait" in names
    stats = {n: s for n, _, _, s, _ in reversed(events)}   # first of each
    assert int(stats["llm.prefill.dispatch"]["rows"]) == 1
    assert int(stats["llm.prefill.dispatch"]["tokens_real"]) == 5
    assert int(stats["llm.step.select"]["occupancy"]) == 1
    assert int(stats["llm.step.fanout"]["tokens"]) == 1
    assert {"queued", "free"} <= set(stats["llm.admit"])
    assert before <= int(stats["llm.step.dispatch"]["epoch_ns"]) <= after


@pytest.mark.parametrize("how", ["failpoint", "step_fn"])
def test_no_phase_is_left_open_when_a_step_raises(tmp_path, how):
    eng = _engine()
    try:
        eng.generate([1, 2, 3], 2)
        if how == "failpoint":
            failpoints.arm("serve.llm.before_step", "raise,once")
        else:
            real, calls = eng._step_fn, []

            def flaky(*a):
                calls.append(1)
                if len(calls) == 1:
                    raise RuntimeError("injected")
                return real(*a)

            eng._step_fn = flaky
        events = _profiled(
            tmp_path, lambda: eng.generate([7, 8, 9], 3))
        assert eng.llm_stats()["errors"] == 1
    finally:
        eng.shutdown_engine()
    events, _ = _loop_events(events)
    names = [e[0] for e in events]
    # the profile holds only spans that ended, and they never overlap:
    # the raise closed whatever was open
    assert all(a[2] <= b[1] for a, b in zip(events, events[1:]))
    # the failed turn enqueued nothing and still read what was outstanding
    # (the first token); then two steps, each read a turn after its enqueue
    assert names.count("llm.step.select") >= 4
    assert names.count("llm.step.dispatch") == (2 if how == "failpoint"
                                                else 3)
    assert names.count("llm.step.sync") == names.count("llm.step.fanout") == 4


def test_prefill_lane_counters_count_exactly():
    """Seeded prompts of 3, 8 and 11 tokens (the last truncated to the
    8 a slot's prompt rows hold) and one whose first prefill is refused
    (failpoint ``serve.llm.before_admit``) and retried: only prefills that
    ran count, each execution of the chunk program as its 8 tokens
    whatever ``prefill_rows`` is (a scheduling bound, not a shape)."""
    import numpy as np

    rng = np.random.default_rng(24)
    eng = _engine(max_batch=4, prefill_rows=2, max_prompt_len=8)
    try:
        for n in (3, 8, 11):
            eng.generate(rng.integers(1, 200, n).tolist(), 2)
        failpoints.arm("serve.llm.before_admit", "raise,once")
        eng.generate(rng.integers(1, 200, 5).tolist(), 2)
        st = eng.llm_stats()
    finally:
        eng.shutdown_engine()
    assert st["prefill_batches"] == 4            # the refused one never ran
    assert st["prefill_rows_real"] == st["admitted"] == 4
    assert st["prefill_tokens_real"] == 3 + 8 + 8 + 5
    assert st["prefill_chunks"] == 4 and st["prefill_chunk"] == 8
    assert st["prefill_tokens_lane"] == 4 * 8
    assert st["prefill_rows"] == 2               # the setting, as before
    assert set(st["init_s"]) == {"params", "cache", "first_prefill",
                                 "first_step"}
    assert all(v > 0 for v in st["init_s"].values())
    assert st["compiles"] == {"decode": 1, "prefill": 1}


def test_a_stopped_engine_ends_what_it_held_and_refuses_more():
    """Once the loop has ended nothing would serve a request: the one in
    a slot and the one queued end with an error at the stop, and a
    later submit is refused (a retiring replica may still be routed
    to until the new table reaches every router)."""
    failpoints.arm("serve.llm.before_step", "delay:0.2")
    eng = _engine(max_batch=1, max_new_tokens=8)
    try:
        active = eng.llm_submit([1, 2, 3], 8)
        queued = eng.llm_submit([4, 5], 8)
        deadline = time.monotonic() + 60
        while eng.llm_stats()["admitted"] < 1 and time.monotonic() < deadline:
            time.sleep(0.01)
    finally:
        assert eng.shutdown_engine()
    for rid in (active, queued):
        resp = eng.llm_next(rid, timeout_s=5.0)
        assert resp["done"] and resp["error"] == "engine stopped"
    assert eng.llm_stats()["queued"] == 0 and eng.llm_stats()["active"] == 0
    with pytest.raises(RuntimeError, match="stopped"):
        eng.llm_submit([1, 2, 3], 2)


def test_serve_shutdown_stops_the_engines_loop():
    def loops():
        return [t for t in threading.enumerate()
                if t.name == "llm-engine-loop" and t.is_alive()]

    before = set(loops())
    ray_tpu.init()
    try:
        dep = serve.deployment(name="llm_stop")(LLMEngine)
        handle = serve.run(dep.bind(
            model="gpt2", config=TINY, max_batch=2, cache_len=32,
            max_prompt_len=8))
        assert len(ray_tpu.get(handle.remote(
            {"tokens": [1, 2, 3], "max_tokens": 2}), timeout=120)["tokens"]) == 2
        started = set(loops()) - before
        assert len(started) == 1
        serve.shutdown()
        assert not any(t.is_alive() for t in started)
    finally:
        serve.shutdown()
        ray_tpu.shutdown()


class _StopsOnRetire:
    """A callable with the engine's stop hook, which notes how many
    replicas the controller lists at the moment it is asked to stop."""

    def __init__(self, note_path):
        self.note_path = note_path

    def __call__(self, _):
        return "ok"

    def shutdown_engine(self):
        from ray_tpu.serve import _private as sp

        _, table = ray_tpu.get(
            sp.get_or_create_controller().get_routing_table.remote(),
            timeout=30)
        listed = len(table["retiring"]["replicas"])
        with open(self.note_path, "a") as f:
            f.write(f"{listed}\n")
        return True


def test_scale_down_publishes_the_smaller_set_before_it_retires(tmp_path):
    note = tmp_path / "listed_at_stop"
    ray_tpu.init()
    try:
        dep = serve.deployment(
            name="retiring", num_replicas=2,
            autoscaling_config={"min_replicas": 1, "max_replicas": 2,
                                "downscale_delay_s": 0.2})(_StopsOnRetire)
        handle = serve.run(dep.bind(str(note)))
        assert ray_tpu.get(handle.remote(None), timeout=30) == "ok"
        deadline = time.monotonic() + 30
        while not note.exists() and time.monotonic() < deadline:
            time.sleep(0.05)
        # idle: one of the two is retired, and was already off the table
        assert note.read_text().split() == ["1"]
        assert serve.status()["retiring"]["num_replicas"] == 1
        assert ray_tpu.get(handle.remote(None), timeout=30) == "ok"
    finally:
        serve.shutdown()
        ray_tpu.shutdown()


# -- the training path ----------------------------------------------------------


def test_trainer_annotations_in_a_profile(tmp_path):
    import queue

    from ray_tpu.parallel import MeshConfig, build_mesh
    from ray_tpu.train import session as train_session
    from ray_tpu.train.train_step import _MeshBound

    mesh = build_mesh(MeshConfig(fsdp=1, devices=jax.devices()[:1]))
    bound = _MeshBound(jax.jit(lambda x: x + 1), mesh)
    bound(jnp.zeros(2))
    sess = train_session._Session(
        world_rank=0, world_size=1, local_rank=0, node_rank=0,
        results_queue=queue.Queue(), checkpoint=None, dataset_shards=None)

    def body():
        bound(jnp.zeros(2))
        sess.report({"loss": 1.0})

    names = [e[0] for e in _profiled(tmp_path, body)]
    assert names == ["train.step.dispatch", "train.report"]


# -- named scopes ---------------------------------------------------------------


def _scopes_in(lowered) -> set:
    """Scope names on the paths of a lowered program's operations (a
    transformation wraps the scope: ``transpose(jvp(head_loss))``)."""
    found = set()
    for path in re.findall(r'loc\("([^"]+)"', lowered.as_text(debug_info=True)):
        found.update(re.split(r"[/()]", path))
    return found


BLOCK = {"embed", "ln", "attn_proj", "attn", "mlp"}


@pytest.mark.parametrize("scan_layers", [False, True])
@pytest.mark.parametrize("ce_chunks", [1, 4])
def test_scopes_survive_grad_remat_and_scan(scan_layers, ce_chunks):
    from ray_tpu.train.optim import AdamWConfig, adamw_init, adamw_update

    cfg = dataclasses.replace(TINY, remat="dots", scan_layers=scan_layers,
                              ce_vocab_chunks=ce_chunks)
    params = gpt2.gpt2_init(jax.random.PRNGKey(0), cfg)
    batch = {"tokens": jnp.zeros((2, 17), jnp.int32)}

    def step(p, b):
        loss, grads = jax.value_and_grad(gpt2.gpt2_loss)(p, b, cfg)
        return loss, adamw_update(AdamWConfig(), grads, p, adamw_init(p),
                                  jnp.zeros((), jnp.int32))

    lowered = jax.jit(step).lower(params, batch)
    text = lowered.as_text(debug_info=True)
    assert BLOCK | {"head_loss", "adamw"} <= _scopes_in(lowered)
    # and on the backward pass, not only the forward (a scan's body is
    # lowered apart, its paths relative to the loop: there the backward
    # is the body that holds ``rematted_computation``)
    back = ("head_loss",) if scan_layers else ("head_loss", "mlp", "attn")
    for scope in back:
        assert re.search(rf'loc\("[^"]*transpose\([^"]*{scope}', text), scope
    if scan_layers:
        assert re.search(r'loc\("[^"]*rematted_computation/mlp/', text)


@pytest.mark.parametrize("which", ["decode", "prefill"])
def test_scopes_of_the_serving_programs(which):
    params = gpt2.gpt2_init(jax.random.PRNGKey(0), TINY)
    cache = gpt2.gpt2_init_cache(TINY, 3, 32)
    if which == "decode":
        lowered = jax.jit(
            lambda p, c, t, pos: gpt2.gpt2_decode_step(p, c, t, pos, TINY)
        ).lower(params, cache, jnp.zeros(3, jnp.int32),
                jnp.zeros(3, jnp.int32))
    else:
        lowered = jax.jit(
            lambda p, c, t, s, n: gpt2.gpt2_prefill(p, c, t, s, n, TINY)
        ).lower(params, cache, jnp.zeros((2, 8), jnp.int32),
                jnp.zeros(2, jnp.int32), jnp.ones(2, jnp.int32))
    assert BLOCK | {"cache_write", "head"} <= _scopes_in(lowered)


# -- the cache stays in place across the layer loop (PR 25) ----------------------


def _sub_jaxprs(eqn):
    for value in eqn.params.values():
        for item in (value if isinstance(value, (list, tuple)) else (value,)):
            inner = getattr(item, "jaxpr", item)
            if hasattr(inner, "eqns"):
                yield inner


def _cache_moves(jaxpr, cache_shape):
    """Every way ``jaxpr`` (and what it calls) makes a value of the stacked
    cache's shape other than by writing rows into one: the faults of the
    contract. A row write is a ``dynamic_update_slice`` or scatter whose
    update is smaller than ONE slot's block of ONE layer."""
    slot_block = 1
    for dim in cache_shape[2:]:
        slot_block *= dim
    faults = []
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        outs = [getattr(v.aval, "shape", None) for v in eqn.outvars]
        if name == "scan":
            carries = eqn.params["num_carry"]
            if cache_shape in outs[carries:]:
                faults.append("the cache is a stacked output (ys) of the "
                              "layer loop")
        elif cache_shape in outs and not list(_sub_jaxprs(eqn)):
            rows = name == "dynamic_update_slice" or name.startswith("scatter")
            update = eqn.invars[2 if name.startswith("scatter") else 1].aval
            if not rows:
                faults.append(f"{name} makes a whole cache")
            elif update.size >= slot_block:
                faults.append(f"{name} writes {update.shape}, not rows")
        for inner in _sub_jaxprs(eqn):
            faults += _cache_moves(inner, cache_shape)
    return faults


def _serving_program(family, which):
    from ray_tpu.models import llama

    if family == "gpt2":
        cfg, init, init_cache = TINY, gpt2.gpt2_init, gpt2.gpt2_init_cache
        decode, prefill = gpt2.gpt2_decode_step, gpt2.gpt2_prefill
    else:
        cfg = dataclasses.replace(llama.LlamaConfig.tiny(),
                                  dtype=jnp.float32)
        init, init_cache = llama.llama_init, llama.llama_init_cache
        decode, prefill = llama.llama_decode_step, llama.llama_prefill
    params = init(jax.random.PRNGKey(0), cfg)
    cache = init_cache(cfg, 3, 32)
    if which == "decode":
        return jax.make_jaxpr(lambda p, c, t, n: decode(p, c, t, n, cfg))(
            params, cache, jnp.zeros(3, jnp.int32),
            jnp.zeros(3, jnp.int32)), cache["k"].shape
    return jax.make_jaxpr(lambda p, c, t, s, n: prefill(p, c, t, s, n, cfg))(
        params, cache, jnp.zeros((2, 8), jnp.int32),
        jnp.zeros(2, jnp.int32), jnp.ones(2, jnp.int32)), cache["k"].shape


@pytest.mark.parametrize("which", ["decode", "prefill"])
@pytest.mark.parametrize("family", ["gpt2", "llama"])
def test_the_cache_is_only_written_by_rows(family, which):
    """No stacked output of the layer loop has the cache's shape, and
    whatever makes a cache-shaped value anywhere in the program writes
    rows into the one it was given. (What the TPU's compiler makes of it,
    no layer-sized copy in the loop and the temp space, is the
    compile-only rehearsal's to say: PERF.md section 6, PR 25.)"""
    closed, cache_shape = _serving_program(family, which)
    assert _cache_moves(closed.jaxpr, cache_shape) == []
    loops = [e for e in closed.jaxpr.eqns if e.primitive.name == "scan"]
    assert len(loops) == 1 and loops[0].params["length"] == cache_shape[0]


def test_the_guard_refuses_the_cache_through_xs_and_ys():
    """The form this PR took out: each layer's block cut out of the stack
    as ``xs``, written, and stacked again as ``ys``."""
    cache = gpt2.gpt2_init_cache(TINY, 3, 32)["k"]
    rows = jnp.ones((3, 1) + cache.shape[3:], cache.dtype)

    def through(cache):
        def layer(x, block):
            return x, jax.lax.dynamic_update_slice(
                block, rows, (0, 5) + (0,) * (block.ndim - 2))
        return jax.lax.scan(layer, 0.0, cache)[1]

    faults = _cache_moves(jax.make_jaxpr(through)(cache).jaxpr, cache.shape)
    assert faults == ["the cache is a stacked output (ys) of the layer loop"]

    def whole_layer(cache):   # in the carry, but a block at a time
        def layer(c, i):
            block = jax.lax.dynamic_index_in_dim(c, i, 0) + 1.0
            return jax.lax.dynamic_update_slice(
                c, block, (i,) + (0,) * (c.ndim - 1)), None
        return jax.lax.scan(layer, cache, jnp.arange(cache.shape[0]))[0]

    faults = _cache_moves(jax.make_jaxpr(whole_layer)(cache).jaxpr,
                          cache.shape)
    assert len(faults) == 1 and "not rows" in faults[0]


# -- the compile log --------------------------------------------------------------


def test_compile_log_sees_one_miss_then_one_hit(tmp_path):
    from jax.experimental.compilation_cache import compilation_cache as cc

    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes",
            "jax_enable_compilation_cache")
    saved = {k: getattr(jax.config, k) for k in keys}
    assert device_telemetry.ensure_listeners()
    try:
        jax.config.update("jax_enable_compilation_cache", True)
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        cc.reset_cache()

        def pr24_logged_fn(x):
            return jnp.sin(x) * 24.0 + 1.0

        t0 = time.time_ns()
        for _ in range(2):
            jax.jit(pr24_logged_fn)(jnp.arange(8.0)).block_until_ready()
            jax.clear_caches()           # the in-memory cache, not the disk
        # by name, not by position: the log keeps the last 512 compiles,
        # and a worker that has run other files first has filled it
        mine = [e for e in device_telemetry.compile_log()
                if "pr24_logged_fn" in (e["fun_name"] or "")]
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        cc.reset_cache()
    assert [e["cache"] for e in mine] == ["miss", "hit"]
    assert all(e["seconds"] > 0 and t0 <= e["epoch_ns"] <= time.time_ns()
               for e in mine)
    counts = device_telemetry.compile_counts()
    assert counts["cache_hits"] >= 1 and counts["backend_compiles"] >= 2
