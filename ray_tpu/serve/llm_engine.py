"""Continuous-batching LLM decode engine (the millions-of-users datapath).

The single-tenant ``generate()``-per-request serving shape recompiles or
runs a private decode loop per caller; on TPU the idiomatic XLA answer
is the opposite: ONE compiled decode step over a fixed ``[max_batch]``
state, with requests admitted into (and evicted from) the running batch
**between** steps — iteration-level scheduling. This module is that
engine, mounted as an ordinary Serve deployment callable:

* **Two compiled shapes, ever.** A fixed ``[max_batch]`` decode step
  and ONE prefill chunk: ``[1, C]`` tokens of one request at a start
  offset (``models/*.py``: ``<family>_prefill_chunk``), executed
  ``ceil(len / C)`` times a prompt, so a prefill costs what its prompt
  costs and not what the longest allowed prompt would. C is the
  engine's (``models/prefill.py``). ``prefill_rows`` is a scheduling
  bound, not a shape: a turn admits up to that many waiting requests,
  and every chunk of each runs before the next decode step, so a slot
  is never half-prefilled when a step runs. Per-engine compile counters
  (trace-time side effects, the ``fused_norm`` test idiom) prove no
  per-request recompile ever happens — the benchmark's serving cells
  require ``compiles == {decode: 1, prefill: 1}``.
* **A step may yield more than one token a slot.** Where a family's
  bundle holds a verify-and-draft step (a model that drafts for itself
  with its own prediction module), the decode program runs a slot's
  newest token AND its draft, and yields the main stack's one or two
  greedy tokens and the next draft: still one array and one sync a step.
  What is served is token for token what the family serves undrafted;
  ``max_tokens`` and the end token cut inside a pair. The other
  families run the programs and the host path they always ran.
* **One step ahead.** The loop enqueues decode step n + 1 before it reads
  step n: the step's tokens stay on the device (step n's output, with the
  first tokens of requests admitted since put into their slots, also from
  the device), so the chip does not wait for the read, the fan-out and
  the runtime's hand-over between two steps. At most one step is
  dispatched and unread; its fan-out belongs to the requests it was
  dispatched FOR (``_loop``).
* **Slot-indexed ring KV-cache in device memory.** Per-slot write
  cursors via ``lax.dynamic_update_slice``; the cache rides the model's
  activation dtype (bf16 — no fp32 copy) and, for Llama, the GQA
  ``n_kv_head`` layout. A finished/shed request's slot is recycled at
  the next step boundary; generations longer than the cache degrade to
  sliding-window attention instead of erroring.
* **Deadline semantics ride the PR-8 shed plumbing.** A request whose
  absolute deadline dies — queued or mid-decode — frees its slot at the
  next step boundary as a TYPED shed (``RequestShedError``,
  ``reason="decode"``, counted in ``ray_tpu_serve_shed_total``), never
  a hang; admission prefers requests by deadline slack.
* **Token streaming.** Every request is a stream of per-step token
  chunks, drained by long-polls. ``llm_next`` long-polls ONE stream
  (``generate()`` and any direct speaker of the protocol).
  ``llm_poll(poller=...)`` long-polls EVERY stream submitted under that
  poller id in one call: a client process names itself at
  ``llm_submit(..., poller=<id>)``, the engine keeps one event a poller,
  and one blocked call a step takes what all its streams were given —
  the transport ``serve._private.stream_call`` (handle ``.stream()``,
  HTTP chunked transfer, the ``ray://`` proxy's server-streaming RPC)
  builds on: a poll a step a client process, not a poll a token a stream.

Failpoints ``serve.llm.before_admit`` / ``serve.llm.before_step`` let
chaos crash, delay or hang the scheduler mid-iteration; the loop
requeues interrupted admissions (bounded retries) and fails active
streams fast after repeated step errors — fail fast, never hang.

Metric families (two-sided through ``serve/_observability``):
``ray_tpu_serve_decode_{step_seconds,batch_occupancy,ttft_seconds,
tokens_total}``.
"""

from __future__ import annotations

import heapq
import logging
import os
import threading
import time
import warnings
from typing import Dict, List, Optional

from ray_tpu.serve import _observability as _obs
from ray_tpu.serve._observability import RequestShedError
from ray_tpu.util import failpoints
from ray_tpu.util import metrics as _metrics
from ray_tpu.util import tracing

logger = logging.getLogger(__name__)

# How many consecutive decode-step failures fail the active streams
# (each failure already surfaced; three in a row means the step itself
# is broken, and holding streams open past that would be a hang).
_MAX_STEP_ERRORS = 3
# Abandoned-stream reap: a DONE stream nobody polls for this long is
# dropped (the bench's fire-and-forget shed probes must not accumulate).
_STREAM_TTL_S = 120.0
# Upper edges of ``deliver_lag_hist``'s first seven buckets, in ms (the
# eighth holds the rest): each twice the one before, so a lag's bucket is
# the bit length of its count of quarter milliseconds.
DELIVER_LAG_EDGES_MS = (0.25, 0.5, 1.0, 2.0, 4.0, 8.0, 16.0)
_LAG_TOP = len(DELIVER_LAG_EDGES_MS)
# The loop's record of its own turns (``LLMEngine._turn_done``). The phases
# of a pass of the loop, in the order of ``turn_phase_ns``: the seven
# device spans as the loop names them, its idle wait, and ``other``, the
# pass less these.
TURN_PHASES = ("llm.admit", "llm.prefill.dispatch", "llm.step.select",
               "llm.step.dispatch", "llm.step.sync", "llm.step.fanout",
               "llm.loop.reap", "llm.loop.wait", "other")
_PHASE_AT = {name: i for i, name in enumerate(TURN_PHASES)}
_SYNC_AT = _PHASE_AT["llm.step.sync"]
# Upper edges, in ms, of the first fifteen buckets of the four
# ``turn_hist_*`` lists (the sixteenth holds 16 s and more): each twice the
# one before, so a turn's bucket is the bit length of its whole
# milliseconds.
TURN_EDGES_MS = tuple(2 ** i for i in range(15))
_TURN_TOP = len(TURN_EDGES_MS)
# The allocator's numbers a kept turn reads from
# ``jax.Device.memory_stats()`` (-1 where the backend gives none).
MEMORY_KEYS = ("num_allocs", "bytes_in_use", "bytes_reserved",
               "largest_free_block_bytes")
# ``slow_turns`` is ONE flat list of integers, this many a kept turn, in
# this order: when the turn began (``time.perf_counter_ns``) and how long
# it was, its phases, what it did, the ``llm.step.sync`` of the turn after
# it (-1 until that turn has ended), the allocator's numbers at the turn's
# end, and how far each had moved since the loop's last ``llm.loop.reap``.
SLOW_TURN_FIELDS = (
    "start_ns", "turn_ns", *TURN_PHASES, "chunks", "admitted", "rows",
    "steps_read", "firsts_read", "outstanding", "next_sync_ns",
    *MEMORY_KEYS, *(k + "_since_reap" for k in MEMORY_KEYS))
_NEXT_SYNC_AT = SLOW_TURN_FIELDS.index("next_sync_ns")
SLOW_TURNS = 8                       # kept at most
SLOW_TURN_AGE_NS = 60_000_000_000    # and no longer than this
# a plain turn this long is a stall, and is logged
SLOW_TURN_WARN_NS = 1_000_000_000


class _Stream:
    """One request's token stream: per-step chunks pending delivery plus
    the terminal state. ``event`` is set whenever there is something new
    to deliver (chunks or the terminal transition); a stream submitted
    under a poller shares its ``poller``'s event, so whatever tells the
    stream tells the poller's one blocked call. ``visible_ns`` is when
    ``pending`` last turned from empty to non-empty, ``last_poll`` the
    last drain, both ``time.perf_counter_ns()``."""

    __slots__ = ("pending", "done", "shed", "error", "delivered",
                 "last_poll", "event", "n_tokens", "visible_ns", "poller")

    def __init__(self):
        self.pending: List[List[int]] = []
        self.done = False
        self.shed: Optional[str] = None
        self.error: Optional[str] = None
        self.delivered = False
        self.last_poll = time.perf_counter_ns()
        self.event = threading.Event()
        self.n_tokens = 0
        self.visible_ns = 0
        self.poller: Optional[_Poller] = None


class _Poller:
    """The streams one client process holds on this engine and the ONE
    event its blocked ``llm_poll`` waits on. ``waiting`` counts the calls
    inside that wait: a poller with no stream left is forgotten, but not
    from under a call, or a stream submitted meanwhile would set an event
    nobody waits on. All of it is read and written under the engine's
    lock."""

    __slots__ = ("pid", "event", "streams", "waiting")

    def __init__(self, pid: str):
        self.pid = pid
        self.event = threading.Event()
        self.streams: Dict[str, _Stream] = {}
        self.waiting = 0


class _Request:
    __slots__ = ("rid", "prompt", "max_new", "deadline_ts", "submitted",
                 "remaining", "unread", "retries", "stream", "seq",
                 "trace_ctx", "span")

    def __init__(self, rid: str, prompt: List[int], max_new: int,
                 deadline_ts: Optional[float], seq: int,
                 trace_ctx: Optional[dict] = None):
        self.rid = rid
        self.prompt = prompt
        self.max_new = max_new
        self.deadline_ts = deadline_ts
        self.submitted = time.time()
        self.remaining = max_new
        # Results dispatched for it and not fanned out yet (its prompt's
        # last chunk, a decode step's row): each yields a token at least,
        # so it is owed a further step while ``remaining > unread``. The
        # loop's thread alone reads and writes it.
        self.unread = 0
        self.retries = 0
        self.stream = _Stream()
        self.seq = seq  # FIFO tiebreak for slack ordering
        # Flight-recorder state (None when the caller doesn't trace):
        # the caller's span context, and the request's OPEN phase span
        # (llm.queue -> llm.prefill -> llm.decode, exactly one open at
        # a time). Manual spans (tracing.start_span): the engine loop
        # runs on its own thread, and a request's lifecycle crosses
        # submit-thread -> loop-thread, so thread-local context
        # managers cannot carry them. Mutated only under the engine
        # lock; every terminal path closes via _finish_locked.
        self.trace_ctx = trace_ctx
        self.span: Optional[dict] = None


class _Turn:
    """One pass of the loop's body as the loop's thread times it: when it
    began, the nanoseconds of each phase (``TURN_PHASES``; ``other`` is
    filled in where the pass ends) and what it did."""

    __slots__ = ("t0", "ns", "chunks", "admitted", "rows", "steps_read",
                 "firsts_read")

    def __init__(self):
        self.t0 = time.perf_counter_ns()
        self.ns = [0] * len(TURN_PHASES)
        self.chunks = self.admitted = self.rows = 0
        self.steps_read = self.firsts_read = 0

    @property
    def plain(self) -> bool:
        """It read a step with no prefill in front of it."""
        return bool(self.steps_read) and not self.chunks \
            and not self.firsts_read


class _Phase:
    """One phase of a turn: the profiler's annotation
    (``tracing.device_span``) and, at the same two edges, the loop's own
    clock, added to the turn's nanoseconds of that phase."""

    __slots__ = ("_ns", "_at", "_span", "_t0")

    def __init__(self, ns: List[int], at: int, span):
        self._ns, self._at, self._span = ns, at, span

    def __enter__(self):
        self._t0 = time.perf_counter_ns()
        return self._span.__enter__()

    def __exit__(self, *exc):
        out = self._span.__exit__(*exc)
        self._ns[self._at] += time.perf_counter_ns() - self._t0
        return out


class _Step:
    """A decode step on the device and not read yet. ``out`` is the one
    array the step hands back, ``rows`` the ``(slot, request)`` pairs it
    was dispatched FOR: its fan-out is theirs, whoever holds the slots when
    it is read. ``ahead``: another step was unread when this one was
    dispatched."""

    __slots__ = ("out", "rows", "ahead")

    def __init__(self, out, rows: List[tuple], ahead: bool):
        self.out, self.rows, self.ahead = out, rows, ahead


class _Firsts:
    """An admission turn's results on the device and not read yet:
    ``rows`` holds ``(slot, request, the last chunk's token array)`` of
    each admitted request, ``counted`` what the programs had counted in
    the cache once the turn's last chunk ran (one array, or None)."""

    __slots__ = ("rows", "counted")

    def __init__(self, rows: List[tuple], counted):
        self.rows, self.counted = rows, counted


def _model_bundle(model: str, config, preset: str):
    """(config, init, init_cache, prefill_chunk, decode_step) for a model
    family — resolved lazily so importing this module never pulls jax.

    A family that drafts for itself adds a SIXTH element, its
    verify-and-draft step (``models/exaone_moe.exaone_moe_verify_step``
    has the contract: two rows a slot in, ``served [S, 4]`` out: how many
    tokens, the tokens, the next draft). An engine serves with it where
    the bundle has one: its prefill chunk then also takes ``follows`` and
    returns the first draft's logits; ``decode_step`` stays the family's
    undrafted step, which tests hold the drafted engine's tokens to."""
    if model == "gpt2":
        from ray_tpu.models import gpt2 as m

        cfg = config or (m.GPT2Config.tiny() if preset == "tiny"
                         else m.GPT2Config.small())
        return (cfg, m.gpt2_init, m.gpt2_init_cache, m.gpt2_prefill_chunk,
                m.gpt2_decode_step_counted)
    if model == "llama":
        from ray_tpu.models import llama as m

        cfg = config or (m.LlamaConfig.tiny() if preset == "tiny"
                         else m.LlamaConfig.small())
        return (cfg, m.llama_init, m.llama_init_cache, m.llama_prefill_chunk,
                m.llama_decode_step)
    if model == "nemotron_h":
        from ray_tpu.models import nemotron_h as m

        cfg = config or (m.NemotronHConfig.tiny() if preset == "tiny"
                         else m.NemotronHConfig())
        return (cfg, m.nemotron_h_init, m.nemotron_h_init_cache,
                m.nemotron_h_prefill_chunk, m.nemotron_h_decode_step)
    if model == "granite_hybrid":
        from ray_tpu.models import granite_hybrid as m

        cfg = config or (m.GraniteHybridConfig.tiny() if preset == "tiny"
                         else m.GraniteHybridConfig())
        return (cfg, m.granite_hybrid_init, m.granite_hybrid_init_cache,
                m.granite_hybrid_prefill_chunk, m.granite_hybrid_decode_step)
    if model == "deepseek_v2":
        from ray_tpu.models import deepseek_v2 as m

        cfg = config or (m.DeepseekV2Config.tiny() if preset == "tiny"
                         else m.DeepseekV2Config())
        return (cfg, m.deepseek_v2_init, m.deepseek_v2_init_cache,
                m.deepseek_v2_prefill_chunk, m.deepseek_v2_decode_step)
    if model == "falcon_h1":
        from ray_tpu.models import falcon_h1 as m

        cfg = config or (m.FalconH1Config.tiny() if preset == "tiny"
                         else m.FalconH1Config())
        return (cfg, m.falcon_h1_init, m.falcon_h1_init_cache,
                m.falcon_h1_prefill_chunk, m.falcon_h1_decode_step)
    if model == "qwen3_next":
        from ray_tpu.models import qwen3_next as m

        cfg = config or (m.Qwen3NextConfig.tiny() if preset == "tiny"
                         else m.Qwen3NextConfig())
        return (cfg, m.qwen3_next_init, m.qwen3_next_init_cache,
                m.qwen3_next_prefill_chunk, m.qwen3_next_decode_step)
    if model == "smallthinker":
        from ray_tpu.models import smallthinker as m

        cfg = config or (m.SmallThinkerConfig.tiny() if preset == "tiny"
                         else m.SmallThinkerConfig())
        return (cfg, m.smallthinker_init, m.smallthinker_init_cache,
                m.smallthinker_prefill_chunk, m.smallthinker_decode_step)
    if model == "exaone_moe":
        from ray_tpu.models import exaone_moe as m

        cfg = config or (m.ExaoneMoeConfig.tiny() if preset == "tiny"
                         else m.ExaoneMoeConfig())
        return (cfg, m.exaone_moe_init, m.exaone_moe_init_cache,
                m.exaone_moe_prefill_chunk, m.exaone_moe_decode_step,
                m.exaone_moe_verify_step)
    if model == "keye_vl2":
        from ray_tpu.models import keye_vl2 as m

        cfg = config or (m.KeyeVL2Config.tiny() if preset == "tiny"
                         else m.KeyeVL2Config())
        return (cfg, m.keye_vl2_init, m.keye_vl2_init_cache,
                m.keye_vl2_prefill_chunk, m.keye_vl2_decode_step)
    raise ValueError(
        f"unknown model family {model!r} (want gpt2|llama|nemotron_h|"
        f"granite_hybrid|deepseek_v2|falcon_h1|qwen3_next|smallthinker|"
        f"exaone_moe|keye_vl2)")


def _stored_params(init, key, cfg):
    """The family's seeded parameters, each leaf in the type in which the
    family's two programs consume it (``cfg.serving_dtypes``: the family
    knows its leaves, the engine does not), and the bytes held by stored
    type. Leaf by leaf, each source let go as soon as its cast exists, so
    that construction holds one leaf's copy beside the tree and the engine
    ends with ONE copy of the weights."""
    import jax

    params = init(key, cfg)
    leaves, treedef = jax.tree.flatten(params)
    dtypes = treedef.flatten_up_to(cfg.serving_dtypes(params))
    del params  # from here on `leaves` holds the only references
    held: Dict[str, int] = {}
    for i, dt in enumerate(dtypes):
        if leaves[i].dtype != dt:
            leaves[i] = jax.block_until_ready(leaves[i].astype(dt))
        held[str(dt)] = held.get(str(dt), 0) + leaves[i].nbytes
    return jax.block_until_ready(treedef.unflatten(leaves)), held


class LLMEngine:
    """The deployment callable: one decode engine per replica.

    The engine never updates a weight, so it stores each parameter leaf
    once in the type its two programs would cast it to at every step (the
    family's ``serving_dtypes``) and keeps nothing of what it was cast
    from: the step reads the bytes it multiplies with and no others.

    Its two programs are the ``[max_batch + 1]`` decode step (two rows a
    slot where the family drafts for itself: ``_model_bundle``) and the
    ``[1, prefill_chunk]`` prefill chunk; a prompt runs the second once
    for every ``prefill_chunk`` tokens (``llm_stats()``:
    ``prefill_chunks`` executions for ``prefill_rows_real`` requests).
    ``prefill_rows`` bounds how many requests one turn admits between
    two decode steps; ``prefill_chunk`` defaults to the rule of
    ``models/prefill.py``: the power of two, from 256 up, at which a
    chunk's operations reach the chip's ridge for the weights it reads
    once, about 240 x ``params_stored`` / ``params_a_token`` (both in
    ``llm_stats()``) and no more than one lane of the experts' kernel
    (PERF.md section 6, PR 52's layer-alone table and PR 53's cells):
    256 where a token multiplies with every stored matrix, 512 where it
    takes ``top_k`` of the held experts; the longest prompt if that is
    shorter, and 256 again where the cache holds whole chunks of 256 up
    to ``max_prompt_len`` but not of 512 (tests at toy widths pass a
    small one). The cache must hold whole chunks up to
    ``max_prompt_len``.

    Deploy it like any Serve class::

        eng = serve.deployment(name="llm", max_concurrent_queries=64)(
            LLMEngine)
        handle = serve.run(eng.bind(model="gpt2", max_batch=32))
        for chunk in handle.stream([1, 2, 3], max_new_tokens=16):
            ...

    ``__call__``/``generate`` are the blocking request/response lane;
    ``llm_submit``/``llm_next``/``llm_poll`` are the streaming protocol:
    ``stream_call`` submits under its process's poller id and one thread
    of that process drains all its streams with ``llm_poll(poller=...)``,
    ``generate`` long-polls its own stream with ``llm_next``.
    """

    def __init__(self, model: str = "gpt2", config=None,
                 preset: str = "tiny", seed: int = 0,
                 max_batch: int = 8, cache_len: int = 64,
                 max_prompt_len: int = 16, prefill_rows: int = 4,
                 max_new_tokens: int = 16, max_new_cap: int = 512,
                 max_queue: int = 8192, eos_token: Optional[int] = None,
                 step_throttle_s: float = 0.0,
                 deployment: Optional[str] = None,
                 prefill_chunk: Optional[int] = None):
        import jax
        import numpy as np

        from ray_tpu.models.prefill import (chunk_len, key_window,
                                            token_parameters)
        from ray_tpu.util.compile_cache import ensure_compile_cache

        ensure_compile_cache()
        cfg, init, init_cache, prefill_chunk_fn, decode, *verify = \
            _model_bundle(model, config, preset)
        # the family's verify-and-draft step, where its bundle has one: a
        # step then yields one or two tokens a slot (_step_fanout)
        verify = verify[0] if verify else None
        self._drafting = verify is not None
        # The chunk is the engine's, by rule (models/prefill.py), from the
        # stored leaves' shapes and the configuration's routing; the
        # argument is for tests at toy widths, where a rule made for a
        # chip's ridge would never cut a prompt.
        self._rule_params = token_parameters(cfg, jax.eval_shape(
            lambda: init(jax.random.PRNGKey(seed), cfg)))
        chunk = int(prefill_chunk or chunk_len(
            max_prompt_len, *self._rule_params, cache_len=cache_len))
        window = key_window(max_prompt_len, chunk)
        if chunk < 1 or window > cache_len:
            raise ValueError(
                f"max_prompt_len={max_prompt_len} in chunks of {chunk} "
                f"writes {window} rows of a slot: they must fit the cache "
                f"(cache_len={cache_len})")
        self._np = np
        self._jnp = jax.numpy
        self.model = model
        self.max_batch = int(max_batch)
        self.cache_len = int(cache_len)
        self.max_prompt_len = int(max_prompt_len)
        self.prefill_rows = max(1, min(int(prefill_rows), self.max_batch))
        self.prefill_chunk = chunk
        self.max_new_tokens = int(max_new_tokens)
        self.max_new_cap = int(max_new_cap)
        self.max_queue = int(max_queue)
        self.eos_token = eos_token
        self.step_throttle_s = float(step_throttle_s)
        # Metrics label. None = adopt the Serve deployment's name (the
        # Replica calls set_deployment_name at construction); an
        # explicit bind arg wins over the adoption.
        self._dep = deployment or "llm"
        self._dep_explicit = deployment is not None

        if model == "gpt2" and self.max_prompt_len > cfg.seq_len:
            # gpt2's learned position table bounds the prefill window;
            # fail at bind time, not per-request inside the jit.
            raise ValueError(
                f"max_prompt_len={self.max_prompt_len} exceeds the "
                f"model's position window (seq_len={cfg.seq_len})")
        self._cfg = cfg
        # Where the engine's own set-up goes, in seconds (llm_stats):
        # the two below, then the first call of each jitted program
        # (trace + compile or cache load + run), stamped where it runs.
        t0 = time.perf_counter()
        self.params, self._param_bytes = _stored_params(
            init, jax.random.PRNGKey(seed), cfg)
        t1 = time.perf_counter()
        # One slot past max_batch that no request ever holds: a padded
        # lane's unused rows wrote there. The chunk program has no such
        # row, but the decode step's shape includes the slot (and the
        # benchmark counts it as cache), so it stays.
        self._cache = jax.block_until_ready(
            init_cache(cfg, self.max_batch + 1, self.cache_len))
        self._init_s = {"params": t1 - t0,
                        "cache": time.perf_counter() - t1}
        self._compiles = {"decode": 0, "prefill": 0}
        # A family's decode step may return, beside logits and cache, a
        # dict of int32 scalars that count what the step did (a sparse
        # model's experts hit). They ride behind the step's tokens in the
        # ONE array the step syncs on, and add up in stats_counters; a
        # family that returns none runs the program it always ran.
        self._step_counters: tuple = ()
        # What the model says of itself beside its counters (llm_stats).
        # Every family's takes the engine's chunk and key window: one
        # whose chunk program depends on them says which program that is.
        self._model_stats = dict(getattr(
            cfg, "serving_stats", lambda chunk, window: {})(chunk, window))

        def with_counters(out, counted):
            # the step's counters behind what it hands out: ONE array
            self._step_counters = tuple(sorted(counted))
            return self._jnp.concatenate([out, self._jnp.stack(
                [counted[k] for k in self._step_counters]).astype(
                    self._jnp.int32)])

        def step_fn(params, cache, tokens, pos):
            self._compiles["decode"] += 1  # trace-time: fires per compile
            logits, cache, *counted = decode(params, cache, tokens, pos, cfg)
            with jax.named_scope("head"):
                nxt = self._jnp.argmax(logits, axis=-1).astype(
                    self._jnp.int32)
            if counted:
                nxt = with_counters(nxt, counted[0])
            return nxt, cache

        def verify_fn(params, cache, tokens, pos):
            # tokens [S, 2]: a slot's newest token and its draft. The ONE
            # array the step syncs on: served [S, 4] (how many tokens, the
            # tokens, the next draft) flattened, then the counters.
            self._compiles["decode"] += 1
            _, cache, counted, served, *_ = verify(
                params, cache, tokens, pos, cfg)
            return with_counters(served.reshape(-1).astype(
                self._jnp.int32), counted), cache

        def draft_prefill_fn(params, cache, packed):
            # packed [1, chunk + 4]: ``prefill_fn``'s and the prompt's
            # token after the chunk (negative where the prompt ends in
            # it); -> the first token and the first draft
            self._compiles["prefill"] += 1
            logits, cache, draft_logits = prefill_chunk_fn(
                params, cache, packed[:, :chunk], packed[:, chunk],
                packed[:, chunk + 1], packed[:, chunk + 2], cfg,
                window=window, follows=packed[:, chunk + 3])
            with jax.named_scope("head"):
                return (self._jnp.argmax(
                    self._jnp.concatenate([logits, draft_logits]),
                    axis=-1).astype(self._jnp.int32), cache)

        def prefill_fn(params, cache, packed):
            # packed [1, chunk + 3] int32: the chunk's tokens, then its
            # slot, start and real tokens (ONE host array an execution:
            # each upload is a turn of the GIL among the streams' pollers)
            self._compiles["prefill"] += 1
            logits, cache = prefill_chunk_fn(
                params, cache, packed[:, :chunk], packed[:, chunk],
                packed[:, chunk + 1], packed[:, chunk + 2], cfg,
                window=window)
            with jax.named_scope("head"):
                return (self._jnp.argmax(logits, axis=-1).astype(
                    self._jnp.int32), cache)

        n_slots = self.max_batch + 1

        def carry_fn(out, pos, ahead):
            # What the next step is handed of every slot, from the last
            # step's ``out`` as it lies on the device: the slot's newest
            # token (and its draft, where the family drafts) and its
            # position. ``pos`` is the host's, which stands where the
            # last step it READ left it; ``ahead`` marks the rows of the
            # unread step, which are past it by what that step yielded: one
            # token, or what ``served [S, 4]`` says the device accepted.
            if not self._drafting:
                return out[:n_slots], pos + ahead
            served = out[:4 * n_slots].reshape(n_slots, 4)
            newest = self._jnp.where(served[:, 0] > 1, served[:, 2],
                                     served[:, 1])
            return (self._jnp.stack([newest, served[:, 3]], axis=1),
                    pos + ahead * served[:, 0])

        def put_fn(tokens, tok, slot):
            # an admitted request's first token (and first draft) into its
            # slot, from the chunk program's result on the device
            return tokens.at[slot].set(tok.reshape(tokens.shape[1:]))

        def counted_fn(counted):
            return self._jnp.stack(
                [counted[k] for k in self._counted_keys]).astype(
                    self._jnp.int32)

        # Donate the cache: the engine holds the ONLY reference and the
        # step replaces it, so XLA can update in place (2x HBM saved on
        # the big buffer). CPU test runs warn that donation was unused.
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable")
        if self._drafting:
            # (a profile shows these two as jit_verify_fn and
            # jit_draft_prefill_fn)
            step_fn, prefill_fn = verify_fn, draft_prefill_fn
        self._step_fn = jax.jit(step_fn, donate_argnums=(1,))
        self._prefill_fn = jax.jit(prefill_fn, donate_argnums=(1,))
        # The three helpers that keep a step's inputs on the device: a few
        # int32 operations each, none of them the engine's two programs
        # (``compiles`` counts those alone).
        self._carry_fn = jax.jit(carry_fn)
        self._put_fn = jax.jit(put_fn)
        self._counted_fn = jax.jit(counted_fn)
        # What a family's programs count in the cache itself (the leaves
        # of ``cache["counted"]``, int32 scalars that never stop rising
        # and so wrap): their names, and the last value read of each
        # (``_first_fanout``).
        self._counted_keys = tuple(sorted(self._cache.get("counted", {})))
        self._counted_seen: Dict[str, int] = {}

        # A step is handed, of each slot, its newest token (and, where the
        # family drafts, its draft for the position after, side by side:
        # [S, 2]) and its position. The tokens never come to the host on
        # their way from one step to the next: they are ``carry_fn`` of
        # the newest step's output (``_out``, read or not) with
        # ``_fresh``, the first tokens of the slots admitted since that
        # step was dispatched, put over it. Before the first step there is
        # no output, and the slots' tokens are these zeros.
        self._no_tokens = self._jnp.zeros(
            (n_slots, 2) if self._drafting else n_slots, self._jnp.int32)
        self._out = None
        self._fresh: Dict[int, object] = {}
        # What is dispatched and not read, in the device's order: at most
        # one decode step (``_Step``) and the admission turns behind it
        # (``_Firsts``). Only the loop's thread touches it (and
        # ``shutdown_engine``, once that thread has ended).
        self._outstanding: List[object] = []
        # A slot's position as of the last result READ for it (a freed
        # slot's is 0, an admitted one's its prompt's length).
        self._pos = np.zeros(n_slots, np.int32)
        self._slot_req: List[Optional[_Request]] = [None] * self.max_batch
        # Admission queue: a HEAP keyed (deadline slack, seq) — the 10k
        # flagship load would pay an O(n log n) re-sort per scheduler
        # iteration under the engine lock with a sorted list. Expiry and
        # cancellation are lazy (checked at pop); _n_queued is the live
        # count (heap entries may be dead).
        self._queue: List[tuple] = []
        self._n_queued = 0
        self._streams: Dict[str, _Stream] = {}
        # poller id -> the streams submitted under it that were not
        # handed out to their end yet, and the event they share
        self._pollers: Dict[str, _Poller] = {}
        self._lock = threading.Lock()
        self._wake = threading.Event()
        self._stop = False
        self._seq = 0
        self._step_errors_row = 0
        # The first prefill/decode failure's traceback is logged once: a
        # compile failure or an OOM on the chip repeats every step (the
        # donated cache is gone), and only the first one says why.
        self._failure_logged = False
        # Last wall-clock instant a token batch reached the streams —
        # the previous edge of the inter-token-latency (TPOT) gap.
        # None until the first prefill delivers (the first decode step
        # after a gap measures from the last delivery, so ITL includes
        # scheduling stalls between steps, not just compute).
        self._last_tokens_at: Optional[float] = None
        # Enqueue first, wake later. A decode step's token is in its
        # stream's ``pending`` (under the lock) when ``_step_fanout``
        # appends it, and the poller of a stream that goes on decoding is
        # told at the tail of that turn's fan-out, outside the lock: by
        # then the device already has its next program, because the turn
        # enqueued the step after this one BEFORE it read this one. Every
        # woken long-poll wants the lock and the interpreter; woken ahead
        # of the loop's dispatch they once kept the device waiting for it,
        # now they share the host with a turn the device does not wait
        # for. The streams of one fan-out owed a wake-up wait here; only
        # the loop's thread touches the list (``_flush_wakes``). Why no
        # order of set, drain and clear loses a token or delivers one
        # twice: the token is in ``pending`` under the lock BEFORE its
        # event can be set, and a long-poll (``_drain``) drains and clears
        # under the same lock; so a set that comes late finds either the
        # token still pending (the woken poll takes it) or already drained
        # by a poll that timed out or was woken for a neighbour token (the
        # woken poll returns no chunk, as a time-out does, and its caller
        # goes round again). A poller's call drains ALL its streams, so it
        # is told of a put-off token only where one is still pending.
        self._wakes: List[_Stream] = []
        # when the fan-out that filled ``_wakes`` made its tokens visible
        self._fanout_ns = 0
        self._last_reap = time.monotonic()
        self.stats_counters = {
            "steps": 0, "admitted": 0, "completed": 0, "shed": 0,
            "errors": 0, "tokens_out": 0,
            "occupancy_sum": 0, "ring_wraps": 0,
            # The step ahead: steps dispatched while another was
            # dispatched and unread (of ``steps``; both counted where the
            # step is read), and slot-rows a step computed for a request
            # that had ended, been cancelled or been shed before the step
            # was read (such a row is dropped, and is not in
            # ``occupancy_sum``, the rows that served a live request).
            "steps_ahead": 0, "rows_dropped": 0,
            # A drafting family's steps: drafts verified (one a slot a
            # step) and those the device's comparison accepted, each of
            # which made its step yield a second token. tokens_out over
            # occupancy_sum is then the tokens a step a slot.
            "draft_proposed": 0, "draft_accepted": 0,
            # The prefill lane's fill: admission turns that ran a
            # prefill, requests in them, their (truncated) prompt tokens,
            # executions of the chunk program (chunks a request =
            # prefill_chunks / prefill_rows_real) and the tokens those
            # computed (prefill_chunk each). (prefill_rows_real:
            # llm_stats() already has the setting under "prefill_rows".)
            "prefill_batches": 0, "prefill_rows_real": 0,
            "prefill_tokens_real": 0, "prefill_tokens_lane": 0,
            "prefill_chunks": 0,
            # Wake-ups of decode steps put off to the tail of their
            # fan-out (a stream a step that goes on decoding), and how
            # many of them were then set with a program on the device;
            # the rest were set with nothing behind their step (the
            # next step's dispatch raised, a throttle sleeps next).
            # Both are counted where the wake-ups are set, in one write
            # (``_flush_wakes``), so no snapshot reads them a step apart.
            "wakes_deferred": 0, "wakes_after_dispatch": 0,
            # The token's way out. Long-polls served, of one stream
            # (``llm_next``) or of a poller's streams
            # (``llm_poll(poller=...)``: those are ``next_batched``
            # too), and those that came back with no chunk and nothing
            # ended (a time-out, or the wake-up for a token an earlier
            # poll took). One body serves both (``_wait_drain``) and
            # counts a call once.
            "next_calls": 0, "next_empty": 0, "next_batched": 0,
            # How long the drained chunks lay in ``pending``: drain time
            # minus ``visible_ns``, summed and in the buckets of
            # DELIVER_LAG_EDGES_MS (``llm_stats()`` adds ``deliver_chunks``,
            # the buckets' sum). A drain that takes several chunks charges
            # each the oldest's lag (one clock slot a stream): exact where
            # a poll takes one, as a poller a stream does, an upper bound
            # for a lane that batches.
            "deliver_lag_ns": 0,
            "deliver_lag_hist": [0] * (len(DELIVER_LAG_EDGES_MS) + 1),
            # The part of that lag the loop chose: over the put-off
            # wake-ups whose token still waited when they were set, set
            # time minus the fan-out's. The rest of ``deliver_lag_ns``
            # is the interpreter, the lock and the poller's thread.
            "wake_defer_ns": 0,
            # The loop's record of its own turns, integers and flat lists
            # of integers that its thread alone writes, once a pass
            # (``_turn_done``). ``turn_ns`` and ``turn_phase_ns`` (in the
            # order of TURN_PHASES) add up every pass of the loop, idle
            # ones too, so the phases sum to ``turn_ns`` and both to the
            # loop's life; ``turns`` counts the passes that did something.
            # Of those, by class (plain: read a step, dispatched no chunk,
            # read no first token; prefill: the rest), how many fell into
            # each bucket of TURN_EDGES_MS and the nanoseconds they sum
            # to. ``slow_turns``: the longest of the last minute, at most
            # SLOW_TURNS, SLOW_TURN_FIELDS a turn.
            "turns": 0, "turn_ns": 0,
            "turn_phase_ns": [0] * len(TURN_PHASES),
            "turn_hist_plain": [0] * (_TURN_TOP + 1),
            "turn_hist_plain_ns": [0] * (_TURN_TOP + 1),
            "turn_hist_prefill": [0] * (_TURN_TOP + 1),
            "turn_hist_prefill_ns": [0] * (_TURN_TOP + 1),
            "slow_turns": [],
        }
        # The loop's thread's own: the pass it is in; the kept turns one
        # list each (``slow_turns`` is their copy, laid end to end where
        # ``_slow_changed``), the shortest's length once SLOW_TURNS are
        # kept (a turn enters by beating it), the one kept last while it
        # is owed its ``next_sync_ns`` (and whether it is logged then);
        # the device whose allocator a kept turn reads, and what it read
        # at the last reap.
        self._turn = _Turn()
        self._slow: List[List[int]] = []
        self._slow_floor = -1
        self._slow_changed = False
        self._slow_last: Optional[tuple] = None
        self._device = next(iter(
            jax.tree_util.tree_leaves(self._cache)[0].devices()))
        self._mem_at_reap = self._memory()
        self._loop_thread = threading.Thread(
            target=self._loop, daemon=True, name="llm-engine-loop")
        self._loop_thread.start()

    # -- scheduler loop ----------------------------------------------------

    def _loop(self):  # jax-hot-path
        """The scheduler, one step ahead of what it reads. A turn
        (``_step_once``, then ``_admit_once``):

        1. *select*: deadline eviction, then the rows of the next step:
           the slots whose request is live and still owed a token AFTER
           what is dispatched and unread for it (by count: ``remaining``
           over ``unread``; an end token the host does not know yet);
        2. *dispatch* step n + 1: its tokens are step n's output ON THE
           DEVICE, with the first tokens of the requests admitted since
           step n was dispatched put into their slots, also from the
           device; its positions are the host's, and the device adds what
           the unread step n moved a row by;
        3. *sync*: read step n, which the device ended about a step ago
           or is ending, then the first tokens of the requests whose
           chunks were dispatched last turn;
        4. *fanout* of step n and of those first tokens; then admission:
           the chunks go to the device BEHIND step n + 1, and their first
           token is read in the next turn's sync.

        So at any moment at most one decode step is dispatched and
        unread. A turn that finds nothing to enqueue reads what is
        outstanding before the loop waits; a dispatch that raises, a
        ``step_throttle_s`` that sleeps next and the loop's end leave
        nothing outstanding and unread (``_step_once`` reads the turn's
        own step too where nothing may stay behind).

        A token is VISIBLE the moment a fan-out appends it to its stream
        under the lock: every drain (``llm_next``, ``llm_poll``) sees it
        from then on. Its poller is TOLD (the stream's event set, which is
        its poller's where it was submitted under one: one blocked call
        for all of a client process's streams) at once for a first token
        and a terminal transition, and for a decode step's token at the
        tail of its fan-out, outside the lock (``_flush_wakes``): the
        device has the step after it by then. A request the stop, a
        cancel or a shed ends is woken by its terminal transition, so no
        wake-up waits for anything but its own turn's fan-out."""
        while not self._stop:
            turn = self._turn = _Turn()
            did = False
            try:
                did = self._admit_once() or did
            except BaseException:
                # _admit_once handles its own requeue; anything that
                # still escapes must not kill the scheduler — but a
                # scheduler stuck in a crash-restart cycle must be
                # visible on the scrape, not just a silent hot core.
                _metrics.count_loop_restart("llm.engine")
            try:
                did = self._step_once() or did
            except BaseException:
                # Step errors are already counted (3-strike fail-fast
                # in _step_once); this tick records the loop survival.
                _metrics.count_loop_restart("llm.engine")
            if time.monotonic() - self._last_reap > 5.0:
                with self._phase("llm.loop.reap"):
                    self._reap_streams()
                    self._reap_turns()
            if not did:
                with self._phase("llm.loop.wait"):
                    self._wake.wait(0.02)
                self._wake.clear()
            self._turn_done(turn, did)
        turn = self._turn = _Turn()
        did = False
        try:
            # the loop ends with nothing dispatched and unread: what the
            # device still holds is delivered before the streams are ended
            did = self._step_once(settle=True)
        except BaseException:
            _metrics.count_loop_restart("llm.engine")
        self._turn_done(turn, did)

    # -- the loop's record of its own turns (its thread only) --------------

    def _phase(self, name: str, **attrs) -> _Phase:
        """``tracing.device_span(name)``, and the running turn's clock at
        the same edges: the counters and a profile's annotations cut the
        turn alike."""
        return _Phase(self._turn.ns, _PHASE_AT[name],
                      tracing.device_span(name, **attrs))

    def _memory(self) -> List[int]:
        st = self._device.memory_stats() or {}
        return [int(st.get(k, -1)) for k in MEMORY_KEYS]

    def _reap_turns(self) -> None:
        """With the streams' reap, every 5 s: the allocator's numbers a
        kept turn's are held against, and the kept turns that are older
        than a minute forgotten (the next turns take their places)."""
        self._mem_at_reap = self._memory()
        cutoff = time.perf_counter_ns() - SLOW_TURN_AGE_NS
        if any(e[0] < cutoff for e in self._slow):
            self._slow = [e for e in self._slow if e[0] >= cutoff]
            self._slow_floor = -1
            self._slow_changed = True

    def _turn_done(self, turn: _Turn, did: bool) -> None:
        """A pass of the loop has ended: its times go into the counters.
        Every list is written anew and put into ``stats_counters`` in the
        ONE ``update`` below, with the sums: no other thread's copy
        (``llm_stats()``) falls between two of them or sees a list change
        under it. An ordinary turn costs the clock reads of its phases,
        three short lists and one comparison with the shortest kept turn."""
        total = time.perf_counter_ns() - turn.t0
        ns = turn.ns
        ns[-1] = total - sum(ns)    # ``other``
        c = self.stats_counters
        new = {"turn_ns": c["turn_ns"] + total,
               "turn_phase_ns": [a + b for a, b in
                                 zip(c["turn_phase_ns"], ns)]}
        if did:
            new["turns"] = c["turns"] + 1
            key = "turn_hist_plain" if turn.plain else "turn_hist_prefill"
            b = min((total // 1_000_000).bit_length(), _TURN_TOP)
            for k, add in ((key, 1), (key + "_ns", total)):
                new[k] = hist = list(c[k])
                hist[b] += add
            if self._slow_last is not None:
                # the turn before this one was kept: this turn's sync
                # says whether the device had stood still with it
                (last, warn), self._slow_last = self._slow_last, None
                last[_NEXT_SYNC_AT] = ns[_SYNC_AT]
                self._slow_changed = True
                if warn:
                    self._log_stall(last)
            if total > self._slow_floor:
                self._keep_slow(turn, total)
        if self._slow_changed:
            self._slow_changed = False
            new["slow_turns"] = [x for e in self._slow for x in e]
        c.update(new)

    def _keep_slow(self, turn: _Turn, total: int) -> None:
        """The turn is among the longest the list holds (the reap forgets
        those of more than a minute ago): keep it, with the allocator's
        numbers as they stand at its end."""
        mem = self._memory()
        entry = [turn.t0, total, *turn.ns, turn.chunks, turn.admitted,
                 turn.rows, turn.steps_read, turn.firsts_read,
                 len(self._outstanding), -1, *mem,
                 *(a - b for a, b in zip(mem, self._mem_at_reap))]
        slow = self._slow
        if len(slow) == SLOW_TURNS:
            slow.remove(min(slow, key=lambda e: e[1]))
        slow.append(entry)
        self._slow_floor = min(e[1] for e in slow) \
            if len(slow) == SLOW_TURNS else -1
        self._slow_changed = True
        self._slow_last = (entry,
                           turn.plain and total >= SLOW_TURN_WARN_NS)

    def _log_stall(self, kept: List[int]) -> None:
        """One line for a plain turn of SLOW_TURN_WARN_NS or more: a
        decode step with no prefill in front of it took that long to come
        to the host. A next sync as long as any plain turn's says the
        device itself stood still, one near nothing that the step after it
        ran on time and only this one's hand-over was late."""
        t = dict(zip(SLOW_TURN_FIELDS, kept))
        logger.warning(
            "llm engine loop stalled: a plain turn of %.1f ms, "
            "llm.step.sync %.1f ms of it, the next turn's %.1f ms; "
            "%d rows, %d outstanding", t["turn_ns"] * 1e-6,
            t["llm.step.sync"] * 1e-6, t["next_sync_ns"] * 1e-6,
            t["rows"], t["outstanding"])

    def _flush_wakes(self, enqueued: bool) -> None:
        """Set the event of every stream the fan-out just made put off
        (loop thread only): its own, or ONCE its poller's, however many
        of the poller's streams are owed, and only where a token still
        waits (a call woken since for a first token or an ended
        neighbour took them all: only this thread appends, so what reads
        empty here stays empty). ``enqueued``: the device holds a program
        behind the step that was fanned out, which is what the wake-ups
        were put off for."""
        wakes = self._wakes
        if not wakes:
            return
        self._wakes = []
        waited = time.perf_counter_ns() - self._fanout_ns
        waiting = 0
        pollers = set()
        for st in wakes:
            # still pending after the clock was read: its drain comes
            # later than this, so its lag holds at least ``waited``
            if st.pending:
                waiting += 1
                if st.poller is not None:
                    pollers.add(st.poller)
            if st.poller is None:
                st.event.set()
        for p in pollers:
            p.event.set()
        # The loop's thread is these keys' only writer, and writes them
        # in ONE call that no other thread's copy can fall into.
        c = self.stats_counters
        c.update(
            wakes_deferred=c["wakes_deferred"] + len(wakes),
            wakes_after_dispatch=c["wakes_after_dispatch"]
            + (len(wakes) if enqueued else 0),
            wake_defer_ns=c["wake_defer_ns"] + waiting * waited)

    def _push_queued_locked(self, req: _Request):
        """Heap key = (deadline, seq): admission prefers deadline slack
        — tightest budget first, FIFO among the unbounded. seq is
        unique, so _Request itself is never compared."""
        dl = req.deadline_ts if req.deadline_ts is not None \
            else float("inf")
        heapq.heappush(self._queue, (dl, req.seq, req))
        self._n_queued += 1

    def _shed_expired_locked(self, now: float):
        """Typed-shed the expired HEAD of the queue (caller holds the
        lock). The heap is deadline-ordered, so expired entries are a
        prefix — this is O(expired), not O(queue), and it runs every
        iteration so a dead budget sheds at the next step boundary even
        when no slot ever frees (a saturated engine must not hold a
        dead request's poller hostage)."""
        while self._queue:
            dl, _, req = self._queue[0]
            if req.stream.done:
                heapq.heappop(self._queue)  # cancelled: drop lazily
                continue
            if dl == float("inf") or now <= dl:
                break
            heapq.heappop(self._queue)
            self._n_queued -= 1
            self._finish_locked(req, shed="decode")

    def _admit_once(self) -> bool:
        # The loop's phases are device_spans (profiler annotations on
        # the device's clock; PERF.md lists the names, which readers of
        # a captured profile rely on), each with the turn's own clock at
        # its edges (``_phase``).
        with self._phase("llm.admit") as ds, self._lock:
            now = time.time()
            self._shed_expired_locked(now)
            free = [i for i in range(self.max_batch)
                    if self._slot_req[i] is None]
            ds.set_metadata(queued=self._n_queued, free=len(free))
            if not free or not self._n_queued:
                return False
            take = min(len(free), self.prefill_rows)
            batch: List[_Request] = []
            while self._queue and len(batch) < take:
                _, _, req = heapq.heappop(self._queue)
                if req.stream.done:
                    continue  # cancelled in queue: already accounted
                self._n_queued -= 1
                if req.deadline_ts is not None \
                        and now > req.deadline_ts:
                    # The budget died waiting for a slot: typed shed,
                    # reason=decode (the engine owns the budget once
                    # the router handed the request over).
                    self._finish_locked(req, shed="decode")
                    continue
                batch.append(req)
            if not batch:
                # Expired/cancelled entries were drained — progress.
                return True
            slots = free[:len(batch)]  # slot-guard: _push_queued_locked,_finish_locked
            for req in batch:
                # Admission: queue phase ends, prefill phase starts
                # (the span covers the prefill compute below).
                self._phase_span_locked(req, "llm.prefill")
        try:
            failpoints.hit("serve.llm.before_admit")
            self._prefill_batch(batch, slots)
        except BaseException as e:  # noqa: BLE001 — requeue, bounded
            self._log_first_failure("prefill")
            with self._lock:
                for req in batch:
                    req.retries += 1
                    if req.retries > 3:
                        self._finish_locked(req, error=repr(e))
                    else:
                        # Back to the queue: the failed prefill span
                        # closes errored and a fresh queue span opens —
                        # an open span must never re-enter the heap.
                        self._phase_span_locked(
                            req, "llm.queue",
                            status="ERROR: prefill_retry")
                        self._push_queued_locked(req)
        return True

    def _prefill_batch(self, batch: List[_Request], slots: List[int]):  # jax-hot-path
        """Every admitted request's prompt, whole, behind whatever the
        device already has: ``ceil(len / prefill_chunk)`` executions of
        the one chunk program each, dispatched back to back (the device
        runs them in order on the cache each hands the next). Nothing is
        read here: each request's last chunk's token stays on the device,
        where the next step takes it from (``_fresh``), and comes to the
        host in the next turn's sync (``_Firsts``). From here on the
        requests hold their slots."""
        np = self._np
        chunk = self.prefill_chunk
        t0 = time.perf_counter()
        with self._phase("llm.prefill.dispatch") as ds:
            rows, lengths, n_chunks = [], [], 0
            for req, slot in zip(batch, slots):
                # truncate to the longest prompt the slot's rows hold
                prompt = req.prompt[-self.max_prompt_len:]
                lengths.append(len(prompt))
                for at in range(0, len(prompt), chunk):
                    piece = prompt[at:at + chunk]
                    packed = np.zeros(
                        (1, chunk + 3 + self._drafting), np.int32)
                    packed[0, :len(piece)] = piece
                    packed[0, chunk:chunk + 3] = slot, at, len(piece)
                    if self._drafting:
                        packed[0, chunk + 3] = prompt[at + chunk] \
                            if at + chunk < len(prompt) else -1
                    tok, self._cache = self._prefill_fn(
                        self.params, self._cache, packed)
                    n_chunks += 1
                rows.append((slot, req, tok))  # the last chunk's
            # What the programs have counted in the cache up to here (a
            # sparse model's token-expert pairs; most families: none),
            # copied out while the cache is this turn's: the next step
            # donates it, and a leaf of the cache it hands back would make
            # the read wait for that step.
            counted = self._counted_fn(self._cache["counted"]) \
                if self._counted_keys else None
            tokens_real = sum(lengths)
            ds.set_metadata(rows=len(batch), tokens_real=tokens_real,
                            chunks=n_chunks)
        self._turn.chunks += n_chunks
        self._turn.admitted += len(batch)
        self._init_s.setdefault("first_prefill", time.perf_counter() - t0)
        with self._lock:
            c = self.stats_counters
            c["prefill_batches"] += 1
            c["prefill_rows_real"] += len(batch)
            c["prefill_tokens_real"] += tokens_real
            c["prefill_chunks"] += n_chunks
            c["prefill_tokens_lane"] += n_chunks * chunk
            for (slot, req, tok), length in zip(rows, lengths):
                self._slot_req[slot] = req
                self._pos[slot] = length
                self._fresh[slot] = tok
                req.unread += 1
        self._outstanding.append(_Firsts(rows, counted))

    def _first_fanout(self, firsts: _Firsts, toks: list, counted) -> int:
        """An admission turn's first tokens to their streams (read in the
        turn after the one that dispatched their chunks), and what the
        chunk programs counted; returns the tokens handed out. A request
        that was cancelled or shed meanwhile no longer holds its slot: its
        token is dropped."""
        now = time.time()
        delivered = 0
        with self._lock:
            visible_ns = time.perf_counter_ns()
            c = self.stats_counters
            for key, n in zip(self._counted_keys,
                              () if counted is None else counted):
                n = int(n)
                c[key] = c.get(key, 0) + (
                    n - self._counted_seen.get(key, 0)) % 2 ** 32
                self._counted_seen[key] = n
            for (slot, req, _), first in zip(firsts.rows, toks):
                req.unread -= 1
                if self._slot_req[slot] is not req:
                    continue
                tok = int(first[0])
                delivered += 1
                req.remaining -= 1
                c["admitted"] += 1
                c["tokens_out"] += 1
                req.stream.n_tokens += 1
                req.stream.visible_ns = visible_ns  # its first chunk
                req.stream.pending.append([tok])
                req.stream.event.set()
                # TTFT: submit -> first token available for delivery.
                _obs.record_ttft(self._dep, max(0.0, now - req.submitted))
                # First token exists: prefill phase ends HERE (the TTFT
                # decomposition keys on the prefill span's end), decode
                # phase runs until the terminal transition.
                self._phase_span_locked(req, "llm.decode")
                if req.remaining <= 0 or tok == self.eos_token:
                    self._finish_locked(req, done=True, slot=slot)
            self._last_tokens_at = now
        _obs.record_decode_tokens(self._dep, delivered)
        return delivered

    def _log_first_failure(self, what: str) -> None:
        """Call from an ``except`` block: logs the active traceback the
        first time the engine's device path fails."""
        if not self._failure_logged:
            self._failure_logged = True
            logger.exception("llm engine %s failed (first failure; later "
                             "ones are only counted)", what)

    def _step_once(self, settle: bool = False) -> bool:  # jax-hot-path  # step-timed
        """One turn's select, dispatch, sync and fanout (``_loop`` has the
        order and why). ``settle``: dispatch nothing, read everything (the
        loop's last act)."""
        np = self._np
        outstanding = self._outstanding
        with self._phase("llm.step.select") as ds, self._lock:
            now = time.time()
            # Deadline eviction happens at the step boundary: the slot
            # frees NOW, before the next step is enqueued, and the shed
            # is typed.
            for slot in range(self.max_batch):
                req = self._slot_req[slot]
                if req is not None and req.deadline_ts is not None \
                        and now > req.deadline_ts:
                    self._finish_locked(req, shed="decode", slot=slot)
            # owed a token after all that is dispatched and unread for it
            rows = [] if settle else [
                (slot, req) for slot, req in enumerate(self._slot_req)
                if req is not None and req.remaining > req.unread]
            ds.set_metadata(occupancy=len(rows))
            if not rows and not outstanding:
                return False
            turn = self._turn
            turn.rows = len(rows)
            # The rows of the unread step that are still their request's
            # stand one step past the host's position (a slot that changed
            # hands since stands where its new holder's prompt ends).
            unread = next((d for d in outstanding
                           if isinstance(d, _Step)), None)
            ahead = np.zeros(self.max_batch + 1, np.int32)
            for slot, req in unread.rows if unread else ():
                if self._slot_req[slot] is req:
                    ahead[slot] = 1
            # Per-turn span: ONE per engine step (not one per traced
            # request per step — that would square the span volume),
            # parented under the oldest traced request's decode span so
            # it lands inside a real trace. It runs from the turn's
            # enqueue to the fan-out of the step the turn READ, and is
            # recorded where the turn read one.
            step_parent = None
            for _, req in (*rows, *(unread.rows if unread else ())):
                if req.span is not None and (
                        step_parent is None
                        or req.submitted < step_parent[0]):
                    step_parent = (req.submitted, req.span)
        step_span = tracing.start_span(
            "llm.step", {"occupancy": len(rows)},
            parent={"trace_id": step_parent[1]["trace_id"],
                    "span_id": step_parent[1]["span_id"]},
            cat="llm") if step_parent is not None else None
        t0 = time.perf_counter()
        failed = None
        if rows:
            try:
                # The failpoint lives INSIDE the error-counted region: a
                # raise-armed before_step must trip the 3-strike fail-fast
                # (streams error out), not silently skip every step while
                # the site stays armed — that would be the hang the
                # never-hang contract forbids.
                failpoints.hit("serve.llm.before_step")
                # epoch_ns ties this thread's clock (time.time_ns, the span
                # store's) to the profile's: a reader takes the offset as
                # the median over these anchors.
                with self._phase("llm.step.dispatch",
                                 epoch_ns=time.time_ns()):
                    self._dispatch(rows, ahead, unread is not None)
            except BaseException as e:  # noqa: BLE001 — re-raised below
                # the step already on the device keeps its tokens: this
                # turn reads and delivers all that is outstanding
                failed = e
                self._log_first_failure("decode step")
        # What this turn reads: all that was enqueued before its own step,
        # and that step too where nothing may stay behind (no step follows
        # for a while).
        keep = 1 if rows and failed is None and not self.step_throttle_s \
            else 0
        due = outstanding[:len(outstanding) - keep]
        try:
            with self._phase("llm.step.sync"):
                # The intentional syncs of a turn (tokens fan out to
                # streams from host memory): the device ended these about
                # a step ago.
                read = []
                for d in due:
                    try:
                        read.append(self._sync(d))
                    except BaseException as e:  # noqa: BLE001 — its rows fail
                        read.append(e)
                        if failed is None:
                            failed = e
                            self._log_first_failure("decode step")
            step_s = time.perf_counter() - t0
            if rows and failed is None:
                self._init_s.setdefault("first_step", step_s)
            with self._phase("llm.step.fanout") as ds:
                tokens = 0
                for d, host in zip(due, read):
                    if isinstance(host, BaseException):
                        self._lost_fanout(d, host)
                    elif isinstance(d, _Step):
                        turn.steps_read += 1
                        tokens += self._step_fanout(d, host, step_s,
                                                    step_span)
                        step_span = None  # recorded with its tokens
                    else:
                        turn.firsts_read += len(d.rows)
                        tokens += self._first_fanout(d, *host)
                ds.set_metadata(tokens=tokens)
                self._flush_wakes(enqueued=keep > 0)
        finally:
            # outstanding until handed out (``llm_stats()`` says so)
            del outstanding[:len(due)]
        if failed is not None:
            tracing.finish_span(step_span, "ERROR: step")
            self._step_errors_row += 1
            self.stats_counters["errors"] += 1
            if self._step_errors_row >= _MAX_STEP_ERRORS:
                with self._lock:
                    for slot in range(self.max_batch):
                        req = self._slot_req[slot]
                        if req is not None:
                            self._finish_locked(
                                req, error="decode step failing "
                                f"repeatedly: {failed!r}", slot=slot)
                self._step_errors_row = 0
            raise failed
        self._step_errors_row = 0
        if self.step_throttle_s and not settle:
            time.sleep(self.step_throttle_s)
        return True

    def _dispatch(self, rows: List[tuple], ahead, behind: bool) -> None:  # jax-hot-path
        """Enqueue a decode step for ``rows``. Every live slot is handed
        exactly the token and position a loop that read each step before
        the next would hand it; what a free slot or the spare slot is
        handed (a stale token, position 0) no one reads. ``behind``: an
        unread step lies before this one."""
        # A COPY of the positions: the runtime may read a host array it
        # was handed only when the program runs, behind the unread step,
        # and this turn's fan-out moves ``_pos`` before that.
        pos = self._pos.copy()
        if self._out is None:
            tokens, pos = self._no_tokens, self._jnp.asarray(pos)
        else:
            tokens, pos = self._carry_fn(self._out, pos, ahead)
        # in program order after each admitted slot's last chunk
        for slot, tok in self._fresh.items():
            tokens = self._put_fn(tokens, tok, self._np.int32(slot))
        out, self._cache = self._step_fn(self.params, self._cache,
                                         tokens, pos)
        self._fresh.clear()
        self._out = out
        for _, req in rows:
            req.unread += 1
        self._outstanding.append(_Step(out, rows, behind))

    def _sync(self, d):
        """Bring a dispatched result to the host (the turn's sync)."""
        np = self._np
        if isinstance(d, _Step):
            return np.asarray(d.out)  # analyze: ignore[JX002]
        return ([np.asarray(tok) for _, _, tok in d.rows],  # analyze: ignore[JX002]
                None if d.counted is None else np.asarray(d.counted))  # analyze: ignore[JX002]

    def _lost_fanout(self, d, error: BaseException) -> None:
        """A result the device could not hand over: its tokens are lost,
        so the requests it was dispatched for end with the error (a stream
        that went on would lack a token)."""
        with self._lock:
            for slot, req, *_ in d.rows:
                req.unread -= 1
                if self._slot_req[slot] is req:
                    self._finish_locked(
                        req, error=f"decode step lost: {error!r}",
                        slot=slot)

    def _step_fanout(self, step: _Step, nxt, step_s: float,
                     step_span: Optional[dict]) -> int:
        """After the sync: the step's tokens to the streams of the
        requests it was dispatched FOR, under the lock, then the step's
        metrics and store span (inside the ``llm.step.fanout`` device
        span); returns the tokens handed out. A slot whose request ended, was cancelled or was shed
        since the dispatch (it may hold a NEW request by now) has its row
        dropped. A token is visible to every drain from its append here.
        A stream that ends with it is woken here (``_finish_locked``); one
        that goes on decoding is put on ``_wakes`` and woken at the tail
        of this fan-out (``_step_once``).

        A drafting family's step yields ONE chunk of one or two tokens a
        slot (``served [S, 4]`` at the head of ``nxt``): the position moves
        by what the device accepted, the chunk is cut where ``max_tokens``
        or the end token falls inside a pair."""
        drafting = self._drafting
        with self._lock:
            produced = chunks = drafted = accepted = dropped = 0
            # the one clock read of this fan-out's tokens
            self._fanout_ns = visible_ns = time.perf_counter_ns()
            for slot, req in step.rows:
                req.unread -= 1
                if self._slot_req[slot] is not req:
                    dropped += 1
                    continue
                if drafting:
                    n = int(nxt[4 * slot])
                    toks = [int(t) for t in
                            nxt[4 * slot + 1:4 * slot + 1 + n]]
                    drafted += 1
                    accepted += n - 1
                else:
                    toks = [int(nxt[slot])]
                was = int(self._pos[slot])
                self._pos[slot] = now = was + len(toks)
                # a wrap is the position CROSSING a multiple of the ring
                if now // self.cache_len != was // self.cache_len:
                    self.stats_counters["ring_wraps"] += 1
                if drafting:
                    toks = toks[:req.remaining]
                    if self.eos_token in toks:
                        toks = toks[:toks.index(self.eos_token) + 1]
                req.remaining -= len(toks)
                produced += len(toks)
                chunks += 1
                req.stream.n_tokens += len(toks)
                if not req.stream.pending:
                    req.stream.visible_ns = visible_ns
                req.stream.pending.append(toks)
                if req.remaining <= 0 or toks[-1] == self.eos_token:
                    self._finish_locked(req, done=True, slot=slot)
                else:
                    self._wakes.append(req.stream)
            c = self.stats_counters
            counters_at = (self.max_batch + 1) * (4 if drafting else 1)
            for i, key in enumerate(self._step_counters):
                c[key] = c.get(key, 0) + int(nxt[counters_at + i])
            # the loop's thread is these keys' only writer: ONE call
            c.update(
                steps=c["steps"] + 1,
                steps_ahead=c["steps_ahead"] + step.ahead,
                rows_dropped=c["rows_dropped"] + dropped,
                tokens_out=c["tokens_out"] + produced,
                occupancy_sum=c["occupancy_sum"] + chunks,
                draft_proposed=c["draft_proposed"] + drafted,
                draft_accepted=c["draft_accepted"] + accepted)
            # ITL (TPOT): delivery-to-delivery gap. All slots advance
            # in lockstep, so every CHUNK this step produced arrived
            # the same gap after its stream's previous one — one event
            # carries the shared gap plus the chunk count.
            done_at = time.time()
            itl = step_s if self._last_tokens_at is None \
                else max(0.0, done_at - self._last_tokens_at)
            self._last_tokens_at = done_at
        _obs.record_decode_step(self._dep, step_s, chunks, produced)
        # a chunk's FIRST token came the gap after its stream's last one;
        # the second token of an accepted pair came with it, no gap apart
        _obs.record_decode_itl(self._dep, itl, chunks)
        _obs.record_decode_itl(self._dep, 0.0, produced - chunks)
        if step_span is not None:
            step_span["attributes"]["tokens"] = produced
            if drafting:
                step_span["attributes"].update(
                    drafted=drafted, accepted=accepted)
            tracing.finish_span(step_span)
        return produced

    def _phase_span_locked(self, req: _Request, name: Optional[str],
                           status: str = "OK") -> None:
        """Close the request's open phase span and (when ``name``) open
        the next one — at most one open span per request, every
        transition closes before it opens (caller holds the lock).
        No-op end to end for untraced requests."""
        if req.span is not None:
            tracing.finish_span(req.span, status)
            req.span = None
        if name is not None and req.trace_ctx:
            req.span = tracing.start_span(
                name, {"rid": req.rid, "deployment": self._dep},
                parent=req.trace_ctx, cat="llm")

    def _finish_locked(self, req: _Request, done: bool = False,
                       shed: Optional[str] = None,
                       error: Optional[str] = None,
                       slot: Optional[int] = None):
        """Terminal transition (caller holds the lock): free the slot,
        mark the stream, wake pollers, count the outcome."""
        if slot is not None and self._slot_req[slot] is req:
            self._slot_req[slot] = None
            # A free slot goes on through the step: at position 0 its
            # attention reads one block of its ring and not the dead
            # context (what it computes, and the row it writes at 0, no
            # one reads; the next prefill writes the slot's rows from 0).
            self._pos[slot] = 0
        st = req.stream
        if st.done:
            return
        st.done = True
        st.shed = shed
        st.error = error
        st.event.set()
        if shed is not None:
            self.stats_counters["shed"] += 1
            _obs.record_shed(self._dep, shed)
        elif error is not None:
            self.stats_counters["errors"] += 1
        else:
            self.stats_counters["completed"] += 1
        # Every terminal path funnels here, so this is THE place the
        # request's open phase span closes — queued (shed/cancel),
        # decoding (done/shed/error), step-failure fan-out alike.
        self._phase_span_locked(
            req, None,
            status="OK" if done and not shed and not error
            else f"ERROR: {shed or error or 'aborted'}")

    def _reap_streams(self):
        self._last_reap = time.monotonic()
        cutoff = time.perf_counter_ns() - int(_STREAM_TTL_S * 1e9)
        with self._lock:
            # Fully-delivered streams leave the table at delivery
            # (_drain_locked); only DONE streams nobody polls linger.
            for rid, st in [(r, s) for r, s in self._streams.items()
                            if s.done and s.last_poll < cutoff]:
                self._forget_stream_locked(rid, st)

    # -- request surface (called through Replica.handle_request) ----------

    def _normalize(self, prompt, max_new_tokens):
        if isinstance(prompt, dict):
            max_new_tokens = prompt.get("max_tokens", max_new_tokens)
            prompt = prompt.get("tokens")
        from ray_tpu.core.object_ref import ObjectRef

        if isinstance(prompt, ObjectRef):
            # The shm handoff lane: the proxy put the prompt payload in
            # the object store and handed us the ref — the fetch is a
            # same-node shared-memory read, not a copy over the wire.
            import ray_tpu

            prompt = ray_tpu.get(prompt, timeout=30.0)
        if not prompt or not all(isinstance(t, int) for t in prompt):
            raise ValueError("prompt must be a non-empty list of token "
                             "ids (or {'tokens': [...]})")
        if max_new_tokens is None:
            max_new_tokens = self.max_new_tokens
        return list(prompt), max(1, min(int(max_new_tokens),
                                        self.max_new_cap))

    def llm_submit(self, prompt, max_new_tokens=None,
                   deadline_ts: Optional[float] = None,
                   poller: Optional[str] = None) -> str:
        """Admit a request into the engine queue; returns the stream id.
        A full queue sheds typed (reason=decode) instead of erroring —
        admission under a full BATCH merely queues. ``poller``: the
        stream belongs to that poller, and ``llm_poll(poller=...)``
        delivers it, a call that is blocked at this moment included."""
        prompt, max_new = self._normalize(prompt, max_new_tokens)
        # The caller's span context rides the serve request scope (set
        # by Replica.handle_request); read on THIS thread, before the
        # request crosses to the engine loop's.
        trace_ctx = (_obs.current_request() or {}).get("trace_ctx")
        with self._lock:
            if self._stop:
                # the loop has ended: nothing would ever serve it
                raise RuntimeError("llm engine is stopped")
            if self._n_queued >= self.max_queue:
                _obs.record_shed(self._dep, "decode")
                self.stats_counters["shed"] += 1
                raise RequestShedError(
                    f"llm engine queue full ({self.max_queue})",
                    reason="decode")
            self._seq += 1
            rid = f"llm-{os.getpid():x}-{self._seq:x}"
            req = _Request(rid, prompt, max_new, deadline_ts, self._seq,
                           trace_ctx=trace_ctx)
            self._push_queued_locked(req)
            self._phase_span_locked(req, "llm.queue")
            self._streams[rid] = st = req.stream
            if poller is not None:
                st.poller = p = self._poller_locked(poller)
                st.event = p.event
                p.streams[rid] = st
        self._wake.set()
        return rid

    def llm_submit_many(self, requests: List[dict]) -> List[str]:
        """Batched submit (the 10k-stream bench lane): each entry is
        {"tokens": [...], "max_tokens": n, "deadline_ts": ts|None}."""
        return [self.llm_submit(r.get("tokens"), r.get("max_tokens"),
                                r.get("deadline_ts")) for r in requests]

    def _drain_locked(self, rid: str, st: _Stream, now_ns: int) -> dict:
        """Hand out what is pending (caller holds the lock and read the
        clock, once for all it drains) and count how long it lay there."""
        chunks, st.pending = st.pending, []
        st.last_poll = now_ns
        if chunks:
            n, lag = len(chunks), now_ns - st.visible_ns
            c = self.stats_counters
            c["deliver_lag_ns"] += n * lag
            b = (lag // 250_000).bit_length()
            c["deliver_lag_hist"][b if b < _LAG_TOP else _LAG_TOP] += n
        resp = {"chunks": chunks, "done": st.done, "shed": st.shed,
                "error": st.error}
        if st.done and not st.pending:
            st.delivered = True
            self._forget_stream_locked(rid, st)
        return resp

    def _poller_locked(self, pid: str) -> _Poller:
        p = self._pollers.get(pid)
        if p is None:
            p = self._pollers[pid] = _Poller(pid)
        return p

    def _forget_stream_locked(self, rid: str, st: _Stream) -> None:
        """The stream leaves the engine's tables; a poller whose last
        stream left, and that no call waits on, goes with it."""
        self._streams.pop(rid, None)
        p = st.poller
        if p is not None:
            p.streams.pop(rid, None)
            if not p.streams and not p.waiting:
                self._pollers.pop(p.pid, None)

    def llm_next(self, rid: str, timeout_s: float = 2.0) -> dict:
        """Long-poll one stream: blocks until it is told of a chunk or
        of the terminal transition, up to ``timeout_s``, then drains
        whatever is pending. A decode step's token is pending from the
        step's fan-out on, and its poller is told once the next program
        is enqueued (``_loop``): a poll may therefore return a token it
        was not woken for, and the wake-up that follows it no chunk
        (``done`` false, no error), as after a time-out. ``held_ns`` is
        what this call spent in here up to its drain, the wait included
        (the drain's clock read is the last one: a poll pays two): a
        caller that times the round trip owes the rest to the way here
        and back."""
        t0 = time.perf_counter_ns()
        with self._lock:
            st = self._streams.get(rid)
        if st is None:
            return {"chunks": [], "done": True, "shed": None,
                    "error": f"unknown stream {rid!r}", "held_ns": 0}
        out = self._wait_drain(st.event, timeout_s, t0,
                               lambda: ((rid, st),), batched=False)
        return {**out[rid], "held_ns": out["held_ns"]}

    def _wait_drain(self, event: threading.Event, timeout_s: float,
                    t0: int, streams, batched: bool) -> dict:
        """A long-poll's body, whichever lane: wait on ``event``, then
        the drain (``_drain``) with ``held_ns`` since the call's entry at
        ``t0`` beside the streams' responses, keyed by stream id."""
        event.wait(max(0.0, float(timeout_s)))
        if not tracing.profiling():
            return self._drain(event, t0, streams, batched)[0]
        # While a profile is taken, the poller's work on its clock: from
        # the wait's return on, and not around it (a span over a blocked
        # thread would own every idle gap of the device).
        with tracing.device_span("llm.next.drain") as ds:
            out, chunks = self._drain(event, t0, streams, batched)
            ds.set_metadata(chunks=chunks)
        return out

    def _drain(self, event: threading.Event, t0: int, streams,
               batched: bool) -> tuple:
        """Under ONE acquisition of the lock and one clock read: hand out
        what the streams of ``streams()`` hold, clear the event they were
        told through (all that was pending is taken, and an ended stream
        is gone), count the call (``next_calls``; ``next_empty`` where it
        brought no chunk and no end). Returns
        ``{rid: response, "held_ns": drain's clock - t0}`` and the chunks
        drained."""
        with self._lock:
            now_ns = time.perf_counter_ns()
            out = {rid: self._drain_locked(rid, st, now_ns)
                   for rid, st in streams()}
            event.clear()
            chunks = sum(len(r["chunks"]) for r in out.values())
            c = self.stats_counters
            c["next_calls"] += 1
            c["next_batched"] += batched
            if not chunks and not any(r["done"] for r in out.values()):
                c["next_empty"] += 1
        out["held_ns"] = now_ns - t0
        return out, chunks

    def llm_poll(self, rids: Optional[List[str]] = None,
                 poller: Optional[str] = None,
                 timeout_s: float = 0.0) -> Dict[str, dict]:
        """Batched drain. ``llm_poll(rids)`` takes, without waiting, what
        those streams hold (the bench's collector lane).

        ``llm_poll(poller=<id>, timeout_s=...)`` is the long-poll of a
        client process: it blocks until the poller is told of anything (a
        first token, a chunk whose next step is enqueued, a terminal
        transition of any stream submitted under that id, one submitted
        while this call waits included) or the time-out, then drains
        every stream of the poller that holds a chunk or has ended, and
        returns ``{rid: {"chunks", "done", "shed", "error"}}`` for those
        alone, with ``"held_ns"`` beside them as ``llm_next`` says it.
        One call at a time a poller."""
        if poller is None:
            out = {}
            with self._lock:
                now_ns = time.perf_counter_ns()
                for rid in rids or ():
                    st = self._streams.get(rid)
                    if st is None:
                        out[rid] = {"chunks": [], "done": True,
                                    "shed": None,
                                    "error": f"unknown stream {rid!r}"}
                    else:
                        out[rid] = self._drain_locked(rid, st, now_ns)
            return out
        t0 = time.perf_counter_ns()
        with self._lock:
            p = self._poller_locked(poller)
            p.waiting += 1

        def ready():  # under the lock, at the drain
            p.waiting -= 1
            if not p.streams and not p.waiting:
                self._pollers.pop(p.pid, None)
            return [(rid, st) for rid, st in p.streams.items()
                    if st.pending or st.done]

        return self._wait_drain(p.event, timeout_s, t0, ready, batched=True)

    def open_streams(self) -> int:
        """Streams of pollers that have not ended, less the pollers'
        calls in flight: what ``Replica.get_num_ongoing`` adds to its
        count of calls, so that an open stream reads as one request there
        (as when each held a long-poll of its own) and the one call that
        carries a poller's streams is not counted beside them."""
        with self._lock:
            return sum(
                sum(not st.done for st in p.streams.values()) - p.waiting
                for p in self._pollers.values())

    def llm_cancel(self, rid: str) -> bool:
        """Cancel a stream: a queued request leaves the queue, an
        active one frees its slot at the cancel (what is dispatched and
        unread for it, a step's row or its first token, is dropped when
        it is read, whoever holds the slot by then). The stream
        terminates with a 'cancelled' error; returns whether the request
        was still live. A request holds its slot from the dispatch of its
        prompt's chunks; while the loop's thread is dispatching them it
        is in neither table and the cancel returns False — it completes
        normally and is reaped."""
        with self._lock:
            for slot in range(self.max_batch):
                req = self._slot_req[slot]
                if req is not None and req.rid == rid:
                    self._finish_locked(req, error="cancelled",
                                        slot=slot)
                    return True
            for _, _, req in self._queue:
                if req.rid == rid and not req.stream.done:
                    # The heap entry stays and is dropped lazily at
                    # pop; the live count updates now.
                    self._n_queued -= 1
                    self._finish_locked(req, error="cancelled")
                    return True
        return False

    def generate(self, prompt, max_new_tokens=None,
                 deadline_ts: Optional[float] = None,
                 timeout_s: Optional[float] = None) -> List[int]:
        """Blocking request/response lane: submit, drain own stream,
        return the generated tokens. Sheds raise typed. On timeout the
        orphaned request is CANCELLED (slot freed, queue entry
        dropped) — an abandoned caller must not leave the engine
        decoding tokens nobody reads."""
        rid = self.llm_submit(prompt, max_new_tokens, deadline_ts)
        if timeout_s is None:
            # A caller-supplied deadline bounds the wait (+grace for
            # the final drain); without one, a generous static cap.
            timeout_s = 300.0 if deadline_ts is None else max(
                5.0, deadline_ts - time.time() + 30.0)
        out: List[int] = []
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            resp = self.llm_next(rid, timeout_s=2.0)
            for chunk in resp["chunks"]:
                out.extend(chunk)
            if resp["done"]:
                if resp["shed"]:
                    raise RequestShedError(
                        f"llm request shed: {resp['shed']}",
                        reason=resp["shed"])
                if resp["error"]:
                    raise RuntimeError(resp["error"])
                return out
        self.llm_cancel(rid)
        raise TimeoutError(
            f"llm generate did not finish within {timeout_s:.0f}s "
            f"(request cancelled)")

    def __call__(self, payload) -> dict:
        """HTTP/graph lane: {"tokens": [...], "max_tokens": n} ->
        {"tokens": [generated...]}. The serve request context's
        deadline (handle.options(deadline_s=...) / the deadline header)
        carries into the engine, so the blocking lane gets the same
        mid-decode shed semantics as the streaming lane."""
        ctx = _obs.current_request() or {}
        return {"tokens": self.generate(
            payload, deadline_ts=ctx.get("deadline_ts"))}

    def llm_stats(self) -> dict:
        with self._lock:
            active = sum(1 for r in self._slot_req if r is not None)
            queued = self._n_queued
            c = dict(self.stats_counters)
            # written in place by the drains; the loop's lists (turn_*,
            # slow_turns) are put there whole and never written again
            c["deliver_lag_hist"] = list(c["deliver_lag_hist"])
        # chunks drained by either lane: every one is in a bucket
        c["deliver_chunks"] = sum(c["deliver_lag_hist"])
        steps = c["steps"]
        return {
            "model": self.model,
            "max_batch": self.max_batch,
            "cache_len": self.cache_len,
            "max_prompt_len": self.max_prompt_len,
            "prefill_rows": self.prefill_rows,
            "prefill_chunk": self.prefill_chunk,
            # what the chunk's rule read (models/prefill.chunk_len): the
            # parameters stored and those one token multiplies with
            "params_stored": self._rule_params[0],
            "params_a_token": self._rule_params[1],
            "active": active,
            "queued": queued,
            # results dispatched and not read (a step, admission turns)
            "outstanding": len(self._outstanding),
            "compiles": dict(self._compiles),
            "init_s": dict(self._init_s),
            "param_bytes": dict(self._param_bytes),
            "mean_occupancy": round(c["occupancy_sum"] / steps, 3)
            if steps else 0.0,
            **self._model_stats,
            **c,
        }

    def set_deployment_name(self, name: str) -> None:
        """Called by the Replica wrapper at construction so the decode
        metric families carry the ACTUAL deployment name — without it,
        an engine deployed under any name but the bind-arg default
        would be invisible to the stats join."""
        if not self._dep_explicit and name:
            self._dep = name

    def check_health(self) -> str:
        return "ok"

    def _fail_unserved(self, error: str) -> None:
        """End every request still queued or in a slot with ``error``:
        once the loop is gone its caller would wait for the kill."""
        with self._lock:
            for slot in range(self.max_batch):
                req = self._slot_req[slot]
                if req is not None:
                    self._finish_locked(req, error=error, slot=slot)
            for _, _, req in self._queue:
                if not req.stream.done:
                    self._n_queued -= 1
                    self._finish_locked(req, error=error)

    def shutdown_engine(self) -> bool:
        """Stop the loop and wait for its thread (it reads and delivers
        what it still has on the device, then ends); ``serve.shutdown()``
        calls this through the replica. True once the thread has ended."""
        with self._lock:  # ``llm_submit`` reads it under the same lock
            self._stop = True
        self._wake.set()
        self._loop_thread.join(timeout=30.0)
        if not self._loop_thread.is_alive():
            self._fail_unserved("engine stopped")
        _metrics.retract_loop_series(["llm.engine"])
        return not self._loop_thread.is_alive()
