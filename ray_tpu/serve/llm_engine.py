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
                 "remaining", "retries", "stream", "seq", "trace_ctx",
                 "span")

    def __init__(self, rid: str, prompt: List[int], max_new: int,
                 deadline_ts: Optional[float], seq: int,
                 trace_ctx: Optional[dict] = None):
        self.rid = rid
        self.prompt = prompt
        self.max_new = max_new
        self.deadline_ts = deadline_ts
        self.submitted = time.time()
        self.remaining = max_new
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
    raise ValueError(
        f"unknown model family {model!r} (want gpt2|llama|nemotron_h|"
        f"granite_hybrid|deepseek_v2|falcon_h1|qwen3_next|smallthinker|"
        f"exaone_moe)")


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
        # What a family's programs count in the cache itself (the leaves
        # of ``cache["counted"]``, int32 scalars that never stop rising
        # and so wrap): the last value read of each (_prefill_batch).
        self._counted_seen: Dict[str, int] = {}
        # What the model says of itself beside its counters (llm_stats).
        self._model_stats = dict(
            getattr(cfg, "serving_stats", lambda: {})())

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

        # What a step is handed of each slot: its newest token and, where
        # the family drafts, its draft for the position after, side by side
        # ([S, 2]: ``_tokens`` and ``_draft`` are its columns).
        self._step_in = np.zeros(
            (self.max_batch + 1, 2) if self._drafting
            else self.max_batch + 1, np.int32)
        self._tokens = self._step_in[:, 0] if self._drafting \
            else self._step_in
        self._draft = self._step_in[:, 1] if self._drafting else None
        self._pos = np.zeros(self.max_batch + 1, np.int32)
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
        # returns, but the poller of a stream that goes on decoding is
        # told only once the device has its next program: every woken
        # long-poll wants the lock and the interpreter, and ahead of the
        # loop's own dispatch they kept the device waiting for it. The
        # streams owed a wake-up wait here; only the loop's thread
        # touches the list (``_flush_wakes``). Why no order of set, drain
        # and clear loses a token or delivers one twice: the token is in
        # ``pending`` under the lock BEFORE its event can be set, and a
        # long-poll (``_drain``) drains and clears under the same lock;
        # so a set that comes late finds either the token still pending
        # (the woken poll takes it) or already drained by a poll that
        # timed out or was woken for a neighbour token (the woken poll
        # returns no chunk, as a time-out does, and its caller goes round
        # again). A poller's call drains ALL its streams, so it is told
        # of a put-off token only where one is still pending.
        self._wakes: List[_Stream] = []
        # when the fan-out that filled ``_wakes`` made its tokens visible
        self._fanout_ns = 0
        self._last_reap = time.monotonic()
        self.stats_counters = {
            "steps": 0, "admitted": 0, "completed": 0, "shed": 0,
            "errors": 0, "tokens_out": 0, "queue_peak": 0,
            "occupancy_sum": 0, "ring_wraps": 0,
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
            # Wake-ups of decode steps put off until after the next
            # enqueue (a stream a step that goes on decoding), and how
            # many of them were then set with a program on the device;
            # the rest were set because nothing followed (a step that
            # raised before its enqueue, a throttle, the idle wait).
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
        }
        self._loop_thread = threading.Thread(
            target=self._loop, daemon=True, name="llm-engine-loop")
        self._loop_thread.start()

    # -- scheduler loop ----------------------------------------------------

    def _loop(self):  # jax-hot-path
        """The scheduler: admit, step, go round. A token is VISIBLE the
        moment ``_step_fanout`` (or ``_prefill_batch``) appends it to its
        stream under the lock: every drain (``llm_next``, ``llm_poll``)
        sees it from then on. Its poller is TOLD (the stream's event set,
        which is its poller's where it was submitted under one: one
        blocked call for all of a client process's streams) at once for a
        first token and a terminal transition,
        and for a decode step's token only after the next enqueue: once
        the next step's ``_step_fn`` call has returned or an admission
        turn's first chunk is dispatched, whichever comes first; and
        where no program follows: when a step raises before its enqueue,
        before ``step_throttle_s`` sleeps, before the idle wait. A turn
        whose admission raised goes on to its step, and a request the
        stop, a cancel or a shed ends is woken by its terminal
        transition, so a token's wake-up never waits longer than one
        turn's host work."""
        while not self._stop:
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
                with tracing.device_span("llm.loop.reap"):
                    self._reap_streams()
            if not did:
                # an idle engine owes nobody (every stream still on the
                # list has ended, which woke it: this empties the list)
                self._flush_wakes(enqueued=False)
                with tracing.device_span("llm.loop.wait"):
                    self._wake.wait(0.02)
                self._wake.clear()

    def _flush_wakes(self, enqueued: bool) -> None:
        """Set the event of every stream the last decode step put off
        (loop thread only): its own, or ONCE its poller's, however many
        of the poller's streams are owed, and only where a token still
        waits (a call woken since for a first token or an ended
        neighbour took them all: only this thread appends, so what reads
        empty here stays empty). ``enqueued``: a program was handed to
        the device since, which is what the wake-ups waited for."""
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
        # a captured profile rely on).
        with tracing.device_span("llm.admit") as ds, self._lock:
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
        """Every admitted request's prompt, whole, before the next decode
        step: ``ceil(len / prefill_chunk)`` executions of the one chunk
        program each, dispatched back to back (the device runs them in
        order on the cache each hands the next), then one sync on each
        request's last chunk's token, in admission order."""
        np = self._np
        chunk = self.prefill_chunk
        t0 = time.perf_counter()
        with tracing.device_span("llm.prefill.dispatch") as ds:
            first, lengths, n_chunks = [], [], 0
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
                    # the device has work: tell the last step's streams
                    # (after the turn's FIRST chunk; a no-op from then on)
                    self._flush_wakes(enqueued=True)
                first.append(tok)  # the last chunk's
            tokens_real = sum(lengths)
            ds.set_metadata(rows=len(batch), tokens_real=tokens_real,
                            chunks=n_chunks)
        with tracing.device_span("llm.prefill.sync"):
            # The one intentional sync per request: first tokens must
            # reach the streams now.  # analyze: ignore[JX002]
            first = [np.asarray(tok) for tok in first]  # analyze: ignore[JX002]
            # what the programs have counted in the cache up to here
            # (a sparse model's token-expert pairs; most families: none)
            counted = {key: int(np.asarray(n)) for key, n in  # analyze: ignore[JX002]
                       self._cache.get("counted", {}).items()}
        self._init_s.setdefault("first_prefill", time.perf_counter() - t0)
        now = time.time()
        with tracing.device_span("llm.prefill.fanout"):
            _obs.record_decode_tokens(self._dep, len(batch))
            with self._lock:
                visible_ns = time.perf_counter_ns()
                c = self.stats_counters
                c["prefill_batches"] += 1
                c["prefill_rows_real"] += len(batch)
                c["prefill_tokens_real"] += tokens_real
                c["prefill_chunks"] += n_chunks
                c["prefill_tokens_lane"] += n_chunks * chunk
                for key, n in counted.items():
                    c[key] = c.get(key, 0) + (
                        n - self._counted_seen.get(key, 0)) % 2 ** 32
                self._counted_seen = counted
                for i, req in enumerate(batch):
                    slot = slots[i]
                    tok = int(first[i][0])
                    self._tokens[slot] = tok
                    if self._drafting:
                        self._draft[slot] = int(first[i][1])
                    self._pos[slot] = lengths[i]
                    self._slot_req[slot] = req
                    req.remaining = req.max_new - 1
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

    def _log_first_failure(self, what: str) -> None:
        """Call from an ``except`` block: logs the active traceback the
        first time the engine's device path fails."""
        if not self._failure_logged:
            self._failure_logged = True
            logger.exception("llm engine %s failed (first failure; later "
                             "ones are only counted)", what)

    def _step_once(self) -> bool:  # jax-hot-path  # step-timed
        np = self._np
        with tracing.device_span("llm.step.select") as ds, self._lock:
            now = time.time()
            # Deadline eviction happens at the step boundary: the slot
            # frees NOW, before compute, and the shed is typed.
            for slot in range(self.max_batch):
                req = self._slot_req[slot]
                if req is not None and req.deadline_ts is not None \
                        and now > req.deadline_ts:
                    self._finish_locked(req, shed="decode", slot=slot)
            active = [i for i in range(self.max_batch)
                      if self._slot_req[i] is not None]
            ds.set_metadata(occupancy=len(active))
            if not active:
                return False
            # Per-decode-step span: ONE per engine step (not one per
            # traced request per step — that would square the span
            # volume), parented under the oldest traced request's
            # decode span so it lands inside a real trace.
            step_parent = None
            for slot in active:
                req = self._slot_req[slot]
                if req is not None and req.span is not None and (
                        step_parent is None
                        or req.submitted < step_parent[0]):
                    step_parent = (req.submitted, req.span)
        step_span = tracing.start_span(
            "llm.step", {"occupancy": len(active)},
            parent={"trace_id": step_parent[1]["trace_id"],
                    "span_id": step_parent[1]["span_id"]},
            cat="llm") if step_parent is not None else None
        t0 = time.perf_counter()
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
            with tracing.device_span("llm.step.dispatch",
                                     epoch_ns=time.time_ns()):
                nxt, self._cache = self._step_fn(
                    self.params, self._cache,
                    self._jnp.asarray(self._step_in),
                    self._jnp.asarray(self._pos))
            with tracing.device_span("llm.step.sync"):
                # The device has this step: wake the LAST step's streams
                # now, so their pollers take the lock and the interpreter
                # while the sync below waits (at the head of this span,
                # not in one of its own: a turn stays select, dispatch,
                # sync, fanout, and the host's share of it, select +
                # dispatch + fanout, does not hold the wake-ups).
                self._flush_wakes(enqueued=True)
                # The one intentional sync per decode step (tokens fan
                # out to streams from host memory).
                nxt = np.asarray(nxt)  # analyze: ignore[JX002]
        except BaseException as e:
            # A raise before the enqueue (an armed before_step, a step
            # that fails to dispatch) makes nobody wait for the next
            # attempt; after the enqueue this finds the list empty.
            self._flush_wakes(enqueued=False)
            tracing.finish_span(step_span, "ERROR: step")
            self._log_first_failure("decode step")
            self._step_errors_row += 1
            self.stats_counters["errors"] += 1
            if self._step_errors_row >= _MAX_STEP_ERRORS:
                with self._lock:
                    for slot in range(self.max_batch):
                        req = self._slot_req[slot]
                        if req is not None:
                            self._finish_locked(
                                req, error="decode step failing "
                                f"repeatedly: {e!r}", slot=slot)
                self._step_errors_row = 0
            raise
        self._step_errors_row = 0
        step_s = time.perf_counter() - t0
        self._init_s.setdefault("first_step", step_s)
        with tracing.device_span("llm.step.fanout") as ds:
            self._step_fanout(active, nxt, step_s, step_span, ds)
        if self.step_throttle_s:
            self._flush_wakes(enqueued=False)  # nothing follows for a while
            time.sleep(self.step_throttle_s)
        return True

    def _step_fanout(self, active: List[int], nxt, step_s: float,
                     step_span: Optional[dict], ds) -> None:
        """After the step's sync: tokens to their streams under the lock,
        then the step's metrics and store span (all of it is the
        ``llm.step.fanout`` device span ``ds``). A token is visible
        to every drain from its append here. A stream that ends with it
        is woken here (``_finish_locked``); one that goes on decoding is
        put on ``_wakes`` and woken after the next enqueue (``_loop``).

        A drafting family's step yields ONE chunk of one or two tokens a
        slot (``served [S, 4]`` at the head of ``nxt``): the position moves
        by what the device accepted, the chunk is cut where ``max_tokens``
        or the end token falls inside a pair."""
        drafting = self._drafting
        with self._lock:
            produced = chunks = drafted = accepted = 0
            # the one clock read of this fan-out's tokens
            self._fanout_ns = visible_ns = time.perf_counter_ns()
            for slot in active:
                req = self._slot_req[slot]
                if req is None:
                    continue  # cancelled while the step was in flight
                if drafting:
                    n = int(nxt[4 * slot])
                    toks = [int(t) for t in
                            nxt[4 * slot + 1:4 * slot + 1 + n]]
                    self._draft[slot] = int(nxt[4 * slot + 3])
                    drafted += 1
                    accepted += n - 1
                else:
                    toks = [int(nxt[slot])]
                self._tokens[slot] = toks[-1]
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
                    # (the list was emptied when this step was enqueued)
                    self._wakes.append(req.stream)
            self.stats_counters["steps"] += 1
            counters_at = (self.max_batch + 1) * (4 if drafting else 1)
            for i, key in enumerate(self._step_counters):
                self.stats_counters[key] = self.stats_counters.get(key, 0) \
                    + int(nxt[counters_at + i])
            self.stats_counters["tokens_out"] += produced
            self.stats_counters["occupancy_sum"] += len(active)
            self.stats_counters["draft_proposed"] += drafted
            self.stats_counters["draft_accepted"] += accepted
            # ITL (TPOT): delivery-to-delivery gap. All slots advance
            # in lockstep, so every CHUNK this step produced arrived
            # the same gap after its stream's previous one — one event
            # carries the shared gap plus the chunk count.
            done_at = time.time()
            itl = step_s if self._last_tokens_at is None \
                else max(0.0, done_at - self._last_tokens_at)
            self._last_tokens_at = done_at
        ds.set_metadata(tokens=produced)
        _obs.record_decode_step(self._dep, step_s, len(active), produced)
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
            self.stats_counters["queue_peak"] = max(
                self.stats_counters["queue_peak"], self._n_queued)
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
        active one frees its slot at the cancel (the in-flight step's
        token for it is discarded). The stream terminates with a
        'cancelled' error; returns whether the request was still live.
        A request mid-admission (its prefill in flight) is in neither
        table and returns False — it completes normally and is reaped;
        the window is one prefill call."""
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
        """Stop the loop and wait for its thread (a step or a prefill in
        flight ends first); ``serve.shutdown()`` calls this through the
        replica. True once the thread has ended."""
        with self._lock:  # ``llm_submit`` reads it under the same lock
            self._stop = True
        self._wake.set()
        self._loop_thread.join(timeout=30.0)
        if not self._loop_thread.is_alive():
            self._fail_unserved("engine stopped")
        _metrics.retract_loop_series(["llm.engine"])
        return not self._loop_thread.is_alive()
