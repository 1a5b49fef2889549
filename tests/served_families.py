"""The families ``LLMEngine`` serves: ONE table, a row a family.

A row holds what the tests of the engine's contract, of the prefill chunks,
of the stored parameters and of the six contracts every family's file used
to spell out for itself (``tests/test_served_family_contract.py``) need of a
family: its float32 tiny configuration, what the chunk tests change of it,
its functions (taken from ``_model_bundle``, not listed again), its plain
reference under ``benchmark/reference/`` with the glue that hands it the
system's weights, and the data each contract compares against. Adding a
family is adding a row here, the tests only it needs in a file of its own,
and its compile-only file.

What is expensive is made once a process and shared: seeded parameters, a
family's full-context forward as ONE compiled program, the reference's
logits. Engines are never shared here: their counters are what tests assert.
"""

import dataclasses
import functools
import importlib
import os
import re
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.loading import load_json, load_module
from ray_tpu.serve.llm_engine import _model_bundle

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
F32 = jnp.float32
BF16 = jnp.bfloat16
PROMPT = [5, 9, 2, 17, 3]


def benchmark_file(kind, name):
    """A file of the benchmark by its kind's directory: read, never edited."""
    path = os.path.join(REPO, "benchmark", kind, name)
    return load_json(path) if name.endswith(".json") else load_module(path)


def moved(params, seed=6, keys=512):
    """Every weight moved off its initial value: the norm scales start at
    one (Qwen3-Next's zero-centred ones at 0, a LayerNorm's bias at 0), and
    a dropped or swapped scale would go unseen."""
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), keys))
    return jax.tree.map(
        lambda x: x + 0.05 * jax.random.normal(next(keys), x.shape, x.dtype),
        params)


def rel_l2(got, want):
    return float(jnp.max(jnp.linalg.norm(got - want, axis=-1)
                         / jnp.linalg.norm(want, axis=-1)))


@dataclasses.dataclass(frozen=True)
class Family:
    name: str
    config: type
    # What the chunk tests (tests/test_prefill_chunks*.py) change of the
    # float32 tiny configuration, and nothing else does: they cut prompts of
    # up to 16 tokens into chunks of 4 and compare states at 1e-5, so a scan
    # blocks by 4 (a chunk of 4 and a window of 16 then block alike),
    # Qwen3-Next is one period deep (two periods of float32 sums in another
    # order pass 1e-5 by a third), SmallThinker's window rings hold 16 rows
    # (the whole-window pass is ONE chunk of 16, which must divide a ring;
    # the wraps are tests/test_smallthinker.py's) and Keye-VL-2.0 picks 8
    # keys (prompts select from their ninth token on, across chunks).
    in_chunks: dict = dataclasses.field(default_factory=dict)
    # The configuration ``tests/test_serving_params.py`` stores (bfloat16
    # programs): the tiny preset, but for the two families whose float32
    # leftovers that file looks for among the live arrays.
    stored: Any = None
    # (benchmark/configs/<...>.json, benchmark/deployments/<...>.json) of
    # the family's cell; Llama has none.
    published: tuple = ()
    # -- the plain reference and the glue that hands it the weights --------
    toy_file: Callable | None = None     # cfg -> the keys its file reads
    ref_kwargs: Callable | None = None   # cfg, **turned -> forward's kwargs
    to_ref: Callable | None = None       # params, cfg -> the reference's tree
    draws: int = 512                     # keys ``moved`` splits
    weighty: Callable | None = None      # params -> params that count
    # -- what the six contracts compare against ----------------------------
    sizes: Callable | None = None        # row -> asserts the published sizes
    stated: tuple = ()                   # dtype attributes the file states
    types: Callable | None = None        # row, program, args, out, text
    programs: Callable | None = None     # cfg -> {program: (fn, args)}
    scopes: Callable | None = None       # row, {program: text} -> asserts
    rows: tuple = (3, 40)                # the forward contract's tokens
    agrees: Callable | None = None       # row, forward, tokens, want
    serves: dict | None = None           # the engine against the reference
    preset_engine: dict | None = None    # the tiny preset's engine

    @property
    def module(self):
        return importlib.import_module(f"ray_tpu.models.{self.name}")

    @functools.cached_property
    def cfg(self):
        """The float32 tiny configuration the contracts use."""
        return dataclasses.replace(self.config.tiny(), dtype=F32,
                                   param_dtype=F32)

    @functools.cached_property
    def chunked(self):
        return dataclasses.replace(self.cfg, **self.in_chunks)

    @property
    def serving(self):
        """The bfloat16 configuration ``tests/test_serving_params.py``
        stores."""
        return self.stored or self.config.tiny()

    def bundle(self, cfg=None):
        """(cfg, init, init_cache, prefill_chunk, decode_step[, verify])."""
        return _model_bundle(self.name, cfg, "tiny")

    @property
    def init(self):
        return self.bundle()[1]

    @property
    def init_cache(self):
        return self.bundle()[2]

    @property
    def prefill_chunk(self):
        return self.bundle()[3]

    @property
    def decode(self):
        return self.bundle()[4]

    @property
    def prefill(self):
        """The whole-window form the benchmark's reference check calls."""
        return getattr(self.module, f"{self.name}_prefill")

    def forward(self, params, tokens, cfg):
        """The full-context logits (a self-drafting family's main ones)."""
        out = getattr(self.module, f"{self.name}_forward")(params, tokens,
                                                           cfg)
        return out[0] if isinstance(out, tuple) else out

    # -- the benchmark's files of this family ------------------------------

    @property
    def reference(self):
        return benchmark_file("reference", self.name + ".py")

    @property
    def family(self):
        return benchmark_file("families", self.name + ".py")

    @property
    def CONFIG(self):
        return benchmark_file("configs", self.published[0] + ".json")

    def cell(self):
        """(the program's configuration at the published widths, ``top_k``
        and held counts, the deployment's engine settings) of the family's
        cell."""
        return (self.family.system_config(self.CONFIG),
                benchmark_file("deployments",
                               self.published[1] + ".json")["engine"])

    def reference_kwargs(self, cfg=None, **turned):
        cfg = cfg or self.cfg
        if self.toy_file is None:
            return self.ref_kwargs(cfg, **turned)
        extra = self.ref_kwargs(cfg) if self.ref_kwargs else {}
        return {**self.family.reference_kwargs(self.toy_file(cfg)), **extra,
                **turned}

    def to_reference(self, params, cfg=None):
        cfg = cfg or self.cfg
        if self.toy_file is None:
            return self.to_ref(self, params, cfg)
        return self.family.to_reference(params, self.toy_file(cfg))

    def reference_forward(self, params, cfg=None, **turned):
        """The reference's forward over ``params`` as one compiled program
        (op by op it costs several times as much)."""
        ref = self.to_reference(params, cfg)
        kwargs = self.reference_kwargs(cfg, **turned)
        return jax.jit(lambda t: self.reference.forward(ref, t, **kwargs))


# -- what is made once a process ----------------------------------------------


@functools.lru_cache(maxsize=None)
def contract_params(name):
    """The float32 tiny weights every family's file compares on: seed 0,
    moved off their initial values."""
    row = FAMILIES[name]
    params = moved(row.init(jax.random.PRNGKey(0), row.cfg), keys=row.draws)
    return row.weighty(params) if row.weighty else params


@functools.lru_cache(maxsize=None)
def contract_tokens(name):
    row = FAMILIES[name]
    return jnp.asarray(np.random.default_rng(1).integers(
        0, row.cfg.vocab_size, row.rows, dtype=np.int32))


@functools.lru_cache(maxsize=None)
def contract_want(name):
    """The reference's logits of ``contract_tokens``."""
    return FAMILIES[name].reference_forward(contract_params(name))(
        contract_tokens(name))


@functools.lru_cache(maxsize=None)
def forward_fn(name, cfg):
    """``forward(params, tokens)`` of ``cfg`` under ONE jit: a compile a
    shape a process, whoever asks."""
    row = FAMILIES[name]
    return jax.jit(lambda params, tokens: row.forward(params, tokens, cfg))


def greedy(forward, prompt, n, width):
    """The single-tenant loop the engine must match token for token:
    ``forward(tokens [1, width]) -> logits`` and argmax, a token a pass.
    The families are causal, so one padded shape serves every length."""
    toks = [int(t) for t in prompt]
    for _ in range(n):
        padded = np.zeros((1, width), np.int32)
        padded[0, :len(toks)] = toks
        toks.append(int(jnp.argmax(forward(jnp.asarray(padded))[
            0, len(toks) - 1])))
    return toks[len(prompt):]


def generated_alone(name, params, prompt, n, cfg=None, width=32):
    """What ``prompt`` gets generated ALONE by the family's full-context
    forward over ``params`` (an engine's own): ``n`` greedy tokens."""
    fwd = forward_fn(name, cfg or FAMILIES[name].cfg)
    return greedy(lambda t: fwd(params, t), prompt, n, width)


def abstract_programs(row, cfg):
    """The two programs as the engine traces them, over shapes alone (three
    slots of 16 rows, a chunk of 4 over a window of 8): ``{"decode": (fn,
    args), "prefill": (fn, args)}``. A family whose engine runs other
    programs (the self-drafting one's verify step) says so in its row."""
    if row.programs is not None:
        return row.programs(cfg)
    cfg, init, init_cache, chunk_fn, step = row.bundle(cfg)[:5]
    params = jax.eval_shape(lambda: init(jax.random.PRNGKey(0), cfg))
    cache = jax.eval_shape(lambda: init_cache(cfg, 3, 16))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    return {
        "decode": (lambda p, c, t, n: step(p, c, t, n, cfg),
                   (params, cache, i32(3), i32(3))),
        "prefill": (lambda p, c, t, s, a, n: chunk_fn(
            p, c, t, s, a, n, cfg, window=8),
            (params, cache, i32(1, 4), i32(1), i32(1), i32(1)))}


def counters_are_scalars(counted, names):
    """``counted``: the step's counters (if it returns any) then the
    cache's; each an int32 scalar, under the names the family states."""
    assert all(v.dtype == jnp.int32 and v.shape == ()
               for c in counted for v in c.values())
    assert [set(c) for c in counted] == names


def assert_expert_counters(stats, layers, held, top=12):
    """The step's expert counters in ``llm_stats()``: every step runs
    ``max_batch + 1`` (4) rows through ``layers`` expert layers, top 3: at
    most ``top`` pairs a layer land here, at most ``held`` experts are
    hit."""
    steps = stats["steps"]
    assert steps >= 10
    assert stats["expert_layers"] == layers and stats["experts_held"] == held
    assert 0 < stats["experts_hit"] <= steps * layers * held
    assert stats["experts_hit"] <= stats["expert_rows"] \
        <= steps * layers * top


# -- gpt2, llama --------------------------------------------------------------


def _gpt2_stored():
    from ray_tpu.models.gpt2 import GPT2Config

    return GPT2Config(vocab_size=136, n_layer=3, n_head=3, d_model=48,
                      seq_len=44)


def _llama_stored():
    from ray_tpu.models.llama import LlamaConfig

    return dataclasses.replace(LlamaConfig.tiny(), vocab_size=136, n_layer=3)


# -- nemotron_h ---------------------------------------------------------------


def _nemotron_h_ref_kwargs(cfg, **over):
    return {**dict(pattern=cfg.pattern, eps=cfg.eps, n_head=cfg.n_head,
                n_kv_head=cfg.n_kv_head, head_dim=cfg.head_dim,
                mamba_heads=cfg.mamba_heads,
                mamba_head_dim=cfg.mamba_head_dim, n_groups=cfg.ssm_groups,
                ssm_state=cfg.ssm_state, top_k=cfg.top_k,
                routed_scale=cfg.routed_scale,
                first_expert=cfg.experts_held[0]), **over}


def _nemotron_h_to_ref(row, params, cfg):
    return {"embeddings": params["embed"], "lm_head": params["lm_head"],
            "norm_f": params["norm_f"],
            "layers": [{ref: p[name] for name, ref
                        in row.family.LAYER_NAMES[kind].items()}
                       for kind, p in zip(cfg.pattern, params["layers"])]}


def _nemotron_h_sizes(row):
    nh = row.module
    assert set(row.cfg.pattern) == {"M", "*", "E"}
    assert row.cfg.experts_held[1] < row.cfg.n_experts  # a share, not all
    assert nh.NemotronHConfig().pattern == nh.PUBLISHED_PATTERN
    assert len(nh.PUBLISHED_PATTERN) == 88
    assert [nh.PUBLISHED_PATTERN.count(k) for k in "ME*"] == [40, 40, 8]
    with pytest.raises(ValueError, match="pattern"):
        nh.NemotronHConfig.tiny(pattern="MXE")
    with pytest.raises(ValueError, match="experts_held"):
        nh.NemotronHConfig.tiny(experts_held=(6, 4))


def _nemotron_h_types(row, program, args, out, text):
    """``correct`` cannot see the experts in float8 or the state in
    bfloat16 through the logits (PERF.md section 7: both lie under the
    rounding of bfloat16 activations), so the stated precision is held by
    the programs' own types (the router's float32 scores are
    ``tests/test_moe_dropless.py``'s)."""
    assert row.CONFIG["assumed"]["ssm_state_dtype"] == "float32"
    assert [s.dtype for s in out[1]["ssm"]] == [jnp.float32] * 2


def _abs_close(tol, odd=37):
    """The forward contract where the family's file held the largest
    absolute difference."""
    def agrees(row, forward, tokens, want):
        got = forward(tokens)
        assert got.shape == want.shape == (*row.rows, row.cfg.vocab_size)
        assert float(jnp.abs(got - want).max()) < tol
        # a prompt longer than one chunk, and not a multiple of it
        assert tokens.shape[1] > 2 * row.cfg.chunk_size
        assert tokens.shape[1] % row.cfg.chunk_size == 0
        assert float(jnp.abs(forward(tokens[:, :odd])
                             - want[:, :odd]).max()) < tol
    return agrees


def _rel_close(tol, odd=None, scan=False, spread=None):
    """The forward contract at the largest position's relative L2; ``odd``:
    also a row that is no multiple of the scan's block (``scan``: and longer
    than two of them); ``spread``: logits worth comparing."""
    def agrees(row, forward, tokens, want):
        got = forward(tokens)
        assert got.dtype == F32
        assert got.shape == want.shape == (*row.rows, row.cfg.vocab_size)
        assert rel_l2(got, want) < tol
        if scan:
            assert tokens.shape[1] > 2 * row.cfg.chunk_size
        if odd:
            assert rel_l2(forward(tokens[:, :odd]), want[:, :odd]) < tol
        if spread:
            assert float(jnp.std(want)) > spread
    return agrees


_TWO_PROMPTS = ([5, 9, 2, 17, 3], [11, 200, 4, 4, 8, 1, 99, 23, 54])
_SMALL_ENGINE = dict(max_batch=3, cache_len=32, max_prompt_len=16,
                     prefill_rows=2)


def _nemotron_h_served(stats):
    assert stats["model"] == "nemotron_h"
    assert_expert_counters(stats, layers=2, held=4)
    # a tile is up to 128 pairs of one expert, so a hit expert is one tile
    assert stats["expert_row_tiles"] == stats["experts_hit"]


# -- granite_hybrid -----------------------------------------------------------


def _granite_hybrid_ref_kwargs(cfg, **over):
    return {**dict(layer_types=cfg.layer_types, eps=cfg.eps, n_head=cfg.n_head,
                n_kv_head=cfg.n_kv_head, head_dim=cfg.head_dim,
                mamba_heads=cfg.mamba_heads,
                mamba_head_dim=cfg.mamba_head_dim, n_groups=cfg.ssm_groups,
                ssm_state=cfg.ssm_state, top_k=cfg.top_k,
                first_expert=cfg.experts_held[0],
                embedding_multiplier=cfg.embedding_multiplier,
                attention_multiplier=cfg.attention_multiplier,
                residual_multiplier=cfg.residual_multiplier,
                logits_scaling=cfg.logits_scaling), **over}


def _granite_hybrid_to_ref(row, params, cfg):
    names = row.family
    return {"embed_tokens": params["embed"], "norm": params["norm_f"],
            "layers": [{ref: p[name] for name, ref in {
                **names.LAYER_NAMES, **names.MIXER_NAMES[kind]}.items()}
                for kind, p in zip(cfg.layer_types, params["layers"])]}


def _granite_hybrid_weighty(params):
    """At their seeded scale the routed experts and attention add a
    hundredth of what a Mamba mixer adds: make them count, so that a
    fault in either is seen."""
    big = {"w2": 6.0, "wo": 6.0}
    return {**params, "layers": [
        {k: v * big.get(k, 1.0) for k, v in p.items()}
        for p in params["layers"]]}


def _granite_hybrid_sizes(row):
    gh, cfg = row.module, row.cfg
    types = gh.GraniteHybridConfig().layer_types
    assert len(types) == 40
    assert [types.count(k) for k in ("mamba", "attention")] == [36, 4]
    assert all(types[i:i + 10] == types[:10] for i in range(0, 40, 10))
    assert types[:10].index("attention") == 5
    assert set(cfg.layer_types) == {"mamba", "attention"}
    assert cfg.attention_multiplier != cfg.head_dim ** -0.5
    assert gh.GraniteHybridConfig().attention_multiplier == 1 / 128
    assert cfg.serving_stats() == {"expert_layers": 3, "experts_held": 4}
    with pytest.raises(ValueError, match="layer_types"):
        gh.GraniteHybridConfig.tiny(layer_types=("mamba", "moe"))
    with pytest.raises(ValueError, match="experts_held"):
        gh.GraniteHybridConfig.tiny(experts_held=(6, 4))


_EXPERT_COUNTERS = {"experts_hit", "expert_rows", "expert_row_tiles"}


def _granite_hybrid_types(row, program, args, out, text):
    logits, new_cache, *counted = out
    assert row.CONFIG["assumed"]["ssm_state_dtype"] == "float32"
    assert [s.dtype for s in new_cache["ssm"]] == [jnp.float32] * 2
    # the step returns its counters third; the chunk program counts in
    # the cache, which both hand on
    counters_are_scalars(
        [*counted, new_cache["counted"]],
        ([_EXPERT_COUNTERS] if program == "decode" else [])
        + [{"prefill_expert_rows"}])


def _chunks_counted(stats, pairs):
    # the chunks: 2 + 3 executions, 14 real tokens, their pairs counted
    assert stats["prefill_chunks"] == 5
    assert stats["prefill_tokens_real"] == 14
    assert 0 < stats["prefill_expert_rows"] <= 14 * pairs


def _granite_hybrid_served(stats):
    assert stats["model"] == "granite_hybrid"
    assert_expert_counters(stats, layers=3, held=4)
    _chunks_counted(stats, 3 * 3)


def _granite_hybrid_preset(eng):
    assert eng._step_counters == ("expert_row_tiles", "expert_rows",
                                  "experts_hit")
    assert eng.llm_stats()["prefill_expert_rows"] == int(
        eng._cache["counted"]["prefill_expert_rows"]) > 0


# -- deepseek_v2 --------------------------------------------------------------


def deepseek_v2_rope_scaling(cfg):
    return {"type": "yarn", "factor": cfg.yarn_factor,
            "original_max_position_embeddings": cfg.yarn_original,
            "beta_fast": cfg.beta_fast, "beta_slow": cfg.beta_slow,
            "mscale": cfg.mscale, "mscale_all_dim": cfg.mscale_all_dim}


def _deepseek_v2_ref_kwargs(cfg, **over):
    return {**dict(n_head=cfg.n_head, nope=cfg.nope_dim, rope=cfg.rope_dim,
                v_dim=cfg.v_dim, eps=cfg.eps, rope_theta=cfg.rope_theta,
                rope_scaling=deepseek_v2_rope_scaling(cfg), top_k=cfg.top_k,
                n_group=cfg.n_group, topk_group=cfg.topk_group,
                routed_scale=cfg.routed_scale,
                first_expert=cfg.experts_held[0]), **over}


def _deepseek_v2_sizes(row):
    ds = row.module
    cfg = ds.DeepseekV2Config()
    assert (cfg.n_layer, cfg.first_dense, cfg.n_head) == (60, 1, 128)
    assert (cfg.q_rank, cfg.kv_rank, cfg.nope_dim, cfg.rope_dim, cfg.v_dim) \
        == (1536, 512, 128, 64, 128)
    assert (cfg.n_experts, cfg.n_group, cfg.topk_group, cfg.top_k) \
        == (160, 8, 3, 6)
    assert cfg.shared_ff == 2 * cfg.expert_ff == 3072
    assert cfg.is_dense(0) and not cfg.is_dense(1)
    # ISSUE 38: s = 192 ** -0.5 * m(0.707) ** 2 = 0.07217 x 1.5896
    assert cfg.softmax_scale == pytest.approx(0.07217 * 1.5896, rel=1e-4)
    assert row.cfg.serving_stats() == {"expert_layers": 2, "experts_held": 8}
    assert [row.cfg.is_dense(i) for i in range(3)] == [True, False, False]
    with pytest.raises(ValueError, match="experts_held"):
        ds.DeepseekV2Config.tiny(experts_held=(12, 8))
    with pytest.raises(ValueError, match="groups"):
        ds.DeepseekV2Config.tiny(n_group=3)
    with pytest.raises(ValueError, match="groups"):
        ds.DeepseekV2Config.tiny(topk_group=5)


def _deepseek_v2_types(row, program, args, out, text):
    assert "float32 router" in row.CONFIG["computes_in"]
    # the attention's scores and the router's are float32 products
    assert re.search(r"f32\[[0-9,]*\] = dot_general\[", text)
    assert "preferred_element_type=float32" in text
    logits, new_cache, *counted = out
    assert new_cache["latent"].dtype == jnp.bfloat16
    counters_are_scalars(
        [*counted, new_cache["counted"]],
        ([_EXPERT_COUNTERS | {"expert_tokens_here"}]
         if program == "decode" else []) + [{"prefill_expert_rows"}])


def _deepseek_v2_served(stats):
    assert stats["model"] == "deepseek_v2"
    assert_expert_counters(stats, layers=2, held=8)
    steps = stats["steps"]
    assert 0 < stats["expert_tokens_here"] <= steps * 2 * 4
    assert stats["expert_tokens_here"] <= stats["expert_rows"]
    _chunks_counted(stats, 3 * 2)


def _deepseek_v2_preset(eng):
    assert eng._step_counters == ("expert_row_tiles", "expert_rows",
                                  "expert_tokens_here", "experts_hit")
    assert eng.llm_stats()["prefill_expert_rows"] == int(
        eng._cache["counted"]["prefill_expert_rows"]) > 0


# -- falcon_h1 ----------------------------------------------------------------

FALCON_H1_SCALARS = (
    "embedding_multiplier", "lm_head_multiplier", "key_multiplier",
    "attention_in_multiplier", "attention_out_multiplier",
    "ssm_in_multiplier", "ssm_out_multiplier")


def _falcon_h1_ref_kwargs(cfg, **over):
    return dict(eps=cfg.eps, n_head=cfg.n_head, n_kv_head=cfg.n_kv_head,
                head_dim=cfg.head_dim, rope_theta=cfg.rope_theta,
                mamba_heads=cfg.mamba_heads,
                mamba_head_dim=cfg.mamba_head_dim, n_groups=cfg.ssm_groups,
                ssm_state=cfg.ssm_state,
                ssm_multipliers=cfg.ssm_multipliers,
                mlp_multipliers=cfg.mlp_multipliers,
                **{**{name: getattr(cfg, name)
                      for name in FALCON_H1_SCALARS}, **over})


def _falcon_h1_sizes(row):
    fh = row.module
    cfg = fh.FalconH1Config()
    assert (cfg.n_layer, cfg.d_model, cfg.vocab_size, cfg.d_ff) \
        == (72, 5120, 261120, 21504)
    assert (cfg.n_head, cfg.n_kv_head, cfg.head_dim) == (20, 4, 128)
    assert cfg.rope_theta == 1e11
    m = cfg.mamba
    assert (m.heads, m.head_dim, m.groups, m.state, m.kernel, m.block) \
        == (32, 128, 2, 256, 4, 128)
    assert (m.d_inner, m.conv_dim, m.in_width) == (4096, 5120, 9248)
    assert m.in_multipliers == cfg.ssm_multipliers
    # the tiny preset keeps what makes the family: two groups, a state that
    # is not the head size, d_inner that is not twice the hidden size,
    # grouped queries, and no multiplier that a test could lose unseen
    tiny = fh.FalconH1Config.tiny()
    assert tiny.ssm_groups == 2 and tiny.ssm_state != tiny.mamba_head_dim
    assert tiny.mamba.d_inner != 2 * tiny.d_model
    assert tiny.n_kv_head < tiny.n_head
    every = [getattr(tiny, n) for n in FALCON_H1_SCALARS] \
        + list(tiny.ssm_multipliers) + list(tiny.mlp_multipliers)
    assert len(every) == 14
    assert all(v != 1 and np.log2(v) % 1 for v in every)
    assert row.cfg.serving_stats() == {
        "prefill_expert_rows": 0,        # no experts
        "chunk_attention_arm": "xla"}    # toy widths
    with pytest.raises(ValueError, match="five factors"):
        fh.FalconH1Config.tiny(ssm_multipliers=(1.0, 2.0))
    with pytest.raises(ValueError, match="gains"):
        fh.FalconH1Config.tiny(gains=(("embed", 1.0),))
    with pytest.raises(ValueError, match="divide"):
        fh.FalconH1Config.tiny(n_kv_head=3)


def _falcon_h1_types(row, program, args, out, text):
    logits, new_cache, *counted = out
    assert row.CONFIG["assumed"]["ssm_state_dtype"] == "float32"
    assert [s.dtype for s in new_cache["ssm"]] == [jnp.float32] * 3
    assert new_cache["k"].dtype == new_cache["conv"].dtype == jnp.bfloat16
    assert jax.tree.structure(new_cache) == jax.tree.structure(args[1])
    assert "counted" not in new_cache  # no experts: nothing to count
    # the step says what its attention read of the rings (PR 48)
    assert [sorted(c) for c in counted] == (
        [["ring_rows_held", "ring_rows_read"]] if program == "decode"
        else [])


def _falcon_h1_scopes(row, texts):
    """Every scope the three new readers (and the older ones) sum over is
    on some operation's path in the program each names it for: the step's
    ``ssm_update`` is the chunk's ``ssm_scan``, and the sum of the two
    branches is an operation of its own (``mixer_sum``)."""
    reader = benchmark_file("metrics", "decode_parallel_mixer_time_pct.py")
    for scope in reader.ATTENTION + reader.STATE + (
            "embed", "ln", "mlp", "head", "mixer_sum"):
        if scope != "ssm_scan":
            assert f"/{scope}/" in texts["decode"], scope
        if scope != "ssm_update":
            assert f"/{scope}/" in texts["prefill"], scope


def _falcon_h1_served(stats):
    assert stats["model"] == "falcon_h1"
    assert stats["steps"] >= 10
    # the chunks: 2 + 3 executions, 14 real tokens, no expert to count
    assert stats["prefill_chunks"] == 5
    assert stats["prefill_tokens_real"] == 14
    assert stats["prefill_expert_rows"] == 0


def _falcon_h1_preset(eng):
    # no experts to count; the rings' rows read and held (PR 48): a toy
    # row keeps the XLA arm, which reads every row it holds
    assert eng._step_counters == ("ring_rows_held", "ring_rows_read")
    stats = eng.llm_stats()
    assert stats["ring_rows_read"] == stats["ring_rows_held"] \
        == stats["steps"] * eng._cfg.n_layer * 3 * 16


# -- qwen3_next ---------------------------------------------------------------


def _qwen3_next_toy_file(cfg):
    """The keys of a configuration file that ``families/qwen3_next.py``
    reads, for ``cfg``'s sizes."""
    return {"hidden_size": cfg.d_model, "num_hidden_layers": cfg.n_layer,
            "full_attention_interval": cfg.full_attention_interval,
            "linear_num_key_heads": cfg.linear_key_heads,
            "linear_num_value_heads": cfg.linear_value_heads,
            "linear_key_head_dim": cfg.linear_key_dim,
            "linear_value_head_dim": cfg.linear_value_dim,
            "linear_conv_kernel_dim": cfg.conv_kernel,
            "num_attention_heads": cfg.n_head,
            "num_key_value_heads": cfg.n_kv_head, "head_dim": cfg.head_dim,
            "partial_rotary_factor": cfg.partial_rotary_factor,
            "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.eps,
            "num_experts": cfg.experts_held[1],
            "num_experts_published": cfg.n_experts,
            "num_experts_per_tok": cfg.top_k,
            "moe_intermediate_size": cfg.expert_ff,
            "shared_expert_intermediate_size": cfg.shared_ff,
            "vocab_size": cfg.vocab_size, "max_position_embeddings": 64,
            "assumed": {"experts_held": list(cfg.experts_held),
                        "scan_block": cfg.scan_block}}


def _qwen3_next_sizes(row):
    qn = row.module
    cfg = qn.Qwen3NextConfig()
    assert cfg.layer_types[:8] == ("linear_attention",) * 3 \
        + ("full_attention",) + ("linear_attention",) * 3 \
        + ("full_attention",)
    assert (cfg.count("linear_attention"), cfg.count("full_attention")) \
        == (36, 12)
    assert (cfg.rotary_dim, cfg.head_dim, cfg.n_head, cfg.n_kv_head) \
        == (64, 256, 16, 2)
    held = row.family.system_config(row.CONFIG)
    stats = held.serving_stats()
    assert stats == {"expert_layers": 8, "experts_held": 128,
                     "linear_layers": 6,
                     "delta_state_bytes_per_slot": 6 * 2_146_304,
                     "kv_bytes_per_token": 4096,
                     "chunk_attention_arm": "xla"}  # no ring to read
    # the engine's chunk and key window: heads of 256 over whole blocks
    assert held.serving_stats(512, 16384)["chunk_attention_arm"] == "kernel"
    assert qn.Qwen3NextConfig.tiny().serving_stats(512, 16384)[
        "chunk_attention_arm"] == "xla"  # toy widths
    tiny = qn.Qwen3NextConfig.tiny()
    # value heads twice the key heads, dk != dv, grouped queries, a partial
    # rotary, a strict part of the router's experts, two periods
    assert tiny.linear_value_heads == 2 * tiny.linear_key_heads
    assert tiny.linear_key_dim != tiny.linear_value_dim
    assert tiny.n_kv_head < tiny.n_head
    assert 0 < tiny.rotary_dim < tiny.head_dim
    assert tiny.experts_held[1] < tiny.n_experts and tiny.experts_held[0] > 0
    assert tiny.layer_types.count("full_attention") == 2
    with pytest.raises(ValueError, match="experts_held"):
        qn.Qwen3NextConfig.tiny(experts_held=(12, 8))
    with pytest.raises(ValueError, match="pairs"):
        qn.Qwen3NextConfig.tiny(partial_rotary_factor=0.45)


def _qwen3_next_types(row, program, args, out, text):
    logits, new_cache, *_ = out
    assert row.CONFIG["assumed"]["delta_state_dtype"] == "float32"
    assert [s.dtype for s in new_cache["delta"]] == [jnp.float32] * 6
    assert [s.shape for s in new_cache["delta"]] == [(3, 4, 8, 12)] * 6
    assert new_cache["k"].shape == (2, 3, 16, 32)  # merged rows, 2 x 16
    assert new_cache["k"].dtype == new_cache["conv"].dtype == jnp.bfloat16
    assert jax.tree.structure(new_cache) == jax.tree.structure(args[1])


def _qwen3_next_scopes(row, texts):
    reader = benchmark_file("metrics", "decode_linear_attention_time_pct.py")
    for scope in reader.LINEAR + reader.EXPERTS + reader.ATTENTION \
            + ("embed", "ln", "head"):
        if scope != "gdn_scan":
            assert f"/{scope}/" in texts["decode"], scope
        if scope != "gdn_update":
            assert f"/{scope}/" in texts["prefill"], scope


def _qwen3_next_served(stats):
    assert stats["model"] == "qwen3_next"
    assert stats["steps"] >= 10
    _chunks_counted(stats, 3 * 8)
    assert 0 < stats["experts_hit"] <= stats["expert_rows"]
    assert (stats["expert_layers"], stats["experts_held"],
            stats["linear_layers"]) == (8, 8, 6)
    assert stats["delta_state_bytes_per_slot"] == 6 * (
        4 * 8 * 12 * 4 + 3 * (2 * 16 + 48) * 4)
    assert stats["kv_bytes_per_token"] == 2 * 2 * 32 * 4


def _qwen3_next_preset(eng):
    # the experts', and the rings' rows read and held (PR 48): a toy
    # row keeps the XLA arm, which reads every row of the full layers'
    assert eng._step_counters == ("expert_row_tiles", "expert_rows",
                                  "experts_hit", "ring_rows_held",
                                  "ring_rows_read")
    stats = eng.llm_stats()
    assert stats["ring_rows_read"] == stats["ring_rows_held"] \
        == stats["steps"] * eng._cache["k"].shape[0] * 3 * 16


# -- smallthinker -------------------------------------------------------------


def _smallthinker_toy_file(cfg):
    """The keys of a configuration file that ``families/smallthinker.py``
    reads, for ``cfg``'s sizes."""
    return {"hidden_size": cfg.d_model, "num_hidden_layers": cfg.n_layer,
            "rope_layout": list(cfg.window_layout),
            "sliding_window_layout": list(cfg.window_layout),
            "sliding_window_size": cfg.window,
            "num_attention_heads": cfg.n_head,
            "num_key_value_heads": cfg.n_kv_head, "head_dim": cfg.head_dim,
            "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.eps,
            "moe_num_primary_experts": cfg.n_experts,
            "moe_num_active_primary_experts": cfg.top_k,
            "moe_ffn_hidden_size": cfg.expert_ff,
            "vocab_size": cfg.vocab_size, "max_position_embeddings": 64,
            "assumed": {"init_gains": dict(cfg.gains)}}


def _smallthinker_sizes(row):
    st = row.module
    cfg = st.SmallThinkerConfig()
    assert (cfg.n_layer, cfg.n_global, cfg.n_window) == (52, 13, 39)
    assert cfg.window_layout[:8] == (0, 1, 1, 1, 0, 1, 1, 1)
    assert (cfg.d_model, cfg.n_head, cfg.n_kv_head, cfg.head_dim) \
        == (2560, 28, 4, 128)
    assert (cfg.n_experts, cfg.top_k, cfg.expert_ff) == (64, 6, 768)
    assert (cfg.window, cfg.rope_theta, cfg.row_width) == (4096, 1.5e6, 512)
    stated = row.family.system_config(row.CONFIG)
    assert dataclasses.replace(
        cfg, window_layout=cfg.window_layout[:8], vocab_size=18992,
        gains=stated.gains) == stated
    tiny = st.SmallThinkerConfig.tiny()
    assert tiny.window in (8, 16) and tiny.n_kv_head < tiny.n_head
    assert tiny.top_k < tiny.n_experts and tiny.window_layout \
        == (0, 1, 1, 1) * 2
    for bad in (dict(window_layout=(0, 2)), dict(window_layout=()),
                dict(n_head=3), dict(top_k=9), dict(window=0),
                dict(gains=(("embed", 1.0),))):
        with pytest.raises(ValueError):
            st.SmallThinkerConfig.tiny(**bad)


def _same_cache_in_and_out(args, new_cache):
    assert jax.tree.map(lambda a: (a.shape, a.dtype), new_cache) \
        == jax.tree.map(lambda a: (a.shape, a.dtype), args[1])


def _smallthinker_types(row, program, args, out, text):
    logits, new_cache, *_ = out
    assert new_cache["k_full"].shape == (2, 3, 16, 32)  # merged rows, 2 x 16
    assert new_cache["k_win"].shape == (6, 3, 8, 32)
    assert jax.tree.structure(new_cache) == jax.tree.structure(args[1])
    _same_cache_in_and_out(args, new_cache)


_EXPERT_FAMILY_SCOPES = ("embed", "ln", "router", "attn_proj", "rope", "attn",
                         "cache_write", "moe_dispatch", "experts",
                         "moe_combine", "head")


def _smallthinker_scopes(row, texts):
    reader = benchmark_file("metrics", "decode_window_attention_time_pct.py")
    for scope in _EXPERT_FAMILY_SCOPES + reader.KINDS:
        for name, text in texts.items():
            assert f"/{scope}/" in text, (name, scope)
    # both kinds under the outer scope that ``decode_attention_time_pct``
    # reads; only the window layers turn anything
    for text in texts.values():
        assert "/attn/attn_window/" in text and "/attn/attn_global/" in text
        assert text.count("/rope/") > 0


def _smallthinker_prompts(cfg):
    rng = np.random.default_rng(3)
    return [rng.integers(0, cfg.vocab_size, n).tolist() for n in (26, 7)]


def _smallthinker_served(stats):
    assert stats["prefill_chunks"] == 7 + 2 and stats["window_rows"] == 8
    for key in ("ring_rows_read", "ring_rows_held", "window_rows_read",
                "window_rows_held", "experts_hit", "expert_rows",
                "expert_row_tiles", "prefill_expert_rows"):
        assert stats[key] > 0, key
    assert stats["prefill_expert_rows"] == (26 + 7) * 3 * 8


def _windowed_preset(full_rings):
    """A prompt three and a half times the window passes the engine's
    check: ``cache_len`` bounds a context and the FULL rings, not the window
    rings."""
    def holds(eng):
        assert eng._cache["k_win"].shape[2] == 8
        assert eng._cache["k_full"].shape[::2] == (full_rings, 32)
    return holds


# -- exaone_moe ---------------------------------------------------------------


def _exaone_moe_toy_file(cfg):
    """The keys of a configuration file that ``families/exaone_moe.py``
    reads, for ``cfg``'s sizes."""
    sliding, full = "sliding_attention", "full_attention"
    return {"hidden_size": cfg.d_model, "num_hidden_layers": cfg.n_layer,
            "layer_types": [sliding if w else full
                            for w in cfg.window_layout],
            "mlp_layer_types": ["dense"] * cfg.dense_layers
            + ["sparse"] * (cfg.n_layer - cfg.dense_layers),
            "sliding_windows": [cfg.window * w for w in cfg.window_layout],
            "sliding_window": cfg.window,
            "first_k_dense_replace": cfg.dense_layers,
            "intermediate_size": cfg.dense_ff,
            "moe_intermediate_size": cfg.expert_ff,
            "num_experts": cfg.n_held,
            "num_experts_published": cfg.n_experts,
            "num_experts_per_tok": cfg.top_k, "num_shared_experts": 1,
            "routed_scaling_factor": cfg.routed_scale,
            "num_attention_heads": cfg.n_head,
            "num_key_value_heads": cfg.n_kv_head, "head_dim": cfg.head_dim,
            "rope_parameters": {"rope_theta": cfg.rope_theta},
            "rms_norm_eps": cfg.eps, "vocab_size": cfg.vocab_size,
            "max_position_embeddings": 64,
            "assumed": {"init_gains": dict(cfg.gains)}}


def _exaone_moe_sizes(row):
    ex = row.module
    cfg = row.family.system_config(row.CONFIG)
    assert cfg == ex.ExaoneMoeConfig(
        vocab_size=19200, window_layout=(1, 1, 1, 0, 1),
        experts_held=(0, 16))
    assert (cfg.d_model, cfg.n_head, cfg.n_kv_head, cfg.head_dim,
            cfg.dense_ff, cfg.expert_ff, cfg.shared_ff) \
        == (6144, 64, 8, 128, 18432, 2048, 2048)
    assert (cfg.n_window, cfg.n_global, cfg.n_held, cfg.row_width) \
        == (4, 1, 16, 1024)
    whole = ex.ExaoneMoeConfig()
    assert (whole.n_layer, whole.n_window, whole.n_global, whole.n_held) \
        == (48, 36, 12, 128)
    tiny = ex.ExaoneMoeConfig.tiny()
    assert (tiny.n_layer, tiny.n_window, tiny.n_global, tiny.window,
            tiny.n_held, tiny.n_experts, tiny.top_k) == (6, 5, 1, 8, 4, 8, 3)
    for bad in (dict(window_layout=(1, 2)), dict(n_head=3),
                dict(experts_held=(4, 9)), dict(top_k=9),
                dict(dense_layers=7), dict(gains=(("embed", 1.0),))):
        with pytest.raises(ValueError):
            ex.ExaoneMoeConfig.tiny(**bad)


def _exaone_moe_programs(cfg, chunk=8):
    """The verify-and-draft step (what its engine runs) and the chunk with
    the module's pass in it (``follows``)."""
    cfg, init, init_cache, chunk_fn, _, verify = _model_bundle(
        "exaone_moe", cfg, "tiny")
    params = jax.eval_shape(lambda: init(jax.random.PRNGKey(0), cfg))
    cache = jax.eval_shape(lambda: init_cache(cfg, 3, 16))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    return {
        "decode": (lambda p, c, t, n: verify(p, c, t, n, cfg),
                   (params, cache, i32(3, 2), i32(3))),
        "prefill": (
            lambda p, c, t, s, a, n, f: chunk_fn(
                p, c, t, s, a, n, cfg, window=8, follows=f),
            (params, cache, i32(1, chunk), i32(1), i32(1), i32(1), i32(1)))}


def _exaone_moe_types(row, program, args, out, text):
    logits, new_cache, *rest = out
    assert new_cache["k_full"].shape == (2, 3, 16, 32)  # merged rows, 2 x 16
    assert new_cache["k_win"].shape == (5, 3, 8, 32)
    _same_cache_in_and_out(args, new_cache)
    if program == "decode":
        _, served, drafts = rest
        assert logits.shape == drafts.shape == (3, 2, 256)
        assert (served.shape, served.dtype) == ((3, 4), jnp.int32)
    else:
        assert logits.shape == rest[0].shape == (1, 256)


def _exaone_moe_scopes(row, texts):
    window = benchmark_file("metrics", "decode_window_attention_time_pct.py")
    draft = benchmark_file("metrics", "decode_draft_time_pct.py")
    for scope in _EXPERT_FAMILY_SCOPES + (
            "shared_expert", "mlp", "mtp_head", "mtp_proj") \
            + window.KINDS + draft.PARTS:
        for name, text in texts.items():
            assert f"/{scope}/" in text, (name, scope)
    for text in texts.values():
        # the main stack under ``verify``, the module under ``mtp``, each
        # with its attention under the outer scope the accepted readers read
        assert "/verify/attn/attn_window/" in text
        assert "/verify/attn/attn_global/" in text
        assert "/mtp/attn/attn_global/" in text
        assert "/mtp/attn/attn_window/" not in text
        assert "/mtp/mtp_head/" in text and "/verify/head/" in text


def _exaone_moe_preset(eng):
    from ray_tpu.serve import llm_engine

    assert "SIXTH" in llm_engine._model_bundle.__doc__
    _windowed_preset(full_rings=2)(eng)
    assert eng.llm_stats()["draft_proposed"] > 0


# -- keye_vl2 -----------------------------------------------------------------


def _keye_vl2_toy_file(cfg):
    """The keys of a configuration file that ``families/keye_vl2.py``
    reads, for ``cfg``'s sizes."""
    half = cfg.head_dim // 2
    return {"hidden_size": cfg.d_model, "num_hidden_layers": cfg.n_layer,
            "num_attention_heads": cfg.n_head,
            "num_key_value_heads": cfg.n_kv_head, "head_dim": cfg.head_dim,
            "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.eps,
            "rope_scaling": {"mrope_section": [half - 2 * (half // 3),
                                               half // 3, half // 3],
                             "rope_type": "default"},
            "sa_config": {"indexer_head_dim": cfg.index_dim,
                          "indexer_num_heads": cfg.index_heads,
                          "indexer_num_kv_heads": 1, "topk": cfg.index_topk},
            "num_experts": cfg.experts_held[1],
            "num_local_experts": cfg.experts_held[1],
            "num_experts_per_tok": cfg.top_k,
            "moe_intermediate_size": cfg.expert_ff,
            "vocab_size": cfg.vocab_size, "max_position_embeddings": 128,
            "assumed": {"router_experts": cfg.n_experts,
                        "init_gains": dict(cfg.gains)}}


def _keye_vl2_sizes(row):
    kv = row.module
    cfg = kv.KeyeVL2Config()
    assert (cfg.d_model, cfg.n_layer, cfg.n_head, cfg.n_kv_head,
            cfg.head_dim, cfg.rope_theta) == (2048, 48, 32, 4, 128, 1e7)
    assert (cfg.index_heads, cfg.index_dim, cfg.index_topk) == (16, 64, 2048)
    assert (cfg.n_experts, cfg.experts_held, cfg.top_k, cfg.expert_ff) \
        == (128, (0, 128), 8, 768)
    assert cfg.row_width == 512
    tiny = kv.KeyeVL2Config.tiny()
    assert tiny.n_kv_head < tiny.n_head and tiny.top_k < tiny.n_experts
    assert tiny.index_heads > 1 and tiny.index_topk == 16


def _keye_vl2_scopes(row, texts):
    for text in texts.values():
        # (in the chunk program a conditional's branch stands between
        # ``attn`` and the three scopes inside it)
        for scope in ("indexer", "select", "attn_sparse"):
            assert re.search(rf'"[^"]*/attn/([^"]*/)?{scope}/', text), scope
        for scope in ("attn_proj", "router", "experts", "cache_write",
                      "head"):
            assert re.search(rf'"[^"]*/{scope}/', text), scope


def _keye_vl2_agrees(row, forward, tokens, want):
    assert rel_l2(forward(tokens), want) < 2e-5


def _keye_vl2_prompts(cfg):
    rng = np.random.default_rng(3)
    return [rng.integers(1, 200, n).tolist() for n in (3, 8, 15, 17, 33, 40)]


def _keye_vl2_served(stats):
    assert 0 < stats["sparse_keys_selected"] <= stats["sparse_keys_eligible"]
    assert "prefill_sparse_keys_selected" not in stats
    assert stats["sparse_topk"] == 16 and stats["sparse_layers"] == 3
    assert stats["sparse_chunk_select"] == "xla"  # toy widths


# -- the table ----------------------------------------------------------------


def _rows():
    from ray_tpu.models import (deepseek_v2, exaone_moe, falcon_h1, gpt2,
                                granite_hybrid, keye_vl2, llama, nemotron_h,
                                qwen3_next, smallthinker)

    preset = dict(max_batch=2, cache_len=16, max_prompt_len=8)
    windowed = dict(max_batch=2, cache_len=32, max_prompt_len=28,
                    prefill_chunk=4)
    chunks_of_4 = {**_SMALL_ENGINE, "prefill_chunk": 4}
    return [
        Family("gpt2", gpt2.GPT2Config, stored=_gpt2_stored(),
               published=("gpt2-xl-1.5b", "gpt2xl_1chip_b8"),
               preset_engine=dict(engine=preset)),
        Family("llama", llama.LlamaConfig, stored=_llama_stored(),
               preset_engine=dict(engine=preset)),
        Family("nemotron_h", nemotron_h.NemotronHConfig,
               in_chunks=dict(chunk_size=4),
               published=("nemotron3-super-120b-a12b",
                          "nemotron3s_1chip_b64"),
               ref_kwargs=_nemotron_h_ref_kwargs, to_ref=_nemotron_h_to_ref,
               draws=256, sizes=_nemotron_h_sizes,
               stated=("param_dtype", "dtype", "ssm_state_dtype"),
               types=_nemotron_h_types, agrees=_abs_close(1e-4),
               serves=dict(seed=3, engine=_SMALL_ENGINE,
                           prompts=_TWO_PROMPTS, new=6, width=16,
                           stats=_nemotron_h_served),
               preset_engine=dict(engine=preset)),
        Family("granite_hybrid", granite_hybrid.GraniteHybridConfig,
               in_chunks=dict(chunk_size=4),
               published=("granite-4.0-h-small", "granite4hs_1chip_b32"),
               ref_kwargs=_granite_hybrid_ref_kwargs,
               to_ref=_granite_hybrid_to_ref, draws=256,
               weighty=_granite_hybrid_weighty, sizes=_granite_hybrid_sizes,
               stated=("param_dtype", "dtype", "ssm_state_dtype"),
               types=_granite_hybrid_types,
               agrees=_rel_close(1e-4, odd=37, scan=True),
               # With the tied head and the published multiplier 12, seeded
               # weights answer every token with itself (the embedding's own
               # row leads its logits by several spreads: on the chip too,
               # PERF.md section 7), and a greedy continuation would then
               # say nothing of state or cache. At 0.3 the continuation
               # depends on the whole context.
               serves=dict(seed=3, engine=chunks_of_4, prompts=_TWO_PROMPTS,
                           new=6, width=16, distinct=3,
                           cfg=dict(embedding_multiplier=0.3),
                           stats=_granite_hybrid_served),
               preset_engine=dict(engine=preset,
                                  holds=_granite_hybrid_preset)),
        Family("deepseek_v2", deepseek_v2.DeepseekV2Config,
               published=("deepseek-v2", "dsv2_1chip_b64"),
               ref_kwargs=_deepseek_v2_ref_kwargs,
               to_ref=lambda row, params, cfg: row.family.to_reference(
                   params, {}),
               draws=256, sizes=_deepseek_v2_sizes,
               stated=("param_dtype", "dtype"), types=_deepseek_v2_types,
               agrees=_rel_close(1e-4, odd=37),
               serves=dict(seed=3, engine=chunks_of_4, prompts=_TWO_PROMPTS,
                           new=6, width=16, distinct=2,
                           stats=_deepseek_v2_served),
               preset_engine=dict(engine=preset,
                                  holds=_deepseek_v2_preset)),
        Family("falcon_h1", falcon_h1.FalconH1Config,
               in_chunks=dict(chunk_size=4),
               published=("falcon-h1-34b-instruct", "falconh1_1chip_b32"),
               ref_kwargs=_falcon_h1_ref_kwargs,
               to_ref=lambda row, params, cfg: row.family.to_reference(
                   params, None),
               draws=256, sizes=_falcon_h1_sizes,
               stated=("param_dtype", "dtype", "ssm_state_dtype"),
               types=_falcon_h1_types, scopes=_falcon_h1_scopes,
               agrees=_rel_close(1e-4, odd=37, scan=True),
               serves=dict(seed=10, engine=chunks_of_4,
                           prompts=_TWO_PROMPTS, new=6, width=16, distinct=3,
                           stats=_falcon_h1_served),
               preset_engine=dict(engine=preset, holds=_falcon_h1_preset)),
        Family("qwen3_next", qwen3_next.Qwen3NextConfig,
               in_chunks=dict(scan_block=4, n_layer=4),
               published=("qwen3-next-80b-a3b-instruct",
                          "qwen3next_1chip_b64"),
               toy_file=_qwen3_next_toy_file, sizes=_qwen3_next_sizes,
               stated=("param_dtype", "dtype", "delta_state_dtype"),
               types=_qwen3_next_types, scopes=_qwen3_next_scopes,
               agrees=_rel_close(2e-4, spread=0.3),
               serves=dict(seed=10, engine=chunks_of_4,
                           prompts=_TWO_PROMPTS, new=6, width=16, distinct=2,
                           stats=_qwen3_next_served),
               preset_engine=dict(engine=preset, holds=_qwen3_next_preset)),
        # (window rings of 8 rows beside the global rings of ``cache_len``)
        Family("smallthinker", smallthinker.SmallThinkerConfig,
               in_chunks=dict(window=16),
               published=("smallthinker-21b-a3b-instruct",
                          "smallthinker_1chip_b48"),
               toy_file=_smallthinker_toy_file, sizes=_smallthinker_sizes,
               stated=("param_dtype", "dtype"), types=_smallthinker_types,
               scopes=_smallthinker_scopes, rows=(3, 56),
               agrees=_rel_close(2e-5),
               # a prompt of three windows and a generation of three more
               serves=dict(seed=0, engine=dict(
                   max_batch=2, cache_len=64, max_prompt_len=32,
                   prefill_chunk=4, max_new_cap=24),
                   prompts=_smallthinker_prompts, new=24, width=50,
                   distinct=2, stats=_smallthinker_served),
               preset_engine=dict(engine=windowed,
                                  prompt=(list(range(1, 29)), 4),
                                  holds=_windowed_preset(full_rings=2))),
        # (served by its verify-and-draft step: two rows a slot a step, one
        # or two tokens a slot; the tokens are the main stack's greedy ones)
        Family("exaone_moe", exaone_moe.ExaoneMoeConfig,
               published=("k-exaone-236b-a23b", "kexaone_1chip_b64"),
               toy_file=_exaone_moe_toy_file, sizes=_exaone_moe_sizes,
               stated=("param_dtype", "dtype"), types=_exaone_moe_types,
               programs=_exaone_moe_programs, scopes=_exaone_moe_scopes,
               preset_engine=dict(engine=windowed,
                                  prompt=(list(range(1, 29)), 4),
                                  holds=_exaone_moe_preset)),
        # (a query reads the 16 keys its indexer picks)
        Family("keye_vl2", keye_vl2.KeyeVL2Config,
               in_chunks=dict(index_topk=8),
               published=("keye-vl-2.0-30b-a3b", "keyevl2_1chip_b16"),
               toy_file=_keye_vl2_toy_file,
               ref_kwargs=lambda cfg: {"first_expert": cfg.experts_held[0]},
               sizes=_keye_vl2_sizes, scopes=_keye_vl2_scopes,
               agrees=_keye_vl2_agrees,
               # prompts of assorted lengths on both sides of ``topk``
               serves=dict(seed=0, through="engine", engine=dict(
                   max_batch=3, cache_len=64, max_prompt_len=40,
                   prefill_chunk=8, max_new_cap=10),
                   prompts=_keye_vl2_prompts, new=8, width=48,
                   stats=_keye_vl2_served),
               preset_engine=dict(engine=dict(
                   max_batch=2, cache_len=48, max_prompt_len=24,
                   prefill_chunk=8), prompt=(PROMPT, 20))),
    ]


FAMILIES = {row.name: row for row in _rows()}
every_family = pytest.mark.parametrize("model", list(FAMILIES))


def families_with(field):
    """The rows that hold ``field``'s contract, as a parametrisation."""
    return pytest.mark.parametrize("model", [
        name for name, row in FAMILIES.items()
        if getattr(row, field) is not None])
