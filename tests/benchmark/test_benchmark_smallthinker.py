"""The ``smallthinker`` family's benchmark files: its configuration file
against the catalog row it was copied from, its counts against the arrays
the system makes, the bytes of a decode step on hand-made counters, a
window layer's chunk attention and a prefill chunk's work by hand, the four
new readers on hand-made runs, the controls' tool, and a CPU rehearsal of
the cell's kind with a toy configuration of this family added to the tests'
toy root AS FILES AND ENTRIES (no tiny override lives in the benchmark
itself)."""

import json
import os
import types

import jax
import jax.numpy as jnp
import pytest

import benchmark_toy
from benchmark import run as bench_run
from benchmark.loading import load_json, load_module

REPO = benchmark_toy.REPO
METRICS = os.path.join(REPO, "benchmark", "metrics")
CONFIG = "smallthinker-21b-a3b-instruct"
CELL = "serve_smallthinker_mixedwin_sat"
REDUCED = ["num_hidden_layers", "rope_layout", "sliding_window_layout",
           "vocab_size"]
PERIOD = [0, 1, 1, 1]
# The catalog row's ``config`` (guides/model-configs/architectures.jsonl,
# SmallThinker-21BA3B-Instruct), copied here so that the test needs no file
# outside the repository.
PUBLISHED = {
    "head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384,
    "model_name": "smallthinker_21b_instruct", "moe_ffn_hidden_size": 768,
    "moe_num_active_primary_experts": 6, "moe_num_primary_experts": 64,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "num_attention_heads": 28, "num_hidden_layers": 52,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_layout": PERIOD * 13, "rope_scaling": None, "rope_theta": 1500000,
    "sliding_window_layout": PERIOD * 13, "sliding_window_size": 4096,
    "tie_word_embeddings": False, "vocab_size": 151936}
ATTENTION = 20_971_520
EXPERT = 5_898_240
LAYER = 398_627_840
HELD = 3_286_264_320
SLOT = 117_440_512        # a slot's cache bytes at a cache_len of 16,384

TOY_CONFIG = {
    "family": "smallthinker",
    "source": "none: a toy of the smallthinker family for CPU rehearsals of "
              "the harness, never a benchmark configuration",
    "model_name": "smallthinker_toy", "vocab_size": 256, "hidden_size": 48,
    "num_hidden_layers": 8, "rope_layout": PERIOD * 2,
    "sliding_window_layout": PERIOD * 2, "sliding_window_size": 8,
    "rms_norm_eps": 1e-06, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "rope_theta": 10000,
    "rope_scaling": None, "moe_num_primary_experts": 8,
    "moe_num_active_primary_experts": 3, "moe_ffn_hidden_size": 24,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "tie_word_embeddings": False, "max_position_embeddings": 64,
    "reduced": [],
    "assumed": {"router_reads": "input", "window_keys_with_own": 8,
                "why": "SmallThinkerConfig.tiny()'s sizes: a window of 8 "
                       "rows, so that the toy's prompts wrap the window "
                       "rings inside their prefill"},
    "reference_check": {"prompt_lens": [13, 27], "follow": 3},
    "tolerance": {"serve_logits_rel_l2": 0.15, "serve_token_regret_rms": 0.5,
                  "reason": "bfloat16 compute against a float32 reference "
                            "at toy width"},
}
# (the tests' toy engine takes prompts of 16 tokens in ONE chunk, which no
# ring of 8 rows takes: chunks of 8, and prompts of up to four windows)
TOY_ENGINE = {"engine": {"max_batch": 4, "cache_len": 64,
                         "max_prompt_len": 32, "prefill_rows": 2,
                         "prefill_chunk": 8},
              "max_concurrent": 64, "trace_seconds": 1.0,
              "device_programs": {"decode": "jit_step_fn",
                                  "prefill": "jit_prefill_fn"}}
TOY_CELL = {"name": "toy_smallthinker_closed", "config": "smallthinker-toy",
            "traffic": "toy_closed", "chips": 1,
            "why": "CPU rehearsal of kind serve_closed on this family"}


@pytest.fixture(scope="module")
def config():
    return load_json(os.path.join(REPO, "benchmark", "configs",
                                  CONFIG + ".json"))


@pytest.fixture(scope="module")
def family():
    return load_module(os.path.join(REPO, "benchmark", "families",
                                    "smallthinker.py"))


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    """The tests' toy root with this family's toy added as files and
    entries, the way a PR adds a configuration."""
    root = benchmark_toy.make_root(
        str(tmp_path_factory.mktemp("smallthinker")))
    bench = os.path.join(root, "benchmark")
    for folder, name, held in (
            ("configs", "smallthinker-toy", TOY_CONFIG),
            ("deployments", "toy_window_engine", TOY_ENGINE),
            ("cells", TOY_CELL["name"], {"deployment": "toy_window_engine"})):
        with open(os.path.join(bench, folder, name + ".json"), "w") as f:
            json.dump(held, f)
    spec = load_json(os.path.join(root, "BENCHMARK.json"))
    spec["configs"].append({
        "name": "smallthinker-toy", "source": TOY_CONFIG["source"],
        "file": "benchmark/configs/smallthinker-toy.json", "reduced": [],
        "why": "CPU rehearsal"})
    spec["workloads"].append(TOY_CELL)
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "toy_closed" in m.get("workloads", []) \
                or CELL in m.get("workloads", []):
            m["workloads"] = m["workloads"] + [TOY_CELL["name"]]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    return root


def test_the_file_holds_the_catalog_rows_config(config):
    """Every key of the row's ``config`` is in the file, equal, except the
    four in ``reduced``; those state the published value beside the held
    one. No width is among them."""
    assert config["reduced"] == REDUCED
    assert config["source"] == "https://huggingface.co/PowerInfer/" \
        "SmallThinker-21BA3B-Instruct/blob/main/config.json"
    for key, value in PUBLISHED.items():
        if key not in REDUCED:
            assert config[key] == value, key
        else:
            assert config[key + "_published"] == value, key
    # one pipeline stage of eight: two whole periods, every expert, an
    # eighth of both tables; the guide's floors (a period and four layers,
    # 8 experts, an eighth)
    assert config["num_hidden_layers"] == 8
    assert config["rope_layout"] == config["sliding_window_layout"] \
        == PERIOD * 2 == PUBLISHED["rope_layout"][:8]
    assert config["vocab_size"] * 8 == config["vocab_size_published"]
    assert config["moe_num_primary_experts"] == 64
    a = config["assumed"]
    assert a["router_reads"] == "input" and a["window_keys_with_own"] == 4096
    assert set(a["init_gains"]) == {"embed", "q", "k", "v", "o", "router",
                                    "expert_in", "expert_down", "head"}
    for why in ("router_reads_why", "window_keys_with_own_why",
                "secondary_experts_why", "rotary_lanes_why",
                "init_gains_why"):
        assert len(a[why]) > 40, why
    assert "llama.cpp" in a["router_reads_why"]
    deployment = config["deployment"].lower()
    for said in ("one v5e-8 host as eight pipeline stages",
                 "eight row slices of 18,992", "3,286,264,320",
                 "117.4 mb", "12.3 gb", "why not the whole vocabulary",
                 "micro-batches", "floors kept"):
        assert said in deployment, said
    assert "param_dtype" not in json.dumps(config)
    assert "bfloat16 weights" in config["computes_in"]
    assert config["reference_check"]["follow"] == 8
    short, long = config["reference_check"]["prompt_lens"]
    assert short < 4096 and long > 2 * 4096 and short % 256 and long % 256
    assert set(config["tolerance"]) == {
        "serve_logits_rel_l2", "serve_token_regret_rms", "reason"}


def test_counts_by_hand(config, family):
    """ISSUE 51's arithmetic, reckoned again by the family file."""
    sh = family.shape(config)
    assert (sh["n_global"], sh["n_window"], sh["window"]) == (2, 6, 4096)
    assert sh["attention_params"] == 2 * 2560 * 3584 + 2 * 2560 * 512 \
        == ATTENTION
    assert sh["router_params"] == 163_840
    assert sh["expert_params"] == 3 * 2560 * 768 == EXPERT
    assert ATTENTION + 163_840 + 64 * EXPERT + 5_120 == LAYER
    assert family.param_count(config) \
        == 8 * LAYER + 2 * 18_992 * 2_560 + 2_560 == HELD
    whole = {**config, "num_hidden_layers": 52, "vocab_size": 151936,
             "rope_layout": PERIOD * 13, "sliding_window_layout": PERIOD * 13}
    assert family.param_count(whole) \
        == 52 * LAYER + 2 * 151_936 * 2_560 + 2_560 == 21_506_562_560
    # a slot: 2,048 B a token a layer, two layers of cache_len rows and six
    # of 4,096 whatever cache_len is
    assert sh["kv_bytes_per_layer_token"] == 2_048
    assert family.cache_bytes(config, 1, 16384) \
        == 2 * 16_384 * 2_048 + 6 * 4_096 * 2_048 == SLOT
    assert family.cache_bytes(config, 49, 16384) == 49 * SLOT \
        == 5_754_585_088
    assert family.cache_bytes(config, 1, 32768) - SLOT == 2 * 16_384 * 2_048
    # one ring length for all eight layers: 268 MB a slot, 21 slots
    assert 8 * 16_384 * 2_048 == 268_435_456
    for refused, args in ((family.train_flops_per_token, (config,)),
                          (family.attention_calls, (config, 16)),
                          (family.build_train, (config, None))):
        with pytest.raises(NotImplementedError, match="no training cell"):
            refused(*args)


def test_system_config_is_the_files_and_refuses_what_does_not_run(
        config, family):
    from ray_tpu.models.smallthinker import GAINS, SmallThinkerConfig

    cfg = family.system_config(config)
    assert cfg == SmallThinkerConfig(vocab_size=18992,
                                     window_layout=tuple(PERIOD * 2))
    assert dict(cfg.gains) == config["assumed"]["init_gains"] == dict(GAINS)
    assert cfg.dtype == cfg.param_dtype == jnp.bfloat16
    assert (cfg.n_experts, cfg.top_k, cfg.window, cfg.rope_theta) \
        == (64, 6, 4096, 1.5e6)
    with pytest.raises(ValueError, match="tie_word_embeddings"):
        family.system_config({**config, "tie_word_embeddings": True})
    with pytest.raises(ValueError, match="norm_topk_prob"):
        family.system_config({**config, "norm_topk_prob": False})
    with pytest.raises(ValueError, match="router_reads"):
        family.system_config({**config, "assumed": {
            **config["assumed"], "router_reads": "normed_input"}})
    with pytest.raises(ValueError, match="window_keys_with_own"):
        family.system_config({**config, "assumed": {
            **config["assumed"], "window_keys_with_own": 4097}})
    with pytest.raises(ValueError, match="rope_layout"):
        family.system_config({**config, "rope_layout": [1] * 8})
    with pytest.raises(ValueError, match="rope_layout"):
        family.system_config({**config, "num_hidden_layers": 12})
    kw = family.reference_kwargs(config)
    assert kw["rotates"] == kw["windows"] == (False, True, True, True) * 2
    assert (kw["window"], kw["top_k"], kw["router_reads"]) \
        == (4096, 6, "input")


@pytest.mark.parametrize("name, root_of", [
    ("smallthinker-toy", "toy"), (CONFIG, "repository")])
def test_counts_agree_with_the_arrays_the_system_makes(toy_root, name,
                                                       root_of):
    """``param_count`` and ``cache_bytes`` against the shapes of what
    ``smallthinker_init`` / ``smallthinker_init_cache`` make (by
    ``eval_shape``), and ``engine_memory`` reading 2 bytes a parameter."""
    root = toy_root if root_of == "toy" else REPO
    config = load_json(os.path.join(root, "benchmark", "configs",
                                    name + ".json"))
    family = load_module(os.path.join(root, "benchmark", "families",
                                      "smallthinker.py"))
    common = load_module(os.path.join(root, "benchmark", "kinds",
                                      "serve_common.py"))
    engine = {"max_batch": 4, "cache_len": 64} if root_of == "toy" \
        else load_json(os.path.join(
            REPO, "benchmark", "deployments",
            "smallthinker_1chip_b48.json"))["engine"]
    from ray_tpu.serve.llm_engine import _model_bundle

    bind = family.engine_bind(config, engine, 3)
    assert bind["model"] == "smallthinker"
    cfg, init, init_cache, _, _ = _model_bundle(
        bind["model"], bind["config"], "tiny")
    params = jax.eval_shape(lambda: init(jax.random.PRNGKey(3), cfg))
    cache = jax.eval_shape(lambda: init_cache(
        cfg, engine["max_batch"] + 1, engine["cache_len"]))
    nbytes = lambda tree: sum(x.size * x.dtype.itemsize
                              for x in jax.tree.leaves(tree))
    n_params = sum(x.size for x in jax.tree.leaves(params))
    assert family.param_count(config) == n_params
    # (the cache's one int32 counter is not a slot's)
    assert family.cache_bytes(config, engine["max_batch"] + 1,
                              engine["cache_len"]) == nbytes(cache) - 4
    assert nbytes(params) == 2 * n_params  # bfloat16, every leaf
    stats = cfg.serving_stats()
    sh = family.shape(config)
    assert stats["kv_bytes_per_token"] \
        == sh["n_global"] * sh["kv_bytes_per_layer_token"]
    assert stats["window_kv_bytes_per_token"] \
        == sh["n_window"] * sh["kv_bytes_per_layer_token"]
    assert stats["window_rows"] == sh["window"]
    said = []
    run = types.SimpleNamespace(
        family=family, config=config,
        say=lambda event, **f: said.append((event, f)))
    held = nbytes(params) + nbytes(cache)
    assert common._weight_bytes(run, held, engine) == 2.0 * n_params
    assert said[0][1]["bytes_per_param"] == 2
    if root_of == "repository":  # what the cell holds at rest: 12.33 GB
        assert n_params == HELD
        assert held == 2 * HELD + 49 * SLOT + 4 == 12_327_113_732
        assert held > 11e9 and held / 16e9 > 0.77


def test_to_reference_hands_the_leaves_over_as_they_are_stored(toy_root):
    config = load_json(os.path.join(toy_root, "benchmark", "configs",
                                    "smallthinker-toy.json"))
    family = load_module(os.path.join(toy_root, "benchmark", "families",
                                      "smallthinker.py"))
    reference = load_module(os.path.join(toy_root, "benchmark", "reference",
                                         "smallthinker.py"))
    params = family.init_params(config, 5)
    ref = family.to_reference(params, config)
    assert sum(x.size for x in jax.tree.leaves(ref)) \
        == family.param_count(config)
    assert all(x.dtype == jnp.bfloat16 for x in jax.tree.leaves(ref))
    layer = ref["layers"][0]
    assert {"q_proj", "k_proj", "v_proj", "o_proj", "router", "experts_gate",
            "experts_up", "experts_down", "input_layernorm",
            "post_attention_layernorm"} == set(layer)
    assert layer["experts_gate"].shape == layer["experts_up"].shape \
        == (8, 48, 24) and layer["experts_down"].shape == (8, 24, 48)
    tokens = jnp.asarray(jax.random.randint(
        jax.random.PRNGKey(2), (1, 24), 0, 256))
    logits = reference.forward(ref, tokens,
                               **family.reference_kwargs(config))
    assert logits.dtype == jnp.float32 and logits.shape == (1, 24, 256)
    # the serving path in bfloat16 against it, through the cache: a prompt
    # of two windows and five tokens in chunks of one window, then steps
    got = family.serve_logits(
        config, params, jnp.pad(tokens[:, :21], ((0, 0), (0, 11))),
        jnp.asarray([21]), tokens[:, 21:], slots=2, cache_len=32)
    err = jnp.linalg.norm(got[0] - logits[0, 20:], axis=-1) \
        / jnp.linalg.norm(logits[0, 20:], axis=-1)
    assert got.shape == (1, 4, 256) and float(err.max()) < 0.15
    loss, gnorm = jax.jit(lambda p: reference.loss_and_grad_norm(
        p, tokens, **family.reference_kwargs(config)))(ref)
    assert 4.0 < float(loss) < 9.0 and 0 < float(gnorm) < 1e3


def test_the_seeded_draw_makes_every_branch_some_tenths_of_the_stream(
        toy_root):
    """``assumed.init_gains``: in the first global and the first window
    layer both branches are within a factor of ten of the stream, and the
    router's six (here three) weights are neither flat nor one-hot."""
    config = load_json(os.path.join(toy_root, "benchmark", "configs",
                                    "smallthinker-toy.json"))
    family = load_module(os.path.join(toy_root, "benchmark", "families",
                                      "smallthinker.py"))
    tokens = jnp.asarray(jax.random.randint(
        jax.random.PRNGKey(0), (2, 48), 0, 256))
    got = family.branch_readings(config, family.init_params(config, 5),
                                 tokens)
    assert set(got) == {"global", "window"}
    assert 0.8 < got["global"]["stream_rms"] < 1.2
    for kind, readings in got.items():
        for branch in ("attention_rms", "routed_rms"):
            share = readings[branch] / readings["stream_rms"]
            assert 0.1 < share < 10.0, (kind, branch, readings)
        assert 1.0 < readings["router_logit_spread"] < 8.0
        assert 1 / 3 < readings["largest_weight_mean"] < 0.95
        assert 0.005 < readings["smallest_weight_mean"] < 1 / 3


def test_decode_step_bytes_on_hand_made_counters(config, family):
    n = family.param_count(config)
    weights = 2.0 * n
    embed = 18_992 * 2_560
    dense = n - 8 * 64 * EXPERT - embed
    # 300 of the 512 experts a step, 40 slots at a mean context of 3,000:
    # every ring's live rows, the window not reached
    counters = {"open": {"steps": 100, "experts_hit": 1_000},
                "close": {"steps": 300, "experts_hit": 61_000}}
    got = family.decode_step_bytes(config, weights, 40.0, 3000.0, counters)
    assert got == pytest.approx(
        2.0 * (dense + 300 * EXPERT + 40 * 2_560)
        + 40 * 8 * 3000 * 2_048)
    # past the window a window ring gives 4,096 rows and no more
    far = family.decode_step_bytes(config, weights, 40.0, 9000.0, counters)
    assert far - got == pytest.approx(
        40 * 2_048 * (2 * 6000 + 6 * (4096 - 3000)))
    # no counters (the parent's line): every expert
    every = family.decode_step_bytes(config, weights, 40.0, 3000.0, {})
    assert every - got == pytest.approx(2.0 * (512 - 300) * EXPERT)
    assert every < weights + 40 * 8 * 3000 * 2_048


def test_a_window_chunks_work_and_a_chunks_by_hand(config, family):
    ops, io = family.window_chunk_attention_work(config, 256)
    assert ops == 6 * 4.0 * 28 * 256 * 4352 * 128 == 6 * 15_971_909_632
    assert io == 6 * (4352 * 2_048 + 2 * 256 * 3_584 * 2)
    # compute-bound on a v5e: 0.49 ms over six layers
    assert ops / 197e12 > io / 819e9
    weights = 2.0 * family.param_count(config)
    row = 2.0 * 2_560
    ops, io = family.prefill_chunk_work(config, weights, 256.0, 256.0 * 6 * 8,
                                        mean_keys=6000.0, last_share=0.25)
    keys = 2 * 6000.0 + 6 * 4096
    assert io == pytest.approx(
        weights - row * (18_992 - 256) - 0.75 * row * 18_992 + keys * 2_048)
    assert ops == pytest.approx(
        2.0 * 256 * 8 * (ATTENTION + 163_840) + 2.0 * 256 * 48 * EXPERT
        + 256 * 4.0 * 3_584 * keys + 0.25 * 2.0 * 18_992 * 2_560)
    # a short prompt's keys are the same in both kinds of layer
    short = family.prefill_chunk_work(config, weights, 256.0, 0.0, 1000.0)
    assert short[0] == pytest.approx(
        2.0 * 256 * 8 * (ATTENTION + 163_840) + 256 * 4.0 * 3_584 * 8000
        + 2.0 * 18_992 * 2_560)


def hand_run(family, config, counters, ops=()):
    """Three executions of the decode program (0.01 s each) around two of
    the prefill program in a 0.1 s window; ``ops`` are the program trace's
    operations (name, start, end, scope path)."""
    said = []
    modules = [("jit_step_fn(1)", 0.00, 0.01),
               ("jit_prefill_fn(2)", 0.02, 0.04),
               ("jit_step_fn(1)", 0.04, 0.05),
               ("jit_prefill_fn(2)", 0.05, 0.08),
               ("jit_step_fn(1)", 0.08, 0.09)]
    tr = {"window": (0.0, 0.1), "host": [], "devices": [{
        "name": "/device:TPU:0", "async": [], "modules": modules,
        "ops": [("fusion.1", s, e, "fusion") for _, s, e in modules]}]}
    return types.SimpleNamespace(
        trace=tr, family=family, config=config, counters=counters,
        raw={"weight_bytes": 2.0 * family.param_count(config),
             "requests": []},
        params={"device_programs": {"decode": "jit_step_fn",
                                    "prefill": "jit_prefill_fn"}},
        device_kind="TPU v5 lite", window_ns=(0, 100),
        program_trace={"host": [], "ops": sorted(ops, key=lambda o: o[1]),
                       "modules": modules, "window": (0.0, 0.1)},
        trace_on=True, said=said,
        say=lambda event, **f: said.append((event, f)))


def scoped_ops(program, at, parts):
    """One execution's operations from ``at`` on: (scope path, ms) each."""
    out, t = [], at
    for scope, ms in parts:
        path = f"jit({program})/jit(main)/{scope}/fusion" if scope else ""
        out.append((f"fusion.{len(out)}", t, t + ms * 1e-3, path))
        t += ms * 1e-3
    return out


def test_the_four_readers_on_hand_made_runs(config, family):
    share = load_module(os.path.join(
        METRICS, "decode_window_attention_time_pct.py"))
    chunk = load_module(os.path.join(
        METRICS, "prefill_window_attention_time_pct.py"))
    roofline = load_module(os.path.join(
        METRICS, "window_chunk_attention_roofline.py"))
    rows = load_module(os.path.join(METRICS, "window_ring_rows_read_pct.py"))
    counters = {
        "open": {"steps": 100, "window_rows_read": 1_000_000,
                 "window_rows_held": 2_000_000, "prefill_chunk": 256},
        "close": {"steps": 300, "window_rows_read": 151_000_000,
                  "window_rows_held": 242_000_000, "prefill_chunk": 256}}
    assert rows.read(hand_run(family, config, counters)) \
        == pytest.approx(62.5)
    # a step: 1.5 ms under attn/attn_window, 1 ms under attn/attn_global,
    # 5 ms of experts, 1 ms of cache writes, 0.5 ms under no scope of ours
    step = [("attn/attn_window", 1.5), ("attn/attn_global", 1.0),
            ("experts", 5.0), ("cache_write", 1.0), ("", 0.5)]
    ops = [op for at in (0.0, 0.04, 0.08)
           for op in scoped_ops("step_fn", at, step)]
    run = hand_run(family, config, counters, ops)
    assert share.read(run) == pytest.approx(100 * 1.5 / 9.0)
    said = dict(run.said)["decode_by_attention_kind"]
    assert said["executions"] == 3 and said["program"] == "jit_step_fn"
    assert said["attn_window_ms"] == pytest.approx(1.5)
    assert said["attn_global_ms"] == pytest.approx(1.0)
    assert said["attn_global_pct"] == pytest.approx(100 / 9.0)
    # the chunk program: 4.5 ms of the window layers' attention, 3 ms of
    # the global layers', 6 ms of experts, 1.5 ms of the rotary
    lane = [("attn/attn_window", 4.5), ("attn/attn_global", 3.0),
            ("experts", 6.0), ("rope", 1.5)]
    chunk_ops = [op for at in (0.02, 0.05)
                 for op in scoped_ops("prefill_fn", at, lane)]
    run = hand_run(family, config, counters, ops + chunk_ops)
    assert chunk.read(run) == pytest.approx(30.0)
    assert dict(run.said)["prefill_by_attention_kind"]["executions"] == 2
    value = roofline.read(run)
    work_ops, io = family.window_chunk_attention_work(config, 256)
    assert value == pytest.approx(100 * (work_ops / 197e12) / 4.5e-3)
    said = dict(run.said)["window_chunk_attention_roofline"]
    assert said["bound_by"] == "compute" and said["executions"] == 2
    assert said["device_ms"] == pytest.approx(4.5) and said["chunk"] == 256
    assert 0 < value < 100
    assert share.read(run) == pytest.approx(100 * 1.5 / 9.0)  # unmoved
    # a program with nothing under ``attn_window`` (the parent, another
    # family), a family without the function, a run with no trace or no
    # counters: nothing to read, nothing raised
    bare = hand_run(family, config, counters,
                    [o for o in ops + chunk_ops if "attn_window" not in o[3]])
    for reader in (share, chunk, roofline):
        assert reader.read(bare) is None
    other = hand_run(family, config, counters, ops + chunk_ops)
    other.family = load_module(os.path.join(REPO, "benchmark", "families",
                                            "granite_hybrid.py"))
    assert roofline.read(other) is None
    none = hand_run(family, config, {}, ops + chunk_ops)
    assert rows.read(none) is None and roofline.read(none) is None
    none.trace = none.program_trace = None
    none.trace_on = False
    for reader in (share, chunk, roofline):
        assert reader.read(none) is None


@pytest.mark.parametrize("trace_on, names", [
    (0, {"setup_s", "serve_out_tokens_per_s"}),
    (1, {"serve_decode_step_ms_p50", "serve_batch_occupancy_pct",
         "serve_prefill_fill_pct.decode", "window_ring_rows_read_pct",
         "decode_ring_rows_read_pct"}),
])
def test_rehearsal_of_the_cells_kind_with_this_family(toy_root, capsys,
                                                      trace_on, names):
    code = bench_run.main([
        "--root", toy_root, "--workload", TOY_CELL["name"], "--seed",
        "3000000019", "--seconds", "2.5", "--trace", str(trace_on),
        "--rehearsal"])
    out = capsys.readouterr().out.strip().splitlines()
    last, earlier = json.loads(out[-1]), out[:-1]
    assert code == 0
    assert last["correct"] is True, earlier[-3:]
    assert last["attempted"] > 0 and last["failed"] == 0
    assert last["metrics"] == {}  # names, never a value, on a CPU
    assert names <= set(last["rehearsal"]["metric_names"])
    said = {json.loads(line[len("[bench] "):])["event"]: json.loads(
        line[len("[bench] "):]) for line in earlier
        if line.startswith("[bench] ")}
    # 5 slots x (two rings of 64 rows and six of 8, K and V rows of 32)
    assert said["engine_memory"]["cache_bytes"] == 5 * (
        2 * 64 + 6 * 8) * 2 * 32 * 2
    close = said["engine_counters"]["close"]
    assert close["compiles"] == {"decode": 1, "prefill": 1}
    assert close["prefill_chunk"] == 8
    assert close["prefill_expert_rows"] > 0 and close["experts_hit"] > 0
    assert close["window_rows_held"] == close["steps"] * 6 * 5 * 8
    assert close["ring_rows_held"] == close["steps"] * 5 * (6 * 8 + 2 * 64)
    assert (close["expert_layers"], close["experts_held"],
            close["global_layers"], close["window_layers"],
            close["window_rows"]) == (8, 8, 2, 6, 8)


@pytest.mark.parametrize("control", [
    "ring_not_wrapped", "padded_rows_written", "window_ignored",
    "global_rotated"])
def test_the_window_controls_fail_the_toys_limit(toy_root, capsys,
                                                 monkeypatch, control):
    """``tools/serve_check_window.py`` turns one mechanism the other way,
    in the SYSTEM or in the reference: with it the logits comparison fails,
    without it the same seed passes. The toy's prompts of 13 and 27 tokens
    wrap rings of 8 rows once and three times, in chunks of 8, and end off
    a chunk boundary: both controls turned in the system and two of those
    turned in the reference (the tool's other four go the same way through
    ``reference_kwargs``; ``tests/test_smallthinker.py`` holds every one at
    this size). (``router_normed_input`` is no control on SEEDED
    weights, whose norms are all ones over a stream of rms one, and
    ``silu_for_relu`` reads under the toy's wide limit on this seed:
    ``tests/test_smallthinker.py`` holds both, the first with the norms
    drawn away.)"""
    from ray_tpu.models import smallthinker
    from ray_tpu.ops import attention
    from ray_tpu.serve import llm_engine

    tool = load_module(os.path.join(REPO, "benchmark", "tools",
                                    "serve_check_window.py"))
    monkeypatch.setattr(llm_engine, "_model_bundle",
                        llm_engine._model_bundle)  # put back
    monkeypatch.setattr(tool.many, "patch", tool.many.patch)
    monkeypatch.setattr(attention, "ring_positions",
                        attention.ring_positions)
    monkeypatch.setattr(smallthinker, "cache_write_ring_chunk",
                        smallthinker.cache_write_ring_chunk)
    # (the harness keeps one module a file, so what the tool patches into
    # the toy root's family would stand for the next test)
    toy_family = load_module(os.path.join(toy_root, "benchmark", "families",
                                          "smallthinker.py"))
    for name in ("reference_kwargs", "serve_logits"):
        monkeypatch.setattr(toy_family, name, getattr(toy_family, name))
    args = ["--root", toy_root, "--workload", TOY_CELL["name"],
            "--seeds", "1", "--first-seed", "3000000023", "--rehearsal"]
    assert tool.many.main(args) == 0
    clean = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert clean["failed"] == 0 and clean["largest"] < 0.15
    assert tool.main(["--control", control] + args) == 0
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert last["failed"] == 1 and last["largest"] > 0.15


def test_the_cell_and_its_files(config):
    spec = load_json(os.path.join(REPO, "BENCHMARK.json"))
    cell = [w for w in spec["workloads"] if w["name"] == CELL]
    assert cell == [{**cell[0], "config": CONFIG, "chips": 1,
                     "traffic": "mixed_window_closed"}]
    assert "48 slots" in cell[0]["why"] and "4096" in cell[0]["why"]
    entry = [c for c in spec["configs"] if c["name"] == CONFIG]
    assert entry == [{**entry[0], "reduced": REDUCED,
                      "source": config["source"],
                      "file": f"benchmark/configs/{CONFIG}.json"}]
    assert load_json(os.path.join(
        REPO, "benchmark", "cells", CELL + ".json")) \
        == {"deployment": "smallthinker_1chip_b48"}
    traffic = load_json(os.path.join(REPO, "benchmark", "traffic",
                                     "mixed_window_closed.json"))
    assert traffic["kind"] == "serve_closed"
    assert (traffic["clients_per_slot"], traffic["pool_requests"],
            traffic["sizes_seed"]) == (2, 4096, 20261002)
    assert traffic["prompt_len"] == {"dist": "log_uniform", "min": 512,
                                     "max": 14336}
    assert traffic["max_tokens"] == {"dist": "log_normal", "median": 768,
                                     "sigma": 0.5, "min": 256, "max": 2048}
    deployment = load_json(os.path.join(
        REPO, "benchmark", "deployments", "smallthinker_1chip_b48.json"))
    assert deployment["engine"] == {
        "max_batch": 48, "cache_len": 16384, "max_prompt_len": 14336,
        "prefill_rows": 4, "max_new_cap": 2048}
    assert deployment["trace_seconds"] == 5.0
    assert "117.4 MB" in deployment["what"]
    # the longest request is the published 16,384 positions and fits the
    # global rings without a wrap; a prompt may be three and a half windows
    assert traffic["prompt_len"]["max"] + traffic["max_tokens"]["max"] \
        == deployment["engine"]["cache_len"] \
        == config["max_position_embeddings"]
    assert traffic["prompt_len"]["max"] == 3.5 * config["sliding_window_size"]
    # the pool's means: about 4,150 tokens in (17 chunks), about 860 out,
    # nearly two prompts in five past the window
    common = load_module(os.path.join(REPO, "benchmark", "kinds",
                                      "serve_common.py"))
    lens, new = common.draw_sizes(traffic, 4096)
    assert 3950 < lens.mean() < 4350 and 820 < new.mean() < 900
    assert 0.33 < (lens > 4096).mean() < 0.43
    reports = {m["name"] for m in spec["end_to_end"]
               if CELL in m.get("workloads", [CELL])}
    # (a later PR may report more on this cell, list further cells on the
    # metrics below and append metrics of its own: nothing here pins a
    # list to this cell alone or to the end of the file)
    assert {"serve_out_tokens_per_s", "setup_s"} <= reports
    per_layer = {m["name"]: m for m in spec["per_layer"]}
    new_ones = {"decode_window_attention_time_pct": "device_trace",
                "prefill_window_attention_time_pct": "device_trace",
                "window_chunk_attention_roofline": "device_trace",
                "window_ring_rows_read_pct": "program_counter"}
    for name in ("serve_decode_step_ms_p50", "serve_batch_occupancy_pct",
                 "decode_step_roofline", "serve_device_idle_pct.decode",
                 "serve_step_host_ms_p50",
                 "serve_idle_attributed_pct.decode",
                 "serve_prefill_fill_pct.decode",
                 "decode_attention_time_pct", "serve_sync_overshoot_ms_p50",
                 "serve_deliver_lag_ms_mean", "serve_polls_per_chunk",
                 "serve_poll_rpc_ms_p50", "prefill_chunk_roofline",
                 "serve_prefill_device_pct", "decode_ring_rows_read_pct",
                 *new_ones):
        assert CELL in per_layer[name]["workloads"], name
        assert per_layer[name]["moves"] == "serve_out_tokens_per_s"
    for name, source in new_ones.items():
        assert per_layer[name]["source"] == source
        assert per_layer[name]["unit"] == "%"
        assert os.path.exists(os.path.join(METRICS, name + ".py"))
    # ten cells, one of them on four chips
    assert len(spec["workloads"]) >= 10
    assert sum(w["chips"] == 4 for w in spec["workloads"]) == 1
