"""The ``exaone_moe`` family's benchmark files: its configuration file
against the catalog row it was copied from, its counts against the arrays
the system makes, the bytes of a verify-and-draft step on hand-made
counters, a step's attention and a prefill chunk's work by hand, the four
new readers on hand-made runs, the draft tool, and a CPU rehearsal of the
cell's kind with a toy configuration of this family added to the tests' toy
root AS FILES AND ENTRIES (no tiny override lives in the benchmark
itself)."""

import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import benchmark_toy
from benchmark import run as bench_run
from benchmark.loading import load_json, load_module

REPO = benchmark_toy.REPO
METRICS = os.path.join(REPO, "benchmark", "metrics")
CONFIG = "k-exaone-236b-a23b"
CELL = "serve_kexaone_selfdraft_sat"
REDUCED = ["num_hidden_layers", "layer_types", "mlp_layer_types",
           "sliding_windows", "num_experts", "vocab_size"]
L, G = "sliding_attention", "full_attention"
# The catalog row's ``config`` (guides/model-configs/architectures.jsonl,
# K-EXAONE-236B-A23B), copied here so that the test needs no file outside
# the repository.
PUBLISHED = {
    "first_k_dense_replace": 1, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 6144, "intermediate_size": 18432,
    "layer_types": [L, L, L, G] * 12, "max_position_embeddings": 262144,
    "mlp_layer_types": ["dense"] + ["sparse"] * 47,
    "model_type": "exaone_moe", "moe_intermediate_size": 2048,
    "mtp_layer_types": [G], "mtp_sliding_windows": [0], "n_group": 1,
    "norm_topk_prob": True, "num_attention_heads": 64, "num_experts": 128,
    "num_experts_per_tok": 8, "num_hidden_layers": 48,
    "num_key_value_heads": 8, "num_nextn_predict_layers": 1,
    "num_shared_experts": 1, "rms_norm_eps": 1e-05,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "sliding_window": 128, "sliding_window_pattern": "LLLG",
    "sliding_windows": [128, 128, 128, 0] * 12,
    "tie_word_embeddings": False, "topk_group": 1, "vocab_size": 153600}
ATTENTION = 113_246_208
DENSE_FF = 339_738_624
EXPERT = 37_748_736
ROUTER = 786_432
SPARSE_LAYER = 755_761_152   # attention, router, 16 experts, the shared one
DENSE_LAYER = 452_984_832
MODULE = 528_482_304
TABLES = 235_929_600
MATRICES = 4_240_441_344     # ISSUE 54's count: the matrices alone
# the norms and the selection biases beside them: six blocks' two sublayer
# norms and two head norms, the module's three norms, the last norm, four
# biases of 128
SMALL = 6 * (2 * 6144 + 2 * 128) + 3 * 6144 + 6144 + 4 * 128
HELD = MATRICES + SMALL
SLOT = 2 * 8192 * 4096 + 4 * 128 * 4096  # a slot's cache bytes at 8,192

TOY_CONFIG = {
    "family": "exaone_moe",
    "source": "none: a toy of the exaone_moe family for CPU rehearsals of "
              "the harness, never a benchmark configuration",
    "model_type": "exaone_moe", "vocab_size": 256, "hidden_size": 48,
    "num_hidden_layers": 6, "layer_types": [L, L, L, G, L, L],
    "mlp_layer_types": ["dense"] + ["sparse"] * 5,
    "sliding_windows": [8, 8, 8, 0, 8, 8], "sliding_window": 8,
    "first_k_dense_replace": 1, "intermediate_size": 96,
    "moe_intermediate_size": 24, "num_experts": 4,
    "num_experts_published": 8, "num_experts_per_tok": 3,
    "num_shared_experts": 1, "n_group": 1, "topk_group": 1,
    "norm_topk_prob": True, "scoring_func": "sigmoid",
    "routed_scaling_factor": 2.5, "hidden_act": "silu",
    "rms_norm_eps": 1e-05, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16,
    "rope_parameters": {"rope_theta": 10000, "rope_type": "default"},
    "num_nextn_predict_layers": 1, "mtp_layer_types": [G],
    "mtp_sliding_windows": [0], "tie_word_embeddings": False,
    "max_position_embeddings": 64,
    "reduced": [],
    "assumed": {"norm_placement": "post", "window_keys_with_own": 8,
                "mtp": "served: depth-1 self-draft", "mtp_block": "dense",
                "why": "ExaoneMoeConfig.tiny()'s sizes: a window of 8 "
                       "rows, so that the toy's prompts wrap the window "
                       "rings inside their prefill and its verify steps "
                       "run across a wrap"},
    "reference_check": {"prompt_lens": [13, 27], "follow": 5},
    "tolerance": {"serve_logits_rel_l2": 0.15, "serve_token_regret_rms": 0.5,
                  "reason": "bfloat16 compute against a float32 reference "
                            "at toy width"},
}
TOY_ENGINE = {"engine": {"max_batch": 4, "cache_len": 64,
                         "max_prompt_len": 32, "prefill_rows": 2,
                         "prefill_chunk": 16},
              "max_concurrent": 64, "trace_seconds": 1.0,
              "device_programs": {"decode": "jit_verify_fn",
                                  "prefill": "jit_draft_prefill_fn"}}
TOY_CELL = {"name": "toy_exaone_closed", "config": "exaone-toy",
            "traffic": "toy_closed", "chips": 1,
            "why": "CPU rehearsal of kind serve_closed on this family"}


@pytest.fixture(scope="module")
def config():
    return load_json(os.path.join(REPO, "benchmark", "configs",
                                  CONFIG + ".json"))


@pytest.fixture(scope="module")
def family():
    return load_module(os.path.join(REPO, "benchmark", "families",
                                    "exaone_moe.py"))


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    """The tests' toy root with this family's toy added as files and
    entries, the way a PR adds a configuration."""
    root = benchmark_toy.make_root(
        str(tmp_path_factory.mktemp("exaone_moe")))
    bench = os.path.join(root, "benchmark")
    for folder, name, held in (
            ("configs", "exaone-toy", TOY_CONFIG),
            ("deployments", "toy_draft_engine", TOY_ENGINE),
            ("cells", TOY_CELL["name"], {"deployment": "toy_draft_engine"})):
        with open(os.path.join(bench, folder, name + ".json"), "w") as f:
            json.dump(held, f)
    spec = load_json(os.path.join(root, "BENCHMARK.json"))
    spec["configs"].append({
        "name": "exaone-toy", "source": TOY_CONFIG["source"],
        "file": "benchmark/configs/exaone-toy.json", "reduced": [],
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
    six in ``reduced``; those state the published value beside the held
    one. No width is among them."""
    assert config["reduced"] == REDUCED
    assert config["source"] == "https://huggingface.co/LGAI-EXAONE/" \
        "K-EXAONE-236B-A23B/blob/main/config.json"
    for key, value in PUBLISHED.items():
        if key not in REDUCED:
            assert config[key] == value, key
        else:
            assert config[key + "_published"] == value, key
    # one chip of eight that share each layer by expert parallelism: the
    # leading dense layer and ONE whole period of what follows it, 16 of
    # 128 experts, an eighth of both tables; the guide's floors (a period
    # and four layers after the dense one, 8 experts, an eighth)
    assert config["num_hidden_layers"] == 5
    assert config["layer_types"] == [L, L, L, G, L] \
        == PUBLISHED["layer_types"][:5]
    assert config["layer_types"][1:] == [L, L, G, L]  # a whole period
    assert config["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    assert config["sliding_windows"] == [128, 128, 128, 0, 128]
    assert config["vocab_size"] * 8 == config["vocab_size_published"]
    assert config["vocab_size"] == 19200 == 150 * 128
    assert config["num_experts"] * 8 == config["num_experts_published"]
    assert config["num_experts_per_tok"] == 8
    a = config["assumed"]
    assert a["norm_placement"] == "post" and a["window_keys_with_own"] == 128
    assert a["mtp"] == "served: depth-1 self-draft"
    assert a["mtp_block"] == "dense" and "hnorm" in a["mtp_combine"]
    assert set(a["init_gains"]) == {"embed", "attn", "ff_in", "ff_down",
                                    "router", "expert_down", "shared_down",
                                    "eh", "head"}
    for why in ("norm_placement_why", "window_keys_with_own_why", "mtp_why",
                "mtp_block_why", "mtp_combine_why", "init_gains_why"):
        assert len(a[why]) > 40, why
    assert "exaone4" in a["norm_placement_why"]
    deployment = config["deployment"].lower()
    for said in ("8 chips share each layer by expert parallelism",
                 "4,240,441,344", "8.48 gb", "16 of 128", "19,200",
                 "floors kept", "what the cut distorts", "8x its share"):
        assert said in deployment, said
    assert "param_dtype" not in json.dumps(config)
    assert "bfloat16 weights" in config["computes_in"]
    assert config["reference_check"]["follow"] == 8
    short, long = config["reference_check"]["prompt_lens"]
    # the verify steps after each prompt run across a wrap of a window ring
    assert short < 128 < short + 8 and long > 512
    assert long // 128 != (long + 8) // 128
    assert set(config["tolerance"]) == {
        "serve_logits_rel_l2", "serve_token_regret_rms", "reason"}
    assert "serve_check_draft" in config["tolerance"]["reason"]


def test_counts_by_hand(config, family):
    """ISSUE 54's arithmetic, reckoned again by the family file."""
    sh = family.shape(config)
    assert (sh["n_global"], sh["n_window"], sh["window"]) == (1, 4, 128)
    assert (sh["dense_layers"], sh["sparse_layers"]) == (1, 4)
    assert (sh["held"], sh["router_width"]) == (16, 128)
    assert sh["attention_params"] == 2 * 6144 * 8192 + 2 * 6144 * 1024 \
        == ATTENTION
    assert sh["dense_ff_params"] == 3 * 6144 * 18432 == DENSE_FF
    assert sh["expert_params"] == sh["shared_params"] == 3 * 6144 * 2048 \
        == EXPERT
    assert sh["router_params"] == 6144 * 128 == ROUTER
    assert ATTENTION + ROUTER + 16 * EXPERT + EXPERT == SPARSE_LAYER
    assert ATTENTION + DENSE_FF == DENSE_LAYER
    assert 2 * 6144 * 6144 + ATTENTION + DENSE_FF == MODULE
    assert 2 * 19200 * 6144 == TABLES
    assert DENSE_LAYER + 4 * SPARSE_LAYER + MODULE + TABLES == MATRICES
    assert family.param_count(config) == HELD == 4_240_541_696
    # a slot: 4,096 B a token a layer (K and V, 1,024 columns each); the
    # global layer's ring and the module's of cache_len rows, four of 128
    assert sh["kv_bytes_per_layer_token"] == 4_096
    assert family.cache_bytes(config, 1, 8192) == SLOT == 69_206_016
    assert family.cache_bytes(config, 65, 8192) == 65 * SLOT \
        == 4_498_391_040
    assert family.cache_bytes(config, 1, 16384) - SLOT == 2 * 8192 * 4096
    # two periods would be 8 sparse layers: 12.1 GB with 16 experts
    assert 8 * SPARSE_LAYER * 2 / 1e9 == pytest.approx(12.09, abs=0.01)
    for refused, args in ((family.train_flops_per_token, (config,)),
                          (family.attention_calls, (config, 16)),
                          (family.build_train, (config, None))):
        with pytest.raises(NotImplementedError, match="no training cell"):
            refused(*args)


def test_system_config_is_the_files_and_refuses_what_does_not_run(
        config, family):
    from ray_tpu.models.exaone_moe import GAINS, ExaoneMoeConfig

    cfg = family.system_config(config)
    assert cfg == ExaoneMoeConfig(
        vocab_size=19200, window_layout=(1, 1, 1, 0, 1),
        experts_held=(0, 16))
    assert dict(cfg.gains) == config["assumed"]["init_gains"] == dict(GAINS)
    assert cfg.dtype == cfg.param_dtype == jnp.bfloat16
    assert (cfg.n_experts, cfg.top_k, cfg.n_held, cfg.window, cfg.rope_theta,
            cfg.routed_scale, cfg.dense_layers) \
        == (128, 8, 16, 128, 1e6, 2.5, 1)
    for key, value in (("tie_word_embeddings", True),
                       ("norm_topk_prob", False), ("scoring_func", "softmax"),
                       ("n_group", 8), ("num_nextn_predict_layers", 0),
                       ("mtp_layer_types", [L])):
        with pytest.raises(ValueError, match=key):
            family.system_config({**config, key: value})
    with pytest.raises(ValueError, match="norm_placement"):
        family.system_config({**config, "assumed": {
            **config["assumed"], "norm_placement": "pre"}})
    with pytest.raises(ValueError, match="window_keys_with_own"):
        family.system_config({**config, "assumed": {
            **config["assumed"], "window_keys_with_own": 129}})
    with pytest.raises(ValueError, match="layer_types"):
        family.system_config({**config, "num_hidden_layers": 6})
    with pytest.raises(ValueError, match="layer_types"):
        family.system_config({**config,
                              "sliding_windows": [128, 128, 128, 128, 128]})
    kw = family.reference_kwargs(config)
    assert kw["rotates"] == kw["windows"] == (True, True, True, False, True)
    assert (kw["window"], kw["top_k"], kw["routed_scale"],
            kw["norm_placement"], kw["first_expert"]) \
        == (128, 8, 2.5, "post", 0)


@pytest.mark.parametrize("name, root_of", [
    ("exaone-toy", "toy"), (CONFIG, "repository")])
def test_counts_agree_with_the_arrays_the_system_makes(toy_root, name,
                                                       root_of):
    """``param_count`` and ``cache_bytes`` against the shapes of what
    ``exaone_moe_init`` / ``exaone_moe_init_cache`` make (by
    ``eval_shape``), the module and both stacks of rings counted, and
    ``engine_memory`` reading 2 bytes a parameter."""
    root = toy_root if root_of == "toy" else REPO
    config = load_json(os.path.join(root, "benchmark", "configs",
                                    name + ".json"))
    family = load_module(os.path.join(root, "benchmark", "families",
                                      "exaone_moe.py"))
    common = load_module(os.path.join(root, "benchmark", "kinds",
                                      "serve_common.py"))
    engine = {"max_batch": 4, "cache_len": 64} if root_of == "toy" \
        else load_json(os.path.join(
            REPO, "benchmark", "deployments",
            "kexaone_1chip_b64.json"))["engine"]
    from ray_tpu.serve.llm_engine import _model_bundle

    bind = family.engine_bind(config, engine, 3)
    assert bind["model"] == "exaone_moe"
    cfg, init, init_cache, _, _, verify = _model_bundle(
        bind["model"], bind["config"], "tiny")
    assert verify.__name__ == "exaone_moe_verify_step"
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
        == (sh["n_global"] + 1) * sh["kv_bytes_per_layer_token"]
    assert stats["window_kv_bytes_per_token"] \
        == sh["n_window"] * sh["kv_bytes_per_layer_token"]
    assert stats["window_rows"] == sh["window"]
    assert (stats["expert_layers"], stats["experts_held"]) \
        == (sh["sparse_layers"], sh["held"])
    said = []
    run = types.SimpleNamespace(
        family=family, config=config,
        say=lambda event, **f: said.append((event, f)))
    held = nbytes(params) + nbytes(cache)
    assert common._weight_bytes(run, held, engine) == 2.0 * n_params
    assert said[0][1]["bytes_per_param"] == 2
    if root_of == "repository":  # what the cell holds at rest: 12.98 GB
        assert n_params == HELD
        assert held == 2 * HELD + 65 * SLOT + 4 == 12_979_474_436
        assert held / 16e9 > 0.8


def test_to_reference_hands_the_leaves_over_as_they_are_stored(toy_root):
    config = load_json(os.path.join(toy_root, "benchmark", "configs",
                                    "exaone-toy.json"))
    family = load_module(os.path.join(toy_root, "benchmark", "families",
                                      "exaone_moe.py"))
    reference = load_module(os.path.join(toy_root, "benchmark", "reference",
                                         "exaone_moe.py"))
    params = family.init_params(config, 5)
    ref = family.to_reference(params, config)
    assert sum(x.size for x in jax.tree.leaves(ref)) \
        == family.param_count(config)
    assert all(x.dtype == jnp.bfloat16 for x in jax.tree.leaves(ref))
    dense, sparse = ref["layers"][0], ref["layers"][1]
    shared = {"q_proj", "k_proj", "v_proj", "o_proj", "q_norm", "k_norm",
              "post_attention_layernorm", "post_feedforward_layernorm"}
    assert set(dense) == shared | {"gate_proj", "up_proj", "down_proj"}
    assert set(sparse) == shared | {
        "router", "e_score_correction_bias", "experts_gate", "experts_up",
        "experts_down", "shared_gate", "shared_up", "shared_down"}
    assert sparse["experts_gate"].shape == sparse["experts_up"].shape \
        == (4, 48, 24) and sparse["experts_down"].shape == (4, 24, 48)
    assert sparse["router"].shape == (48, 8)  # the published width
    assert set(ref["module"]) == {"hnorm", "enorm", "eh_proj", "norm",
                                  "block"}
    assert set(ref["module"]["block"]) == set(dense)
    tokens = jnp.asarray(jax.random.randint(
        jax.random.PRNGKey(2), (1, 26), 0, 256))
    kw = family.reference_kwargs(config)
    # (under jit: the plain functions op by op take several times as long)
    logits = jax.jit(lambda r: reference.forward(r, tokens, **kw))(ref)
    assert logits.dtype == jnp.float32 and logits.shape == (1, 26, 256)
    assert jax.eval_shape(lambda r: reference.module_forward(
        r, tokens, **kw), ref).shape == (1, 25, 256)
    # the serving path in bfloat16 against it, through the cache: a prompt
    # of two windows and five tokens in chunks of two windows, then verify
    # steps with accepted and rejected drafts
    got = family.serve_logits(
        config, params, jnp.pad(tokens[:, :21], ((0, 0), (0, 11))),
        jnp.asarray([21]), tokens[:, 21:], slots=2, cache_len=32)
    err = jnp.linalg.norm(got[0] - logits[0, 20:], axis=-1) \
        / jnp.linalg.norm(logits[0, 20:], axis=-1)
    assert got.shape == (1, 6, 256) and float(err.max()) < 0.15
    loss, gnorm = jax.jit(lambda p: reference.loss_and_grad_norm(
        p, tokens, **kw))(ref)
    assert 4.0 < float(loss) < 9.0 and 0 < float(gnorm) < 1e3


def test_decode_step_bytes_on_hand_made_counters(config, family):
    n = family.param_count(config)
    weights = 2.0 * n
    embed = 19_200 * 6_144
    dense = n - 4 * 16 * EXPERT - embed
    # 40 of the 64 held experts a step, 60 slots at a mean context of
    # 3,000: two full rings' live rows and four whole window rings, each
    # read once for a slot's two rows; four table rows a slot
    counters = {"open": {"steps": 100, "experts_hit": 1_000},
                "close": {"steps": 300, "experts_hit": 9_000}}
    got = family.decode_step_bytes(config, weights, 60.0, 3000.0, counters)
    assert got == pytest.approx(
        2.0 * (dense + 40 * EXPERT + 4 * 60 * 6_144)
        + 60 * (2 * 3000 + 4 * 128) * 4_096)
    # inside the window a window ring gives the context and no more
    near = family.decode_step_bytes(config, weights, 60.0, 100.0, counters)
    assert got - near == pytest.approx(
        60 * 4_096 * (2 * 2900 + 4 * 28))
    # no counters (the parent's line): every held expert
    every = family.decode_step_bytes(config, weights, 60.0, 3000.0, {})
    assert every - got == pytest.approx(2.0 * (64 - 40) * EXPERT)
    # the module's 0.53 G parameters are among the bytes
    assert dense > MODULE + DENSE_LAYER + 19_200 * 6_144


def test_a_steps_attention_and_a_chunks_work_by_hand(config, family):
    ops, io = family.verify_attention_work(config, 60.0, 3000.0)
    keys = 2 * 3000.0 + 4 * 128
    assert ops == pytest.approx(60 * 2 * 4.0 * 8192 * keys)
    assert io == pytest.approx(60 * (keys + 2 * 6) * 4_096)
    # memory-bound on a v5e, by far
    assert io / 819e9 > 10 * ops / 197e12
    weights = 2.0 * family.param_count(config)
    row = 2.0 * 6_144
    ops, io = family.prefill_chunk_work(config, weights, 512.0, 512.0 * 4,
                                        mean_keys=2000.0, last_share=0.25)
    keys = 2 * 2000.0 + 4 * 128
    assert io == pytest.approx(
        weights - row * (19_200 - 1024) - 0.75 * row * 19_200 + keys * 4_096)
    a_token = 6 * ATTENTION + 2 * DENSE_FF + 4 * (ROUTER + EXPERT) \
        + 2 * 6144 * 6144
    assert ops == pytest.approx(
        2.0 * 512 * a_token + 2.0 * 2048 * EXPERT
        + 512 * 4.0 * 8192 * keys + 0.25 * 2 * 2.0 * 19_200 * 6_144)


def hand_run(family, config, counters, ops=()):
    """Three executions of the decode program (0.01 s each) around two of
    the prefill program in a 0.1 s window; ``ops`` are the program trace's
    operations (name, start, end, scope path)."""
    said = []
    modules = [("jit_verify_fn(1)", 0.00, 0.01),
               ("jit_draft_prefill_fn(2)", 0.02, 0.04),
               ("jit_verify_fn(1)", 0.04, 0.05),
               ("jit_draft_prefill_fn(2)", 0.05, 0.08),
               ("jit_verify_fn(1)", 0.08, 0.09)]
    tr = {"window": (0.0, 0.1), "host": [], "devices": [{
        "name": "/device:TPU:0", "async": [], "modules": modules,
        "ops": [("fusion.1", s, e, "fusion") for _, s, e in modules]}]}
    ms = 1_000_000
    return types.SimpleNamespace(
        trace=tr, family=family, config=config, counters=counters,
        raw={"weight_bytes": 2.0 * family.param_count(config),
             "requests": [{"prompt_len": 1000, "chunk_tokens": [1, 2, 1, 2],
                           "chunk_ns": [10 * ms, 20 * ms, 30 * ms,
                                        40 * ms]}]},
        params={"device_programs": {"decode": "jit_verify_fn",
                                    "prefill": "jit_draft_prefill_fn"}},
        device_kind="TPU v5 lite", window_ns=(0, 100 * ms),
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
    accept = load_module(os.path.join(METRICS, "draft_accept_pct.py"))
    yielded = load_module(os.path.join(
        METRICS, "serve_tokens_per_step_slot.py"))
    share = load_module(os.path.join(METRICS, "decode_draft_time_pct.py"))
    roofline = load_module(os.path.join(
        METRICS, "verify_attention_roofline.py"))
    # 200 steps of 60 slots: 12,000 drafts, 3,000 accepted; 15,000 tokens
    # from steps beside 20 first tokens from prefills
    counters = {
        "open": {"steps": 100, "occupancy_sum": 6_000, "tokens_out": 7_000,
                 "admitted": 70, "draft_proposed": 6_000,
                 "draft_accepted": 900},
        "close": {"steps": 300, "occupancy_sum": 18_000,
                  "tokens_out": 22_020, "admitted": 90,
                  "draft_proposed": 18_000, "draft_accepted": 3_900}}
    run = hand_run(family, config, counters)
    assert accept.read(run) == pytest.approx(25.0)
    assert yielded.read(run) == pytest.approx(1.25)
    # a step: 4 ms under verify (1 of it attention), 1.5 ms under mtp (0.5
    # of it attention, 0.25 its head), 0.5 ms of cache writes
    step = [("verify/attn/attn_window", 0.25), ("verify/attn/attn_global",
                                                0.75),
            ("verify/experts", 3.0), ("mtp/attn/attn_global", 0.5),
            ("mtp/mlp", 0.75), ("mtp/mtp_head", 0.25), ("cache_write", 0.5)]
    ops = [op for at in (0.0, 0.04, 0.08)
           for op in scoped_ops("verify_fn", at, step)]
    run = hand_run(family, config, counters, ops)
    assert share.read(run) == pytest.approx(100 * 1.5 / 6.0)
    said = dict(run.said)["decode_by_draft"]
    assert said["executions"] == 3 and said["program"] == "jit_verify_fn"
    assert said["verify_ms"] == pytest.approx(4.0)
    assert said["mtp_ms"] == pytest.approx(1.5)
    assert said["attn_ms"] == pytest.approx(1.5)
    value = roofline.read(run)
    # the one request's decoded tokens (the first is its prefill's) stand at
    # contexts 1001 .. 1005
    _, io = family.verify_attention_work(config, 60.0, 1003.0)
    assert value == pytest.approx(100 * (io / 819e9) / 1.5e-3)
    said = dict(run.said)["verify_attention_roofline"]
    assert said["bound_by"] == "memory" and said["executions"] == 3
    assert said["device_ms"] == pytest.approx(1.5)
    assert said["mean_context"] == pytest.approx(1003.0)
    assert 0 < value < 100
    # a program that drafts nothing (nothing under ``mtp``: the parent,
    # another family), a family without the function, an engine without the
    # counters, a run with no trace: nothing to read, nothing raised
    bare = hand_run(family, config, counters,
                    [o for o in ops if "mtp" not in o[3]])
    assert share.read(bare) is None and roofline.read(bare) is None
    other = hand_run(family, config, counters, ops)
    other.family = load_module(os.path.join(REPO, "benchmark", "families",
                                            "smallthinker.py"))
    assert roofline.read(other) is None
    old = {end: {k: v for k, v in c.items() if not k.startswith("draft")}
           for end, c in counters.items()}
    none = hand_run(family, config, old, ops)
    assert accept.read(none) is None and yielded.read(none) is None
    none = hand_run(family, config, {}, ops)
    assert accept.read(none) is None and roofline.read(none) is None
    none.trace = none.program_trace = None
    none.trace_on = False
    assert share.read(none) is None and roofline.read(none) is None


@pytest.mark.parametrize("trace_on, names", [
    (0, {"setup_s", "serve_out_tokens_per_s"}),
    (1, {"serve_decode_step_ms_p50", "serve_batch_occupancy_pct",
         "serve_prefill_fill_pct.decode", "window_ring_rows_read_pct",
         "decode_ring_rows_read_pct", "draft_accept_pct",
         "serve_tokens_per_step_slot"}),
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
    # 5 slots x (two rings of 64 rows and five of 8, K and V rows of 32)
    assert said["engine_memory"]["cache_bytes"] == 5 * (
        2 * 64 + 5 * 8) * 2 * 32 * 2
    close = said["engine_counters"]["close"]
    assert close["compiles"] == {"decode": 1, "prefill": 1}
    assert close["prefill_chunk"] == 16
    assert close["prefill_expert_rows"] > 0 and close["experts_hit"] > 0
    assert close["window_rows_held"] == close["steps"] * 5 * 5 * 8
    assert close["ring_rows_held"] == close["steps"] * 5 * (5 * 8 + 2 * 64)
    assert (close["expert_layers"], close["experts_held"],
            close["global_layers"], close["window_layers"],
            close["window_rows"], close["draft_depth"]) == (5, 4, 2, 5, 8, 1)
    # every step drafts for every occupied slot, and what it yields is one
    # or two tokens a slot
    assert close["draft_proposed"] == close["occupancy_sum"] > 0
    assert 0 <= close["draft_accepted"] <= close["draft_proposed"]
    assert close["tokens_out"] - close["admitted"] \
        <= close["occupancy_sum"] + close["draft_accepted"]


def test_the_draft_tool_reads_the_module_and_the_rejected_rows(toy_root,
                                                               capsys):
    """``tools/serve_check_draft.py`` at the toy's size: the module's
    logits against the reference's module, the main logits with rejected
    drafts among the steps against the same steps with none, and the
    device's own counts."""
    tool = load_module(os.path.join(REPO, "benchmark", "tools",
                                    "serve_check_draft.py"))
    args = ["--root", toy_root, "--workload", TOY_CELL["name"],
            "--seeds", "1", "--first-seed", "3000000023", "--rehearsal"]
    assert tool.main(args) == 0
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    seed = last["seeds"][0]
    assert last["counts_as_expected"] is True
    assert seed["positions"] == 2 * 6 and seed["steps"] == 3
    assert seed["wrong_drafts"] == [False, True, False]
    assert seed["module_positions_compared"] >= 6
    assert last["module_rel_l2_max"] < 0.15
    assert last["main_shift_rel_l2_max"] < 0.05
    # float8 weights in the system alone: the module's reading rises
    assert tool.main(args + ["--fault", "fp8_weights"]) == 0
    low = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert low["module_rel_l2_max"] > 1.5 * last["module_rel_l2_max"]


def test_the_cell_and_its_files(config):
    spec = load_json(os.path.join(REPO, "BENCHMARK.json"))
    cell = [w for w in spec["workloads"] if w["name"] == CELL]
    assert cell == [{**cell[0], "config": CONFIG, "chips": 1,
                     "traffic": "reasoning_selfdraft_closed"}]
    assert "64 slots" in cell[0]["why"] and "verifies 2 rows" in cell[0]["why"]
    entry = [c for c in spec["configs"] if c["name"] == CONFIG]
    assert entry == [{**entry[0], "reduced": REDUCED,
                      "source": config["source"],
                      "file": f"benchmark/configs/{CONFIG}.json"}]
    assert load_json(os.path.join(
        REPO, "benchmark", "cells", CELL + ".json")) \
        == {"deployment": "kexaone_1chip_b64"}
    traffic = load_json(os.path.join(REPO, "benchmark", "traffic",
                                     "reasoning_selfdraft_closed.json"))
    assert traffic["kind"] == "serve_closed"
    assert (traffic["clients_per_slot"], traffic["pool_requests"],
            traffic["sizes_seed"]) == (2, 4096, 20261002)
    assert traffic["prompt_len"] == {"dist": "log_uniform", "min": 256,
                                     "max": 4096}
    assert traffic["max_tokens"] == {"dist": "log_normal", "median": 1024,
                                     "sigma": 0.6, "min": 256, "max": 3840}
    deployment = load_json(os.path.join(
        REPO, "benchmark", "deployments", "kexaone_1chip_b64.json"))
    assert deployment["engine"] == {
        "max_batch": 64, "cache_len": 8192, "max_prompt_len": 4096,
        "prefill_rows": 4, "max_new_cap": 3840}
    assert deployment["trace_seconds"] == 5.0
    assert deployment["device_programs"] == {
        "decode": "jit_verify_fn", "prefill": "jit_draft_prefill_fn"}
    # the longest request and its draft's row fit the full rings
    assert traffic["prompt_len"]["max"] + traffic["max_tokens"]["max"] + 1 \
        <= deployment["engine"]["cache_len"]
    # the pool's means: about 1,400 tokens in, about 1,200 out
    common = load_module(os.path.join(REPO, "benchmark", "kinds",
                                      "serve_common.py"))
    lens, new = common.draw_sizes(traffic, 4096)
    assert 1300 < lens.mean() < 1500 and 1100 < new.mean() < 1300
    reports = {m["name"] for m in spec["end_to_end"]
               if CELL in m.get("workloads", [CELL])}
    assert {"serve_out_tokens_per_s", "setup_s"} <= reports
    per_layer = {m["name"]: m for m in spec["per_layer"]}
    new_ones = {"draft_accept_pct": ("program_counter", "%", "higher"),
                "serve_tokens_per_step_slot": (
                    "program_counter", "tokens/step/slot", "higher"),
                "decode_draft_time_pct": ("device_trace", "%", "lower"),
                "verify_attention_roofline": ("device_trace", "%", "higher")}
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
    for name, (source, unit, better) in new_ones.items():
        assert per_layer[name]["workloads"] == [CELL]
        assert (per_layer[name]["source"], per_layer[name]["unit"],
                per_layer[name]["better"]) == (source, unit, better)
        assert os.path.exists(os.path.join(METRICS, name + ".py"))
    assert len(spec["workloads"]) >= 11
    assert sum(w["chips"] == 4 for w in spec["workloads"]) == 1
