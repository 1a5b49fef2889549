"""The ``deepseek_v2`` family's benchmark files: its configuration file
against the catalog row it was copied from, its counts against the arrays
the system makes (3,145,466,880 parameters at the published widths), the
bytes of a decode step, the work of a prefill chunk and of the decode
step's latent attention by hand, the two new readers on hand-made runs, and
a CPU rehearsal of the cell's kind with a toy configuration of this family
added to the tests' toy root AS FILES AND ENTRIES (no tiny override lives
in the benchmark itself)."""

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
CONFIG = "deepseek-v2"
CELL = "serve_dsv2_longctx_sat"
DEPLOYMENT = "dsv2_1chip_b64"
REDUCED = ["num_hidden_layers", "n_routed_experts", "vocab_size"]
# The catalog row's ``config`` (guides/model-configs/architectures.jsonl,
# DeepSeek-V2), copied here so that the test needs no file outside the
# repository.
PUBLISHED = {
    "attention_bias": False, "first_k_dense_replace": 1,
    "hidden_act": "silu", "hidden_size": 5120, "intermediate_size": 12288,
    "kv_lora_rank": 512, "max_position_embeddings": 163840,
    "model_type": "deepseek_v2", "moe_intermediate_size": 1536,
    "moe_layer_freq": 1, "n_group": 8, "n_routed_experts": 160,
    "n_shared_experts": 2, "norm_topk_prob": False,
    "num_attention_heads": 128, "num_experts_per_tok": 6,
    "num_hidden_layers": 60, "num_key_value_heads": 128,
    "q_lora_rank": 1536, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-06,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 0.707, "mscale_all_dim": 0.707,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 16,
    "scoring_func": "softmax", "seq_aux": True,
    "tie_word_embeddings": False, "topk_group": 3,
    "topk_method": "group_limited_greedy", "v_head_dim": 128,
    "vocab_size": 102400}

TOY_CONFIG = {
    "family": "deepseek_v2",
    "source": "none: a toy of the deepseek_v2 family for CPU rehearsals of "
              "the harness, never a benchmark configuration",
    "model_type": "deepseek_v2", "vocab_size": 256, "hidden_size": 64,
    "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "intermediate_size": 96, "rms_norm_eps": 1e-06,
    "num_attention_heads": 4, "num_key_value_heads": 4, "q_lora_rank": 24,
    "kv_lora_rank": 16, "qk_nope_head_dim": 8, "qk_rope_head_dim": 8,
    "v_head_dim": 8, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 0.707, "mscale_all_dim": 0.707,
                     "original_max_position_embeddings": 16, "type": "yarn"},
    "n_routed_experts": 8, "n_routed_experts_published": 16, "n_group": 4,
    "topk_group": 2, "num_experts_per_tok": 3, "routed_scaling_factor": 4,
    "moe_intermediate_size": 24, "n_shared_experts": 2,
    "max_position_embeddings": 64, "reduced": [],
    "assumed": {"experts_held": [0, 8], "init_std": 0.02,
                "rope_lanes": "split_halves",
                "why": "DeepseekV2Config.tiny()'s sizes"},
    "reference_check": {"prompt_lens": [5, 11], "follow": 3},
    "tolerance": {"serve_logits_rel_l2": 0.08, "serve_token_regret_rms": 0.5,
                  "reason": "bfloat16 compute against a float32 reference "
                            "at toy width"},
}
TOY_CELL = {"name": "toy_dsv2_closed", "config": "dsv2-toy",
            "traffic": "toy_closed", "chips": 1,
            "why": "CPU rehearsal of kind serve_closed on this family"}


@pytest.fixture(scope="module")
def config():
    return load_json(os.path.join(REPO, "benchmark", "configs",
                                  CONFIG + ".json"))


@pytest.fixture(scope="module")
def family():
    return load_module(os.path.join(REPO, "benchmark", "families",
                                    "deepseek_v2.py"))


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    """The tests' toy root with this family's toy added as files and
    entries, the way a PR adds a configuration."""
    root = benchmark_toy.make_root(str(tmp_path_factory.mktemp("dsv2")))
    bench = os.path.join(root, "benchmark")
    with open(os.path.join(bench, "configs", "dsv2-toy.json"), "w") as f:
        json.dump(TOY_CONFIG, f)
    with open(os.path.join(bench, "cells",
                           TOY_CELL["name"] + ".json"), "w") as f:
        json.dump({"deployment": "toy_engine"}, f)
    spec = load_json(os.path.join(root, "BENCHMARK.json"))
    spec["configs"].append({
        "name": "dsv2-toy", "source": TOY_CONFIG["source"],
        "file": "benchmark/configs/dsv2-toy.json", "reduced": [],
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
    three in ``reduced``; those state the published value beside the held
    one. No width is among them."""
    assert config["reduced"] == REDUCED
    assert config["source"] == "https://huggingface.co/deepseek-ai/" \
        "DeepSeek-V2/blob/main/config.json"
    for key, value in PUBLISHED.items():
        if key not in REDUCED:
            assert config[key] == value, key
        else:
            assert config[key + "_published"] == value, key
    # the whole pattern: the leading dense layer and four that follow
    assert config["num_hidden_layers"] == 5 \
        == config["first_k_dense_replace"] + 4
    assert config["n_routed_experts"] == 20 >= 8
    assert config["n_routed_experts"] * config["n_group"] \
        == config["n_routed_experts_published"]
    assert config["vocab_size"] * 8 == config["vocab_size_published"]
    a = config["assumed"]
    assert a["experts_held"] == [0, 20]
    assert a["rope_lanes"] == "split_halves" and "rope_lanes_why" in a
    assert "init_embed_std_why" in a and "router_why" in a
    words = config["deployment"].lower()
    assert "8 chips share each layer" in words
    assert "group 0" in words and "96 chips" in words
    assert "param_dtype" not in json.dumps(config)
    assert "float32 router" in config["computes_in"]
    assert config["reference_check"]["follow"] == 8
    lens = config["reference_check"]["prompt_lens"]
    assert 1024 < lens[0] <= 2100 < lens[1] <= 8300
    assert lens[0] % 256 and lens[1] % 256  # each ends inside a chunk
    assert "reason" in config["tolerance"]


def test_counts_by_hand(config, family):
    """ISSUE 38's arithmetic, reckoned again by the family file."""
    sh = family.shape(config)
    assert sh["attention_params"] + sh["attention_norms"] == 149_227_520 \
        == 7_864_320 + 1_536 + 37_748_736 + 2_949_120 + 512 + 16_777_216 \
        + 83_886_080
    assert sh["dense_params"] == 188_743_680
    assert sh["expert_params"] == 23_592_960
    assert sh["shared_params"] == 47_185_920
    dense_layer = 149_227_520 + 10_240 + 188_743_680
    expert_layer = 149_227_520 + 10_240 + 819_200 + 47_185_920 \
        + 20 * 23_592_960
    assert (dense_layer, expert_layer) == (337_981_440, 669_102_080)
    assert family.param_count(config) == dense_layer + 4 * expert_layer \
        + 2 * 12_800 * 5_120 + 5_120 == 3_145_466_880
    # a row: 512 of latent + 64 of rotated key, 1,152 B a layer; whole
    # heads of K and V would be 81,920 B (the published 71x)
    assert sh["row_width"] == 576
    assert sh["latent_bytes_per_token"] == 5 * 1_152 == 5_760
    assert 128 * (128 + 64 + 128) * 2 == 81_920 and 81_920 // 1_152 == 71
    assert family.cache_bytes(config, 65, 16_896) == 65 * 16_896 * 5_760 \
        == 6_325_862_400
    # 242 operations a byte of cached row: the v5e's ridge is 240
    assert sh["absorbed_ops_per_row"] == 278_528
    assert sh["absorbed_ops_per_row"] // 1_152 == 241
    assert sh["decompressed_ops_per_row"] == 81_920
    for refused, args in ((family.train_flops_per_token, (config,)),
                          (family.attention_calls, (config, 16)),
                          (family.build_train, (config, None))):
        with pytest.raises(NotImplementedError, match="no training cell"):
            refused(*args)


def test_system_config_is_the_files_and_refuses_what_does_not_run(
        config, family):
    from ray_tpu.models.deepseek_v2 import DeepseekV2Config

    cfg = family.system_config(config)
    a = config["assumed"]
    assert cfg == DeepseekV2Config(
        vocab_size=12800, n_layer=5, experts_held=(0, 20),
        embed_std=a["init_embed_std"],
        routed_out_std=a["init_routed_out_std"])
    # the seeded draw: 0.02, and the two departures the file gives its
    # reasons for (a file that assumes none gets 0.02 throughout)
    assert (cfg.embed_std, cfg.routed_out_std) == (0.002, 0.0025)
    assert "init_routed_out_std_why" in a
    plain = family.system_config({**config, "assumed": {
        k: v for k, v in a.items() if not k.startswith("init_")}})
    assert (plain.embed_std, plain.routed_out_std) == (0.02, 0.02)
    assert cfg.dtype == cfg.param_dtype == jnp.bfloat16
    assert cfg.softmax_scale == pytest.approx(0.114725, rel=1e-4)
    with pytest.raises(ValueError, match="yarn"):
        family.system_config({**config, "rope_scaling": {
            **config["rope_scaling"], "type": "linear"}})
    with pytest.raises(ValueError, match="tie_word_embeddings"):
        family.system_config({**config, "tie_word_embeddings": True})
    with pytest.raises(ValueError, match="topk_method"):
        family.system_config({**config, "topk_method": "greedy"})
    with pytest.raises(ValueError, match="norm_topk_prob"):
        family.system_config({**config, "norm_topk_prob": True})
    with pytest.raises(ValueError, match="rope_lanes"):
        family.system_config({**config, "assumed": {
            **a, "rope_lanes": "interleaved"}})
    with pytest.raises(ValueError, match="experts_held"):
        family.system_config({**config, "n_routed_experts": 10})
    with pytest.raises(ValueError, match="num_key_value_heads"):
        family.system_config({**config, "num_key_value_heads": 16})


@pytest.mark.parametrize("name, root_of", [
    ("dsv2-toy", "toy"), (CONFIG, "repository")])
def test_counts_agree_with_the_arrays_the_system_makes(toy_root, name,
                                                       root_of):
    """``param_count`` and ``cache_bytes`` against the shapes of what
    ``deepseek_v2_init`` / ``deepseek_v2_init_cache`` make (by
    ``eval_shape``; at the published widths 3,145,466,880), and
    ``engine_memory`` reading 2 bytes a parameter."""
    root = toy_root if root_of == "toy" else REPO
    config = load_json(os.path.join(root, "benchmark", "configs",
                                    name + ".json"))
    family = load_module(os.path.join(root, "benchmark", "families",
                                      "deepseek_v2.py"))
    common = load_module(os.path.join(root, "benchmark", "kinds",
                                      "serve_common.py"))
    engine = {"max_batch": 4, "cache_len": 64} if root_of == "toy" \
        else load_json(os.path.join(
            REPO, "benchmark", "deployments", DEPLOYMENT + ".json"))["engine"]
    from ray_tpu.serve.llm_engine import _model_bundle

    bind = family.engine_bind(config, engine, 3)
    cfg, init, init_cache, _, _ = _model_bundle(
        bind["model"], bind["config"], "tiny")
    params = jax.eval_shape(lambda: init(jax.random.PRNGKey(3), cfg))
    cache = jax.eval_shape(lambda: init_cache(
        cfg, engine["max_batch"] + 1, engine["cache_len"]))
    nbytes = lambda tree: sum(x.size * x.dtype.itemsize
                              for x in jax.tree.leaves(tree))
    n_params = sum(x.size for x in jax.tree.leaves(params))
    assert family.param_count(config) == n_params
    counted = cache.pop("counted")  # int32 scalars, not cache
    assert set(counted) == {"prefill_expert_rows"}
    assert family.cache_bytes(config, engine["max_batch"] + 1,
                              engine["cache_len"]) == nbytes(cache)
    assert nbytes(params) == 2 * n_params  # bfloat16, every leaf
    said = []
    run = types.SimpleNamespace(
        family=family, config=config,
        say=lambda event, **f: said.append((event, f)))
    held = nbytes(params) + nbytes(cache)
    assert common._weight_bytes(run, held, engine) == 2.0 * n_params
    assert said[0][1]["bytes_per_param"] == 2
    if root_of == "repository":
        assert n_params == 3_145_466_880
        # what the cell holds at rest: 12.6 GB, 79 % of the chip's 16 GB,
        # half of it weights and half latent rows
        assert 12.6e9 < held < 12.65e9
        assert 0.49 < nbytes(cache) / held < 0.51


def test_to_reference_hands_the_leaves_over_as_they_are_stored(toy_root):
    config = load_json(os.path.join(toy_root, "benchmark", "configs",
                                    "dsv2-toy.json"))
    family = load_module(os.path.join(toy_root, "benchmark", "families",
                                      "deepseek_v2.py"))
    reference = load_module(os.path.join(toy_root, "benchmark", "reference",
                                         "deepseek_v2.py"))
    params = family.init_params(config, 5)
    ref = family.to_reference(params, config)
    assert sum(x.size for x in jax.tree.leaves(ref)) \
        == family.param_count(config)
    assert all(x.dtype == jnp.bfloat16 for x in jax.tree.leaves(ref))
    # a head's key part before its value part, side by side in one axis
    layer = params["layers"][1]
    assert ref["layers"][1]["kv_b_proj"].shape == (16, 4 * (8 + 8))
    assert bool((ref["layers"][1]["kv_b_proj"].reshape(16, 4, 16)[..., 8:]
                 == layer["w_uv"]).all())
    assert "mlp_in" in ref["layers"][0] and "router" in ref["layers"][1]
    tokens = jnp.asarray([[3, 1, 4, 1, 5, 9, 2, 6]])
    logits = reference.forward(ref, tokens,
                               **family.reference_kwargs(config))
    assert logits.dtype == jnp.float32 and logits.shape == (1, 8, 256)
    # the serving path in bfloat16 against it, through the cache
    got = family.serve_logits(
        config, params, jnp.pad(tokens[:, :5], ((0, 0), (0, 11))),
        jnp.asarray([5]), tokens[:, 5:], slots=2, cache_len=32)
    err = jnp.linalg.norm(got[0] - logits[0, 4:], axis=-1) \
        / jnp.linalg.norm(logits[0, 4:], axis=-1)
    assert got.shape == (1, 4, 256) and float(err.max()) < 0.05
    loss, gnorm = jax.jit(lambda p: reference.loss_and_grad_norm(
        p, tokens, **family.reference_kwargs(config)))(ref)
    assert 4.0 < float(loss) < 7.0 and 0 < float(gnorm) < 1e3


def test_decode_step_bytes_counts_hit_experts_and_live_rows(config, family):
    n = family.param_count(config)
    expert = 2 * 23_592_960
    stats = {"open": {"steps": 100, "experts_hit": 7_000},
             "close": {"steps": 300, "experts_hit": 21_000}}  # 70 a step
    need = family.decode_step_bytes(config, 2.0 * n, 64.0, 7000.0, stats)
    # outside the experts and the embedding (of which 64 rows): the head's
    # table is read whole
    dense = 2 * (n - 80 * 23_592_960 - 12_800 * 5_120 + 64 * 5_120)
    assert need == dense + 70 * expert + 64 * 7000 * 5_760
    # ISSUE 38: 6.29 GB of weights and some GB of latent rows a step
    assert 7.5e9 < need < 8.7e9
    every = family.decode_step_bytes(config, 2.0 * n, 64.0, 7000.0, {})
    assert every - need == (80 - 70) * expert
    # live rows only: an empty batch reads no row
    assert family.decode_step_bytes(config, 2.0 * n, 0.0, 7000.0, stats) \
        == need - 64 * 7000 * 5_760 - 2 * 64 * 5_120


def test_the_decode_attentions_work_is_the_absorbed_forms_over_live_rows(
        config, family):
    ops, io = family.latent_decode_attention_work(config, 64.0, 7000.0)
    assert io == 64 * 7000 * 5 * 1_152
    assert ops == 64 * 7000 * 5 * 2 * 128 * (576 + 512)
    # on the ridge: 819 GB/s against 197 TFLOP/s, 3.15 ms either way
    assert io / 819e9 == pytest.approx(ops / 197e12, rel=0.01)
    assert family.latent_decode_attention_work(config, 0.0, 7000.0) \
        == (0.0, 0.0)


def test_a_chunks_work_counts_required_work_only(config, family):
    n = family.param_count(config)
    # 230 real tokens, an eighth of their 4 x 6 pairs landed here, a query
    # sees 3500 keys on average
    ops, io = family.prefill_chunk_work(config, 2.0 * n, 230.0, 690.0,
                                        3500.0)
    assert io == 2.0 * n - 2 * 5_120 * (12_800 - 230) \
        + (3500 + 230) * 5_760
    passed = 5 * (149_227_520 - 2_048) + 188_743_680 \
        + 4 * (819_200 + 47_185_920)
    assert family._token_params(config) == passed == 1_126_891_520
    assert ops == 2.0 * 230 * passed + 2.0 * 690 * 23_592_960 \
        + 230 * 5 * 81_920 * 3500.0 + 2.0 * 12_800 * 5_120
    # ISSUE 38: 0.6 TFLOP outside attention at 256 tokens, as much again
    # in attention at 3,500 keys
    outside, _ = family.prefill_chunk_work(config, 2.0 * n, 256.0, 768.0)
    assert 0.55e12 < outside < 0.65e12
    assert 0.8e12 < ops < 0.9e12
    # no real token, no pair: the weights are still read
    none, same = family.prefill_chunk_work(config, 2.0 * n, 0.0, 0.0)
    assert none == 2.0 * 12_800 * 5_120
    assert same == 2.0 * n - 2 * 5_120 * 12_800
    # only a prompt's last chunk needs logits: where one execution in
    # four is one, the others do not read the head's table
    ops4, io4 = family.prefill_chunk_work(config, 2.0 * n, 230.0, 690.0,
                                          3500.0, 0.25)
    assert ops - ops4 == 0.75 * 2.0 * 12_800 * 5_120
    assert io - io4 == 0.75 * 12_800 * 5_120 * 2


def hand_run(family, config, counters, requests=()):
    """A 0.1 s window: three executions of the decode program (0.01 s
    each) and two of the prefill program (0.02 s and 0.03 s), their
    operations under the program's scopes."""
    said = []
    d, p = "jit(step_fn)/", "jit(prefill_fn)/"
    modules = [("jit_step_fn(1)", 0.00, 0.01),
               ("jit_prefill_fn(2)", 0.02, 0.04),
               ("jit_step_fn(1)", 0.04, 0.05),
               ("jit_prefill_fn(2)", 0.05, 0.08),
               ("jit_step_fn(1)", 0.08, 0.09)]
    ops = []
    for _, s, _ in [m for m in modules if "step" in m[0]]:
        ops += [("fusion.1", s, s + 0.001, d + "attn_proj/dot_general:"),
                ("fusion.2", s + 0.001, s + 0.002, d + "absorb/dot_general:"),
                ("while.1", s + 0.002, s + 0.006, d + "attn/while:"),
                ("fusion.3", s + 0.002, s + 0.006,
                 d + "attn/while/body/dot_general:"),
                ("fusion.4", s + 0.006, s + 0.007, d + "absorb/dot_general:"),
                ("fusion.5", s + 0.007, s + 0.010, d + "experts/dot_general:")]
    for _, s, e in [m for m in modules if "prefill" in m[0]]:
        ops += [("fusion.6", s, s + 0.002, p + "cache_write/dus:"),
                ("while.2", s + 0.002, s + 0.012, p + "attn/while:"),
                ("fusion.7", s + 0.002, s + 0.006,
                 p + "attn/while/body/kv_up/dot_general:"),
                ("fusion.8", s + 0.006, s + 0.012,
                 p + "attn/while/body/dot_general:"),
                ("fusion.9", s + 0.012, e, p + "mlp/dot_general:")]
    pt = {"host": [], "modules": modules, "window": (0.0, 0.1),
          "ops": sorted(ops, key=lambda o: o[1])}
    tr = {"window": (0.0, 0.1), "host": [], "devices": [{
        "name": "/device:TPU:0", "async": [], "modules": modules,
        "ops": [(n, s, e, "fusion") for n, s, e, _ in pt["ops"]
                if not n.startswith("while")]}]}
    return types.SimpleNamespace(
        trace=tr, family=family, config=config, counters=counters,
        raw={"weight_bytes": 2.0 * family.param_count(config),
             "requests": list(requests)},
        params={"device_programs": {"decode": "jit_step_fn",
                                    "prefill": "jit_prefill_fn"}},
        device_kind="TPU v5 lite", window_ns=(0, 100_000_000),
        program_trace=pt, trace_on=True, said=said,
        say=lambda event, **f: said.append((event, f)))


def test_the_chunks_latent_attention_share(config, family):
    reader = load_module(os.path.join(
        METRICS, "prefill_latent_attention_time_pct.py"))
    run = hand_run(family, config, {})
    # cache_write 2 + kv_up 4 + attn 6 ms of each execution's 20 and 30
    assert reader.read(run) == pytest.approx(100 * 0.024 / 0.05)
    # the ``while`` that only holds its body's operations is not counted
    under, total, runs = reader.seconds(run, "jit_prefill_fn", ("kv_up",))
    assert (under, total, runs) == ({"kv_up": pytest.approx(0.008)},
                                    pytest.approx(0.05), 2)
    said = dict(run.said)["prefill_latent_attention"]
    assert said["ms_an_execution"] == {
        "attn": pytest.approx(6.0), "cache_write": pytest.approx(2.0),
        "kv_up": pytest.approx(4.0), "absorb": 0.0}
    # where the compiler fuses the decompression into the product that
    # uses it, ``kv_up`` holds no time of its own (seen on the chip): the
    # four scopes together read the same
    fused = hand_run(family, config, {})
    fused.program_trace["ops"] = [
        (n, s, e, path.replace("kv_up/", ""))
        for n, s, e, path in fused.program_trace["ops"]]
    assert reader.read(fused) == pytest.approx(100 * 0.024 / 0.05)
    # a family that is not a latent one (no reader holds a family's
    # name: it is known by the function it brings), no program, no
    # profile: nothing, nothing raised
    other = hand_run(family, config, {})
    other.family = load_module(os.path.join(REPO, "benchmark", "families",
                                            "granite_hybrid.py"))
    assert reader.read(other) is None
    run.params = {"device_programs": {"decode": "jit_step_fn"}}
    assert reader.read(run) is None
    run.program_trace = None
    run.trace = None
    assert reader.read(run) is None


def test_the_decode_attentions_roofline_share(config, family):
    reader = load_module(os.path.join(
        METRICS, "latent_decode_attention_roofline.py"))
    ms = 1_000_000
    a = {"steps": 100, "occupancy_sum": 6_000}
    b = {"steps": 103, "occupancy_sum": 6_192}   # 64 slots a step
    # one stream of a 6,990-token prompt: tokens 1..20 decoded inside the
    # window attend 6,991..7,010 rows: 7,000.5 on average
    requests = [{"prompt_len": 6990, "chunk_tokens": [1] * 21,
                 "chunk_ns": [i * ms for i in range(21)]}]
    run = hand_run(family, config, {"open": a, "close": b}, requests)
    value = reader.read(run)
    ops, io = family.latent_decode_attention_work(config, 64.0, 7000.5)
    least = max(ops / 197e12, io / 819e9)
    # attn 4 ms + absorb 2 ms of each of the three executions
    assert value == pytest.approx(100 * least / 0.006)
    said = dict(run.said)["latent_decode_attention_roofline"]
    assert said["executions"] == 3 and said["occupancy"] == 64.0
    assert said["device_ms"] == pytest.approx(6.0)
    assert said["bound_by"] in ("memory", "compute")
    assert 0 < value < 100
    # a family without the function, a program with nothing under the
    # two scopes: nothing to read, nothing raised
    other = hand_run(family, config, {"open": a, "close": b}, requests)
    other.family = load_module(os.path.join(REPO, "benchmark", "families",
                                            "gpt2.py"))
    assert reader.read(other) is None
    plain = hand_run(family, config, {"open": a, "close": b}, requests)
    plain.program_trace["ops"] = [
        (n, s, e, path.replace("absorb", "attn_proj").replace(
            "attn/", "mlp/"))
        for n, s, e, path in plain.program_trace["ops"]]
    assert reader.read(plain) is None


@pytest.mark.parametrize("trace_on, names", [
    (0, {"setup_s", "serve_out_tokens_per_s"}),
    (1, {"serve_decode_step_ms_p50", "serve_batch_occupancy_pct",
         "serve_prefill_fill_pct.decode"}),
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
    # (a CPU reports no bytes in use: bytes_per_param is read on the chip)
    assert said["engine_memory"]["cache_bytes"] == 5 * 64 * 3 * 24 * 2
    close = said["engine_counters"]["close"]
    assert close["compiles"] == {"decode": 1, "prefill": 1}
    assert close["expert_layers"] == 2 and close["experts_held"] == 8
    assert 0 < close["experts_hit"] <= close["steps"] * 2 * 8
    assert 0 < close["expert_tokens_here"] <= close["expert_rows"]
    assert 0 < close["prefill_expert_rows"] \
        <= close["prefill_tokens_real"] * 3 * 2


def test_the_cell_and_its_files(config):
    spec = load_json(os.path.join(REPO, "BENCHMARK.json"))
    cell = [w for w in spec["workloads"] if w["name"] == CELL]
    assert cell == [{**cell[0], "config": CONFIG, "chips": 1,
                     "traffic": "longctx_answer_closed"}]
    entry = [c for c in spec["configs"] if c["name"] == CONFIG]
    assert entry == [{**entry[0], "reduced": REDUCED,
                      "file": "benchmark/configs/deepseek-v2.json",
                      "source": config["source"]}]
    traffic = load_json(os.path.join(REPO, "benchmark", "traffic",
                                     "longctx_answer_closed.json"))
    assert traffic["kind"] == "serve_closed"
    assert (traffic["clients_per_slot"], traffic["pool_requests"],
            traffic["sizes_seed"]) == (2, 4096, 20260930)
    assert traffic["prompt_len"] == {"dist": "log_uniform", "min": 2048,
                                     "max": 16384}
    assert traffic["max_tokens"] == {"dist": "log_normal", "median": 256,
                                     "sigma": 0.4, "min": 128, "max": 512}
    assert load_json(os.path.join(REPO, "benchmark", "cells",
                                  CELL + ".json")) \
        == {"deployment": DEPLOYMENT}
    deployment = load_json(os.path.join(
        REPO, "benchmark", "deployments", DEPLOYMENT + ".json"))
    engine = deployment["engine"]
    assert engine["max_batch"] in (64, 48)  # ISSUE 38's fallback
    assert {k: v for k, v in engine.items() if k != "max_batch"} == {
        "cache_len": 16896, "max_prompt_len": 16384, "prefill_rows": 4,
        "max_new_cap": 512}
    assert deployment["trace_seconds"] == 5.0
    # the longest request fits the ring without a wrap
    assert traffic["prompt_len"]["max"] + traffic["max_tokens"]["max"] \
        == engine["cache_len"]
    # the pool's mean prompt: about 6,900 tokens, 27 chunks and more
    common = load_module(os.path.join(REPO, "benchmark", "kinds",
                                      "serve_common.py"))
    lens, new = common.draw_sizes(traffic, 4096)
    assert 6600 < lens.mean() < 7200 and 240 < new.mean() < 290
    # the new metrics are looked up BY NAME, the cell is wanted IN their
    # lists and its two end-to-end metrics AMONG those it reports: a later
    # PR may report more on this cell, list further cells on these metrics
    # and append metrics of its own
    reports = {m["name"] for m in spec["end_to_end"]
               if CELL in m.get("workloads", [CELL])}
    assert {"serve_out_tokens_per_s", "setup_s"} <= reports
    per_layer = {m["name"]: m for m in spec["per_layer"]}
    for name in ("prefill_latent_attention_time_pct",
                 "latent_decode_attention_roofline",
                 "decode_step_roofline", "prefill_chunk_roofline",
                 "decode_attention_time_pct", "serve_prefill_device_pct",
                 "serve_batch_occupancy_pct"):
        assert CELL in per_layer[name]["workloads"], name
        assert per_layer[name]["moves"] == "serve_out_tokens_per_s"
        assert os.path.exists(os.path.join(METRICS, name + ".py")), name
    assert per_layer["latent_decode_attention_roofline"]["unit"] == "%"
    assert per_layer["latent_decode_attention_roofline"]["better"] \
        == "higher"
    assert per_layer["prefill_latent_attention_time_pct"]["better"] \
        == "lower"
