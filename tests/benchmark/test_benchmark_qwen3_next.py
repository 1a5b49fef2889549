"""The ``qwen3_next`` family's benchmark files: its configuration file
against the catalog row it was copied from, its counts against the arrays
the system makes, the bytes of a decode step, the delta rule's two works and
a prefill chunk's by hand, the four new readers on hand-made runs, the
controls' tool, and a CPU rehearsal of the cell's kind with a toy
configuration of this family added to the tests' toy root AS FILES AND
ENTRIES (no tiny override lives in the benchmark itself)."""

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
CONFIG = "qwen3-next-80b-a3b-instruct"
CELL = "serve_qwen3next_mixedctx_sat"
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size"]
# The catalog row's ``config`` (guides/model-configs/architectures.jsonl,
# Qwen3-Next-80B-A3B-Instruct), copied here so that the test needs no file
# outside the repository.
PUBLISHED = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5120,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
    "linear_num_key_heads": 16, "linear_num_value_heads": 32,
    "linear_value_head_dim": 128, "max_position_embeddings": 262144,
    "mlp_only_layers": [], "model_type": "qwen3_next",
    "moe_intermediate_size": 512, "norm_topk_prob": True,
    "num_attention_heads": 16, "num_experts": 512, "num_experts_per_tok": 10,
    "num_hidden_layers": 48, "num_key_value_heads": 2,
    "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 10000000,
    "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}
LINEAR_MIXER = 33_718_464
FULL_MIXER = 27_263_488
EXPERT = 3_145_728
REST = 4_196_352          # router, shared expert, its gate
HELD = 3_667_251_328
SLOT_STATE = 2_146_304    # a slot's bytes a linear layer: state and tail

TOY_GAINS = {"embed": 1.0, "gdn_in": 1.0, "gdn_ba": 0.5, "gdn_out": 1.0,
             "q": 1.0, "k": 1.0, "v": 1.0, "o": 4.0, "router": 4.0,
             "expert_in": 1.0, "expert_down": 0.5, "shared_in": 1.0,
             "shared_down": 1.5, "shared_gate": 1.0, "head": 1.0}
TOY_CONFIG = {
    "family": "qwen3_next",
    "source": "none: a toy of the qwen3_next family for CPU rehearsals of "
              "the harness, never a benchmark configuration",
    "model_type": "qwen3_next", "vocab_size": 256, "hidden_size": 48,
    "num_hidden_layers": 8, "full_attention_interval": 4,
    "rms_norm_eps": 1e-06, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "partial_rotary_factor": 0.5,
    "rope_theta": 10000, "linear_num_key_heads": 2,
    "linear_num_value_heads": 4, "linear_key_head_dim": 8,
    "linear_value_head_dim": 12, "linear_conv_kernel_dim": 4,
    "num_experts": 8, "num_experts_published": 16, "num_experts_per_tok": 3,
    "moe_intermediate_size": 24, "shared_expert_intermediate_size": 40,
    "norm_topk_prob": True, "tie_word_embeddings": False,
    "max_position_embeddings": 64, "reduced": [],
    "assumed": {"experts_held": [4, 8], "delta_state_dtype": "float32",
                "scan_block": 8, "init_gains": TOY_GAINS,
                "why": "Qwen3NextConfig.tiny()'s sizes; three of sixteen "
                       "experts a token at 48 lanes turn on rounding far "
                       "more often than ten of 512 at 2048, so the toy's "
                       "routed branch is drawn lighter"},
    "reference_check": {"prompt_lens": [5, 11], "follow": 3},
    "tolerance": {"serve_logits_rel_l2": 0.15, "serve_token_regret_rms": 0.5,
                  "reason": "bfloat16 compute against a float32 reference "
                            "at toy width"},
}
TOY_CELL = {"name": "toy_qwen3next_closed", "config": "qwen3next-toy",
            "traffic": "toy_closed", "chips": 1,
            "why": "CPU rehearsal of kind serve_closed on this family"}


@pytest.fixture(scope="module")
def config():
    return load_json(os.path.join(REPO, "benchmark", "configs",
                                  CONFIG + ".json"))


@pytest.fixture(scope="module")
def family():
    return load_module(os.path.join(REPO, "benchmark", "families",
                                    "qwen3_next.py"))


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    """The tests' toy root with this family's toy added as files and
    entries, the way a PR adds a configuration."""
    root = benchmark_toy.make_root(str(tmp_path_factory.mktemp("qwen3next")))
    bench = os.path.join(root, "benchmark")
    with open(os.path.join(bench, "configs", "qwen3next-toy.json"),
              "w") as f:
        json.dump(TOY_CONFIG, f)
    with open(os.path.join(bench, "cells",
                           TOY_CELL["name"] + ".json"), "w") as f:
        json.dump({"deployment": "toy_engine"}, f)
    spec = load_json(os.path.join(root, "BENCHMARK.json"))
    spec["configs"].append({
        "name": "qwen3next-toy", "source": TOY_CONFIG["source"],
        "file": "benchmark/configs/qwen3next-toy.json", "reduced": [],
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
    assert config["source"] == "https://huggingface.co/Qwen/" \
        "Qwen3-Next-80B-A3B-Instruct/blob/main/config.json"
    for key, value in PUBLISHED.items():
        if key not in REDUCED:
            assert config[key] == value, key
        else:
            assert config[key + "_published"] == value, key
    # one chip of the four that share each of a stage's eight layers: two
    # whole periods, a quarter of the experts and of both tables; the
    # guide's floors (a period and four layers, 8 experts, an eighth)
    assert config["num_hidden_layers"] == 2 * config[
        "full_attention_interval"] and config["num_hidden_layers"] * 6 == 48
    assert config["num_experts"] * 4 == config["num_experts_published"]
    assert config["vocab_size"] * 4 == config["vocab_size_published"]
    a = config["assumed"]
    assert a["experts_held"] == [0, 128]
    assert a["delta_state_dtype"] == "float32" and a["scan_block"] == 64
    assert set(a["init_gains"]) == set(TOY_GAINS)
    for why in ("experts_held_why", "delta_state_dtype_why",
                "scan_block_why", "rotary_lanes_why", "init_gains_why",
                "decay_draw_why"):
        assert len(a[why]) > 40, why
    assert "halves" in a["decay_draw_why"]
    deployment = config["deployment"].lower()
    for said in ("four chips of one v5e host share each layer",
                 "four row slices of 37,984", "3,667,251,328",
                 "six layers are one and a half periods",
                 "multi-token-prediction", "four times their share"):
        assert said in deployment, said
    assert "5120" in config["intermediate_size_why"]
    assert "param_dtype" not in json.dumps(config)
    assert "bfloat16 weights" in config["computes_in"]
    assert config["reference_check"]["follow"] == 8
    lens = config["reference_check"]["prompt_lens"]
    assert lens == [1100, 12000] and all(n % 256 for n in lens)
    assert set(config["tolerance"]) == {
        "serve_logits_rel_l2", "serve_token_regret_rms", "reason"}


def test_counts_by_hand(config, family):
    """ISSUE 45's arithmetic, reckoned again by the family file."""
    sh = family.shape(config)
    assert sh["layer_types"] == (["linear_attention"] * 3
                                 + ["full_attention"]) * 2
    assert sh["linear_params"] == 25_165_824 + 131_072 + 32_768 + 64 + 128 \
        + 8_388_608 == LINEAR_MIXER
    assert sh["full_params"] == 16_777_216 + 2_097_152 + 8_388_608 + 512 \
        == FULL_MIXER
    assert sh["expert_params"] == sh["shared_params"] == EXPERT
    assert 2048 * 512 + EXPERT + 2048 == REST
    assert LINEAR_MIXER + REST + 4096 == 37_918_912
    assert FULL_MIXER + REST + 4096 == 31_463_936
    assert family.param_count(config) == 2 * (3 * 37_918_912 + 31_463_936) \
        + 8 * 128 * EXPERT + 2 * 37_984 * 2048 + 2048 == HELD
    whole = {**config, "num_hidden_layers": 48, "num_experts": 512,
             "vocab_size": 151936,
             "assumed": {**config["assumed"], "experts_held": [0, 512]}}
    assert family.param_count(whole) == 79_674_391_296
    # a slot: a float32 matrix a value head and a bfloat16 tail in six
    # layers, 2 KiB a token in each of two
    assert sh["state_bytes_per_slot"] == 6 * (2_097_152 + 49_152) \
        == 6 * SLOT_STATE
    assert sh["kv_bytes_per_token"] == 2 * 2_048
    assert family.cache_bytes(config, 65, 18432) == 65 * (
        6 * SLOT_STATE + 18432 * 4096) == 5_744_394_240
    for refused, args in ((family.train_flops_per_token, (config,)),
                          (family.attention_calls, (config, 16)),
                          (family.build_train, (config, None))):
        with pytest.raises(NotImplementedError, match="no training cell"):
            refused(*args)


def test_system_config_is_the_files_and_refuses_what_does_not_run(
        config, family):
    from ray_tpu.models.qwen3_next import GAINS, Qwen3NextConfig

    cfg = family.system_config(config)
    assert cfg == Qwen3NextConfig(vocab_size=37984, n_layer=8,
                                  experts_held=(0, 128))
    assert dict(cfg.gains) == config["assumed"]["init_gains"] == dict(GAINS)
    assert cfg.dtype == cfg.param_dtype == jnp.bfloat16
    assert cfg.delta_state_dtype == jnp.float32
    assert (cfg.n_experts, cfg.top_k, cfg.rotary_dim) == (512, 10, 64)
    assert (cfg.delta.conv_dim, cfg.delta.qkvz_width, cfg.delta.block) \
        == (8192, 12288, 64)
    with pytest.raises(ValueError, match="tie_word_embeddings"):
        family.system_config({**config, "tie_word_embeddings": True})
    with pytest.raises(ValueError, match="norm_topk_prob"):
        family.system_config({**config, "norm_topk_prob": False})
    with pytest.raises(ValueError, match="mlp_only_layers"):
        family.system_config({**config, "mlp_only_layers": [0]})
    with pytest.raises(ValueError, match="experts_held"):
        family.system_config({**config, "num_experts": 256})
    with pytest.raises(ValueError, match="float32"):
        family.system_config({**config, "assumed": {
            **config["assumed"], "delta_state_dtype": "bfloat16"}})


@pytest.mark.parametrize("name, root_of", [
    ("qwen3next-toy", "toy"), (CONFIG, "repository")])
def test_counts_agree_with_the_arrays_the_system_makes(toy_root, name,
                                                       root_of):
    """``param_count`` and ``cache_bytes`` against the shapes of what
    ``qwen3_next_init`` / ``qwen3_next_init_cache`` make (by
    ``eval_shape``), and ``engine_memory`` reading 2 bytes a parameter."""
    root = toy_root if root_of == "toy" else REPO
    config = load_json(os.path.join(root, "benchmark", "configs",
                                    name + ".json"))
    family = load_module(os.path.join(root, "benchmark", "families",
                                      "qwen3_next.py"))
    common = load_module(os.path.join(root, "benchmark", "kinds",
                                      "serve_common.py"))
    engine = {"max_batch": 4, "cache_len": 64} if root_of == "toy" \
        else load_json(os.path.join(
            REPO, "benchmark", "deployments",
            "qwen3next_1chip_b64.json"))["engine"]
    from ray_tpu.serve.llm_engine import _model_bundle

    bind = family.engine_bind(config, engine, 3)
    assert bind["model"] == "qwen3_next"
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
    assert stats["delta_state_bytes_per_slot"] == sh["state_bytes_per_slot"]
    assert stats["kv_bytes_per_token"] == sh["kv_bytes_per_token"]
    said = []
    run = types.SimpleNamespace(
        family=family, config=config,
        say=lambda event, **f: said.append((event, f)))
    held = nbytes(params) + nbytes(cache)
    assert common._weight_bytes(run, held, engine) == 2.0 * n_params
    assert said[0][1]["bytes_per_param"] == 2
    if root_of == "repository":  # what the cell holds at rest: 13.08 GB
        assert n_params == HELD
        assert held == 2 * HELD + 5_744_394_244 == 13_078_896_900
        assert held / 16e9 > 0.81


def test_to_reference_hands_the_leaves_over_as_they_are_stored(toy_root):
    config = load_json(os.path.join(toy_root, "benchmark", "configs",
                                    "qwen3next-toy.json"))
    family = load_module(os.path.join(toy_root, "benchmark", "families",
                                      "qwen3_next.py"))
    reference = load_module(os.path.join(toy_root, "benchmark", "reference",
                                         "qwen3_next.py"))
    params = family.init_params(config, 5)
    ref = family.to_reference(params, config)
    assert sum(x.size for x in jax.tree.leaves(ref)) \
        == family.param_count(config)
    assert all(x.dtype == jnp.bfloat16 for x in jax.tree.leaves(ref))
    linear = ref["layers"][0]
    assert {"q_proj", "k_proj", "v_proj", "z_proj", "b_proj", "a_proj",
            "A_log", "norm_w"} <= set(linear) and "in_qkvz" not in linear
    assert linear["v_proj"].shape == (48, 48) \
        and linear["b_proj"].shape == (48, 4)
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
    assert got.shape == (1, 4, 256) and float(err.max()) < 0.15
    loss, gnorm = jax.jit(lambda p: reference.loss_and_grad_norm(
        p, tokens, **family.reference_kwargs(config)))(ref)
    assert 4.0 < float(loss) < 9.0 and 0 < float(gnorm) < 1e3


def test_the_seeded_draw_makes_every_branch_some_tenths_of_the_stream(
        toy_root):
    """``assumed.init_gains``: in the first linear layer and the first full
    layer each branch is within a factor of ten of the stream, the scores
    spread over a unit (the query/key norms see to that), a head's state
    halves in under a token to hundreds of tokens, and half of a token's
    experts are held at the toy's share."""
    config = load_json(os.path.join(toy_root, "benchmark", "configs",
                                    "qwen3next-toy.json"))
    family = load_module(os.path.join(toy_root, "benchmark", "families",
                                      "qwen3_next.py"))
    tokens = jnp.asarray(jax.random.randint(
        jax.random.PRNGKey(0), (2, 48), 0, 256))
    params = family.init_params(config, 5)
    got = family.branch_readings(config, params, tokens)
    assert set(got) == {"linear_attention", "full_attention"}
    assert 0.8 < got["linear_attention"]["stream_rms"] < 1.2
    for kind, readings in got.items():
        for branch in ("mixer_rms", "routed_rms", "shared_rms"):
            share = readings[branch] / readings["stream_rms"]
            assert 0.05 < share < 4.0, (kind, branch, readings)
        assert 0.3 < readings["held_share_of_chosen"] < 0.7
    assert 0.9 < got["full_attention"]["score_spread"] < 1.1
    low, mid, high = got["linear_attention"]["state_halves_in_tokens"]
    assert 0.3 < low < mid < high < 1000 and mid > 2


def test_decode_step_bytes_and_the_delta_rules_two_works(config, family):
    n = family.param_count(config)
    table = 37984 * 2048
    experts = 8 * 128 * EXPERT
    need = family.decode_step_bytes(config, 2.0 * n, 64.0, 5000.0, {})
    assert need == 2 * (n - table + 64 * 2048) + 64 * (
        5000 * 4096 + 2 * 6 * SLOT_STATE)
    # ISSUE 45's estimate with every held expert read: 6.44 GB of experts,
    # 1.65 GB of state both ways, 1.31 GB of live K/V rows
    assert 10.1e9 < need < 10.3e9
    counters = {"open": {"steps": 100, "experts_hit": 73_728},
                "close": {"steps": 200, "experts_hit": 147_456}}
    hit = family.decode_step_bytes(config, 2.0 * n, 64.0, 5000.0, counters)
    assert need - hit == pytest.approx(2.0 * (8 * 128 - 737.28) * EXPERT)
    assert 2 * experts == pytest.approx(6.44e9, rel=1e-3)
    # the step's rule: each slot's state and tail both ways in six layers,
    # 7 dk dv operations a head a token
    ops, io = family.gated_delta_step_work(config, 64.0)
    assert io == 64 * 2 * 6 * SLOT_STATE == 1_648_361_472
    assert ops == 64 * 6 * 7 * 32 * 128 * 128
    assert ops / 197e12 < io / 819e9  # memory bounds it: 2.0 ms
    assert family.gated_delta_step_work(config, 0.0) == (0.0, 0.0)
    # the chunk's: q, k, v, g, beta in and the output out once a token,
    # the state both ways once a chunk
    ops, io = family.gated_delta_scan_work(config, 256.0)
    row = 2 * (2 * 2048 + 2 * 4096) + 8 * 32
    assert io == 6 * (256 * row + 2 * 2_097_152)
    assert ops == 6 * 256 * 7 * 32 * 128 * 128
    assert ops / 197e12 < io / 819e9


def test_a_chunks_work_counts_required_work_only(config, family):
    n = family.param_count(config)
    ops, io = family.prefill_chunk_work(config, 2.0 * n, 230.0, 4000.0,
                                        3000.0)
    row = 2 * 2048
    assert io == 2.0 * n - row * (37984 - 230) + 2 * 6 * SLOT_STATE \
        + 3000 * 4096
    passed = 6 * (LINEAR_MIXER - 32_768 - 64 - 128) \
        + 2 * (FULL_MIXER - 512) + 8 * REST
    assert family._token_params(config) == passed
    assert ops == 2.0 * 230 * passed + 2.0 * 4000 * EXPERT \
        + 230 * 2 * 4.0 * 4096 * 3000 \
        + family.gated_delta_scan_work(config, 230.0)[0] \
        + 2.0 * 37984 * 2048
    # memory bounds a chunk on the chip: 7.2 GB at 819 GB/s against
    # 0.17 TFLOP at 197 TFLOP/s
    assert ops / 197e12 < io / 819e9
    ops4, io4 = family.prefill_chunk_work(config, 2.0 * n, 230.0, 4000.0,
                                          3000.0, 0.25)
    assert ops - ops4 == 0.75 * 2.0 * 37984 * 2048
    assert io - io4 == 0.75 * 37984 * row


def hand_run(family, config, counters, requests=(), ops=()):
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
             "requests": list(requests)},
        params={"device_programs": {"decode": "jit_step_fn",
                                    "prefill": "jit_prefill_fn"}},
        device_kind="TPU v5 lite", window_ns=(0, 100),
        program_trace={"host": [], "ops": sorted(ops, key=lambda o: o[1]),
                       "modules": modules, "window": (0.0, 0.1)},
        trace_on=True, said=said,
        say=lambda event, **f: said.append((event, f)))


def step_ops(at):
    """One decode execution's operations: 3 ms of the linear layers (2 of
    them the update, 0.5 the state's write), 4 ms of experts, 2 ms of
    attention, 1 ms head."""
    path = "jit(step_fn)/jit(main)/{}/fusion"
    parts = [("gdn_update", 2), ("state_write", 0.5), ("gdn_proj", 0.5),
             ("experts", 3), ("router", 1), ("attn", 1.5), ("qk_norm", 0.5),
             ("head", 1)]
    out, t = [], at
    for scope, ms in parts:  # (0.9 ms a unit: inside the 10 ms execution)
        out.append((f"fusion.{scope}", t, t + ms * 9e-4, path.format(scope)))
        t += ms * 9e-4
    return out


def test_the_four_readers_on_hand_made_runs(config, family):
    share = load_module(os.path.join(
        METRICS, "decode_linear_attention_time_pct.py"))
    step = load_module(os.path.join(METRICS, "gated_delta_step_roofline.py"))
    chunk = load_module(os.path.join(
        METRICS, "prefill_linear_attention_time_pct.py"))
    scan = load_module(os.path.join(METRICS, "gated_delta_scan_roofline.py"))
    counters = {
        "open": {"steps": 100, "occupancy_sum": 6400, "prefill_chunks": 10,
                 "prefill_tokens_real": 2000},
        "close": {"steps": 300, "occupancy_sum": 19200, "prefill_chunks": 30,
                  "prefill_tokens_real": 6800}}
    ops = step_ops(0.0) + step_ops(0.04) + step_ops(0.08)
    run = hand_run(family, config, counters, (), ops)
    assert share.read(run) == pytest.approx(30.0)
    said = dict(run.said)["decode_by_scope_linear"]
    assert said["executions"] == 3
    assert said["linear_pct"] == pytest.approx(30.0)
    assert said["experts_pct"] == pytest.approx(40.0)
    assert said["attention_pct"] == pytest.approx(20.0)
    assert said["linear_ms"] == pytest.approx(2.7)
    assert said["by_scope"]["gdn_update"]["pct"] == pytest.approx(20.0)
    assert said["by_scope"]["gdn_update"]["ms"] == pytest.approx(1.8)
    value = step.read(run)
    work_ops, io = family.gated_delta_step_work(config, 64.0)
    assert value == pytest.approx(100 * (io / 819e9) / 2.25e-3)
    said = dict(run.said)["gated_delta_step_roofline"]
    assert said["bound_by"] == "memory" and said["executions"] == 3
    assert said["device_ms"] == pytest.approx(2.25)
    assert said["state_bytes_per_step"] == io and said["occupancy"] == 64.0
    assert 0 < value < 105
    # the chunk program: 2 executions, in each 4 ms of the linear layers
    # (3 of them the scan), 3 ms experts, 3 ms under no scope of ours
    path = "jit(prefill_fn)/jit(main)/{}/fusion"
    chunk_ops = [(f"fusion.{scope}", at + a * 1e-3, at + b * 1e-3,
                  path.format(scope) if scope else "")
                 for at in (0.02, 0.05)
                 for scope, a, b in (("gdn_scan", 0, 3), ("conv", 3, 4),
                                     ("experts", 4, 7), ("", 7, 10))]
    run = hand_run(family, config, counters, (), ops + chunk_ops)
    assert chunk.read(run) == pytest.approx(40.0)
    said = dict(run.said)["prefill_by_scope_linear"]
    assert said["executions"] == 2 and said["program"] == "jit_prefill_fn"
    assert said["linear_ms"] == pytest.approx(4.0)
    assert said["experts_ms"] == pytest.approx(3.0)
    value = scan.read(run)
    work_ops, io = family.gated_delta_scan_work(config, 240.0)
    assert value == pytest.approx(100 * (io / 819e9) / 3e-3)
    said = dict(run.said)["gated_delta_scan_roofline"]
    assert said["tokens_per_chunk"] == 240.0 and said["executions"] == 2
    assert 0 < value < 105
    assert share.read(run) == pytest.approx(30.0)  # the step's, unmoved
    # a program with no linear layer under its scopes (the parent, another
    # family), a family without the functions, a run with no trace:
    # nothing to read, nothing raised
    bare = hand_run(family, config, counters, (),
                    [o for o in ops + chunk_ops if not any(
                        s in o[0] for s in share.LINEAR)])
    for reader in (share, step, chunk, scan):
        assert reader.read(bare) is None
    other = hand_run(family, config, counters, (), ops + chunk_ops)
    other.family = load_module(os.path.join(REPO, "benchmark", "families",
                                            "granite_hybrid.py"))
    for reader in (share, step, chunk, scan):
        assert reader.read(other) is None
    none = hand_run(family, config, counters, (), ops)
    none.trace = none.program_trace = None
    none.trace_on = False
    for reader in (share, step, chunk, scan):
        assert reader.read(none) is None


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
    # 5 slots x (six layers' float32 state and tail, two rings of 64 rows)
    assert said["engine_memory"]["cache_bytes"] == 5 * (
        6 * (4 * 8 * 12 * 4 + 3 * 80 * 2) + 64 * 2 * 2 * 32 * 2)
    close = said["engine_counters"]["close"]
    assert close["compiles"] == {"decode": 1, "prefill": 1}
    assert close["prefill_expert_rows"] > 0 and close["experts_hit"] > 0
    assert (close["expert_layers"], close["experts_held"],
            close["linear_layers"]) == (8, 8, 6)


@pytest.mark.parametrize("control", ["no_delta_term"])
def test_the_delta_rules_controls_fail_the_toys_limit(toy_root, capsys,
                                                      monkeypatch, control):
    """``tools/serve_check_delta_rule.py`` breaks the rule in the SYSTEM
    alone: with it the logits comparison fails, without it the same seed
    passes. (The toy engine's prompts are one chunk, so the state that is
    not carried has no boundary to show at: ``tests/test_qwen3_next.py``
    holds that control in chunks of 8.)"""
    from ray_tpu.ops import gated_delta
    from ray_tpu.serve import llm_engine

    tool = load_module(os.path.join(REPO, "benchmark", "tools",
                                    "serve_check_delta_rule.py"))
    monkeypatch.setattr(llm_engine, "_model_bundle",
                        llm_engine._model_bundle)  # put back
    monkeypatch.setattr(tool.many, "patch", tool.many.patch)
    for name in ("chunked_delta_rule", "delta_step", "rows_through_cache"):
        monkeypatch.setattr(gated_delta, name, getattr(gated_delta, name))
    # prompts that cross a chunk boundary of the toy engine's chunks
    config = {**TOY_CONFIG, "reference_check": {"prompt_lens": [13, 27],
                                                "follow": 3}}
    with open(os.path.join(toy_root, "benchmark", "configs",
                           "qwen3next-toy.json"), "w") as f:
        json.dump(config, f)
    try:
        args = ["--root", toy_root, "--workload", TOY_CELL["name"],
                "--seeds", "1", "--first-seed", "3000000023", "--rehearsal"]
        assert tool.many.main(args) == 0
        clean = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert clean["failed"] == 0 and clean["largest"] < 0.15
        assert tool.main(["--control", control] + args) == 0
        last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        assert last["failed"] == 1 and last["largest"] > 0.3
    finally:
        with open(os.path.join(toy_root, "benchmark", "configs",
                               "qwen3next-toy.json"), "w") as f:
            json.dump(TOY_CONFIG, f)


def test_the_cell_and_its_files(config):
    spec = load_json(os.path.join(REPO, "BENCHMARK.json"))
    cell = [w for w in spec["workloads"] if w["name"] == CELL]
    assert cell == [{**cell[0], "config": CONFIG, "chips": 1,
                     "traffic": "mixed_context_closed"}]
    entry = [c for c in spec["configs"] if c["name"] == CONFIG]
    assert entry == [{**entry[0], "reduced": REDUCED,
                      "source": config["source"],
                      "file": f"benchmark/configs/{CONFIG}.json"}]
    assert load_json(os.path.join(
        REPO, "benchmark", "cells", CELL + ".json")) \
        == {"deployment": "qwen3next_1chip_b64"}
    traffic = load_json(os.path.join(REPO, "benchmark", "traffic",
                                     "mixed_context_closed.json"))
    assert traffic["kind"] == "serve_closed"
    assert (traffic["clients_per_slot"], traffic["pool_requests"],
            traffic["sizes_seed"]) == (2, 4096, 20261002)
    assert traffic["prompt_len"] == {"dist": "log_uniform", "min": 512,
                                     "max": 16384}
    assert traffic["max_tokens"] == {"dist": "log_normal", "median": 768,
                                     "sigma": 0.5, "min": 256, "max": 2048}
    deployment = load_json(os.path.join(
        REPO, "benchmark", "deployments", "qwen3next_1chip_b64.json"))
    assert deployment["engine"] == {
        "max_batch": 64, "cache_len": 18432, "max_prompt_len": 16384,
        "prefill_rows": 4, "max_new_cap": 2048}
    assert deployment["trace_seconds"] == 5.0
    assert "four times their share" in deployment["what"]
    # the longest request fits the ring without a wrap
    assert traffic["prompt_len"]["max"] + traffic["max_tokens"]["max"] \
        == deployment["engine"]["cache_len"]
    # the pool's means: about 4,600 tokens in (18 chunks), about 860 out
    common = load_module(os.path.join(REPO, "benchmark", "kinds",
                                      "serve_common.py"))
    lens, new = common.draw_sizes(traffic, 4096)
    assert 4400 < lens.mean() < 4800 and 820 < new.mean() < 900
    reports = {m["name"] for m in spec["end_to_end"]
               if CELL in m.get("workloads", [CELL])}
    # (a later PR may report more on this cell, list further cells on the
    # metrics below and append metrics of its own: nothing here pins a
    # list to this cell alone or to the end of the file)
    assert {"serve_out_tokens_per_s", "setup_s"} <= reports
    per_layer = {m["name"]: m for m in spec["per_layer"]}
    new_ones = ("decode_linear_attention_time_pct",
                "prefill_linear_attention_time_pct",
                "gated_delta_scan_roofline", "gated_delta_step_roofline")
    for name in ("serve_decode_step_ms_p50", "serve_batch_occupancy_pct",
                 "decode_step_roofline", "serve_device_idle_pct.decode",
                 "serve_step_host_ms_p50",
                 "serve_idle_attributed_pct.decode",
                 "serve_prefill_fill_pct.decode",
                 "decode_attention_time_pct", "serve_sync_overshoot_ms_p50",
                 "serve_deliver_lag_ms_mean", "serve_polls_per_chunk",
                 "serve_poll_rpc_ms_p50", "prefill_chunk_roofline",
                 "serve_prefill_device_pct") + new_ones:
        assert CELL in per_layer[name]["workloads"], name
        assert per_layer[name]["moves"] == "serve_out_tokens_per_s"
    for name in new_ones:
        assert per_layer[name]["source"] == "device_trace"
        assert per_layer[name]["unit"] == "%"
        assert os.path.exists(os.path.join(METRICS, name + ".py"))
