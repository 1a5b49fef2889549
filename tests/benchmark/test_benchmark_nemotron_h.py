"""The ``nemotron_h`` family's benchmark files: its counts against the
arrays the system makes, its configuration file against the catalog row it
was copied from, the decode step's bytes from the engine's counters, the
new readers on hand-made runs, and a CPU rehearsal of the cell's kind with
a toy configuration of this family added to the tests' toy root AS FILES
AND ENTRIES (no tiny override lives in the benchmark itself)."""

import json
import os
import types

import jax
import jax.numpy as jnp
import pytest

import benchmark_toy
from benchmark import run as bench_run
from benchmark.loading import load_json, load_module
from ray_tpu.models import nemotron_h as nh

REPO = benchmark_toy.REPO
CONFIG = "nemotron3-super-120b-a12b"
CELL = "serve_nemotron3s_decode_sat"
REDUCED = ["hybrid_override_pattern", "n_routed_experts", "vocab_size"]
# The catalog row's ``config`` (guides/model-configs/architectures.jsonl,
# NVIDIA-Nemotron-3-Super-120B-A12B-BF16), copied here so that the test
# needs no file outside the repository.
PUBLISHED = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
    "expand": 2, "head_dim": 128, "hidden_size": 4096,
    "hybrid_override_pattern":
        "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
        "EMEMEMEMEM*EMEMEMEMEM*EMEMEMEM*EMEMEMEME",
    "intermediate_size": 2688, "layer_norm_epsilon": 1e-05,
    "mamba_head_dim": 64, "mamba_hidden_act": "silu",
    "mamba_num_heads": 128, "mamba_proj_bias": False,
    "max_position_embeddings": 262144, "mlp_bias": False,
    "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
    "moe_intermediate_size": 2688, "moe_latent_size": 1024,
    "moe_shared_expert_intermediate_size": 5376,
    "moe_shared_expert_overlap": False, "mtp_hybrid_override_pattern": "*E",
    "n_group": 1, "n_groups": 8, "n_routed_experts": 512,
    "n_shared_experts": 1, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 22,
    "num_hidden_layers": 88, "num_key_value_heads": 2,
    "num_logits_to_keep": 1, "num_nextn_predict_layers": 1,
    "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
    "residual_in_fp32": False, "rope_theta": 10000,
    "routed_scaling_factor": 5, "sliding_window": None,
    "ssm_state_size": 128, "tie_word_embeddings": False,
    "time_step_floor": 0.0001, "time_step_max": 0.1, "time_step_min": 0.001,
    "topk_group": 1, "use_bias": False, "use_conv_bias": True,
    "use_mamba_kernels": True, "vocab_size": 131072}

TOY_CONFIG = {
    "family": "nemotron_h",
    "source": "none: a toy of the nemotron_h family for CPU rehearsals of "
              "the harness, never a benchmark configuration",
    "model_type": "nemotron_h", "vocab_size": 256, "hidden_size": 64,
    "hybrid_override_pattern": "ME*EM", "layer_norm_epsilon": 1e-05,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "mamba_num_heads": 8, "mamba_head_dim": 16, "expand": 2, "n_groups": 2,
    "ssm_state_size": 16, "conv_kernel": 4, "chunk_size": 8,
    "n_routed_experts": 4, "n_routed_experts_published": 8,
    "num_experts_per_tok": 3, "moe_latent_size": 32,
    "moe_intermediate_size": 48, "moe_shared_expert_intermediate_size": 96,
    "routed_scaling_factor": 5, "max_position_embeddings": 64,
    "reduced": [],
    "assumed": {"attention_position_embedding": "none",
                "ssm_state_dtype": "float32", "mtp": "not served",
                "experts_held": [0, 4],
                "why": "NemotronHConfig.tiny()'s sizes"},
    "reference_check": {"prompt_lens": [5, 11], "follow": 3},
    "tolerance": {"serve_logits_rel_l2": 0.08, "serve_token_regret_rms": 0.5,
                  "reason": "bfloat16 compute against a float32 reference "
                            "at toy width"},
}
TOY_CELL = {"name": "toy_nemotron_closed", "config": "nemotron-toy",
            "traffic": "toy_closed", "chips": 1,
            "why": "CPU rehearsal of kind serve_closed on the hybrid family"}


@pytest.fixture(scope="module")
def config():
    return load_json(os.path.join(REPO, "benchmark", "configs",
                                  CONFIG + ".json"))


@pytest.fixture(scope="module")
def family():
    return load_module(os.path.join(REPO, "benchmark", "families",
                                    "nemotron_h.py"))


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    """The tests' toy root with this family's toy added as files and
    entries, the way a PR adds a configuration."""
    root = benchmark_toy.make_root(str(tmp_path_factory.mktemp("nemotron")))
    bench = os.path.join(root, "benchmark")
    with open(os.path.join(bench, "configs", "nemotron-toy.json"), "w") as f:
        json.dump(TOY_CONFIG, f)
    with open(os.path.join(bench, "cells",
                           TOY_CELL["name"] + ".json"), "w") as f:
        json.dump({"deployment": "toy_engine"}, f)
    spec = load_json(os.path.join(root, "BENCHMARK.json"))
    spec["configs"].append({
        "name": "nemotron-toy", "source": TOY_CONFIG["source"],
        "file": "benchmark/configs/nemotron-toy.json", "reduced": [],
        "why": "CPU rehearsal"})
    spec["workloads"].append(TOY_CELL)
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "toy_closed" in m.get("workloads", []) \
                or m.get("workloads") == [CELL]:
            m["workloads"] = m["workloads"] + [TOY_CELL["name"]]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    return root


def test_the_file_holds_the_catalog_rows_config(config):
    """Every key of the row's ``config`` is in the file, equal, except
    the three in ``reduced``; those state the published value beside the
    held one."""
    assert config["reduced"] == REDUCED
    assert config["source"] == "https://huggingface.co/nvidia/" \
        "NVIDIA-Nemotron-3-Super-120B-A12B-BF16/blob/main/config.json"
    for key, value in PUBLISHED.items():
        if key not in REDUCED:
            assert config[key] == value, key
    pattern = PUBLISHED["hybrid_override_pattern"]
    assert config["hybrid_override_pattern"] == pattern[:11] \
        == "MEMEMEM*EME"
    assert config["hybrid_override_pattern_published"] == pattern
    # one whole period: the published ratio 40:40:8 is 5:5:1
    assert [pattern.count(k) for k in "ME*"] == [40, 40, 8]
    assert [pattern[:11].count(k) for k in "ME*"] == [5, 5, 1]
    assert config["n_routed_experts"] == 128
    assert config["n_routed_experts_published"] == 512
    assert config["vocab_size"] * 4 == config["vocab_size_published"] \
        == 131072
    assert config["assumed"]["experts_held"] == [0, 128]
    assert "four chips share each layer" in config["deployment"].lower()
    assert "param_dtype" not in json.dumps(config)


def test_counts_by_hand(config, family):
    """ISSUE 28's arithmetic, reckoned again by the family file."""
    assert family.shape(config)["expert_params"] == 5_505_024
    assert family._layer_params(config, "E") == 759_173_632
    assert family._layer_params(config, "M") == 109_640_064
    assert family._layer_params(config, "*") == 35_655_680
    assert family.param_count(config) == 4_648_163_712
    # a slot: 5 x (float32 state + bfloat16 tail), and 1 KiB of K/V a token
    sh = family.shape(config)
    assert sh["state_bytes_per_slot"] == 5 * (4_194_304 + 61_440)
    assert sh["kv_bytes_per_token"] == 1024
    assert family.cache_bytes(config, 65, 2048) == 65 * (
        2048 * 1024 + 5 * 4_255_744) == 1_519_431_680
    for refused, args in ((family.train_flops_per_token, (config,)),
                          (family.attention_calls, (config, 16)),
                          (family.build_train, (config, None))):
        with pytest.raises(NotImplementedError, match="no training cell"):
            refused(*args)


def test_system_config_is_the_files_and_refuses_what_does_not_run(
        config, family):
    from ray_tpu.models.nemotron_h import NemotronHConfig

    cfg = family.system_config(config)
    assert cfg == NemotronHConfig(
        vocab_size=32768, pattern="MEMEMEM*EME", experts_held=(0, 128))
    assert cfg.dtype == cfg.param_dtype == jnp.bfloat16
    with pytest.raises(ValueError, match="relu2"):
        family.system_config({**config, "mlp_hidden_act": "silu"})
    with pytest.raises(ValueError, match="position"):
        family.system_config({**config, "assumed": {
            **config["assumed"], "attention_position_embedding": "rope"}})
    with pytest.raises(ValueError, match="experts_held"):
        family.system_config({**config, "n_routed_experts": 64})


@pytest.mark.parametrize("name, root_of", [
    ("nemotron-toy", "toy"), (CONFIG, "repository")])
def test_counts_agree_with_the_arrays_the_system_makes(toy_root, name,
                                                       root_of):
    """``param_count`` and ``cache_bytes`` against the shapes of what
    ``nemotron_h_init`` / ``nemotron_h_init_cache`` make (the toy's real
    arrays; the benchmark configuration's by ``eval_shape``), and
    ``engine_memory`` reading 2 bytes a parameter from them."""
    root = toy_root if root_of == "toy" else REPO
    config = load_json(os.path.join(root, "benchmark", "configs",
                                    name + ".json"))
    family = load_module(os.path.join(root, "benchmark", "families",
                                      "nemotron_h.py"))
    common = load_module(os.path.join(root, "benchmark", "kinds",
                                      "serve_common.py"))
    engine = {"max_batch": 4, "cache_len": 64} if root_of == "toy" \
        else load_json(os.path.join(
            REPO, "benchmark", "deployments",
            "nemotron3s_1chip_b64.json"))["engine"]
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


def test_to_reference_hands_the_leaves_over_as_they_are_stored(toy_root):
    config = load_json(os.path.join(toy_root, "benchmark", "configs",
                                    "nemotron-toy.json"))
    family = load_module(os.path.join(toy_root, "benchmark", "families",
                                      "nemotron_h.py"))
    reference = load_module(os.path.join(toy_root, "benchmark", "reference",
                                         "nemotron_h.py"))
    params = family.init_params(config, 5)
    ref = family.to_reference(params, config)
    assert sum(x.size for x in jax.tree.leaves(ref)) \
        == family.param_count(config)
    assert all(x.dtype == jnp.bfloat16 for x in jax.tree.leaves(ref))
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


def test_decode_step_bytes_counts_hit_experts_and_state_both_ways(config,
                                                                  family):
    n = family.param_count(config)
    expert = 2 * 5_505_024
    stats = {"open": {"steps": 100, "experts_hit": 60_000},
             "close": {"steps": 300, "experts_hit": 180_000}}  # 600 a step
    need = family.decode_step_bytes(config, 2.0 * n, 64.0, 700.0, stats)
    dense = 2 * (n - 5 * 128 * 5_505_024 - (32768 - 64) * 4096)
    assert need == dense + 600 * expert + 64 * (
        700 * 1024 + 2 * 5 * 4_255_744)
    # ISSUE 28's estimate: about 11.4 GB a step at 64 rows
    assert 10.5e9 < need < 11.6e9
    # a dense family's caller passes no counters: every held expert
    every = family.decode_step_bytes(config, 2.0 * n, 64.0, 700.0, {})
    assert every - need == (640 - 600) * expert


def fake_run(counters, raw=None):
    return types.SimpleNamespace(counters=counters, raw=raw or {},
                                 say=lambda *a, **k: None)


def test_the_counter_readers_on_a_hand_made_window():
    hit = load_module(os.path.join(REPO, "benchmark", "metrics",
                                   "moe_experts_hit_pct.py"))
    rows = load_module(os.path.join(REPO, "benchmark", "metrics",
                                    "moe_rows_per_expert.py"))
    a = {"steps": 10, "experts_hit": 6_000, "expert_rows": 18_000}
    b = {"steps": 110, "experts_hit": 66_160, "expert_rows": 198_480,
         "expert_layers": 5, "experts_held": 128}
    run = fake_run({"open": a, "close": b})
    assert hit.read(run) == pytest.approx(100 * 60_160 / (100 * 640))
    assert rows.read(run) == pytest.approx(180_480 / 60_160)
    # a program that keeps no such counter (a dense family, the parent)
    dense = fake_run({"open": {"steps": 10}, "close": {"steps": 110}})
    assert hit.read(dense) is None and rows.read(dense) is None
    assert hit.read(fake_run({})) is None and rows.read(fake_run({})) is None


def test_the_scope_readers_on_a_hand_made_profile():
    """The four by-scope readers reduce one table a program by the longer
    scope list; the TPU's grouped product, which carries no scope, counts
    as the experts'; a profile that names none of the new scopes (the
    parent's programs, a dense family's) gives None and nothing is
    raised."""
    moe, ssm, lane_moe, lane_ssm = (
        load_module(os.path.join(REPO, "benchmark", "metrics", f"{n}.py"))
        for n in ("decode_moe_time_pct", "decode_ssm_time_pct",
                  "prefill_moe_time_pct", "prefill_ssm_time_pct"))
    assert moe.scope_of("jit(prefill_fn)/ragged-dot-none") == "experts"
    assert moe.scope_of("jit(step_fn)/experts/dot_general:") == "experts"
    assert moe.scope_of("jit(step_fn)/ssm_update/mul") == "ssm_update"
    assert moe.scope_of("jit(step_fn)/attn/ln/mul") == "ln"
    assert moe.scope_of(None) == "(no path)"
    assert moe.scope_of("jit(step_fn)/while/body/add") == "(no scope) add"
    ops = [("fusion.1", 0.0, 4.0, "jit(step_fn)/experts/dot_general"),
           ("fusion.2", 4.0, 5.0, "jit(step_fn)/router/dot_general"),
           ("fusion.3", 5.0, 8.0, "jit(step_fn)/ssm_update/mul"),
           ("fusion.4", 8.0, 9.0, "jit(step_fn)/state_write/dus"),
           ("fusion.5", 9.0, 10.0, "jit(step_fn)/attn/dot_general"),
           # the lane, after the step
           ("ragged-dot.1", 10.0, 13.0, "jit(prefill_fn)/ragged-dot-none"),
           ("fusion.6", 13.0, 14.0, "jit(prefill_fn)/experts/square"),
           ("fusion.7", 14.0, 18.0, "jit(prefill_fn)/ssm_scan/dot_general"),
           ("fusion.8", 18.0, 20.0, "jit(prefill_fn)/attn/dot_general")]
    said = []

    def run_of(ops):
        return types.SimpleNamespace(
            raw={}, program_trace={
                "host": [], "ops": ops, "window": (0.0, 20.0),
                "modules": [("jit_step_fn(1)", 0.0, 10.0),
                            ("jit_prefill_fn(2)", 10.0, 20.0)]},
            params={"device_programs": {"decode": "jit_step_fn",
                                        "prefill": "jit_prefill_fn"}},
            trace=object(), trace_on=True,
            say=lambda event, **f: said.append((event, f)))

    run = run_of(ops)
    assert moe.read(run) == pytest.approx(50.0)
    assert ssm.read(run) == pytest.approx(40.0)
    assert [e for e, _ in said] == ["decode_by_scope_hybrid"]  # said once
    assert said[0][1]["by_scope_pct"]["experts"] == pytest.approx(40.0)
    assert lane_moe.read(run) == pytest.approx(40.0)
    assert lane_ssm.read(run) == pytest.approx(40.0)
    assert [e for e, _ in said] == ["decode_by_scope_hybrid",
                                    "prefill_by_scope_hybrid"]
    assert said[1][1]["by_scope_pct"]["experts"] == pytest.approx(40.0)
    dense = run_of([("fusion.1", 0.0, 4.0, "jit(step_fn)/attn/dot_general"),
                    ("fusion.2", 4.0, 5.0, "jit(step_fn)/mlp/dot_general"),
                    ("fusion.3", 10.0, 15.0,
                     "jit(prefill_fn)/mlp/dot_general")])
    assert [r.read(dense) for r in (moe, ssm, lane_moe, lane_ssm)] \
        == [None] * 4


@pytest.mark.parametrize("trace, names", [
    (0, {"setup_s", "serve_out_tokens_per_s"}),
    (1, {"serve_decode_step_ms_p50", "serve_batch_occupancy_pct",
         "moe_experts_hit_pct", "moe_rows_per_expert"}),
])
def test_rehearsal_of_the_cells_kind_with_this_family(toy_root, capsys,
                                                      trace, names):
    code = bench_run.main([
        "--root", toy_root, "--workload", TOY_CELL["name"], "--seed",
        "3000000019", "--seconds", "2.5", "--trace", str(trace),
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
    assert said["engine_memory"]["cache_bytes"] == 5 * (
        64 * 2 * 2 * 16 * 2 + 2 * (8 * 16 * 16 * 4 + 3 * 192 * 2))
    close = said["engine_counters"]["close"]
    assert close["compiles"] == {"decode": 1, "prefill": 1}
    assert close["expert_layers"] == 2 and close["experts_held"] == 4
    assert 0 < close["experts_hit"] <= close["steps"] * 2 * 4


@pytest.mark.parametrize("fault", ["state_bf16", "fp8_experts"])
def test_the_hand_run_control_reaches_the_deployed_engine(toy_root, capsys,
                                                          monkeypatch, fault):
    """``tools/serve_check_many.py --engine`` puts the fault into the
    serving functions AND into the engine it deploys, and reads both
    comparisons: the control that the two limits' reasons quote."""
    from ray_tpu.serve import llm_engine

    tool = load_module(os.path.join(REPO, "benchmark", "tools",
                                    "serve_check_many.py"))
    bundle = llm_engine._model_bundle
    monkeypatch.setattr(llm_engine, "_model_bundle", bundle)  # put back
    # (the tool patches the family's module, which every run of this
    # process shares: put that back too)
    family = load_module(os.path.join(toy_root, "benchmark", "families",
                                      "nemotron_h.py"))
    for name in ("system_config", "serve_logits"):
        monkeypatch.setattr(family, name, getattr(family, name))
    seen = []
    init_cache = nh.nemotron_h_init_cache
    monkeypatch.setattr(nh, "nemotron_h_init_cache", lambda cfg, *a: (
        seen.append(cfg.ssm_state_dtype), init_cache(cfg, *a))[1])
    assert tool.main(["--root", toy_root, "--workload", TOY_CELL["name"],
                      "--seeds", "1", "--first-seed", "3000000023",
                      "--fault", fault, "--engine", "--rehearsal"]) == 0
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert last["fault"] == fault and len(last["seeds"]) == 1
    one = last["seeds"][0]
    assert one["min"] <= one["median"] <= one["max"] == last["largest"]
    assert one["compared"] >= 2 and one["regret"] == last["largest_regret"]
    assert len(seen) == 2  # the logits comparison's cache, the engine's
    want = jnp.bfloat16 if fault == "state_bf16" else jnp.float32
    assert seen == [want, want]
    if fault == "fp8_experts":
        assert llm_engine._model_bundle is not bundle
    # without the engine: many seeds a process, and the regret of the
    # serving functions' own greedy choice at every position
    assert tool.main(["--root", toy_root, "--workload", TOY_CELL["name"],
                      "--seeds", "2", "--first-seed", "3000000023",
                      "--fault", fault, "--rehearsal"]) == 0
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert [o["seed"] for o in last["seeds"]] == [3000000023, 3000000024]
    assert all(o["regret"] >= 0 and 0 <= o["turned"] <= 2 * 4
               for o in last["seeds"])
    assert last["largest_regret"] == max(o["regret"] for o in last["seeds"])


def test_the_cell_and_its_files(config):
    spec = load_json(os.path.join(REPO, "BENCHMARK.json"))
    cell = [w for w in spec["workloads"] if w["name"] == CELL]
    assert cell == [{**cell[0], "config": CONFIG, "chips": 1,
                     "traffic": "reasoning_decode_closed"}]
    traffic = load_json(os.path.join(REPO, "benchmark", "traffic",
                                     "reasoning_decode_closed.json"))
    assert traffic["kind"] == "serve_closed"
    assert (traffic["clients_per_slot"], traffic["pool_requests"],
            traffic["sizes_seed"]) == (2, 4096, 20260928)
    assert traffic["prompt_len"] == {"dist": "log_uniform", "min": 128,
                                     "max": 1024}
    assert traffic["max_tokens"] == {"dist": "log_normal", "median": 128,
                                     "sigma": 0.6, "min": 32, "max": 512}
    deployment = load_json(os.path.join(
        REPO, "benchmark", "deployments", "nemotron3s_1chip_b64.json"))
    assert deployment["engine"] == {
        "max_batch": 64, "cache_len": 2048, "max_prompt_len": 1024,
        "prefill_rows": 4, "max_new_cap": 512}
    assert deployment["max_concurrent"] == 256
    assert deployment["trace_seconds"] == 5.0
    # the longest request fits the cache without a wrap
    assert traffic["prompt_len"]["max"] + traffic["max_tokens"]["max"] \
        <= deployment["engine"]["cache_len"]
    assert config["reference_check"] == {"prompt_lens": [300, 900],
                                         "follow": 8}
    new = {m["name"]: m for m in spec["per_layer"]
           if m.get("workloads") == [CELL]}
    assert set(new) == {"decode_moe_time_pct", "decode_ssm_time_pct",
                        "moe_experts_hit_pct", "moe_rows_per_expert",
                        "prefill_moe_time_pct", "prefill_ssm_time_pct"}
    assert {m["moves"] for m in new.values()} == {"serve_out_tokens_per_s"}
