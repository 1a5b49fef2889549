"""The ``granite_hybrid`` family's benchmark files: its configuration file
against the catalog row it was copied from, its counts against the arrays
the system makes, the bytes of a decode step and the work of a prefill
chunk by hand, the two new readers on hand-made runs, and a CPU rehearsal
of the cell's kind with a toy configuration of this family added to the
tests' toy root AS FILES AND ENTRIES (no tiny override lives in the
benchmark itself)."""

import json
import os
import types

import jax
import jax.numpy as jnp
import pytest

import benchmark_toy
from benchmark import run as bench_run
from benchmark import trace
from benchmark.loading import load_json, load_module

REPO = benchmark_toy.REPO
METRICS = os.path.join(REPO, "benchmark", "metrics")
CONFIG = "granite-4.0-h-small"
CELL = "serve_granite4hs_longdoc_sat"
REDUCED = ["num_hidden_layers", "layer_types", "num_local_experts",
           "vocab_size"]
PERIOD = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
# The catalog row's ``config`` (guides/model-configs/architectures.jsonl,
# granite-4.0-h-small), copied here so that the test needs no file outside
# the repository.
PUBLISHED = {
    "attention_bias": False, "attention_multiplier": 0.0078125,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 4096,
    "intermediate_size": 768, "layer_types": PERIOD * 4,
    "logits_scaling": 16, "mamba_chunk_size": 256, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128,
    "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 128,
    "mamba_proj_bias": False, "max_position_embeddings": 131072,
    "model_type": "granitemoehybrid", "normalization_function": "rmsnorm",
    "num_attention_heads": 32, "num_experts_per_tok": 10,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "num_local_experts": 72, "position_embedding_type": "nope",
    "residual_multiplier": 0.22, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 10000,
    "shared_intermediate_size": 1536, "tie_word_embeddings": True,
    "vocab_size": 100352}

TOY_CONFIG = {
    "family": "granite_hybrid",
    "source": "none: a toy of the granite_hybrid family for CPU rehearsals "
              "of the harness, never a benchmark configuration",
    "model_type": "granitemoehybrid", "vocab_size": 256, "hidden_size": 64,
    "num_hidden_layers": 3,
    "layer_types": ["mamba", "attention", "mamba"], "rms_norm_eps": 1e-05,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "attention_multiplier": 0.5, "embedding_multiplier": 12,
    "residual_multiplier": 0.22, "logits_scaling": 16,
    "mamba_n_heads": 8, "mamba_d_head": 16, "mamba_expand": 2,
    "mamba_n_groups": 1, "mamba_d_state": 16, "mamba_d_conv": 4,
    "mamba_chunk_size": 8, "num_local_experts": 4,
    "num_local_experts_published": 8, "num_experts_per_tok": 3,
    "intermediate_size": 48, "shared_intermediate_size": 96,
    "max_position_embeddings": 64, "reduced": [],
    "assumed": {"ssm_state_dtype": "float32", "init_std": 0.02,
                "experts_held": [0, 4],
                "why": "GraniteHybridConfig.tiny()'s sizes"},
    "reference_check": {"prompt_lens": [5, 11], "follow": 3},
    "tolerance": {"serve_logits_rel_l2": 0.08, "serve_token_regret_rms": 0.5,
                  "reason": "bfloat16 compute against a float32 reference "
                            "at toy width"},
}
TOY_CELL = {"name": "toy_granite_closed", "config": "granite-toy",
            "traffic": "toy_closed", "chips": 1,
            "why": "CPU rehearsal of kind serve_closed on this family"}


@pytest.fixture(scope="module")
def config():
    return load_json(os.path.join(REPO, "benchmark", "configs",
                                  CONFIG + ".json"))


@pytest.fixture(scope="module")
def family():
    return load_module(os.path.join(REPO, "benchmark", "families",
                                    "granite_hybrid.py"))


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    """The tests' toy root with this family's toy added as files and
    entries, the way a PR adds a configuration."""
    root = benchmark_toy.make_root(str(tmp_path_factory.mktemp("granite")))
    bench = os.path.join(root, "benchmark")
    with open(os.path.join(bench, "configs", "granite-toy.json"), "w") as f:
        json.dump(TOY_CONFIG, f)
    with open(os.path.join(bench, "cells",
                           TOY_CELL["name"] + ".json"), "w") as f:
        json.dump({"deployment": "toy_engine"}, f)
    spec = load_json(os.path.join(root, "BENCHMARK.json"))
    spec["configs"].append({
        "name": "granite-toy", "source": TOY_CONFIG["source"],
        "file": "benchmark/configs/granite-toy.json", "reduced": [],
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
    """Every key of the row's ``config`` is in the file, equal, except
    the four in ``reduced``; those state the published value beside the
    held one."""
    assert config["reduced"] == REDUCED
    assert config["source"] == "https://huggingface.co/ibm-granite/" \
        "granite-4.0-h-small/blob/main/config.json"
    for key, value in PUBLISHED.items():
        if key not in REDUCED:
            assert config[key] == value, key
        else:
            assert config[key + "_published"] == value, key
    # one whole period at the published 9 : 1, experts in every layer
    assert config["layer_types"] == PERIOD and config["num_hidden_layers"] \
        == len(PERIOD) == 10
    assert [PUBLISHED["layer_types"].count(k)
            for k in ("mamba", "attention")] == [36, 4]
    assert config["num_local_experts"] == 36 >= 8
    assert config["vocab_size"] * 2 == config["vocab_size_published"]
    assert config["assumed"]["experts_held"] == [0, 36]
    assert config["assumed"]["ssm_state_dtype"] == "float32"
    assert "expert_width_why" in config["assumed"]
    assert "two chips share each layer" in config["deployment"].lower()
    assert "8 chips" in config["deployment"]
    assert "param_dtype" not in json.dumps(config)
    assert config["reference_check"]["follow"] == 8
    lens = config["reference_check"]["prompt_lens"]
    assert lens[0] == 1100 and 2100 <= lens[1] <= 4100
    assert -(-lens[0] // 256) == 5 and lens[0] % 256  # inside its fifth


def test_counts_by_hand(config, family):
    """ISSUE 34's arithmetic, reckoned again by the family file."""
    sh = family.shape(config)
    assert sh["expert_params"] == 9_437_184
    assert sh["shared_params"] == 18_874_368
    assert family._mixer_params(config, "mamba") == 102_286_976
    assert family._mixer_params(config, "attention") == 41_943_040
    per_mamba = 102_286_976 + 294_912 + 18_874_368 + 36 * 9_437_184 + 8192
    assert per_mamba == 461_203_072
    assert family.param_count(config) == 9 * per_mamba + 400_859_136 \
        + 205_520_896 + 4096 == 4_757_211_776
    # a slot: 9 x (float32 state + bfloat16 tail), and 4 KiB of K/V a token
    assert sh["state_bytes_per_slot"] == 9 * (4_194_304 + 3 * 8448 * 2)
    assert sh["kv_bytes_per_token"] == 4096
    assert family.cache_bytes(config, 33, 8448) == 33 * (
        8448 * 4096 + 9 * 4_244_992) == 2_402_661_888
    for refused, args in ((family.train_flops_per_token, (config,)),
                          (family.attention_calls, (config, 16)),
                          (family.build_train, (config, None))):
        with pytest.raises(NotImplementedError, match="no training cell"):
            refused(*args)


def test_system_config_is_the_files_and_refuses_what_does_not_run(
        config, family):
    from ray_tpu.models.granite_hybrid import GraniteHybridConfig

    cfg = family.system_config(config)
    a = config["assumed"]
    assert cfg == GraniteHybridConfig(
        vocab_size=50176, layer_types=tuple(PERIOD), experts_held=(0, 36),
        embed_std=a["init_embed_std"])
    # the seeded draw: 0.02, and the one departure the file gives its
    # reason for (a file that assumes none gets 0.02 throughout)
    assert cfg.embed_std == 0.002 and "init_embed_std_why" in a
    plain = family.system_config({**config, "assumed": {
        k: v for k, v in a.items() if not k.startswith("init_")}})
    assert plain.embed_std == 0.02
    assert cfg.dtype == cfg.param_dtype == jnp.bfloat16
    assert (cfg.embedding_multiplier, cfg.attention_multiplier,
            cfg.residual_multiplier, cfg.logits_scaling) \
        == (12.0, 0.0078125, 0.22, 16.0)
    with pytest.raises(ValueError, match="nope"):
        family.system_config({**config, "position_embedding_type": "rope"})
    with pytest.raises(ValueError, match="tie_word_embeddings"):
        family.system_config({**config, "tie_word_embeddings": False})
    with pytest.raises(ValueError, match="float32"):
        family.system_config({**config, "assumed": {
            **config["assumed"], "ssm_state_dtype": "bfloat16"}})
    with pytest.raises(ValueError, match="experts_held"):
        family.system_config({**config, "num_local_experts": 18})
    with pytest.raises(ValueError, match="num_hidden_layers"):
        family.system_config({**config, "num_hidden_layers": 40})


@pytest.mark.parametrize("name, root_of", [
    ("granite-toy", "toy"), (CONFIG, "repository")])
def test_counts_agree_with_the_arrays_the_system_makes(toy_root, name,
                                                       root_of):
    """``param_count`` and ``cache_bytes`` against the shapes of what
    ``granite_hybrid_init`` / ``granite_hybrid_init_cache`` make (by
    ``eval_shape``), and ``engine_memory`` reading 2 bytes a parameter."""
    root = toy_root if root_of == "toy" else REPO
    config = load_json(os.path.join(root, "benchmark", "configs",
                                    name + ".json"))
    family = load_module(os.path.join(root, "benchmark", "families",
                                      "granite_hybrid.py"))
    common = load_module(os.path.join(root, "benchmark", "kinds",
                                      "serve_common.py"))
    engine = {"max_batch": 4, "cache_len": 64} if root_of == "toy" \
        else load_json(os.path.join(
            REPO, "benchmark", "deployments",
            "granite4hs_1chip_b32.json"))["engine"]
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
    if root_of == "repository":  # what the cell holds at rest: 11.9 GB
        assert 11.9e9 < held < 11.95e9


def test_to_reference_hands_the_leaves_over_as_they_are_stored(toy_root):
    config = load_json(os.path.join(toy_root, "benchmark", "configs",
                                    "granite-toy.json"))
    family = load_module(os.path.join(toy_root, "benchmark", "families",
                                      "granite_hybrid.py"))
    reference = load_module(os.path.join(toy_root, "benchmark", "reference",
                                         "granite_hybrid.py"))
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
    expert = 2 * 9_437_184
    stats = {"open": {"steps": 100, "experts_hit": 35_000},
             "close": {"steps": 300, "experts_hit": 105_000}}  # 350 a step
    need = family.decode_step_bytes(config, 2.0 * n, 32.0, 3500.0, stats)
    dense = 2 * (n - 10 * 36 * 9_437_184)  # the table once, as the head
    assert need == dense + 350 * expert + 32 * (
        3500 * 4096 + 2 * 9 * 4_244_992)
    # ISSUE 34's estimate: 9.5 GB of weights and 2.5 GB of state a step
    assert 11.0e9 < need < 12.5e9
    every = family.decode_step_bytes(config, 2.0 * n, 32.0, 3500.0, {})
    assert every - need == (360 - 350) * expert


def test_a_chunks_work_counts_required_work_only(config, family):
    n = family.param_count(config)
    # 230 real tokens, half of their 10 x 10 pairs landed here, a query
    # sees 2000 keys on average
    ops, io = family.prefill_chunk_work(config, 2.0 * n, 230.0, 11_500.0,
                                        2000.0)
    assert io == 2.0 * n + 2 * 9 * 4_244_992 + 2000 * 4096
    passed = 9 * (4096 * 16768 + 8192 * 4096) + 41_943_040 \
        + 10 * (294_912 + 18_874_368)
    assert family._token_params(config) == passed == 1_153_761_280
    assert ops == 2.0 * 230 * passed + 2.0 * 11_500 * 9_437_184 \
        + 230 * 4.0 * 4096 * 2000 + 2.0 * 50176 * 4096
    # under a TFLOP where the batched form computes about 2.3: required
    # work only, so memory bounds it (9.6 GB at 819 GB/s: 11.7 ms)
    assert 0.7e12 < ops < 0.8e12
    assert ops / 197e12 < io / 819e9
    # no real token, no pair: the weights are still read
    none, same = family.prefill_chunk_work(config, 2.0 * n, 0.0, 0.0)
    assert none == 2.0 * 50176 * 4096 and same == io - 2000 * 4096
    # only a prompt's last chunk needs logits: where one execution in
    # four is one, the others read their tokens' rows of the table and
    # not the table (the program runs the head in all: not required work)
    ops4, io4 = family.prefill_chunk_work(config, 2.0 * n, 230.0, 11_500.0,
                                          2000.0, 0.25)
    assert ops - ops4 == 0.75 * 2.0 * 50176 * 4096
    assert io - io4 == 0.75 * (50176 - 230) * 4096 * 2


def hand_run(family, config, counters, requests=()):
    """Two executions of the prefill program (0.02 s busy each) between
    three of the decode program (0.01 s each) in a 0.1 s window."""
    said = []
    tr = {"window": (0.0, 0.1), "host": [], "devices": [{
        "name": "/device:TPU:0", "async": [],
        "modules": [("jit_step_fn(1)", 0.00, 0.01),
                    ("jit_prefill_fn(2)", 0.02, 0.04),
                    ("jit_step_fn(1)", 0.04, 0.05),
                    ("jit_prefill_fn(2)", 0.05, 0.08),
                    ("jit_step_fn(1)", 0.08, 0.09)],
        "ops": [("fusion.1", 0.00, 0.01, "fusion"),
                ("fusion.2", 0.02, 0.04, "fusion"),
                ("fusion.1", 0.04, 0.05, "fusion"),
                ("fusion.2", 0.05, 0.06, "fusion"),
                ("fusion.3", 0.07, 0.08, "fusion"),
                ("fusion.1", 0.08, 0.09, "fusion")]}]}
    return types.SimpleNamespace(
        trace=tr, family=family, config=config, counters=counters,
        raw={"weight_bytes": 2.0 * family.param_count(config),
             "requests": list(requests)},
        params={"device_programs": {"decode": "jit_step_fn",
                                    "prefill": "jit_prefill_fn"}},
        device_kind="TPU v5 lite", window_ns=(0, 100),
        program_trace={"host": [], "ops": [], "modules": [],
                       "window": (0.0, 0.1)},
        trace_on=True, said=said,
        say=lambda event, **f: said.append((event, f)))


def test_the_prefill_programs_share_of_the_busy_time(config, family):
    reader = load_module(os.path.join(METRICS, "serve_prefill_device_pct.py"))
    run = hand_run(family, config, {})
    # 0.04 s of the 0.07 s busy were the prefill program's
    assert reader.read(run) == pytest.approx(100 * 0.04 / 0.07)
    assert run.said[0][0] == "prefill_device_share"
    assert run.said[0][1]["executions"] == 2
    # a deployment that names no prefill program, a run with no trace
    run.params = {"device_programs": {"decode": "jit_step_fn"}}
    assert reader.read(run) is None
    run.trace = None
    assert reader.read(run) is None


def test_the_chunk_programs_roofline_share(config, family):
    reader = load_module(os.path.join(METRICS, "prefill_chunk_roofline.py"))
    a = {"prefill_chunks": 10, "prefill_tokens_real": 2_000,
         "prefill_expert_rows": 100_000, "prefill_rows_real": 1}
    b = {"prefill_chunks": 30, "prefill_tokens_real": 6_600,
         "prefill_expert_rows": 330_000, "prefill_rows_real": 6}
    requests = [{"prompt_len": 3999, "first_ns": 50},
                {"prompt_len": 1000, "first_ns": 500}]  # outside the window
    run = hand_run(family, config, {"open": a, "close": b}, requests)
    value = reader.read(run)
    ops, io = family.prefill_chunk_work(
        config, run.raw["weight_bytes"], 230.0, 11_500.0, 2000.0, 0.25)
    least = max(ops / 197e12, io / 819e9)
    assert value == pytest.approx(100 * least / 0.02)
    said = dict(run.said)["prefill_chunk_roofline"]
    assert said["bound_by"] == "memory" and said["mean_keys"] == 2000.0
    assert said["tokens_per_chunk"] == 230.0
    assert said["last_chunk_share"] == 0.25
    assert 0 < value < 100
    # the parent of this PR keeps no such counter; a dense family has no
    # such function: nothing to read, nothing raised
    for key in ("prefill_expert_rows",):
        less = hand_run(family, config, {
            "open": {k: v for k, v in a.items() if k != key},
            "close": {k: v for k, v in b.items() if k != key}})
        assert reader.read(less) is None
    dense = hand_run(family, config, {"open": a, "close": b})
    dense.family = load_module(os.path.join(REPO, "benchmark", "families",
                                            "gpt2.py"))
    assert reader.read(dense) is None


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
    assert said["engine_memory"]["cache_bytes"] == 5 * (
        64 * 1 * 2 * 2 * 16 * 2 + 2 * (8 * 16 * 16 * 4 + 3 * 160 * 2))
    close = said["engine_counters"]["close"]
    assert close["compiles"] == {"decode": 1, "prefill": 1}
    assert close["expert_layers"] == 3 and close["experts_held"] == 4
    assert 0 < close["experts_hit"] <= close["steps"] * 3 * 4
    assert 0 < close["prefill_expert_rows"] \
        <= close["prefill_tokens_real"] * 3 * 3


def test_the_cell_and_its_files(config):
    spec = load_json(os.path.join(REPO, "BENCHMARK.json"))
    cell = [w for w in spec["workloads"] if w["name"] == CELL]
    assert cell == [{**cell[0], "config": CONFIG, "chips": 1,
                     "traffic": "longdoc_answer_closed"}]
    traffic = load_json(os.path.join(REPO, "benchmark", "traffic",
                                     "longdoc_answer_closed.json"))
    assert traffic["kind"] == "serve_closed"
    assert (traffic["clients_per_slot"], traffic["pool_requests"],
            traffic["sizes_seed"]) == (2, 4096, 20260929)
    assert traffic["prompt_len"] == {"dist": "log_uniform", "min": 1024,
                                     "max": 8192}
    assert traffic["max_tokens"] == {"dist": "log_normal", "median": 128,
                                     "sigma": 0.4, "min": 64, "max": 256}
    deployment = load_json(os.path.join(
        REPO, "benchmark", "deployments", "granite4hs_1chip_b32.json"))
    assert deployment["engine"] == {
        "max_batch": 32, "cache_len": 8448, "max_prompt_len": 8192,
        "prefill_rows": 4, "max_new_cap": 256}
    assert deployment["trace_seconds"] == 5.0
    # the longest request fits the ring without a wrap
    assert traffic["prompt_len"]["max"] + traffic["max_tokens"]["max"] \
        == deployment["engine"]["cache_len"]
    # the pool's mean prompt: about 3,450 tokens, 13.5 chunks and more
    common = load_module(os.path.join(REPO, "benchmark", "kinds",
                                      "serve_common.py"))
    lens, new = common.draw_sizes(traffic, 4096)
    assert 3300 < lens.mean() < 3600 and 120 < new.mean() < 145
    reports = {m["name"] for m in spec["end_to_end"]
               if CELL in m.get("workloads", [CELL])}
    # (a later PR may report more on this cell, list further cells on the
    # two metrics below and append metrics of its own: nothing here pins a
    # list to this cell alone or to the end of the file)
    assert {"serve_out_tokens_per_s", "setup_s"} <= reports
    per_layer = {m["name"]: m for m in spec["per_layer"]}
    for name in ("prefill_chunk_roofline", "serve_prefill_device_pct"):
        assert CELL in per_layer[name]["workloads"], name
        assert per_layer[name]["moves"] == "serve_out_tokens_per_s"
