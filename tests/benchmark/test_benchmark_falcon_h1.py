"""The ``falcon_h1`` family's benchmark files: its configuration file
against the catalog row it was copied from, its counts against the arrays
the system makes, the bytes of a decode step, the two mixers' work and a
prefill chunk's by hand, the two new readers on hand-made runs, and a CPU
rehearsal of the cell's kind with a toy configuration of this family added
to the tests' toy root AS FILES AND ENTRIES (no tiny override lives in the
benchmark itself)."""

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
CONFIG = "falcon-h1-34b-instruct"
CELL = "serve_falconh1_longgen_sat"
REDUCED = ["num_hidden_layers", "vocab_size"]
# The catalog row's ``config`` (guides/model-configs/architectures.jsonl,
# Falcon-H1-34B-Instruct), copied here so that the test needs no file
# outside the repository.
PUBLISHED = {
    "attention_bias": False, "attention_in_multiplier": 1,
    "attention_out_multiplier": 0.0375, "attn_layer_indices": None,
    "embedding_multiplier": 5.656854249492381, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 5120, "intermediate_size": 21504,
    "key_multiplier": 0.011048543456039804,
    "lm_head_multiplier": 0.0078125, "mamba_chunk_size": 128,
    "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 128,
    "mamba_d_ssm": 4096, "mamba_d_state": 256, "mamba_expand": 2,
    "mamba_n_groups": 2, "mamba_n_heads": 32,
    "mamba_norm_before_gate": False, "mamba_proj_bias": False,
    "mamba_rms_norm": True, "mamba_use_mlp": True,
    "max_position_embeddings": 262144, "mlp_bias": False,
    "mlp_expansion_factor": 8,
    "mlp_multipliers": [0.1767766952966369, 0.011160714285714284],
    "model_type": "falcon_h1", "num_attention_heads": 20,
    "num_hidden_layers": 72, "num_key_value_heads": 4,
    "num_logits_to_keep": 1, "projectors_bias": False,
    "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 100000000000, "ssm_in_multiplier": 0.25,
    "ssm_multipliers": [0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
                        0.3535533905932738],
    "ssm_out_multiplier": 0.08838834764831845,
    "tie_word_embeddings": False, "vocab_size": 261120}
LAYER = 430_120_032
HELD = 4_205_319_008
SLOT_LAYER = 14_710_784  # a slot's bytes a layer: state, tail, 5120 rows

TOY_CONFIG = {
    "family": "falcon_h1",
    "source": "none: a toy of the falcon_h1 family for CPU rehearsals of "
              "the harness, never a benchmark configuration",
    "model_type": "falcon_h1", "vocab_size": 256, "hidden_size": 48,
    "num_hidden_layers": 3, "rms_norm_eps": 1e-05,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "rope_theta": 10000, "mamba_n_heads": 8, "mamba_d_head": 8,
    "mamba_d_ssm": 64, "mamba_expand": 2, "mamba_n_groups": 2,
    "mamba_d_state": 24, "mamba_d_conv": 4, "mamba_chunk_size": 8,
    "intermediate_size": 80, "embedding_multiplier": 2.3,
    "lm_head_multiplier": 0.37, "key_multiplier": 0.43,
    "attention_in_multiplier": 0.9, "attention_out_multiplier": 0.31,
    "ssm_in_multiplier": 0.7, "ssm_out_multiplier": 0.27,
    "ssm_multipliers": [0.6, 0.45, 0.35, 0.8, 0.55],
    "mlp_multipliers": [0.65, 0.21], "tie_word_embeddings": False,
    "max_position_embeddings": 64, "reduced": [],
    "assumed": {"ssm_state_dtype": "float32",
                "init_gains": {"embed": 1.0, "q": 1.2, "k": 1.2, "v": 1.0,
                               "o": 4.0, "ssm_in": 2.5, "ssm_out": 0.6,
                               "gate": 1.0, "up": 1.0, "down": 1.2,
                               "head": 1.0},
                "why": "FalconH1Config.tiny()'s sizes; a state of 24 "
                       "numbers needs a heavier in_proj than one of 256 "
                       "for its readout to weigh what the skip weighs"},
    "reference_check": {"prompt_lens": [5, 11], "follow": 3},
    "tolerance": {"serve_logits_rel_l2": 0.08, "serve_token_regret_rms": 0.5,
                  "reason": "bfloat16 compute against a float32 reference "
                            "at toy width"},
}
TOY_CELL = {"name": "toy_falcon_closed", "config": "falcon-toy",
            "traffic": "toy_closed", "chips": 1,
            "why": "CPU rehearsal of kind serve_closed on this family"}


@pytest.fixture(scope="module")
def config():
    return load_json(os.path.join(REPO, "benchmark", "configs",
                                  CONFIG + ".json"))


@pytest.fixture(scope="module")
def family():
    return load_module(os.path.join(REPO, "benchmark", "families",
                                    "falcon_h1.py"))


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    """The tests' toy root with this family's toy added as files and
    entries, the way a PR adds a configuration."""
    root = benchmark_toy.make_root(str(tmp_path_factory.mktemp("falcon")))
    bench = os.path.join(root, "benchmark")
    with open(os.path.join(bench, "configs", "falcon-toy.json"), "w") as f:
        json.dump(TOY_CONFIG, f)
    with open(os.path.join(bench, "cells",
                           TOY_CELL["name"] + ".json"), "w") as f:
        json.dump({"deployment": "toy_engine"}, f)
    spec = load_json(os.path.join(root, "BENCHMARK.json"))
    spec["configs"].append({
        "name": "falcon-toy", "source": TOY_CONFIG["source"],
        "file": "benchmark/configs/falcon-toy.json", "reduced": [],
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
    two in ``reduced``; those state the published value beside the held
    one. No width is among them."""
    assert config["reduced"] == REDUCED
    assert config["source"] == "https://huggingface.co/tiiuae/" \
        "Falcon-H1-34B-Instruct/blob/main/config.json"
    for key, value in PUBLISHED.items():
        if key not in REDUCED:
            assert config[key] == value, key
        else:
            assert config[key + "_published"] == value, key
    # one pipeline stage of eight: nine whole layers, an eighth of both
    # tables; the guide's floors (four layers, an eighth) are kept
    assert config["num_hidden_layers"] * 8 == 72 and \
        config["num_hidden_layers"] >= 4
    assert config["vocab_size"] * 8 == config["vocab_size_published"]
    a = config["assumed"]
    assert a["ssm_state_dtype"] == "float32"
    assert set(a["init_gains"]) == {
        "embed", "q", "k", "v", "o", "ssm_in", "ssm_out", "gate", "up",
        "down", "head"}
    for why in ("init_gains_why", "rotary_lanes_why", "multipliers_why",
                "gate_norm_why", "d_inner_why"):
        assert len(a[why]) > 40, why
    assert "none of the fourteen multipliers is folded" in \
        a["multipliers_why"]
    deployment = config["deployment"].lower()
    for said in ("eight pipeline stages of nine whole layers",
                 "eight row slices of 32640", "4,205,319,008",
                 "whole vocabulary, up to 6 layers", "micro-batches"):
        assert said in deployment, said
    assert "param_dtype" not in json.dumps(config)
    assert "bfloat16 weights" in config["computes_in"]
    assert config["reference_check"]["follow"] == 8
    lens = config["reference_check"]["prompt_lens"]
    assert lens == [1100, 3100] and all(n % 256 for n in lens)
    assert set(config["tolerance"]) == {
        "serve_logits_rel_l2", "serve_token_regret_rms", "reason"}


def test_counts_by_hand(config, family):
    """ISSUE 43's arithmetic, reckoned again by the family file."""
    sh = family.shape(config)
    assert sh["attn_params"] == 31_457_280
    assert sh["mixer_params"] == 5120 * 9248 + 20_480 + 5_120 + 96 \
        + 4_096 + 4096 * 5120 == 68_351_072
    assert sh["mlp_params"] == 330_301_440
    assert sh["attn_params"] + sh["mixer_params"] + sh["mlp_params"] \
        + 10_240 == LAYER
    assert family.param_count(config) == 9 * LAYER + 2 * 167_116_800 \
        + 5_120 == HELD
    whole = {**config, "num_hidden_layers": 72, "vocab_size": 261120}
    assert family.param_count(whole) == 72 * LAYER + 2_673_868_800 + 5_120
    assert 33.6e9 < family.param_count(whole) < 33.7e9
    # a slot, in EVERY layer: float32 state, bfloat16 tail, 2 KiB a token
    assert sh["state_bytes_per_slot"] == 9 * (4_194_304 + 30_720)
    assert sh["kv_bytes_per_token"] == 9 * 2_048
    assert family.cache_bytes(config, 33, 5120) == 33 * 9 * SLOT_LAYER \
        == 4_369_102_848
    # the published depth: 302 MB of state a sequence
    assert 72 * 4_194_304 == 301_989_888
    for refused, args in ((family.train_flops_per_token, (config,)),
                          (family.attention_calls, (config, 16)),
                          (family.build_train, (config, None))):
        with pytest.raises(NotImplementedError, match="no training cell"):
            refused(*args)


def test_system_config_is_the_files_and_refuses_what_does_not_run(
        config, family):
    from ray_tpu.models.falcon_h1 import GAINS, FalconH1Config

    cfg = family.system_config(config)
    assert cfg == FalconH1Config(vocab_size=32640, n_layer=9)
    assert dict(cfg.gains) == config["assumed"]["init_gains"] == dict(GAINS)
    assert cfg.dtype == cfg.param_dtype == jnp.bfloat16
    assert cfg.ssm_state_dtype == jnp.float32
    assert cfg.mamba.in_multipliers == tuple(config["ssm_multipliers"])
    # d_inner is the heads' width, not mamba_expand x hidden
    assert cfg.mamba.d_inner == 4096 != 2 * 5120
    assert (cfg.mamba.conv_dim, cfg.mamba.in_width) == (5120, 9248)
    with pytest.raises(ValueError, match="tie_word_embeddings"):
        family.system_config({**config, "tie_word_embeddings": True})
    with pytest.raises(ValueError, match="mamba_norm_before_gate"):
        family.system_config({**config, "mamba_norm_before_gate": True})
    with pytest.raises(ValueError, match="mamba_d_ssm"):
        family.system_config({**config, "mamba_d_ssm": 10240})
    with pytest.raises(ValueError, match="float32"):
        family.system_config({**config, "assumed": {
            **config["assumed"], "ssm_state_dtype": "bfloat16"}})


@pytest.mark.parametrize("name, root_of", [
    ("falcon-toy", "toy"), (CONFIG, "repository")])
def test_counts_agree_with_the_arrays_the_system_makes(toy_root, name,
                                                       root_of):
    """``param_count`` and ``cache_bytes`` against the shapes of what
    ``falcon_h1_init`` / ``falcon_h1_init_cache`` make (by ``eval_shape``),
    and ``engine_memory`` reading 2 bytes a parameter."""
    root = toy_root if root_of == "toy" else REPO
    config = load_json(os.path.join(root, "benchmark", "configs",
                                    name + ".json"))
    family = load_module(os.path.join(root, "benchmark", "families",
                                      "falcon_h1.py"))
    common = load_module(os.path.join(root, "benchmark", "kinds",
                                      "serve_common.py"))
    engine = {"max_batch": 4, "cache_len": 64} if root_of == "toy" \
        else load_json(os.path.join(
            REPO, "benchmark", "deployments",
            "falconh1_1chip_b32.json"))["engine"]
    from ray_tpu.serve.llm_engine import _model_bundle

    bind = family.engine_bind(config, engine, 3)
    assert bind["model"] == "falcon_h1"
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
    if root_of == "repository":  # what the cell holds at rest: 12.78 GB
        assert n_params == HELD
        assert held == 2 * HELD + 33 * 9 * SLOT_LAYER == 12_779_740_864
        assert held / 16e9 > 0.79


def test_to_reference_hands_the_leaves_over_as_they_are_stored(toy_root):
    config = load_json(os.path.join(toy_root, "benchmark", "configs",
                                    "falcon-toy.json"))
    family = load_module(os.path.join(toy_root, "benchmark", "families",
                                      "falcon_h1.py"))
    reference = load_module(os.path.join(toy_root, "benchmark", "reference",
                                         "falcon_h1.py"))
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
    assert 4.0 < float(loss) < 8.0 and 0 < float(gnorm) < 1e3


def test_the_seeded_draw_makes_every_branch_of_the_streams_order(toy_root):
    """``assumed.init_gains``: after the first layer each of the three
    branches is within a factor of four of the stream, the scores spread
    over more than a unit, and leaving the skip out moves the mixer's
    output by a part and not by all of it (it holds its state). At 0.02
    throughout, what the gains replace, the branches are lost beside the
    stream."""
    config = load_json(os.path.join(toy_root, "benchmark", "configs",
                                    "falcon-toy.json"))
    family = load_module(os.path.join(toy_root, "benchmark", "families",
                                      "falcon_h1.py"))
    tokens = jnp.asarray(jax.random.randint(
        jax.random.PRNGKey(0), (2, 48), 0, 256))
    params = family.init_params(config, 5)
    got = family.branch_readings(config, params, tokens)
    assert 0.8 < got["stream_rms"] < 1.2
    for branch in ("attention_rms", "ssm_rms", "mlp_rms"):
        assert 0.25 < got[branch] / got["stream_rms"] < 4.0, (branch, got)
    assert got["score_spread"] > 1.0
    assert 0.05 < got["ssm_moved_by_skip"] < 1.2
    flat = jax.tree.map(
        lambda x: x if x.ndim < 2 else (0.02 * jax.random.normal(
            jax.random.PRNGKey(x.size), x.shape)).astype(x.dtype), params)
    lost = family.branch_readings(config, flat, tokens)
    assert lost["mlp_rms"] < 0.02 * lost["stream_rms"]
    assert lost["score_spread"] < 0.05


def test_decode_step_bytes_and_the_two_mixers_work(config, family):
    n = family.param_count(config)
    need = family.decode_step_bytes(config, 2.0 * n, 32.0, 2000.0, {})
    table = 32640 * 5120
    assert need == 2 * (n - table + 32 * 5120) + 32 * (
        2000 * 18_432 + 2 * 9 * 4_225_024)
    # ISSUE 43's estimate: 8.08 GB of weights read, 2.4 GB of state both
    # ways, 1.2 GB of live K/V rows
    assert 11.5e9 < need < 12.0e9
    # the two mixers' CACHE passes: live K/V rows read, state and tail
    # both ways; their weights (1.80 GB) are NOT among the bytes, because
    # the time the reader divides by does not hold their reads
    ops, io = family.parallel_mixer_decode_work(config, 32.0, 2000.0)
    mixers = 9 * (31_457_280 + 68_351_072)
    assert io == 32 * (2000 * 18_432 + 2 * 9 * 4_225_024)
    assert 3.6e9 < io < 3.7e9
    assert ops == 32 * (2.0 * mixers + 9 * (4.0 * 2560 * 2000
                                            + 5.0 * 4096 * 256))
    assert ops / 197e12 < io / 819e9  # memory bounds it
    assert family.parallel_mixer_decode_work(config, 0.0, 0.0) == (0.0, 0.0)


def test_a_chunks_work_counts_required_work_only(config, family):
    n = family.param_count(config)
    ops, io = family.prefill_chunk_work(config, 2.0 * n, 230.0, 0.0, 1500.0)
    row = 2 * 5120
    assert io == 2.0 * n - row * (32640 - 230) + 2 * 9 * 4_225_024 \
        + 1500 * 18_432
    passed = 31_457_280 + 5120 * 9248 + 4096 * 5120 + 330_301_440
    assert family.shape(config)["token_params"] == passed
    assert ops == 2.0 * 230 * 9 * passed + 230 * 9 * 4.0 * 2560 * 1500 \
        + 2.0 * 32640 * 5120
    # compute bounds a full chunk on the chip: 1.8 TFLOP at 197 TFLOP/s
    # against 8.2 GB at 819 GB/s
    assert ops / 197e12 < io / 819e9 < 2 * ops / 197e12
    ops4, io4 = family.prefill_chunk_work(config, 2.0 * n, 230.0, 0.0,
                                          1500.0, 0.25)
    assert ops - ops4 == 0.75 * 2.0 * 32640 * 5120
    assert io - io4 == 0.75 * 32640 * row


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
    """One decode execution's operations: 4 ms attention branch (1 ms of
    it rope), 3 ms state branch, 1 ms sum, 2 ms MLP."""
    path = "jit(step_fn)/jit(main)/{}/fusion"
    parts = [("attn", 2), ("rope", 1), ("cache_write", 1), ("ssm_update", 2),
             ("ssm_proj", 1), ("mixer_sum", 1), ("mlp", 2)]
    out, t = [], at
    for scope, ms in parts:  # (0.9 ms a unit: inside the 10 ms execution)
        out.append((f"fusion.{scope}", t, t + ms * 9e-4, path.format(scope)))
        t += ms * 9e-4
    return out


def test_the_two_mixers_share_of_the_step_and_of_their_roofline(config,
                                                                family):
    share = load_module(os.path.join(
        METRICS, "decode_parallel_mixer_time_pct.py"))
    roofline = load_module(os.path.join(
        METRICS, "parallel_mixer_decode_roofline.py"))
    counters = {"open": {"steps": 100, "occupancy_sum": 3200},
                "close": {"steps": 300, "occupancy_sum": 9600}}
    requests = [{"prompt_len": 1990, "chunk_ns": [10, 20, 30],
                 "chunk_tokens": [1, 1, 1]}]  # contexts 1991, 1992
    ops = step_ops(0.0) + step_ops(0.04) + step_ops(0.08)
    run = hand_run(family, config, counters, requests, ops)
    assert share.read(run) == pytest.approx(70.0)
    said = dict(run.said)["decode_by_scope_parallel"]
    assert said["executions"] == 3
    assert said["attention_pct"] == pytest.approx(40.0)
    assert said["state_pct"] == pytest.approx(30.0)
    assert said["attention_ms"] == pytest.approx(3.6)
    assert said["state_ms"] == pytest.approx(2.7)
    assert said["by_scope_pct"]["mixer_sum"] == pytest.approx(10.0)
    value = roofline.read(run)
    work_ops, io = family.parallel_mixer_decode_work(config, 32.0, 1991.5)
    assert value == pytest.approx(100 * (io / 819e9) / 6.3e-3)
    said = dict(run.said)["parallel_mixer_decode_roofline"]
    assert said["bound_by"] == "memory" and said["executions"] == 3
    assert said["device_ms"] == pytest.approx(6.3)
    assert said["cache_bytes_per_step"] == io
    assert 0 < value < 105
    # the chunk program's share, by the same reduction: 2 executions, in
    # each 3 ms of attention, 4 ms of the state branch (2 of them the
    # scan), 3 ms under no scope of ours
    chunk = load_module(os.path.join(
        METRICS, "prefill_parallel_mixer_time_pct.py"))
    path = "jit(prefill_fn)/jit(main)/{}/fusion"
    chunk_ops = [(f"fusion.{scope}", at + a * 1e-3, at + b * 1e-3,
                  path.format(scope) if scope else "")
                 for at in (0.02, 0.05)
                 for scope, a, b in (("attn", 0, 3), ("ssm_scan", 3, 5),
                                     ("ssm_proj", 5, 7), ("", 7, 10))]
    run = hand_run(family, config, counters, requests, ops + chunk_ops)
    assert chunk.read(run) == pytest.approx(70.0)
    said = dict(run.said)["prefill_by_scope_parallel"]
    assert said["executions"] == 2 and said["program"] == "jit_prefill_fn"
    assert said["attention_ms"] == pytest.approx(3.0)
    assert said["state_ms"] == pytest.approx(4.0)
    assert share.read(run) == pytest.approx(70.0)  # the step's, unmoved
    # a program with no state branch under its scopes (the parent, another
    # family), a family without the function, a run with no trace: nothing
    # to read, nothing raised
    bare = hand_run(family, config, counters, requests,
                    [o for o in ops if "ssm" not in o[0]])
    assert share.read(bare) is None and roofline.read(bare) is None
    assert chunk.read(bare) is None
    other = hand_run(family, config, counters, requests, ops)
    other.family = load_module(os.path.join(REPO, "benchmark", "families",
                                            "granite_hybrid.py"))
    assert share.read(other) is None and roofline.read(other) is None
    other.program_trace["ops"] = sorted(ops + chunk_ops, key=lambda o: o[1])
    assert chunk.read(other) is None
    none = hand_run(family, config, counters, requests, ops)
    none.trace = none.program_trace = None
    none.trace_on = False
    assert share.read(none) is None and roofline.read(none) is None
    assert chunk.read(none) is None


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
    # 5 slots x 3 layers x (rings of 64 rows, a float32 state, a tail)
    assert said["engine_memory"]["cache_bytes"] == 5 * 3 * (
        64 * 2 * 2 * 16 * 2 + 8 * 8 * 24 * 4 + 3 * 160 * 2)
    close = said["engine_counters"]["close"]
    assert close["compiles"] == {"decode": 1, "prefill": 1}
    assert close["prefill_expert_rows"] == 0  # no experts: none counted


@pytest.mark.parametrize("leaf", ["out_proj", "wo"])
def test_the_left_out_branch_control_fails_the_toys_limit(toy_root, capsys,
                                                          monkeypatch, leaf):
    """``tools/serve_check_left_out.py`` zeroes a branch's output matrix in
    the SYSTEM alone (``serve_check_many.py --scale-leaf`` scales it for the
    reference too): with it the logits comparison fails, without it the
    same seed passes."""
    from ray_tpu.serve import llm_engine

    tool = load_module(os.path.join(REPO, "benchmark", "tools",
                                    "serve_check_left_out.py"))
    monkeypatch.setattr(llm_engine, "_model_bundle",
                        llm_engine._model_bundle)  # put back
    monkeypatch.setattr(tool.many, "patch", tool.many.patch)
    family = load_module(os.path.join(toy_root, "benchmark", "families",
                                      "falcon_h1.py"))
    for name in ("system_config", "serve_logits"):
        monkeypatch.setattr(family, name, getattr(family, name))
    args = ["--root", toy_root, "--workload", TOY_CELL["name"], "--seeds",
            "1", "--first-seed", "3000000023", "--rehearsal"]
    assert tool.many.main(args) == 0
    clean = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert clean["failed"] == 0 and clean["largest"] < 0.08
    assert tool.main(["--zero-leaf", leaf] + args) == 0
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert last["failed"] == 1 and last["largest"] > 0.3
    # the system's leaf alone: the reference kept the seeded weights
    params = family.init_params(TOY_CONFIG, 1)
    gone = tool.without(params, leaf)
    assert all(not p[leaf].any() for p in gone["layers"])
    assert all(p[leaf].any() for p in params["layers"])


def test_the_cell_and_its_files(config):
    spec = load_json(os.path.join(REPO, "BENCHMARK.json"))
    cell = [w for w in spec["workloads"] if w["name"] == CELL]
    assert cell == [{**cell[0], "config": CONFIG, "chips": 1,
                     "traffic": "chat_longgen_closed"}]
    entry = [c for c in spec["configs"] if c["name"] == CONFIG]
    assert entry == [{**entry[0], "reduced": REDUCED,
                      "source": config["source"],
                      "file": f"benchmark/configs/{CONFIG}.json"}]
    assert load_json(os.path.join(
        REPO, "benchmark", "cells", CELL + ".json")) \
        == {"deployment": "falconh1_1chip_b32"}
    traffic = load_json(os.path.join(REPO, "benchmark", "traffic",
                                     "chat_longgen_closed.json"))
    assert traffic["kind"] == "serve_closed"
    assert (traffic["clients_per_slot"], traffic["pool_requests"],
            traffic["sizes_seed"]) == (2, 4096, 20261001)
    assert traffic["prompt_len"] == {"dist": "log_uniform", "min": 512,
                                     "max": 4096}
    assert traffic["max_tokens"] == {"dist": "log_normal", "median": 384,
                                     "sigma": 0.5, "min": 128, "max": 1024}
    deployment = load_json(os.path.join(
        REPO, "benchmark", "deployments", "falconh1_1chip_b32.json"))
    assert deployment["engine"] == {
        "max_batch": 32, "cache_len": 5120, "max_prompt_len": 4096,
        "prefill_rows": 4, "max_new_cap": 1024}
    assert deployment["trace_seconds"] == 5.0
    assert "micro-batches" in deployment["what"]
    # the longest request fits the ring without a wrap
    assert traffic["prompt_len"]["max"] + traffic["max_tokens"]["max"] \
        == deployment["engine"]["cache_len"]
    # the pool's means: about 1,720 tokens in (6.7 chunks and more), and
    # several hundred out
    common = load_module(os.path.join(REPO, "benchmark", "kinds",
                                      "serve_common.py"))
    lens, new = common.draw_sizes(traffic, 4096)
    assert 1650 < lens.mean() < 1800 and 380 < new.mean() < 460
    reports = {m["name"] for m in spec["end_to_end"]
               if CELL in m.get("workloads", [CELL])}
    # (a later PR may report more on this cell, list further cells on the
    # metrics below and append metrics of its own: nothing here pins a
    # list to this cell alone or to the end of the file)
    assert {"serve_out_tokens_per_s", "setup_s"} <= reports
    per_layer = {m["name"]: m for m in spec["per_layer"]}
    for name in ("serve_decode_step_ms_p50", "serve_batch_occupancy_pct",
                 "decode_step_roofline", "serve_device_idle_pct.decode",
                 "serve_step_host_ms_p50",
                 "serve_idle_attributed_pct.decode",
                 "serve_prefill_fill_pct.decode",
                 "decode_attention_time_pct", "serve_sync_overshoot_ms_p50",
                 "serve_deliver_lag_ms_mean", "serve_polls_per_chunk",
                 "serve_poll_rpc_ms_p50", "prefill_chunk_roofline",
                 "serve_prefill_device_pct",
                 "decode_parallel_mixer_time_pct",
                 "parallel_mixer_decode_roofline",
                 "prefill_parallel_mixer_time_pct"):
        assert CELL in per_layer[name]["workloads"], name
        assert per_layer[name]["moves"] == "serve_out_tokens_per_s"
    for name in ("decode_parallel_mixer_time_pct",
                 "parallel_mixer_decode_roofline",
                 "prefill_parallel_mixer_time_pct"):
        assert per_layer[name]["source"] == "device_trace"
        assert per_layer[name]["unit"] == "%"
        assert os.path.exists(os.path.join(METRICS, name + ".py"))
