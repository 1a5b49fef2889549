"""The ``keye_vl2`` family's benchmark files: its configuration file against
the catalog row it was copied from, its counts against the arrays the
system makes, the bytes of a decode step on hand-made counters, a step's
sparse attention, its indexer and a prefill chunk's work by hand, the five
new readers on hand-made runs, the sets' tool sound and under its fault,
and a CPU rehearsal of the cell's kind with a toy configuration of this
family added to the tests' toy root AS FILES AND ENTRIES (no tiny override
lives in the benchmark itself)."""

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
CONFIG = "keye-vl-2.0-30b-a3b"
CELL = "serve_keyevl2_sparsectx_sat"
REDUCED = ["num_hidden_layers", "num_experts", "num_local_experts",
           "vocab_size"]
# The catalog row's ``config`` (guides/model-configs/architectures.jsonl,
# Keye-VL-2.0-30B-A3B), copied here so that the test needs no file outside
# the repository.
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 262144, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "KeyeVL2",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4,
    "num_local_experts": 128, "rms_norm_eps": 1e-06,
    "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default",
                     "type": "default"},
    "rope_theta": 10000000,
    "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                  "q_chunk_size": 512, "topk": 2048},
    "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}
ATTENTION = 18_874_368 + 256
INDEXER = 2_261_120
EXPERT = 4_718_592
LAYER = 96_899_456
HELD = 852_988_928
TOKEN = 2_176             # ring bytes a token a layer: K, V and the index key

TOY_CONFIG = {
    "family": "keye_vl2",
    "source": "none: a toy of the keye_vl2 family for CPU rehearsals of the "
              "harness, never a benchmark configuration",
    "model_type": "KeyeVL2", "vocab_size": 256, "hidden_size": 48,
    "num_hidden_layers": 3, "rms_norm_eps": 1e-06, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "rope_theta": 10000,
    "rope_scaling": {"mrope_section": [4, 2, 2], "rope_type": "default"},
    "sa_config": {"indexer_head_dim": 8, "indexer_num_heads": 3,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 8,
                  "q_chunk_size": 8, "topk": 16},
    "num_experts": 8, "num_local_experts": 8, "num_experts_per_tok": 3,
    "moe_intermediate_size": 24, "norm_topk_prob": True,
    "tie_word_embeddings": False, "max_position_embeddings": 64,
    "reduced": [],
    "assumed": {"indexer_rotary": "all", "indexer_key_norm": "layernorm",
                "why": "KeyeVL2Config.tiny()'s sizes: a topk of 16, so that "
                       "the toy's prompts cross into selecting inside "
                       "their prefill and inside their decode steps"},
    "reference_check": {"prompt_lens": [13, 27], "follow": 5},
    # (a set of 16 keys of 27: one pick that bfloat16 turns moves a sixteenth
    # of a query's attention, 0.27 of a row at this seed; at the published
    # 2,048 keys a turned pick is a two-thousandth)
    "tolerance": {"serve_logits_rel_l2": 0.5, "serve_token_regret_rms": 1.0,
                  "reason": "bfloat16 compute against a float32 reference "
                            "at toy width, a turned pick among 16 keys"},
}
TOY_ENGINE = {"engine": {"max_batch": 4, "cache_len": 64,
                         "max_prompt_len": 32, "prefill_rows": 2,
                         "prefill_chunk": 8},
              "max_concurrent": 64, "trace_seconds": 1.0,
              "device_programs": {"decode": "jit_step_fn",
                                  "prefill": "jit_prefill_fn"}}
TOY_CELL = {"name": "toy_keyevl2_closed", "config": "keye-vl2-toy",
            "traffic": "toy_closed", "chips": 1,
            "why": "CPU rehearsal of kind serve_closed on this family"}


@pytest.fixture(scope="module")
def config():
    return load_json(os.path.join(REPO, "benchmark", "configs",
                                  CONFIG + ".json"))


@pytest.fixture(scope="module")
def family():
    return load_module(os.path.join(REPO, "benchmark", "families",
                                    "keye_vl2.py"))


@pytest.fixture(scope="module")
def toy_root(tmp_path_factory):
    """The tests' toy root with this family's toy added as files and
    entries, the way a PR adds a configuration."""
    root = benchmark_toy.make_root(str(tmp_path_factory.mktemp("keye_vl2")))
    bench = os.path.join(root, "benchmark")
    for folder, name, held in (
            ("configs", "keye-vl2-toy", TOY_CONFIG),
            ("deployments", "toy_sparse_engine", TOY_ENGINE),
            ("cells", TOY_CELL["name"], {"deployment": "toy_sparse_engine"})):
        with open(os.path.join(bench, folder, name + ".json"), "w") as f:
            json.dump(held, f)
    spec = load_json(os.path.join(root, "BENCHMARK.json"))
    spec["configs"].append({
        "name": "keye-vl2-toy", "source": TOY_CONFIG["source"],
        "file": "benchmark/configs/keye-vl2-toy.json", "reduced": [],
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
    assert config["source"] == "https://huggingface.co/Kwai-Keye/" \
        "Keye-VL-2.0-30B-A3B/blob/main/config.json"
    for key, value in PUBLISHED.items():
        if key not in REDUCED:
            assert config[key] == value, key
        else:
            assert config[key + "_published"] == value, key
    # one chip of the eight that share a layer, one pipeline stage of six:
    # the guide's floors (four layers, 8 experts, an eighth of the tables)
    assert config["num_hidden_layers"] == 8
    assert config["num_experts"] == config["num_local_experts"] == 16
    assert config["vocab_size"] * 8 == config["vocab_size_published"]
    a = config["assumed"]
    assert (a["indexer_reads"], a["indexer_key_norm"], a["indexer_rotary"],
            a["sa_tiling"], a["vision_tower"], a["router_experts"]) == (
        "normed_input", "layernorm", "all", "tiles_only", "not served", 128)
    for why in ("indexer_reads_why", "indexer_key_norm_why",
                "indexer_rotary_why", "sa_tiling_why", "vision_tower_why",
                "router_experts_why", "scale_on_w_why", "init_gains_why"):
        assert len(a[why]) > 40, why
    assert "1,048,576" in a["sa_tiling_why"]
    assert set(a["init_gains"]) == {
        "embed", "q", "k", "v", "o", "idx_q", "idx_k", "idx_w", "router",
        "expert_in", "expert_down", "head"}
    deployment = config["deployment"].lower()
    for said in ("expert parallelism", "pipeline stages", "852,988,928",
                 "2,176", "8x their share"):
        assert said in deployment, said
    assert "param_dtype" not in json.dumps(config)
    assert "bfloat16 weights" in config["computes_in"]
    assert config["reference_check"]["follow"] == 8
    short, long = config["reference_check"]["prompt_lens"]
    assert short < 2048 < short + 8 and long > 3 * 2048 and long % 512
    assert set(config["tolerance"]) == {
        "serve_logits_rel_l2", "serve_token_regret_rms", "reason"}
    assert "fp8_weights" in config["tolerance"]["reason"]


def test_counts_by_hand(config, family):
    """ISSUE 60's arithmetic, reckoned again by the family file."""
    sh = family.shape(config)
    assert sh["attention_params"] == 2 * 2048 * 4096 + 2 * 2048 * 512 + 256 \
        == ATTENTION
    assert sh["indexer_params"] == 2_097_152 + 131_072 + 32_768 + 128 \
        == INDEXER
    assert sh["router_params"] == 262_144 and sh["router_experts"] == 128
    assert sh["expert_params"] == 3 * 2048 * 768 == EXPERT
    assert ATTENTION + INDEXER + 262_144 + 16 * EXPERT + 4_096 == LAYER
    assert family.param_count(config) \
        == 8 * LAYER + 2 * 18_992 * 2_048 + 2_048 == HELD
    whole = {**config, "num_hidden_layers": 48, "vocab_size": 151936,
             "num_experts": 128, "num_local_experts": 128}
    assert family.param_count(whole) == 48 * 625_381_760 \
        + 2 * 151_936 * 2_048 + 2_048 == 30_640_656_384
    assert sh["kv_bytes_per_layer_token"] == 2_048
    assert sh["index_bytes_per_layer_token"] == 128
    assert family.cache_bytes(config, 1, 33792) == 8 * 33_792 * TOKEN
    assert family.cache_bytes(config, 17, 33792) == 10_000_269_312
    assert (sh["vocab"], sh["n_positions"], sh["topk"]) \
        == (18992, 262144, 2048)


@pytest.mark.parametrize("name, root_of", [
    ("keye-vl2-toy", "toy"), (CONFIG, "repository")])
def test_counts_agree_with_the_arrays_the_system_makes(toy_root, name,
                                                       root_of):
    """``param_count`` and ``cache_bytes`` against the shapes of what
    ``init_params`` and the family's cache would hold, toy and published."""
    root = toy_root if root_of == "toy" else REPO
    fam = load_module(os.path.join(root, "benchmark", "families",
                                   "keye_vl2.py"))
    config = load_json(os.path.join(root, "benchmark", "configs",
                                    name + ".json"))
    from ray_tpu.models.keye_vl2 import keye_vl2_init, keye_vl2_init_cache

    cfg = fam.system_config(config)
    params = jax.eval_shape(
        lambda: keye_vl2_init(jax.random.PRNGKey(0), cfg))
    assert sum(x.size for x in jax.tree.leaves(params)) \
        == fam.param_count(config)
    cache = jax.eval_shape(lambda: keye_vl2_init_cache(cfg, 5, 64))
    assert sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(cache)
               if x.ndim == 4) == fam.cache_bytes(config, 5, 64)


def test_to_reference_hands_the_leaves_over_as_they_are_stored(toy_root):
    fam = load_module(os.path.join(toy_root, "benchmark", "families",
                                   "keye_vl2.py"))
    config = load_json(os.path.join(toy_root, "benchmark", "configs",
                                    "keye-vl2-toy.json"))
    params = fam.init_params(config, 3)
    ref = fam.to_reference(params, config)
    layer, mine = ref["layers"][0], params["layers"][0]
    assert layer["q_proj"] is mine["wq"]
    assert layer["indexer_k_bias"] is mine["idx_k_bias"]
    assert layer["experts_gate"].shape == (8, 48, 24) \
        == layer["experts_up"].shape
    assert {x.dtype for x in jax.tree.leaves(ref)} \
        == {jnp.dtype(jnp.bfloat16)}
    kw = fam.reference_kwargs(config)
    assert (kw["topk"], kw["top_k"], kw["indexer_heads"], kw["indexer_dim"],
            kw["mrope_section"], kw["first_expert"]) \
        == (16, 3, 3, 8, (4, 2, 2), 0)
    assert (kw["indexer_rotary"], kw["indexer_key_norm"]) \
        == ("all", "layernorm")


def test_the_seeded_draw_makes_every_branch_some_tenths_of_the_stream(
        toy_root):
    """``branch_readings`` at toy width: both branches of the first layer
    are neither nothing nor everything beside the stream, and the index
    scores' gap at the ``topk``-th is a small share of their spread."""
    fam = load_module(os.path.join(toy_root, "benchmark", "families",
                                   "keye_vl2.py"))
    config = load_json(os.path.join(toy_root, "benchmark", "configs",
                                    "keye-vl2-toy.json"))
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 48), 0, 256)
    got = fam.branch_readings(config, fam.init_params(config, 1), tokens)
    assert 0.5 < got["stream_rms"] < 2.0
    for branch in ("attention_rms", "routed_rms"):
        # (at toy width a set holds 16 keys, not 2,048: attention is large)
        assert 0.05 < got[branch] / got["stream_rms"] < 6.0, (branch, got)
    assert got["largest_weight_mean"] > got["smallest_weight_mean"] > 0
    assert got["index_score_spread"] > 0
    assert 0 <= got["index_gap_at_topk"] < 1.0


def test_decode_step_bytes_on_hand_made_counters(config, family):
    """A step at 16 slots and 14,000 rows of context that hit 100 of the
    128 held experts: the dense weights without the embedding's table, 100
    experts, 16 rows of the table, and a slot a layer 2,048 rows of K and V
    and 14,000 index keys."""
    weight_bytes = 2.0 * HELD
    counters = {"open": {"steps": 10, "experts_hit": 1_000},
                "close": {"steps": 20, "experts_hit": 2_000}}
    got = family.decode_step_bytes(config, weight_bytes, 16.0, 14_000.0,
                                   counters)
    dense = HELD - 128 * EXPERT - 18_992 * 2_048
    want = 2.0 * (dense + 100 * EXPERT + 16 * 2_048) \
        + 16 * 8 * (2_048 * 2_048 + 14_000 * 128)
    assert got == pytest.approx(want)
    # under topk every live row is read; no counters: every held expert
    short = family.decode_step_bytes(config, weight_bytes, 16.0, 1_000.0, {})
    assert short == pytest.approx(
        2.0 * (dense + 128 * EXPERT + 16 * 2_048)
        + 16 * 8 * 1_000 * TOKEN)


def test_a_steps_sparse_work_its_indexers_and_a_chunks_by_hand(config,
                                                               family):
    ops, io = family.sparse_attention_work(config, 16.0, 14_000.0)
    assert ops == 16 * 8 * 4.0 * 4096 * 2048
    assert io == 16 * 8 * (2049 * 2048 + 2 * 4096 * 2)
    # under topk: the live rows
    ops, io = family.sparse_attention_work(config, 16.0, 1_000.0)
    assert ops == 16 * 8 * 4.0 * 4096 * 1000
    ops, io = family.indexer_work(config, 16.0, 14_000.0)
    assert ops == 8 * 16 * (2.0 * INDEXER + 14_000 * 16 * (128 + 2.0))
    assert io == 8 * (INDEXER * 2 + 16 * 14_001 * 128)
    # a last chunk of 512 real tokens over 10,000 keys in sight
    weight_bytes = 2.0 * HELD
    ops, io = family.prefill_chunk_work(config, weight_bytes, 512.0,
                                        512.0 * 8 * 8 / 8, 10_000.0, 1.0)
    row = 2.0 * 2_048
    assert io == pytest.approx(weight_bytes - row * (18_992 - 512)
                               + 8 * 10_000 * TOKEN)
    assert ops == pytest.approx(
        2.0 * 512 * 8 * (ATTENTION + INDEXER + 262_144)
        + 2.0 * 4096 * EXPERT
        + 512 * 8 * (4.0 * 4096 * 2048 + 10_000 * 16 * 130.0)
        + 2.0 * 18_992 * 2_048)
    # not a last chunk: the head's table is not read
    _, less = family.prefill_chunk_work(config, weight_bytes, 512.0, 4096.0,
                                        10_000.0, 0.0)
    assert io - less == pytest.approx(row * 18_992)


def hand_run(family, config, counters, ops=(), requests=()):
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


def scoped_ops(program, at, parts):
    """One execution's operations from ``at`` on: (scope path, ms) each."""
    out, t = [], at
    for scope, ms in parts:
        path = f"jit({program})/jit(main)/{scope}/fusion" if scope else ""
        out.append((f"fusion.{len(out)}", t, t + ms * 1e-3, path))
        t += ms * 1e-3
    return out


def test_the_five_readers_on_hand_made_runs(config, family):
    readers = {name: load_module(os.path.join(METRICS, name + ".py"))
               for name in ("decode_indexer_time_pct",
                            "prefill_indexer_time_pct",
                            "sparse_decode_attention_roofline",
                            "indexer_decode_roofline",
                            "sparse_keys_read_pct")}
    counters = {
        "open": {"steps": 100, "occupancy_sum": 1_600,
                 "sparse_keys_selected": 1_000, "sparse_keys_eligible": 9_000},
        "close": {"steps": 300, "occupancy_sum": 4_800,
                  "sparse_keys_selected": 4_001_000,
                  "sparse_keys_eligible": 28_009_000}}
    # a request whose tokens 2.. were decoded inside the window at contexts
    # 14,001 and 14,002
    requests = [{"prompt_len": 14_000, "first_ns": 10, "chunk_ns": [10, 50],
                 "chunk_tokens": [1, 2]}]
    assert readers["sparse_keys_read_pct"].read(
        hand_run(family, config, counters)) == pytest.approx(100 / 7)
    # a step: 0.5 ms of the indexer's projections and scores, 0.7 ms of the
    # selection, 6 ms of the gather and the softmax, 2 ms of experts, 0.3 ms
    # under no scope of ours
    step = [("attn/indexer", 0.5), ("attn/select", 0.7),
            ("attn/attn_sparse", 6.0), ("experts", 2.0), ("", 0.3)]
    ops = [op for at in (0.0, 0.04, 0.08)
           for op in scoped_ops("step_fn", at, step)]
    run = hand_run(family, config, counters, ops, requests)
    assert readers["decode_indexer_time_pct"].read(run) \
        == pytest.approx(100 * 1.2 / 9.5)
    said = dict(run.said)["decode_by_sparse_scope"]
    assert said["executions"] == 3 and said["program"] == "jit_step_fn"
    assert said["indexer_ms"] == pytest.approx(0.5)
    assert said["select_ms"] == pytest.approx(0.7)
    assert said["attn_sparse_ms"] == pytest.approx(6.0)
    assert said["attn_sparse_pct"] == pytest.approx(100 * 6.0 / 9.5)
    value = readers["sparse_decode_attention_roofline"].read(run)
    work_ops, io = family.sparse_attention_work(config, 16.0, 14_001.5)
    assert value == pytest.approx(100 * (io / 819e9) / 6.0e-3)
    said = dict(run.said)["sparse_attention_work"]
    assert said["bound_by"] == "memory" and said["executions"] == 3
    assert said["achieved_gb_per_s"] == pytest.approx(io / 6.0e-3 / 1e9)
    assert said["mean_context"] == pytest.approx(14_001.5)
    assert 0 < value < 100
    value = readers["indexer_decode_roofline"].read(run)
    work_ops, io = family.indexer_work(config, 16.0, 14_001.5)
    assert value == pytest.approx(100 * max(work_ops / 197e12, io / 819e9)
                                  / 0.5e-3)
    assert 0 < value < 100
    # the chunk program, whose scopes stand inside a conditional's branch
    lane = [("attn/cond/branch_3_fun/indexer", 2.0),
            ("attn/cond/branch_3_fun/select", 3.0),
            ("attn/cond/branch_3_fun/attn_sparse", 10.0), ("experts", 4.0)]
    chunk_ops = [op for at in (0.02, 0.05)
                 for op in scoped_ops("prefill_fn", at, lane)]
    run = hand_run(family, config, counters, ops + chunk_ops, requests)
    assert readers["prefill_indexer_time_pct"].read(run) \
        == pytest.approx(100 * 5.0 / 19.0)
    assert dict(run.said)["prefill_by_sparse_scope"]["executions"] == 2
    assert readers["decode_indexer_time_pct"].read(run) \
        == pytest.approx(100 * 1.2 / 9.5)  # unmoved
    # a program with nothing under ``attn_sparse`` (the parent, another
    # family), a family without the functions, a run with no trace or no
    # counters: nothing to read, nothing raised
    traced = ("decode_indexer_time_pct", "prefill_indexer_time_pct",
              "sparse_decode_attention_roofline", "indexer_decode_roofline")
    bare = hand_run(family, config, counters,
                    [o for o in ops + chunk_ops if "attn_sparse" not in o[3]],
                    requests)
    for name in traced:
        assert readers[name].read(bare) is None, name
    other = hand_run(family, config, counters, ops + chunk_ops, requests)
    other.family = load_module(os.path.join(REPO, "benchmark", "families",
                                            "granite_hybrid.py"))
    for name in traced[2:]:
        assert readers[name].read(other) is None, name
    none = hand_run(family, config, {}, ops + chunk_ops, requests)
    for name in ("sparse_keys_read_pct",) + traced[2:]:
        assert readers[name].read(none) is None, name
    none.trace = none.program_trace = None
    none.trace_on = False
    for name in traced:
        assert readers[name].read(none) is None, name


@pytest.mark.parametrize("trace_on, names", [
    (0, {"setup_s", "serve_out_tokens_per_s"}),
    (1, {"serve_decode_step_ms_p50", "serve_batch_occupancy_pct",
         "serve_prefill_fill_pct.decode", "sparse_keys_read_pct"}),
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
    # 5 slots x 3 layers x 64 rows x (K and V rows of 32 and a key of 8)
    assert said["engine_memory"]["cache_bytes"] == 5 * 3 * 64 * (
        2 * 32 + 8) * 2
    close = said["engine_counters"]["close"]
    assert close["compiles"] == {"decode": 1, "prefill": 1}
    assert close["prefill_chunk"] == 8
    assert close["prefill_expert_rows"] > 0 and close["experts_hit"] > 0
    assert 0 < close["sparse_keys_selected"] <= close["sparse_keys_eligible"]
    assert "prefill_sparse_keys_selected" not in close
    assert (close["expert_layers"], close["experts_held"],
            close["sparse_layers"], close["sparse_topk"]) == (3, 8, 3, 16)


@pytest.mark.parametrize("fault", [False, True])
def test_the_sets_tool_passes_the_sound_program_and_fails_the_fault(
        toy_root, capsys, fault):
    """``tools/serve_check_sparse.py`` at toy size: the sets the chunk
    program's queries and the decode steps picked are the reference's, and
    so are the sets the programs' arithmetic picks from the reference's own
    stream; with the indexer's rings shifted by one row after the prefill
    the steps' sets are not, and the logits of that run fail the cell's
    limit too (the tool compares the run it made, not ``check_serve``'s)."""
    tool = load_module(os.path.join(REPO, "benchmark", "tools",
                                    "serve_check_sparse.py"))
    args = ["--root", toy_root, "--workload", TOY_CELL["name"], "--seeds",
            "2", "--first-seed", "3000000023", "--rehearsal"]
    assert tool.main(args + ["--fault"] * fault) == 0
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert last["fault"] is fault and len(last["seeds"]) == 2
    for seed in last["seeds"]:
        # one chunk of 32 at toy size: queries 0, 10, 20, 31 of prompts of
        # 13 and 27 tokens; five steps a prompt
        assert seed["prefill"]["queries"] == 2 + 3
        assert seed["steps"]["queries"] == 2 * 5
        assert seed["forced"]["queries"] == 15
        assert all(seed[name]["wrong_size"] == 0
                   and len(seed[name]["overlap_by_layer"]) == 3
                   for name in ("prefill", "steps", "forced"))
        # (the shorter prompt's steps cross from "all rows" to selecting)
        assert seed["set_sizes"] == [14, 15, 16]
        assert seed["logits_ok"] is not fault
    # the fault is made after the prefill, and not to the reference's stream
    assert last["prefill_overlap_min"] > 0.8
    assert last["forced_overlap_min"] > 0.8
    assert last["forced_largest_margin"] < tool.FORCED_EPS
    if fault:
        assert last["failed"] == last["logits_failed"] == 2
        assert last["steps_overlap_min"] < 0.9
        assert last["steps_largest_margin"] > 0.2
        assert last["steps_overlap_a_layer_min"] < tool.OVERLAP_MIN
        assert min(last["logits_rel_l2"]) > TOY_CONFIG["tolerance"][
            "serve_logits_rel_l2"]
    else:
        # (at toy width a turned pick is one key of 16: the tool's limits
        # are the published widths', so only the readings are held here)
        assert last["logits_failed"] == 0
        assert last["steps_overlap_min"] > 0.8
        assert last["steps_largest_margin"] < 0.2


def test_the_cell_and_its_files(config):
    spec = load_json(os.path.join(REPO, "BENCHMARK.json"))
    cell = [w for w in spec["workloads"] if w["name"] == CELL]
    assert cell == [{**cell[0], "config": CONFIG, "chips": 1,
                     "traffic": "sparse_context_closed"}]
    assert "16 slots" in cell[0]["why"] and "top-2048" in cell[0]["why"]
    entry = [c for c in spec["configs"] if c["name"] == CONFIG]
    assert entry == [{**entry[0], "reduced": REDUCED,
                      "source": config["source"],
                      "file": f"benchmark/configs/{CONFIG}.json"}]
    assert load_json(os.path.join(
        REPO, "benchmark", "cells", CELL + ".json")) \
        == {"deployment": "keyevl2_1chip_b16"}
    traffic = load_json(os.path.join(REPO, "benchmark", "traffic",
                                     "sparse_context_closed.json"))
    assert traffic["kind"] == "serve_closed"
    assert (traffic["clients_per_slot"], traffic["pool_requests"],
            traffic["sizes_seed"]) == (2, 4096, 20261004)
    assert traffic["prompt_len"] == {"dist": "log_uniform", "min": 4096,
                                     "max": 32768}
    assert traffic["max_tokens"] == {"dist": "log_normal", "median": 384,
                                     "sigma": 0.5, "min": 128, "max": 1024}
    deployment = load_json(os.path.join(
        REPO, "benchmark", "deployments", "keyevl2_1chip_b16.json"))
    assert deployment["engine"] == {
        "max_batch": 16, "cache_len": 33792, "max_prompt_len": 32768,
        "prefill_rows": 4, "max_new_cap": 1024}
    assert deployment["trace_seconds"] == 5.0
    assert "10.00 GB" in deployment["what"]
    # the longest request fits a ring without a wrap, and every prompt is
    # past topk: every step selects
    assert traffic["prompt_len"]["max"] + traffic["max_tokens"]["max"] \
        == deployment["engine"]["cache_len"] == 66 * 512
    assert traffic["prompt_len"]["min"] == 2 * config["sa_config"]["topk"]
    common = load_module(os.path.join(REPO, "benchmark", "kinds",
                                      "serve_common.py"))
    lens, new = common.draw_sizes(traffic, 4096)
    assert 13_000 < lens.mean() < 14_600 and 380 < new.mean() < 470
    assert lens.min() >= 4096 and lens.max() <= 32768
    reports = {m["name"] for m in spec["end_to_end"]
               if CELL in m.get("workloads", [CELL])}
    assert {"serve_out_tokens_per_s", "setup_s"} <= reports
    per_layer = {m["name"]: m for m in spec["per_layer"]}
    new_ones = {"decode_indexer_time_pct": "device_trace",
                "prefill_indexer_time_pct": "device_trace",
                "sparse_decode_attention_roofline": "device_trace",
                "indexer_decode_roofline": "device_trace",
                "sparse_keys_read_pct": "program_counter"}
    for name in ("serve_decode_step_ms_p50", "serve_batch_occupancy_pct",
                 "decode_step_roofline", "serve_device_idle_pct.decode",
                 "serve_step_host_ms_p50", "serve_prefill_fill_pct.decode",
                 "decode_attention_time_pct", "serve_sync_overshoot_ms_p50",
                 "serve_deliver_lag_ms_mean", "serve_polls_per_chunk",
                 "serve_poll_rpc_ms_p50", "prefill_chunk_roofline",
                 "serve_prefill_device_pct", "serve_steps_ahead_pct",
                 "serve_stall_pct", "serve_turn_ms_max",
                 "serve_loop_host_pct", *new_ones):
        assert CELL in per_layer[name]["workloads"], name
        assert per_layer[name]["moves"] == "serve_out_tokens_per_s"
    # (it reads nothing where the device is never idle: ISSUE 60)
    assert CELL not in per_layer["serve_idle_attributed_pct.decode"][
        "workloads"]
    # (test_benchmark_nemotron_h.py holds these lists to its cell alone)
    for name in ("moe_experts_hit_pct", "moe_rows_per_expert"):
        assert per_layer[name]["workloads"] == [
            "serve_nemotron3s_decode_sat"]
    for name, source in new_ones.items():
        assert per_layer[name]["source"] == source
        assert per_layer[name]["unit"] == "%"
        assert per_layer[name]["workloads"][0] == CELL
        assert os.path.exists(os.path.join(METRICS, name + ".py"))
    # twelve cells or more, one of them on four chips
    assert len(spec["workloads"]) >= 12
    assert sum(w["chips"] == 4 for w in spec["workloads"]) == 1
