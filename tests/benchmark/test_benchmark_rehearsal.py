"""CPU REHEARSALS of the benchmark's harness, end to end at toy size.

Each kind (``train_packed``, ``serve_closed``, ``serve_open``) runs through
the same command the driver uses, in a temporary copy of the benchmark to
which a third configuration, a configuration of ANOTHER FAMILY (its family
file and its plain reference with it), four traffic mixes, six cells (one
on four devices) and a new metric were added AS FILES AND ENTRIES, with no
edit to a file that was there (``benchmark_toy.make_root``). A rehearsal
finds wrong paths and control flow; it is not evidence for chips, and the
command prints no metric value in it.
"""

import json
import os
import statistics
import subprocess
import sys
import types

import pytest

import benchmark_toy
from benchmark import run as bench_run

REPO = benchmark_toy.REPO
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return benchmark_toy.make_root(str(tmp_path_factory.mktemp("bench")))


def rehearse(root, capsys, cell, trace, seconds=2.5):
    code = bench_run.main([
        "--root", root, "--workload", cell, "--seed", "3000000019",
        "--seconds", str(seconds), "--trace", str(trace), "--rehearsal"])
    out = capsys.readouterr().out.strip().splitlines()
    return code, json.loads(out[-1]), out[:-1]


@pytest.mark.parametrize("cell, trace, names", [
    ("toy_train", 0, {"setup_s", "train_tokens_per_s_chip"}),
    ("toy_train", 1, {"toy_slices", "train_stall_pct",
                      "train_median_slice_tokens_per_s_chip",
                      "train_data_wait_pct"}),
    ("toy_train_4dev", 0, {"setup_s", "train_tokens_per_s_chip"}),
    ("toy_closed", 0, {"setup_s", "serve_out_tokens_per_s", "itl_p99_ms"}),
    ("toy_closed", 1, {"serve_decode_step_ms_p50",
                       "serve_batch_occupancy_pct"}),
    ("toy_open", 0, {"setup_s", "ttft_p90_ms"}),
    ("toy_open", 1, {"gen_late_ms_p99", "serve_queue_ms_p50",
                     "serve_prefill_ms_p50", "serve_handle_ms_p50"}),
    # the family added as files: the toy reference decides `correct`
    ("toy_llama_train", 0, {"setup_s", "train_tokens_per_s_chip"}),
    ("toy_llama_train", 1, {"toy_slices", "train_stall_pct",
                            "train_median_slice_tokens_per_s_chip"}),
    ("toy_llama_closed", 0, {"setup_s", "serve_out_tokens_per_s",
                             "itl_p99_ms"}),
    ("toy_llama_closed", 1, {"serve_decode_step_ms_p50",
                             "serve_batch_occupancy_pct"}),
])
def test_rehearsal_of_each_kind(root, capsys, cell, trace, names):
    code, last, earlier = rehearse(root, capsys, cell, trace)
    assert code == 0
    # The last line's keys are the contract's, plus the rehearsal's label.
    assert set(last) == RESULT_KEYS | {"rehearsal"}
    assert last["correct"] is True, earlier[-3:]
    assert last["attempted"] > 0 and last["failed"] == 0
    # A CPU run prints metric NAMES, never a value under a device metric.
    assert last["metrics"] == {}
    assert last["rehearsal"]["platform"] == "cpu"
    assert names <= set(last["rehearsal"]["metric_names"])
    assert last["device"]["platform"] == "cpu"
    assert not any('"event": "compile_in_window"' in line
                   for line in earlier)
    if "train" in cell:
        # every slice's time is on an earlier line
        assert sum('"event": "slice"' in line for line in earlier) >= 2
    if "closed" in cell:
        # the replay's two lines, and the hand-out's order among the checks
        # (a rehearsal says what admitted_in_order read and is not held to
        # it: serve_closed.check_replay says why)
        said = {json.loads(line[len("[bench] "):])["event"]:
                json.loads(line[len("[bench] "):]) for line in earlier
                if line.startswith("[bench] ")}
        assert {"admission_pattern", "itl_quantiles", "hand_out",
                "admitted_in_order_not_held_in_rehearsal"} <= set(said)
        assert said["itl_quantiles"]["gaps"] == sum(
            said["itl_quantiles"]["prefills_inside"].values()) > 0
        assert said["hand_out"]["taken"] >= last["attempted"]
        assert "sent_in_order" in [c[0] for c in said["checks"]["checks"]]
    if cell == "toy_llama_closed":
        # The engine's cache by the family's own shape: 4 + 1 slots of 64
        # rows, K and V of 2 layers x 2 key/value heads (of 4 query heads)
        # x 16, in bfloat16. A cache as wide as the model would be twice it.
        said = [json.loads(line[len("[bench] "):]) for line in earlier
                if '"event": "engine_memory"' in line]
        assert [m["cache_bytes"] for m in said] \
            == [5 * 64 * 2 * 2 * 2 * 16 * 2]


def test_values_exist_inside_the_process_and_the_added_metric_is_read(root):
    args = types.SimpleNamespace(
        root=root, workload="toy_train", seed=7, seconds=3.0, trace=1,
        rehearsal=True)
    code, run, result = bench_run.run_cell(args)
    assert code == 0 and result["correct"]
    assert result["metrics"]["toy_slices"]["value"] == len(
        run.raw["slice_seconds"])
    assert result["metrics"]["toy_slices"]["unit"] == "slices"
    # 100 * (1 - slices * median / sum): under 0 whenever the median slice
    # is longer than the mean one (-0.0034 on four chips: ledger, PR 25),
    # so no `0 <=`. This toy's slices are a few milliseconds on a CPU that
    # runs five other test files: it has read -2.3 (PR 24's whole run), and
    # no range short of what the formula allows any slice times holds it.
    # What a stall-free run can read is held on slice times that say so:
    # test_a_run_without_a_stall_reads_near_zero_on_both_sides, below.
    slices = run.raw["slice_seconds"]
    assert result["metrics"]["train_stall_pct"]["value"] == pytest.approx(
        100 * (1 - len(slices) * statistics.median(slices) / sum(slices)))
    assert run.compiles_in_window == 0
    # the run's own output file holds every slice
    with open(run.log_path) as f:
        events = [json.loads(line)["event"] for line in f]
    assert events.count("slice") == len(run.raw["slice_seconds"])


@pytest.mark.parametrize("slices, low, high", [
    # Slices that all lie within a share e of their median put the reading
    # between -100 e / (1 - e) and 100 e / (1 + e), whatever their order or
    # number. The chip's own (PERF.md section 6, PR 23: 298 of 299 slices
    # took 1.26455-1.26597 s, e = 0.0006): inside +-0.06.
    ([1.26455, 1.26597, 1.26520, 1.26530, 1.26525, 1.26590, 1.26460],
     -0.06, 0.06),
    # the median longer than the mean: a little UNDER zero, as the ledger's
    # four-chip line reads (-0.0034); the old `0 <=` refused a sound run
    ([1.2652, 1.2652, 1.2652, 1.2652, 1.2640, 1.2640], -0.04, -0.03),
    # e = 0.01: between -1.0101 and 0.9901
    ([1.00, 1.01, 0.99, 1.00, 1.01, 0.99, 1.00], -1.0102, 0.9902),
    # and a stall is outside it: one slice of seven twice as long reads
    # 100 / 8, far over what e = 0.01 allows
    ([1.0, 1.0, 1.0, 2.0, 1.0, 1.0, 1.0], 12.49, 12.51),
])
def test_a_run_without_a_stall_reads_near_zero_on_both_sides(
        root, slices, low, high):
    reader = bench_run.load_module(os.path.join(
        root, "benchmark", "metrics", "train_stall_pct.py"))
    run = types.SimpleNamespace(raw={"slice_seconds": slices,
                                     "window_whole_s": sum(slices)})
    value = reader.read(run)
    assert low < value < high
    median = statistics.median(slices)
    e = max(abs(s - median) for s in slices) / median
    if e < 0.5:
        assert -100 * e / (1 - e) - 1e-9 <= value <= 100 * e / (1 + e) + 1e-9
    else:  # the stall: outside what the other six slices' e = 0 allows
        assert value > 100 * 0.01 / 1.01


def test_a_family_that_no_test_names_rehearses(tmp_path, capsys):
    """The files of one more family dropped into the copy
    (``make_root(third_family=True)``), its cell run through the command:
    the harness finds the family and its reference by the configuration's
    word alone."""
    third = benchmark_toy.make_root(str(tmp_path), third_family=True)
    code, last, earlier = rehearse(third, capsys, "toy_third_closed", 0)
    assert code == 0 and last["correct"] is True, earlier[-3:]
    assert last["attempted"] > 0 and last["failed"] == 0
    assert {"setup_s", "serve_out_tokens_per_s", "itl_p99_ms"} \
        <= set(last["rehearsal"]["metric_names"])


def test_a_compile_inside_the_window_is_counted(root):
    import jax
    import jax.numpy as jnp

    run = bench_run.Run(root, "toy_train", 1, 1.0, False, rehearsal=True)
    assert run.take_devices()
    jax.jit(lambda x: x * 3 + 1)(jnp.ones(3)).block_until_ready()
    assert run.compiles_in_window == 0       # set-up compiles do not count
    run.open_window()
    try:
        jax.jit(lambda x: x * 5 - 2)(jnp.ones(5)).block_until_ready()
    finally:
        run.close_window()
        import gc
        gc.unfreeze()
    assert run.compiles_in_window >= 1
    run.check("no_compile_in_window", run.compiles_in_window == 0)
    assert run.result()["correct"] is False


def test_same_seed_same_inputs_and_large_seeds(root):
    kind = bench_run.Run(root, "toy_train", 1, 1.0, False,
                         rehearsal=True).kind

    def pool(seed):
        run = bench_run.Run(root, "toy_train", seed, 1.0, False,
                            rehearsal=True)
        return kind.make_pool(run.traffic, 8, 65, run.rng("pool"))

    big = 2**31 + 12345
    assert (pool(big) == pool(big)).all()
    assert (pool(big) != pool(big + 1)).any()
    rows = pool(big)
    assert rows.shape == (8, 65) and rows.max() == 249  # the separator
    # serving: every seed gets the same sizes in the same order; the
    # token ids are the seed's
    common = bench_run.Run(root, "toy_open", 1, 1.0, False, rehearsal=True)
    serve = bench_run.load_module(os.path.join(
        root, "benchmark", "kinds", "serve_common.py"))
    a = serve.make_requests(common, 64)
    common.seed = 99
    b = serve.make_requests(common, 64)
    sizes = lambda reqs: [(r["prompt_len"], r["asked"]) for r in reqs]
    assert sizes(a) == sizes(b)
    assert [r["prompt"] for r in a] != [r["prompt"] for r in b]


def test_without_a_tpu_the_command_prints_nothing_and_fails():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload",
         "train_gpt2s_1chip", "--seed", "1", "--seconds", "1", "--trace",
         "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 2
    assert proc.stdout.strip() == ""
    assert "nothing was run" in proc.stderr
