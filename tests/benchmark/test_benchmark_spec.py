"""``BENCHMARK.json`` against the contract it was written to, and the
rule that the harness is driven by data: every name in it leads to a file,
and no harness file branches on a cell's, configuration's, metric's or
family's name.

Every check is a function of a checkout's root and runs on three: the
repository; a copy to which ``benchmark_toy.make_root`` added two
configurations (one of a family the benchmark does not have), six cells and
a metric as files and entries; and that copy with the files of one family
more, which no test names, dropped in beside them. What the benchmark has
accepted is pinned (``ACCEPTED_*``: present, unchanged, in its order); what
a later PR adds after it is held to the contract and is otherwise free, so
that adding needs no edit here."""

import ast
import json
import os
import re

import pytest

import benchmark_toy
from benchmark.loading import load_module

REPO = benchmark_toy.REPO
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
# What ``reduced`` may never name: a width. ``hidden`` counts only where
# it is not the published DEPTH key's (``num_hidden_layers``: until PR 33
# the word anywhere in a key refused it, so a family had to list its depth
# cut under another key).
WIDTHS = re.compile(r"(hidden(?!_layers)|intermediate|latent|state|proj|"
                    r"_dim$|_rank$|head_dim|n_embd|n_inner|expansion|"
                    r"experts_per)")


# The cells and end-to-end metrics the benchmark has accepted. A later PR
# appends to BENCHMARK.json; it may not take one of these away, change it
# or reorder them (the ledger speaks of them by name).
ACCEPTED_CELLS = [
    ("train_gpt2s_1chip", "gpt2-124m", "packed_docs_zipf", 1),
    ("serve_gpt2xl_decode_sat", "gpt2-xl-1.5b", "decode_sat_closed", 1),
    ("serve_gpt2xl_prompt_rate", "gpt2-xl-1.5b", "prompt_heavy_open", 1),
    ("train_gpt2xl_4chip", "gpt2-xl-1.5b", "packed_docs_zipf", 4),
]
ACCEPTED_END_TO_END = [  # ... and the accepted cells each reports in
    ("train_tokens_per_s_chip", "tokens/s/chip", "higher", 0.01,
     ["train_gpt2s_1chip", "train_gpt2xl_4chip"]),
    # 0.02 until PR 26, 0.05 until PR 33: the closed loop hands its pool
    # out in one order now, so which ended requests share a prefill turn
    # repeats; two sets of 6 a closed cell spread 1.29 % and 0.76 % (GPT-2
    # XL) and 0.85 % and 1.44 % (Nemotron, which decides): 2.5 x 1.44 %
    # rounded up to half a percent (PERF.md section 2; my chip runs, PR 33)
    ("serve_out_tokens_per_s", "tokens/s", "higher", 0.04,
     ["serve_gpt2xl_decode_sat"]),
    # 0.01 until PR 33, set from ten pairs on one machine; the driver's
    # notes on PR 29 and PR 31 and PR 32's `unresolved` said it could not
    # hold. The same two sets spread 1.42 % and 0.71 %: 2.5 x 1.42 %
    ("itl_p99_ms", "ms", "lower", 0.04, ["serve_gpt2xl_decode_sat"]),
    ("ttft_p90_ms", "ms", "lower", 0.08, ["serve_gpt2xl_prompt_rate"]),
    ("setup_s", "s", "lower", 0.1, []),  # every cell: no list
]


@pytest.fixture(scope="module",
                params=["repository", "toy_root", "third_family_root"])
def root(request, tmp_path_factory):
    if request.param == "repository":
        return REPO
    return benchmark_toy.make_root(
        str(tmp_path_factory.mktemp("spec")),
        third_family=request.param == "third_family_root")


@pytest.fixture(scope="module")
def spec(root):
    path = os.path.join(root, "BENCHMARK.json")
    assert os.path.getsize(path) <= 64 * 1024
    with open(path) as f:
        return json.load(f)


def bench(root, *parts):
    return os.path.join(root, "benchmark", *parts)


def kind_of(root, workload):
    with open(bench(root, "traffic", workload["traffic"] + ".json")) as f:
        return json.load(f)["kind"]


def line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


@pytest.mark.parametrize("key, is_width", [
    # depth and counts: a cut of these is a cut of scale and may be listed
    ("num_hidden_layers", False), ("n_layer", False), ("num_layers", False),
    ("hybrid_override_pattern", False), ("n_routed_experts", False),
    ("vocab_size", False), ("max_position_embeddings", False),
    # widths: refused, as before
    ("hidden_size", True), ("mamba_hidden_act_dim", True),
    ("intermediate_size", True), ("moe_intermediate_size", True),
    ("moe_latent_size", True), ("ssm_state_size", True),
    ("kv_lora_rank", True), ("head_dim", True), ("n_embd", True),
    ("n_inner", True), ("num_experts_per_tok", True),
    ("mamba_expansion", True), ("q_proj_width", True),
])
def test_reduced_may_name_a_depth_and_never_a_width(key, is_width):
    assert bool(WIDTHS.search(key)) is is_width


def test_top_level_keys_and_command(spec):
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert spec["command"] == ["python3", "-m", "benchmark.run"]
    assert spec["paths"] == ["benchmark", "tests/benchmark"]
    assert isinstance(spec["run_seconds"], int)
    cells = len(spec["workloads"])
    # the full check with 24 cells must fit 43200 s
    assert (2 + 14 * 24) * (spec["run_seconds"] + 60) + 24 * 180 + 1200 \
        <= 43200
    assert 1 <= cells <= 24


def test_configs(root, spec):
    names = [c["name"] for c in spec["configs"]]
    assert len(set(names)) == len(names)
    used = {w["config"] for w in spec["workloads"]}
    files = [c["file"] for c in spec["configs"]]
    assert len(set(files)) == len(files)
    for c in spec["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and line(c["source"]) and line(c["why"])
        assert c["name"] in used
        assert c["file"].startswith("benchmark/configs/")
        with open(os.path.join(root, c["file"])) as f:
            held = json.load(f)
        assert held["source"] == c["source"]
        assert held["reduced"] == c["reduced"]
        assert not any(WIDTHS.search(k) for k in c["reduced"])
        # the tolerances of the kinds its cells use (each kind file names
        # the keys its comparison reads), with their reason
        wanted = {"reason"}
        for w in spec["workloads"]:
            if w["config"] == c["name"]:
                wanted |= set(load_module(bench(
                    root, "kinds", kind_of(root, w) + ".py")).TOLERANCES)
        assert len(wanted) > 1 and wanted <= set(held["tolerance"]), c["name"]
        # the file sets no storage type, and no path flag beyond those its
        # family names (notes beside them have keys that end in "why")
        assert "param_dtype" not in json.dumps(held)
        family = load_module(bench(root, "families",
                                   held["family"] + ".py"))
        assert os.path.exists(bench(root, "reference",
                                    held["family"] + ".py"))
        assumed = {k for k in held["assumed"] if not k.endswith("why")}
        assert assumed <= family.ASSUMED, c["name"]
        if held["family"] == "gpt2":  # as PR 23 held them: every flag said
            assert assumed == {"vocab_rows", "remat", "scan_layers",
                               "use_flash"} == family.ASSUMED


def test_workloads_lead_to_files(root, spec):
    names = [w["name"] for w in spec["workloads"]]
    assert len(set(names)) == len(names)
    assert [(w["name"], w["config"], w["traffic"], w["chips"])
            for w in spec["workloads"][:len(ACCEPTED_CELLS)]] \
        == ACCEPTED_CELLS
    pairs = {(w["config"], w["traffic"]) for w in spec["workloads"]}
    assert len(pairs) == len(names)
    four = [w for w in spec["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(names) // 4)
    configs = {c["name"] for c in spec["configs"]}
    for w in spec["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert line(w["why"])
        assert os.path.exists(bench(root, "cells", w["name"] + ".json"))
        assert os.path.exists(bench(root, "kinds",
                                    kind_of(root, w) + ".py"))


def test_metrics(root, spec):
    cells = [w["name"] for w in spec["workloads"]]
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    assert [(m["name"], m["unit"], m["better"], m["bound"],
             m.get("workloads", [])[:len(had)])
            for m, (*_, had) in zip(spec["end_to_end"],
                                    ACCEPTED_END_TO_END)] \
        == [tuple(a) for a in ACCEPTED_END_TO_END]
    every = spec["end_to_end"] + spec["per_layer"]
    assert len({m["name"] for m in every}) == len(every)

    def cells_of(m):
        return m.get("workloads", cells)

    for m in every:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert set(cells_of(m)) <= set(cells)
        assert os.path.exists(bench(root, "metrics", m["name"] + ".py")), \
            m["name"]
    for m in spec["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    assert "workloads" not in e2e["setup_s"]
    for m in spec["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert line(m["layer"])
        # the metric it moves is reported in every cell where this one is
        assert set(cells_of(m)) <= set(cells_of(e2e[m["moves"]])), m["name"]
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%"
    for c in cells:
        mine = [m["name"] for m in spec["end_to_end"] if c in cells_of(m)]
        assert "setup_s" in mine and len(mine) >= 2
        assert any(c in cells_of(m) for m in spec["per_layer"])


def test_file_names_are_made_of_name_characters(root, spec):
    for base in spec["paths"]:
        for d, dirs, files in os.walk(os.path.join(root, base)):
            dirs[:] = [x for x in dirs if x != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(d, f), root)
                assert re.match(r"^[A-Za-z0-9_.\-/]+$", rel), rel


def test_the_harness_names_no_cell_configuration_or_metric(root, spec):
    """Later PRs add files and entries and edit nothing: so no harness
    file may branch on (or even mention, outside comments and docstrings
    it could not run without) a name from BENCHMARK.json. A family's name
    (a file under ``benchmark/families/``) may not appear at all, in the
    harness or in a metric reader: what is one family's lives in its
    file."""
    names = [w["name"] for w in spec["workloads"]] \
        + [c["name"] for c in spec["configs"]] \
        + [w["traffic"] for w in spec["workloads"]] \
        + [m["name"] for m in spec["end_to_end"] + spec["per_layer"]
           if m["name"] != "setup_s"]
    def py_files(sub):
        return [os.path.join(sub, f) for f in sorted(os.listdir(
            bench(root, sub))) if f.endswith(".py")]

    harness = ["run.py", "trace.py", "compare.py", "loading.py", "stats.py",
               "shapes.py", "peaks.py"] + py_files("kinds")
    for rel in harness:
        with open(bench(root, rel)) as f:
            text = f.read()
        for n in names:
            assert f'"{n}"' not in text and f"'{n}'" not in text, (rel, n)
    families = [os.path.basename(f)[:-3] for f in py_files("families")]
    assert "gpt2" in families
    for rel in ["run.py", "compare.py"] + py_files("kinds") \
            + py_files("metrics"):
        with open(bench(root, rel)) as f:
            text = f.read()
        for n in families:
            assert family_named_in_code(text, n) == [], (rel, n)


def family_named_in_code(text, family):
    """The identifiers and string constants of ``text`` that hold
    ``family`` as a word of its own (``_`` separates words; letters and
    digits do not). Comments and docstrings are not code: a family with a
    short name may share it with prose in a file that was there before it."""
    tree = ast.parse(text)
    docs = set()
    for node in ast.walk(tree):
        if isinstance(node, (ast.Module, ast.ClassDef, ast.FunctionDef,
                             ast.AsyncFunctionDef)) and node.body \
                and isinstance(node.body[0], ast.Expr) \
                and isinstance(node.body[0].value, ast.Constant):
            docs.add(id(node.body[0].value))
    words = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Constant):
            if isinstance(node.value, str) and id(node) not in docs:
                words.append(node.value)
        for field in ("id", "attr", "name", "arg", "module", "asname"):
            value = getattr(node, field, None)
            if isinstance(value, str):
                words.append(value)
    own = re.compile(rf"(?<![A-Za-z0-9]){re.escape(family)}(?![A-Za-z0-9])")
    return [w for w in words if own.search(w)]


@pytest.mark.parametrize("text, family, found", [
    # what this guard exists to refuse: one family's formula or a branch
    # on its name inside a kind or a reader
    ("n = shapes.gpt2_param_count(L, d, V, T)", "gpt2", True),
    ("if run.config['family'] == 'gpt2':\n    pass", "gpt2", True),
    ("from benchmark.families import gpt2", "gpt2", True),
    ("path = 'benchmark/families/moe.py'", "moe", True),
    # prose and longer words are not the family's name
    ('"""As gpt2 counts them."""\nx = 1  # gpt2 too', "gpt2", False),
    ("part5 = t50 + ct5", "t5", False),
    ("def f():\n    'a moe model is sparse'\n    return remoes", "moe", False),
])
def test_the_family_guard_reads_code_and_whole_words(text, family, found):
    assert bool(family_named_in_code(text, family)) is found
