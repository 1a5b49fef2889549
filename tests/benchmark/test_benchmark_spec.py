"""``BENCHMARK.json`` against the contract it was written to, and the
rule that the harness is driven by data: every name in it leads to a file,
and no harness file branches on a cell's, configuration's or metric's
name."""

import json
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmark")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
WIDTHS = re.compile(r"(hidden|intermediate|latent|state|proj|_dim$|_rank$|"
                    r"head_dim|n_embd|n_inner|expansion|experts_per)")


@pytest.fixture(scope="module")
def spec():
    path = os.path.join(REPO, "BENCHMARK.json")
    assert os.path.getsize(path) <= 64 * 1024
    with open(path) as f:
        return json.load(f)


def line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


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


def test_configs(spec):
    names = [c["name"] for c in spec["configs"]]
    assert len(set(names)) == len(names)
    used = {w["config"] for w in spec["workloads"]}
    for c in spec["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and line(c["source"]) and line(c["why"])
        assert c["name"] in used
        assert c["file"].startswith("benchmark/configs/")
        with open(os.path.join(REPO, c["file"])) as f:
            held = json.load(f)
        assert held["source"] == c["source"]
        assert held["reduced"] == c["reduced"]
        assert not any(WIDTHS.search(k) for k in c["reduced"])
        assert {"train_loss_rel", "train_grad_norm_rel",
                "serve_logits_rel_l2", "serve_token_regret_rms",
                "reason"} <= set(held["tolerance"])
        # the file sets no storage type and no path flag beyond the named
        assert "param_dtype" not in json.dumps(held)
        assert set(held["assumed"]) - {"why", "vocab_rows_why"} == {
            "vocab_rows", "remat", "scan_layers", "use_flash"}


def test_workloads_lead_to_files(spec):
    names = [w["name"] for w in spec["workloads"]]
    assert names == ["train_gpt2s_1chip", "serve_gpt2xl_decode_sat",
                     "serve_gpt2xl_prompt_rate", "train_gpt2xl_4chip"]
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
        assert os.path.exists(os.path.join(BENCH, "cells",
                                           w["name"] + ".json"))
        with open(os.path.join(BENCH, "traffic", w["traffic"] + ".json")) as f:
            kind = json.load(f)["kind"]
        assert os.path.exists(os.path.join(BENCH, "kinds", kind + ".py"))


def test_metrics(spec):
    cells = [w["name"] for w in spec["workloads"]]
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    assert list(e2e) == ["train_tokens_per_s_chip", "serve_out_tokens_per_s",
                         "itl_p99_ms", "ttft_p90_ms", "setup_s"]
    every = spec["end_to_end"] + spec["per_layer"]
    assert len({m["name"] for m in every}) == len(every)

    def cells_of(m):
        return m.get("workloads", cells)

    for m in every:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert set(cells_of(m)) <= set(cells)
        assert os.path.exists(os.path.join(BENCH, "metrics",
                                           m["name"] + ".py")), m["name"]
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


def test_file_names_are_made_of_name_characters():
    for base in ("benchmark", os.path.join("tests", "benchmark")):
        for d, dirs, files in os.walk(os.path.join(REPO, base)):
            dirs[:] = [x for x in dirs if x != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(d, f), REPO)
                assert re.match(r"^[A-Za-z0-9_.\-/]+$", rel), rel


def test_the_harness_names_no_cell_configuration_or_metric(spec):
    """Later PRs add files and entries and edit nothing: so no harness
    file may branch on (or even mention, outside comments and docstrings
    it could not run without) a name from BENCHMARK.json."""
    names = [w["name"] for w in spec["workloads"]] \
        + [c["name"] for c in spec["configs"]] \
        + [w["traffic"] for w in spec["workloads"]] \
        + [m["name"] for m in spec["end_to_end"] + spec["per_layer"]
           if m["name"] != "setup_s"]
    harness = ["run.py", "trace.py", "compare.py", "loading.py", "stats.py",
               "shapes.py", "peaks.py"] + [
        os.path.join("kinds", f) for f in os.listdir(
            os.path.join(BENCH, "kinds")) if f.endswith(".py")]
    for rel in harness:
        with open(os.path.join(BENCH, rel)) as f:
            text = f.read()
        for n in names:
            assert f'"{n}"' not in text and f"'{n}'" not in text, (rel, n)
