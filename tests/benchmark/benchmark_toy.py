"""A temporary copy of the benchmark with toy cells added AS FILES: a
third configuration of the family the benchmark has and one of a family
it has not (``llama_toy``: its family file, its plain reference and its
configuration), four traffic mixes, six cells and one metric, and their
entries in the copy's BENCHMARK.json. No file of the copy is edited
except that list of entries, which is how a later PR adds to it.

``make_root(tmp, third_family=True)`` does what the next ``model_config`` PR
will: it drops the files of a family that no test names (``third_toy``: the
toy family's files under another name), its configuration and a cell into
the copy. Every check that runs on a root then runs on that one too, so a
test that pins the list of families fails here and not in that PR.

The toy cells are CPU rehearsals of the harness's control flow. They say
nothing about a device, and the harness prints no metric value for them.
"""

import json
import os
import shutil

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))

TOY_CELLS = [
    {"name": "toy_train", "config": "gpt2-toy", "traffic": "toy_packed",
     "chips": 1, "why": "CPU rehearsal of kind train_packed"},
    {"name": "toy_train_4dev", "config": "gpt2-toy",
     "traffic": "toy_packed_4dev", "chips": 4,
     "why": "CPU rehearsal of kind train_packed on 4 devices"},
    {"name": "toy_closed", "config": "gpt2-toy", "traffic": "toy_closed",
     "chips": 1, "why": "CPU rehearsal of kind serve_closed"},
    {"name": "toy_open", "config": "gpt2-toy", "traffic": "toy_open",
     "chips": 1, "why": "CPU rehearsal of kind serve_open"},
    {"name": "toy_llama_closed", "config": "llama-toy",
     "traffic": "toy_closed", "chips": 1,
     "why": "CPU rehearsal of kind serve_closed on a family added as files"},
    {"name": "toy_llama_train", "config": "llama-toy",
     "traffic": "toy_packed", "chips": 1,
     "why": "CPU rehearsal of kind train_packed on a family added as files"},
]


THIRD_CELL = {
    "name": "toy_third_closed", "config": "third-toy",
    "traffic": "toy_closed", "chips": 1,
    "why": "CPU rehearsal of kind serve_closed on a family no test names"}


def make_root(tmp: str, third_family: bool = False) -> str:
    """Copy BENCHMARK.json and benchmark/ to ``tmp``, overlay the toy
    files, add their entries. Returns the root of the copy."""
    root = os.path.join(tmp, "checkout")
    shutil.copytree(os.path.join(REPO, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _listing(root)
    shutil.copytree(os.path.join(HERE, "toy"),
                    os.path.join(root, "benchmark"), dirs_exist_ok=True)
    # Overlaying added files and changed none that was there.
    assert all(_same(os.path.join(REPO, p), os.path.join(root, p))
               for p in before)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cells, closed = list(TOY_CELLS), ["toy_closed", "toy_llama_closed"]
    if third_family:
        _add_third_family(root)
        cells.append(THIRD_CELL)
        closed.append(THIRD_CELL["name"])
    for name in ["gpt2-toy", "llama-toy"] + ["third-toy"] * third_family:
        file = f"benchmark/configs/{name}.json"
        with open(os.path.join(root, file)) as f:
            source = json.load(f)["source"]
        spec["configs"].append({"name": name, "source": source, "file": file,
                                "reduced": [], "why": "CPU rehearsal"})
    spec["workloads"].extend(cells)
    train = ["toy_train", "toy_train_4dev", "toy_llama_train"]
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" not in m:
            continue
        real = m["workloads"]
        if "train_gpt2s_1chip" in real:
            m["workloads"] = real + train
        if "serve_gpt2xl_decode_sat" in real:
            m["workloads"] = real + closed
        if "serve_gpt2xl_prompt_rate" in real:
            m["workloads"] = real + ["toy_open"]
    spec["per_layer"].append({
        "name": "toy_slices", "unit": "slices", "better": "higher",
        "source": "host_clock", "layer": "trainer loop",
        "moves": "train_tokens_per_s_chip", "workloads": train})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    return root


def _add_third_family(root: str) -> None:
    """The toy family's files again under a name no test holds: family
    file, plain reference, configuration, cell."""
    bench = os.path.join(root, "benchmark")
    for sub in ("families", "reference"):
        shutil.copy(os.path.join(bench, sub, "llama_toy.py"),
                    os.path.join(bench, sub, "third_toy.py"))
    with open(os.path.join(bench, "configs", "llama-toy.json")) as f:
        config = json.load(f)
    with open(os.path.join(bench, "configs", "third-toy.json"), "w") as f:
        json.dump({**config, "family": "third_toy"}, f)
    shutil.copy(os.path.join(bench, "cells", "toy_llama_closed.json"),
                os.path.join(bench, "cells", THIRD_CELL["name"] + ".json"))


def _listing(root: str) -> list:
    out = []
    for d, _, files in os.walk(os.path.join(root, "benchmark")):
        out.extend(os.path.relpath(os.path.join(d, f), root) for f in files)
    return out


def _same(a: str, b: str) -> bool:
    with open(a, "rb") as fa, open(b, "rb") as fb:
        return fa.read() == fb.read()
