"""A temporary copy of the benchmark with toy cells added AS FILES: a
third configuration, three traffic mixes, four cells and one metric, and
their entries in the copy's BENCHMARK.json. No file of the copy is edited
except that list of entries, which is how a later PR adds to it.

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
    {"name": "toy_train_4dev", "config": "gpt2-toy", "traffic": "toy_packed",
     "chips": 4, "why": "CPU rehearsal of kind train_packed on 4 devices"},
    {"name": "toy_closed", "config": "gpt2-toy", "traffic": "toy_closed",
     "chips": 1, "why": "CPU rehearsal of kind serve_closed"},
    {"name": "toy_open", "config": "gpt2-toy", "traffic": "toy_open",
     "chips": 1, "why": "CPU rehearsal of kind serve_open"},
]


def make_root(tmp: str) -> str:
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
    spec["configs"].append({
        "name": "gpt2-toy", "source": "none (toy)",
        "file": "benchmark/configs/gpt2-toy.json", "reduced": [],
        "why": "CPU rehearsal"})
    spec["workloads"].extend(TOY_CELLS)
    train = ["toy_train", "toy_train_4dev"]
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" not in m:
            continue
        real = m["workloads"]
        if "train_gpt2s_1chip" in real:
            m["workloads"] = real + train
        if "serve_gpt2xl_decode_sat" in real:
            m["workloads"] = real + ["toy_closed"]
        if "serve_gpt2xl_prompt_rate" in real:
            m["workloads"] = real + ["toy_open"]
    spec["per_layer"].append({
        "name": "toy_slices", "unit": "slices", "better": "higher",
        "source": "host_clock", "layer": "trainer loop",
        "moves": "train_tokens_per_s_chip", "workloads": train})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    return root


def _listing(root: str) -> list:
    out = []
    for d, _, files in os.walk(os.path.join(root, "benchmark")):
        out.extend(os.path.relpath(os.path.join(d, f), root) for f in files)
    return out


def _same(a: str, b: str) -> bool:
    with open(a, "rb") as fa, open(b, "rb") as fb:
        return fa.read() == fb.read()
