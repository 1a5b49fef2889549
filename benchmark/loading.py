"""Finding the benchmark's files by name: every kind, family, reference
and metric reader is a file of its own, loaded from its path."""

from __future__ import annotations

import importlib.util
import json
import os
import sys


def load_module(path: str):
    if not os.path.exists(path):
        raise FileNotFoundError(f"the benchmark has no file {path}")
    name = "benchmark_file_" + "".join(
        c if c.isalnum() else "_" for c in os.path.abspath(path))
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    try:
        spec.loader.exec_module(mod)
    except BaseException:
        del sys.modules[name]
        raise
    return mod


def sibling(file: str, name: str):
    """Another file of the same directory (a reader that shares a
    reduction with its neighbour)."""
    return load_module(os.path.join(os.path.dirname(file), name))


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)
