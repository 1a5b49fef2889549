"""Pass 12 — step-timing honesty (TH): timed walls must end at a sync.

The step-anatomy plane (round 19) stands on a discipline the runtime
cannot check: a wall-clock interval around asynchronously-dispatched
device work measures *dispatch*, not *compute*, unless a real host sync
sits between the timer reads. The bug shape is silent and flattering —
an unsynced loop reports a 40x "speedup" (the launch latency) and the
compute phase reads garbage. The ``# step-timed`` marker (on or directly
above a ``def``, same idiom as ``# jax-hot-path``) declares a function
whose timer reads bracket device work; this pass makes the sync
requirement static:

* **TH001** — a ``# step-timed`` function takes two or more timer
  reads (``time.perf_counter`` / ``time.monotonic`` and their ``_ns``
  forms) with no recognizable host sync between the FIRST and LAST
  read: ``jax.block_until_ready`` / ``.item()`` / ``np.asarray`` /
  ``np.array`` / ``jax.device_get`` / a builtin ``float(...)`` of a
  device value / a ``*sync*``-named helper
  (``_block_sync``). Whatever the interval is timing, it is not synced
  device work.
* **TH002** — a ``# step-timed`` function with fewer than two timer
  reads: the marker declares a timed region that times nothing — a
  stale annotation is a lie the next reader will trust.

Intermediate unsynced reads are fine (the anatomy host/compute split
reads the clock after dispatch *and* after the sync); the rule only
demands that a sync exists somewhere between the first and last read.
"""

from __future__ import annotations

import ast
from typing import List, Optional, Tuple

from ray_tpu.util.analyze.core import (
    FindingSink,
    ParsedModule,
    analysis_pass,
)
from ray_tpu.util.analyze.resolver import callee_name, receiver_of

_MARK = "# step-timed"

_TIMER_READS = frozenset({
    "perf_counter", "perf_counter_ns", "monotonic", "monotonic_ns",
})
_SYNC_ATTRS = frozenset({"block_until_ready", "item", "device_get"})
_NP_ALIASES = frozenset({"np", "numpy", "onp"})


def _marked(mod: ParsedModule, fn: ast.AST) -> bool:
    for ln in (fn.lineno, fn.lineno - 1):
        if _MARK in mod.line_text(ln):
            return True
    # Decorated defs: the marker may sit above the decorator stack.
    deco = getattr(fn, "decorator_list", None)
    if deco:
        top = min(d.lineno for d in deco)
        if _MARK in mod.line_text(top - 1):
            return True
    return False


def _walk_own(fn: ast.AST):
    """Walk a function's own body, excluding nested defs (each nested
    function is its own markable region)."""
    stack = list(ast.iter_child_nodes(fn))
    while stack:
        node = stack.pop()
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        yield node
        stack.extend(ast.iter_child_nodes(node))


def _is_sync(node: ast.Call) -> Optional[str]:
    """Human-readable label when the call forces device completion
    (None otherwise)."""
    name = callee_name(node) or ""
    recv = receiver_of(node)
    if name in _SYNC_ATTRS:
        return f".{name}()" if recv is not None else f"{name}()"
    if name in ("asarray", "array") and isinstance(recv, ast.Name) \
            and recv.id in _NP_ALIASES:
        return f"{recv.id}.{name}"
    if isinstance(node.func, ast.Name) and node.func.id == "float" \
            and node.args:
        return "float(...)"
    if "sync" in name.lower():
        return f"{name}()"
    return None


@analysis_pass("timing")
def timing_pass(mod: ParsedModule) -> List:
    sink = FindingSink(mod.relpath)
    model = mod.model()
    for cm, fn, scope in model.functions():
        if not _marked(mod, fn):
            continue
        reads: List[Tuple[int, int]] = []
        syncs: List[Tuple[Tuple[int, int], str]] = []
        for node in _walk_own(fn):
            if not isinstance(node, ast.Call):
                continue
            pos = (node.lineno, node.col_offset)
            if callee_name(node) in _TIMER_READS:
                reads.append(pos)
                continue
            label = _is_sync(node)
            if label is not None:
                syncs.append((pos, label))
        if len(reads) < 2:
            sink.emit(
                "TH002", fn.lineno, scope, "untimed",
                f"`# step-timed` region {scope} takes "
                f"{len(reads)} timer read(s): the marker declares a "
                f"timed step region but the function times nothing — "
                f"a stale annotation the next reader will trust",
                "remove the marker, or time the region (two "
                "perf_counter reads bracketing the work)")
            continue
        first, last = min(reads), max(reads)
        if not any(first < pos <= last for pos, _ in syncs):
            sink.emit(
                "TH001", last[0], scope, "unsynced-wall",
                f"`# step-timed` region {scope} measures a wall "
                f"between timer reads (lines {first[0]}-{last[0]}) "
                f"with no host sync between them: around async "
                f"dispatch this times the launch, not the device — "
                f"the anatomy seconds built on it are fiction",
                "force completion before the closing read "
                "(jax.block_until_ready on the step outputs, or "
                "float() a device scalar)")
    return sink.findings
