"""Test harness: force an 8-virtual-device CPU platform.

Mirrors the reference's trick of simulating multi-node clusters on one host
(``python/ray/cluster_utils.py:99``): here we simulate an 8-chip TPU slice
with 8 XLA CPU devices so every sharding/collective path is exercised
without TPU hardware (SURVEY.md §4.3).
"""

import os
import re

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax

# Tests never take a chip: the platform is pinned to the CPU before any
# backend is initialized, even where JAX_PLATFORMS names another one.
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running chaos/bench workouts, deselected by the "
        "tier-1 run's -m 'not slow'",
    )
    # Reclaim /dev/shm segments leaked by SIGKILLed earlier runs (their
    # owner pids are dead): 121 GB of leaked segments after one
    # interrupted soak made later tier-1 runs OOM spuriously.
    try:
        from ray_tpu.util.shm_sweep import sweep_stale_shm

        swept, nbytes = sweep_stale_shm()
        if swept:
            print(f"[conftest] swept {swept} stale /dev/shm segment(s), "
                  f"{nbytes / 1e9:.2f} GB")
    except Exception:
        pass


@pytest.fixture(scope="session")
def devices8():
    devs = jax.devices()
    assert len(devs) >= 8, f"expected 8 cpu devices, got {len(devs)}"
    return devs[:8]


@pytest.fixture(scope="session")
def experts_through_the_kernel():
    """What the five expert families' compile-only files hold of a program
    compiled for the chip (``ops/moe_experts.py``): ``check(compiled,
    layers, experts, d, f1, f)`` holds that the experts' product is the
    kernel's custom call once an expert layer, under scope ``experts``,
    handed each layer's ``w1`` and ``w2`` stacks as they lie (bfloat16, no
    float32 copy of either anywhere), and that the TPU's grouped product is
    nowhere."""
    def check(compiled, layers, experts, d, f1, f):
        text = compiled.as_text()
        calls = [line for line in text.splitlines()
                 if 'custom_call_target="tpu_custom_call"' in line
                 and "%grouped_experts" in line]
        assert len(calls) == layers, (len(calls), layers)
        w1, w2 = f"bf16[{experts},{d},{f1}]", f"bf16[{experts},{f},{d}]"
        for line in calls:
            assert re.search(r'op_name="[^"]*/experts/grouped_experts/',
                             line), line[-300:]
            handed = re.search(r"operand_layout_constraints=\{(.*?)\}, \w+=",
                               line).group(1)
            assert handed.count(w1) == f1 // f and handed.count(w2) == 1, \
                handed
        assert "ragged" not in text
        assert f"f32[{experts},{d},{f1}]" not in text
        assert f"f32[{experts},{f},{d}]" not in text

    return check


@pytest.fixture(scope="session")
def chunk_attends_through_the_kernel():
    """What the four merged-ring families' compile-only files hold of a
    chunk program compiled for the chip (``ops/merged_chunk.py``, PR 64):
    ``check(compiled, layers, stack, chunk, window)`` holds that the
    full layers' attention is the kernel's custom call once a layer, under
    scope ``attn``, handed the K stack and the V stack as they lie (``stack``
    is their shape, ``[N, S, L, W]``); that nothing, inside a fusion or
    outside, cuts the window's old rows out of a stack; and that no float32
    array over those ``window - chunk`` rows (the XLA arm's scores, ``[heads,
    chunk, window - chunk]``) is anywhere in the program."""
    def check(compiled, layers, stack, chunk, window):
        text = compiled.as_text()
        calls = [line for line in text.splitlines()
                 if 'custom_call_target="tpu_custom_call"' in line
                 and "/merged_chunk_attention/" in line]
        assert len(calls) == layers, (len(calls), layers)
        handed_whole = "bf16[" + ",".join(str(d) for d in stack) + "]"
        for line in calls:
            assert re.search(r'op_name="[^"]*/attn/', line), line[-300:]
            handed = re.search(r"operand_layout_constraints=\{(.*?)\}, \w+=",
                               line).group(1)
            assert handed.count(handed_whole) == 2, handed
        old, w = window - chunk, stack[-1]
        cut = re.compile(rf"= bf16\[(?:1,)*{old},{w}\]\S* "
                         rf"(?:dynamic-slice|slice|copy)\(")
        assert not cut.search(text), cut.search(text).group(0)
        over_the_window = [dims for dims in set(re.findall(
            r"f32\[([\d,]+)\]", text)) if str(old) in dims.split(",")]
        assert over_the_window == []

    return check


# -- reading a compiled program's text ----------------------------------------
#
# What the compile-only files (``tests/test_serving_programs*_v5e.py``) share
# of reading ``compiled.as_text()``: plain functions, imported from here.

HLO_SHAPE = re.compile(r"^\s*(?:ROOT )?%[\w.\-]+ = (\w+)\[([\d,]+)\]\S* "
                       r"([\w\-]+)\(")
HLO_RESULT = re.compile(r"^\s*(?:ROOT )?%[\w.\-]+ = (.*?) ([\w\-]+)\(")
# opcodes that hand an array on and make none
HANDED_ON = ("parameter", "get-tuple-element", "tuple", "bitcast")
# ... and those that work inside a buffer they were given
PASSES_ON = ("get-tuple-element", "parameter", "bitcast", "tuple",
             "fusion", "dynamic-update-slice", "custom-call", "while",
             "conditional", "call", "opt-barrier")


def nbytes(shape, itemsize):
    n = itemsize
    for d in shape:
        n *= d
    return n


def unfused(hlo_text):
    """The text of every computation but the ones a ``fusion`` calls:
    inside a fusion a slice or a convert is a step of one loop, not a
    buffer."""
    fused = set(re.findall(r" fusion\(.*?calls=(%[\w.\-]+)", hlo_text))
    return "\n".join(block for block in hlo_text.split("\n\n")
                     if block.lstrip().split(" ", 1)[0] not in fused)


def unfused_lines(hlo_text):
    """The instructions that make an array of their own: those of every
    computation but the ones a ``fusion`` calls."""
    for block in unfused(hlo_text).split("\n\n"):
        yield from block.splitlines()[1:]


def arrays_made(hlo_text):
    """(type, elements, opcode) of every instruction of ``hlo_text`` that
    makes an array by moving one: ``copy``, ``transpose``, ``convert`` and
    slices."""
    for line in hlo_text.splitlines():
        m = HLO_SHAPE.match(line)
        if m and m.group(3) in ("copy", "transpose", "convert", "slice",
                                "dynamic-slice"):
            yield m.group(1), nbytes(
                [int(d) for d in m.group(2).split(",")], 1), m.group(3)


def made_as_large_as(hlo_text, large):
    """(opcode, first operand) of every instruction that gives out an array
    whose element count ``large`` says yes to (a tuple's members counted
    each) and does not merely hand one on."""
    made = []
    for line in hlo_text.splitlines():
        m = HLO_RESULT.match(line)
        if m and m.group(2) not in HANDED_ON and any(
                large(nbytes([int(d) for d in dims.split(",")], 1))
                for dims in re.findall(r"\w+\[([\d,]+)\]", m.group(1))):
            made.append((m.group(2), re.findall(r"\(%([\w.\-]+)", line)[0]))
    return made
