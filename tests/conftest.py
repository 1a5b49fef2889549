"""Test harness: force an 8-virtual-device CPU platform.

Mirrors the reference's trick of simulating multi-node clusters on one host
(``python/ray/cluster_utils.py:99``): here we simulate an 8-chip TPU slice
with 8 XLA CPU devices so every sharding/collective path is exercised
without TPU hardware (SURVEY.md §4.3).
"""

import os

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
    import re

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
    import re

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
