"""What the TPU's compiler makes of the hybrid model's two serving
programs (PR 28).

Compile-only, for one described v5e chip, at the published widths of
``benchmark/configs/nemotron3-super-120b-a12b.json`` and the cell's shapes
(64 slots and the scratch one, a 2048-row cache, prompts of up to 1024
tokens in the engine's [1, 512] prefill chunks, PR 53; [1, 256] since
PR 31, a [4, 1024] lane before): nothing runs, so nothing here is a time. It holds that both programs fit
the chip beside their arguments, that the donated cache (K/V rows, the
convolution tails, the float32 SSM state) is updated in its own buffers,
and that no program copies a layer's expert stack or the whole state: XLA's
choices decide that, not the jaxpr.

The topology is described inside a fixture, in this one file: only the
worker that runs this file loads the TPU's library.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest

from conftest import HLO_SHAPE, PASSES_ON, nbytes, unfused_lines
from ray_tpu.models import nemotron_h as nh
from ray_tpu.models.prefill import (chunk_len, key_window,
                                    token_parameters)
from served_families import FAMILIES

SLOTS, CACHE_LEN, PROMPT_LEN = 65, 2048, 1024
HBM = 15.75 * 2 ** 30


@pytest.fixture(scope="module")
def cfg():
    return FAMILIES["nemotron_h"].cell()[0]


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to say
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def compiled(one_chip, cfg):
    """Both programs as the engine jits them (cache donated), compiled
    once for the module, with the persistent cache out of the way: such a
    compile is written to it but cannot be read back without a chip."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    def sds(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    params = sds(jax.eval_shape(
        lambda: nh.nemotron_h_init(jax.random.PRNGKey(0), cfg)))
    cache = sds(jax.eval_shape(
        lambda: nh.nemotron_h_init_cache(cfg, SLOTS, CACHE_LEN)))
    chunk = chunk_len(PROMPT_LEN, *token_parameters(cfg, params),
                      cache_len=CACHE_LEN)
    assert chunk == 512  # as the engine derives it
    programs = {
        "decode": (lambda p, c, t, n: nh.nemotron_h_decode_step(
            p, c, t, n, cfg), (params, cache, i32(SLOTS), i32(SLOTS))),
        "prefill": (lambda p, c, t, s, at, n: nh.nemotron_h_prefill_chunk(
            p, c, t, s, at, n, cfg, window=key_window(PROMPT_LEN, chunk)),
                    (params, cache, i32(1, chunk), i32(1), i32(1), i32(1))),
    }
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        # ``jax.default_backend()`` chooses a kernel's interpret mode and is
        # the CPU here: while the programs are traced it says the chip's, so
        # they hold the experts' kernel (PR 52), not the interpreter's loops
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(jax, "default_backend", lambda: "tpu")
            return {name: jax.jit(fn, donate_argnums=(1,)).lower(
                *args).compile() for name, (fn, args) in programs.items()}
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


@pytest.mark.parametrize("which", ["decode", "prefill"])
def test_the_program_fits_the_chip_beside_its_arguments(compiled, cfg,
                                                        which):
    """4.65 B bfloat16 parameters (9.30 GB) and 1.52 GB of cache are the
    arguments; the cache is aliased to the output, so it is held once."""
    mem = compiled[which].memory_analysis()
    cache_bytes = 2 * nbytes((1, SLOTS, CACHE_LEN, 2, 128), 2) \
        + nbytes((5, 3, SLOTS, cfg.conv_dim), 2) \
        + nbytes((5, SLOTS, 128, 64, 128), 4)
    assert cache_bytes == 1_519_431_680
    assert mem.alias_size_in_bytes >= cache_bytes
    assert 10.7e9 < mem.argument_size_in_bytes < 10.9e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM
    # the step holds next to nothing of its own; a chunk's sorted rows
    # and scan 0.10 GB, where the [4, 1024] lane's took 1.20 GB
    assert mem.temp_size_in_bytes < {"decode": 0.3e9, "prefill": 0.3e9}[which]


@pytest.mark.parametrize("which", ["decode", "prefill"])
def test_no_expert_stack_and_no_whole_state_is_copied(compiled, cfg, which):
    """A layer's expert stack is 128 x 1024 x 2688 bfloat16 (705 MB a
    matrix) and the SSM state 5 arrays of 65 x 128 x 64 x 128 float32 (1.36 GB; a
    layer's 273 MB). No ``copy``, ``transpose``, ``convert`` or slice in
    either program makes an array of their size: the experts are read where
    they lie, and the state is rewritten inside its donated buffer (fusions
    and in-place ``dynamic-update-slice``s pass it on)."""
    stack = nbytes((128, 1024, 2688), 1)  # elements
    layer_state = nbytes((SLOTS, 128, 64, 128), 1)
    theirs = {stack, layer_state, 5 * layer_state}
    sizes = {"bf16", "f32"}
    moved, lines = [], 0
    for line in unfused_lines(compiled[which].as_text()):
        m = HLO_SHAPE.match(line)
        if not m or m.group(1) not in sizes:
            continue
        lines += 1
        dims = [int(d) for d in m.group(2).split(",")]
        if nbytes(dims, 1) in theirs and m.group(3) not in PASSES_ON:
            moved.append(line.strip()[:150])
    assert lines > 200, "read no program"
    assert moved == []


def test_both_programs_and_the_wide_lane_run_the_experts_through_the_kernel(
        compiled, one_chip, cfg, experts_through_the_kernel):
    """PR 52 (until then: batched products here, the TPU's grouped product
    in the wide lane): 65 rows a step, 512 a chunk, and in both of the
    engine's programs the experts' two products are ONE custom call of the
    kernel of ``ops/moe_experts.py`` an ``E`` layer, under scope
    ``experts``, handed the layer's expert stacks as they lie. ``[E, T, F]`` in float32, which
    the batched product of every held expert over every row made (352 MB a
    chunk), is gone: the step keeps under 0.05 GB of its own and the chunk
    under 0.15 GB. The whole-window form on two prompts of 1024 (what the
    benchmark's reference check calls: two rows of the rule's 512 tokens a
    chunk, two lanes of the kernel) runs the same kernel, and is ONE traced
    copy of the layers looped over its two chunks: ten calls, not
    twenty."""
    shape = (128, 1024, 2688, 2688)  # held, latent, w1's and w2's F
    assert shape == (cfg.experts_held[1], cfg.latent, cfg.expert_ff,
                     cfg.expert_ff)
    for which in ("decode", "prefill"):
        experts_through_the_kernel(compiled[which], cfg.count("E"), *shape)
        assert compiled[which].memory_analysis().temp_size_in_bytes \
            < {"decode": 0.05e9, "prefill": 0.15e9}[which]

    def sds(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32,
                                              sharding=one_chip)
    params = sds(jax.eval_shape(
        lambda: nh.nemotron_h_init(jax.random.PRNGKey(0), cfg)))
    cache = sds(jax.eval_shape(
        lambda: nh.nemotron_h_init_cache(cfg, 3, CACHE_LEN)))
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(jax, "default_backend", lambda: "tpu")
            whole = jax.jit(lambda p, c, t, s, n: nh.nemotron_h_prefill(
                p, c, t, s, n, cfg), donate_argnums=(1,)).lower(
                    params, cache, i32(2, PROMPT_LEN), i32(2),
                    i32(2)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
    experts_through_the_kernel(whole, 2 * cfg.count("E"), *shape)
    assert whole.memory_analysis().temp_size_in_bytes < 1.0e9


def test_the_chunk_keeps_the_cache_in_the_steps_layout(compiled):
    """K/V rows and a layer's SSM state (1.50 of the cache's 1.52 GB): each
    shape has one layout in the chunk program, and it is the decode
    program's. The chunk reads the slot's rows and state out of the buffers
    it then writes, and neither is re-laid out around that. The 20 MB of
    convolution tails are the exception the compiler makes for a chunk of
    one row: it takes them whole into fast memory (``S(1)``) in a tiling of
    their own, writes the slot's five tails there and copies them back,
    once an execution and not a layer."""
    def layouts(shape, which):
        # (a trailing S(n) names a memory space, not a layout)
        return {re.sub(r"S\(\d+\)", "", found) for found in re.findall(
            shape + r"(\{[^}]*\})", compiled[which].as_text())}

    for shape in (f"bf16\\[1,{SLOTS},{CACHE_LEN},2,128\\]",
                  f"f32\\[{SLOTS},128,64,128\\]"):
        assert len(layouts(shape, "prefill")) == 1, shape
        assert layouts(shape, "prefill") == layouts(shape, "decode"), shape
    tails = compiled["prefill"].as_text()
    assert len(re.findall(r" copy\(%c__conv__", tails)) <= 1
