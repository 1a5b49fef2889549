"""What the TPU's compiler makes of Granite 4.0-H's two serving programs
(PR 34).

Compile-only, for one described v5e chip, at the published widths of
``benchmark/configs/granite-4.0-h-small.json`` and the shapes of the cell
``serve_granite4hs_longdoc_sat`` (32 slots and the scratch one, rings of
8448 rows, prompts of up to 8192 tokens in the engine's [1, 512] chunks
over a key window of 8192): nothing runs, so nothing here is a time. It
holds that both programs fit the chip beside their arguments (the decode
step's float32 pass over 33 K/V windows included), that the donated cache
is updated in its own buffers, and that no program copies a layer's expert
stack or the whole state: XLA's choices decide that, not the jaxpr.

The topology is described inside a fixture, in this one file: only the
worker that runs this file loads the TPU's library.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest

from conftest import HLO_SHAPE, PASSES_ON, nbytes, unfused_lines
from ray_tpu.models import granite_hybrid as gh
from ray_tpu.models.prefill import (chunk_len, key_window,
                                    token_parameters)
from served_families import FAMILIES

HBM = 15.75 * 2 ** 30


@pytest.fixture(scope="module")
def engine():
    return FAMILIES["granite_hybrid"].cell()[1]


@pytest.fixture(scope="module")
def cfg():
    return FAMILIES["granite_hybrid"].cell()[0]


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
def compiled(one_chip, cfg, engine):
    """Both programs as the engine jits them (cache donated), compiled
    once for the module, with the persistent cache out of the way: such a
    compile is written to it but cannot be read back without a chip."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    def sds(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    slots = engine["max_batch"] + 1
    params = sds(jax.eval_shape(
        lambda: gh.granite_hybrid_init(jax.random.PRNGKey(0), cfg)))
    chunk = chunk_len(  # as the engine derives it
        engine["max_prompt_len"], *token_parameters(cfg, params),
        cache_len=engine["cache_len"])
    window = key_window(engine["max_prompt_len"], chunk)
    assert (slots, chunk, window, engine["cache_len"]) \
        == (33, 512, 8192, 8448)
    cache = sds(jax.eval_shape(lambda: gh.granite_hybrid_init_cache(
        cfg, slots, engine["cache_len"])))
    programs = {
        "decode": (lambda p, c, t, n: gh.granite_hybrid_decode_step(
            p, c, t, n, cfg), (params, cache, i32(slots), i32(slots))),
        "prefill": (lambda p, c, t, s, at, n:
                    gh.granite_hybrid_prefill_chunk(
                        p, c, t, s, at, n, cfg, window=window),
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
    """4.757 B bfloat16 parameters (9.51 GB) and 2.40 GB of cache are the
    arguments; the cache is aliased to the output, so it is held once. The
    step's float32 pass over 33 windows of 8448 K/V rows (2.3 GB if it
    were an array) is fused into the products that read it."""
    mem = compiled[which].memory_analysis()
    cache_bytes = 2 * nbytes((1, 33, 8448, 8, 128), 2) \
        + nbytes((9, 3, 33, cfg.mamba.conv_dim), 2) \
        + nbytes((9, 33, 128, 64, 128), 4)
    assert cache_bytes == 2_402_661_888
    assert mem.alias_size_in_bytes >= cache_bytes
    assert 11.9e9 < mem.argument_size_in_bytes < 11.95e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM
    # the step holds next to nothing of its own; a chunk holds its 512
    # queries' scores over the 8192-row window (537 MB in float32) and its
    # experts' rows: 0.65 GB (0.36 at the 256 queries of before PR 53)
    assert mem.temp_size_in_bytes < {"decode": 0.1e9,
                                     "prefill": 0.75e9}[which]


@pytest.mark.parametrize("which", ["decode", "prefill"])
def test_no_expert_stack_and_no_whole_state_is_copied(compiled, which):
    """A layer's expert stacks are 36 x 4096 x 1536 and 36 x 768 x 4096
    bfloat16 (453 and 226 MB), the SSM state 9 arrays of 33 x 128 x 64 x 128
    float32 (138 MB each), a K/V ring stack 33 x 8448 x 8 x 128 (571 MB).
    No ``copy``, ``transpose``, ``convert`` or slice in either program
    makes an array of their size: the experts are read where they lie, and
    state and rings are rewritten inside their donated buffers."""
    layer_state = nbytes((33, 128, 64, 128), 1)  # elements
    theirs = {nbytes((36, 4096, 1536), 1), nbytes((36, 768, 4096), 1),
              layer_state, 9 * layer_state, nbytes((33, 8448, 8, 128), 1)}
    moved, lines = [], 0
    for line in unfused_lines(compiled[which].as_text()):
        m = HLO_SHAPE.match(line)
        if not m or m.group(1) not in {"bf16", "f32"}:
            continue
        lines += 1
        dims = [int(d) for d in m.group(2).split(",")]
        if nbytes(dims, 1) in theirs and m.group(3) not in PASSES_ON:
            moved.append(line.strip()[:150])
    assert lines > 200, "read no program"
    assert moved == []


def test_both_programs_run_the_experts_through_the_kernel(
        compiled, cfg, experts_through_the_kernel):
    """PR 52 (until then: one batched product a matrix): 33 rows a step, 512 a chunk, and in both of the engine's programs the
    gated experts' two products are ONE custom call of the kernel of
    ``ops/moe_experts.py`` a layer, under scope ``experts``, handed the
    layer's 36 x 4096 x 1536 and 36 x 768 x 4096 stacks as they lie (``w1``
    twice: a block of F takes the same columns of its gate and up halves).
    No sort's rows, no ``[E, T, F]`` in float32 (the batched product's, 56
    MB a chunk) and no float32 copy of a stack."""
    assert (cfg.experts_held[1], cfg.d_model, cfg.expert_ff) \
        == (36, 4096, 768)
    for which in ("decode", "prefill"):
        experts_through_the_kernel(compiled[which], len(cfg.layer_types), 36,
                                   4096, 1536, 768)


def test_the_chunk_keeps_the_cache_in_the_steps_layout(compiled):
    """The K/V rings and a layer's SSM state (2.38 of the cache's 2.40 GB):
    each shape has one layout in the chunk program, and it is the decode
    program's, so neither is re-laid out between the two."""
    def layouts(shape, which):
        # (a trailing S(n) names a memory space, not a layout)
        return {re.sub(r"S\(\d+\)", "", found) for found in re.findall(
            shape + r"(\{[^}]*\})", compiled[which].as_text())}

    for shape in (r"bf16\[1,33,8448,8,128\]", r"f32\[33,128,64,128\]"):
        assert len(layouts(shape, "prefill")) == 1, shape
        assert layouts(shape, "prefill") == layouts(shape, "decode"), shape
