"""What the TPU's compiler makes of DeepSeek-V2's two serving programs
(PR 38).

Compile-only, for one described v5e chip, at the published widths of
``benchmark/configs/deepseek-v2.json`` and the shapes of the cell
``serve_dsv2_longctx_sat`` (64 slots and the scratch one, rings of 16896
latent rows, prompts of up to 16384 tokens in the engine's [1, 512]
chunks): nothing runs, so nothing here is a time. It holds that both
programs fit the chip beside their arguments with next to nothing of their
own (no float32 copy of a window, no scores over a whole ring: both
attentions read the ring in blocks), that the donated cache is updated in
its own buffers and lies in ONE unpadded layout in both programs, that the
step's attention is a loop whose trip count the positions decide and, since
PR 59, that the chunk's is one call a layer of the kernel of
``ops/latent_chunk.py``, whose loop is its own.

The topology is described inside a fixture, in this one file: only the
worker that runs this file loads the TPU's library.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.models import deepseek_v2 as ds
from ray_tpu.models.prefill import (chunk_len, key_window,
                                    token_parameters)
from served_families import FAMILIES

HBM = 15.75 * 2 ** 30
RING = r"bf16\[5,65,16896,1,576\]"


@pytest.fixture(scope="module")
def engine():
    return FAMILIES["deepseek_v2"].cell()[1]


@pytest.fixture(scope="module")
def cfg():
    return FAMILIES["deepseek_v2"].cell()[0]


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
        lambda: ds.deepseek_v2_init(jax.random.PRNGKey(0), cfg)))
    chunk = chunk_len(  # as the engine derives it
        engine["max_prompt_len"], *token_parameters(cfg, params),
        cache_len=engine["cache_len"])
    window = key_window(engine["max_prompt_len"], chunk)
    assert (slots, chunk, window, engine["cache_len"]) \
        == (65, 512, 16384, 16896)
    cache = sds(jax.eval_shape(lambda: ds.deepseek_v2_init_cache(
        cfg, slots, engine["cache_len"])))
    programs = {
        "decode": (lambda p, c, t, n: ds.deepseek_v2_decode_step(
            p, c, t, n, cfg), (params, cache, i32(slots), i32(slots))),
        "prefill": (lambda p, c, t, s, at, n: ds.deepseek_v2_prefill_chunk(
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
def test_the_program_fits_the_chip_beside_its_arguments(compiled, which):
    """3.145 B bfloat16 parameters (6.29 GB) and 6.33 GB of latent rings
    are the arguments; the rings are aliased to the output, so they are
    held once: 12.6 GB at rest, 79 % of the chip. As arrays a float32 copy
    of one layer's rings would be 2.5 GB and a chunk's scores over its
    whole 16384-row window 2.1 GB: read in blocks, the step keeps under
    0.1 GB of its own and the chunk under 0.25 GB."""
    mem = compiled[which].memory_analysis()
    rings = 5 * 65 * 16896 * 576 * 2
    assert rings == 6_325_862_400
    assert mem.alias_size_in_bytes >= rings
    assert 12.6e9 < mem.argument_size_in_bytes < 12.65e9
    assert mem.argument_size_in_bytes / 16e9 > 0.78
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM
    assert mem.temp_size_in_bytes < {"decode": 0.1e9, "prefill": 0.25e9}[
        which]


@pytest.mark.parametrize("which", ["decode", "prefill"])
def test_the_rings_are_read_in_blocks_where_they_lie(compiled, which):
    """No float32 array as long as a ring, no second array of the rings'
    size, and the block loop is there: a ``while`` in the step's program,
    none in the chunk's (PR 59: its blocks are walked inside the kernel)."""
    text = compiled[which].as_text()
    assert len(text) > 100_000, "read no program"
    assert not re.search(r"f32\[[\d,]*\b16896\b[\d,]*\]", text)
    assert not re.search(r"f32\[[\d,]*\b16384\b[\d,]*\]", text)
    assert not re.search(r"bf16\[65,16896,[\d,]*\]", text)  # a layer's rings
    assert bool(re.search(r" while\(", text)) == (which == "decode")


def test_the_chunk_attends_through_the_kernel(compiled, cfg):
    """PR 59: the chunk's attention is ONE custom call a layer of the
    kernel of ``ops/latent_chunk.py`` under scope ``attn``, handed the
    row's own slot of the layer's rings (one ring of 16896 rows copied out,
    its 576 columns in five lane tiles) and the layer's ``w_uk`` and
    ``w_uv``; a block's keys, values and scores live inside it, so the
    program holds no float32 scores of a block ([128, 256, 256] a group of
    queries before) and no decompressed block ([256, 128, 128])."""
    text = compiled["prefill"].as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line
             and "%latent_chunk_attention" in line]
    assert len(calls) == cfg.n_layer == 5
    for line in calls:
        assert re.search(r'op_name="[^"]*/attn/[^"]*latent_chunk_attention/'
                         r'pallas_call', line), line[-300:]
        handed = re.search(r"operand_layout_constraints=\{(.*?)\}, \w+=",
                           line).group(1)
        assert handed.count("bf16[16896,640]") == 1, handed
        assert handed.count("bf16[512,16384]") == 3, handed  # q, w_uk, w_uv
    assert "%latent_chunk_attention" not in compiled["decode"].as_text()
    assert not re.search(r"f32\[128,(256|512),(256|512)\]", text)
    assert not re.search(r"bf16\[256,128,128\]", text)


@pytest.mark.parametrize("which", ["decode", "prefill"])
def test_the_experts_run_through_the_kernel(compiled, cfg, which,
                                            experts_through_the_kernel):
    """PR 52: 65 rows a step, 512 a chunk, and in both programs the routed
    experts' two products are ONE custom call of the kernel of
    ``ops/moe_experts.py`` an expert layer (the first layer's feed-forward
    is dense), under scope ``experts``, handed the layer's 20 x 5120 x 3072
    and 20 x 1536 x 5120 stacks as they lie; no grouped product and no
    float32 copy of a stack."""
    assert (cfg.experts_held[1], cfg.d_model, cfg.expert_ff) \
        == (20, 5120, 1536)
    experts_through_the_kernel(compiled[which],
                               cfg.n_layer - cfg.first_dense, 20, 5120,
                               3072, 1536)


def test_the_chunk_keeps_the_rings_in_the_steps_unpadded_layout(compiled):
    """One layout of the stacked rings in both programs, ring rows
    minor-most (576 = 4.5 x 128 lanes would pad a row-major tile by a
    ninth: 0.7 GB), so neither program re-lays the cache out."""
    def layouts(which):
        # (a trailing S(n) names a memory space, not a layout; a size-one
        # axis may stand anywhere among the major ones)
        found = {re.sub(r"S\(\d+\)", "", f) for f in re.findall(
            RING + r"(\{[^}]*\})", compiled[which].as_text())}
        return {re.sub(r"^\{2,4,(?:3,1|1,3),0", "{2,4,*,0", f)
                for f in found}

    assert len(layouts("prefill")) == 1
    assert layouts("prefill") == layouts("decode")
    assert next(iter(layouts("decode"))).startswith("{2,4,")
