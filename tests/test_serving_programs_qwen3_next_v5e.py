"""What the TPU's compiler makes of Qwen3-Next's two serving programs.

Compile-only, for one described v5e chip, at the published widths of
``benchmark/configs/qwen3-next-80b-a3b-instruct.json`` and the shapes of the
cell ``serve_qwen3next_mixedctx_sat`` (64 slots and the scratch one, a
float32 delta state [65, 32, 128, 128] in six of eight layers beside rings
of 18432 merged rows in the other two, 128 held experts a layer, prompts of
up to 16384 tokens in the engine's [1, 512] chunks over a key window of
16384): nothing runs, so nothing here is a time. It holds that both programs
fit the chip beside their arguments (13.08 GB of weights and cache), that
the donated cache is updated in its own buffers, that no program makes a
float32 array as long as a ring or copies a layer's delta state, that the
chunk's full layers attend through the kernel of ``ops/merged_chunk.py``
(PR 64: no window of old rows cut out of a stack, no float32 scores over
it), that the step re-lays no ring out (a token's two K/V heads of 256 lanes merged in one
row of 512 columns, four whole lane tiles, read as they lie), that the chunk
program writes each stack once and makes no other array that large, and
that it keeps the cache in the step's layout: XLA's choices decide that, not
the jaxpr.

The topology is described inside a fixture, in this one file: only the
worker that runs this file loads the TPU's library.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest

from conftest import arrays_made, made_as_large_as, nbytes, unfused
from ray_tpu.models import qwen3_next as qn
from ray_tpu.models.prefill import (chunk_len, key_window,
                                    token_parameters)
from served_families import FAMILIES

HBM = 15.75 * 2 ** 30


@pytest.fixture(scope="module")
def engine():
    return FAMILIES["qwen3_next"].cell()[1]


@pytest.fixture(scope="module")
def cfg():
    return FAMILIES["qwen3_next"].cell()[0]


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
        lambda: qn.qwen3_next_init(jax.random.PRNGKey(0), cfg)))
    chunk = chunk_len(  # as the engine derives it
        engine["max_prompt_len"], *token_parameters(cfg, params),
        cache_len=engine["cache_len"])
    window = key_window(engine["max_prompt_len"], chunk)
    assert (slots, chunk, window, engine["cache_len"]) \
        == (65, 512, 16384, 18432)
    cache = sds(jax.eval_shape(lambda: qn.qwen3_next_init_cache(
        cfg, slots, engine["cache_len"])))
    programs = {
        "decode": (lambda p, c, t, n: qn.qwen3_next_decode_step(
            p, c, t, n, cfg), (params, cache, i32(slots), i32(slots))),
        "prefill": (lambda p, c, t, s, at, n: qn.qwen3_next_prefill_chunk(
            p, c, t, s, at, n, cfg, window=window),
            (params, cache, i32(1, chunk), i32(1), i32(1), i32(1))),
    }
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        # the kernels pick interpret mode from the process's backend, the
        # CPU here: while the programs are traced it says the chip's, so
        # the step holds its kernel (PR 48), not the interpreter's loops
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
    """3.667 B bfloat16 parameters (7.33 GB) and 5.74 GB of cache are the
    arguments; the cache is aliased to the output, so it is held once."""
    mem = compiled[which].memory_analysis()
    cache_bytes = 2 * nbytes((2, 65, 18432, 512), 2) \
        + nbytes((6, 3, 65, cfg.delta.conv_dim), 2) \
        + 6 * nbytes((65, 32, 128, 128), 4) + 4
    assert cache_bytes == 65 * (6 * 2_146_304 + 18432 * 4096) + 4 \
        == 5_744_394_244
    assert mem.alias_size_in_bytes >= cache_bytes
    gb = {k: getattr(mem, k + "_size_in_bytes") / 1e9
          for k in ("argument", "temp", "alias", "output")}
    print(which, gb)
    assert 13.07e9 < mem.argument_size_in_bytes < 13.10e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM, gb
    # the step holds its float32 scores over 65 rings of 18432 rows a head
    # (77 MB a layer) and the experts' [128, 65, 1024] product, and no copy
    # of a ring or a state: 0.05 GB. A chunk held its 512 queries'
    # scores over the 16384-row window (537 MB in float32 a full layer) and
    # both stacks' old rows cut out, 0.62 GB, until PR 64: the kernel of
    # ``ops/merged_chunk.py`` keeps a block's scores in VMEM and reads the
    # stacks as they lie, and the chunk's temp is 0.07 GB.
    assert mem.temp_size_in_bytes < {"decode": 0.2e9,
                                     "prefill": 0.2e9}[which]


RING = nbytes((65, 18432, 512), 1)       # elements of a layer's K or V ring
STATE = nbytes((65, 32, 128, 128), 1)    # elements of a layer's delta state


@pytest.mark.parametrize("which", ["decode", "prefill"])
def test_no_float32_array_as_long_as_a_ring_and_no_state_is_copied(compiled,
                                                                   which):
    """A full layer's ring is 65 x 18432 x 512 bfloat16 (1.23 GB), a linear
    layer's state 65 x 32 x 128 x 128 float32 (136 MB). Neither program
    widens a ring to float32 (2.45 GB a layer: it would not fit), and
    neither copies a state: it is rewritten inside its donated buffer. Nor
    does either make a copy of a ring in any type."""
    text = compiled[which].as_text()
    made = list(arrays_made(unfused(text)))
    assert len(made) > 50, "read no program"
    assert [m for m in made if m[0] == "f32" and m[1] >= RING] == []
    assert [m for m in made if m[1] in (STATE, 6 * STATE)] == []
    # (inside fusions too: a fusion whose root is a copy writes it out)
    assert [m for m in arrays_made(text)
            if m[1] == RING and m[2] == "copy"] == []


STACK = 2 * RING                         # elements of the whole K or V stack


def test_the_chunk_writes_each_stack_once_and_makes_no_other_as_large(
        compiled):
    """The K stack and the V stack are 2.45 GB each. The chunk program
    reads the slot's old rows before it writes its own, so all it does to a
    stack is ONE row-sized ``dynamic-update-slice`` into the donated
    buffer, after the layer loop: no fusion, copy or anything else, inside
    a fusion or outside, gives out an array that large (a tuple's members
    counted each)."""
    made = made_as_large_as(compiled["prefill"].as_text(),
                            lambda n: n >= STACK)
    assert sorted(op for op, _ in made) == ["dynamic-update-slice"] * 2, made
    assert len({stack for _, stack in made}) == 2, made


def test_the_chunks_full_layers_attend_through_the_kernel(
        compiled, chunk_attends_through_the_kernel):
    """PR 64: each of the two full layers' attention in the chunk program
    is ONE custom call of the kernel of ``ops/merged_chunk.py``, handed the
    K and V STACKS as they lie; no ``dynamic-slice`` of the window's 15,872
    old rows out of either stack, and no float32 array over them (the XLA
    arm's scores were ``[16, 512, 15872]``, 520 MB a layer)."""
    chunk_attends_through_the_kernel(compiled["prefill"], 2,
                                     (2, 65, 18432, 512), 512, 16384)


def test_the_step_reads_its_rings_through_the_kernel_and_copies_none(
        compiled):
    """PR 48: the decode attention of every ring-holding layer is ONE
    custom call of the kernel of ``ops/ring_decode.py``, handed the K and V
    STACKS as they lie and the layer's index. Nothing, inside a fusion or
    outside, gives out an array of a layer's ring's size or of a stack's
    but the row-sized writes into the donated stacks after the layer loop
    (a slot's row a write, each into the buffer the last one left): a
    layer's slice handed to the kernel would be copied out first, a whole
    layer's rings a layer, the traffic the kernel is there to save
    (``ring_decode_attention``'s operand IS the stack)."""
    text = compiled["decode"].as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line
             and "%grouped_experts" not in line]  # the experts': PR 52
    assert len(calls) == 2
    for line in calls:
        assert "ring_decode_attention" in line
        operands = re.findall(r"(\w+\[[\d,]*\])", re.search(
            r"operand_layout_constraints=\{(.*?)\}, \w+=", line).group(1))
        assert operands.count("bf16[2,65,18432,512]") == 2, operands
    made = made_as_large_as(text,
                            lambda n: n in (RING, STACK))
    assert {op for op, _ in made} == {"dynamic-update-slice"}, made
    assert len(made) % 2 == 0 and len(made) >= 2


@pytest.mark.parametrize("which", ["decode", "prefill"])
def test_the_experts_run_through_the_kernel(compiled, cfg, which,
                                            experts_through_the_kernel):
    """PR 52: in both of the engine's programs the gated experts' two
    products are ONE custom call of the kernel of ``ops/moe_experts.py`` a
    layer, under scope ``experts``, handed the layer's 128 x 2048 x 1024 and 128 x 512 x 2048
    stacks as they lie; no grouped product, no float32 copy of a stack."""
    assert (cfg.d_model, cfg.expert_ff) == (2048, 512)
    experts_through_the_kernel(compiled[which], cfg.n_layer, 128, 2048,
                               1024, 512)


def test_the_chunk_keeps_the_cache_in_the_steps_layout(compiled):
    """The stacked K/V rings and a layer's delta state (5.72 of the
    cache's 5.74 GB): each shape has one layout as a whole array in the
    chunk program, and it is the decode program's, so neither is re-laid
    out between the two; the rings are row-minor (a merged row of 512
    columns is four whole lane tiles)."""
    def layouts(shape, which):
        # (a trailing S(n) names a memory space, not a layout)
        # (nor is what the kernel's custom call asks of its operands,
        # ``operand_layout_constraints``: an order of dimensions, no tiling)
        text = re.sub(r"operand_layout_constraints=\{[^=]*\}, ", "",
                      compiled[which].as_text())
        return {re.sub(r"S\(\d+\)", "", found) for found in re.findall(
            shape + r"(\{[^}]*\})", text)}

    for shape in (r"bf16\[2,65,18432,512\]", r"f32\[65,32,128,128\]"):
        assert len(layouts(shape, "prefill")) == 1, shape
        assert layouts(shape, "prefill") == layouts(shape, "decode"), shape
    assert all(found.startswith("{3,2,1,0")
               for found in layouts(r"bf16\[2,65,18432,512\]", "decode"))
