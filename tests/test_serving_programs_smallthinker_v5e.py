"""What the TPU's compiler makes of SmallThinker's two serving programs.

Compile-only, for one described v5e chip, at the published widths of
``benchmark/configs/smallthinker-21b-a3b-instruct.json`` and the shapes of
the cell ``serve_smallthinker_mixedwin_sat`` (48 slots and the scratch one,
TWO stacks of rings of merged rows of 512 columns in one donated pytree: the
two global layers' of 16384 rows and the six window layers' of 4096, 64
experts a layer all held, prompts of up to 14336 tokens in the engine's
[1, 512] chunks over a key window of 14336): nothing runs, so nothing here
is a time. It holds that both programs fit the chip beside their arguments
(12.33 GB of weights and cache), that the donated cache is updated in its
own buffers, that no program makes a float32 array as long as a ring or a
copy of a ring or a stack, that the step reads all eight layers' rings
through the kernel of ``ops/ring_decode.py``, each STACK handed whole with
its layer's index, that the chunk program writes each of the four stacks
once (the window stacks' write keeps the rows past the chunk's real tokens:
a row-sized read beside it, no more) and makes no other array that large,
and that it keeps both stacks in the step's layout, row-minor: XLA's
choices decide that, not the jaxpr.

The topology is described inside a fixture, in this one file: only the
worker that runs this file loads the TPU's library.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest

from conftest import arrays_made, made_as_large_as, nbytes, unfused
from ray_tpu.models import smallthinker as st
from ray_tpu.models.prefill import (chunk_len, key_window,
                                    token_parameters)
from served_families import FAMILIES

HBM = 15.75 * 2 ** 30
FULL = "bf16[2,49,16384,512]"
WIN = "bf16[6,49,4096,512]"


@pytest.fixture(scope="module")
def engine():
    return FAMILIES["smallthinker"].cell()[1]


@pytest.fixture(scope="module")
def cfg():
    return FAMILIES["smallthinker"].cell()[0]


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
        lambda: st.smallthinker_init(jax.random.PRNGKey(0), cfg)))
    chunk = chunk_len(  # as the engine derives it
        engine["max_prompt_len"], *token_parameters(cfg, params),
        cache_len=engine["cache_len"])
    window = key_window(engine["max_prompt_len"], chunk)
    assert (slots, chunk, window, engine["cache_len"]) \
        == (49, 512, 14336, 16384)
    cache = sds(jax.eval_shape(lambda: st.smallthinker_init_cache(
        cfg, slots, engine["cache_len"])))
    programs = {
        "decode": (lambda p, c, t, n: st.smallthinker_decode_step(
            p, c, t, n, cfg), (params, cache, i32(slots), i32(slots))),
        "prefill": (lambda p, c, t, s, at, n: st.smallthinker_prefill_chunk(
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
    """3.286 B bfloat16 parameters (6.57 GB) and 5.75 GB of cache are the
    arguments; the cache is aliased to the output, so it is held once."""
    mem = compiled[which].memory_analysis()
    cache_bytes = 2 * nbytes((2, 49, 16384, 512), 2) \
        + 2 * nbytes((6, 49, 4096, 512), 2) + 4
    assert cache_bytes == 49 * 117_440_512 + 4 == 5_754_585_092
    assert mem.alias_size_in_bytes >= cache_bytes
    gb = {k: getattr(mem, k + "_size_in_bytes") / 1e9
          for k in ("argument", "temp", "alias", "output")}
    print(which, gb)
    assert 12.32e9 < mem.argument_size_in_bytes < 12.35e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM, gb
    # the step holds the experts' [64, 49, 1536] product and no copy of a
    # ring. A chunk held its float32 scores over the 14336-row window of a
    # global layer (28 heads x 512 x 13824 x 4 B = 793 MB, and their
    # exponentials) and the rows cut out of the stacks, 1.09 GB in all,
    # until PR 64 (``ops/merged_chunk.py``: a block's scores in VMEM, the
    # stacks read as they lie); what is left is a window layer's scores
    # over 4608 keys and its ring cut out: 0.23 GB.
    assert mem.temp_size_in_bytes < {"decode": 0.2e9, "prefill": 0.4e9}[which]


RINGS = {nbytes((49, 16384, 512), 1),    # elements of a global layer's ring
         nbytes((49, 4096, 512), 1)}     # and of a window layer's
STACKS = {nbytes((2, 49, 16384, 512), 1), nbytes((6, 49, 4096, 512), 1)}


@pytest.mark.parametrize("which", ["decode", "prefill"])
def test_no_float32_array_as_long_as_a_ring_and_no_ring_is_copied(compiled,
                                                                  which):
    """A window layer's ring is 49 x 4096 x 512 bfloat16 (206 MB), a global
    layer's four times that. Neither program widens a ring to float32, and
    neither makes a copy of a ring or of a stack in any type."""
    text = compiled[which].as_text()
    made = list(arrays_made(unfused(text)))
    assert len(made) > 50, "read no program"
    assert [m for m in made if m[0] == "f32" and m[1] >= min(RINGS)] == []
    assert [m for m in arrays_made(text)
            if m[1] in RINGS | STACKS and m[2] == "copy"] == []


def test_the_chunk_writes_each_stack_once_and_makes_no_other_as_large(
        compiled):
    """K and V of the global stack are 1.64 GB each, of the window stack
    1.23 GB each. The chunk program reads a slot's old rows before it writes
    its own, so all it does to a stack is ONE row-sized
    ``dynamic-update-slice`` into the donated buffer, after the layer loop
    (the window stacks' update is the chunk's rows where they are real and
    the ring's own where they are not: a read of 256 rows a layer, no more):
    no fusion, copy or anything else, inside a fusion or outside, gives out
    an array as large as a stack or a ring."""
    made = made_as_large_as(compiled["prefill"].as_text(),
                            lambda n: n in RINGS | STACKS)
    assert sorted(op for op, _ in made) == ["dynamic-update-slice"] * 4, made
    assert len({stack for _, stack in made}) == 4, made


def test_the_chunks_global_layers_attend_through_the_kernel(
        compiled, chunk_attends_through_the_kernel):
    """PR 64: each of the two global layers' attention in the chunk program
    is ONE custom call of the kernel of ``ops/merged_chunk.py``, handed the
    global K and V STACKS as they lie; no ``dynamic-slice`` of the window's
    13,824 old rows out of either, and no float32 array over them (the XLA
    arm's scores were ``[28, 512, 13824]``, 793 MB a layer). The window
    layers keep ``wrapped_chunk_attention``."""
    chunk_attends_through_the_kernel(compiled["prefill"], 2,
                                     (2, 49, 16384, 512), 512, 14336)


def test_the_step_reads_its_rings_through_the_kernel_and_copies_none(
        compiled):
    """The decode attention of every layer, global or window, is ONE custom
    call of the kernel of ``ops/ring_decode.py`` (rings of 16384 and of 4096
    rows are whole blocks of 256, rows of 512 columns whole lane tiles),
    handed its kind's K and V STACKS as they lie and the layer's index in
    that stack: two calls on the global stacks, six on the window stacks.
    Nothing gives out an array of a ring's size or of a stack's but the
    row-sized writes into the donated stacks after the layer loop."""
    text = compiled["decode"].as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line
             and "%grouped_experts" not in line]  # the experts': PR 52
    assert len(calls) == 8
    handed = []
    for line in calls:
        assert "ring_decode_attention" in line
        operands = re.findall(r"(\w+\[[\d,]*\])", re.search(
            r"operand_layout_constraints=\{(.*?)\}, \w+=", line).group(1))
        handed.append((operands.count(FULL), operands.count(WIN)))
    assert sorted(handed) == [(0, 2)] * 6 + [(2, 0)] * 2, handed
    made = made_as_large_as(text, lambda n: n in RINGS | STACKS)
    assert {op for op, _ in made} == {"dynamic-update-slice"}, made
    assert len(made) % 4 == 0 and len(made) >= 4


@pytest.mark.parametrize("which", ["decode", "prefill"])
def test_the_experts_run_through_the_kernel(compiled, cfg, which,
                                            experts_through_the_kernel):
    """PR 52: in both of the engine's programs the gated experts' two
    products are ONE custom call of the kernel of ``ops/moe_experts.py`` a
    layer, under scope ``experts``, handed the layer's 64 x 2560 x 1536 and 64 x 768 x 2560
    stacks as they lie; no grouped product, no float32 copy of a stack."""
    assert (cfg.d_model, cfg.expert_ff) == (2560, 768)
    experts_through_the_kernel(compiled[which], cfg.n_layer, 64, 2560,
                               1536, 768)


def test_the_chunk_keeps_the_cache_in_the_steps_layout(compiled):
    """Both stacks: each shape has one layout as a whole array in the chunk
    program, and it is the decode program's, so neither is re-laid out
    between the two; the rings are row-minor (a merged row of 512 columns
    is four whole lane tiles)."""
    def layouts(shape, which):
        # (a trailing S(n) names a memory space, not a layout)
        # (nor is what the kernel's custom call asks of its operands,
        # ``operand_layout_constraints``: an order of dimensions, no tiling)
        text = re.sub(r"operand_layout_constraints=\{[^=]*\}, ", "",
                      compiled[which].as_text())
        return {re.sub(r"S\(\d+\)", "", found) for found in re.findall(
            re.escape(shape) + r"(\{[^}]*\})", text)}

    for shape in (FULL, WIN):
        assert len(layouts(shape, "prefill")) == 1, shape
        assert layouts(shape, "prefill") == layouts(shape, "decode"), shape
        assert all(found.startswith("{3,2,1,0")
                   for found in layouts(shape, "decode")), shape
