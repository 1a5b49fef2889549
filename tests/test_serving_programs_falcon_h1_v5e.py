"""What the TPU's compiler makes of Falcon-H1's two serving programs
(PR 43; the rings as merged rows, PR 44).

Compile-only, for one described v5e chip, at the published widths of
``benchmark/configs/falcon-h1-34b-instruct.json`` and the shapes of the cell
``serve_falconh1_longgen_sat`` (32 slots and the scratch one, rings of 5120
rows in EVERY one of the nine layers beside a float32 state, prompts of up
to 4096 tokens in the engine's [1, 256] chunks over a key window of 4096):
nothing runs, so nothing here is a time. It holds that both programs fit the
chip beside their arguments (the decode step's float32 pass over 33 K/V
windows in nine layers included), that the donated cache is updated in its
own buffers, that no program makes a float32 array as long as a ring or
copies a layer's state, that the step re-lays no ring out (the rings hold a
token's K/V heads merged in one row of 512 columns, four whole lane tiles,
and both products read them as they lie), that the chunk program writes
each stack once and makes no other array that large, and that it keeps the
cache in the step's layout: XLA's choices decide that, not the jaxpr.

The topology is described inside a fixture, in this one file: only the
worker that runs this file loads the TPU's library.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest

from conftest import arrays_made, made_as_large_as, nbytes, unfused
from ray_tpu.models import falcon_h1 as fh
from ray_tpu.models.prefill import (chunk_len, key_window,
                                    token_parameters)
from served_families import FAMILIES

HBM = 15.75 * 2 ** 30


@pytest.fixture(scope="module")
def engine():
    return FAMILIES["falcon_h1"].cell()[1]


@pytest.fixture(scope="module")
def cfg():
    return FAMILIES["falcon_h1"].cell()[0]


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
        lambda: fh.falcon_h1_init(jax.random.PRNGKey(0), cfg)))
    chunk = chunk_len(  # as the engine derives it
        engine["max_prompt_len"], *token_parameters(cfg, params),
        cache_len=engine["cache_len"])
    window = key_window(engine["max_prompt_len"], chunk)
    assert (slots, chunk, window, engine["cache_len"]) \
        == (33, 256, 4096, 5120)
    cache = sds(jax.eval_shape(lambda: fh.falcon_h1_init_cache(
        cfg, slots, engine["cache_len"])))
    programs = {
        "decode": (lambda p, c, t, n: fh.falcon_h1_decode_step(
            p, c, t, n, cfg), (params, cache, i32(slots), i32(slots))),
        "prefill": (lambda p, c, t, s, at, n: fh.falcon_h1_prefill_chunk(
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


def test_the_chunks_layers_attend_through_the_kernel(
        compiled, chunk_attends_through_the_kernel):
    """PR 64: every one of the nine layers' attention in the chunk program
    is ONE custom call of the kernel of ``ops/merged_chunk.py``, handed the
    K and V STACKS as they lie; no ``dynamic-slice`` of the window's 3,840
    old rows out of either, and no float32 array over them (the XLA arm's
    scores were ``[20, 256, 3840]``, 79 MB a layer)."""
    chunk_attends_through_the_kernel(compiled["prefill"], 9,
                                     (9, 33, 5120, 512), 256, 4096)


@pytest.mark.parametrize("which", ["decode", "prefill"])
def test_the_program_fits_the_chip_beside_its_arguments(compiled, cfg,
                                                        which):
    """4.205 B bfloat16 parameters (8.41 GB) and 4.37 GB of cache are the
    arguments; the cache is aliased to the output, so it is held once."""
    mem = compiled[which].memory_analysis()
    cache_bytes = 2 * nbytes((9, 33, 5120, 512), 2) \
        + nbytes((9, 3, 33, cfg.mamba.conv_dim), 2) \
        + nbytes((9, 33, 32, 128, 256), 4) + 4
    assert cache_bytes == 33 * 9 * 14_710_784 + 4 == 4_369_102_852
    assert mem.alias_size_in_bytes >= cache_bytes
    gb = {k: getattr(mem, k + "_size_in_bytes") / 1e9
          for k in ("argument", "temp", "alias", "output")}
    print(which, gb)
    assert 12.77e9 < mem.argument_size_in_bytes < 12.80e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM, gb
    # a chunk held its scores over the 4096-row window (84 MB in float32 a
    # layer) and the nine layers' old rows cut out of both stacks at once,
    # 0.75 GB, until PR 64 (``ops/merged_chunk.py``); without them it holds
    # 0.68 GB (whose, this file does not say). The step holds 0.04 GB. It held 2.8 GB while the
    # rings were [row, K/V head, head_dim] and the compiler re-laid every
    # layer's K and V window out for the grouped products, eighteen copies
    # of 173 MB alive side by side: that debt (PERF.md section 7, PR 43) is
    # paid, PR 44.
    assert mem.temp_size_in_bytes < {"decode": 0.3e9, "prefill": 0.75e9}[which]


RING = nbytes((33, 5120, 512), 1)        # elements of a layer's K or V ring
STATE = nbytes((33, 32, 128, 256), 1)    # elements of a layer's SSM state


@pytest.mark.parametrize("which", ["decode", "prefill"])
def test_no_float32_array_as_long_as_a_ring_and_no_state_is_copied(compiled,
                                                                   which):
    """A layer's ring is 33 x 5120 x 4 x 128 bfloat16 (173 MB), its state
    33 x 32 x 128 x 256 float32 (138 MB). Neither program widens a ring to
    float32 (346 MB a layer: the step's float32 scores are over 20 heads x
    5120 rows, 13.5 MB), and neither copies a state: it is rewritten inside
    its donated buffer. Nor does either make a copy of a ring in any type:
    the step made eighteen, each layer's K and V window with rows and heads
    swapped, until the rings held merged rows (PR 44)."""
    text = compiled[which].as_text()
    made = list(arrays_made(unfused(text)))
    assert len(made) > 50, "read no program"
    assert [m for m in made if m[0] == "f32" and m[1] >= RING] == []
    assert [m for m in made if m[1] in (STATE, 9 * STATE)] == []
    # (inside fusions too: a fusion whose root is a copy writes it out)
    assert [m for m in arrays_made(text)
            if m[1] == RING and m[2] == "copy"] == []


STACK = 9 * RING                         # elements of the whole K or V stack


def test_the_chunk_writes_each_stack_once_and_makes_no_other_as_large(
        compiled):
    """The K stack and the V stack are 1.56 GB each. The chunk program
    read the slot's old rows before it writes its own, so all it does to a
    stack is ONE row-sized ``dynamic-update-slice`` into the donated
    buffer, after the layer loop: no fusion, copy or anything else, inside
    a fusion or outside, gives out an array that large (a tuple's members
    counted each). While the write stood inside the loop, two fusions each
    gave a whole stack out anew, 14 of a chunk's 26.5 ms (PERF.md section
    6, PR 43)."""
    made = made_as_large_as(compiled["prefill"].as_text(),
                            lambda n: n >= STACK)
    assert sorted(op for op, _ in made) == ["dynamic-update-slice"] * 2, made
    assert len({stack for _, stack in made}) == 2, made


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
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 9
    for line in calls:
        assert "ring_decode_attention" in line
        operands = re.findall(r"(\w+\[[\d,]*\])", re.search(
            r"operand_layout_constraints=\{(.*?)\}, \w+=", line).group(1))
        assert operands.count("bf16[9,33,5120,512]") == 2, operands
    made = made_as_large_as(text,
                            lambda n: n in (RING, STACK))
    assert {op for op, _ in made} == {"dynamic-update-slice"}, made
    assert len(made) % 2 == 0 and len(made) >= 2


def test_the_chunk_keeps_the_cache_in_the_steps_layout(compiled):
    """The stacked K/V rings and a layer's SSM state (4.36 of the cache's
    4.37 GB): each shape has one layout as a whole array in the chunk
    program, and it is the decode program's, so neither is re-laid out
    between the two."""
    def layouts(shape, which):
        # (a trailing S(n) names a memory space, not a layout)
        # (nor is what the kernel's custom call asks of its operands,
        # ``operand_layout_constraints``: an order of dimensions, no tiling)
        text = re.sub(r"operand_layout_constraints=\{[^=]*\}, ", "",
                      compiled[which].as_text())
        return {re.sub(r"S\(\d+\)", "", found) for found in re.findall(
            shape + r"(\{[^}]*\})", text)}

    for shape in (r"bf16\[9,33,5120,512\]", r"f32\[33,32,128,256\]"):
        assert len(layouts(shape, "prefill")) == 1, shape
        assert layouts(shape, "prefill") == layouts(shape, "decode"), shape
