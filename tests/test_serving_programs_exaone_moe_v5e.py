"""What the TPU's compiler makes of K-EXAONE's two serving programs.

Compile-only, for one described v5e chip, at the published widths of
``benchmark/configs/k-exaone-236b-a23b.json`` and the shapes of the cell
``serve_kexaone_selfdraft_sat`` (64 slots and the scratch one, TWO stacks of
rings of merged rows of 1024 columns in one donated pytree: the global
layer's and the prediction module's of 8192 rows and the four window layers'
of 128, 16 of 128 experts held in each of four sparse layers, prompts of up
to 4096 tokens in the engine's [1, 512] chunks over a key window of 4096):
nothing runs, so nothing here is a time. The decode program is the
VERIFY-AND-DRAFT step, two rows a slot. It holds that both programs fit the
chip beside their arguments (12.98 GB of weights and cache), that the
donated cache is updated in its own buffers, that no program makes a
float32 array as long as a full ring or a copy of one, that the step reads
both full rings through the kernel of ``ops/ring_decode.py`` (its two query
rows' heads stacked, the stack handed whole with the layer's index) and the
128-row window rings in XLA, that the experts run through their kernel once
a sparse layer in both programs, and that the chunk program keeps both
stacks in the step's layout, row-minor.

The topology is described inside a fixture, in this one file: only the
worker that runs this file loads the TPU's library.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest

from conftest import arrays_made, nbytes, unfused
from ray_tpu.models import exaone_moe as ex
from ray_tpu.models.prefill import (chunk_len, key_window,
                                    token_parameters)
from served_families import FAMILIES

HBM = 15.75 * 2 ** 30
FULL = "bf16[2,65,8192,1024]"
WIN = "bf16[4,65,128,1024]"


@pytest.fixture(scope="module")
def engine():
    return FAMILIES["exaone_moe"].cell()[1]


@pytest.fixture(scope="module")
def cfg():
    return FAMILIES["exaone_moe"].cell()[0]


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
    """Both programs as the engine jits them (cache donated; the step's
    served tokens and counters out, the chunk's two greedy tokens),
    compiled once for the module, with the persistent cache out of the
    way: such a compile is written to it but cannot be read back without a
    chip."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    def sds(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    slots = engine["max_batch"] + 1
    params = sds(jax.eval_shape(
        lambda: ex.exaone_moe_init(jax.random.PRNGKey(0), cfg)))
    chunk = chunk_len(  # as the engine derives it
        engine["max_prompt_len"], *token_parameters(cfg, params),
        cache_len=engine["cache_len"])
    window = key_window(engine["max_prompt_len"], chunk)
    assert (slots, chunk, window, engine["cache_len"]) \
        == (65, 512, 4096, 8192)
    cache = sds(jax.eval_shape(lambda: ex.exaone_moe_init_cache(
        cfg, slots, engine["cache_len"])))

    def verify(p, c, t, n):
        _, c, counted, served, _ = ex.exaone_moe_verify_step(p, c, t, n, cfg)
        return served, c, counted

    def prefill(p, c, t, s, at, n, f):
        logits, c, drafts = ex.exaone_moe_prefill_chunk(
            p, c, t, s, at, n, cfg, window=window, follows=f)
        return jnp.argmax(jnp.concatenate([logits, drafts]), -1), c

    programs = {
        "decode": (verify, (params, cache, i32(slots, 2), i32(slots))),
        "prefill": (prefill, (params, cache, i32(1, chunk), i32(1), i32(1),
                              i32(1), i32(1))),
    }
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        # the kernels pick interpret mode from the process's backend, the
        # CPU here: while the programs are traced it says the chip's, so
        # the step holds its kernels, not the interpreter's loops
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(jax, "default_backend", lambda: "tpu")
            return {name: jax.jit(fn, donate_argnums=(1,)).lower(
                *args).compile() for name, (fn, args) in programs.items()}
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


def test_the_chunks_global_layers_attend_through_the_kernel(
        compiled, chunk_attends_through_the_kernel):
    """PR 64: the global layer's attention and the prediction module's in
    the chunk program are ONE custom call each of the kernel of
    ``ops/merged_chunk.py``, handed the global K and V STACKS as they lie;
    no ``dynamic-slice`` of the window's 3,584 old rows out of either, and
    no float32 array over them (the XLA arm's scores were ``[64, 512,
    3584]``, 470 MB a layer). The window layers keep
    ``wrapped_chunk_attention``."""
    chunk_attends_through_the_kernel(compiled["prefill"], 2,
                                     (2, 65, 8192, 1024), 512, 4096)


@pytest.mark.parametrize("which", ["decode", "prefill"])
def test_the_program_fits_the_chip_beside_its_arguments(compiled, cfg,
                                                        which):
    """4.24 B bfloat16 parameters (8.48 GB) and 4.50 GB of cache are the
    arguments; the cache is aliased to the output, so it is held once."""
    mem = compiled[which].memory_analysis()
    cache_bytes = 2 * nbytes((2, 65, 8192, 1024), 2) \
        + 2 * nbytes((4, 65, 128, 1024), 2) + 4
    assert cache_bytes == 65 * 69_206_016 + 4 == 4_498_391_044
    assert mem.alias_size_in_bytes >= cache_bytes
    gb = {k: getattr(mem, k + "_size_in_bytes") / 1e9
          for k in ("argument", "temp", "alias", "output")}
    print(which, gb)
    assert 12.97e9 < mem.argument_size_in_bytes < 12.99e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM, gb
    # the step holds 130 rows' products and no copy of a ring (0.12 GB); a
    # chunk held its float32 scores over the 4096-row window of the global
    # layer and of the module (64 heads x 512 x 3584 x 4 B = 470 MB, and
    # their exponentials) and the rows cut out of the stacks, 0.76 GB,
    # until PR 64 (``ops/merged_chunk.py``: a block's scores in VMEM, the
    # stacks read as they lie): 0.19 GB
    assert mem.temp_size_in_bytes < {"decode": 0.25e9, "prefill": 0.4e9}[which]


RING = nbytes((65, 8192, 1024), 1)     # elements of a full ring
STACK = nbytes((2, 65, 8192, 1024), 1)


@pytest.mark.parametrize("which", ["decode", "prefill"])
def test_no_float32_array_as_long_as_a_ring_and_no_ring_is_copied(compiled,
                                                                  which):
    """A full ring is 65 x 8192 x 1024 bfloat16 (1.09 GB). Neither program
    widens one to float32, and neither makes a copy of a ring or of the
    stack in any type."""
    text = compiled[which].as_text()
    made = list(arrays_made(unfused(text)))
    assert len(made) > 50, "read no program"
    assert [m for m in made if m[0] == "f32" and m[1] >= RING] == []
    assert [m for m in arrays_made(text)
            if m[1] in (RING, STACK) and m[2] == "copy"] == []


def test_the_step_reads_the_full_rings_through_the_kernel(compiled):
    """The verify step's attention over the global layer's ring and over
    the module's is ONE custom call of the kernel of ``ops/ring_decode.py``
    each (rings of 8192 rows are whole blocks of 256, rows of 1024 columns
    whole lane tiles), handed the K and V STACKS as they lie, the layer's
    index and BOTH query rows' heads stacked (128 rows of queries a slot);
    the four window layers' rings of 128 rows are no whole block and are
    read in XLA."""
    text = compiled["decode"].as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line
             and "%grouped_experts" not in line]
    assert len(calls) == 2
    for line in calls:
        assert "ring_decode_attention" in line
        operands = re.findall(r"(\w+\[[\d,]*\])", re.search(
            r"operand_layout_constraints=\{(.*?)\}, \w+=", line).group(1))
        assert operands.count(FULL) == 2 and operands.count(WIN) == 0
        assert "bf16[65,128,1024]" in operands   # two rows x 64 heads
        assert operands.count("bf16[65,2,1024]") == 2  # the new K, V rows
    assert sum("/mtp/attn/attn_global/" in line for line in calls) == 1
    assert sum("/verify/attn/attn_global/" in line for line in calls) == 1


@pytest.mark.parametrize("which", ["decode", "prefill"])
def test_the_experts_run_through_the_kernel(compiled, cfg, which,
                                            experts_through_the_kernel):
    """In both programs the gated experts' two products are ONE custom call
    of the kernel of ``ops/moe_experts.py`` a sparse layer, handed the
    layer's 16 x 6144 x 4096 and 16 x 2048 x 6144 stacks as they lie."""
    assert (cfg.d_model, cfg.expert_ff, cfg.n_held) == (6144, 2048, 16)
    experts_through_the_kernel(compiled[which], cfg.n_layer - 1, 16, 6144,
                               4096, 2048)


def test_the_chunk_keeps_the_cache_in_the_steps_layout(compiled):
    """Both stacks: each shape has one layout as a whole array in the chunk
    program, and it is the verify step's, so neither is re-laid out between
    the two; the rings are row-minor (a merged row of 1024 columns is eight
    whole lane tiles)."""
    def layouts(shape, which):
        text = re.sub(r"operand_layout_constraints=\{[^=]*\}, ", "",
                      compiled[which].as_text())
        return {re.sub(r"S\(\d+\)", "", found) for found in re.findall(
            re.escape(shape) + r"(\{[^}]*\})", text)}

    for shape in (FULL, WIN):
        assert len(layouts(shape, "prefill")) == 1, shape
        assert layouts(shape, "prefill") == layouts(shape, "decode"), shape
        assert all(found.startswith("{3,2,1,0")
                   for found in layouts(shape, "decode")), shape
