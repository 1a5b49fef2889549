"""What the TPU's compiler makes of Keye-VL-2.0's two serving programs.

Compile-only, for one described v5e chip, at the published widths of
``benchmark/configs/keye-vl-2.0-30b-a3b.json`` and the shapes of the cell
``serve_keyevl2_sparsectx_sat`` (16 slots and the scratch one, TWO stacks
of rings of 33792 rows in one donated pytree: K/V rows of 1024 columns, a
token's merged K row and V row side by side, and the indexer's keys of 64;
16 of 128 experts a layer held, prompts of up to 32768 tokens in the
engine's [1, 512] chunks over a key window of 32768): nothing runs, so
nothing here is a time. It holds that both programs fit the chip beside
their arguments (11.7 GB of weights and cache) with less than 2 GB of
temporaries, that the donated cache is updated in its own buffers, that
neither program copies the K/V stack or a ring of it or widens one to
float32, that each writes each stack once, that the step GATHERS its picked
rows out of the K/V stack (one gather a layer, no slice of a ring as long
as a context), that the chunk program picks through the kernel of
``ops/sparse_pick.py``, handed the slot's indexer keys cut out of their
stack and never the stack, and attends through the kernel of
``ops/sparse_chunk.py``, handed the K/V stack as it lies, with no
``conditional`` over key windows left (PR 61), that no approximate top-k
and no sort is on either path, and that the K/V stack keeps one row-minor
layout in both programs.

The topology is described inside a fixture, in this one file: only the
worker that runs this file loads the TPU's library.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest

from conftest import arrays_made, made_as_large_as, nbytes, unfused
from ray_tpu.models import keye_vl2 as kv
from ray_tpu.models.prefill import (chunk_len, key_window,
                                    token_parameters)
from served_families import FAMILIES

HBM = 15.75 * 2 ** 30
KV = "bf16[8,17,33792,1024]"
IDX = "bf16[8,17,33792,64]"


@pytest.fixture(scope="module")
def engine():
    return FAMILIES["keye_vl2"].cell()[1]


@pytest.fixture(scope="module")
def cfg():
    return FAMILIES["keye_vl2"].cell()[0]


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
        lambda: kv.keye_vl2_init(jax.random.PRNGKey(0), cfg)))
    chunk = chunk_len(  # as the engine derives it
        engine["max_prompt_len"], *token_parameters(cfg, params),
        cache_len=engine["cache_len"])
    window = key_window(engine["max_prompt_len"], chunk)
    assert (slots, chunk, window, engine["cache_len"]) \
        == (17, 512, 32768, 33792)
    cache = sds(jax.eval_shape(lambda: kv.keye_vl2_init_cache(
        cfg, slots, engine["cache_len"])))
    programs = {
        "decode": (lambda p, c, t, n: kv.keye_vl2_decode_step(
            p, c, t, n, cfg), (params, cache, i32(slots), i32(slots))),
        "prefill": (lambda p, c, t, s, at, n: kv.keye_vl2_prefill_chunk(
            p, c, t, s, at, n, cfg, window=window),
            (params, cache, i32(1, chunk), i32(1), i32(1), i32(1))),
    }
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        # the kernels pick interpret mode from the process's backend, the
        # CPU here: while the programs are traced it says the chip's, so
        # the programs hold the experts' kernel, not the interpreter's loops
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
    """852,988,928 bfloat16 parameters (1.71 GB) and 10.00 GB of cache are
    the arguments; the cache is aliased to the output, so it is held once;
    the temporaries stay under 2 GB, and with the two kernels under 0.4
    (neither the index scores nor the attention's leave VMEM: what is left,
    0.25 GB, is the eight cuts of the slot's indexer keys, made together,
    and a layer's bias)."""
    mem = compiled[which].memory_analysis()
    cache_bytes = nbytes((8, 17, 33792, 1024), 2) \
        + nbytes((8, 17, 33792, 64), 2) + 4
    assert cache_bytes == 17 * 8 * 33792 * 2176 + 4 == 10_000_269_316
    assert mem.alias_size_in_bytes >= cache_bytes
    gb = {k: getattr(mem, k + "_size_in_bytes") / 1e9
          for k in ("argument", "temp", "alias", "output")}
    print(which, gb)
    assert 11.70e9 < mem.argument_size_in_bytes < 11.72e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM, gb
    assert mem.temp_size_in_bytes < {"decode": 0.5e9, "prefill": 0.4e9}[which]


RINGS = {nbytes((17, 33792, 1024), 1)}
STACKS = {nbytes((8, 17, 33792, 1024), 1), nbytes((8, 17, 33792, 64), 1)}
IDX_RING = nbytes((17, 33792, 64), 1)


@pytest.mark.parametrize("which", ["decode", "prefill"])
def test_no_ring_is_copied_or_widened(compiled, which):
    """A layer's K/V rings are 17 x 33792 x 1024 bfloat16 (1.18 GB).
    Neither program widens them to float32, and neither makes a copy of
    them or of a stack in any type."""
    text = compiled[which].as_text()
    made = list(arrays_made(unfused(text)))
    assert len(made) > 50, "read no program"
    assert [m for m in made if m[0] == "f32"
            and m[1] >= nbytes((17, 33792, 512), 1)] == []
    assert [m for m in arrays_made(text)
            if m[1] in RINGS | STACKS and m[2] == "copy"] == []


@pytest.mark.parametrize("which", ["decode", "prefill"])
def test_each_stack_is_written_once_and_nothing_else_is_as_large(compiled,
                                                                 which):
    """The K/V stack is 9.41 GB, the indexer's keys 0.59 GB. Both programs
    read the rings before they write their own rows, so all either does to
    a stack is row-sized ``dynamic-update-slice``s into the donated buffer
    after the layer loop (one a slot in the step, one in the chunk): no
    fusion, copy or anything else gives out an array as large as a stack or
    as a layer's K/V rings; in particular neither kernel of the chunk
    program has a stack re-laid for it. (The step cuts a layer's
    indexer keys out of their stack for its scores, 73 MB a layer: PERF.md
    section 7 has it among what is left on the table.)"""
    text = compiled[which].as_text()
    made = made_as_large_as(text, lambda n: n in RINGS | STACKS)
    assert {op for op, _ in made} == {"dynamic-update-slice"}, made
    writes = 17 if which == "decode" else 1
    assert len(made) == 2 * writes, made
    cut = [op for op, _ in made_as_large_as(text, lambda n: n == IDX_RING)]
    assert len(cut) <= (16 if which == "decode" else 0), cut


def _no_sort_under_attention(text):
    """The router's top 8 of 128 and the experts' dispatch sort (128
    numbers a token, the step's pairs by expert); nothing under ``attn``
    sorts, and nothing anywhere takes an approximate top-k."""
    sorts = [re.search(r'op_name="([^"]*)"', line).group(1)
             for line in text.splitlines()
             if re.search(r" sort\(|TopK|top_k", line)
             and "op_name=" in line]
    assert sorts, "read no program"
    assert [name for name in sorts if "/attn/" in name] == []
    assert "approx" not in text.lower()


def test_the_step_gathers_its_rows_and_sorts_nothing(compiled):
    """The step's attention reads ``topk`` rows a slot a layer: ONE gather
    a layer out of the K/V stack as a table of rows, giving 17 x 2048 rows
    of 1024 columns, and nothing cut out of a K/V ring. No sort, no
    ``top_k`` and no approximate one on the path: the selection is a search
    for the threshold (``ops/sparse_select.kth_largest``)."""
    text = compiled["decode"].as_text()
    gathers = [line for line in text.splitlines()
               if re.search(r"= bf16\[34816,1024\]\S* gather\(", line)]
    assert len(gathers) == 8, len(gathers)
    _no_sort_under_attention(text)


def test_the_chunk_picks_and_attends_through_its_kernels(compiled, cfg):
    """No ``conditional`` over key windows any more (PR 61): a layer's
    selection is ONE custom call of the kernel of ``ops/sparse_pick.py``
    under scope ``select``, handed the slot's indexer keys cut out of their
    stack and transposed (``bf16[64,32256]``, never the stack) and giving
    the two bfloat16 biases that the one custom call of the kernel of
    ``ops/sparse_chunk.py`` under ``attn_sparse`` reads beside the K/V
    stack as it lies, twice (its K half and its V half are column blocks
    of one array); no sort or top-k of any kind; and the configuration
    says so of these shapes."""
    text = compiled["prefill"].as_text()
    assert " conditional(" not in text
    assert cfg.serving_stats(512, 32768)["sparse_chunk_select"] == "kernel"

    def calls_of(name, scope):
        calls = [line for line in text.splitlines()
                 if 'custom_call_target="tpu_custom_call"' in line
                 and f"/{name}/" in line]
        assert len(calls) == 8, (name, len(calls))
        for line in calls:
            assert re.search(rf'op_name="[^"]*/attn/{scope}/', line), \
                line[-300:]
        return [re.search(r"operand_layout_constraints=\{(.*?)\}, \w+=",
                          line).group(1) for line in calls]

    for handed in calls_of("sparse_pick", "select"):
        assert handed.count("bf16[64,32256]") == 1, handed
        assert IDX not in handed and KV not in handed, handed
    for handed in calls_of("sparse_chunk_attention", "attn_sparse"):
        assert handed.count(KV) == 2, handed
        assert handed.count("bf16[512,32256]") == 1, handed
    _no_sort_under_attention(text)


@pytest.mark.parametrize("old, rows", [(45568, 128), (81408, 64),
                                       (126464, 32), (171008, 16)])
def test_the_picking_kernel_fits_vmem_over_the_longest_window_of_each_step(
        one_chip, cfg, old, rows):
    """The kernel's scratch and output block grow with the key window, so
    ``sparse_pick.query_rows`` halves a grid step's queries as the window
    grows (the configuration allows 262,144 positions): the kernel alone,
    at the published chunk over the LONGEST window that each count of
    queries is given, float32 keys (the reckoning's), compiles for the
    chip; past the last of them ``chunk_select`` keeps the XLA arm."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    from ray_tpu.ops import sparse_pick

    heads, di = cfg.index_heads, cfg.index_dim
    assert sparse_pick.query_rows(512, heads, di, old) == rows
    assert sparse_pick.query_rows(512, heads, di, old + 512) \
        == (rows // 2 if rows > 16 else 0)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(jax, "default_backend", lambda: "tpu")
            text = jax.jit(lambda *a: sparse_pick.sparse_pick(
                *a, cfg.index_topk)).lower(
                    sds((512, heads, di), jnp.float32),
                    sds((512, heads), jnp.float32),
                    sds((512, di), jnp.float32),
                    sds((di, old), jnp.float32), sds((), jnp.int32),
                    sds((), jnp.int32)).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()
    assert text.count('custom_call_target="tpu_custom_call"') == 1


@pytest.mark.parametrize("which", ["decode", "prefill"])
def test_the_experts_run_through_the_kernel(compiled, cfg, which,
                                            experts_through_the_kernel):
    """PR 52: in both of the engine's programs the gated experts' two
    products are ONE custom call of the kernel of ``ops/moe_experts.py`` a
    layer, under scope ``experts``, handed the layer's 16 x 2048 x 1536 and
    16 x 768 x 2048 stacks as they lie (16 held experts at 1 to 3 rows an
    expert a step take the kernel as 64 and 128 do)."""
    assert (cfg.d_model, cfg.expert_ff, cfg.experts_held) \
        == (2048, 768, (0, 16))
    experts_through_the_kernel(compiled[which], cfg.n_layer, 16, 2048,
                               1536, 768)


def test_the_chunk_keeps_the_cache_in_the_steps_layout(compiled):
    """Both stacks: each shape has one layout as a whole array in the chunk
    program, and it is the decode program's, so none is re-laid out between
    the two; the K/V rings are row-minor (a row of 1024 columns is eight
    whole lane tiles), which is how the kernel takes them."""
    def layouts(shape, which):
        # (a trailing S(n) names a memory space, not a layout)
        text = re.sub(r"operand_layout_constraints=\{[^=]*\}, ", "",
                      compiled[which].as_text())
        return {re.sub(r"S\(\d+\)", "", found) for found in re.findall(
            re.escape(shape) + r"(\{[^}]*\})", text)}

    for shape in (KV, IDX):
        assert len(layouts(shape, "prefill")) == 1, shape
        assert layouts(shape, "prefill") == layouts(shape, "decode"), shape
    assert all(found.startswith("{3,2,1,0")
               for found in layouts(KV, "decode"))
