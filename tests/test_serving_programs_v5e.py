"""What the TPU's compiler makes of the two serving programs (PR 25).

Compile-only, for one described v5e chip, at GPT-2 XL's published widths
and the benchmark deployment's shapes (8 slots and the scratch one, a
1024-row cache, prompts of up to 768 tokens in the engine's [1, 256]
prefill chunks, PR 31; a [4, 768] lane before): nothing runs, so nothing
here is a time. It holds what ``test_the_cache_is_only_written_by_rows`` cannot
see from the jaxpr: that XLA keeps the stacked cache's layout through the
row writes (a scatter, or the same updates under a ``fori_loop``, made it
re-lay out the whole cache around them), so the layer loop moves no
layer-sized block and the program's temp space holds no second cache.

The topology is described inside a fixture, in this one file: only the
worker that runs this file loads the TPU's library.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.models import gpt2
from ray_tpu.models.prefill import chunk_len, key_window

XL = gpt2.GPT2Config(vocab_size=50304, n_layer=48, n_head=25, d_model=1600,
                     seq_len=1024)
SLOTS, CACHE_LEN, PROMPT_LEN = 9, 1024, 768
CHUNK = chunk_len(PROMPT_LEN)  # as the engine derives it: 256
LAYER_BLOCK = SLOTS * CACHE_LEN * XL.n_head * XL.head_dim


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
def compiled(one_chip):
    """Both programs as the engine jits them (cache donated), compiled
    once for the module, with the persistent cache out of the way: such
    a compile is written to it but cannot be read back without a chip."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    def sds(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    made = jax.eval_shape(lambda: gpt2.gpt2_init(jax.random.PRNGKey(0), XL))
    # as the engine stores them (PR 29): bfloat16 but for the norms
    params = sds(jax.tree.map(
        lambda a, dt: jax.ShapeDtypeStruct(a.shape, dt), made,
        XL.serving_dtypes(made)))
    cache = sds(jax.eval_shape(
        lambda: gpt2.gpt2_init_cache(XL, SLOTS, CACHE_LEN)))
    programs = {
        "decode": (lambda p, c, t, n: gpt2.gpt2_decode_step(p, c, t, n, XL),
                   (params, cache, i32(SLOTS), i32(SLOTS))),
        "prefill": (lambda p, c, t, s, at, n: gpt2.gpt2_prefill_chunk(
            p, c, t, s, at, n, XL, window=key_window(PROMPT_LEN, CHUNK)),
                    (params, cache, i32(1, CHUNK), i32(1), i32(1), i32(1))),
    }
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        return {name: jax.jit(fn, donate_argnums=(1,)).lower(*args).compile()
                for name, (fn, args) in programs.items()}
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


BLOCK_DIMS = f"{SLOTS},{CACHE_LEN},{XL.n_head},{XL.head_dim}"
PASSES_ON = ("get-tuple-element", "parameter", "bitcast", "tuple")


def _loop_bodies(hlo_text):
    """The computations a ``while`` runs as its body."""
    bodies = set(re.findall(r"\bwhile\(.*?body=(%[\w.\-]+)", hlo_text))
    for block in hlo_text.split("\n\n"):
        if block.lstrip().split(" ", 1)[0] in bodies:
            yield block


@pytest.mark.parametrize("which", ["decode", "prefill"])
def test_no_layer_sized_block_is_moved_inside_the_layer_loop(compiled, which):
    """In the parent's programs the loop held ``copy.31/33/34/35`` (a
    layer's block of the cache re-laid out on its way in and out), a
    ``dynamic-slice`` that cut it out of the stack and a
    ``dynamic-update-slice`` that put it back. Now nothing in a loop body
    makes a layer's block, and what makes a whole cache there (the
    prefill's in-place row writes) takes no layer's block to do it."""
    made_block = re.compile(
        rf"\s*(?:ROOT )?(%[\w.\-]+) = \w+\[(1,)?{BLOCK_DIMS}\]\S* ([\w\-]+)\(")
    made_cache = re.compile(
        rf"\s*(?:ROOT )?%[\w.\-]+ = \w+\[{XL.n_layer},{BLOCK_DIMS}\]\S* "
        r"([\w\-]+)\((.*)")
    moved, lines = [], 0
    for body in _loop_bodies(compiled[which].as_text()):
        blocks = set()
        for line in body.splitlines()[1:]:
            lines += 1
            m = made_block.match(line)
            if m:
                blocks.add(m.group(1))
                if m.group(3) not in PASSES_ON:
                    moved.append(line.strip()[:140])
        for line in body.splitlines()[1:]:
            m = made_cache.match(line)
            if m and m.group(1) not in PASSES_ON and \
                    blocks & set(re.findall(r"%[\w.\-]+", m.group(2))):
                moved.append(line.strip()[:140])
    assert lines > 20, "found no layer loop to read"
    assert moved == []


@pytest.mark.parametrize("which", ["decode", "prefill"])
def test_temp_space_holds_no_second_cache_and_no_copy_of_the_weights(
        compiled, which):
    """The stacked cache is 2.83 GB and is updated in the donated buffer
    (before PR 25 the programs took 6.03 and 6.49 GB of temp space). The
    weights come in as the engine stores them, 3.14 GB, and no bfloat16
    copy of them is made (3.15 and 3.37 GB of temp space until PR 29): what
    is left is the embedding laid out for the head (0.16 GB) and the
    activations, 0.17 GB in both: the chunk's are under the [4, 768]
    lane's 0.24 GB."""
    mem = compiled[which].memory_analysis()
    cache_bytes = 2 * XL.n_layer * LAYER_BLOCK * 2
    assert mem.alias_size_in_bytes >= cache_bytes
    assert mem.temp_size_in_bytes < {"decode": 0.4e9, "prefill": 0.24e9}[which]
    assert mem.argument_size_in_bytes < cache_bytes + 2.02 * XL.n_params


def test_the_chunk_keeps_the_cache_in_the_layout_the_step_reads(compiled):
    """The chunk program writes its rows into, and cuts its key window out
    of, the stacked cache its layer loop carries. Everywhere either program
    names an array of the cache's shape it has ONE layout, the same in both
    (on this runtime ``cache_len`` minor-most): no program re-lays the
    cache out around a write, and what a chunk leaves is what the step
    reads."""
    # (a trailing S(n) names a memory space, not a layout)
    layouts = {which: {re.sub(r"S\(\d+\)", "", found) for found in re.findall(
        rf"bf16\[{XL.n_layer},{BLOCK_DIMS}\](\{{[^}}]*\}})", prog.as_text())}
        for which, prog in compiled.items()}
    assert len(layouts["prefill"]) == 1, layouts
    assert layouts["prefill"] == layouts["decode"]
