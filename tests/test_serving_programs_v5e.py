"""What the TPU's compiler makes of the two serving programs (PR 25).

Compile-only, for one described v5e chip, at GPT-2 XL's published widths
and the benchmark deployment's shapes (8 slots and the scratch one, a
1024-row cache, prompts of up to 768 tokens in the engine's [1, 256]
prefill chunks, PR 31; a [4, 768] lane before): nothing runs, so nothing
here is a time. It holds what ``test_the_cache_is_only_written_by_rows`` cannot
see from the jaxpr: that XLA keeps the stacked cache's layout through the
row writes (a scatter, or the same updates under a ``fori_loop``, made it
re-lay out the whole cache around them), so the layer loop moves no
layer-sized block and the program's temp space holds no second cache; and,
since PR 42, that the layout it keeps, in BOTH programs from the entry to
the donated output, is the one with a token's merged, lane-padded row
contiguous (``{3,2,1,0}``), and that the pad is what buys it.

The topology is described inside a fixture, in this one file: only the
worker that runs this file loads the TPU's library.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.models import gpt2
from ray_tpu.models.prefill import (chunk_len, key_window,
                                    token_parameters)

XL = gpt2.GPT2Config(vocab_size=50304, n_layer=48, n_head=25, d_model=1600,
                     seq_len=1024)
SLOTS, CACHE_LEN, PROMPT_LEN = 9, 1024, 768
CHUNK = 256  # as the engine derives it (held where the programs are made)
ROW = 1664  # 25 heads of 64 merged, padded to whole lane tiles: 13 x 128
LAYER_BLOCK = SLOTS * CACHE_LEN * ROW
CACHE_DIMS = f"{XL.n_layer},{SLOTS},{CACHE_LEN},{ROW}"


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


def _programs(one_chip, cache=None):
    """name -> (function, abstract arguments) as the engine jits them, on
    the parameters as it stores them (PR 29: bfloat16 but for the norms);
    ``cache`` stands in for ``gpt2_init_cache``'s where given."""
    def sds(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    made = jax.eval_shape(lambda: gpt2.gpt2_init(jax.random.PRNGKey(0), XL))
    params = sds(jax.tree.map(
        lambda a, dt: jax.ShapeDtypeStruct(a.shape, dt), made,
        XL.serving_dtypes(made)))
    assert CHUNK == chunk_len(PROMPT_LEN, *token_parameters(XL, params),
                              cache_len=CACHE_LEN)
    cache = sds(cache or jax.eval_shape(
        lambda: gpt2.gpt2_init_cache(XL, SLOTS, CACHE_LEN)))
    return {
        "decode": (lambda p, c, t, n: gpt2.gpt2_decode_step(p, c, t, n, XL),
                   (params, cache, i32(SLOTS), i32(SLOTS))),
        "prefill": (lambda p, c, t, s, at, n: gpt2.gpt2_prefill_chunk(
            p, c, t, s, at, n, XL, window=key_window(PROMPT_LEN, CHUNK)),
                    (params, cache, i32(1, CHUNK), i32(1), i32(1), i32(1))),
    }


def _compile(programs):
    """Cache donated, with the persistent cache out of the way: such a
    compile is written to it but cannot be read back without a chip. The
    kernels pick interpret mode from the process's backend, which is the
    CPU here: while these programs are traced it says what the described
    chip's would, so that the decode step holds its kernel (PR 48) and
    not the interpreter's loops."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(jax, "default_backend", lambda: "tpu")
            return {name: jax.jit(fn, donate_argnums=(1,)).lower(
                *args).compile() for name, (fn, args) in programs.items()}
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


@pytest.fixture(scope="module")
def compiled(one_chip):
    """Both programs, compiled once for the module."""
    programs = _programs(one_chip)
    assert programs["decode"][1][1]["k"].shape == (
        XL.n_layer, SLOTS, CACHE_LEN, ROW)
    return _compile(programs)


BLOCK_DIMS = f"{SLOTS},{CACHE_LEN},{ROW}"
PASSES_ON = ("get-tuple-element", "parameter", "bitcast", "tuple")


def _loop_bodies(hlo_text):
    """The computations a ``while`` runs as its body."""
    bodies = set(re.findall(r"\bwhile\(.*?body=(%[\w.\-]+)", hlo_text))
    for block in hlo_text.split("\n\n"):
        if block.lstrip().split(" ", 1)[0] in bodies:
            yield block


@pytest.mark.parametrize("which", ["decode", "prefill"])
def test_no_layer_sized_block_is_moved_inside_the_layer_loop(compiled, which):
    """Before PR 25 the loop held ``copy.31/33/34/35`` (a layer's block of
    the cache re-laid out on its way in and out), a ``dynamic-slice`` that
    cut it out of the stack and a ``dynamic-update-slice`` that put it
    back. Nothing in a loop body makes a layer's block or a whole cache
    (since PR 42 neither program writes inside its loop), and nowhere in
    the program is an array as large as the cache, or as a layer's block,
    copied or transposed."""
    made_large = re.compile(
        rf"\s*(?:ROOT )?%[\w.\-]+ = \w+\[(?:{XL.n_layer},|1,)?{BLOCK_DIMS}\]"
        r"\S* ([\w\-]+)\(")
    text = compiled[which].as_text()
    moved, lines = [], 0
    for body in _loop_bodies(text):
        for line in body.splitlines()[1:]:
            lines += 1
            m = made_large.match(line)
            if m and m.group(1) not in PASSES_ON:
                moved.append(line.strip()[:140])
    assert lines > 20, "found no layer loop to read"
    assert moved == []
    copied = [line.strip()[:140] for line in text.splitlines()
              if (m := made_large.match(line))
              and m.group(1) in ("copy", "transpose")]
    assert copied == []


def test_the_step_reads_its_rings_through_the_kernel_and_copies_none(
        compiled):
    """PR 48: the decode attention is ONE custom call of the kernel of
    ``ops/ring_decode.py`` in the layer loop's body (so once a layer),
    handed the K and V STACKS as they lie and the layer's index: the scan
    runs over the index with the cache closed over, since a layer's slice
    as the scan's ``xs`` would be copied out for the kernel first, a whole
    layer's rings a layer, the traffic the kernel is there to save. So
    nothing anywhere in the program, inside a fusion or outside, gives out
    an array of a layer's block's size or of a stack's but the row-sized
    writes into the donated stacks after the loop."""
    text = compiled["decode"].as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 1 and "ring_decode_attention" in calls[0]
    in_loop = [body for body in _loop_bodies(text) if calls[0] in body]
    assert len(in_loop) == 1
    operands = re.findall(r"(\w+\[[\d,]*\])", re.search(
        r"operand_layout_constraints=\{(.*?)\}, \w+=", calls[0]).group(1))
    assert operands.count(f"bf16[{CACHE_DIMS}]") == 2, operands
    result = re.compile(r"^\s*(?:ROOT )?%[\w.\-]+ = (.*?) ([\w\-]+)\(")
    made = []
    for line in text.splitlines():
        m = result.match(line)
        # (the loop hands the stacks it closes over through its carry)
        if m and m.group(2) not in PASSES_ON + ("while",) and any(
                dims in (BLOCK_DIMS, "1," + BLOCK_DIMS, CACHE_DIMS)
                for dims in re.findall(r"\w+\[([\d,]+)\]", m.group(1))):
            made.append(m.group(2))
    assert set(made) == {"dynamic-update-slice"}, made


# what one execution of the chunk program stacks as its layer loop's
# ``ys``: the chunk's own K and V rows of every layer, [48, 1, 256, 1664]
CHUNK_ROWS = 2 * XL.n_layer * CHUNK * ROW * 2


@pytest.mark.parametrize("which", ["decode", "prefill"])
def test_temp_space_holds_no_second_cache_and_no_copy_of_the_weights(
        compiled, which):
    """The stacked cache is 2.94 GB (2.83 GB of rows and their pad) and is
    updated in the donated buffer (before PR 25 the programs took 6.03 and
    6.49 GB of temp space). The weights come in as the engine stores
    them, 3.14 GB, and no bfloat16 copy of them is made (3.15 and 3.37 GB
    of temp space until PR 29): what is left is the embedding laid out for
    the head (0.16 GB) and the activations, 0.17 GB in both, under the
    [4, 768] lane's 0.24 GB. The chunk's stacked rows (2 x 41 MB, written
    after its loop since PR 42) fit within that: they are allowed for by
    name, so a limit that had to grow would say by how much."""
    mem = compiled[which].memory_analysis()
    cache_bytes = 2 * XL.n_layer * LAYER_BLOCK * 2
    assert mem.alias_size_in_bytes >= cache_bytes
    assert mem.temp_size_in_bytes < {
        "decode": 0.4e9, "prefill": 0.16e9 + CHUNK_ROWS}[which]
    assert mem.argument_size_in_bytes < cache_bytes + 2.02 * XL.n_params


def _cache_layouts(text):
    """Every layout the program names an array of the cache's shape in
    (a trailing S(n) names a memory space, not a layout; what the kernel's
    custom call asks of its operands, ``operand_layout_constraints``, names
    an order of dimensions and no tiling, and is no array's layout)."""
    text = re.sub(r"operand_layout_constraints=\{[^=]*\}, ", "", text)
    return {re.sub(r"S\(\d+\)", "", found) for found in re.findall(
        rf"bf16\[{CACHE_DIMS}\](\{{[^}}]*\}})", text)}


def _entry_layouts(text, dims):
    """(parameters', results') layouts of the arrays of shape ``dims`` in
    the module's ``entry_computation_layout``."""
    entry = re.search(
        r"entry_computation_layout=\{\((.*)\)->\((.*?)\)\}(?:, \w+=|$)",
        text.split("\n", 1)[0])
    find = rf"bf16\[{dims}\](\{{[^}}]*\}})"
    return re.findall(find, entry.group(1)), re.findall(find, entry.group(2))


@pytest.mark.parametrize("which", ["decode", "prefill"])
def test_the_cache_is_row_minor_from_the_entry_to_the_donated_output(
        compiled, which):
    """Both programs take the cache and hand it back with a token's row
    minor-most (``{3,2,1,0}``: a row written is 13 tiles of 4 KB, where
    ``cache_len`` minor-most made it 4,800), and name it in no other
    layout anywhere between: each reads the cache as it was and writes its
    rows after the layer loop. (A chunk that wrote its rows into the cache
    its loop carried and then cut its key window out of the same carry
    made the compiler re-lay the whole cache out and back, four cache-sized
    copies a call; a layout only one program keeps is a copy a call.)"""
    text = compiled[which].as_text()
    took, gave = _entry_layouts(text, CACHE_DIMS)
    assert len(took) == len(gave) == 2
    for layout in took + gave:
        assert layout.startswith("{3,2,1,0"), layout
    assert len(_cache_layouts(text)) == 1, _cache_layouts(text)


def test_the_chunk_keeps_the_cache_in_the_layout_the_step_reads(compiled):
    """ONE layout, the same in both programs: what a chunk leaves is what
    the step reads, and neither re-lays the shared, donated buffer out."""
    layouts = {which: _cache_layouts(prog.as_text())
               for which, prog in compiled.items()}
    assert len(layouts["prefill"]) == 1, layouts
    assert layouts["prefill"] == layouts["decode"]


def test_the_merged_row_without_its_pad_is_not_row_minor(one_chip):
    """Why the pad: the same decode step over merged rows of 1600 columns
    (12.5 lane tiles) compiles with ``cache_len`` minor-most again, as the
    heads-apart cache did; 64 more columns a row (4 % of the cache) are
    what keeps a row contiguous."""
    unpadded = jax.eval_shape(lambda: {n: jnp.zeros(
        (XL.n_layer, SLOTS, CACHE_LEN, XL.d_model), XL.dtype)
        for n in ("k", "v")})
    programs = _programs(one_chip, cache=unpadded)
    text = _compile({"decode": programs["decode"]})["decode"].as_text()
    took, gave = _entry_layouts(
        text, f"{XL.n_layer},{SLOTS},{CACHE_LEN},{XL.d_model}")
    assert len(took) == 2
    for layout in took + gave:
        assert layout.startswith("{2,3,1,0"), layout
