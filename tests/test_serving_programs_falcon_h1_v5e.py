"""What the TPU's compiler makes of Falcon-H1's two serving programs
(PR 43).

Compile-only, for one described v5e chip, at the published widths of
``benchmark/configs/falcon-h1-34b-instruct.json`` and the shapes of the cell
``serve_falconh1_longgen_sat`` (32 slots and the scratch one, rings of 5120
rows in EVERY one of the nine layers beside a float32 state, prompts of up
to 4096 tokens in the engine's [1, 256] chunks over a key window of 4096):
nothing runs, so nothing here is a time. It holds that both programs fit the
chip beside their arguments (the decode step's float32 pass over 33 K/V
windows in nine layers included), that the donated cache is updated in its
own buffers, that no program makes a float32 array as long as a ring or
copies a layer's state, and that the chunk program keeps the cache in the
step's layout: XLA's choices decide that, not the jaxpr.

The topology is described inside a fixture, in this one file: only the
worker that runs this file loads the TPU's library.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest

from benchmark.loading import load_json, load_module
from ray_tpu.models import falcon_h1 as fh
from ray_tpu.models.prefill import chunk_len, key_window

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HBM = 15.75 * 2 ** 30


@pytest.fixture(scope="module")
def engine():
    return load_json(os.path.join(
        REPO, "benchmark", "deployments",
        "falconh1_1chip_b32.json"))["engine"]


@pytest.fixture(scope="module")
def cfg():
    family = load_module(os.path.join(REPO, "benchmark", "families",
                                      "falcon_h1.py"))
    return family.system_config(load_json(os.path.join(
        REPO, "benchmark", "configs", "falcon-h1-34b-instruct.json")))


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
    chunk = chunk_len(engine["max_prompt_len"])
    window = key_window(engine["max_prompt_len"], chunk)
    assert (slots, chunk, window, engine["cache_len"]) \
        == (33, 256, 4096, 5120)
    params = sds(jax.eval_shape(
        lambda: fh.falcon_h1_init(jax.random.PRNGKey(0), cfg)))
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
        return {name: jax.jit(fn, donate_argnums=(1,)).lower(*args).compile()
                for name, (fn, args) in programs.items()}
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


def nbytes(shape, itemsize):
    n = itemsize
    for d in shape:
        n *= d
    return n


@pytest.mark.parametrize("which", ["decode", "prefill"])
def test_the_program_fits_the_chip_beside_its_arguments(compiled, cfg,
                                                        which):
    """4.205 B bfloat16 parameters (8.41 GB) and 4.37 GB of cache are the
    arguments; the cache is aliased to the output, so it is held once."""
    mem = compiled[which].memory_analysis()
    cache_bytes = 2 * nbytes((9, 33, 5120, 4, 128), 2) \
        + nbytes((9, 3, 33, cfg.mamba.conv_dim), 2) \
        + nbytes((9, 33, 32, 128, 256), 4) + 4
    assert cache_bytes == 33 * 9 * 14_710_784 + 4 == 4_369_102_852
    assert mem.alias_size_in_bytes >= cache_bytes
    gb = {k: getattr(mem, k + "_size_in_bytes") / 1e9
          for k in ("argument", "temp", "alias", "output")}
    print(which, gb)
    assert 12.77e9 < mem.argument_size_in_bytes < 12.80e9
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM, gb
    # a chunk holds its scores over the 4096-row window (84 MB in float32 a
    # layer) and little else. The step holds 2.8 GB: the compiler re-lays
    # every layer's K and V window out, [row, head] -> [head, row], for the
    # grouped products (the test below), and keeps the eighteen copies of
    # 173 MB alive side by side. PERF.md section 7 names the debt.
    assert mem.temp_size_in_bytes < {"decode": 2.9e9, "prefill": 0.6e9}[which]


SHAPE = re.compile(r"^\s*(?:ROOT )?%[\w.\-]+ = (\w+)\[([\d,]+)\]\S* "
                   r"([\w\-]+)\(")
RING = nbytes((33, 5120, 4, 128), 1)     # elements of a layer's K or V ring
STATE = nbytes((33, 32, 128, 256), 1)    # elements of a layer's SSM state


def _unfused(hlo_text):
    """The text of every computation but the ones a ``fusion`` calls:
    inside a fusion a slice or a convert is a step of one loop, not a
    buffer."""
    fused = set(re.findall(r" fusion\(.*?calls=(%[\w.\-]+)", hlo_text))
    return "\n".join(block for block in hlo_text.split("\n\n")
                     if block.lstrip().split(" ", 1)[0] not in fused)


def _arrays_made(hlo_text):
    """(type, elements, opcode) of every instruction of ``hlo_text`` that
    makes an array by moving one: ``copy``, ``transpose``, ``convert`` and
    slices."""
    for line in hlo_text.splitlines():
        m = SHAPE.match(line)
        if m and m.group(3) in ("copy", "transpose", "convert", "slice",
                                "dynamic-slice"):
            yield m.group(1), nbytes(
                [int(d) for d in m.group(2).split(",")], 1), m.group(3)


@pytest.mark.parametrize("which", ["decode", "prefill"])
def test_no_float32_array_as_long_as_a_ring_and_no_state_is_copied(compiled,
                                                                   which):
    """A layer's ring is 33 x 5120 x 4 x 128 bfloat16 (173 MB), its state
    33 x 32 x 128 x 256 float32 (138 MB). Neither program widens a ring to
    float32 (346 MB a layer: the step's float32 scores are over 20 heads x
    5120 rows, 13.5 MB), and neither copies a state: it is rewritten inside
    its donated buffer. What the step DOES make is a bfloat16 copy of each
    layer's K and V window with rows and heads swapped, eighteen in all
    (a later PR that reads the rings as they lie brings this to zero: the
    bound is from above)."""
    text = compiled[which].as_text()
    made = list(_arrays_made(_unfused(text)))
    assert len(made) > 50, "read no program"
    assert [m for m in made if m[0] == "f32" and m[1] >= RING] == []
    assert [m for m in made if m[1] in (STATE, 9 * STATE)] == []
    # (inside fusions too: a fusion whose root is a copy writes it out)
    relaid = [m for m in _arrays_made(text)
              if m[1] == RING and m[2] == "copy"]
    assert all(m[0] == "bf16" for m in relaid)
    assert len(relaid) <= {"decode": 18, "prefill": 0}[which]


def test_the_chunk_keeps_the_cache_in_the_steps_layout(compiled):
    """The stacked K/V rings and a layer's SSM state (4.36 of the cache's
    4.37 GB): each shape has one layout as a whole array in the chunk
    program, and it is the decode program's, so neither is re-laid out
    between the two."""
    def layouts(shape, which):
        # (a trailing S(n) names a memory space, not a layout)
        return {re.sub(r"S\(\d+\)", "", found) for found in re.findall(
            shape + r"(\{[^}]*\})", compiled[which].as_text())}

    for shape in (r"bf16\[9,33,5120,4,128\]", r"f32\[33,32,128,256\]"):
        assert len(layouts(shape, "prefill")) == 1, shape
        assert layouts(shape, "prefill") == layouts(shape, "decode"), shape
