"""The plain reference (``benchmark/reference/gpt2.py``) against the
system's model code (``ray_tpu/models/gpt2.py``) at the ``tiny`` preset on
the CPU: loss, gradient norm, and prefill then decode logits through a
cache. Both run in float32 here, so they must agree closely; on the chip
the benchmark makes the same comparison at the published widths, in
set-up, under the tolerance its configuration file states."""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.loading import load_module

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
family = load_module(os.path.join(REPO, "benchmark", "families", "gpt2.py"))
reference = load_module(os.path.join(REPO, "benchmark", "reference",
                                     "gpt2.py"))

# The tiny preset as a configuration file would state it; no padding rows.
CONFIG = {"vocab_size": 256, "n_positions": 64, "n_embd": 64, "n_layer": 2,
          "n_head": 4, "layer_norm_epsilon": 1e-5,
          "assumed": {"remat": False, "scan_layers": True,
                      "use_flash": False}}


@pytest.fixture(scope="module")
def model():
    from ray_tpu.models.gpt2 import GPT2Config, gpt2_init

    cfg = dataclasses.replace(family.system_config(CONFIG),
                              dtype=jnp.float32)
    assert dataclasses.replace(cfg, dtype=jnp.bfloat16, remat=True,
                               use_flash=None) == GPT2Config.tiny()
    params = gpt2_init(jax.random.PRNGKey(5), cfg)
    # Biases and LayerNorm offsets start at zero; move them, or a swapped
    # or dropped bias would go unseen.
    keys = iter(jax.random.split(jax.random.PRNGKey(6), 64))
    params = jax.tree.map(
        lambda x: x + 0.05 * jax.random.normal(next(keys), x.shape, x.dtype),
        params)
    return cfg, params


def test_names_cover_every_weight(model):
    _, params = model
    ref = family.to_reference(params, CONFIG)
    assert set(family.BLOCK_NAMES) == set(params["blocks"])
    n_sys = sum(x.size for x in jax.tree.leaves(params))
    assert sum(x.size for x in jax.tree.leaves(ref)) == n_sys


def test_loss_and_gradient_norm_agree(model):
    from ray_tpu.models.gpt2 import gpt2_loss

    cfg, params = model
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 65), 0, 256)
    want_loss, want_norm = reference.loss_and_grad_norm(
        family.to_reference(params, CONFIG), tokens,
        **family.reference_kwargs(CONFIG))
    loss, grads = jax.value_and_grad(
        lambda p: gpt2_loss(p, {"tokens": tokens}, cfg))(params)
    norm = jnp.sqrt(sum(jnp.sum(g ** 2) for g in jax.tree.leaves(grads)))
    assert float(loss) == pytest.approx(float(want_loss), rel=2e-5)
    assert float(norm) == pytest.approx(float(want_norm), rel=2e-4)
    # remat changes memory, not mathematics
    again = reference.loss_and_grad_norm(
        family.to_reference(params, CONFIG), tokens, remat=True,
        **family.reference_kwargs(CONFIG))
    assert float(again[1]) == pytest.approx(float(want_norm), rel=1e-5)


def test_prefill_then_decode_logits_agree(model, monkeypatch):
    _, params = model
    # serve_logits builds its config from the file: float32 for this test
    real = family.system_config
    monkeypatch.setattr(
        family, "system_config",
        lambda c: dataclasses.replace(real(c), dtype=jnp.float32))
    rng = np.random.default_rng(2)
    lens = np.array([5, 11], np.int32)
    prompts = np.zeros((2, 16), np.int32)
    follow = rng.integers(0, 256, (2, 3), dtype=np.int32)
    full = np.zeros((2, 14), np.int32)
    for i, n in enumerate(lens):
        prompts[i, :n] = rng.integers(0, 256, n)
        full[i, :n] = prompts[i, :n]
        full[i, n:n + 3] = follow[i]
    got = family.serve_logits(CONFIG, params, jnp.asarray(prompts),
                              jnp.asarray(lens), jnp.asarray(follow),
                              slots=3, cache_len=32)
    ref_all = reference.forward(family.to_reference(params, CONFIG),
                                jnp.asarray(full),
                                **family.reference_kwargs(CONFIG))
    at = lens[:, None] - 1 + np.arange(4)[None, :]
    want = ref_all[np.arange(2)[:, None], at]
    assert got.shape == want.shape == (2, 4, 256)
    err = jnp.linalg.norm(got - want, axis=-1) / jnp.linalg.norm(
        want, axis=-1)
    assert float(err.max()) < 1e-4, err


def test_the_comparison_would_catch_lower_precision(model):
    """The tolerance has to be tight enough that computing in a lower
    precision than the configuration states fails: the same model in
    bfloat16 is ~100x further from the reference than float32 is."""
    from ray_tpu.models.gpt2 import gpt2_forward

    cfg, params = model
    tokens = jax.random.randint(jax.random.PRNGKey(3), (2, 64), 0, 256)
    want = reference.forward(family.to_reference(params, CONFIG), tokens,
                             **family.reference_kwargs(CONFIG))

    def err(c):
        got = gpt2_forward(params, tokens, c)
        return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))

    assert err(cfg) < 1e-5
    assert err(dataclasses.replace(cfg, dtype=jnp.bfloat16)) > 1e-3


def test_engine_tokens_are_held_to_the_reference_rows():
    """``compare.check_engine_tokens`` on hand-made rows: the reference's
    own choice passes; a near-tie turned passes and ends that prompt's
    comparison; a token the reference ranks far down fails; so does an
    answer of the wrong length."""
    import types

    from benchmark import compare

    # Two prompts, three steps, five tokens. Row spread (std) is 1.0 for
    # [2, 1, 0, -1, -2] / sqrt(2).
    base = np.array([2.0, 1.0, 0.0, -1.0, -2.0]) / np.sqrt(2.0)
    logits = np.tile(base, (2, 3, 1)).astype(np.float32)
    logits[0, 1] = base[[1, 0, 2, 3, 4]]       # step 1 of prompt 0: token 1
    near = base.copy()
    near[1] = near[0] - 0.05                   # token 1 trails by 0.05
    logits[1, 0] = near
    ref = {"tokens": logits.argmax(-1), "logits": logits}
    assert ref["tokens"].tolist() == [[0, 1, 0], [0, 0, 0]]

    def verdict(served):
        checks, said = [], []
        run = types.SimpleNamespace(
            config={"tolerance": {"serve_token_regret_rms": 0.1}},
            say=lambda e, **f: said.append(f),
            check=lambda name, ok, detail="": checks.append(bool(ok)))
        compare.check_engine_tokens(run, ref, served)
        return checks[0], said[0]

    ok, said = verdict([[0, 1, 0], [0, 0, 0]])
    assert ok and said["compared"] == 6 and said["flips"] == 0
    # prompt 1 takes the near-tie at its first step: allowed, and its two
    # later steps are no longer compared
    ok, said = verdict([[0, 1, 0], [1, 4, 4]])
    assert ok and said["compared"] == 4 and said["flips"] == 1
    assert said["regret_rms_max"] == pytest.approx(
        0.05 / float(near.std()), rel=1e-4)
    # a token the reference ranks a whole spread down is a fault
    ok, said = verdict([[0, 1, 2], [0, 0, 0]])
    assert not ok and said["regret_rms_max"] > 1.0
    ok, said = verdict([[0, 1], [0, 0, 0]])
    assert not ok and said["short"] == 1
