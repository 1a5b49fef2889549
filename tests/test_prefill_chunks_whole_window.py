"""Chunks leave what the whole window leaves (PR 31; split off
``tests/test_prefill_chunks.py`` in PR 64 so that no file of the three is
the whole run's longest): for every family and every row width GPT-2 is
served with, ``<family>_prefill_chunk`` run chunk after chunk over prompts
that end before, at and after a chunk's edge leaves the first-token logits,
K/V rows and states of one whole-window pass, and those logits are the
full-context forward's.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from served_families import forward_fn
from test_prefill_chunks import (CHUNK, MAX_PROMPT, _assert_same_state, _cfg,
                                 _in_chunks, _params, _prompt, _used_cache,
                                 _whole, every_row_width)


@every_row_width
@pytest.mark.parametrize("n", [CHUNK - 1, CHUNK, CHUNK + 1, 2 * CHUNK,
                               MAX_PROMPT])
def test_chunks_leave_what_the_whole_window_leaves(family, n):
    params, prompt = _params(family), _prompt(n, seed=n)
    cache = _used_cache(family, seed=5)
    got, got_cache = _in_chunks(family, params, cache, prompt, slot=2)
    want, want_cache = _whole(family, params, cache, prompt, slot=2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    _assert_same_state(family, got_cache, want_cache, 2, n)
    # and the whole-window logits are the full-context forward's (causal:
    # one padded shape, compiled once a family, serves every length)
    padded = np.zeros((1, MAX_PROMPT), np.int32)
    padded[0, :n] = prompt
    full = forward_fn(family.split("-")[0], _cfg(family))(
        params, jnp.asarray(padded))[0, n - 1]
    np.testing.assert_allclose(np.asarray(got), np.asarray(full),
                               rtol=2e-4, atol=2e-4)
