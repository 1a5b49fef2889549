"""``serve_check_many.py`` with the delta rule of the SYSTEM broken by hand:
the controls that show a serving cell's logits comparison fails when a
linear-attention layer's program is not the rule the reference runs.

    python3 benchmark/tools/serve_check_delta_rule.py --control NAME \
        --workload <serving cell> --seeds 3 [serve_check_many's options]

* ``no_delta_term``: ``d_t = beta_t v_t`` in both forms of
  ``ops/gated_delta.py`` (gated linear attention without the rule: what the
  state already holds of ``k_t`` is not taken off before the write). The
  chunked form becomes the plain recurrence over tokens, which is slow and
  is not what is measured.
* ``state_not_carried``: a chunk's rows begin from an empty state and an
  empty convolution tail whatever their slot holds (``rows_through_cache``
  told that no row goes on): every chunk boundary forgets.

In the serving functions of the logits comparison and in every engine
deployed from here on; the reference keeps its own recurrence. By hand
only: the driver never runs this, and it patches the program from outside.
The last line is ``serve_check_many.py``'s.
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.loading import sibling  # noqa: E402

many = sibling(__file__, "serve_check_many.py")


def no_delta_term(gd) -> None:
    import jax
    import jax.numpy as jnp

    def rule(q, k, v, g, beta, dims, state=None):
        r, _, h, dk = q.shape
        f32 = jnp.float32
        state = jnp.zeros((r, h, dk, v.shape[-1]), f32) if state is None \
            else state.astype(f32)

        def token(s, inp):
            q_t, k_t, v_t, g_t, beta_t = inp
            s = s * jnp.exp(g_t)[..., None, None] \
                + k_t[..., :, None] * (beta_t[..., None] * v_t)[..., None, :]
            return s, jnp.sum(s * q_t[..., None], axis=-2)

        state, o = jax.lax.scan(token, state, tuple(
            x.astype(f32).swapaxes(0, 1) for x in (q, k, v, g, beta)))
        return o.swapaxes(0, 1), state

    def step(p, y, tail, state, dims):  # a row of one token, by ``rule``
        out, tail, state = gd.delta_rows(
            p, y[:, None], jnp.ones(y.shape[0], jnp.int32), dims,
            jnp.moveaxis(tail, 0, 1), state)
        return out[:, 0], tail, state

    gd.chunked_delta_rule, gd.delta_step = rule, step


def state_not_carried(gd) -> None:
    import jax.numpy as jnp

    rows = gd.rows_through_cache
    gd.rows_through_cache = lambda p, y, lengths, conv_all, delta, layer, \
        slots, goes_on, dims: rows(p, y, lengths, conv_all, delta, layer,
                                   slots, jnp.zeros_like(goes_on), dims)


CONTROLS = {"no_delta_term": no_delta_term,
            "state_not_carried": state_not_carried}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--control", required=True, choices=sorted(CONTROLS))
    args, rest = ap.parse_known_args(argv)
    patch = many.patch

    def both(family, fault):
        from ray_tpu.ops import gated_delta

        patch(family, fault)
        CONTROLS[args.control](gated_delta)

    many.patch = both
    return many.main(rest)


if __name__ == "__main__":
    sys.exit(main())
