"""``serve_check_many.py`` with one mechanism of a window-and-global model
turned the other way by hand: the controls that show a serving cell's
logits comparison fails when the program is not the model the reference
runs.

    python3 benchmark/tools/serve_check_window.py --control NAME \\
        --workload <serving cell> --seeds 2 [serve_check_many's options]

Turned in the REFERENCE (its keyword arguments; the sound system is then
held to another model, which costs no second program):

* ``window_ignored``: the window layers see every earlier key;
* ``global_rotated``: the global layers rotate q and k too;
* ``window_not_rotated``: no layer rotates anything;
* ``router_normed_input``: the router reads ``N1(x)``, not ``x``;
* ``router_post_attention``: the router reads the post-attention stream;
* ``silu_for_relu``: the experts' gate is SiLU.

Turned in the SYSTEM (patched from outside, in the serving functions of the
comparison and in every engine deployed from here on):

* ``ring_not_wrapped``: a prompt chunk reads a window layer's ring as a
  straight cache (row j holds position j), as the two older merged-row
  programs read theirs;
* ``padded_rows_written``: a prompt's last chunk writes its padded rows into
  the window rings too, over the prompt's own rows a window back.

By hand only: the driver never runs this. The last line is
``serve_check_many.py``'s.
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.loading import sibling  # noqa: E402

many = sibling(__file__, "serve_check_many.py")


IN_THE_REFERENCE = {
    "window_ignored": lambda kw: {"windows": (False,) * len(kw["windows"])},
    "global_rotated": lambda kw: {"rotates": (True,) * len(kw["rotates"])},
    "window_not_rotated":
        lambda kw: {"rotates": (False,) * len(kw["rotates"])},
    "router_normed_input": lambda kw: {"router_reads": "normed_input"},
    "router_post_attention": lambda kw: {"router_reads": "post_attention"},
    "silu_for_relu": lambda kw: {"activation": "silu"},
}


def ring_not_wrapped() -> None:
    import jax.numpy as jnp

    from ray_tpu.ops import attention

    def straight(start, n_rows):
        rows = jnp.arange(n_rows)
        return jnp.where(rows < start[..., None], rows, -1)

    attention.ring_positions = straight


def padded_rows_written() -> None:
    import jax.numpy as jnp

    from ray_tpu.models import smallthinker
    from ray_tpu.ops import attention

    write = attention.cache_write_ring_chunk
    smallthinker.cache_write_ring_chunk = lambda cache, rows, slots, start, \
        lengths: write(cache, rows, slots, start,
                       jnp.full_like(lengths, rows.shape[2]))


IN_THE_SYSTEM = {"ring_not_wrapped": ring_not_wrapped,
                 "padded_rows_written": padded_rows_written}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--control", required=True,
                    choices=sorted({**IN_THE_REFERENCE, **IN_THE_SYSTEM}))
    args, rest = ap.parse_known_args(argv)
    patch = many.patch

    def both(family, fault):
        patch(family, fault)
        if args.control in IN_THE_SYSTEM:
            IN_THE_SYSTEM[args.control]()
            return
        kwargs = family.reference_kwargs
        family.reference_kwargs = lambda config: {
            **kwargs(config),
            **IN_THE_REFERENCE[args.control](kwargs(config))}

    many.patch = both
    return many.main(rest)


if __name__ == "__main__":
    sys.exit(main())
