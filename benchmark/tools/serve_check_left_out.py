"""``serve_check_many.py`` with a part of the SYSTEM left out by hand: the
control that shows a serving cell's logits comparison fails when a branch
of the model is missing from the program.

    python3 benchmark/tools/serve_check_left_out.py --zero-leaf NAME \
        --workload <serving cell> --seeds 3 [serve_check_many's options]

Every leaf called ``NAME`` in the system's seeded weights (a branch's output
matrix: ``out_proj`` for a state-space mixer, ``wo`` for attention) is set
to zero, in the serving functions of the logits comparison and in every
engine deployed from here on; the reference keeps the seeded weights.
(``serve_check_many.py --scale-leaf NAME=0`` scales the leaf for BOTH, which
says how the readings move with a part's share, not whether its absence is
seen.) By hand only: the driver never runs this, and it patches the program
from outside. The last line is ``serve_check_many.py``'s.
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.loading import sibling  # noqa: E402

many = sibling(__file__, "serve_check_many.py")


def without(params, name: str):
    import jax
    import jax.numpy as jnp

    return jax.tree_util.tree_map_with_path(
        lambda path, x: jnp.zeros_like(x)
        if getattr(path[-1], "key", None) == name else x, params)


def leave_out(family, name: str) -> None:
    from ray_tpu.serve import llm_engine

    logits, bundle = family.serve_logits, llm_engine._model_bundle
    family.serve_logits = lambda config, params, *a, **k: logits(
        config, without(params, name), *a, **k)

    def faulty(model, config, preset):
        cfg, init, *rest = bundle(model, config, preset)
        return (cfg, lambda key, cfg: without(init(key, cfg), name), *rest)

    llm_engine._model_bundle = faulty


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--zero-leaf", required=True, metavar="NAME")
    args, rest = ap.parse_known_args(argv)
    patch = many.patch

    def both(family, fault):
        patch(family, fault)
        leave_out(family, args.zero_leaf)

    many.patch = both
    return many.main(rest)


if __name__ == "__main__":
    sys.exit(main())
