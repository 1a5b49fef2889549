"""What decides ``correct``: the system against the plain reference, at
the configuration's own widths, from the same seeded weights, in set-up.

The tolerances are in the configuration's file with their reason.
"""

from __future__ import annotations


def jax_seed(seed: int) -> int:
    """``--seed`` is any whole number; JAX's keys take 32 bits."""
    return abs(int(seed)) % 2147483647


def rel(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), 1e-30)


def check_train(run, params, step_once, two_rows, remat: bool) -> None:
    """Loss and global gradient norm of two seeded rows: the cell's own
    step (``step_once() -> (loss, grad_norm)``, which runs them tiled to
    the cell's batch shape on the cell's mesh) against the reference.
    The reference runs first, because the step donates its state."""
    import jax

    config, tol = run.config, run.config["tolerance"]
    kwargs = run.family.reference_kwargs(config)
    ref_fn = jax.jit(lambda p, t: run.reference.loss_and_grad_norm(
        run.family.to_reference(p, config), t, remat=remat, **kwargs))
    ref_loss, ref_gnorm = (float(x) for x in ref_fn(params, two_rows))
    run.say("reference_done")
    sys_loss, sys_gnorm = step_once()
    run.say("reference_train", ref_loss=ref_loss, sys_loss=sys_loss,
            ref_grad_norm=ref_gnorm, sys_grad_norm=sys_gnorm,
            loss_rel=rel(sys_loss, ref_loss),
            grad_norm_rel=rel(sys_gnorm, ref_gnorm))
    run.check("reference_loss",
              rel(sys_loss, ref_loss) <= tol["train_loss_rel"],
              f"system {sys_loss} vs reference {ref_loss}, tolerance "
              f"{tol['train_loss_rel']}")
    run.check("reference_grad_norm",
              rel(sys_gnorm, ref_gnorm) <= tol["train_grad_norm_rel"],
              f"system {sys_gnorm} vs reference {ref_gnorm}, tolerance "
              f"{tol['train_grad_norm_rel']}")


def check_serve(run, engine: dict) -> dict:
    """Before the engine exists. The reference continues the seeded
    prompts greedily, one full forward pass a token, and keeps its logits
    row at every step. The serving path's own functions (prefill, then
    decode steps through a cache, fed the reference's tokens) must give
    those rows within ``serve_logits_rel_l2``: logits, not tokens, so the
    precision the configuration states is held. Returns the prompts, the
    reference's tokens and rows, for ``check_engine_tokens``."""
    import gc

    import jax
    import jax.numpy as jnp
    import numpy as np

    config, tol = run.config, run.config["tolerance"]
    sample = config["reference_check"]
    lens = [min(n, engine["max_prompt_len"]) for n in sample["prompt_lens"]]
    n_follow = int(sample["follow"])
    vocab = run.family.shape(config)["vocab"]
    rng = run.rng("reference_check")
    rows = np.arange(len(lens))
    prompts = np.zeros((len(lens), engine["max_prompt_len"]), np.int32)
    for i, n in enumerate(lens):
        prompts[i, :n] = rng.integers(0, vocab, n, dtype=np.int32)
    lengths = np.asarray(lens, np.int32)
    full = np.zeros((len(lens), max(lens) + n_follow), np.int32)
    full[:, :max(lens)] = prompts[:, :max(lens)]

    params = run.family.init_params(config, jax_seed(run.seed))
    ref_rows = jax.jit(lambda p, t, at: run.reference.forward(
        run.family.to_reference(p, config), t,
        **run.family.reference_kwargs(config))[rows, at])
    want = np.zeros((len(lens), n_follow + 1, vocab), np.float32)
    for i in range(n_follow + 1):
        want[:, i] = np.asarray(ref_rows(
            params, jnp.asarray(full), jnp.asarray(lengths - 1 + i)))
        if i < n_follow:
            full[rows, lengths + i] = want[:, i].argmax(-1)
    tokens = want.argmax(-1).astype(np.int32)
    top2 = np.sort(want, axis=-1)[..., -2:]
    run.say("reference_greedy", margin_rms=np.round(
        (top2[..., 1] - top2[..., 0]) / want.std(-1), 4).tolist())

    got = np.asarray(run.family.serve_logits(
        config, params, jnp.asarray(prompts), jnp.asarray(lengths),
        jnp.asarray(tokens[:, :n_follow]), slots=len(lens) + 1,
        cache_len=engine["cache_len"])[..., :vocab], np.float32)
    err = np.linalg.norm(got - want, axis=-1) / np.linalg.norm(want, axis=-1)
    run.say("reference_serve", rel_l2_prefill=err[:, 0].tolist(),
            rel_l2_decode_max=float(err[:, 1:].max()),
            finite=bool(np.isfinite(err).all()))
    run.check("reference_logits",
              bool(np.isfinite(err).all())
              and float(err.max()) <= tol["serve_logits_rel_l2"],
              f"relative L2 error of logits, per position: "
              f"{np.round(err, 5).tolist()}, tolerance "
              f"{tol['serve_logits_rel_l2']}")
    del params, got
    gc.collect()
    return {"prompts": [prompts[i, :n].tolist() for i, n in enumerate(lens)],
            "tokens": tokens, "logits": want}


def check_engine_tokens(run, ref: dict, served: list) -> None:
    """What the deployed engine served for the reference's prompts,
    through the same public call a client makes, against the reference's
    own greedy choice at every step. Where the engine chose another token
    the reference must rank it within ``serve_token_regret_rms`` of its
    best (in units of that row's spread): a near-tie that rounding may
    turn. From there on the two contexts differ, so that prompt's later
    steps are not compared."""
    import numpy as np

    tol = run.config["tolerance"]["serve_token_regret_rms"]
    regrets, flips, short = [], 0, 0
    for r, out in enumerate(served):
        want = ref["tokens"][r]
        short += len(out) != len(want)
        for i, tok in enumerate(out[:len(want)]):
            row = ref["logits"][r, i]
            regrets.append(float((row[want[i]] - row[int(tok)]) / row.std()))
            if int(tok) != int(want[i]):
                flips += 1
                break
    worst = max(regrets, default=float("inf"))
    run.say("reference_tokens", compared=len(regrets), flips=flips,
            regret_rms_max=worst, short=short,
            served=[list(map(int, o)) for o in served],
            reference=np.asarray(ref["tokens"]).tolist())
    run.check("reference_tokens",
              short == 0 and np.isfinite(worst) and worst <= tol,
              f"{len(regrets)} steps compared, {flips} near-ties turned, "
              f"largest regret {worst} of the row's spread, tolerance "
              f"{tol}; {short} answers of the wrong length")
