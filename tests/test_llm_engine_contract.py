"""The engine's contract for EVERY family it serves (a row of
``tests/served_families.py``'s table): the whole engine against the naive
loop, slots recycled under a queue, a freed slot stepping on at position 0,
a cancel, the ring's wrap, and one compiled shape a program. Split off
``tests/test_llm_serving.py`` in PR 65; the steps-ahead contracts are
``tests/test_llm_steps_ahead.py``'s.
"""

import threading
import time

import pytest

from llm_engine_helpers import (_clean_between_tests, _drain, _engine,
                                _runtime)
from served_families import PROMPT, every_family, generated_alone


@pytest.mark.parametrize("model", ["gpt2", "llama"])
def test_engine_generate_matches_naive(model):
    """The whole engine (admission -> prefill lane -> batched decode)
    reproduces the naive loop (the hybrid family's engine is held to its
    float32 reference in test_nemotron_h.py)."""
    eng = _engine(model=model)
    try:
        want = generated_alone(model, eng.params, PROMPT, 6)
        assert eng.generate(PROMPT, 6) == want
    finally:
        eng.shutdown_engine()


# -- scheduler: slots, admission, deadlines ---------------------------------


@every_family
def test_slot_recycle_and_admission_queue(model):
    """More concurrent requests than slots: the overflow QUEUES (never
    errors), slots recycle as streams finish, and every request gets
    its full generation — the one it would get alone, whatever the slot
    held before it (a K/V row or a recurrent state)."""
    eng = _engine(model=model, max_batch=2, prefill_rows=2)
    try:
        results: dict = {}
        errors: list = []

        def one(i):
            try:
                results[i] = eng.generate([i + 1, 7, 11], 5)
            except Exception as e:  # noqa: BLE001
                errors.append(repr(e))

        threads = [threading.Thread(target=one, args=(i,))
                   for i in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors, errors
        assert len(results) == 8
        for i, got in results.items():
            assert got == generated_alone(model, eng.params,
                                          [i + 1, 7, 11], 5), i
        st = eng.llm_stats()
        assert st["admitted"] == 8          # every request held a slot
        assert st["admitted"] > eng.max_batch  # ... by recycling
        assert st["active"] == 0 and st["queued"] == 0
        assert st["completed"] == 8
    finally:
        eng.shutdown_engine()


@every_family
def test_a_freed_slot_steps_on_at_position_0_and_live_tokens_are_the_same(
        model):
    """Where a slot frees, its position goes back to 0, so that the step's
    attention reads one block of the free slot's ring and not the dead
    request's context (PR 48). What a free slot computes no one reads:
    requests of different lengths on two slots, one ending while the other
    goes on and a third taking the freed slot, get the tokens they get
    with the position left where the dead request stood (the engine as it
    was), which are the tokens each would get alone."""
    asked = {0: ([3, 7, 11], 2), 1: ([4, 7, 11, 2], 12), 2: ([5, 9], 7),
             3: ([6, 1, 8, 8, 2], 4)}

    def served(reset):
        eng = _engine(model=model, max_batch=2, prefill_rows=1,
                      max_new_cap=16)
        if not reset:
            finish = eng._finish_locked

            def leave_the_position(req, *a, slot=None, **kw):
                was = None if slot is None else int(eng._pos[slot])
                finish(req, *a, slot=slot, **kw)
                if slot is not None:
                    eng._pos[slot] = was

            eng._finish_locked = leave_the_position
        got, errors = {}, []

        def one(i, rid):
            try:
                got[i], last = _drain(eng, rid)
                assert not last["error"] and not last["shed"], last
            except BaseException as e:  # noqa: BLE001
                errors.append(repr(e))

        try:
            threads = [threading.Thread(
                target=one, args=(i, eng.llm_submit(prompt, n)))
                for i, (prompt, n) in asked.items()]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
            assert not errors, errors
            assert eng.llm_stats()["completed"] == len(asked)
            return got, [int(p) for p in eng._pos[:eng.max_batch]], \
                eng.params
        finally:
            eng.shutdown_engine()

    with_reset, free_at, params = served(reset=True)
    as_it_was, stood_at, _ = served(reset=False)
    assert free_at == [0, 0] and min(stood_at) > 0
    assert with_reset == as_it_was
    for i, (prompt, n) in asked.items():
        assert with_reset[i] == generated_alone(model, params, prompt, n), i


@every_family
def test_cancel_frees_slot_and_queue(model):
    """llm_cancel drops a queued request and evicts an active one (the
    abandoned-caller path generate() uses on timeout): slot freed,
    stream terminates with a 'cancelled' error, engine keeps serving —
    and the next request in that slot starts from a clean state."""
    eng = _engine(model=model, max_batch=1, prefill_rows=1,
                  max_new_tokens=100, max_new_cap=200,
                  step_throttle_s=0.01)
    try:
        active = eng.llm_submit(PROMPT, 100)
        deadline = time.monotonic() + 30.0
        while eng.llm_stats()["active"] < 1 \
                and time.monotonic() < deadline:
            time.sleep(0.05)  # first prefill compiles; wait for the slot
        assert eng.llm_stats()["active"] == 1
        queued = eng.llm_submit(PROMPT, 4)
        assert eng.llm_cancel(queued)
        assert eng.llm_cancel(active)
        assert not eng.llm_cancel(active)  # already gone
        resp = eng.llm_next(active, timeout_s=2.0)
        assert resp["done"] and resp["error"] == "cancelled"
        other = [7, 1, 30]  # the one slot, reused mid-generation
        assert eng.generate(other, 3) == generated_alone(
            model, eng.params, other, 3)
    finally:
        eng.shutdown_engine()


@every_family
def test_ring_cache_wrap(model):
    """Generation past cache_len wraps the ring cursor (sliding-window
    attention) instead of erroring."""
    eng = _engine(model=model, max_batch=2, cache_len=8, max_prompt_len=8,
                  max_new_tokens=20, max_new_cap=64)
    try:
        out = eng.generate([1, 2, 3], 20)
        assert len(out) == 20
        assert eng.llm_stats()["ring_wraps"] > 0
    finally:
        eng.shutdown_engine()


@every_family
def test_compile_counters_single_shape(model):
    """Assorted prompt lengths and generation lengths all ride the SAME
    two compiled shapes — the no-per-request-recompile claim, asserted
    via trace-time counters: the engine owns two programs and no more."""
    eng = _engine(model=model, max_batch=2)
    try:
        for prompt, n in (([1], 1), ([1, 2, 3], 4), (list(range(1, 9)),
                                                     6), ([9, 9], 2)):
            assert len(eng.generate(prompt, n)) == n
        assert eng.llm_stats()["compiles"] == {"decode": 1, "prefill": 1}
    finally:
        eng.shutdown_engine()
