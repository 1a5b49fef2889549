"""One step ahead (PR 55), for every served family: the loop enqueues step
n + 1 before it reads step n. Split off ``tests/test_llm_serving.py`` in
PR 65.
"""

import threading
import time

import numpy as np
import pytest

from ray_tpu.util import failpoints
from llm_engine_helpers import (_clean_between_tests, _drain, _engine, _Gate,
                                _runtime, _serve_all, _settled,
                                _stop_before_read)
from served_families import PROMPT, every_family, generated_alone


# -- one step ahead (PR 55) ----------------------------------------------------
#
# The loop enqueues step n + 1 before it reads step n. These four hold, for
# every served family and against each request generated ALONE, that every
# live slot is still handed the token and position it would be handed by a
# loop that read first: with slots ending by count and by end token under
# an unread step, a slot changing hands under one, a dispatch that raises
# over one, and the compile count after all of it. One engine a family
# serves all four, in this order, so the last one's counts cover the lot.


@pytest.fixture(scope="module")
def ahead_engine():
    engines = {}

    def get(model):
        if model not in engines:
            eng = engines[model] = _engine(
                model=model, max_batch=2, prefill_rows=2, max_new_cap=16)
            # A host array on a 64-byte boundary is handed to the CPU's
            # runtime WITHOUT a copy, and a program that runs later (behind
            # an unread step) reads it as it is then: the engine has to
            # hand over positions that its fan-out will not move.
            raw = np.zeros(eng.max_batch + 1 + 16, np.int32)
            at = (-raw.ctypes.data % 64) // 4
            eng._pos = raw[at:at + eng.max_batch + 1]
            assert eng._pos.ctypes.data % 64 == 0
        return engines[model]

    yield get
    for eng in engines.values():
        eng.shutdown_engine()


def _alone(model, eng, asked):
    """What each ``(prompt, n)`` of ``asked`` gets generated alone."""
    return {i: generated_alone(model, eng.params, prompt, n)
            for i, (prompt, n) in asked.items()}


@every_family
def test_slots_end_by_count_and_by_end_token_under_an_unread_step(
        model, ahead_engine):
    """Eight requests of staggered lengths on two slots, and an end token
    the model emits: a slot that ends by count is known to the host before
    the step is read, one that ends by its end token is stepped once more
    (the row is dropped); each successor gets the tokens it gets alone."""
    eng = ahead_engine(model)
    asked = {i: ([i + 1, 7, 11][:1 + i % 3] + [2 + i], 2 + (3 * i) % 7)
             for i in range(8)}
    alone = _alone(model, eng, asked)

    def cut(toks, eos):
        return toks[:toks.index(eos) + 1] if eos in toks else toks

    # the end token that cuts some generations short and leaves others
    # their count
    def kinds(eos):
        short = sum(len(cut(t, eos)) < len(t) for t in alone.values())
        return min(short, len(alone) - short)

    eos = max({t for toks in alone.values() for t in toks}, key=kinds)
    assert kinds(eos) >= 1, alone
    before = _settled(eng)
    eng.eos_token = eos
    try:
        got = _serve_all(eng, asked)
    finally:
        eng.eos_token = None
    st = _settled(eng)
    for i, (tokens, last) in got.items():
        assert not last["error"] and not last["shed"], last
        assert tokens == cut(alone[i], eos), (i, eos)
    assert st["completed"] - before["completed"] == len(asked)
    # every generation cut short had a step enqueued for its next token
    short = sum(len(cut(t, eos)) < len(t) for t in alone.values())
    dropped = st["rows_dropped"] - before["rows_dropped"]
    assert dropped >= short if eng._drafting else dropped == short, (
        dropped, short, eos, alone)
    assert st["steps_ahead"] > before["steps_ahead"]


@every_family
def test_a_slot_changes_hands_under_an_unread_step(model, ahead_engine):
    """A cancel, then a deadline's eviction, land between a step's
    dispatch and its read, with a request queued for the slot: it is
    admitted at once, its stream holds its own tokens only, the other
    slot's stream goes on undisturbed, and the rows the steps computed for
    the request that left are counted as dropped."""
    eng = ahead_engine(model)
    asked = {"stays": ([4, 7, 11, 2], 14), "heir": ([9, 1, 8], 5),
             "heir2": ([6, 6, 3, 1, 2], 4)}
    alone = _alone(model, eng, asked)
    before_read = _Gate()
    host = eng._sync
    _stop_before_read(eng, before_read)
    got, errors = {}, []

    def one(i, rid):
        try:
            got[i] = _drain(eng, rid)
        except BaseException as e:  # noqa: BLE001
            errors.append(repr(e))

    def submit(i):
        rid = eng.llm_submit(*asked[i])
        t = threading.Thread(target=one, args=(i, rid))
        t.start()
        return t

    try:
        st0 = _settled(eng)
        threads = [submit("stays")]
        leaves = eng.llm_submit(PROMPT, 16)
        # both decoding: a step for both is dispatched and unread, and the
        # step after it is enqueued
        for _ in range(2):
            before_read.reached()
            before_read.let()
        before_read.reached()
        assert eng.llm_stats()["active"] == 2
        threads.append(submit("heir"))                # queued for a slot
        assert eng.llm_cancel(leaves)
        for _ in range(3):   # the two steps computed a row for it; a third
            before_read.let()                         # holds the heir
            before_read.reached()
        st1 = eng.llm_stats()
        assert st1["rows_dropped"] - st0["rows_dropped"] == 2
        # ... and a deadline that dies under an unread step: the step read
        # now still hands its token out, the next select evicts
        evicted = eng.llm_submit([3, 3, 5], 16)
        threads.append(submit("heir2"))               # queued behind it
        before_read.open()
        threads[1].join(timeout=60)                   # the heir ends
        # (evicted holds the heir's slot now, or will; wait for its token)
        assert eng.llm_next(evicted, timeout_s=30.0)["chunks"]
        before_read.shut()
        before_read.reached()
        [victim] = [r for r in eng._slot_req
                    if r is not None and r.prompt == [3, 3, 5]]
        victim.deadline_ts = time.time() - 1.0
        dropped = eng.llm_stats()["rows_dropped"]
        before_read.open()
        tokens, last = _drain(eng, evicted)
        assert last["shed"] == "decode", last
        for t in threads:
            t.join(timeout=60)
        assert not errors and not any(t.is_alive() for t in threads), errors
        st2 = _settled(eng)
    finally:
        before_read.open()
        eng._sync = host
    for i, (tokens, last) in got.items():
        assert not last["error"] and not last["shed"], (i, last)
        assert tokens == alone[i], i
    # the one step enqueued before the eviction computed a row for it
    assert st2["rows_dropped"] - dropped == 1
    assert st2["shed"] - st0["shed"] == 1


@every_family
def test_a_dispatch_that_raises_loses_no_token_of_the_step_before(
        model, ahead_engine):
    """``serve.llm.before_step`` raises with a step dispatched and unread:
    that step's tokens are delivered. Once, and the stream goes on to its
    end with the tokens it gets alone; armed for good, three in a row fail
    the streams, with a prefix of them, and nothing is left unread."""
    eng = ahead_engine(model)
    asked = {"once": ([2, 9, 4], 9), "for_good": ([8, 1, 1, 6], 12),
             "after": ([5, 5, 2], 4)}
    alone = _alone(model, eng, asked)
    before_read = _Gate()
    host = eng._sync
    _stop_before_read(eng, before_read)
    try:
        st0 = _settled(eng)
        for i, arm in (("once", "raise,once"), ("for_good", "raise")):
            rid = eng.llm_submit(*asked[i])
            before_read.reached()         # a step unread, the next enqueued
            before_read.let()
            before_read.reached()
            failpoints.arm("serve.llm.before_step", arm)
            before_read.open()
            tokens, last = _drain(eng, rid)
            failpoints.reset()
            st = _settled(eng)            # nothing outstanding and unread
            if i == "once":
                assert not last["error"] and tokens == alone[i], last
                assert st["errors"] - st0["errors"] == 1
            else:
                assert "decode step failing repeatedly" in last["error"]
                # the first token, two steps read at the gate, and the step
                # that was on the device when the first dispatch raised
                assert len(tokens) >= 4 and tokens == alone[i][:len(tokens)]
                assert st["errors"] - st0["errors"] == 1 + 3 + 1
            before_read.shut()
        before_read.open()
        assert eng.generate(*asked["after"]) == alone["after"]  # recovered
    finally:
        failpoints.reset()
        before_read.open()
        eng._sync = host


@every_family
def test_nothing_is_outstanding_when_the_last_request_ends(
        model, ahead_engine):
    """After all of the above on this engine (whichever ran): the last
    stream ends by its end token with a step enqueued behind it, the loop
    reads that step before it waits, the steps ahead never outnumber the
    steps, and each program was compiled and cached ONCE, helpers
    included: every call presented the same kinds of argument."""
    eng = ahead_engine(model)
    asked = {0: ([7, 2, 9, 4], 10)}
    alone = _alone(model, eng, asked)[0]
    eng.eos_token = alone[4]
    try:
        tokens = eng.generate(*asked[0])
    finally:
        eng.eos_token = None
    assert tokens == alone[:alone.index(alone[4]) + 1]
    st = _settled(eng)
    assert st["outstanding"] == 0 and st["active"] == 0
    assert 0 < st["steps_ahead"] <= st["steps"]
    assert st["rows_dropped"] >= 1
    assert st["compiles"] == {"decode": 1, "prefill": 1}
    assert [f._cache_size() for f in (
        eng._step_fn, eng._prefill_fn, eng._carry_fn, eng._put_fn)] \
        == [1, 1, 1, 1]
