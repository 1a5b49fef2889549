"""What the files that test ``LLMEngine`` share (``tests/test_llm_serving.py``
was one file until PR 65, and one xdist worker's 700 s under ``--dist
loadfile``): the local runtime a module of them runs under, an engine at the
tiny float32 configuration of ``tests/served_families.py``'s table, gates
that stop the loop's thread at a chosen point, and the drains of a stream
and of a poller.
"""

import threading
import time

import pytest

import ray_tpu
from ray_tpu import serve
from ray_tpu.serve import _observability as obs
from ray_tpu.serve import llm_engine
from ray_tpu.serve.llm_engine import LLMEngine
from ray_tpu.util import failpoints, metrics
from served_families import FAMILIES


@pytest.fixture(autouse=True, scope="module")
def _runtime():
    ray_tpu.shutdown()
    ray_tpu.init(num_cpus=8)
    yield
    try:
        if ray_tpu.is_initialized():
            serve.shutdown()
    except Exception:
        pass
    ray_tpu.shutdown()


@pytest.fixture(autouse=True)
def _clean_between_tests():
    yield
    failpoints.reset()
    try:
        if ray_tpu.is_initialized():
            serve.shutdown()
    except Exception:
        pass


def _engine_settings(**kw):
    """``kw`` over the few engine shapes these files share: GPT-2 unless a
    family is named, its row's float32 tiny configuration, four slots of 32
    rows, prompts of up to 8 tokens."""
    kw.setdefault("model", "gpt2")
    kw.setdefault("config", FAMILIES[kw["model"]].cfg)
    kw.setdefault("max_batch", 4)
    kw.setdefault("cache_len", 32)
    kw.setdefault("max_prompt_len", 8)
    kw.setdefault("max_new_tokens", 6)
    return kw


def _engine(**kw):
    return LLMEngine(**_engine_settings(**kw))


def _snapshot():
    return obs.parse_prometheus(metrics.prometheus_text())


def _settled(eng):
    """The engine's counters once nothing is dispatched and unread."""
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        st = eng.llm_stats()
        if not st["outstanding"] and not st["active"] and not st["queued"]:
            return st
        time.sleep(0.005)
    raise AssertionError(f"the engine did not settle: {eng.llm_stats()}")


def _serve_all(eng, asked, **submit):
    """Submit all of ``asked`` at once, a poller thread each:
    ``{i: (tokens, last response)}``."""
    got, errors = {}, []

    def one(i, rid):
        try:
            got[i] = _drain(eng, rid)
        except BaseException as e:  # noqa: BLE001
            errors.append(repr(e))

    threads = [threading.Thread(
        target=one, args=(i, eng.llm_submit(prompt, n, **submit)))
        for i, (prompt, n) in asked.items()]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not errors and not any(t.is_alive() for t in threads), errors
    return got


# -- enqueue first, wake later (PR 40) ---------------------------------------
#
# A decode step's token is visible from its append under the lock; the
# stream's poller is told after the next enqueue. These hold the order,
# that no order of set, drain and clear loses or repeats a token, and
# that nobody's wake-up is stranded.


class _LoggedEvent(threading.Event):
    """A stream's event that notes every ``set`` in the test's log."""

    def __init__(self, log, stream):
        super().__init__()
        self._log, self._stream = log, stream

    def set(self):
        self._log.append(("set", self._stream))
        super().set()


def _log_sets(monkeypatch, log):
    real = llm_engine._Stream.__init__

    def init(st):
        real(st)
        st.event = _LoggedEvent(log, st)

    monkeypatch.setattr(llm_engine._Stream, "__init__", init)


class _Gate:
    """A point at which the loop's thread stops until the test lets it
    go on (``let``), or for good (``open``)."""

    def __init__(self):
        self._reached = threading.Semaphore(0)
        self._go = threading.Semaphore(0)
        self._open = False

    def stop(self):
        if not self._open:
            self._reached.release()
            assert self._go.acquire(timeout=30)

    def reached(self):
        assert self._reached.acquire(timeout=30)

    def let(self):
        self._go.release()

    def open(self):
        self._open = True
        self._go.release()

    def shut(self):
        """Stop the loop's thread at its next arrival again."""
        self._open = False


def _stop_before_flush(eng, gate, log=None):
    """The loop's thread stops at ``gate`` between a fan-out that owes
    wake-ups (tokens pending under the lock) and their flush; ``log``
    notes what was owed."""
    real = eng._flush_wakes

    def gated(enqueued):
        if eng._wakes:
            if log is not None:
                log.append(("owed", list(eng._wakes)))
            gate.stop()
        return real(enqueued)

    eng._flush_wakes = gated


def _stop_before_read(eng, gate):
    """The loop's thread stops at ``gate`` before it reads a decode step
    (its sync), with the step after it already enqueued."""
    real = eng._sync

    def gated(d):
        if isinstance(d, llm_engine._Step):
            gate.stop()
        return real(d)

    eng._sync = gated


def _drain(eng, rid, timeout_s=2.0, took=None):
    """Poll one stream to its end: (tokens, last response). ``took``
    collects how long each poll lasted."""
    out = []
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        t0 = time.monotonic()
        resp = eng.llm_next(rid, timeout_s=timeout_s)
        if took is not None:
            took.append(time.monotonic() - t0)
        for chunk in resp["chunks"]:
            out.extend(chunk)
        if resp["done"]:
            return out, resp
    raise AssertionError(f"stream {rid} did not end")


# -- one poller for many streams (``llm_poll(poller=...)``) -------------------


def _poll_to_the_end(eng, pid, rids, timeout_s=2.0, took=None):
    """Drain a poller's streams to their ends with its batched long-poll:
    ``{rid: tokens}``, ``{rid: last response}``, the calls made."""
    out = {rid: [] for rid in rids}
    last, calls = {}, 0
    deadline = time.monotonic() + 60
    while len(last) < len(rids) and time.monotonic() < deadline:
        t0 = time.monotonic()
        resp = eng.llm_poll(poller=pid, timeout_s=timeout_s)
        if took is not None:
            took.append(time.monotonic() - t0)
        calls += 1
        assert resp.pop("held_ns") > 0
        for rid, r in resp.items():
            assert rid not in last, "a stream spoke after its end"
            assert r["chunks"] or r["done"]    # only those with something
            for chunk in r["chunks"]:
                out[rid].extend(chunk)
            if r["done"]:
                last[rid] = r
    assert len(last) == len(rids), "streams did not end"
    return out, last, calls
