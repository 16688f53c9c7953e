"""The tiny serving engines of the suite, built once.

Constructing an engine compiles every program it can run
(``LLMEngine._precompile``), seconds each on the CPU, and nearly every
engine test wants the same tiny model. So a test module has one dense
and one paged engine, handed out idle to each test that asks; a test
that needs other construction arguments builds its own through
``private_engine`` and asks for the fewest programs its assertions need.

Sharing rules for a test on a shared engine: request ids its module
uses nowhere else (the engine drops a second submit of an id),
``stats()`` and the paged engine's counters as deltas, never absolutes,
and prompts of its own wherever it asserts on prefix-cache hits (the
page cache outlives the test).
"""

import contextlib
import time

import pytest

# top_k only gates sampled rows; greedy rows are the argmax either way
TINY = dict(model_config={"preset": "tiny"}, num_slots=4, max_len=96,
            prefill_buckets=[16], max_new_tokens=8, chunk_steps=4, top_k=20)
PAGE = 8
# the fewest programs an engine can have: one prefill bucket at one
# batch size, one decode chunk length
SMALLEST = dict(num_slots=1, max_len=16, prefill_buckets=[15],
                max_new_tokens=8, chunk_steps=1)


def drain(engine, reqs, timeout_s=120):
    """Submit ``reqs`` — (req_id, prompt, submit kwargs) — and collect
    until each has an answer."""
    for rid, prompt, kw in reqs:
        engine.submit(rid, prompt, **kw)
    ids = [rid for rid, _, _ in reqs]
    out = {}
    deadline = time.monotonic() + timeout_s
    while len(out) < len(ids) and time.monotonic() < deadline:
        out.update(engine.collect(ids))
        time.sleep(0.01)
    return out


def tokens(out):
    return {rid: res["tokens"] for rid, res in out.items()}


@contextlib.contextmanager
def private_engine(cls, **kw):
    eng = cls(**kw)
    try:
        yield eng
    finally:
        eng.shutdown()


def _hand_out_idle(eng):
    """Through the public surface only: the test gets an engine with
    every slot free and an empty mailbox, and whatever it leaves running
    is cancelled before the next test sees the engine."""
    yield eng
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        for rid, view in eng.peek().items():
            if not view["done"]:
                eng.cancel(rid)
        eng.collect()
        st = eng.stats()
        if not (st["active"] or st["queued"] or st["inflight_chunks"]):
            return
        time.sleep(0.01)
    raise AssertionError(f"shared engine never went idle: {eng.stats()}")


@pytest.fixture(scope="module")
def _dense_engine():
    from ray_tpu.serve.llm_engine import LLMEngine

    with private_engine(LLMEngine, **TINY) as eng:
        yield eng


@pytest.fixture(scope="module")
def _paged_engine():
    from ray_tpu.serve.paged_engine import PagedLLMEngine

    with private_engine(PagedLLMEngine, page_size=PAGE, **TINY) as eng:
        yield eng


@pytest.fixture
def dense_engine(_dense_engine):
    yield from _hand_out_idle(_dense_engine)


@pytest.fixture
def paged_engine(_paged_engine):
    yield from _hand_out_idle(_paged_engine)
