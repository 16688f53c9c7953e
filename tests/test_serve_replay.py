"""Serving-plane request fault tolerance: exactly-once replay,
mid-stream resume, gray-replica ejection (serve/retry.py + router).

Chaos model: replicas are killed mid-flight — synthetically via the
``serve_replica_kill`` / ``stream_resume`` fault sites (deterministic,
fires in the router's process) and genuinely via SIGKILL under an
RTPU_NETEM seed sweep — and replay-safe requests must see zero errors,
zero duplicate side effects, and exact token-stream splices at the
resume watermark.
"""

from __future__ import annotations

import os
import pickle
import signal
import time

import pytest

from ray_tpu import serve
from ray_tpu.core import fault_injection, netem
from ray_tpu.core.config import config
from ray_tpu.exceptions import ActorDiedError, ReplicaUnavailableError
from tests.conftest import own_runtime

# ------------------------------------------------------------ unit layer


def test_replica_unavailable_error_pickle_roundtrip():
    cause = ActorDiedError("replica gone", cause="oom")
    e = ReplicaUnavailableError(deployment="d", attempts=3,
                                last_cause=cause)
    e2 = pickle.loads(pickle.dumps(e))
    assert e2.attempts == 3 and e2.deployment == "d"
    assert isinstance(e2.last_cause, ActorDiedError)
    assert str(e2) == str(e) and "3 attempt" in str(e2)
    # legacy no-attempts shape keeps its message through the round-trip
    e3 = pickle.loads(pickle.dumps(ReplicaUnavailableError(deployment="d")))
    assert e3.attempts == 0 and "no running replicas" in str(e3)


def test_request_ledger_counts_replays():
    from ray_tpu.serve.retry import RequestLedger

    led = RequestLedger()
    n1, n2 = led.open(), led.open()
    assert n1 != n2
    led.note_attempt(n1, "r1")
    led.note_attempt(n1, "r2")  # a replay
    led.note_attempt(n2, "r1")
    assert led.stats() == {"open": 2, "opened": 2, "replayed": 1}
    led.close(n1)
    led.close(n1)  # idempotent
    assert led.stats()["open"] == 1


def test_replica_health_streak_and_cooldown():
    from ray_tpu.serve.retry import ReplicaHealth

    h = ReplicaHealth()
    for _ in range(ReplicaHealth.STREAK_LIMIT - 1):
        assert not h.note_failure("r1")
    h.note_ok("r1")  # success clears the streak
    for _ in range(ReplicaHealth.STREAK_LIMIT - 1):
        assert not h.note_failure("r1")
    assert h.note_failure("r1")  # streak hit the limit: ejected
    assert h.is_ejected("r1")
    assert h.ejected_ids() == ["r1"]
    assert h.filter([("r1", 0), ("r2", 0)]) == [("r2", 0)]
    # the filter never empties the candidate set
    assert h.filter([("r1", 0)]) == [("r1", 0)]
    # cooldown expiry restores (hysteresis: it re-ejects on new signal)
    later = time.monotonic() + ReplicaHealth.COOLDOWN_S + 1
    assert not h.is_ejected("r1", now=later)
    assert not h.ejected_ids() or h.ejected_ids() != ["r1"]


def test_replica_health_ttft_outlier_vs_median():
    from ray_tpu.serve.retry import ReplicaHealth

    h = ReplicaHealth()
    snap = {"slow": (0.5, 10), "f1": (0.01, 10), "f2": (0.012, 10)}
    assert h.note_ttft("slow", snap, ratio=3.0)
    assert h.is_ejected("slow")
    # under-observed replicas never eject (own or peer side)
    assert not ReplicaHealth().note_ttft(
        "slow", {"slow": (0.5, 2), "f1": (0.01, 10)}, 3.0)
    assert not ReplicaHealth().note_ttft(
        "slow", {"slow": (0.5, 10), "f1": (0.01, 1)}, 3.0)
    # microsecond-scale spread stays under the absolute excess floor
    assert not ReplicaHealth().note_ttft(
        "a", {"a": (0.004, 10), "b": (0.001, 10)}, 3.0)


def test_ttft_estimator_snapshot_counts():
    from ray_tpu.serve.qos import TtftEstimator

    t = TtftEstimator(0.5)
    t.observe("r1", 0.1)
    t.observe("r1", 0.2)
    t.observe("r2", 0.05)
    snap = t.snapshot()
    assert snap["r1"][1] == 2 and snap["r2"][1] == 1
    assert snap["r1"][0] == pytest.approx(0.15)
    t.drop_replica("r1")
    assert "r1" not in t.snapshot()


def test_resume_call_rebuilds_prompt_and_budget():
    from ray_tpu.serve.router import Router

    # positional shape: prompt grows by the watermark, budget shrinks
    args, _ = Router._resume_call(([0, 1, 2, 3], 10), {}, [7, 8, 9])
    assert args[0] == [0, 1, 2, 3, 7, 8, 9] and args[1] == 7
    # kwarg shape
    _, k2 = Router._resume_call(
        (), {"prompt_tokens": [1], "max_new_tokens": 4}, [5, 6])
    assert k2["prompt_tokens"] == [1, 5, 6] and k2["max_new_tokens"] == 2
    # watermark at the budget: the stream is already complete
    assert Router._resume_call(([1], 3), {}, [4, 5, 6]) == (None, None)
    # nothing delivered yet: the call is unchanged
    assert Router._resume_call(([1, 2], 5), {}, []) == (([1, 2], 5), {})


# --------------------------------------------------------- cluster layer


@pytest.fixture(scope="module")
def replay_ray():
    with own_runtime(4):
        yield


@pytest.fixture
def replay_on():
    os.environ["RTPU_SERVE_REQUEST_REPLAY"] = "1"
    config.reload()
    yield
    fault_injection.clear()
    del os.environ["RTPU_SERVE_REQUEST_REPLAY"]
    config.reload()


@pytest.fixture
def affinity_toggle(request):
    if request.param:
        os.environ["RTPU_SERVE_CACHE_AFFINITY"] = "1"
        config.reload()
    yield request.param
    if request.param:
        del os.environ["RTPU_SERVE_CACHE_AFFINITY"]
        config.reload()


def test_replay_unary_lost_request(replay_ray, replay_on):
    """``die`` = the request is lost before dispatch: the replay re-picks
    and the client sees a normal result, not an error."""
    @serve.deployment(name="lostreq", num_replicas=1)
    def double(x):
        return x * 2

    handle = serve.run(double)
    assert handle.remote(1).result(timeout=30) == 2
    fault_injection.inject("serve_replica_kill", "die", "lostreq", times=1)
    assert handle.remote(5).result(timeout=30) == 10


def test_replay_unary_exactly_once_lost_reply(replay_ray, replay_on):
    """``die_after`` = the call EXECUTED but the reply was lost: the
    replay must return the recorded result via the replica-side nonce
    memo, not re-run the side effect."""
    @serve.deployment(name="once", num_replicas=1)
    class Once:
        def __init__(self):
            self.calls = 0

        def __call__(self, x):
            self.calls += 1
            return x * 2

        def count(self):
            return self.calls

    handle = serve.run(Once.bind())
    assert handle.remote(1).result(timeout=30) == 2
    fault_injection.inject("serve_replica_kill", "die_after", "once",
                           times=1)
    assert handle.remote(21).result(timeout=30) == 42
    fault_injection.clear()
    # warm-up + replayed request: the callable ran exactly twice
    assert handle.count.remote().result(timeout=30) == 2


def test_replay_budget_exhausted_is_typed(replay_ray, replay_on):
    os.environ["RTPU_SERVE_REPLAY_MAX_ATTEMPTS"] = "2"
    config.reload()
    try:
        @serve.deployment(name="exh", num_replicas=1)
        def f(x):
            return x

        handle = serve.run(f)
        assert handle.remote(0).result(timeout=30) == 0
        fault_injection.inject("serve_replica_kill", "die", "exh",
                               times=-1)
        with pytest.raises(ReplicaUnavailableError) as ei:
            handle.remote(1).result(timeout=60)
        assert ei.value.attempts == 2
        assert isinstance(ei.value.last_cause, ActorDiedError)
        assert "2 attempt" in str(ei.value)
    finally:
        fault_injection.clear()
        del os.environ["RTPU_SERVE_REPLAY_MAX_ATTEMPTS"]
        config.reload()


def test_replay_batch_members_dedup(replay_ray, replay_on):
    """handle_batch may fully or partially execute before the reply is
    lost; the replayed batch must dedup member-by-member."""
    @serve.deployment(name="bdedup", max_batch_size=4,
                      batch_wait_timeout_s=0.05, num_replicas=1)
    class BatchCounter:
        def __init__(self):
            self.seen = []

        def __call__(self, items):
            self.seen.extend(items)
            return [i + 100 for i in items]

        def seen_items(self):
            return list(self.seen)

    handle = serve.run(BatchCounter.bind())
    assert handle.remote(0).result(timeout=30) == 100
    fault_injection.inject("serve_replica_kill", "die_after", "bdedup",
                           times=1)
    futs = [handle.remote(i) for i in range(1, 5)]
    assert [f.result(timeout=60) for f in futs] == [101, 102, 103, 104]
    fault_injection.clear()
    # every member executed exactly once across the original + replay
    seen = handle.seen_items.remote().result(timeout=30)
    assert sorted(seen) == [0, 1, 2, 3, 4]


@pytest.mark.parametrize("affinity_toggle", [False, True], indirect=True,
                         ids=["affinity_off", "affinity_on"])
def test_chaos_sigkill_rounds_zero_lost_requests(replay_ray, replay_on,
                                                 affinity_toggle):
    """Chaos drill: a replica SIGKILLed every round under an RTPU_NETEM
    seed, sustained unary+batch traffic — zero client-visible errors and
    zero duplicate side effects for replay-safe requests."""
    seed = 33 if affinity_toggle else 7
    name = f"chaos{int(affinity_toggle)}"

    @serve.deployment(name=name, num_replicas=2)
    class Victim:
        def __init__(self):
            self.seen = []

        def __call__(self, x):
            self.seen.append(x)
            return x * 2 + 1

        def pid(self):
            return os.getpid()

        def dupes(self):
            return sorted(x for x in set(self.seen)
                          if self.seen.count(x) > 1)

    handle = serve.run(Victim.bind())
    netem.load_env({"RTPU_NETEM": f"{seed}:node->node=delay,ms=1,jitter=2"})
    try:
        killed = set()
        base = 0
        for round_no in range(2):
            pids = set()
            deadline = time.monotonic() + 60
            while len(pids) < 2 and time.monotonic() < deadline:
                pids.add(handle.pid.remote().result(timeout=30))
            assert len(pids) == 2, "deployment never reached 2 replicas"
            victim = sorted(pids - killed)[0]
            futs = [handle.remote(base + i) for i in range(10)]
            os.kill(victim, signal.SIGKILL)
            killed.add(victim)
            outs = [f.result(timeout=60) for f in futs]
            assert outs == [(base + i) * 2 + 1 for i in range(10)]
            base += 10
            # wait for the controller to replace the corpse before the
            # next round (pin 2 running so the kill has a survivor)
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline:
                if serve.status()[name]["running"] >= 2:
                    break
                time.sleep(0.3)
        # zero duplicate side effects: each replica's own log holds
        # every request at most once (replays to the same replica were
        # memo hits, not re-executions); sample both survivors
        for _ in range(8):
            assert handle.dupes.remote().result(timeout=30) == []
    finally:
        netem.clear()


@pytest.mark.parametrize("affinity_toggle", [False, True], indirect=True,
                         ids=["affinity_off", "affinity_on"])
def test_stream_resume_exact_splice(replay_ray, replay_on,
                                    affinity_toggle):
    """Mid-stream replica loss (injected ``stream_resume``): the client
    stream must splice at the delivered-token watermark with no
    duplicated or missing tokens vs the uninterrupted transcript."""
    from ray_tpu.serve.llm_engine import LLMEngine

    name = f"llmres{int(affinity_toggle)}"
    dep = serve.deployment(name=name, engine=True, num_cpus=0.1)(
        LLMEngine).bind(
        model_config={"preset": "tiny"}, num_slots=2, max_len=32,
        prefill_buckets=[31], max_new_tokens=12, chunk_steps=1)
    handle = serve.run(dep, timeout=300)

    prompt = [5, 11, 2]
    reference = handle.remote(prompt).result(timeout=300)["tokens"]
    assert len(reference) == 12

    fault_injection.inject("stream_resume", "drop", name, times=1)
    chunks = list(handle.stream(prompt, 12))
    fault_injection.clear()
    streamed = [t for c in chunks for t in c]
    # greedy decoding: the resumed generation must continue the exact
    # transcript — same tokens, same count, spliced at the watermark
    assert streamed == reference


def test_engine_poll_replica_death_redispatches(replay_ray):
    """Satellite regression (FLAG OFF): a SIGKILLed engine replica must
    not surface raw exceptions to callers when a healthy replica exists
    — the seed's _poll_engine cleared st["futures"] and failed every
    in-flight engine request with the collect error."""
    assert not config.serve_request_replay  # seed-default path
    from ray_tpu.serve.llm_engine import LLMEngine

    class KillableEngine(LLMEngine):
        def pid(self):
            return os.getpid()

    dep = serve.deployment(name="llmkill", engine=True, num_cpus=0.1,
                           num_replicas=2)(KillableEngine).bind(
        model_config={"preset": "tiny"}, num_slots=4, max_len=16,
        prefill_buckets=[15], max_new_tokens=8, chunk_steps=1)
    handle = serve.run(dep, timeout=300)

    pids = set()
    deadline = time.monotonic() + 120
    while len(pids) < 2 and time.monotonic() < deadline:
        pids.add(handle.pid.remote().result(timeout=60))
    assert len(pids) == 2

    futs = [handle.remote([5, 11, 2, i]) for i in range(6)]
    time.sleep(0.5)  # submits land; some generations sit on the victim
    os.kill(sorted(pids)[0], signal.SIGKILL)
    outs = [f.result(timeout=180) for f in futs]
    assert all(len(o["tokens"]) == 8 for o in outs)


def test_gray_replica_ejected_and_replaced(replay_ray):
    """A slow-but-alive (gray) replica: the router's TTFT outlier
    scoring ejects it from picks (p99 recovers), its gray report reaches
    the controller, and the controller probes + replaces it."""
    os.environ["RTPU_SERVE_REPLICA_EJECTION"] = "1"
    config.reload()
    try:
        @serve.deployment(name="gray", num_replicas=2)
        class SlowOnDemand:
            def __init__(self):
                self.slow = False

            def __call__(self, x):
                if self.slow:
                    time.sleep(0.3)
                return os.getpid()

            def make_slow(self):
                self.slow = True
                return os.getpid()

        handle = serve.run(SlowOnDemand.bind())
        pids = set()
        deadline = time.monotonic() + 60
        while len(pids) < 2 and time.monotonic() < deadline:
            pids.add(handle.remote(0).result(timeout=30))
        assert len(pids) == 2
        slow_pid = handle.make_slow.remote().result(timeout=30)

        # drive sequential traffic until the outlier ejects: picks stop
        # landing on the gray replica and tail latency recovers
        served = []
        for i in range(60):
            t0 = time.monotonic()
            served.append(handle.remote(i).result(timeout=30))
            if (len(served) >= 10
                    and set(served[-10:]) == (pids - {slow_pid})
                    and time.monotonic() - t0 < 0.2):
                break
        assert set(served[-5:]) == pids - {slow_pid}, (
            f"gray replica {slow_pid} still receiving picks: "
            f"{served[-10:]}")

        # the controller replaces the persistently gray replica (light
        # traffic keeps the router's gray report renewed)
        deadline = time.monotonic() + 45
        replaced = False
        while time.monotonic() < deadline:
            now_pids = {handle.remote(0).result(timeout=30)
                        for _ in range(6)}
            if slow_pid not in now_pids and len(now_pids) == 2:
                replaced = True
                break
            time.sleep(0.5)
        assert replaced, "gray replica was not replaced by the controller"
    finally:
        del os.environ["RTPU_SERVE_REPLICA_EJECTION"]
        config.reload()
