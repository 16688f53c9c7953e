"""Deterministic interleaving fuzzer tests.

These instrument THIS file (``modules=`` override) so the planted racy
workload below is traced without touching the runtime tree. The two
load-bearing properties: the same seed replays the same per-thread
preemption schedule, and a textbook unguarded read-modify-write is
caught inside a small bounded seed sweep with the failing seed printed
for replay.
"""

import os
import threading

import pytest

from ray_tpu.tools import race
from ray_tpu.tools.race import interleave

#: trace only this test module — the racy workload lives here
_MODULES = (os.path.basename(__file__),)


class _Counter:
    """Deliberately unguarded: the read, compute, and write of ``n``
    sit on separate lines so a preemption can land between them."""

    def __init__(self):
        self.n = 0

    def bump(self, iters):
        for _ in range(iters):
            cur = self.n
            cur = cur + 1
            self.n = cur


def _run_racers(iters=200):
    box = _Counter()
    threads = [threading.Thread(target=box.bump, args=(iters,),
                                name=f"racer-{i}") for i in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return box.n


def _schedule_for(seed):
    race.arm(seed, modules=_MODULES, preempt_prob=0.2,
             max_preemptions=400, trace_current=False)
    try:
        _run_racers()
        return race.schedule()
    finally:
        race.disarm()


def test_same_seed_same_schedule():
    # a thread that an earlier test of this process started while the
    # tracer was armed keeps its trace function, and joins this schedule
    # if it still runs: a ClusterCore's death watch, a GcsServer's health
    # loop. Their owners reap them on shutdown()/close().
    assert not [t.name for t in threading.enumerate()
                if t.name in ("driver-deaths", "gcs-health")]
    first = _schedule_for(7)
    second = _schedule_for(7)
    assert first == second
    assert set(first) == {"racer-0", "racer-1"}
    # the workload is long enough that a 20% preemption rate must fire
    assert all(first[name] for name in first)
    # and every recorded point identifies a line of this file
    fname = os.path.basename(__file__)
    assert all(f == fname for sched in first.values()
               for f, _ in sched)


def test_different_seed_different_schedule():
    # hundreds of independent coin flips per thread: two seeds
    # colliding would mean the rng ignores the seed
    assert _schedule_for(7) != _schedule_for(8)


def test_planted_race_caught_in_bounded_sweep(capsys):
    def attempt():
        total = _run_racers(200)
        assert total == 400, f"lost updates: {total} != 400"

    with pytest.raises(AssertionError):
        race.sweep(attempt, range(5), modules=_MODULES,
                   preempt_prob=0.2, max_preemptions=2000)
    err = capsys.readouterr().err
    assert "rtpu-race: seed" in err
    assert f"replay with {interleave.ENV}=" in err
    # sweep disarmed in its finally even though the attempt raised
    assert race.schedule() == {}


def test_parse_env():
    assert race.parse_env("7") == (7, 1)
    assert race.parse_env("7:20") == (7, 20)
    assert race.parse_env(" 3 ") == (3, 1)
    assert race.parse_env("") is None
    assert race.parse_env("junk") is None
    assert race.parse_env("3:x") is None


def test_arm_from_env(monkeypatch):
    monkeypatch.delenv(interleave.ENV, raising=False)
    assert race.arm_from_env(modules=_MODULES) is None

    monkeypatch.setenv(interleave.ENV, "11:4")
    try:
        assert race.arm_from_env(modules=_MODULES,
                                 trace_current=False) == 11
    finally:
        race.disarm()
