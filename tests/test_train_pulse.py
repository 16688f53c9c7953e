"""The train loop's pulse (``ray_tpu/train/pulse.py``): the classification
on synthetic tick series, without a clock, and the thread through
``_TrainSession`` and ``JaxTrainer.fit``. No test here asserts an exact
count of waits or a time under load: the suite runs six workers wide."""
import json
import os
import subprocess
import sys
import threading
import time

import pytest

from ray_tpu.train import pulse
from ray_tpu.train.pulse import KEEP_EVENTS, TICK_NS, Rhythm, classify

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MS = 1_000_000


class Series:
    """Builds ``(monotonic_ns, loop_cpu_ns, proc_cpu_ns, passes)`` ticks:
    the loop comes to its line and works, waits, or the whole pulse comes
    late."""

    def __init__(self):
        self.t = self.cpu = self.proc = self.passes = 0
        self.ticks = [(0, 0, 0, 0)]

    def _tick(self, dt=TICK_NS, cpu=0, proc=0, passes=0):
        self.t += dt
        self.cpu += cpu
        self.proc += proc
        self.passes += passes
        self.ticks.append((self.t, self.cpu, self.proc, self.passes))

    def work(self, cpu_ms=2.0, passes=1):
        """One tick in which the loop thread ran for ``cpu_ms`` and came
        to a counted line."""
        self._tick(cpu=int(cpu_ms * MS), proc=int(cpu_ms * MS),
                   passes=passes)
        return self

    def wait(self, ms):
        for _ in range(int(ms * MS) // TICK_NS):
            self._tick()
        return self

    def late(self, ms, proc_ms=0.0, cpu_ms=0.0):
        """One tick that comes ``ms`` late."""
        self._tick(dt=TICK_NS + int(ms * MS), cpu=int(cpu_ms * MS),
                   proc=int(proc_ms * MS))
        return self

    def steps(self, n, wait_ms=300, cpu_ms=2.0):
        for _ in range(n):
            self.work(cpu_ms).wait(wait_ms)
        return self


def _named(events, name):
    return [e for e in events if e["name"] == name]


def _feed(rhythm, series, start=0):
    """The events of ``series``'s ticks from ``start`` on."""
    return [e for tick in series.ticks[start:] for e in rhythm.tick(*tick)]


def test_a_steady_rhythm_gives_waits_and_no_stall():
    events, r = classify(Series().steps(40).work().ticks,
                         where=lambda arm: "loop.py:7 train")
    assert not _named(events, "rtpu.train.stall")
    assert not _named(events, "rtpu.proc.pause")
    waits = _named(events, "rtpu.train.wait")
    assert len(waits) == 40 == r.main_waits
    assert all(e["place"] == "loop.py:7 train" for e in waits)
    # a wait runs from the tick that saw the loop come to its line to the
    # tick that sees it come again
    assert {round((e["end"] - e["start"]) / MS, 3) for e in waits} == {310.0}
    s = r.summary()
    assert s["place"] == "loop.py:7 train" and s["waits"] == 40
    assert s["wait_ms_p50"] == pytest.approx(310.0)
    assert s["wait_ms_max"] == pytest.approx(310.0)
    assert s["cpu_ms_mean"] == pytest.approx(2.0)
    assert s["loop_cpu_ms"] == pytest.approx(82.0)
    assert (s["stalls"], s["stalled_ms"], s["pauses"]) == (0, 0.0, 0)
    assert s["ticks"] == len(Series().steps(40).work().ticks)
    assert "cpu_ms_p50" not in s and "elsewhere" not in s


def test_one_wait_of_four_times_its_length_is_one_stall():
    readings = iter(range(1000))
    series = Series().steps(12).work().wait(1200).steps(6).work()
    events, r = classify(
        series.ticks, where=lambda arm: "loop.py:7 train",
        readings=lambda: {"majflt": 10 * next(readings)},
        brim=lambda: {"bytes_in_use": 15, "bytes_limit": 16})
    (stall,) = _named(events, "rtpu.train.stall")
    assert stall["usual_ms"] == pytest.approx(310.0)
    assert stall["waited_ms"] == pytest.approx(1210.0)
    assert stall["place"] == "loop.py:7 train"
    assert stall["paused_ms"] == 0.0
    assert stall["proc_cpu_ms"] == pytest.approx(2.0)   # the closing tick's
    # the readings' change from the wait's second tick to its end
    assert stall["majflt"] == 10
    assert (stall["bytes_in_use"], stall["bytes_limit"]) == (15, 16)
    assert (stall["end"] - stall["start"]) / MS == pytest.approx(1210.0)
    s = r.summary()
    assert s["stalls"] == 1 and s["stalled_ms"] == pytest.approx(900.0)
    assert s["wait_ms_max"] == pytest.approx(1210.0)
    assert s["wait_ms_p50"] == pytest.approx(310.0)     # the usual stays
    assert len(_named(events, "rtpu.train.wait")) == 19


@pytest.mark.parametrize("earlier,stalls", [(7, 0), (8, 1)])
def test_a_place_needs_eight_earlier_waits_before_it_can_stall(earlier,
                                                               stalls):
    series = Series().steps(earlier).work().wait(1200).work()
    events, r = classify(series.ticks)
    assert len(_named(events, "rtpu.train.stall")) == stalls == r.stalls


@pytest.mark.parametrize("wait_ms,stalls", [
    (300 + 240, 0),      # 1.8 times the usual, but under 250 ms more
    (300 + 260, 1)])
def test_a_stall_is_both_longer_by_half_and_by_a_quarter_second(wait_ms,
                                                                stalls):
    events, _ = classify(Series().steps(10).work().wait(wait_ms).work().ticks)
    assert len(_named(events, "rtpu.train.stall")) == stalls
    # and a long step's wait of 1.3 times its length is none
    events, _ = classify(
        Series().steps(10, wait_ms=2000).work().wait(2600).work().ticks)
    assert not _named(events, "rtpu.train.stall")


def test_each_place_has_its_own_usual_length():
    at = ["a.py:1 f"]
    r = Rhythm(where=lambda arm: at[0])
    series = Series().steps(10, wait_ms=100).work()
    events = _feed(r, series)
    at[0], n = "b.py:2 g", len(series.ticks)
    events += _feed(r, series.wait(900).steps(9, wait_ms=900).work(), n)
    at[0], n = "a.py:1 f", len(series.ticks)
    events += _feed(r, series.wait(900).work(), n)
    (stall,) = _named(events, "rtpu.train.stall")     # 900 ms at a.py
    assert stall["place"] == "a.py:1 f"
    assert stall["usual_ms"] == pytest.approx(110.0)
    assert r.main_place() == "b.py:2 g"
    assert {e["place"] for e in _named(events, "rtpu.train.wait")} \
        <= {"a.py:1 f", "b.py:2 g"}
    assert r.places["a.py:1 f"].count == 11
    assert r.places["b.py:2 g"].count == 10 == r.main_waits
    # the loop thread's work for a step is read from wait to wait at one
    # place: what lay between the two places is no step's
    assert r.summary()["cpu_ms_mean"] == pytest.approx(2.0)
    assert r.places["a.py:1 f"].cpu_n == 9


def test_a_clock_that_says_nothing_changes_nothing():
    """The chips' machines advance a thread's CPU clock in steps of 10 ms:
    most steps' work shows no CPU at all and some waits show 10 ms that
    were none of theirs. The loop's arrivals delimit the waits, and the
    clock does not."""
    s = Series()
    for i in range(20):
        s.work(cpu_ms=0.0)             # the loop worked; the clock is mute
        s.wait(150)
        if i % 5 == 0:                 # a credit of 10 ms inside the wait
            s.work(10.0, passes=0)
        s.wait(150)
    s.work(cpu_ms=0.0)
    events, r = classify(s.ticks)
    assert r.main_waits == 20 and not r.stalls
    assert r.summary()["wait_ms_p50"] == pytest.approx(310.0)
    assert r.summary()["wait_ms_max"] == pytest.approx(320.0)
    assert r.summary()["cpu_ms_mean"] == pytest.approx(30.0 / 19)   # the first is none's


def test_without_a_count_of_arrivals_the_pauses_alone_are_told():
    series = Series().steps(12).work().wait(1200).late(300).steps(3).work()
    events, r = classify([t[:3] for t in series.ticks])
    assert [e["name"] for e in events] == ["rtpu.proc.pause"]
    s = r.summary()
    assert s["pauses"] == 1 and s["stalls"] == 0 and s["ticks"] > 100
    assert "waits" not in s and "place" not in s


def test_the_first_waits_at_a_new_line_are_not_joined_to_the_last():
    """Nothing counts the arrivals at a line the loop was never seen at:
    a look, every ``LOOK_NS``, finds that it has gone on, ends the old
    place's wait there and has the new line counted."""
    at, armed = ["warm.py:5 f"], []

    def where(arm):
        armed.append(at[0]) if arm and at[0] not in armed else None
        return at[0]

    r = Rhythm(where=where)
    series = Series().steps(9).work()
    events = _feed(r, series)
    # the loop leaves for the window's line 300 ms into the tenth wait;
    # its first arrival there is seen by no count
    n = len(series.ticks)
    events += _feed(r, series.wait(300), n)
    at[0], n = "window.py:9 g", len(series.ticks)
    events += _feed(r, series.wait(300).steps(20).work(), n)
    assert armed == ["warm.py:5 f", "window.py:9 g"]
    assert r.places["warm.py:5 f"].count == 10
    assert r.places["window.py:9 g"].count == 21
    assert r.places["warm.py:5 f"].max_ns <= (310 + 100) * MS
    assert 200 * MS <= min(r.places["window.py:9 g"].recent)
    assert r.main_place() == "window.py:9 g" and not r.stalls


def test_looks_are_due_by_the_clock_and_not_by_the_count_of_ticks():
    """The profiler's stop holds the interpreter: the pulse's ticks come
    90 ms late each, under a pause's length. The look that finds the loop
    gone from its line still comes within one such tick of 100 ms."""
    at = ["loop.py:7 train"]
    r = Rhythm(where=lambda arm: at[0])
    series = Series().steps(10).work().wait(300)
    events = _feed(r, series)
    at[0], n = "loop.py:9 train", len(series.ticks)     # stop_trace()
    for _ in range(9):
        series.late(90)
    events += _feed(r, series.steps(3).work(), n)
    assert r.places["loop.py:7 train"].max_ns <= (310 + 200) * MS
    assert not r.stalls and not r.pauses
    assert "loop.py:9 train" in r.places


def test_a_working_thread_is_made_no_place_of():
    """A thread that runs (its clock advances every tick) is at no line
    worth counting: a look at it arms nothing, and the time is no wait."""
    asked = []
    series = Series()
    for _ in range(60):
        series.work(cpu_ms=10.0, passes=0)
    series.steps(3).work()
    events, r = classify(series.ticks,
                         where=lambda arm: asked.append(arm) or "x.py:1 f")
    assert all(asked) and len(asked) == 3 * 3     # the three waits' looks
    assert r.places["x.py:1 f"].count == 3
    assert r.places["x.py:1 f"].max_ns == 310 * MS


def test_work_between_two_waits_that_do_not_touch_is_no_steps_work():
    """The profiler's stop between two steps: the loop leaves its line
    and works for 0.6 s (a look ends the wait; a working thread gets no
    place), then steps on. The 600 ms are in no pair of waits."""
    at = ["loop.py:7 train"]
    r = Rhythm(where=lambda arm: at[0])
    series = Series().steps(10).work().wait(300)
    _feed(r, series)
    at[0], n = "profiler.py:223 stop_trace", len(series.ticks)
    for _ in range(60):
        series.work(cpu_ms=10.0, passes=0)
    _feed(r, series, n)
    at[0], n = "loop.py:7 train", len(series.ticks)
    _feed(r, series.steps(10).work(), n)
    assert list(r.places) == ["loop.py:7 train"]
    p = r.places["loop.py:7 train"]
    assert p.count == 21 and p.cpu_n == 19
    # but for the tick of it before the look that found the loop gone
    assert r.summary()["cpu_ms_mean"] == pytest.approx((18 * 2.0 + 10.0) / 19)
    assert r.summary()["loop_cpu_ms"] == pytest.approx(22 * 2.0 + 600.0)
    assert p.max_ns <= (310 + 100) * MS and not r.stalls


def test_late_ticks_are_pauses_told_apart_by_the_process_cpu():
    series = Series().steps(10).work().wait(100).late(2000, proc_ms=3.0) \
        .wait(100).steps(3).work().late(400, proc_ms=395.0, cpu_ms=390.0) \
        .steps(2).work().late(99).work()
    events, r = classify(series.ticks)
    asleep, held = _named(events, "rtpu.proc.pause")
    assert asleep["late_ms"] == pytest.approx(2000.0)
    assert asleep["proc_cpu_ms"] == pytest.approx(3.0)      # did not run
    assert held["late_ms"] == pytest.approx(400.0)
    assert held["proc_cpu_ms"] == pytest.approx(395.0)      # ran, held
    assert (asleep["end"] - asleep["start"]) / MS == pytest.approx(2010.0)
    # the first fell into a wait, which is a stall with its paused part
    (stall,) = _named(events, "rtpu.train.stall")
    assert stall["paused_ms"] == pytest.approx(2000.0)
    assert stall["waited_ms"] == pytest.approx(2220.0)
    s = r.summary()
    assert s["pauses"] == 2 and s["paused_ms"] == pytest.approx(2400.0)
    assert s["late_ms_max"] == pytest.approx(2000.0)


def test_at_most_sixty_four_stalls_and_pauses_are_kept_and_all_count():
    series = Series().steps(10)
    for _ in range(KEEP_EVENTS + 6):
        series.steps(2).work().wait(1500).work().late(150)
    events, r = classify(series.ticks)
    assert len(_named(events, "rtpu.train.stall")) == KEEP_EVENTS
    assert len(_named(events, "rtpu.proc.pause")) == KEEP_EVENTS
    assert r.stalls == r.pauses == KEEP_EVENTS + 6
    assert r.summary()["stalled_ms"] == pytest.approx(
        (KEEP_EVENTS + 6) * 1200.0)


def test_a_wait_of_one_tick_is_none_and_places_are_bounded():
    events, r = classify(Series().steps(50, wait_ms=10).ticks)
    assert not events and r.main_waits == 0 and "place" not in r.summary()
    names = iter(f"f.py:{i} g" for i in range(1000))
    _, r = classify(
        Series().steps(pulse.MAX_PLACES + 9, wait_ms=50).work().ticks,
        where=lambda arm: next(names))
    assert len(r.places) == pulse.MAX_PLACES + 1
    assert r.places[pulse.OTHER_PLACE].count == 9


def test_place_of_names_the_callers_line_first():
    seen = {}

    def sleeper():
        seen["frame"] = sys._getframe()
        return sys._getframe().f_lineno

    line = sleeper()
    first, *rest = pulse.place_of(seen["frame"]).split(" < ")
    assert first == f"tests/test_train_pulse.py:{line} sleeper"
    assert rest[0].startswith("tests/test_train_pulse.py:") and \
        rest[0].endswith("test_place_of_names_the_callers_line_first")
    assert len(rest) == 2
    assert pulse.place_of(None) == "?"


def _run_session(loop, **patches):
    from ray_tpu.train.session import TrainContext, _TrainSession
    from ray_tpu.util import tracing

    here = tracing.since()
    s = _TrainSession(loop, {}, TrainContext(trial_name="pulse-test"))
    for k, v in patches.items():
        setattr(s._pulse, k, v)
    s.start()
    res = s.next_result(timeout=120)
    assert res.done and res.error is None, res.error
    mine = [e for e in here.events()
            if e["args"].get("id") == "pulse-test"]
    return s, mine


def _sleepy_loop():
    for i in range(30):
        sum(j * j for j in range(20000))          # a few ms of arithmetic
        time.sleep(1.0 if i == 20 else 0.05)


@pytest.mark.parametrize("clock", ["the threads own CPU clock",
                                   "a CPU clock that never advances"])
def test_a_session_keeps_a_planted_sleep_as_a_stall_with_its_place(clock):
    from ray_tpu import metrics

    patches = {} if clock.startswith("the") else {"_cpu_ns": lambda: 0}
    s, mine = _run_session(_sleepy_loop, **patches)
    sleeping_line = _sleepy_loop.__code__.co_firstlineno + 3
    stalls = _named(mine, "rtpu.train.stall")
    assert stalls, [e["name"] for e in mine]
    longest = max(stalls, key=lambda e: e["dur"])
    assert longest["args"]["place"].startswith(
        f"tests/test_train_pulse.py:{sleeping_line} _sleepy_loop")
    assert longest["args"]["waited_ms"] >= 900
    assert longest["args"]["usual_ms"] < 500
    assert longest["dur"] / 1e3 == pytest.approx(
        longest["args"]["waited_ms"], abs=1.0)
    assert {"paused_ms", "proc_cpu_ms", "majflt", "nvcsw",
            "nivcsw"} <= set(longest["args"])
    (loop,) = _named(mine, "rtpu.train.loop")
    a = loop["args"]
    assert "error" not in a
    assert a["waits"] >= 25 and a["stalls"] >= 1 and a["ticks"] >= 100
    assert a["place"] == longest["args"]["place"]
    assert 30 <= a["wait_ms_p50"] <= 500 and a["wait_ms_max"] >= 900
    assert loop["ts"] <= longest["ts"] and \
        longest["ts"] + longest["dur"] <= loop["ts"] + loop["dur"] + 1e4
    # the pulse ended with the function, before the done sentinel
    assert not s._pulse.alive()
    assert "rtpu-train-pulse" not in {t.name for t in threading.enumerate()}
    assert sys.monitoring.get_tool(pulse.TOOL_ID) is None
    text = metrics.REGISTRY.render()
    served = {ln.split()[0]: float(ln.split()[1])
              for ln in text.splitlines()
              if ln.startswith(("rtpu_train_loop", "rtpu_train_proc"))}
    assert served["rtpu_train_loop_stalls"] == a["stalls"]
    assert served["rtpu_train_loop_waits"] == a["waits"]   # the main place's
    assert served["rtpu_train_loop_stalled_seconds"] == pytest.approx(
        a["stalled_ms"] / 1e3)
    assert served["rtpu_train_proc_paused_seconds"] == pytest.approx(
        a["paused_ms"] / 1e3)


def test_a_fault_of_the_pulse_is_the_spans_error_and_not_the_runs():
    """A stall's reading of the device raises: the pulse ends there, says
    so once, frees its monitoring slot, and the train function's result
    is what it would have been."""
    def brim():
        raise RuntimeError("no such device")

    from ray_tpu.train.session import TrainContext, _TrainSession
    from ray_tpu.util import tracing

    here = tracing.since()
    s = _TrainSession(_sleepy_loop, {}, TrainContext(trial_name="pulse-fault"))
    s._pulse.rhythm._brim = brim
    s.start()
    res = s.next_result(timeout=120)
    assert res.done and res.error is None, res.error
    (loop,) = [e for e in here.events()
               if e["name"] == "rtpu.train.loop"
               and e["args"]["id"] == "pulse-fault"]
    assert "no such device" in loop["args"]["error"]
    assert loop["dur"] >= 2e6               # the loop ran to its end
    assert not s._pulse.alive()
    assert sys.monitoring.get_tool(pulse.TOOL_ID) is None


def test_a_taken_monitoring_slot_leaves_the_pauses_alone():
    sys.monitoring.use_tool_id(pulse.TOOL_ID, "another tool")
    try:
        s, mine = _run_session(_sleepy_loop)
    finally:
        sys.monitoring.free_tool_id(pulse.TOOL_ID)
    (loop,) = _named(mine, "rtpu.train.loop")
    a = loop["args"]
    assert a["ticks"] >= 100 and "waits" not in a and "error" not in a
    assert a["stalls"] == 0 and not _named(mine, "rtpu.train.stall")
    assert not s._pulse.alive()


def test_a_loop_that_raises_still_closes_its_span_and_ends_its_pulse():
    from ray_tpu.train.session import TrainContext, _TrainSession
    from ray_tpu.util import tracing

    def loop():
        time.sleep(0.1)
        raise ValueError("boom")

    here = tracing.since()
    s = _TrainSession(loop, {}, TrainContext(trial_name="pulse-raises"))
    s.start()
    res = s.next_result(timeout=60)
    assert res.done and isinstance(res.error, ValueError)
    assert not s._pulse.alive()
    (loop_span,) = [e for e in here.events()
                    if e["name"] == "rtpu.train.loop"
                    and e["args"]["id"] == "pulse-raises"]
    assert loop_span["dur"] >= 0.09e6 and loop_span["args"]["ticks"] >= 2


def test_waits_are_ring_events_kept_only_while_events_are_on():
    from ray_tpu.core.config import config

    def loop():
        for _ in range(12):
            sum(j * j for j in range(20000))
            time.sleep(0.05)

    _, mine = _run_session(loop)
    assert not _named(mine, "rtpu.train.wait")
    saved, config.task_events_enabled = config.task_events_enabled, True
    try:
        _, mine = _run_session(loop)
    finally:
        config.task_events_enabled = saved
    waits = _named(mine, "rtpu.train.wait")
    assert len(waits) >= 8
    assert all(20e3 <= e["dur"] <= 2e6 for e in waits)


def test_fit_leaves_the_loop_span_in_trace_spans_json(tmp_path):
    code = f"""
import sys, time
import ray_tpu
from ray_tpu import train
from ray_tpu.train import JaxConfig, JaxTrainer, RunConfig, ScalingConfig

def loop():
    for i in range(12):
        sum(j * j for j in range(20000))
        time.sleep(0.05)
    train.report({{"loss": 1.0}})

ray_tpu.init(num_workers=2, object_store_memory=64 << 20)
r = JaxTrainer(loop, train_loop_config={{}},
               scaling_config=ScalingConfig(num_workers=2),
               jax_config=JaxConfig(platform="cpu"),
               run_config=RunConfig(name="pulse", storage_path={str(tmp_path)!r})).fit()
assert r.error is None, r.error
ray_tpu.shutdown()
"""
    p = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    with open(tmp_path / "pulse" / "trace_spans.json") as f:
        ev = json.load(f)
    loops = [e for e in ev if e["name"] == "rtpu.train.loop"]
    assert len(loops) == 2 and len({e["pid"] for e in loops}) == 2
    (start,) = [e for e in ev if e["name"] == "rtpu.train.start"]
    for e in loops:
        a = e["args"]
        assert a["id"] == start["args"]["id"]
        assert a["waits"] >= 6 and "loop" in a["place"]
        assert 20 <= a["wait_ms_p50"] <= 2000
        assert e["dur"] >= 0.5e6
        (report,) = [r for r in ev if r["name"] == "rtpu.train.report"
                     and r["pid"] == e["pid"]]
        assert report["args"]["parent"] == "rtpu.train.loop"
        assert e["ts"] <= report["ts"] <= e["ts"] + e["dur"]
