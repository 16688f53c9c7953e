"""lib/loop_spans.py and its four readers on a hand-written
``trace_spans.json``: what ``ray_tpu/train/pulse.py`` leaves there
(PR 48), beside the set-up spans the readers place the window by."""
import json
import os
import shutil

import pytest

from benchmark.lib import loop_spans, program_spans, spec

NAMES = ("loop_wait_ms_p50", "loop_cpu_ms_per_wait", "loop_stalled_share",
         "proc_paused_share")
T0 = 1.79e9                 # rtpu.init starts here, wall-clock seconds
SETUP_S, WINDOW_S = 20.0, 50.0
OWNER, OTHER, DRIVER = 300, 301, 100


def _event(name, start_s, dur_s, pid, **args):
    return {"name": name, "cat": "span", "ph": "X", "ts": (T0 + start_s) * 1e6,
            "dur": dur_s * 1e6, "pid": pid, "tid": 1,
            "args": {"id": "trial_x", "parent": None, "self_us": 0.0, **args}}


def _run(*pulse_events):
    """A run of two workers; the window is 20 s .. 70 s after ``T0``."""
    return [
        _event("rtpu.init", 0.0, 1.0, DRIVER),
        _event("rtpu.train.start", 1.5, 8.0, DRIVER),
        _event("rtpu.backend.devices", 2.0, 6.0, OWNER),
        _event("rtpu.backend.devices", 2.0, 0.5, OTHER),
        _event("rtpu.train.loop", 9.6, 75.0, OWNER, place="train.py:99 loop",
               waits=131, wait_ms_p50=386.5, wait_ms_max=640.0,
               cpu_ms_mean=2.25, loop_cpu_ms=7000.0),
        _event("rtpu.train.loop", 9.6, 75.0, OTHER, place="other.py:1 loop",
               waits=7, wait_ms_p50=9000.0, cpu_ms_mean=1.0),
        *pulse_events]


@pytest.fixture
def cell():
    name = "test-loop-spans-cell"
    d = os.path.join(spec.ROOT, ".bench_tmp", "train-" + name)
    shutil.rmtree(d, ignore_errors=True)
    os.makedirs(os.path.join(d, name))
    obs = {"cell": {"name": name}, "setup_s": SETUP_S,
           "train": {"window_s": WINDOW_S, "steps": 129}}

    def write(events):
        with open(program_spans.spans_file(obs), "w") as f:
            json.dump(sorted(events, key=lambda e: e["ts"]), f)

    try:
        yield obs, write
    finally:
        shutil.rmtree(d, ignore_errors=True)


def _read(obs):
    return [spec.metric_reader(n)(obs) for n in NAMES]


def test_a_clean_window_reads_the_loops_rhythm_and_two_zeros(cell):
    obs, write = cell
    write(_run())
    assert _read(obs) == [386.5, 2.25, 0.0, 0.0]
    assert loop_spans.window(obs) == (T0 + 20.0, T0 + 70.0)
    assert loop_spans.loop_value(obs, "place") == "train.py:99 loop"


def test_events_count_where_they_start_in_the_window(cell):
    obs, write = cell
    stall = dict(place="train.py:99 loop", paused_ms=0.0, proc_cpu_ms=40.0)
    write(_run(
        # a cold compile before the window and the check's after it
        _event("rtpu.train.stall", 11.0, 8.0, OWNER, waited_ms=8000.0,
               usual_ms=40.0, **stall),
        _event("rtpu.train.stall", 71.0, 3.9, OWNER, waited_ms=3900.0,
               usual_ms=50.0, **stall),
        # two in the window, the second reaching past its end
        _event("rtpu.train.stall", 30.0, 2.4, OWNER, waited_ms=2400.0,
               usual_ms=400.0, **stall),
        _event("rtpu.train.stall", 69.5, 1.4, OWNER, waited_ms=1400.0,
               usual_ms=400.0, **stall),
        # a compile that starts in the window read is no stalled step
        _event("rtpu.train.stall", 69.9, 9.0, OWNER, waited_ms=9000.0,
               usual_ms=40.0, **dict(stall, place="compiler.py:362 compile")),
        # another worker's is not the owner's
        _event("rtpu.train.stall", 40.0, 5.0, OTHER, waited_ms=5000.0,
               usual_ms=400.0, **stall),
        _event("rtpu.proc.pause", 5.0, 0.3, OWNER, late_ms=290.0,
               proc_cpu_ms=300.0),
        _event("rtpu.proc.pause", 30.2, 2.01, OWNER, late_ms=2000.0,
               proc_cpu_ms=0.0),
        _event("rtpu.proc.pause", 50.0, 0.51, OWNER, late_ms=500.0,
               proc_cpu_ms=480.0),
        _event("rtpu.proc.pause", 70.0, 0.2, OWNER, late_ms=190.0,
               proc_cpu_ms=0.0),
        _event("rtpu.proc.pause", 45.0, 1.0, DRIVER, late_ms=990.0,
               proc_cpu_ms=0.0)))
    wait, cpu, stalled, paused = _read(obs)
    assert (wait, cpu) == (386.5, 2.25)
    assert stalled == pytest.approx(100 * (2.0 + 1.0) / 50.0)
    assert paused == pytest.approx(100 * (2.0 + 0.5) / 50.0)
    # a longer window holds the check's stall too
    longer = dict(obs, train={"window_s": 60.0})
    assert spec.metric_reader("loop_stalled_share")(longer) == pytest.approx(
        100 * (2.0 + 1.0 + 3.85) / 60.0)
    assert loop_spans.window_share(
        longer, loop_spans.STALL, lambda a: a["waited_ms"]) == pytest.approx(
        100 * (2.4 + 1.4 + 3.9 + 9.0) / 60.0)


def test_a_program_without_the_pulse_gives_nothing(cell):
    obs, write = cell
    assert _read(obs) == [None] * 4                         # no file
    write([e for e in _run() if e["name"] != "rtpu.train.loop"])
    assert _read(obs) == [None] * 4                         # PR 48's parent
    assert spec.metric_reader("setup_chip_open_s")(obs) == pytest.approx(6.0)
    write(_run())
    no_setup = {k: v for k, v in obs.items() if k != "setup_s"}
    assert _read(no_setup) == [386.5, 2.25, None, None]     # no window
    assert _read({k: v for k, v in obs.items() if k != "train"}) == [
        386.5, 2.25, None, None]
    assert _read({}) == [None] * 4


def test_a_rehearsal_that_opens_no_chip_reads_the_longest_loop(cell):
    obs, write = cell
    events = [e for e in _run() if e["name"] != "rtpu.backend.devices"]
    events[-1]["dur"] += 5e6                      # the other worker's loop
    write(events)
    assert _read(obs)[:2] == [9000.0, 1.0]


def test_the_four_entries_end_the_benchmark_for_every_cell():
    b = spec.load_benchmark()
    cells = [w["name"] for w in b["workloads"]]
    mine = b["per_layer"][-4:]
    assert [m["name"] for m in mine] == list(NAMES)
    assert [m["unit"] for m in mine] == ["ms", "ms", "%", "%"]
    for m in mine:
        assert m["workloads"] == cells and m["layer"] == "train gang"
        assert m["moves"] == "train_tok_per_s_per_chip"
        assert m["source"] == "program_span" and m["better"] == "lower"
        assert callable(spec.metric_reader(m["name"]))
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
