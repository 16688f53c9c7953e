"""lib/compile_spans.py and its three readers on a recorded file:
``fixtures/trace_spans.train-1chip.json`` is what a warm chip run of
``train-1chip`` left (PR 34; the run printed the ``setup_s`` below)."""
import copy
import json
import os
import shutil

import pytest

from benchmark.lib import compile_spans, program_spans, spec

FIXTURE = os.path.join(spec.BENCH_DIR, "fixtures",
                       "trace_spans.train-1chip.json")
SETUP_S = 18.185128450393677
NAMES = ("setup_trace_lower_s", "setup_compile_s", "setup_programs_compiled")


@pytest.fixture
def cell():
    """A cell of the test's own whose run directory holds what the test
    writes; yields ``(obs, write)``."""
    name = "test-compile-spans-cell"
    d = os.path.join(spec.ROOT, ".bench_tmp", "train-" + name)
    shutil.rmtree(d, ignore_errors=True)
    os.makedirs(os.path.join(d, name))
    obs = {"cell": {"name": name}, "setup_s": SETUP_S}

    def write(events):
        with open(program_spans.spans_file(obs), "w") as f:
            json.dump(events, f)

    try:
        yield obs, write
    finally:
        shutil.rmtree(d, ignore_errors=True)


def _read(obs):
    return [spec.metric_reader(n)(obs) for n in NAMES]


def _recorded():
    with open(FIXTURE) as f:
        return json.load(f)


def test_union_counts_nested_and_overlapping_intervals_once():
    u = compile_spans.union_seconds
    assert u([]) == 0.0
    assert u([(0, 10), (2, 3), (4, 5)]) == 10.0          # nested
    assert u([(4, 6), (0, 5), (8, 9)]) == 7.0            # overlap, gap
    assert u([(0, 1), (1, 2)]) == 2.0


def test_readers_on_the_recorded_run(cell):
    obs, write = cell
    events = _recorded()
    write(events)
    trace_lower, compile_s, programs = _read(obs)
    assert trace_lower == pytest.approx(4.1436, abs=1e-3)
    assert compile_s == pytest.approx(1.1289, abs=1e-3)
    assert programs == 0                         # a warm run
    found = compile_spans.setup_events(obs)
    mine, t_from, t_to = found
    assert t_to - t_from == pytest.approx(7.7975, abs=1e-3)
    # nested traces are counted once: the events' durations add up to
    # more than the time they cover
    inside = [e for e in mine if e["name"] != compile_spans.COMPILE
              and t_from <= e["ts"] / 1e6 < t_to]
    assert sum(e["dur"] for e in inside) / 1e6 > trace_lower + 0.3
    # the check's compile after the window is in the file and left out
    late = [e for e in mine if e["name"] == compile_spans.COMPILE
            and e["ts"] / 1e6 > t_to]
    assert any("token_nll" in e["args"]["fun"] for e in late)
    assert compile_spans.union_seconds(
        [(e["ts"], e["ts"] + e["dur"]) for e in mine
         if e["name"] == compile_spans.COMPILE]) / 1e6 > compile_s + 0.4
    # every event read is the chips' owner's
    (owner,) = {e["pid"] for e in mine}
    assert owner == max((e for e in events
                         if e["name"] == "rtpu.backend.devices"),
                        key=lambda e: e["dur"])["pid"]


def test_a_cold_cache_shows_as_programs_compiled(cell):
    obs, write = cell
    events = _recorded()

    def flip(fun):
        (e,) = [e for e in events if e["name"] == compile_spans.COMPILE
                and e["args"]["fun"] == fun]
        assert e["args"]["cache"] == "hit"
        e["args"] = {"fun": fun, "cache": "miss"}

    flip("jit(step)")
    flip("jit(token_nll)")          # compiled after the window: not set-up
    # another process's compile inside the stretch is not the owner's
    other = copy.deepcopy(next(e for e in events if e["name"] ==
                               compile_spans.COMPILE
                               and e["args"]["fun"] == "jit(step)"))
    other["pid"] += 1
    other["dur"] = 5e6
    write(events + [other])
    trace_lower, compile_s, programs = _read(obs)
    assert programs == 1
    assert compile_s == pytest.approx(1.1289, abs=1e-3)


def test_a_program_without_the_spans_gives_nothing(cell):
    obs, write = cell
    assert _read(obs) == [None, None, None]               # no file
    parent = [e for e in _recorded()
              if not e["name"].startswith("rtpu.jax.")]
    write(parent)
    assert _read(obs) == [None, None, None]               # PR 34's parent
    assert spec.metric_reader("setup_chip_open_s")(obs) > 1.0
    write(_recorded())
    assert _read({"cell": obs["cell"]}) == [None, None, None]  # no setup_s
    assert _read({}) == [None, None, None]


def test_the_three_entries_are_in_the_benchmark_for_every_cell():
    b = spec.load_benchmark()
    cells = [w["name"] for w in b["workloads"]]
    mine = [m for m in b["per_layer"] if m["name"] in NAMES]
    assert [m["name"] for m in mine] == list(NAMES)
    assert mine == b["per_layer"][-3:]
    for m in mine:
        assert m["workloads"] == cells and m["moves"] == "setup_s"
        assert m["source"] == "program_span" and m["better"] == "lower"
        assert callable(spec.metric_reader(m["name"]))
