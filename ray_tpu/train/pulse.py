"""The train loop's pulse: where a run's seconds go between two steps.

A train loop that stands still for four seconds calls nothing while it
does, so nothing the loop calls can time it. ``_TrainSession`` therefore
runs one small daemon thread beside the loop's thread, from before the
train function is called until it has returned. Every ``TICK_S`` it reads
three clocks (``time.monotonic_ns``, the loop thread's own CPU clock,
``time.process_time_ns``) and one count: how often the loop's thread has
come to a line it was seen waiting at. From those alone, without the loop
calling anything:

- a **wait** is a maximal run of ticks over which the loop's thread came
  to no such line and, looked at (``sys._current_frames``: on the wait's
  second tick and every ``LOOK_NS`` after), stood at one *place*. Per
  place the pulse keeps a count, the recent lengths and the loop thread's
  CPU time from one wait's end to the next's. The place the loop usually
  waits at (count times median length) is the *main place*: in a train
  loop the line of ``loss.block_until_ready()``;
- a **stall** (``rtpu.train.stall``, kept) is a wait at a place that has
  ``MIN_WAITS`` earlier waits and that lasted ``STALL_FACTOR`` times the
  place's usual (median) length and ``STALL_EXTRA_NS`` more than it;
- a **pause** (``rtpu.proc.pause``, kept) is a tick that came ``LATE_NS``
  or more late, in a wait or not: a process CPU near zero over the gap
  says the process or its machine did not run, one near the gap says
  something held the interpreter or the machine's cores;
- each wait of the main place is a ring event ``rtpu.train.wait``, and the
  session's one ``rtpu.train.loop`` span carries ``Rhythm.summary()``.

``Rhythm`` is the classification alone: ticks in, events out, no clock and
no thread, so a test feeds it a synthetic series. ``LoopPulse`` is the
thread that feeds it the clocks.

The count delimits the waits and no clock does: the sandboxed kernel of
the chips' machines advances a thread's CPU clock in steps of 10 ms, on
the kernel's ticks that happen to fall inside the thread's work, so a
step's two milliseconds of host work show in one step of five (PERF.md
section 6, PR 48). A place the loop is seen at is therefore *armed*: the
interpreter's own monitoring (``sys.monitoring``, PEP 669: a ``LINE``
event of that one code object, every other line of it switched off after
its first event) counts the loop thread's arrivals at that line, one
callback a pass. The CPU clock only says which thread to make no place of
(one that is working) and how much the loop's thread worked from wait to
wait. An interpreter without the monitoring, or whose tool slot is taken,
keeps the pauses alone, and its span has no ``waits``. Nothing here raises
into the session: a fault ends the pulse and is the span's ``error``.
Neither this module nor ``tracing`` imports jax: the device's memory at a
stall's end is asked of a jax that the process has imported and whose
backend is up.
"""

from __future__ import annotations

import collections
import logging
import os
import resource
import statistics
import sys
import threading
import time
from typing import Any, Callable, Dict, List, Optional

from ray_tpu.util import tracing

TICK_S = 0.010                 # the period between two readings
TICK_NS = int(TICK_S * 1e9)
STILL_NS = 200_000             # less CPU than this over a tick: not working
PLACE_TICKS = 2                # a wait this long is looked at
LOOK_NS = 100_000_000          # and again this long after each look
LATE_NS = 100_000_000          # a tick this late is a pause
MIN_WAITS = 8                  # a place's earlier waits before one can stall
STALL_FACTOR = 1.5
STALL_EXTRA_NS = 250_000_000
KEEP_EVENTS = 64               # stalls, and pauses, kept a session
RECENT = 64                    # lengths a place remembers for its median
MAX_PLACES = 64                # places kept, and lines armed
TOOL_ID = 4                    # sys.monitoring's slots 0-2 and 5 have names
OTHER_PLACE = "(other places)"
# frames that are the session's own way into a wait, not the loop's place
_OWN_FILES = (os.path.join("ray_tpu", "train", "session.py"),
              os.path.join("ray_tpu", "train", "pulse.py"), "threading.py")

logger = logging.getLogger(__name__)


class _Place:
    __slots__ = ("count", "max_ns", "cpu_ns", "cpu_n", "recent")

    def __init__(self) -> None:
        self.count = self.max_ns = self.cpu_ns = self.cpu_n = 0
        self.recent: "collections.deque[int]" = collections.deque(
            maxlen=RECENT)


class _Wait:
    __slots__ = ("start", "look", "place", "proc", "paused_ns", "seen")

    def __init__(self, start: int, proc: int) -> None:
        self.start, self.proc = start, proc
        self.paused_ns = 0
        self.look = start + PLACE_TICKS * TICK_NS    # the next look is due
        self.place: Optional[str] = None
        self.seen: Dict[str, float] = {}


def _ms(ns: float) -> float:
    return ns / 1e6


class Rhythm:
    """Ticks in (``tick``), waits, stalls and pauses out, as dicts with
    ``name``, ``start`` and ``end`` (monotonic ns) and the event's
    attributes. ``where(arm)`` names the place the loop stands at and,
    asked to, has its arrivals there counted from now on; ``readings()``
    gives cumulative counters (faults, context switches, pressure) whose
    change a stall or a pause carries, asked once a wait and once an
    event; ``brim()`` gives what a stall carries as it is read at the
    stall's end (the device's memory)."""

    def __init__(self, where: Optional[Callable[[bool], str]] = None,
                 readings: Optional[Callable[[], Dict[str, float]]] = None,
                 brim: Optional[Callable[[], Dict[str, float]]] = None):
        self._where = where or (lambda arm: "?")
        self._readings = readings or dict
        self._brim = brim or dict
        self.places: Dict[str, _Place] = {}
        self.ticks = self.stalls = self.pauses = 0
        self.main_waits = 0              # the span's ``waits``, as they come
        self.stalled_ns = self.paused_ns = self.late_ns_max = 0
        self._last: Optional[tuple] = None
        self._first_cpu = 0
        self._mark: tuple = (None, 0, 0)     # the last wait: place, end, CPU
        self._wait: Optional[_Wait] = None
        self._seen: Dict[str, float] = {}

    def tick(self, t: int, cpu: int, proc: int,
             passes: Optional[int] = None) -> List[Dict[str, Any]]:
        """``passes``: the loop's arrivals so far at the lines it was
        seen waiting at; None where nothing counts them, and then the
        pauses alone are told."""
        last, self._last = self._last, (t, cpu, proc, passes)
        self.ticks += 1
        if last is None:
            self._first_cpu = cpu
            return []
        out: List[Dict[str, Any]] = []
        pt, pcpu, pproc, ppasses = last
        w = self._wait
        late = t - pt - TICK_NS
        self.late_ns_max = max(self.late_ns_max, late)
        if late >= LATE_NS:
            self.pauses += 1
            self.paused_ns += late
            if w is not None:
                w.paused_ns += late
            if self.pauses <= KEEP_EVENTS:
                out.append({"name": "rtpu.proc.pause", "start": pt, "end": t,
                            "late_ms": _ms(late),
                            "proc_cpu_ms": _ms(proc - pproc),
                            **self._changes(self._seen)})
        if passes is None:
            return out
        if passes != ppasses:        # the loop came to a line: it moved
            self._wait = None
            if w is not None:
                out.extend(self._close(w, t, cpu, proc))
            return out
        if w is None:
            w = self._wait = _Wait(pt, pproc)
        still = cpu - pcpu <= STILL_NS
        if t >= w.look and (still or w.place is not None):
            # a working thread is at no place worth a name, and is asked
            # only whether it has left the one the wait has
            w.look = t + LOOK_NS
            place = self._where(still)
            if w.place is not None and place != w.place:
                # it went on to a line that nothing counted: the wait
                # ended since the last look, and another began
                out.extend(self._close(w, pt, pcpu, pproc))
                w = self._wait = _Wait(pt, pproc)
                w.look = t + LOOK_NS
            if w.place is None and still:
                w.place = place
                w.seen = self._seen = self._readings()
        return out

    def _changes(self, since: Dict[str, float]) -> Dict[str, float]:
        now = self._seen = self._readings()
        return {k: v - since[k] for k, v in now.items() if k in since}

    def _close(self, w: _Wait, end: int, cpu: int,
               proc: int) -> List[Dict[str, Any]]:
        place = w.place
        if place is None:            # too short to be looked at, or work
            return []
        if place not in self.places and len(self.places) >= MAX_PLACES:
            place = OTHER_PLACE
        p = self.places.get(place)
        if p is None:
            p = self.places[place] = _Place()
        length = end - w.start
        usual = int(statistics.median(p.recent)) if p.recent else 0
        stalled = (p.count >= MIN_WAITS and length >= STALL_FACTOR * usual
                   and length >= usual + STALL_EXTRA_NS)
        if self._mark[:2] == (place, w.start):
            # one step's work: from a wait's end here to the end of the
            # next, which began where that one ended
            p.cpu_ns += cpu - self._mark[2]
            p.cpu_n += 1
        self._mark = (place, end, cpu)
        p.count += 1
        p.max_ns = max(p.max_ns, length)
        p.recent.append(length)
        out: List[Dict[str, Any]] = []
        main = self.main_place()
        self.main_waits = self.places[main].count
        if place == main:
            out.append({"name": "rtpu.train.wait", "start": w.start,
                        "end": end, "place": place})
        if stalled:
            self.stalls += 1
            self.stalled_ns += length - usual
            if self.stalls <= KEEP_EVENTS:
                out.append({"name": "rtpu.train.stall", "start": w.start,
                            "end": end, "waited_ms": _ms(length),
                            "usual_ms": _ms(usual), "place": place,
                            "paused_ms": _ms(w.paused_ns),
                            "proc_cpu_ms": _ms(proc - w.proc),
                            **self._changes(w.seen), **self._brim()})
        return out

    def main_place(self) -> Optional[str]:
        """Where the loop usually waits: the place whose count times its
        usual length is largest (the total would name a cold start's
        compile, one wait of half a minute)."""
        def usually(k: str) -> tuple:
            p = self.places[k]       # a rhythm is a place come back to
            return (p.count >= MIN_WAITS, p.count
                    * statistics.median(p.recent))

        return max(self.places, default=None, key=usually)

    def summary(self) -> Dict[str, Any]:
        """The attributes of ``rtpu.train.loop``."""
        out: Dict[str, Any] = {
            "ticks": self.ticks, "late_ms_max": _ms(self.late_ns_max),
            "loop_cpu_ms": _ms((self._last[1] if self._last else 0)
                               - self._first_cpu),
            "stalls": self.stalls, "stalled_ms": _ms(self.stalled_ns),
            "pauses": self.pauses, "paused_ms": _ms(self.paused_ns)}
        main = self.main_place()
        if main is not None:
            p = self.places[main]
            out.update(place=main, waits=p.count,
                       wait_ms_p50=_ms(statistics.median(p.recent)),
                       wait_ms_max=_ms(p.max_ns))
            if p.cpu_n:
                out["cpu_ms_mean"] = _ms(p.cpu_ns / p.cpu_n)
        return out


def classify(ticks, **sources) -> "tuple[List[Dict[str, Any]], Rhythm]":
    """Every event of a series of ``(monotonic_ns, loop_cpu_ns,
    proc_cpu_ns, passes)`` tuples, and the ``Rhythm`` that read them."""
    rhythm = Rhythm(**sources)
    return [e for tick in ticks for e in rhythm.tick(*tick)], rhythm


def place_of(frame) -> str:
    """A thread's place: its innermost three frames as ``file:line
    function``, the innermost frame outside the session's and
    ``threading``'s own code named first."""
    inner: List[str] = []
    head = None
    while frame is not None and (head is None or len(inner) < 3):
        code = frame.f_code
        at = (f"{os.sep.join(code.co_filename.split(os.sep)[-2:])}:"
              f"{frame.f_lineno} {code.co_name}")
        if len(inner) < 3:
            inner.append(at)
        if head is None and not code.co_filename.endswith(_OWN_FILES):
            head = at
        frame = frame.f_back
    return " < ".join([head] + [at for at in inner if at != head]
                      if head else inner) or "?"


def _pressure() -> Dict[str, float]:
    """``some total=`` (microseconds stalled) of the kernel's pressure
    files, where there are any."""
    out = {}
    for what in ("cpu", "memory", "io"):
        try:
            with open("/proc/pressure/" + what) as f:
                out[f"pressure_{what}_us"] = float(
                    f.readline().rsplit("total=", 1)[1])
        except (OSError, IndexError, ValueError):
            pass
    return out


def _readings() -> Dict[str, float]:
    ru = resource.getrusage(resource.RUSAGE_SELF)
    return {"majflt": ru.ru_majflt, "nvcsw": ru.ru_nvcsw,
            "nivcsw": ru.ru_nivcsw, **_pressure()}


def _brim() -> Dict[str, float]:
    """The first local device's memory, of a jax that is imported and
    whose backend is up (asking earlier would open the chip from the
    pulse's thread)."""
    bridge = sys.modules.get("jax._src.xla_bridge")
    if bridge is None or not bridge.backends_are_initialized():
        return {}
    stats = sys.modules["jax"].local_devices()[0].memory_stats() or {}
    return {k: stats[k] for k in (
        "bytes_in_use", "peak_bytes_in_use", "bytes_limit",
        "largest_free_block_bytes") if k in stats}


class LoopPulse:
    """The thread. ``start()`` is called on the loop's own thread before
    the train function, ``stop()`` on it once the function has returned:
    the loop thread's CPU clock is never read after the thread has gone.
    Neither raises: the train function's result never depends on the
    pulse, whose first fault ends it and is ``stop()``'s ``error``."""

    def __init__(self, trial: str = ""):
        self.trial = trial
        self.rhythm = Rhythm(self._where, _readings, _brim)
        self.error: Optional[str] = None
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._ident = self._clock = 0
        self._passes = 0
        self._armed: Dict[Any, set] = {}     # code -> its counted lines
        self._monitoring = None

    def start(self) -> None:
        try:
            clock_of = getattr(time, "pthread_getcpuclockid", None)
            if clock_of is None:    # no per-thread CPU clock: no pulse
                return
            self._ident = threading.get_ident()
            self._clock = clock_of(self._ident)
            mon = getattr(sys, "monitoring", None)
            try:
                if mon is not None:
                    mon.use_tool_id(TOOL_ID, "rtpu-train-pulse")
                    mon.register_callback(TOOL_ID, mon.events.LINE,
                                          self._line)
                    self._monitoring = mon
            except ValueError:      # another session's pulse, or a tool
                pass
            self._thread = threading.Thread(target=self._run, daemon=True,
                                            name="rtpu-train-pulse")
            self._thread.start()
        except Exception as e:
            self._fault(e)

    def stop(self) -> Dict[str, Any]:
        """Ends the thread and gives the span's attributes."""
        attrs: Dict[str, Any] = {}
        try:
            if self._thread is not None:
                self._stop.set()
                self._thread.join()
                self._thread = None
                self._passes += 1   # the loop has come to its end: the
                self._beat()        # wait it was in, if any, is closed
                attrs = self.rhythm.summary()
        except Exception as e:
            self._fault(e)
        self._disarm()
        if self.error is not None:
            attrs["error"] = self.error
        return attrs

    def alive(self) -> bool:
        return self._thread is not None and self._thread.is_alive()

    def _run(self) -> None:
        while self.error is None and not self._stop.wait(TICK_S):
            self._beat()

    def _cpu_ns(self) -> int:
        return time.clock_gettime_ns(self._clock)

    def _beat(self) -> None:
        if self.error is not None:
            return
        try:
            for e in self.rhythm.tick(
                    time.monotonic_ns(), self._cpu_ns(),
                    time.process_time_ns(),
                    self._passes if self._monitoring is not None else None):
                name = e.pop("name")
                tracing.record(name, tracing.wall_s(e.pop("start")),
                               tracing.wall_s(e.pop("end")),
                               keep=name != "rtpu.train.wait",
                               id=self.trial, **e)
        except Exception as e:
            self._fault(e)

    def _fault(self, e: Exception) -> None:
        if self.error is None:
            self.error = repr(e)
            logger.warning("the train loop's pulse ended on %r; the run "
                           "goes on without it", e)
        self._disarm()

    def _disarm(self) -> None:
        mon, self._monitoring = self._monitoring, None
        if mon is None:
            return
        try:
            for code in self._armed:
                mon.set_local_events(TOOL_ID, code, 0)
            mon.register_callback(TOOL_ID, mon.events.LINE, None)
            mon.free_tool_id(TOOL_ID)
        except Exception as e:      # the slot is another's by now
            logger.warning("the pulse's monitoring slot: %r", e)

    def _where(self, arm: bool) -> str:
        frame = sys._current_frames().get(self._ident)
        if arm and frame is not None:
            self._arm(frame.f_code, frame.f_lineno)
        return place_of(frame)

    def _arm(self, code, line: int) -> None:
        """Count the loop thread's arrivals at ``line`` of ``code``."""
        if line in self._armed.get(code, ()) or sum(
                map(len, self._armed.values())) >= MAX_PLACES:
            return
        self._armed.setdefault(code, set()).add(line)
        mon = self._monitoring
        # the line may have been switched off: switched on again, and the
        # code instrumented anew so that a frame inside it sees it
        mon.restart_events()
        mon.set_local_events(TOOL_ID, code, 0)
        mon.set_local_events(TOOL_ID, code, mon.events.LINE)

    def _line(self, code, line: int):
        # on whichever thread runs ``code``; the loop's passes count
        if line not in self._armed.get(code, ()):
            return sys.monitoring.DISABLE
        if threading.get_ident() == self._ident:
            self._passes += 1
