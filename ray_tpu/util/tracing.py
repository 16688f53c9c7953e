"""Spans inside the program: the one place a piece of host work gets a
name, a start and an end.

    from ray_tpu.util import tracing
    with tracing.span("rtpu.engine.admit", id=req_id, tokens=n):
        ...

A span costs two clock reads and two adds into a per-name accumulator
(count, total ns), always. Its event (name, start, end, enclosing span,
id, attributes) is kept only

- for a set-up span (``keep=True``: a few dozen per process, ever), or
- under the ``task_events_enabled`` flag, or while a profiler trace
  started through ``start_profile`` is running,

in a bounded in-memory ring. Where ``jax`` is already imported in the
process, a span is also a ``jax.profiler.TraceAnnotation``: inside a
profiler window it is an event on ``/host:CPU`` on the device trace's
clock. This module never imports jax itself (a driver process stays
jax-free).

Events leave a process as the chrome-trace list of ``chrome_events()``
(``ray_tpu.timeline()`` merges it with the task events; ``JaxTrainer``
writes the gang's to ``trace_spans.json``), or inside the profiler's
trace. Durations come from ``time.monotonic_ns``; each process holds one
(wall, monotonic) anchor so that the spans of several processes line up
on the wall clock. Counters are not kept here: they are plain integers on
the object that owns the work, exported by its ``stats()``.

What jax does before a program first runs is recorded too, once a
process that has imported jax calls ``watch_jax()``: every trace,
lowering and backend compile (or fetch from the persistent cache) that
``jax.monitoring`` reports becomes an event ``rtpu.jax.trace``,
``rtpu.jax.lower`` or ``rtpu.jax.compile`` with the function's name, so
a timeline shows which program compiled when, and whether the cache had
it. The listeners run only when jax compiles: a warmed-up step pays
nothing.
"""

from __future__ import annotations

import collections
import os
import sys
import threading
import time
from typing import Any, Dict, List, Optional

from ray_tpu.core.config import config

RING_EVENTS = 65536
_ANCHOR_WALL, _ANCHOR_NS = time.time(), time.monotonic_ns()

_tls = threading.local()
_totals: Dict[str, List[int]] = {}          # name -> [count, total ns]
_ring: "collections.deque[tuple]" = collections.deque(maxlen=RING_EVENTS)
_kept: "collections.deque[tuple]" = collections.deque(maxlen=4096)
_profiling = False

# jax's own events (``jax/_src/dispatch.py``, ``compiler.py``) and what
# they become here; a jax event shorter than JAX_KEEP_S counts in the
# totals alone (a step's trace holds hundreds of inner jits of a few
# microseconds each)
_JAX_SPANS = {
    "/jax/core/compile/jaxpr_trace_duration": "rtpu.jax.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "rtpu.jax.lower",
    "/jax/core/compile/backend_compile_duration": "rtpu.jax.compile",
}
_JAX_CACHE_EVENTS = {
    "/jax/compilation_cache/compile_requests_use_cache": "miss",
    "/jax/compilation_cache/cache_hits": "hit",
}
_JAX_CACHE_DURATIONS = {
    "/jax/compilation_cache/cache_retrieval_time_sec": "retrieval_s",
    "/jax/compilation_cache/compile_time_saved_sec": "saved_s",
}
JAX_KEEP_S = 1e-3
_watch_lock = threading.Lock()
_watching = False
_jax_cache = {"hit": 0, "miss": 0}           # compiles by the cache's answer


def _events_on() -> bool:
    return _profiling or config.task_events_enabled


class span:
    """Context manager; re-entrant use of one object is not supported.
    After the block ``dur_ns`` holds its duration, for the owner of the
    work to add to a counter of its own."""

    __slots__ = ("name", "id", "attrs", "keep", "dur_ns", "_t0", "_ann",
                 "_child_ns", "_parent")

    def __init__(self, name: str, *, id: Optional[str] = None,
                 keep: bool = False, **attrs: Any):
        self.name, self.id, self.attrs, self.keep = name, id, attrs, keep

    def __enter__(self) -> "span":
        stack = getattr(_tls, "stack", None)
        if stack is None:
            stack = _tls.stack = []
        self._parent = stack[-1] if stack else None
        if self.id is None and self._parent is not None:
            self.id = self._parent.id
        stack.append(self)
        self._child_ns = 0
        jax = sys.modules.get("jax")
        self._ann = None
        if jax is not None:
            self._ann = jax.profiler.TraceAnnotation(self.name)
            self._ann.__enter__()
        self._t0 = time.monotonic_ns()
        return self

    def __exit__(self, *exc) -> None:
        dur = self.dur_ns = time.monotonic_ns() - self._t0
        if self._ann is not None:
            self._ann.__exit__(*exc)
        _tls.stack.pop()
        acc = _totals.get(self.name)
        if acc is None:
            acc = _totals.setdefault(self.name, [0, 0])
        acc[0] += 1
        acc[1] += dur
        if self._parent is not None:
            self._parent._child_ns += dur
        if self.keep or _events_on():
            (_kept if self.keep else _ring).append((
                self.name, self._t0, dur, dur - self._child_ns,
                threading.get_ident(),
                self._parent.name if self._parent is not None else None,
                self.id, self.attrs))


def mark(name: str, *, id: Optional[str] = None, **attrs: Any) -> None:
    """A point in time (a span of no length): a request's first token,
    its end."""
    with span(name, id=id, **attrs):
        pass


def record(name: str, start_wall_s: float, end_wall_s: float, *, keep: bool,
           id: Optional[str] = None, **attrs: Any) -> None:
    """An event whose start and end (wall-clock seconds) are known only
    after the fact, as jax reports its compiles. It counts in the
    accumulators, is kept by the rule of a ``span`` and leaves through
    ``chrome_events()`` in the same form; its parent is the span open on
    this thread, whose child time it adds to. Records that lie inside a
    later one on the same thread (an inner jit's trace inside the
    outer's) are that one's children: their time is credited once. No
    ``TraceAnnotation``: the time has passed."""
    t0 = _ANCHOR_NS + int((start_wall_s - _ANCHOR_WALL) * 1e9)
    dur = max(0, int((end_wall_s - start_wall_s) * 1e9))
    stack = getattr(_tls, "stack", None)
    parent = stack[-1] if stack else None
    if id is None and parent is not None:
        id = parent.id
    done = getattr(_tls, "recorded", None)   # (start, dur), none nested
    if done is None:
        done = _tls.recorded = []
    inner = 0
    while done and done[-1][0] >= t0:
        inner += done.pop()[1]
    inner = min(inner, dur)
    done.append((t0, dur))
    if len(done) > 64:                       # nothing will enclose these
        del done[:32]
    acc = _totals.get(name)
    if acc is None:
        acc = _totals.setdefault(name, [0, 0])
    acc[0] += 1
    acc[1] += dur
    if parent is not None:
        parent._child_ns += dur - inner
    if keep or _events_on():
        (_kept if keep else _ring).append((
            name, t0, dur, dur - inner, threading.get_ident(),
            parent.name if parent is not None else None, id, attrs))


def wall_s(monotonic_ns: int) -> float:
    """A ``time.monotonic_ns`` reading on the wall clock of this process's
    anchor, for ``record``: an event timed by the monotonic clock lands
    where a ``span`` that read the same clock would."""
    return _ANCHOR_WALL + (monotonic_ns - _ANCHOR_NS) / 1e9


def _on_jax_event(event: str, **_kw: Any) -> None:
    # the cache's events fire inside the backend compile's span, on its
    # thread, and are folded into it when it closes
    state = _JAX_CACHE_EVENTS.get(event)
    if state is not None:
        _tls.jax_cache = {"cache": state}


def _on_jax_duration(event: str, duration_s: float, **_kw: Any) -> None:
    key = _JAX_CACHE_DURATIONS.get(event)
    if key is not None:
        cache = getattr(_tls, "jax_cache", None)
        if cache is not None:
            cache[key] = duration_s


def _on_jax_span(event: str, start_s: float, end_s: float,
                 fun_name: str = "", **_kw: Any) -> None:
    name = _JAX_SPANS.get(event)
    if name is None:
        return
    attrs = {"fun": fun_name}
    if name == "rtpu.jax.compile":
        cache = getattr(_tls, "jax_cache", None)
        if cache is None:
            attrs["cache"] = "off"
        else:
            _tls.jax_cache = None
            _jax_cache[cache["cache"]] += 1
            attrs.update(cache)
    record(name, start_s, end_s, keep=end_s - start_s >= JAX_KEEP_S, **attrs)


def watch_jax() -> bool:
    """Listen to ``jax.monitoring`` in this process from now on; call it
    once jax is imported (the train backend, the serving engines and the
    worker that creates an actor do). Idempotent; a process that has not
    imported jax registers nothing and gets False.

    ``rtpu.jax.compile`` carries ``cache``: ``"hit"`` (with
    ``retrieval_s``, the fetch and load, and ``saved_s``, the compile
    time the entry remembers minus the fetch), ``"miss"`` (the
    persistent cache was asked and had not the program) or ``"off"`` (it
    was not asked)."""
    global _watching
    if "jax" not in sys.modules:
        return False
    with _watch_lock:
        if not _watching:
            from jax import monitoring

            monitoring.register_event_listener(_on_jax_event)
            monitoring.register_event_duration_secs_listener(_on_jax_duration)
            monitoring.register_event_time_span_listener(_on_jax_span)
            _watching = True
    return True


def totals() -> Dict[str, Any]:
    """Per-name accumulators since the process started and, where
    ``watch_jax`` listens, two plain integers: the programs the
    persistent compile cache had and had not."""
    out: Dict[str, Any] = {k: {"count": v[0], "total_ns": v[1]}
                           for k, v in list(_totals.items())}
    if _watching:
        out["jax_cache_hits"] = _jax_cache["hit"]
        out["jax_cache_misses"] = _jax_cache["miss"]
    return out


def _chrome(entries) -> List[Dict[str, Any]]:
    pid = os.getpid()
    out = []
    for name, t0, dur, self_ns, tid, parent, sid, attrs in \
            sorted(entries, key=lambda e: e[1]):
        out.append({
            "name": name, "cat": "span", "ph": "X",
            "ts": (_ANCHOR_WALL + (t0 - _ANCHOR_NS) / 1e9) * 1e6,
            "dur": dur / 1e3, "pid": pid, "tid": tid,
            "args": {"id": sid, "parent": parent, "self_us": self_ns / 1e3,
                     **attrs}})
    return out


def chrome_events() -> List[Dict[str, Any]]:
    """This process's kept events as chrome://tracing complete events,
    ``ts`` in wall-clock microseconds (as ``ray_tpu.timeline()`` has it),
    sorted by start: a position in this list is not an order of arrival,
    and its length stops growing once a buffer is full. For "what this
    call wrote" use ``since``."""
    return _chrome(list(_kept) + list(_ring))


class since:
    """A mark in this process's two buffers and a read from it::

        here = tracing.since()
        call()
        here.events()          # what ``call`` wrote, on any thread

    in the form and order of ``chrome_events()``, and nothing that stood
    there before. The mark is the newest entry of each buffer, by
    identity (every event is a tuple of its own), so a buffer that was
    full at the mark, or wrapped past it after, reads right: where the
    marked entry has left the buffer, all the buffer holds is newer.
    ``span()`` pays nothing for it."""

    __slots__ = ("_marks",)

    def __init__(self) -> None:
        self._marks = [(buf, buf[-1] if buf else None)
                       for buf in (_kept, _ring)]

    def events(self) -> List[Dict[str, Any]]:
        new: List[tuple] = []
        for buf, newest_then in self._marks:
            entries = list(buf)
            for i in range(len(entries) - 1, -1, -1):
                if entries[i] is newest_then:
                    entries = entries[i + 1:]
                    break
            new += entries
        return _chrome(new)


def start_profile(trace_dir: str) -> None:
    """Start jax's profiler in this process (which must already use jax)
    and keep every span's event until ``stop_profile``."""
    global _profiling
    import jax

    jax.profiler.start_trace(trace_dir)
    _profiling = True


def stop_profile() -> None:
    global _profiling
    import jax

    _profiling = False
    jax.profiler.stop_trace()
