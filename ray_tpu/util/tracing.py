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


def totals() -> Dict[str, Dict[str, int]]:
    """Per-name accumulators since the process started."""
    return {k: {"count": v[0], "total_ns": v[1]}
            for k, v in list(_totals.items())}


def chrome_events() -> List[Dict[str, Any]]:
    """This process's kept events as chrome://tracing complete events,
    ``ts`` in wall-clock microseconds (as ``ray_tpu.timeline()`` has it)."""
    pid = os.getpid()
    out = []
    for name, t0, dur, self_ns, tid, parent, sid, attrs in \
            sorted(list(_kept) + list(_ring), key=lambda e: e[1]):
        out.append({
            "name": name, "cat": "span", "ph": "X",
            "ts": (_ANCHOR_WALL + (t0 - _ANCHOR_NS) / 1e9) * 1e6,
            "dur": dur / 1e3, "pid": pid, "tid": tid,
            "args": {"id": sid, "parent": parent, "self_us": self_ns / 1e3,
                     **attrs}})
    return out


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
